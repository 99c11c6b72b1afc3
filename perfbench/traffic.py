"""The default traffic generator.  A mix is a data file,
``traffic/<mix>.json``; this module turns it and a run's ``--seed`` into
the requests of a run.  A mix that names ``"generator": "<module>"`` is
made instead by ``make_schedule`` in ``traffic/<module>.py``, with the same
signature, so a new arrival process or length mixture is a new file.

Two kinds of mix:

* ``open`` — independent users on a fixed schedule.  A run of ``seconds``
  holds exactly ``round(rate_per_s * seconds)`` arrivals.  The gaps between
  them are exponential and scaled to fill the window: the spacings of a
  Poisson process conditioned on its count.
* ``closed`` — ``clients`` callers that each send their next request when
  the last one is done, drawn in order from a pool of ``pool`` requests.

Everything comes from the run's seed: arrivals, lengths, session structure
and token ids.  Each random size (an arrival gap, a prompt or answer
length, a session's turn count) is drawn by stratified sampling: the ``n``
draws of a run take one value from each of ``n`` equal-probability strata
of the distribution, in an order drawn from the seed.  Every seed then
offers its own schedule, with sizes spread over the whole distribution as
evenly as ``n`` draws allow, so runs differ in when and in which order the
work comes, and hardly in how much of it there is.

Optional parts of a mix:

* ``prefixes`` — shared system prompts: ``count`` prompts of ``tokens``
  tokens, chosen per session with Zipf popularity ``zipf_s``.  A session
  runs ``turns`` (inclusive range) turns; turn k's prompt is the system
  prompt, every earlier turn's user tokens and recorded answer, and its own
  user tokens.  Sessions are laid on ``lanes`` interleaved lanes of the
  arrival slots, so consecutive turns of one session are ``lanes``
  arrivals apart.
* ``prompt`` / ``answer`` — length distributions (``lognormal`` with
  ``median`` and ``sigma``, or ``uniform``), clipped to ``[min, max]``.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np
from scipy.special import ndtri

from . import common

HERE = Path(__file__).resolve().parent


@dataclass
class Spec:
    """One request as the generator makes it."""
    prompt: List[int]
    max_new: int
    due_s: Optional[float] = None     # open loop: offset into the window
    session: int = -1                 # prefix mixes: session index
    turn: int = 0
    prefix: int = -1                  # which shared prefix, or -1


@dataclass
class Schedule:
    kind: str                         # "open" | "closed"
    specs: List[Spec]
    clients: int = 0                  # closed loop only
    prefixes: List[List[int]] = field(default_factory=list)


def load_mix(name: str, root: Path = HERE / "traffic") -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def _strata(rng, n: int) -> np.ndarray:
    """``n`` uniform draws on (0, 1), one in each of ``n`` equal strata, in
    an order drawn from ``rng``."""
    u = (np.arange(n) + rng.uniform(size=n)) / n
    return rng.permutation(np.clip(u, 1e-12, 1.0 - 1e-12))


def _lengths(rng, dist: dict, n: int) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    u = _strata(rng, n)
    if dist["dist"] == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * ndtri(u))
    elif dist["dist"] == "uniform":
        x = lo + u * (hi + 1 - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _arrivals(rng, n: int, seconds: float) -> List[float]:
    """``n`` arrival offsets in ``[0, seconds)``: ``n + 1`` exponential
    gaps, scaled so that they span the window."""
    gaps = np.cumsum(-np.log1p(-_strata(rng, n + 1)))
    return (seconds * gaps[:-1] / gaps[-1]).tolist()


def _ids(rng, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, size=int(n)).tolist()


def _zipf_choice(rng, n_items: int, s: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n_items + 1) ** s
    return rng.choice(n_items, size=n, p=w / w.sum())


def request_count(mix: dict, seconds: float) -> int:
    """Arrivals in a window of ``seconds`` (open) or the pool (closed)."""
    if mix["kind"] == "open":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    return int(mix["pool"])


def generator(mix: dict, root: Path = HERE / "traffic") -> Callable:
    """The function that makes this mix's schedules: ``make_schedule``, or
    that of ``<root>/<generator>.py`` where the mix names a generator."""
    name = mix.get("generator")
    if name is None:
        return make_schedule
    return common.load_module(root / f"{name}.py",
                              f"traffic generator {name!r}").make_schedule


def make_schedule(mix: dict, seed: int, seconds: float,
                  vocab: int) -> Schedule:
    """The requests of one run, all drawn from ``seed``."""
    kind = mix["kind"]
    if kind not in ("open", "closed"):
        raise ValueError(f"unknown mix kind {kind!r}")
    n = request_count(mix, seconds)
    rng = np.random.default_rng(int(seed))
    due = _arrivals(rng, n, seconds) if kind == "open" else [None] * n
    if "prefixes" in mix:
        specs, prefixes = _sessions(mix, rng, due, vocab)
    else:
        prompt = _lengths(rng, mix["prompt"], n)
        answer = _lengths(rng, mix["answer"], n)
        specs = [Spec(_ids(rng, prompt[i], vocab), int(answer[i]), due[i])
                 for i in range(n)]
        prefixes = []
    return Schedule(kind, specs, int(mix.get("clients", 0)), prefixes)


def _sessions(mix, rng, due, vocab):
    """Multi-turn sessions on shared system prompts (see module doc)."""
    pre = mix["prefixes"]
    n = len(due)
    lo, hi = mix["turns"]
    # turn counts, one per session, until n turns exist
    spans = _lengths(rng, {"dist": "uniform", "min": lo, "max": hi}, n)
    turns = spans[:int(np.searchsorted(np.cumsum(spans), n)) + 1].tolist()
    turns[-1] -= sum(turns) - n
    n_sess = len(turns)
    which = _zipf_choice(rng, pre["count"], pre["zipf_s"], n_sess)
    user = _lengths(rng, mix["prompt"], n)
    answer = _lengths(rng, mix["answer"], n)
    recorded = _lengths(rng, mix["answer"], n)
    prefixes = [_ids(rng, pre["tokens"], vocab) for _ in range(pre["count"])]
    # per-session turn shapes, in shape order
    starts = np.concatenate([[0], np.cumsum(turns)])
    # lay sessions on lanes of the arrival slots
    lanes = int(mix["lanes"])
    free = [list(range(j, n, lanes)) for j in range(lanes)]
    specs: List[Optional[Spec]] = [None] * n
    lane = 0
    for s in range(n_sess):
        k0, k1 = int(starts[s]), int(starts[s + 1])
        for step in range(lanes):
            j = (lane + step) % lanes
            if len(free[j]) >= k1 - k0:
                slots, free[j] = free[j][:k1 - k0], free[j][k1 - k0:]
                lane = (j + 1) % lanes
                break
        else:
            # no lane has room for the whole session: the earliest free
            # slots, wherever they lie
            slots = sorted(i for f in free for i in f)[:k1 - k0]
            free = [[i for i in f if i not in slots] for f in free]
        p = int(which[s])
        history = list(prefixes[p])
        for t, (k, slot) in enumerate(zip(range(k0, k1), slots)):
            history = history + _ids(rng, user[k], vocab)
            specs[slot] = Spec(list(history), int(answer[k]), due[slot],
                               session=int(s), turn=t, prefix=p)
            history = history + _ids(rng, recorded[k], vocab)
    return specs, prefixes
