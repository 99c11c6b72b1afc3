"""The harness: everything a run does between reading its cell and printing
its line, as functions that a test can call on the CPU at a small size.

A cell names a configuration and a traffic mix in ``BENCHMARK.json``; the
harness finds ``configs/<config>.json``, ``traffic/<mix>.json`` and, for
each per-layer metric, ``metrics/<metric>.py`` by those names, so a later
change adds a cell, a mix or a metric by adding files.
"""

from __future__ import annotations

import contextlib
import gc
import json
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import common, counts, model, traffic

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent / "BENCHMARK.json"
WARM_SEED_OFFSET = 10 ** 6
SAMPLE_PERIOD_S = 0.25            # counter samples during the window


# ----------------------------------------------------------------- the cell

@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    dims: model.Dims
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    traffic_dir: Path = HERE / "traffic"

    def schedule(self, seed: int, seconds: float,
                 mix: Optional[dict] = None) -> traffic.Schedule:
        """The requests of a run of ``seconds`` from ``seed``, made by the
        mix's generator (``mix`` in place of the cell's own, if given)."""
        mix = self.mix if mix is None else mix
        make = traffic.generator(mix, self.traffic_dir)
        return make(mix, seed, seconds, self.dims.vocab)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None,
              root: Path = HERE) -> Cell:
    bench = bench if bench is not None else json.loads(BENCH.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = model.load_config(w["config"], root / "configs")
    return Cell(name=name, config_name=w["config"], config=cfg,
                dims=model.Dims.from_config(cfg),
                mix=traffic.load_mix(w["traffic"], root / "traffic"),
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)],
                traffic_dir=root / "traffic")


def load_reader(metric: str, root: Path = HERE / "metrics") -> Callable:
    """``metrics/<metric>.py``'s ``read(run)``."""
    return common.load_module(root / f"{metric}.py",
                              f"reader for metric {metric!r}").read


# ------------------------------------------------------------ the serving

def program_config(dims: model.Dims, name: str):
    """The program's model configuration for these sizes."""
    from repro.configs.base import ModelConfig
    return ModelConfig(name=name, family="dense", n_layers=dims.layers,
                       d_model=dims.d_model, n_heads=dims.heads,
                       n_kv_heads=dims.kv_heads, head_dim=dims.head_dim,
                       d_ff=dims.d_ff, vocab_size=dims.vocab, qk_norm=True,
                       rope_theta=dims.rope_theta, dtype=dims.dtype)


def open_session(cell: Cell, seed: int, backend: Optional[str] = None):
    """``(session, params)``: the program serving this cell's model with the
    benchmark's weights for ``seed``, started and warmed."""
    from repro.models import build_model
    from repro.serving import ServingConfig, serve

    params = model.make_params(cell.dims, seed)
    prog = build_model(program_config(cell.dims, cell.config_name))
    config = ServingConfig(**cell.config["serving"], backend=backend)
    session = serve(prog, params, config)
    session.warm()
    return session, params


# -------------------------------------------------------------- the traffic

@dataclass
class Served:
    """One request as the run saw it; times are offsets from the window's
    start, in seconds."""
    prompt: List[int]
    max_new: int
    due: Optional[float]
    sent: float
    status: str = "waiting"
    hit_tokens: int = 0
    out: List[int] = field(default_factory=list)
    times: List[float] = field(default_factory=list)


def _record(spec, handle, t0: float, sent: float) -> Served:
    return Served(prompt=spec.prompt, max_new=spec.max_new,
                  due=spec.due_s, sent=sent, status=handle.status,
                  hit_tokens=handle.req._hit_tokens,
                  out=list(handle.out_tokens),
                  times=[t - t0 for t in handle.req.out_times])


class _Sampler(threading.Thread):
    """Samples ``session.stats()['totals']`` every ``SAMPLE_PERIOD_S``, and
    how long each call took."""

    def __init__(self, session, annotate):
        super().__init__(name="perfbench-sampler", daemon=True)
        self.session = session
        self.annotate = annotate
        self.samples: List[dict] = []
        self.took: List[float] = []
        self.stop = threading.Event()

    def run(self):
        while not self.stop.wait(SAMPLE_PERIOD_S):
            t = time.perf_counter()
            with self.annotate("perfbench.stats"):
                self.samples.append(self.session.stats()["totals"])
            self.took.append(time.perf_counter() - t)


class _Collections:
    """The duration of every garbage collection of the oldest generation
    while it is installed (``gc.callbacks``): such a collection holds every
    Python thread, the engine's and the clients' alike."""

    def __init__(self):
        self.took: List[float] = []
        self._t = None

    def __call__(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.took.append(time.perf_counter() - self._t)
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def drive(session, schedule: traffic.Schedule, seconds: float,
          grace_s: float, annotate=None) -> dict:
    """Offer ``schedule`` for ``seconds``, then wait up to ``grace_s`` for
    what was sent.  Returns the raw record of the window, with the host
    time each ``submit`` and ``stats`` call and each full garbage
    collection took in it."""
    annotate = annotate or (lambda name: contextlib.nullcontext())
    before = session.stats()["totals"]
    sampler = _Sampler(session, annotate)
    handles = []                     # (spec, handle, sent offset)
    submit_s = []
    lock = threading.Lock()

    def submit(spec):
        t = time.perf_counter()
        with annotate("perfbench.submit"):
            h = session.submit(spec.prompt, max_new_tokens=spec.max_new)
        submit_s.append(time.perf_counter() - t)
        handles.append((spec, h, t - t0))
        return h

    sampler.start()
    with annotate("perfbench.window"), _Collections() as collections:
        t0 = time.perf_counter()
        end = t0 + seconds
        if schedule.kind == "open":
            for spec in schedule.specs:
                wait = t0 + spec.due_s - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                submit(spec)
        else:
            queue = iter(schedule.specs)

            def client():
                while True:
                    with lock:
                        if time.perf_counter() >= end:
                            return
                        spec = next(queue, None)
                        if spec is None:
                            return
                        h = submit(spec)
                    h.wait(end - time.perf_counter() + grace_s)

            clients = [threading.Thread(target=client, daemon=True)
                       for _ in range(schedule.clients)]
            for c in clients:
                c.start()
        rest = end - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
    t_end = time.perf_counter()
    sampler.stop.set()
    sampler.join()
    after = session.stats()["totals"]
    deadline = t_end + grace_s
    with lock:
        sent = list(handles)
    for _spec, h, _ in sent:
        h.wait(max(0.0, deadline - time.perf_counter()))
    if schedule.kind == "closed":
        for c in clients:
            c.join(max(0.0, deadline - time.perf_counter()))
    return {"t0": t0, "window_s": t_end - t0, "handles": [h for _, h, _ in sent],
            "requests": [_record(s, h, t0, sent_at) for s, h, sent_at in sent],
            "before": before, "after": after, "samples": sampler.samples,
            "host_s": {"submit": submit_s, "stats": sampler.took,
                       "full_gc": collections.took}}


def prime_prefixes(session, schedule: traffic.Schedule, timeout_s: float):
    """Serve each shared prefix once so the window starts with the system
    prompts in the prefix cache, as a deployment that has been up has them."""
    hs = [session.submit(p + [1], max_new_tokens=1)
          for p in schedule.prefixes]
    for h in hs:
        h.result(timeout=timeout_s)


def warm_traffic(session, cell: Cell, seed: int):
    """Replay the first seconds of the cell's own traffic, from a seed the
    window never uses, so every program that traffic needs is compiled and
    every lazy path has run before the window opens."""
    mix = cell.mix
    warm_s = float(mix["warm_seconds"])
    sched = cell.schedule(seed + WARM_SEED_OFFSET, warm_s)
    if sched.kind == "closed":
        sched.specs = sched.specs[:sched.clients]
    rec = drive(session, sched, warm_s, grace_s=float(mix["warm_drain_s"]))
    for h in rec["handles"]:
        h.cancel()
    for h in rec["handles"]:
        h.wait(float(mix["grace_s"]))


# ---------------------------------------------------------------- the record

@dataclass
class Run:
    """What the readers read: the window's requests and counters, the model
    sizes, the chip's peaks and, in a traced run, the trace's reduction."""
    window_s: float
    requests: List[Served]
    before: dict
    after: dict
    samples: List[dict]
    dims: model.Dims
    peaks: Optional[dict] = None
    trace: Optional[object] = None

    def delta(self, key: str) -> float:
        return self.after[key] - self.before[key]

    def in_window(self, t: float) -> bool:
        return 0.0 <= t < self.window_s

    def emitted(self) -> int:
        return sum(1 for r in self.requests for t in r.times
                   if self.in_window(t))

    def prefilled(self):
        """``(start, end)`` of the prompt positions computed for each
        request whose prefill ended in the window: from its cached prefix
        to its prompt's end."""
        return [(r.hit_tokens, len(r.prompt)) for r in self.requests
                if r.times and self.in_window(r.times[0])]

    def decoded(self):
        """Context length of each decode step in the window: every output
        token after a request's first comes from one query over the prompt
        and the tokens before it."""
        return [len(r.prompt) + j for r in self.requests
                for j, t in enumerate(r.times) if j and self.in_window(t)]

    def mfu(self) -> Optional[float]:
        """The whole step's share of the chip's bf16 peak (%): operations
        the window's served tokens required (prompt tokens not served from
        the prefix cache, and output tokens) at the published widths, over
        the window, over the peak."""
        if self.peaks is None or self.window_s <= 0:
            return None
        flops = self.served_flops()
        if flops <= 0:
            return None
        return 100.0 * flops / self.window_s / self.peaks["bf16_flops_per_s"]

    def served_flops(self) -> int:
        """Operations the window's served tokens required."""
        f = sum(counts.forward_flops(self.dims, a, b, 1)
                for a, b in self.prefilled())
        return f + sum(counts.forward_flops(self.dims, c - 1, c, 1)
                       for c in self.decoded())


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def ttft_ms(run: Run) -> List[float]:
    """Time to first token of every request due in an open-loop window, from
    when it was due; a request that never emitted reads infinite."""
    return [(r.times[0] - r.due) * 1e3 if r.times else float("inf")
            for r in run.requests if r.due is not None]


def end_to_end(run: Run) -> Dict[str, float]:
    """The user-facing numbers of the window (``setup_s`` aside)."""
    ttft = ttft_ms(run)
    itl = [(b - a) * 1e3 for r in run.requests
           for a, b in zip(r.times, r.times[1:]) if run.in_window(b)]
    out = {"itl_p95_ms": percentile(itl, 95) if itl else float("nan"),
           "tokens_per_s": run.emitted() / run.window_s}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50)
    return out


def outcome(run: Run):
    """``(attempted, failed)``: every request sent; a request fails unless it
    finished with all its tokens within the grace period."""
    failed = sum(1 for r in run.requests
                 if r.status != "done" or len(r.out) != r.max_new)
    return len(run.requests), failed
