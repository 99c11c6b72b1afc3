"""How ``correct`` is decided: served greedy tokens against the reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one with
the most served tokens, is run through the reference (``reference.py``)
over its prompt and served tokens.  At each served position the number read
is how far the reference's logit of the served token lies below the
reference's best logit there.  A greedy token that a sound bfloat16 program
picked differs from the reference's only on a near tie, so the widest gap
is small; the cell's limit (``limits/<cell>.json``) sits between the widest
gap that sound runs read and the one that the control (the reference in
float8) reads.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import List

import numpy as np

from . import reference

HERE = Path(__file__).resolve().parent
SAMPLE_SEED_OFFSET = 2 * 10 ** 6
CHECK_TOKENS = 256                # served tokens compared in every run


def load_limits(cell: str, root: Path = HERE / "limits") -> dict:
    path = root / f"{cell}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no limits for workload {cell!r} at {path}")
    return json.loads(path.read_text())


def choose_sample(requests, seed: int, min_tokens: int) -> List:
    """Finished requests, drawn from ``seed``, until they hold at least
    ``min_tokens`` served tokens; the one with the most served tokens is
    always among them."""
    done = [r for r in requests if r.status == "done" and r.out]
    if not done:
        return []
    rng = np.random.default_rng(int(seed) + SAMPLE_SEED_OFFSET)
    longest = max(range(len(done)), key=lambda i: len(done[i].out))
    order = [longest] + [i for i in rng.permutation(len(done))
                         if i != longest]
    picked, n = [], 0
    for i in order:
        picked.append(done[i])
        n += len(done[i].out)
        if n >= min_tokens:
            break
    return picked


def _positions(r):
    p = len(r.prompt)
    return list(range(p - 1, p - 1 + len(r.out)))


def gaps(dims, seed: int, sample, control: bool = False) -> List[float]:
    """The reference's gap at every served token of ``sample``.  With
    ``control``, the program is replaced by the control: at the same
    positions of the same sequences, the gap of the token that the float8
    reference puts first."""
    import jax.numpy as jnp
    seqs = [r.prompt + r.out for r in sample]
    pos = [_positions(r) for r in sample]
    ref = reference.logits_at(dims, seed, seqs, pos)
    picks = [r.out for r in sample]
    if control:
        low = reference.logits_at(dims, seed, seqs, pos, fp8=True)
        picks = [jnp.argmax(lg, axis=1).tolist() for lg in low]
    return reference.served_gaps(ref, picks)
