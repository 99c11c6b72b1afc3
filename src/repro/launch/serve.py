"""Serving launcher: one process serves a named configuration through a
``repro.serving`` session and prints the session totals.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b

The model runs at its published widths and dtype with random weights drawn
from ``--seed`` (nothing is downloaded).  ``--reduced`` swaps in the
family's small float32 configuration, for CPU runs and tests.  The process
owns the device and starts no other process that touches JAX.
(``examples/serve_paged.py`` is the multi-client driver.)
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from ..configs import get_config
from ..models import build_model
from ..serving import ServingConfig, serve
from .compile_cache import enable_compile_cache

# one v5e chip's serving shape for TinyLlama-1.1B: 2048 pages of 16 tokens
# (32 768 cached tokens, ~0.74 GB of bf16 K/V over 22 layers) beside ~2.2 GB
# of bf16 weights; 8 sequences of up to 512 tokens
DEFAULT_SERVING = dict(num_pages=2048, page_size=16, max_batch=8,
                       max_seq_len=512, prefill_chunk_tokens=256,
                       scheduler="packed")
# the traffic a launcher run sends, and chip_smoke.py with it: REQUESTS
# prompts of PROMPT_LEN tokens (inclusive range), NEW_TOKENS new tokens each
REQUESTS = 8
PROMPT_LEN = (64, 256)
NEW_TOKENS = 32


def build(arch: str, seed: int, *, reduced: bool = False):
    """``(model, params)`` for ``arch`` with weights drawn from ``seed``:
    the published configuration, or with ``reduced`` its small float32
    smoke configuration."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced().replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    return model, params


def random_prompts(seed: int, n: int, lengths, vocab_size: int):
    """``n`` prompts with lengths drawn uniformly from the inclusive
    ``lengths`` range and token ids from ``[1, vocab_size)``."""
    rng = np.random.RandomState(seed)
    lo, hi = lengths
    return [rng.randint(1, vocab_size, size=rng.randint(lo, hi + 1)).tolist()
            for _ in range(n)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small float32 configuration (CPU runs)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights and the prompts")
    ap.add_argument("--smr", default="IBR")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    args = ap.parse_args(argv)

    enable_compile_cache()
    model, params = build(args.arch, args.seed, reduced=args.reduced)
    config = ServingConfig(smr=args.smr, num_shards=args.shards,
                           **DEFAULT_SERVING)
    prompts = random_prompts(args.seed, args.requests, PROMPT_LEN,
                             model.cfg.vocab_size)
    with serve(model, params, config) as session:
        session.warm()
        handles = session.submit_many(prompts, max_new_tokens=NEW_TOKENS)
        for h in handles:
            h.result(timeout=600)
        totals = session.stats()["totals"]
    print(f"[serve] {model.cfg.name} on {jax.devices()[0].device_kind} "
          f"backend={config.backend} smr={args.smr} shards={args.shards}: "
          f"{totals}")


if __name__ == "__main__":
    main()
