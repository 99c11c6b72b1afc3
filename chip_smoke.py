#!/usr/bin/env python3
"""Chip smoke check: serve TinyLlama-1.1B at full width on one TPU through
the Pallas kernels, and check what comes out.

    python3 chip_smoke.py [--seed 0]

One process owns the chip.  In order it:

1. refuses to run (exit 1, no result line) unless JAX's first device is a
   TPU;
2. checks the split-K paged-decode and packed-prefill kernels against
   ``repro.kernels.ref`` at the served shapes, within ``KERNEL_ULPS``;
3. opens a ``repro.serving`` session on TinyLlama-1.1B at its published
   widths and dtype (22 layers, d_model 2048, 32/4 heads of 64, d_ff 5632,
   vocab 32000, bf16; random weights from ``--seed``) with the ``packed``
   scheduler, warms it, and serves the launcher's traffic
   (``repro.launch.serve``: 8 prompts of 64-256 tokens, 32 new tokens
   each): one alone, the rest once it decodes, so every packed segment
   bucket that ``warm()`` compiles is used;
4. asserts every request finished with its full token count, that the
   greedy tokens agree with a teacher-forced dense forward pass of the same
   model (``MIN_AGREEMENT``), that the compiled decode step holds the
   Pallas kernels (``tpu_custom_call``), and that every page is back in
   the pool after ``close()``.

Its last line is the JSON result; any failed check raises before it.
JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<checkout>/.jax_cache``, so a second run compiles less.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "tinyllama-1.1b"
# kernel vs reference, in bf16 ulps of max(|reference|, 1).  Both round
# their output to bf16, which alone can leave them one ulp apart at any
# magnitude.  The kernel's P.V dot also rounds the probabilities to bf16,
# as Mosaic does for a float32 dot of default precision: up to ~0.5 ulp
# before the output rounding at the served shapes (PERF.md §6)
KERNEL_ULPS = 2
# share of served greedy tokens that the dense forward pass predicts too;
# bf16 near-ties flip a few, a broken kernel agrees on almost none
MIN_AGREEMENT = 0.75


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def errors(jnp, got, want):
    """``(max |got - want|, max |got - want| / ulp)``, the ulp a bf16 ulp
    of ``max(|want|, 1)`` (8 significant bits: 2^-7 on [1, 2))."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    diff = jnp.abs(got - want)
    ulp = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(want), 1.0))) - 7)
    return float(jnp.max(diff)), float(jnp.max(diff / ulp))


def check_kernels(jax, jnp, config, cfg, seed):
    """Max (abs error, bf16-ulp error) of decode and of packed prefill
    against the references at the served shapes (batch, pages, page size,
    heads, head dim, chunk)."""
    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    dt = getattr(jnp, cfg.dtype)
    b, h, hkv, d = config.max_batch, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    page, n_pages = config.page_size, config.max_pages
    pool = (config.num_pages, page, hkv, d)
    kp = jax.random.normal(ks[0], pool, jnp.float32).astype(dt)
    vp = jax.random.normal(ks[1], pool, jnp.float32).astype(dt)
    q = jax.random.normal(ks[2], (b, h, d), jnp.float32).astype(dt)
    bt = jax.random.randint(ks[3], (b, n_pages), 0, config.num_pages)
    ctx = jax.random.randint(ks[4], (b,), 1, config.max_seq_len + 1)
    occ = jnp.arange(b) < b - 2            # two padding rows
    got = ops.paged_attention(q, kp, vp, bt, ctx, occupancy=occ)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention_ref(q, kp, vp, bt, ctx, occupancy=occ)
    err_decode = errors(jnp, got, want)

    # one packed chunk as the packed scheduler builds it: prefill slices
    # behind page-resident prefixes, one-lane decode riders, padding
    lanes = config.prefill_chunk_tokens + config.max_batch
    spans = [(0, 96), (128, 64), (32, 48), (256, 40),      # (start, take)
             (299, 1), (76, 1), (511, 1), (159, 1)]
    seg, pos = [], []
    for si, (start, take) in enumerate(spans):
        seg += [si] * take
        pos += list(range(start, start + take))
    n_live = len(seg)
    seg = jnp.asarray(seg + [-1] * (lanes - n_live), jnp.int32)
    pos = jnp.asarray(pos + [0] * (lanes - n_live), jnp.int32)
    seg_ctx = jnp.asarray([s + t for s, t in spans], jnp.int32)
    rows = jax.random.randint(ks[5], (len(spans), n_pages), 0,
                              config.num_pages)
    qc = jax.random.normal(ks[6], (lanes, h, d), jnp.float32).astype(dt)
    got = ops.packed_prefill_attention(qc, kp, vp, rows, seg, pos, seg_ctx)
    with jax.default_matmul_precision("highest"):
        want = ref.packed_prefill_attention_ref(qc, kp, vp, rows, seg, pos,
                                                seg_ctx)
    err_packed = errors(jnp, got, want)
    check(bool(jnp.all(got[n_live:] == 0)), "padding lanes leaked output")
    return err_decode, err_packed


def greedy_agreement(jax, jnp, model, params, prompts, outs):
    """Share of served tokens equal to the argmax of a teacher-forced dense
    forward pass (``model.logits_fn``) over prompt + served tokens."""
    width = max(len(p) + len(o) for p, o in zip(prompts, outs))
    toks = jnp.asarray([p + o + [0] * (width - len(p) - len(o))
                        for p, o in zip(prompts, outs)], jnp.int32)
    pred = jax.jit(lambda p, t: jnp.argmax(
        model.logits_fn(p, {"tokens": t})[0], axis=-1))(params, toks)
    pred = jax.device_get(pred)
    hits = sum(int(pred[i, len(p) - 1 + j] == tok)
               for i, (p, o) in enumerate(zip(prompts, outs))
               for j, tok in enumerate(o))
    return hits / sum(len(o) for o in outs)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the prompts and the kernel "
                         "check's data")
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform} "
              f"{dev.device_kind}); nothing was run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import (DEFAULT_SERVING, NEW_TOKENS, PROMPT_LEN,
                                    REQUESTS, build, random_prompts)
    from repro.models.params import count_params
    from repro.serving import ServingConfig, serve

    cache_dir = enable_compile_cache()
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache_dir}", flush=True)

    config = ServingConfig(**DEFAULT_SERVING)
    check(config.backend == "pallas", f"backend {config.backend}")
    model, params = build(ARCH, args.seed)
    cfg = model.cfg
    n_params = count_params(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"params={n_params} bytes={n_bytes}", flush=True)

    err_decode, err_packed = check_kernels(jax, jnp, config, cfg, args.seed)
    print(f"kernels vs ref (max abs; max bf16 ulps, bound {KERNEL_ULPS}): "
          f"paged_decode={err_decode[0]:.3e}; {err_decode[1]:g} "
          f"packed_prefill={err_packed[0]:.3e}; {err_packed[1]:g}",
          flush=True)
    check(err_decode[1] <= KERNEL_ULPS and err_packed[1] <= KERNEL_ULPS,
          "kernel error above bound")

    prompts = random_prompts(args.seed, REQUESTS, PROMPT_LEN, cfg.vocab_size)
    session = serve(model, params, config)
    try:
        t0 = time.perf_counter()
        session.warm()
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        first = session.submit(prompts[0], max_new_tokens=NEW_TOKENS)
        next(iter(first))                  # it decodes: the rest ride along
        handles = [first] + session.submit_many(prompts[1:],
                                                max_new_tokens=NEW_TOKENS)
        outs = [h.result(timeout=600) for h in handles]
        serve_s = time.perf_counter() - t0
        for hd, out in zip(handles, outs):
            check(hd.status == "done" and len(out) == NEW_TOKENS,
                  f"request {hd.req_id}: {hd.status}, {len(out)} tokens")
        shard = session.engine.shards[0]
        n_kernels = shard.decode_hlo().count("tpu_custom_call")
        check(n_kernels >= cfg.n_layers,
              f"{n_kernels} tpu_custom_call in the decode step")
    finally:
        session.close()
    for shard in session.engine.shards:
        free = shard.pool.free_count()
        check(free == config.num_pages,
              f"{free} of {config.num_pages} pages free after close()")
    totals = session.stats()["totals"]
    tokens = sum(len(o) for o in outs)
    print(f"served: {tokens} tokens for {len(outs)} requests "
          f"(prompts {min(map(len, prompts))}-{max(map(len, prompts))}) "
          f"in {serve_s:.2f}s after a {warm_s:.2f}s warm; decode step "
          f"holds {n_kernels} tpu_custom_call", flush=True)

    agreement = greedy_agreement(jax, jnp, model, params, prompts, outs)
    print(f"greedy tokens agreeing with the dense forward pass: "
          f"{agreement:.4f} (bound {MIN_AGREEMENT})", flush=True)
    check(agreement >= MIN_AGREEMENT, "served tokens disagree")

    print(f"totals: {totals}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"compile seconds: {sum(compile_s):.2f} over {len(compile_s)} "
          f"compiles; wall seconds: {time.perf_counter() - t_start:.2f}; "
          f"peak device bytes: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
