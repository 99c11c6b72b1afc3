"""Paged-attention decode — flash-decoding-style Pallas TPU kernel.

This is the device half of the paper's integration: the block tables this
kernel consumes are produced by the SMR-managed block pool
(repro/runtime/block_pool.py) — a page must not be reused while any
scheduler thread still traverses an index entry that references it, which is
exactly the SCOT/SMR guarantee.

Two device-level properties the serving engine relies on (DESIGN.md §13):

* **Native occupancy**: ``occupancy`` (B,) marks real batch rows.  Padded
  rows never enter the compute path — their accumulators stay zero and the
  finalize divide pins their output to exactly 0, whatever their block-table
  entries alias (a recycled page id is inert).  No host-side clamp, no
  post-hoc ``jnp.where``.

* **Split-K over pages** (flash decoding): the page walk of one sequence is
  divided across ``num_splits`` grid slots, each producing an unnormalized
  partial ``(acc, m, l)`` triple; a small on-device max/sum reduce rescales
  and combines them.  Long-context decode rows therefore parallelize over
  the page dimension (``dimension_semantics`` marks the split dim parallel
  for Mosaic's core mapping) instead of serializing the innermost grid.

Tiling: grid (B, num_splits, pages_per_split).  Page indirection goes
through ``PrefetchScalarGridSpec``: the block-table entry selects which
physical page is DMA'd into VMEM for each grid step (no gather
materialization).  One grid step loads a WHOLE page — all Hkv heads,
block ``(1, page, Hkv, D)`` — because Mosaic only accepts a block whose
last two dimensions are (8, 128)-divisible or equal to the array's, and
(Hkv, D) is the array's own; the kernel then walks the kv heads in a
static loop, scoring each head's G = H/Hkv query heads as one (G, D) tile
against that head's (page, D) keys (DESIGN.md §13).  fp32 online-softmax
accumulators persist in VMEM scratch across the (innermost, sequential)
page dimension of one split.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _default_num_splits(n_pages: int) -> int:
    """Flash-decoding split heuristic: ~4 pages per split, at most 8 splits
    (beyond that the combine overhead outgrows the parallelism on one core
    pair), and never more splits than pages."""
    return max(1, min(8, n_pages // 4, n_pages))


def _paged_kernel(block_tables, context_lens, occupancy, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *,
                  page_size: int, pages_per_split: int, n_pages: int,
                  scale: float):
    b = pl.program_id(0)
    sp = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = context_lens[b]
    page_idx = sp * pages_per_split + pi
    # native occupancy: padded rows never compute, so their partials stay
    # (m=-inf, l=0, acc=0) and the combine emits exactly zero for them.
    # Trailing pages beyond ctx (and ceil-division padding slots beyond the
    # table) are skipped the same way.
    live = jnp.logical_and(occupancy[b] > 0, page_idx * page_size < ctx)
    live = jnp.logical_and(live, page_idx < n_pages)

    @pl.when(live)
    def _compute():
        for h in range(k_ref.shape[2]):                    # static kv heads
            q = q_ref[0, h].astype(jnp.float32) * scale    # (G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)      # (page, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
            pos = page_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(pos < ctx, s, NEG_INF)           # (G, page)
            m_prev = m_scr[h]                              # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            v = v_ref[0, :, h, :].astype(jnp.float32)      # (page, D)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_scr[h] = m_new

    @pl.when(pi == pages_per_split - 1)
    def _finalize():
        # per-split partials: UNNORMALIZED accumulator + its own (m, l);
        # the cross-split combine rescales by exp(m - m_max) and divides
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]
        o_ref[0, 0] = acc_scr[...]


@functools.partial(jax.jit, static_argnames=("num_splits", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    occupancy=None, num_splits=None, interpret: bool = False):
    """q (B,H,D); k/v_pages (P,page,Hkv,D); block_tables (B,n_pages) int32;
    context_lens (B,) int32; occupancy (B,) bool optional (False rows are
    batch padding — output exactly 0, in-kernel) → (B,H,D).

    ``num_splits`` splits the page walk flash-decoding style (None → a
    pages-per-split heuristic); the unnormalized per-split partials are
    combined by an on-device max/sum reduce below."""
    b, h, d = q.shape
    n_phys, page_size, hkv, _ = k_pages.shape
    group = h // hkv
    n_pages = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    if num_splits is None:
        num_splits = _default_num_splits(n_pages)
    assert 1 <= num_splits, "num_splits must be >= 1"
    pages_per_split = -(-n_pages // num_splits)  # ceil: pad slots skipped

    if occupancy is None:
        occ = jnp.ones((b,), jnp.int32)
    else:
        occ = occupancy.astype(jnp.int32)

    # (B, Hkv, G, D) query tile layout
    qt = q.reshape(b, hkv, group, d)

    def _page(bi, sp, pi, bt, cl, oc):
        # the physical page for logical page sp*pps+pi comes from the
        # SMR-managed block table (scalar-prefetched); ceil-division pad
        # slots clamp to the last entry and are masked dead in-kernel
        idx = jnp.minimum(sp * pages_per_split + pi, n_pages - 1)
        return (bt[bi, idx], 0, 0, 0)

    def _row(bi, sp, pi, bt, cl, oc):
        return (bi, 0, 0, 0)

    def _split(bi, sp, pi, bt, cl, oc):
        return (sp, bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_splits, pages_per_split),
        in_specs=[
            pl.BlockSpec((1, hkv, group, d), _row),
            pl.BlockSpec((1, page_size, hkv, d), _page),
            pl.BlockSpec((1, page_size, hkv, d), _page),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hkv, group, d), _split),
            pl.BlockSpec((1, 1, hkv, group, 1), _split),
            pl.BlockSpec((1, 1, hkv, group, 1), _split),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               pages_per_split=pages_per_split,
                               n_pages=n_pages, scale=scale)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_splits, b, hkv, group, d), jnp.float32),
            jax.ShapeDtypeStruct((num_splits, b, hkv, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_splits, b, hkv, group, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, context_lens, occ, qt, k_pages, v_pages)

    # on-device max/sum combine (flash decoding step 2): rescale each
    # split's partial to the global max, sum mass and accumulators, divide.
    # Dead splits (m = -inf from padding/occupancy) contribute weight 0; a
    # fully dead row (all splits dead) divides 0 by the epsilon → exactly 0.
    m_max = jnp.max(m, axis=0)                              # (B,Hkv,G,1)
    w = jnp.where(m > NEG_INF * 0.5,
                  jnp.exp(m - jnp.maximum(m_max, NEG_INF * 0.5)[None]), 0.0)
    l_tot = jnp.sum(l * w, axis=0)                          # (B,Hkv,G,1)
    out = jnp.sum(acc * w, axis=0) / \
        jnp.maximum(l_tot, 1e-30)                           # (B,Hkv,G,D)
    return out.astype(q.dtype).reshape(b, h, d)
