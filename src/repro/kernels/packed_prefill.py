"""Packed multi-prompt prefill attention — Pallas TPU kernel.

The scheduler packs several prefilling sequences into ONE fixed-shape
``(1, C)`` chunk (MaxText MLPerf offline-serving style): each chunk lane
carries a sequence-indicator segment id and its absolute position inside
that sequence.  Attention is block-diagonal per segment — a lane attends
only keys of its OWN segment's page run, causally up to its own absolute
position (which includes the segment's page-resident prefix: cache hits and
earlier chunks) — and padding lanes (segment id -1) produce exactly zero
output.

Grid (S, n_pages): for segment ``si``, page ``pi``, the block-table entry
``page_rows[si, pi]`` selects the physical page (scalar-prefetched, no
gather materialization), loaded whole — all Hkv heads, block
``(1, page, Hkv, D)``, the only page block whose last two dimensions
Mosaic accepts without a head-major cache (DESIGN.md §13).  For each kv
head in a static loop, ALL C chunk lanes score against it under the
segment-indicator mask; fp32 online-softmax accumulators for every (kv
head, lane, group-head) persist in VMEM scratch across the sequential
(segment, page) walk.  Pages past a segment's context (``seg_ctx``) and
segments with no lanes are skipped whole.

The pure-jnp oracle is :func:`repro.kernels.ref.packed_prefill_attention_ref`;
:mod:`repro.kernels.ops` dispatches between the two.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _packed_kernel(page_rows, seg_ctx, q_ref, k_ref, v_ref, seg_ref, pos_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, page_size: int,
                   n_segs: int, n_pages: int, scale: float):
    si = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(jnp.logical_and(si == 0, pi == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages holding no token of segment si (and unused segments: ctx 0) are
    # skipped whole — the packed chunk pays for occupied pages only
    live = pi * page_size < seg_ctx[si]

    @pl.when(live)
    def _compute():
        seg = seg_ref[...]                                 # (C, 1) int32
        pos = pos_ref[...]                                 # (C, 1) int32
        for h in range(k_ref.shape[2]):                    # static kv heads
            q = q_ref[:, h].astype(jnp.float32) * scale    # (C, G, D)
            c, g, d = q.shape
            k = k_ref[0, :, h, :].astype(jnp.float32)      # (page, D)
            s = jax.lax.dot_general(
                q.reshape(c * g, d), k,
                (((1,), (1,)), ((), ()))).reshape(c, g, -1)  # (C, G, page)
            # sequence-indicator mask: lane l sees key position kp of page
            # pi iff the lane belongs to THIS segment and kp is causally
            # visible at the lane's absolute position (prefix pages
            # included)
            kp = pi * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 2)
            allowed = jnp.logical_and(seg[..., None] == si,
                                      kp <= pos[..., None])
            s = jnp.where(allowed, s, NEG_INF)
            m_prev = m_scr[h]                              # (C, G)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            # lanes of OTHER segments see an all-masked score row here; pin
            # their running max to 0 before exponentiating so exp(s - m) is
            # a clean 0, not exp(-inf - -inf) = 1
            m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.exp(m_prev - m_safe)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1)
            v = v_ref[0, :, h, :].astype(jnp.float32)      # (page, D)
            acc_scr[h] = acc_scr[h] * corr[..., None] + jax.lax.dot_general(
                p.reshape(c * g, -1), v,
                (((1,), (0,)), ((), ()))).reshape(c, g, d)
            m_scr[h] = m_new

    @pl.when(jnp.logical_and(si == n_segs - 1, pi == n_pages - 1))
    def _finalize():
        # untouched lanes (padding: segment -1 matches no si) still hold
        # (acc=0, l=0): the epsilon divide pins their output to exactly 0
        for h in range(k_ref.shape[2]):
            denom = jnp.maximum(l_scr[h], 1e-30)
            o_ref[:, h] = (acc_scr[h] / denom[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def packed_prefill_attention(q, k_pages, v_pages, page_rows, seg_ids,
                             positions, seg_ctx, *, interpret: bool = False):
    """q (C,H,D) packed chunk queries; k/v_pages (P,page,Hkv,D);
    page_rows (S,n_pages) int32 per-segment block-table rows; seg_ids (C,)
    int32 (-1 = padding lane); positions (C,) int32 absolute position of
    each lane in its own sequence; seg_ctx (S,) int32 per-segment context
    end (max position + 1; 0 for unused segments) → (C,H,D).

    K/V for every lane must already sit in the pages (the engine scatters
    the chunk's keys/values before attending, exactly like the decode
    step), so same-chunk causality comes straight from the page contents.
    """
    c, h, d = q.shape
    n_phys, page_size, hkv, _ = k_pages.shape
    group = h // hkv
    n_segs, n_pages = page_rows.shape
    scale = 1.0 / math.sqrt(d)

    qt = q.reshape(c, hkv, group, d)
    seg2 = seg_ids.reshape(c, 1).astype(jnp.int32)
    pos2 = positions.reshape(c, 1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_segs, n_pages),
        in_specs=[
            pl.BlockSpec((c, hkv, group, d), lambda si, pi, rows, ctx:
                         (0, 0, 0, 0)),
            # the physical page for (segment si, logical page pi) comes from
            # the SMR-managed per-segment block table (scalar-prefetched)
            pl.BlockSpec((1, page_size, hkv, d), lambda si, pi, rows, ctx:
                         (rows[si, pi], 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, d), lambda si, pi, rows, ctx:
                         (rows[si, pi], 0, 0, 0)),
            pl.BlockSpec((c, 1), lambda si, pi, rows, ctx: (0, 0)),
            pl.BlockSpec((c, 1), lambda si, pi, rows, ctx: (0, 0)),
        ],
        out_specs=pl.BlockSpec((c, hkv, group, d), lambda si, pi, rows, ctx:
                               (0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, c, group), jnp.float32),
            pltpu.VMEM((hkv, c, group), jnp.float32),
            pltpu.VMEM((hkv, c, group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_packed_kernel, page_size=page_size,
                               n_segs=n_segs, n_pages=n_pages, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(page_rows, seg_ctx, qt, k_pages, v_pages, seg2, pos2)
    return out.reshape(c, h, d)
