"""The plain reference: a dense Qwen3 decoder (pre-norm RMSNorm, q/k
RMSNorm per head, rotate-half RoPE, grouped-query causal attention, SwiGLU,
untied output head) in float32 ``jax.numpy`` at ``highest`` matmul
precision, one sequence and one layer at a time.  It imports nothing of the
program and makes the weights again from the seed (``model.py``).

:func:`logits_at` runs the reference over whole sequences and returns the
logits at chosen positions.  With ``fp8=True`` it runs the control: every
weight matmul takes its inputs rounded to float8 (e4m3, scaled per row of
the activations and per output column of the weights) and accumulates in
float32, the step below bfloat16 that a later change could be tempted by.
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp

from . import model as M

BUCKET = 512            # sequences are padded to a multiple of this
QBLOCK = 512            # queries per attention block


def _fp8(x, axis):
    """``x`` rounded to float8 e4m3, scaled along ``axis`` to its range."""
    f8 = jnp.float8_e4m3fn
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(f8).max), 1.0)
    return (x / scale).astype(f8).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, w, *, dims: M.Dims, fp8: bool):
    """One decoder block over one padded sequence ``x`` (S, d)."""
    s = x.shape[0]
    hd, h, kvh = dims.head_dim, dims.heads, dims.kv_heads
    pos = jnp.arange(s)
    a = _rms(x, w["ln1"], dims.norm_eps)
    q = _mm(a, w["attn"]["wq"], fp8).reshape(s, h, hd)
    k = _mm(a, w["attn"]["wk"], fp8).reshape(s, kvh, hd)
    v = _mm(a, w["attn"]["wv"], fp8).reshape(s, kvh, hd)
    q = _rope(_rms(q, w["attn"]["q_norm"], dims.norm_eps), pos,
              dims.rope_theta)
    k = _rope(_rms(k, w["attn"]["k_norm"], dims.norm_eps), pos,
              dims.rope_theta)
    g = h // kvh
    q = q.reshape(s // QBLOCK, QBLOCK, kvh, g, hd) * hd ** -0.5

    def block(args):
        qb, q0 = args
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k,
                        precision=jax.lax.Precision.HIGHEST)
        allowed = pos[None, :] <= (q0 + jnp.arange(QBLOCK))[:, None]
        sc = jnp.where(allowed, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, (q, jnp.arange(0, s, QBLOCK)))
    x = x + _mm(o.reshape(s, h * hd), w["attn"]["wo"], fp8)
    a = _rms(x, w["ln2"], dims.norm_eps)
    ff = jax.nn.silu(_mm(a, w["ffn"]["wi_gate"], fp8)) * \
        _mm(a, w["ffn"]["wi_up"], fp8)
    return x + _mm(ff, w["ffn"]["wo"], fp8)


@partial(jax.jit, static_argnames=("dims", "fp8"))
def _head(x, norm, head, *, dims: M.Dims, fp8: bool):
    return _mm(_rms(x, norm, dims.norm_eps), head, fp8)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def logits_at(dims: M.Dims, seed: int, seqs: Sequence[Sequence[int]],
              positions: Sequence[Sequence[int]], *, fp8: bool = False):
    """For each sequence, the float32 logits (len(positions[i]), vocab) at
    ``positions[i]``: the distribution of the token after each position."""
    embed = M.top_leaf(dims, seed, "embed")
    xs = []
    for toks in seqs:
        pad = -(-len(toks) // BUCKET) * BUCKET
        ids = jnp.asarray(list(toks) + [0] * (pad - len(toks)), jnp.int32)
        e = jnp.take(embed, ids, axis=0).astype(jnp.float32)
        xs.append(_fp8(e, -1) if fp8 else e)
    del embed
    for i in range(dims.layers):
        w = _f32(M.block_leaves(dims, seed, i))
        xs = [_layer(x, w, dims=dims, fp8=fp8) for x in xs]
        del w
    norm = M.top_leaf(dims, seed, "final_norm").astype(jnp.float32)
    head = M.top_leaf(dims, seed, "lm_head").astype(jnp.float32)
    out: List = []
    for x, pos in zip(xs, positions):
        rows = x[jnp.asarray(list(pos), jnp.int32)]
        out.append(_head(rows, norm, head, dims=dims, fp8=fp8))
    return out


def served_gaps(ref_logits, tokens) -> List[float]:
    """For each position, how far the reference's logit of the served token
    lies below the reference's best logit there."""
    gaps = []
    for lg, toks in zip(ref_logits, tokens):
        t = jnp.asarray(list(toks), jnp.int32)
        picked = jnp.take_along_axis(lg, t[:, None], axis=1)[:, 0]
        gaps += jax.device_get(jnp.max(lg, axis=1) - picked).tolist()
    return gaps
