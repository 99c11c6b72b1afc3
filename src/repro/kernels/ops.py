"""Jit'd dispatch wrappers: the public kernel API used by the models and the
serving engine.

``backend``:
  * "xla"              — pure-jnp path (ref.py / blockwise-jnp).
  * "pallas"           — the Pallas kernels compiled by Mosaic; TPU only.
  * "pallas_interpret" — the same kernels in Pallas interpret mode (correct
                         but slow; what the CPU tests run).
  * None               — the process default, which follows the platform:
                         "pallas" on a TPU, "xla" anywhere else.

The model zoo calls these wrappers so a single config flag flips the whole
stack onto the TPU kernels.

Dispatch honesty: ``backend="pallas"`` off a TPU raises — interpret mode
is reached only by naming it, so a run that lost its chip cannot pass for
a kernel run.  When a call EXPLICITLY requests a pallas backend but the
kernel cannot take the shapes (block divisibility), the wrapper raises
instead of silently dropping to the jnp reference — a silently changed
execution path is how "the TPU run was slow" bugs hide.  When the pallas
path is only the *session default* (``set_default_backend``), the fallback
still happens but warns once per (op, reason)."""

from __future__ import annotations

import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash_pallas
from .packed_prefill import packed_prefill_attention as _packed_pallas
from .paged_attention import paged_attention as _paged_pallas
from .ssd_scan import ssd_scan as _ssd_pallas

BACKENDS = ("xla", "pallas", "pallas_interpret")
_DEFAULT_BACKEND: Optional[str] = None      # None → platform_backend()
_FALLBACKS_WARNED: set = set()


def platform_backend() -> str:
    """The backend the platform calls for: the Mosaic kernels on a TPU,
    the jnp path elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def default_backend() -> str:
    return _DEFAULT_BACKEND or platform_backend()


def set_default_backend(name: Optional[str]) -> None:
    """Pin the process default (None → follow the platform again)."""
    global _DEFAULT_BACKEND
    if name is not None:
        resolve_backend(name)
    _DEFAULT_BACKEND = name


def resolve_backend(backend: Optional[str]) -> str:
    """Validate a backend name (None → the process default).  Raises on an
    unknown name, and on ``"pallas"`` when JAX's platform is not a TPU."""
    b = backend or default_backend()
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r}; choose from {BACKENDS}")
    platform = jax.default_backend()
    if b == "pallas" and platform != "tpu":
        raise RuntimeError(
            f"backend='pallas' compiles the kernels with Mosaic, which "
            f"needs a TPU, but JAX's platform is {platform!r}; ask for "
            f"'pallas_interpret' to run them in interpret mode")
    return b


def _resolve(backend: Optional[str]):
    b = resolve_backend(backend)
    return ("pallas" if b.startswith("pallas") else "xla"), \
        b == "pallas_interpret"


def _refuse_fallback(op: str, explicit: bool, reason: str) -> None:
    """Explicit-backend contract: raise when the caller named the pallas
    backend for this call; warn once when only the process default did."""
    if explicit:
        raise ValueError(
            f"{op}: backend='pallas' was explicitly requested but {reason}; "
            f"pass backend='xla' (or fix the shapes) instead of relying on "
            f"a silent reference fallback")
    key = (op, reason)
    if key not in _FALLBACKS_WARNED:
        _FALLBACKS_WARNED.add(key)
        warnings.warn(
            f"{op}: default backend is 'pallas' but {reason}; falling back "
            f"to the jnp reference for these shapes (warned once)",
            RuntimeWarning, stacklevel=3)


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    backend: Optional[str] = None):
    kind, interpret = _resolve(backend)
    if kind == "pallas":
        if q.shape[1] % min(block_q, q.shape[1]) == 0:
            return _flash_pallas(q, k, v, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
        _refuse_fallback(
            "flash_attention", backend is not None,
            f"seq_len {q.shape[1]} is not divisible by block_q "
            f"{min(block_q, q.shape[1])}")
    return ref.flash_attention_ref(q, k, v, causal=causal)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    occupancy=None, num_splits=None,
                    backend: Optional[str] = None):
    """``occupancy`` (B,) bool marks real batch rows; ``False`` rows are
    padding — their output is exactly zero and independent of whatever their
    block-table entries point at (the serving engine pads its decode batch
    with masked rows instead of a reserved scratch page).  Both backends
    handle it natively in the kernel.  ``num_splits`` selects the Pallas
    kernel's flash-decoding split-K factor (None → heuristic; the xla
    reference has no split dimension and ignores it)."""
    kind, interpret = _resolve(backend)
    if kind == "pallas":
        return _paged_pallas(q, k_pages, v_pages, block_tables, context_lens,
                             occupancy=occupancy, num_splits=num_splits,
                             interpret=interpret)
    return ref.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                   context_lens, occupancy=occupancy)


def _packed_xla(q, k_pages, v_pages, page_rows, seg_ids, positions):
    """Production XLA path for packed prefill: lay every segment's page run
    end to end into ONE (S*s_max)-key axis and mask by key owner — one
    BLAS-friendly gemm and an S*s_max gather, where the naive oracle
    (ref.packed_prefill_attention_ref) gathers C*s_max key rows (a C-fold
    memory blowup the engine cannot afford per layer per chunk).  Each key
    slot belongs to exactly ONE (segment, position), so segments sharing a
    physical page (prefix-cache hits) just see their own copy unmasked."""
    c, h, d = q.shape
    _, page_size, hkv, _ = k_pages.shape
    g = h // hkv
    s, npg = page_rows.shape
    s_max = npg * page_size
    t = s * s_max
    scale = 1.0 / math.sqrt(d)
    k_seq = k_pages[page_rows].reshape(t, hkv, d).astype(jnp.float32)
    v_seq = v_pages[page_rows].reshape(t, hkv, d).astype(jnp.float32)
    qf = q.reshape(c, hkv, g, d).astype(jnp.float32) * scale
    sc = jnp.einsum("ckgd,tkd->ckgt", qf, k_seq)
    key_seg = jnp.arange(t, dtype=jnp.int32) // s_max
    key_pos = jnp.arange(t, dtype=jnp.int32) % s_max
    allowed = (seg_ids[:, None] == key_seg[None, :]) & \
        (key_pos[None, :] <= positions[:, None])
    sc = jnp.where(allowed[:, None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    # padding lanes (seg -1) match no key: pin their NaN softmax to zero
    p = jnp.where((seg_ids >= 0)[:, None, None, None], p, 0.0)
    out = jnp.einsum("ckgt,tkd->ckgd", p, v_seq)
    return out.reshape(c, h, d).astype(q.dtype)


def packed_prefill_attention(q, k_pages, v_pages, page_rows, seg_ids,
                             positions, seg_ctx, *,
                             backend: Optional[str] = None):
    """Packed multi-prompt prefill attention (block-diagonal per segment
    plus each segment's page-resident prefix); padding lanes (seg_id -1)
    output exactly zero on both backends.  See
    :func:`repro.kernels.ref.packed_prefill_attention_ref` for the shape
    contract (the oracle; the xla path here is the equivalent
    concatenated-key formulation)."""
    kind, interpret = _resolve(backend)
    if kind == "pallas":
        return _packed_pallas(q, k_pages, v_pages, page_rows, seg_ids,
                              positions, seg_ctx, interpret=interpret)
    return _packed_xla(q, k_pages, v_pages, page_rows, seg_ids, positions)


def sample_tokens(logits, temperature, top_k, top_p, seed, position, *,
                  stream=ref.STREAM_TARGET, backend: Optional[str] = None):
    """Fused replay-exact token sampling: logits (B,V) + per-row operands
    (B,) → (tokens (B,) i32, logprobs (B,) f32).  ``temperature <= 0`` rows
    are exact ``argmax(logits)`` (logprob 0) — bit-identical to the
    pre-sampling engine.  Randomness is the stateless counter PRNG keyed by
    ``(seed, position, stream)`` (see :mod:`repro.kernels.ref`), which is
    what makes swap/migration replay reproduce tokens without RNG state.

    The reference sorts the whole vocabulary twice per row, takes a cumsum
    and a scatter over it and draws Gumbel noise for every entry: at a
    151 936-token vocabulary on a TPU, more device time than the model's
    layers.  Inside its vmap a greedy row cannot skip that (the greedy test
    is a per-row value, so XLA computes both sides and selects), so the gate
    sits outside: a batch with no sampled row takes a ``lax.cond`` branch
    that is just ``argmax`` with zero logprobs — what the reference returns
    for greedy rows, bit for bit.  Padding rows carry zero operands and
    count as greedy.

    There is no Pallas variant: both branches are jnp ops inside the
    enclosing jit (the engine's step programs stay one dispatch), so both
    backends share the reference formulation."""
    del backend  # single formulation; kept for dispatch-signature parity

    def argmax_only(logits, *_):
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                jnp.zeros(logits.shape[:1], jnp.float32))

    def full(logits, temperature, top_k, top_p, seed, position):
        return ref.sample_tokens_ref(logits, temperature, top_k, top_p, seed,
                                     position, stream=stream)

    # the same greedy test as sample_token_ref, over the whole batch
    all_greedy = jnp.all(jnp.asarray(temperature, jnp.float32) <= 0.0)
    return jax.lax.cond(all_greedy, argmax_only, full, logits, temperature,
                        top_k, top_p, seed, position)


def spec_verify_rows(p_dist, q_dist, draft_toks, n_draft, seed, base_pos, *,
                     backend: Optional[str] = None):
    """Fused speculative-decode rejection sampling (batched rows); see
    :func:`repro.kernels.ref.spec_verify_ref` for the accept rule, residual
    construction and replay-keying contract.  Like :func:`sample_tokens`
    this is pure jnp fused into the caller's jit on every backend."""
    del backend
    return ref.spec_verify_rows_ref(p_dist, q_dist, draft_toks, n_draft,
                                    seed, base_pos)


def ssd(x, dt, a, b, c, *, chunk=128, d_skip=None,
        backend: Optional[str] = None):
    kind, interpret = _resolve(backend)
    if kind == "pallas":
        if x.shape[1] % min(chunk, x.shape[1]) == 0:
            y, final = _ssd_pallas(x, dt, a, b, c, chunk=chunk,
                                   interpret=interpret)
            if d_skip is not None:
                y = y + (x.astype(jnp.float32) *
                         d_skip.astype(jnp.float32)[None, None, :, None]
                         ).astype(y.dtype)
            return y, final
        _refuse_fallback(
            "ssd", backend is not None,
            f"seq_len {x.shape[1]} is not divisible by chunk "
            f"{min(chunk, x.shape[1])}")
    return ref.ssd_chunked_ref(x, dt, a, b, c, chunk=chunk, d_skip=d_skip)
