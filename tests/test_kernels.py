"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
ref.py pure-jnp oracles (kernels run under interpret=True on CPU; the same
pallas_call lowers to Mosaic on real TPU).

The deterministic sweeps always run; only the hypothesis-driven property
tests need the optional package (they are simply not collected without it,
instead of skipping the whole module)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan

TOLS = {jnp.float32: dict(rtol=3e-5, atol=3e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    # (B, Sq, Sk, H, Hkv, D, bq, bk)
    (1, 64, 64, 4, 4, 16, 32, 32),
    (2, 128, 128, 4, 2, 32, 64, 32),
    (1, 128, 128, 8, 1, 64, 128, 128),   # MQA, full-seq blocks
    (2, 96, 96, 2, 2, 16, 32, 32),       # non-pow2 seq
])
def test_flash_attention_sweep(dtype, causal, shape):
    b, sq, sk, h, hkv, d, bq, bk = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (B, H, Hkv, D, n_phys, page, n_pages)
    (2, 4, 2, 32, 16, 8, 4),
    (3, 8, 8, 16, 32, 16, 6),
    (1, 16, 2, 64, 8, 8, 8),
])
def test_paged_attention_sweep(dtype, shape):
    b, h, hkv, d, nphys, page, npg = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d),
                           jnp.float32).astype(dtype)
    bt = jax.random.randint(ks[3], (b, npg), 0, nphys)
    cl = jax.random.randint(ks[4], (b,), 1, npg * page + 1)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (B, S, H, P, G, N, chunk)
    (2, 64, 4, 8, 2, 16, 16),
    (1, 128, 2, 16, 1, 32, 32),
    (2, 32, 8, 8, 4, 8, 32),   # single chunk
])
def test_ssd_scan_sweep(dtype, shape):
    b, s, h, p, g, n, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bb = (jax.random.normal(ks[3], (b, s, g, n)) * 0.3).astype(dtype)
    cc = (jax.random.normal(ks[4], (b, s, g, n)) * 0.3).astype(dtype)
    y, f = ssd_scan(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    yr, fr = ref.ssd_ref(x, dt, a, bb, cc)
    tol = dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32 else \
        dict(rtol=4e-2, atol=4e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(f, np.float32),
                               np.asarray(fr, np.float32), **tol)


if HAVE_HYPOTHESIS:

    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(
        b=st.integers(1, 3),
        n_pages=st.integers(1, 6),
        page=st.sampled_from([4, 8]),
        hkv=st.sampled_from([1, 2]),
        group=st.sampled_from([1, 2, 4]),
        d=st.sampled_from([8, 16]),
    )
    def test_paged_attention_property(b, n_pages, page, hkv, group, d):
        """Property: kernel == oracle for arbitrary page-table contents and
        context lengths (the shapes the SMR-managed pool can produce)."""
        h = hkv * group
        nphys = max(b * n_pages, 2)
        ks = jax.random.split(jax.random.PRNGKey(b * 100 + n_pages), 5)
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
        vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
        bt = jax.random.randint(ks[3], (b, n_pages), 0, nphys)
        cl = jax.random.randint(ks[4], (b,), 1, n_pages * page + 1)
        out = paged_attention(q, kp, vp, bt, cl, interpret=True)
        want = ref.paged_attention_ref(q, kp, vp, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("num_splits", [None, 1, 2, 3, 4])
@pytest.mark.parametrize("shape", [
    # (B, H, Hkv, D, n_phys, page, n_pages)
    (2, 4, 2, 32, 16, 8, 4),
    (1, 16, 2, 64, 8, 8, 8),
    (3, 8, 8, 16, 32, 16, 6),   # n_pages not divisible by splits 4
])
def test_paged_attention_split_k_sweep(num_splits, shape):
    """Flash-decoding split-K: any split factor (including ones that do NOT
    divide the page count — the last split runs ragged) must reproduce the
    oracle bit-for-bit after the on-device max/sum combine."""
    b, h, hkv, d, nphys, page, npg = shape
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    bt = jax.random.randint(ks[3], (b, npg), 0, nphys)
    cl = jax.random.randint(ks[4], (b,), 1, npg * page + 1)
    out = paged_attention(q, kp, vp, bt, cl, num_splits=num_splits,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("num_splits", [1, 2, 3])
def test_paged_attention_split_k_occupancy(num_splits):
    """Native occupancy × split-K: padded rows (aliasing live rows' pages)
    stay exactly zero whatever the split factor — every split's partial for
    a dead row is dead, and the combine must not resurrect it."""
    b, h, hkv, d, nphys, page, npg = 4, 4, 2, 16, 8, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(12), 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    bt = jax.random.randint(ks[3], (b, npg), 0, nphys)
    bt = bt.at[1].set(bt[0]).at[3].set(bt[2])
    cl = jax.random.randint(ks[4], (b,), 1, npg * page + 1)
    occ = jnp.asarray([True, False, True, False])
    out = np.asarray(paged_attention(q, kp, vp, bt, cl, occupancy=occ,
                                     num_splits=num_splits, interpret=True),
                     np.float32)
    assert np.all(out[~np.asarray(occ)] == 0.0), "padded rows leaked output"
    assert np.all(np.isfinite(out))
    want = ref.paged_attention_ref(q, kp, vp, bt, cl, occupancy=occ)
    np.testing.assert_allclose(out, np.asarray(want, np.float32),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_paged_attention_occupancy_mask(backend):
    """The serving engine's decode-batch padding: rows with occupancy=False
    must produce exactly zero output — independent of whatever their
    block-table entries alias (here: the same pages real rows use, i.e. the
    worst case a recycled page id could produce) — while occupied rows match
    the unmasked reference bit-for-bit."""
    b, h, hkv, d, nphys, page, npg = 4, 4, 2, 16, 8, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    bt = jax.random.randint(ks[3], (b, npg), 0, nphys)
    cl = jax.random.randint(ks[4], (b,), 1, npg * page + 1)
    occ = jnp.asarray([True, False, True, False])
    # padded rows alias the REAL rows' pages — the mask, not the page
    # contents, must keep them inert
    bt = bt.at[1].set(bt[0]).at[3].set(bt[2])
    out = ops.paged_attention(q, kp, vp, bt, cl, occupancy=occ,
                              backend=backend)
    out = np.asarray(out, np.float32)
    assert np.all(out[~np.asarray(occ)] == 0.0), "padded rows leaked output"
    assert np.all(np.isfinite(out)), "mask produced NaN/inf"
    want = ref.paged_attention_ref(q[np.asarray(occ)], kp, vp,
                                   bt[np.asarray(occ)], cl[np.asarray(occ)])
    np.testing.assert_allclose(out[np.asarray(occ)],
                               np.asarray(want, np.float32),
                               rtol=3e-5, atol=3e-5)


def test_paged_attention_occupancy_all_masked_and_zero_ctx():
    """Degenerate corners the engine can produce while every sequence is
    still prefilling: an all-padding batch, and padded rows carrying ctx=0
    (an all-masked softmax must pin to zero, not NaN)."""
    b, h, hkv, d, nphys, page, npg = 2, 2, 1, 8, 4, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    bt = jnp.zeros((b, npg), jnp.int32)
    out = ref.paged_attention_ref(q, kp, vp, bt,
                                  jnp.asarray([0, 0], jnp.int32),
                                  occupancy=jnp.asarray([False, False]))
    assert np.all(np.asarray(out) == 0.0)


def test_ops_dispatch():
    """ops.py wrappers agree across backends."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 32, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 32, 2, 16), jnp.float32)
    a = ops.flash_attention(q, k, v, backend="xla")
    b = ops.flash_attention(q, k, v, backend="pallas_interpret",
                            block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-5, atol=3e-5)


def test_ops_explicit_pallas_raises_on_bad_shapes():
    """Dispatch honesty: an EXPLICIT backend='pallas*' request whose shapes
    the kernel cannot take must raise — never silently run the jnp
    reference (the silent fallback is how 'the TPU run was slow' hides)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    # seq_len 33 is not divisible by any block_q the wrapper would pick
    q = jax.random.normal(ks[0], (1, 33, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 33, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 33, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="explicitly requested"):
        ops.flash_attention(q, k, v, backend="pallas_interpret", block_q=32)
    x = jax.random.normal(ks[0], (1, 33, 4, 8), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 33, 4)))
    a = -jnp.exp(jax.random.normal(ks[2], (4,)) * 0.5)
    bb = jax.random.normal(ks[1], (1, 33, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="explicitly requested"):
        ops.ssd(x, dt, a, bb, bb, chunk=32, backend="pallas_interpret")


def test_ops_default_pallas_warns_once_on_fallback():
    """When pallas is only the SESSION default, the reference fallback still
    happens but warns once per (op, reason) — visible, not fatal."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 35, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 35, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 35, 2, 16), jnp.float32)
    old = ops.default_backend()
    ops.set_default_backend("pallas_interpret")
    try:
        ops._FALLBACKS_WARNED.clear()
        with pytest.warns(RuntimeWarning, match="falling back"):
            first = ops.flash_attention(q, k, v, block_q=32)
        # second identical call: same (op, reason) key — no second warning
        import warnings as _warnings
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            again = ops.flash_attention(q, k, v, block_q=32)
    finally:
        ops.set_default_backend(old)
        ops._FALLBACKS_WARNED.clear()
    np.testing.assert_allclose(np.asarray(first), np.asarray(again))


def test_ops_pallas_refuses_non_tpu():
    """backend='pallas' means Mosaic on a TPU: on the CPU it raises rather
    than drop to interpret mode, so a run that lost its chip cannot pass
    for a kernel run.  The process default follows the platform."""
    assert jax.default_backend() == "cpu"
    assert ops.default_backend() == "xla"
    b, h, hkv, d, nphys, page, npg = 2, 4, 2, 16, 4, 4, 2
    q = jnp.ones((b, h, d), jnp.float32)
    kp = jnp.ones((nphys, page, hkv, d), jnp.float32)
    bt = jnp.zeros((b, npg), jnp.int32)
    cl = jnp.ones((b,), jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ops.paged_attention(q, kp, kp, bt, cl, backend="pallas")
    seg = jnp.zeros((4,), jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ops.packed_prefill_attention(jnp.ones((4, h, d)), kp, kp, bt[:1],
                                     seg, seg, cl[:1], backend="pallas")
    with pytest.raises(ValueError, match="unknown backend"):
        ops.paged_attention(q, kp, kp, bt, cl, backend="mosaic")


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_packed_prefill_ops_backends_agree(backend):
    """ops.packed_prefill_attention: both backends match the oracle on a
    mixed chunk (3 segments + padding tail)."""
    c, h, hkv, d, nphys, page, npg = 16, 4, 2, 16, 12, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (c, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    rows = jax.random.randint(ks[3], (3, npg), 0, nphys)
    lens = (5, 6, 3)
    seg = jnp.asarray(sum(([i] * n for i, n in enumerate(lens)), [])
                      + [-1, -1], jnp.int32)
    pos = jnp.asarray(sum((list(range(page, page + n)) for n in lens), [])
                      + [0, 0], jnp.int32)
    ctx = jnp.asarray([page + n for n in lens], jnp.int32)
    out = np.asarray(ops.packed_prefill_attention(
        q, kp, vp, rows, seg, pos, ctx, backend=backend), np.float32)
    want = ref.packed_prefill_attention_ref(q, kp, vp, rows, seg, pos, ctx)
    np.testing.assert_allclose(out, np.asarray(want, np.float32),
                               rtol=3e-5, atol=3e-5)
    assert np.all(out[sum(lens):] == 0.0), "padding lanes leaked output"
