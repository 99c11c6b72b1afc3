"""Packed multi-prompt prefill — kernel parity and engine behavior.

* **Kernel parity**: the packed-segment attention (ops-xla and
  Pallas-interpret) equals the pure-jnp oracle across a grid of segment
  counts × prompt lengths × prefix-hit offsets × occupancy patterns —
  including padding lanes whose (clamped) segment would alias a live
  segment's pages, the worst case a recycled page id can produce.  A
  hypothesis property sweeps random layouts under the pinned "ci" profile.

* **Engine exactness**: the ``packed`` scheduler emits token-for-token the
  same output as the ``chunked`` baseline (and the one-shot greedy
  reference) for any chunk size, prompt mix, and prefix-hit offset, under
  reclaiming schemes (HP / IBR / EBR), on both the xla and
  pallas_interpret engine backends.

* **Packing**: a wave of short prompts admits in ONE packed chunk
  (``packed_segments_per_chunk`` > 1) while every already-active sequence
  still advances ≥ 1 token per engine step — the ITL bound chunking bought
  survives packing — and the pool drains clean afterwards.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.packed_prefill import packed_prefill_attention
from repro.models import build_model
from repro.serving import ServingConfig

from test_serving import _reference_greedy

try:
    from hypothesis import given
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ------------------------------------------------------------ kernel parity
def _packed_case(seg_lens, prefix_pages, c, page, npg, nphys, h, hkv, d,
                 seed, alias_padding=False):
    """Build one packed chunk layout: segment i contributes seg_lens[i]
    lanes resuming after prefix_pages[i] whole pages; leftover lanes are
    padding (seg -1)."""
    n_segs = len(seg_lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (c, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    rows = jax.random.randint(ks[3], (n_segs, npg), 0, nphys)
    seg, pos = [], []
    for i, (n, pre) in enumerate(zip(seg_lens, prefix_pages)):
        seg += [i] * n
        pos += list(range(pre * page, pre * page + n))
    pad = c - len(seg)
    assert pad >= 0
    if alias_padding and pad:
        # padding lanes carry positions INSIDE segment 0's live range: only
        # the seg==-1 mask (not position luck) keeps them inert, and the
        # clamped gather in the oracle aliases segment 0's pages
        seg += [-1] * pad
        pos += [min(int(pos[0]), page * npg - 1)] * pad
    else:
        seg += [-1] * pad
        pos += [0] * pad
    seg = jnp.asarray(seg, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    ctx = jnp.asarray([pre * page + n
                       for n, pre in zip(seg_lens, prefix_pages)], jnp.int32)
    return q, kp, vp, rows, seg, pos, ctx, pad


# one segment filling the chunk; even split; ragged mix with padding; many
# tiny segments; prefix offsets from cold-start to deep resume
_GRID = [
    # (seg_lens, prefix_pages, C)
    (((16,), (0,), 16)),
    (((8, 8), (1, 0), 16)),
    (((5, 7, 3), (0, 2, 1), 16)),
    (((3, 2, 4, 1, 2), (1, 0, 3, 2, 0), 16)),
    (((10, 13), (2, 3), 24)),
]


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("alias_padding", [False, True])
@pytest.mark.parametrize("case", _GRID)
def test_packed_kernel_parity_grid(backend, alias_padding, case):
    seg_lens, prefix_pages, c = case
    page, npg, nphys, h, hkv, d = 4, 4, 24, 4, 2, 16
    q, kp, vp, rows, seg, pos, ctx, pad = _packed_case(
        seg_lens, prefix_pages, c, page, npg, nphys, h, hkv, d,
        seed=sum(seg_lens), alias_padding=alias_padding)
    out = np.asarray(ops.packed_prefill_attention(
        q, kp, vp, rows, seg, pos, ctx, backend=backend), np.float32)
    want = np.asarray(ref.packed_prefill_attention_ref(
        q, kp, vp, rows, seg, pos, ctx), np.float32)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)
    if pad:
        assert np.all(out[-pad:] == 0.0), \
            "padding lanes must output exactly zero"
    assert np.all(np.isfinite(out))


def test_packed_kernel_matches_per_sequence_paged_decode():
    """Cross-oracle check: a packed chunk whose every lane is a segment's
    LAST token must reproduce single-token paged decode for each segment —
    the packed prefill and the decode kernel agree on the same pages."""
    page, npg, nphys, h, hkv, d = 4, 3, 16, 4, 2, 16
    n_segs = 3
    ks = jax.random.split(jax.random.PRNGKey(42), 4)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    rows = jax.random.randint(ks[3], (n_segs, npg), 0, nphys)
    cls = jnp.asarray([5, 9, 12], jnp.int32)      # context incl. the lane
    q = jax.random.normal(ks[0], (n_segs, h, d), jnp.float32)
    # one lane per segment, positioned at its last token
    seg = jnp.arange(n_segs, dtype=jnp.int32)
    pos = cls - 1
    out = ops.packed_prefill_attention(q, kp, vp, rows, seg, pos, cls,
                                       backend="xla")
    want = ref.paged_attention_ref(q, kp, vp, rows, cls)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


if HAVE_HYPOTHESIS:

    @given(
        n_segs=st.integers(1, 4),
        data=st.data(),
        seed=st.integers(0, 2 ** 16),
    )
    def test_packed_kernel_property(n_segs, data, seed):
        """Property: random segment layouts (lengths, prefix offsets,
        padding tails) match the oracle on both backends.  Runs under the
        pinned CI hypothesis profile (tests/conftest.py)."""
        page, npg, nphys, h, hkv, d = 4, 4, 24, 4, 2, 16
        c = 16
        lens, pres, left = [], [], c
        for i in range(n_segs):
            hi = max(1, left - (n_segs - 1 - i))
            n = data.draw(st.integers(1, min(6, hi)), label=f"len{i}")
            max_pre = npg - (-(-n // page))     # prefix + slice fits npg
            pres.append(data.draw(st.integers(0, max(0, max_pre)),
                                  label=f"pre{i}"))
            lens.append(n)
            left -= n
        q, kp, vp, rows, seg, pos, ctx, pad = _packed_case(
            tuple(lens), tuple(pres), c, page, npg, nphys, h, hkv, d,
            seed=seed, alias_padding=bool(seed % 2))
        want = np.asarray(ref.packed_prefill_attention_ref(
            q, kp, vp, rows, seg, pos, ctx), np.float32)
        for backend in ("xla", "pallas_interpret"):
            out = np.asarray(ops.packed_prefill_attention(
                q, kp, vp, rows, seg, pos, ctx, backend=backend),
                np.float32)
            np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)
            if pad:
                assert np.all(out[-pad:] == 0.0), (backend, lens, pres)


def test_packed_kernel_interpret_direct():
    """The raw pallas_call entry point (not via ops): interpret-mode kernel
    equals the oracle including an unused trailing segment (ctx 0)."""
    page, npg, nphys, h, hkv, d = 4, 3, 12, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    c = 12
    q = jax.random.normal(ks[0], (c, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (nphys, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (nphys, page, hkv, d), jnp.float32)
    rows = jax.random.randint(ks[3], (3, npg), 0, nphys)   # 3 rows, 2 used
    seg = jnp.asarray([0] * 6 + [1] * 4 + [-1] * 2, jnp.int32)
    pos = jnp.asarray(list(range(4, 10)) + list(range(4)) + [0, 0],
                      jnp.int32)
    ctx = jnp.asarray([10, 4, 0], jnp.int32)               # seg 2 unused
    out = packed_prefill_attention(q, kp, vp, rows, seg, pos, ctx,
                                   interpret=True)
    want = ref.packed_prefill_attention_ref(q, kp, vp, rows, seg, pos, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------- engine
_MODEL = None


def _get_model():
    global _MODEL
    if _MODEL is None:
        cfg = get_config("tinyllama-1.1b").reduced().replace(dtype="float32")
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(7))
        _MODEL = (model, params)
    return _MODEL


_REFERENCE = {}


def _ref(prompt, n_new):
    key = (tuple(prompt), n_new)
    if key not in _REFERENCE:
        model, params = _get_model()
        _REFERENCE[key] = _reference_greedy(model, params, prompt, n_new)
    return _REFERENCE[key]


def _serve_packed(smr, chunk, backend="xla", **kw):
    model, params = _get_model()
    return serving.serve(
        model, params,
        ServingConfig(smr=smr, num_pages=64, page_size=4,
                      max_batch=3, max_seq_len=64, scheduler="packed",
                      backend=backend, prefill_chunk_tokens=chunk, **kw))


@pytest.mark.parametrize("chunk", [4, 12, 64])
@pytest.mark.parametrize("smr", ["HP", "IBR", "EBR"])
def test_packed_engine_exactness_grid(smr, chunk):
    """The packed scheduler emits token-for-token the reference greedy
    output — prompts short and long, page-aligned and not, cold and
    resuming from prefix-cache hits at several offsets — and the pool
    drains clean under every reclaiming scheme."""
    session = _serve_packed(smr, chunk)
    rng = np.random.RandomState(23)
    wave1 = [list(rng.randint(1, 200, size=n)) for n in (8, 13, 21)]
    handles = [session.submit(p, max_new_tokens=6) for p in wave1]
    outs = [h.result(timeout=180) for h in handles]
    # wave 2 resumes from prefix-cache hits: packed chunks then start
    # mid-prompt with nonzero positions (the prefix pages feed the mask)
    wave2 = [wave1[0][:8] + [201], wave1[2][:12] + [202, 203]]
    hits_before = session.stats()["totals"]["prefix_hits"]
    handles2 = [session.submit(p, max_new_tokens=6) for p in wave2]
    outs2 = [h.result(timeout=180) for h in handles2]
    stats = session.stats()
    session.close()
    assert stats["totals"]["prefix_hits"] > hits_before, \
        "wave 2 never hit the cache — the resume path went untested"
    assert stats["totals"]["packed_chunks"] > 0, \
        "the packed path never ran"
    for p, out in zip(wave1 + wave2, outs + outs2):
        assert out == _ref(p, 6), (smr, chunk, p[:4])
    pool = session.engine.shards[0].pool.stats()
    assert pool["free"] == 64 and pool["awaiting_reclaim"] == 0, pool


def test_packed_engine_pallas_interpret_backend():
    """One engine run with backend='pallas_interpret': the packed-prefill
    Pallas kernel AND the split-K decode kernel carry the whole session,
    still token-exact vs the reference."""
    session = _serve_packed("IBR", 12, backend="pallas_interpret")
    rng = np.random.RandomState(29)
    prompts = [list(rng.randint(1, 200, size=n)) for n in (6, 11)]
    handles = [session.submit(p, max_new_tokens=4) for p in prompts]
    outs = [h.result(timeout=300) for h in handles]
    session.close()
    for p, out in zip(prompts, outs):
        assert out == _ref(p, 4), p[:4]


if HAVE_HYPOTHESIS:

    @given(
        lens=st.lists(st.integers(3, 20), min_size=1, max_size=3),
        chunk_pages=st.integers(1, 5),
        smr=st.sampled_from(["HP", "IBR", "EBR"]),
        seed=st.integers(0, 2 ** 16),
    )
    def test_packed_engine_property(lens, chunk_pages, smr, seed):
        """Property: random prompt mixes × chunk sizes × schemes — packed
        equals the one-shot greedy oracle token for token.  Pinned CI
        hypothesis profile (tests/conftest.py)."""
        rng = np.random.RandomState(seed)
        prompts = [list(rng.randint(1, 200, size=n)) for n in lens]
        session = _serve_packed(smr, chunk_pages * 4)
        try:
            handles = [session.submit(p, max_new_tokens=4) for p in prompts]
            outs = [h.result(timeout=180) for h in handles]
        finally:
            session.close()
        for p, out in zip(prompts, outs):
            assert out == _ref(p, 4), (smr, chunk_pages, seed)


# --------------------------------------------------------------- packing
def test_short_prompt_wave_admits_in_one_chunk():
    """A wave of short prompts shares ONE packed chunk (the counters show
    several segments per chunk) while every already-active sequence still
    advances ≥ 1 token per engine step — packing buys throughput without
    giving back chunking's ITL bound."""
    model, params = _get_model()
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_pages=128, page_size=4, max_batch=6,
                      max_seq_len=64, scheduler="packed",
                      prefill_chunk_tokens=32),
        start=False)          # manual stepping: we observe every step
    shard = session.engine.shards[0]
    rng = np.random.RandomState(5)

    # two sequences decoding before the wave arrives
    active = [session.submit(list(rng.randint(1, 200, size=5)),
                             max_new_tokens=40) for _ in range(2)]
    for _ in range(200):
        if all(h.status == "active" for h in active):
            break
        shard.step()
    assert all(h.status == "active" for h in active)
    chunks_before = shard.packed_chunks

    # the wave: 4 short prompts, 6+7+5+8 = 26 tokens ≤ the 32-token budget
    wave = [session.submit(list(rng.randint(1, 200, size=n)),
                           max_new_tokens=3) for n in (6, 7, 5, 8)]
    before = [len(h.out_tokens) for h in active]
    shard.step()              # ONE step admits and prefills the whole wave
    assert all(h.status != "waiting" and h.status != "prefilling"
               for h in wave), [h.status for h in wave]
    assert all(len(h.out_tokens) >= 1 for h in wave), \
        "every wave member should stream its first token from the one chunk"
    assert shard.packed_chunks == chunks_before + 1, \
        "the wave should cost exactly one packed chunk"
    for h, b in zip(active, before):
        assert len(h.out_tokens) >= b + 1, \
            "active decoder stalled by the admission wave"

    stats = shard.stats()
    assert stats["packed_segments_per_chunk"] > 1.0, stats
    # waste accounting: the wave's chunk had 32 - 26 = 6 padded lanes
    assert stats["prefill_tokens_wasted"] >= 6

    for _ in range(300):
        if all(h.done.is_set() for h in active + wave):
            break
        shard.step()
    outs = [h.result(timeout=1) for h in wave]
    session.close()
    for h, out in zip(wave, outs):
        assert out == _ref(list(h.req.prompt), 3)
    pool = shard.pool.stats()
    assert pool["free"] == 128 and pool["awaiting_reclaim"] == 0, pool


def test_packed_stats_surface():
    """Session totals expose the new counters; chunked sessions report
    zero packed chunks; ServingConfig validates backend names."""
    session = _serve_packed("IBR", 12)
    rng = np.random.RandomState(11)
    hs = [session.submit(list(rng.randint(1, 200, size=7)),
                         max_new_tokens=2) for _ in range(3)]
    for h in hs:
        h.result(timeout=120)
    stats = session.stats()
    totals = stats["totals"]
    session.close()
    for key in ("prefill_chunks", "prefill_tokens_wasted", "packed_chunks",
                "packed_segments", "packed_segments_per_chunk"):
        assert key in totals, key
    assert totals["packed_chunks"] > 0
    assert totals["packed_segments"] >= totals["packed_chunks"]
    assert totals["packed_segments_per_chunk"] == pytest.approx(
        totals["packed_segments"] / totals["packed_chunks"])
    assert stats["config"]["backend"] == "xla"

    with pytest.raises(ValueError, match="unknown backend"):
        ServingConfig(backend="cuda")


def test_backend_follows_the_platform():
    """The default backend is resolved at construction from the platform
    (the CPU here: "xla"); naming "pallas" off a TPU raises instead of
    falling back to interpret mode."""
    assert ServingConfig().backend == "xla"
    assert ServingConfig(backend="pallas_interpret").backend == \
        "pallas_interpret"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ServingConfig(backend="pallas")
