"""Replay-exact on-device sampling + speculative decoding (DESIGN.md §17).

Layers, bottom up:

1. Kernel oracle units — the filtered-distribution builder (greedy
   one-hot, top-k/top-p masks), the counter-PRNG replay keystone, and
   the speculative rejection-sampling verifier's algebra (identical
   dists accept everything, disjoint dists reject at 0, n_draft=0
   degenerates to a plain sampled step).
2. Policy registry + config surface — names, coercion, validation,
   ``spec_*`` config fields, draft derivation.
3. Engine end-to-end — the greedy policy is BIT-IDENTICAL to the
   pre-sampling engine; seeded sampled decode is deterministic AND
   matches a host-side oracle decode keyed by absolute position;
   logprobs and stop sequences work; speculative greedy equals plain
   greedy token-for-token; sampled speculative decode is seeded-
   deterministic with accept-rate accounting.
4. The ISSUE's acceptance: a seeded ``temperature=0.8`` request that is
   swap-preempted + resumed, or live-migrated off a stalled shard, (or
   both) emits EXACTLY the uninterrupted run's tokens — the
   greedy-determinism assumption is gone, replaced by teacher-forced
   replay + counter PRNG.  Preemption parks and migration stalls are
   excluded from ``itl()`` and reported via ``gaps()``.  A randomized
   schedule property (pinned ``ci`` hypothesis profile) covers policy ×
   burst × spec-mode combinations.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, serving
from repro.configs import get_config
from repro.kernels import ref as kref
from repro.models import build_model
from repro.models.registry import derive_draft
from repro.runtime.swap import page_nbytes
from repro.serving import (
    FaultSpec,
    GreedySampling,
    SamplingPolicy,
    ServingConfig,
    TemperatureSampling,
    TopKSampling,
    TopPSampling,
    as_sampling_policy,
    sampling_policies,
)

from test_serving import _prompt_for_shard, _reference_greedy


# ===================================================== 1. kernel oracles
def test_filtered_dist_greedy_is_onehot():
    logits = jnp.asarray([0.1, 2.0, -1.0, 1.9], jnp.float32)
    d = kref.filtered_dist_ref(logits, 0.0, 0, 1.0)
    np.testing.assert_allclose(np.asarray(d), [0.0, 1.0, 0.0, 0.0])


def test_filtered_dist_topk_mask():
    logits = jnp.asarray([0.0, 3.0, 1.0, 2.0], jnp.float32)
    d = np.asarray(kref.filtered_dist_ref(logits, 1.0, 2, 1.0))
    assert (d > 0).sum() == 2 and d[1] > 0 and d[3] > 0
    np.testing.assert_allclose(d.sum(), 1.0, rtol=1e-6)


def test_filtered_dist_topp_keeps_most_likely():
    # one dominant token: even a tiny p keeps it (mass strictly BEFORE
    # the most likely token is 0 < p)
    logits = jnp.asarray([10.0, 0.0, 0.0, 0.0], jnp.float32)
    d = np.asarray(kref.filtered_dist_ref(logits, 1.0, 0, 0.01))
    np.testing.assert_allclose(d, [1.0, 0.0, 0.0, 0.0], atol=1e-6)
    # p=1 keeps everything
    d = np.asarray(kref.filtered_dist_ref(logits, 1.0, 0, 1.0))
    assert (d > 0).all()


def test_counter_prng_replay_exact():
    """The replay keystone: keys are pure functions of
    (seed, position, stream) — equal inputs give equal draws, and each
    coordinate separates the draws."""
    logits = jnp.asarray(np.random.RandomState(0).randn(64), jnp.float32)
    draws = {}
    for seed in (1, 2):
        for pos in (10, 11):
            for stream in (kref.STREAM_TARGET, kref.STREAM_DRAFT):
                t1, _ = kref.sample_token_ref(logits, 5.0, 0, 1.0, seed,
                                              pos, stream)
                t2, _ = kref.sample_token_ref(logits, 5.0, 0, 1.0, seed,
                                              pos, stream)
                assert int(t1) == int(t2), "same key, different draw"
                draws[(seed, pos, stream)] = int(t1)
    # high temperature spreads the dist enough that 8 independent keys
    # almost surely do not all collide on one token
    assert len(set(draws.values())) > 1


def test_sample_token_greedy_matches_argmax():
    logits = jnp.asarray(np.random.RandomState(1).randn(32), jnp.float32)
    tok, lp = kref.sample_token_ref(logits, 0.0, 0, 1.0, 7, 3)
    assert int(tok) == int(np.argmax(np.asarray(logits)))
    assert float(lp) == 0.0


def test_spec_verify_identical_dists_accept_all():
    """q == p accepts every live proposal (u * p < p for u in [0,1))
    and the bonus token comes from p[n_draft] via the RESIDUAL stream."""
    rng = np.random.RandomState(2)
    k, v = 3, 16
    p = jax.nn.softmax(jnp.asarray(rng.randn(k + 1, v), jnp.float32))
    q = p[:k]
    draft = jnp.asarray([1, 5, 9], jnp.int32)
    toks, n_emit, lps = kref.spec_verify_ref(p, q, draft, 3, 11, 100)
    assert int(n_emit) == k + 1
    assert list(np.asarray(toks[:k])) == [1, 5, 9]
    bonus, _ = kref.gumbel_pick_ref(
        p[k], kref.sample_key_ref(11, 100 + k, kref.STREAM_RESIDUAL))
    assert int(toks[k]) == int(bonus)
    np.testing.assert_allclose(
        np.asarray(lps[:k]), np.log(np.asarray(p[jnp.arange(k), draft])),
        rtol=1e-5)


def test_spec_verify_disjoint_dists_reject_first():
    """p puts zero mass on the draft's token: rejected at j=0 and the
    correction comes from the residual max(p - q, 0) ∝ p."""
    v = 8
    p = jnp.zeros((3, v), jnp.float32).at[:, 2].set(1.0)
    q = jnp.zeros((2, v), jnp.float32).at[:, 5].set(1.0)
    draft = jnp.asarray([5, 5], jnp.int32)
    toks, n_emit, _ = kref.spec_verify_ref(p, q, draft, 2, 0, 0)
    assert int(n_emit) == 1
    assert int(toks[0]) == 2          # residual is one-hot at 2


def test_spec_verify_zero_draft_is_plain_sample():
    """n_draft == 0 degenerates to one sampled token from p[0] — keyed
    on the RESIDUAL stream at base_pos."""
    rng = np.random.RandomState(3)
    p = jax.nn.softmax(jnp.asarray(rng.randn(3, 16), jnp.float32))
    q = jnp.zeros((2, 16), jnp.float32)
    draft = jnp.zeros((2,), jnp.int32)
    toks, n_emit, _ = kref.spec_verify_ref(p, q, draft, 0, 21, 55)
    assert int(n_emit) == 1
    want, _ = kref.gumbel_pick_ref(
        p[0], kref.sample_key_ref(21, 55, kref.STREAM_RESIDUAL))
    assert int(toks[0]) == int(want)


def test_spec_verify_greedy_chain_matches_argmax():
    """One-hot p and q (the greedy sentinel dists): a draft that matches
    p's argmax chain is fully accepted; a mismatch at j corrects to p's
    argmax — SPEC GREEDY is exact, never approximate."""
    v = 8
    argmaxes = [3, 6, 1]
    p = jnp.zeros((3, v), jnp.float32)
    for j, a in enumerate(argmaxes):
        p = p.at[j, a].set(1.0)
    q_match = p[:2]
    toks, n_emit, _ = kref.spec_verify_ref(
        p, q_match, jnp.asarray([3, 6], jnp.int32), 2, 0, 0)
    assert int(n_emit) == 3 and list(np.asarray(toks)) == argmaxes
    q_miss = jnp.zeros((2, v), jnp.float32).at[0, 4].set(1.0).at[1, 6].set(
        1.0)
    toks, n_emit, _ = kref.spec_verify_ref(
        p, q_miss, jnp.asarray([4, 6], jnp.int32), 2, 0, 0)
    assert int(n_emit) == 1 and int(toks[0]) == 3


# ============================================ 2. registry + config layer
def test_sampling_registry_names():
    assert sampling_policies() == ["greedy", "temperature", "top_k",
                                   "top_p"]
    assert api.sampling_policies() == sampling_policies()


def test_as_sampling_policy_coercion():
    assert isinstance(as_sampling_policy(None), GreedySampling)
    assert isinstance(as_sampling_policy("greedy"), GreedySampling)
    assert isinstance(as_sampling_policy("temperature"),
                      TemperatureSampling)
    pol = TopKSampling(k=7, seed=3)
    assert as_sampling_policy(pol) is pol
    with pytest.raises(ValueError, match="unknown sampling policy"):
        as_sampling_policy("beam")
    with pytest.raises(ValueError, match="unknown sampling policy"):
        as_sampling_policy(42)


def test_policy_validation():
    with pytest.raises(ValueError, match="temperature"):
        TemperatureSampling(temperature=0.0)
    with pytest.raises(ValueError, match="k >= 1"):
        TopKSampling(k=0)
    with pytest.raises(ValueError, match="p in"):
        TopPSampling(p=0.0)
    with pytest.raises(ValueError, match="p in"):
        TopPSampling(p=1.5)
    with pytest.raises(ValueError, match="empty stop"):
        GreedySampling(stop=([],))
    with pytest.raises(ValueError, match="temperature must be >= 0"):
        SamplingPolicy(temperature=-1.0)


def test_policy_operands_and_stop_normalization():
    pol = TemperatureSampling(temperature=0.8, seed=42, stop=(1, (2, 3)))
    t, k, p, s = pol.operands()
    assert (t, k, p, s) == (0.8, 0, 1.0, 42)
    assert pol.stop == ((1,), (2, 3))
    assert GreedySampling().operands()[0] == 0.0
    assert TopKSampling(k=5).operands()[1] == 5
    assert TopPSampling(p=0.5).operands()[2] == 0.5


def test_config_spec_validation():
    with pytest.raises(ValueError, match="spec_k"):
        ServingConfig(spec_k=-1)
    with pytest.raises(ValueError, match="spec_draft"):
        ServingConfig(spec_k=2, spec_draft="trained")
    with pytest.raises(ValueError, match="spec_draft_layers"):
        ServingConfig(spec_k=2, spec_draft_layers=-2)
    s = ServingConfig(spec_k=4).summary()
    assert s["spec_k"] == 4 and s["spec_draft"] == "auto"


def test_derive_draft_slices_target():
    cfg = get_config("tinyllama-1.1b").reduced().replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    draft, dparams = derive_draft(model, params)
    assert draft.cfg.n_layers == max(1, cfg.n_layers // 2)
    assert dparams["embed"] is params["embed"]
    leaf = jax.tree_util.tree_leaves(dparams["blocks"])[0]
    assert leaf.shape[0] == draft.cfg.n_layers
    draft1, _ = derive_draft(model, params, n_layers=1)
    assert draft1.cfg.n_layers == 1
    with pytest.raises(ValueError, match="spec_draft"):
        derive_draft(model, params, spec_draft="trained")
    with pytest.raises(ValueError, match="exceeds"):
        derive_draft(model, params, n_layers=cfg.n_layers + 1)


# ================================================ 3. engine end-to-end
@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("tinyllama-1.1b").reduced().replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    return model, params


def _config(**over):
    kw = dict(smr="IBR", num_pages=64, page_size=8, max_batch=2,
              max_seq_len=64)
    kw.update(over)
    return ServingConfig(**kw)


def _run(model, params, prompts, n_new, sampling=None, conf=None,
         want_stats=False):
    session = serving.serve(model, params, conf or _config())
    hs = [session.submit(p, max_new_tokens=n_new, sampling=sampling)
          for p in prompts]
    outs = [h.result(timeout=300) for h in hs]
    totals = session.stats()["totals"]
    session.close()
    return (outs, totals) if want_stats else outs


def _reference_sampled(model, params, prompt, n_new, policy):
    """Host-side oracle: contiguous-cache decode + the ref sampler keyed
    by ABSOLUTE position — the engine (paged, packed, preempted or
    migrated) must reproduce this stream exactly."""
    max_len = len(prompt) + n_new + 1
    cache_shapes, _ = model.init_cache(1, max_len)
    cache = {k: jnp.zeros(s.shape, s.dtype)
             for k, s in cache_shapes.items()}
    step = jax.jit(model.decode_step)
    t_f, k_i, p_f, seed = policy.operands()
    toks = list(prompt)
    out = []
    for t in range(max_len - 1):
        batch = {"tokens": jnp.asarray([[toks[t]]], jnp.int32),
                 "cache_len": jnp.asarray([t + 1], jnp.int32)}
        logits, cache = step(params, cache, batch)
        if t >= len(prompt) - 1:
            vec = jnp.asarray(np.asarray(logits, np.float32).reshape(-1))
            tok, _ = kref.sample_token_ref(vec, t_f, k_i, p_f, seed, t + 1)
            out.append(int(tok))
            if len(out) >= n_new:
                break
            toks.append(int(tok))
    return out


def test_greedy_policy_bit_identical_to_default(small_model):
    """The tentpole's compatibility bar: the greedy policy (by name,
    instance, or omitted) reproduces the pre-sampling engine exactly."""
    model, params = small_model
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 200, size=n)) for n in (9, 17, 12)]
    want = [_reference_greedy(model, params, p, 6) for p in prompts]
    assert _run(model, params, prompts, 6) == want
    assert _run(model, params, prompts, 6, sampling="greedy") == want
    assert _run(model, params, prompts, 6,
                sampling=GreedySampling(seed=99)) == want


def test_seeded_sampling_deterministic_and_matches_oracle(small_model):
    model, params = small_model
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, 200, size=n)) for n in (9, 13)]
    pol = TemperatureSampling(temperature=0.8, seed=123)
    one = _run(model, params, prompts, 6, sampling=pol)
    two = _run(model, params, prompts, 6, sampling=pol)
    assert one == two, "same seed, different stream"
    for p, out in zip(prompts, one):
        assert out == _reference_sampled(model, params, p, 6, pol), \
            "engine sampling diverged from the position-keyed oracle"
    # a different seed decodes a different stream (overwhelmingly)
    other = _run(model, params, prompts, 6,
                 sampling=TemperatureSampling(temperature=0.8, seed=124))
    assert other != one


@pytest.mark.parametrize("policy", [
    TopKSampling(k=20, temperature=0.9, seed=7),
    TopPSampling(p=0.8, temperature=0.9, seed=7),
])
def test_topk_topp_match_oracle(small_model, policy):
    model, params = small_model
    rng = np.random.RandomState(6)
    prompt = list(rng.randint(1, 200, size=11))
    (out,) = _run(model, params, [prompt], 6, sampling=policy)
    assert out == _reference_sampled(model, params, prompt, 6, policy)


def test_logprobs_recorded(small_model):
    model, params = small_model
    rng = np.random.RandomState(7)
    prompt = list(rng.randint(1, 200, size=10))
    session = serving.serve(model, params, _config())
    g = session.submit(prompt, max_new_tokens=5,
                       sampling=GreedySampling(logprobs=True))
    s = session.submit(prompt, max_new_tokens=5,
                       sampling=TemperatureSampling(temperature=0.8,
                                                    seed=5,
                                                    logprobs=True))
    n = session.submit(prompt, max_new_tokens=5)
    g.wait(timeout=300), s.wait(timeout=300), n.wait(timeout=300)
    session.close()
    assert g.logprobs() == [0.0] * 5        # greedy sentinel: lp 0
    assert len(s.logprobs()) == 5
    assert all(lp <= 0.0 for lp in s.logprobs())
    assert n.logprobs() == []               # not requested, not recorded


def test_stop_sequence_halts_generation(small_model):
    model, params = small_model
    rng = np.random.RandomState(8)
    prompt = list(rng.randint(1, 200, size=10))
    full = _reference_greedy(model, params, prompt, 8)
    stop = tuple(full[2:4])
    # generation halts right after the pair's FIRST occurrence, which may
    # come before index 2 when the stream repeats a token
    first = next(i for i in range(len(full) - 1)
                 if tuple(full[i:i + 2]) == stop)
    (out,) = _run(model, params, [prompt], 8,
                  sampling=GreedySampling(stop=(stop,)))
    assert out == full[:first + 2], "stop sequence did not halt at the match"
    # the matched tokens stay in the output; a non-matching stop is inert
    (out,) = _run(model, params, [prompt], 8,
                  sampling=GreedySampling(stop=((_unused_token(full),),)))
    assert out == full


def _unused_token(toks):
    t = 1
    while t in toks:
        t += 1
    return t


def test_spec_greedy_equals_plain_greedy(small_model):
    """Speculative decoding is EXACT: under one-hot dists the rejection
    sampler accepts exactly the argmax-matching prefix, so spec-greedy
    reproduces plain greedy token-for-token while counting proposals."""
    model, params = small_model
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(1, 200, size=n)) for n in (9, 17, 12)]
    want = [_reference_greedy(model, params, p, 6) for p in prompts]
    for k in (2, 4):
        outs, totals = _run(model, params, prompts, 6, conf=_config(
            spec_k=k), want_stats=True)
        assert outs == want, f"spec-k{k} greedy diverged"
        assert totals["draft_proposed"] > 0
        assert 0.0 <= totals["accept_rate"] <= 1.0


def test_spec_sampled_deterministic(small_model):
    model, params = small_model
    rng = np.random.RandomState(10)
    prompts = [list(rng.randint(1, 200, size=11)) for _ in range(2)]
    pol = TemperatureSampling(temperature=0.8, seed=321)
    one, st1 = _run(model, params, prompts, 8, sampling=pol,
                    conf=_config(spec_k=2), want_stats=True)
    two, st2 = _run(model, params, prompts, 8, sampling=pol,
                    conf=_config(spec_k=2), want_stats=True)
    assert one == two, "seeded spec decode not deterministic"
    assert st1["draft_accepted"] == st2["draft_accepted"]
    assert st1["draft_proposed"] > 0
    # every request hit max_new_tokens (no stop): 8 tokens each
    assert all(len(o) == 8 for o in one)


# ====================== 4. interrupted ≡ uninterrupted (the acceptance)
def _arena_bytes(model, slots=64):
    cfg = model.cfg
    return slots * page_nbytes(cfg.n_layers, 8, cfg.n_kv_heads,
                               cfg.head_dim, "float32")


def _swap_config(model, **over):
    kw = dict(smr="IBR", num_pages=32, page_size=8, max_batch=4,
              max_seq_len=128, admission="priority", eviction="swap",
              swap_bytes=_arena_bytes(model),
              priority_classes=("hi:priority=10", "lo:priority=0"))
    kw.update(over)
    return ServingConfig(**kw)


def _wait_decoding(handles, n, timeout=60.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if sum(1 for h in handles if h.out_tokens) >= n:
            return True
        time.sleep(0.002)
    return False


def _uninterrupted(model, params, prompts, n_new, policy, spec_k=0):
    """Reference streams: the same engine, zero pressure (big pool, no
    competing class), one request at a time."""
    conf = _config(num_pages=64, page_size=8, max_batch=4,
                   max_seq_len=128, spec_k=spec_k)
    session = serving.serve(model, params, conf)
    outs = [session.submit(p, max_new_tokens=n_new,
                           sampling=policy).result(timeout=300)
            for p in prompts]
    session.close()
    return outs


def test_sampled_preempt_resume_token_exact(small_model):
    """THE acceptance criterion: seeded temperature=0.8 requests that are
    swap-preempted and resumed emit exactly the uninterrupted streams —
    and the park interval is excluded from itl() but visible in gaps()."""
    model, params = small_model
    rng = np.random.RandomState(42)
    pol = TemperatureSampling(temperature=0.8, seed=1234)
    lows_p = [list(rng.randint(1, 200, size=16)) for _ in range(6)]
    highs_p = [list(rng.randint(1, 200, size=16)) for _ in range(2)]
    want_lo = _uninterrupted(model, params, lows_p, 48, pol)
    want_hi = _uninterrupted(model, params, highs_p, 32, pol)
    session = serving.serve(model, params, _swap_config(model))
    session.warm()
    lows = [session.submit(p, max_new_tokens=48, priority_class="lo",
                           sampling=pol) for p in lows_p]
    assert _wait_decoding(lows, 4), "lows never saturated the batch"
    highs = [session.submit(p, max_new_tokens=32, priority_class="hi",
                            sampling=pol) for p in highs_p]
    for h in lows + highs:
        assert h.wait(timeout=300), "request hung under preemption"
    totals = session.stats()["totals"]
    session.close()
    assert totals["preemptions"] >= 1 and totals["resumed"] >= 1
    for h, want in zip(lows + highs, want_lo + want_hi):
        assert h.status == "done", (h.status, h.req.error)
        assert h.result() == want, \
            f"sampled preempted decode diverged (preempt={h.preemptions})"
    # gap accounting: every preempted request reports its park intervals
    # through gaps(), and itl() excludes exactly those intervals
    preempted = [h for h in lows if h.preemptions > 0]
    assert preempted
    for h in preempted:
        assert len(h.gaps()) >= 1
        assert all(g > 0 for g in h.gaps())
        assert len(h.itl()) + len(h.gaps()) == len(h.out_tokens) - 1
    assert totals["gap_intervals"] >= len(preempted)
    assert totals["gap_seconds"] > 0.0
    clean = [h for h in highs if h.preemptions == 0]
    for h in clean:
        assert h.gaps() == []


def test_sampled_migration_token_exact(small_model):
    """A stalled shard's seeded-sampled sequences live-migrate and still
    emit the uninterrupted streams: teacher-forced replay + counter PRNG,
    not greedy determinism.  The migration stall is a gap, not an ITL."""
    model, params = small_model
    pol = TemperatureSampling(temperature=0.8, seed=777)
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_shards=2, num_pages=128, page_size=8,
                      max_batch=4, max_seq_len=64,
                      heartbeat_timeout_s=0.25, watchdog_interval_s=0.02,
                      faults=(FaultSpec(kind="stall", shard=0,
                                        after_done=2, duration_s=2.0),)))
    rng = np.random.RandomState(11)
    router = session.engine.router
    for shard in range(router.num_shards):
        p = _prompt_for_shard(router, rng, shard, 10)
        session.submit(p, max_new_tokens=2).result(timeout=300)
    deadline = time.perf_counter() + 10.0
    while time.perf_counter() < deadline and \
            any(s.degraded for s in session.engine.shards):
        time.sleep(0.02)
    short = session.submit(_prompt_for_shard(router, rng, 0, 10),
                           max_new_tokens=3)
    longs = [_prompt_for_shard(router, rng, 0, 10) for _ in range(2)]
    handles = [session.submit(p, max_new_tokens=20, sampling=pol)
               for p in longs]
    assert short.result(timeout=300) is not None
    outs = [h.result(timeout=300) for h in handles]
    totals = session.stats()["totals"]
    session.close()
    assert totals["migrations"] >= 1, "stall never forced a migration"
    assert totals["failed_requests"] == 0
    want = _uninterrupted(model, params, longs, 20, pol)
    for out, w in zip(outs, want):
        assert out == w, \
            "migrated sampled continuation diverged from unfaulted decode"
    migrated = [h for h in handles if h.gaps()]
    assert migrated, "no migrated request recorded its adoption gap"
    for h in migrated:
        assert len(h.itl()) + len(h.gaps()) == len(h.out_tokens) - 1


def test_spec_preempt_resume_token_exact(small_model):
    """Speculative mode composes with preemption: nd/accept/residual
    schedules are pure position functions, so a preempted+resumed spec
    request replays the uninterrupted spec stream exactly."""
    model, params = small_model
    rng = np.random.RandomState(47)
    pol = TemperatureSampling(temperature=0.8, seed=555)
    lows_p = [list(rng.randint(1, 200, size=16)) for _ in range(6)]
    highs_p = [list(rng.randint(1, 200, size=16)) for _ in range(2)]
    want_lo = _uninterrupted(model, params, lows_p, 48, pol, spec_k=2)
    want_hi = _uninterrupted(model, params, highs_p, 32, pol, spec_k=2)
    session = serving.serve(model, params,
                            _swap_config(model, spec_k=2))
    session.warm()
    lows = [session.submit(p, max_new_tokens=48, priority_class="lo",
                           sampling=pol) for p in lows_p]
    assert _wait_decoding(lows, 4)
    highs = [session.submit(p, max_new_tokens=32, priority_class="hi",
                            sampling=pol) for p in highs_p]
    for h in lows + highs:
        assert h.wait(timeout=300), "spec request hung under preemption"
    totals = session.stats()["totals"]
    session.close()
    assert totals["preemptions"] >= 1
    assert totals["draft_proposed"] > 0
    for h, want in zip(lows + highs, want_lo + want_hi):
        assert h.status == "done", (h.status, h.req.error)
        assert h.result() == want, \
            f"spec preempted decode diverged (preempt={h.preemptions})"


# ------------------------------------------- randomized (hypothesis)
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                                   # pragma: no cover
    pass
else:

    @settings(max_examples=4)
    @given(policy_kind=st.sampled_from(["temperature", "top_k", "top_p"]),
           seed=st.integers(0, 2**31 - 1),
           spec_k=st.sampled_from([0, 2]),
           n_lows=st.integers(2, 4),
           burst_at=st.integers(1, 3))
    def test_random_interrupted_equals_uninterrupted(
            small_model, policy_kind, seed, spec_k, n_lows, burst_at):
        """Property (pinned ``ci`` profile): for ANY sampling policy,
        seed, spec mode and preemption schedule, every interrupted
        request's stream equals its uninterrupted run, and close()
        leaves pool and arena empty."""
        model, params = small_model
        if policy_kind == "temperature":
            pol = TemperatureSampling(temperature=0.8, seed=seed)
        elif policy_kind == "top_k":
            pol = TopKSampling(k=20, temperature=0.9, seed=seed)
        else:
            pol = TopPSampling(p=0.9, temperature=0.9, seed=seed)
        rng = np.random.RandomState(seed % 1000)
        lows_p = [list(rng.randint(1, 200, size=16))
                  for _ in range(n_lows)]
        highs_p = [list(rng.randint(1, 200, size=16))]
        want = _uninterrupted(model, params, lows_p + highs_p, 24, pol,
                              spec_k=spec_k)
        session = serving.serve(model, params,
                                _swap_config(model, spec_k=spec_k))
        session.warm()
        lows = [session.submit(p, max_new_tokens=24, priority_class="lo",
                               sampling=pol) for p in lows_p]
        _wait_decoding(lows, min(burst_at, n_lows))
        highs = [session.submit(p, max_new_tokens=24,
                                priority_class="hi", sampling=pol)
                 for p in highs_p]
        for h in lows + highs:
            assert h.wait(timeout=300), "hung schedule"
        shard = session.engine.shards[0]
        session.close()
        for h, w in zip(lows + highs, want):
            assert h.status == "done", (h.status, h.req.error)
            assert h.result() == w
        assert shard.pool.free_count() == shard.config.num_pages
        assert shard.swap_arena.slots_used() == 0
