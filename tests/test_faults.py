"""Chaos injection, shard watchdog, live migration, and request deadlines
(DESIGN.md §14): the serving layer must honor the paper's bounded-damage
contract under *injected* faults — a stalled shard loses its router slot
and its sequences move (token-exact) to healthy shards, a crashed shard
fails its requests out with the traceback instead of hanging clients, a
slow device is NOT treated as a dead thread, and pool exhaustion requeues
admissions without wedging."""

import time

import jax
import numpy as np
import pytest

from repro import serving
from repro.configs import get_config
from repro.models import build_model
from repro.serving import (
    FaultSpec,
    Request,
    ServingConfig,
    fault_kinds,
    parse_fault,
)
from repro.serving.faults import build_fault_line
from repro.serving.policies import FifoAdmission, PriorityAdmission

from test_serving import _prompt_for_shard, _reference_greedy


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("tinyllama-1.1b").reduced().replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    return model, params


def _settle(session, timeout=10.0):
    """Wait until no shard is marked degraded (first-traffic jit compiles
    run INSIDE a step, so tight-heartbeat configs degrade every shard
    during warmup; recovery needs a watchdog tick after the compile)."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if not any(s.degraded for s in session.engine.shards):
            return
        time.sleep(0.02)
    raise AssertionError("shards never recovered after warmup")


def _warm_shards(session, rng):
    """One tiny request per shard: pays the jit compiles outside the
    assertions and advances each shard's ``n_completed`` to 1 — the
    ``after_done`` triggers below count from there."""
    router = session.engine.router
    for shard in range(router.num_shards):
        p = _prompt_for_shard(router, rng, shard, 10)
        session.submit(p, max_new_tokens=2).result(timeout=300)


# --------------------------------------------------------------- registry
def test_fault_registry_and_parse():
    kinds = fault_kinds()
    for kind in ("stall", "crash", "delay", "reader_stall", "pool_exhaust"):
        assert kind in kinds
    spec = parse_fault("stall:shard=1,after_done=4,duration_s=0.5")
    assert spec.kind == "stall" and spec.shard == 1
    assert spec.after_done == 4 and spec.duration_s == 0.5
    assert spec.at_step is None       # explicit trigger wins; no default
    # no trigger at all -> first beat
    assert parse_fault("crash").at_step == 0
    with pytest.raises(ValueError):
        parse_fault("meteor:shard=0")
    with pytest.raises(ValueError):
        parse_fault("stall:bogus=1")
    with pytest.raises(ValueError):
        parse_fault("stall:shard")
    with pytest.raises(ValueError):
        FaultSpec(kind="stall", duration_s=-1.0)


def test_build_fault_line_filters_by_shard():
    specs = (FaultSpec(kind="stall", shard=0, duration_s=0.1),
             "crash:shard=1,at_step=5")
    line0 = build_fault_line(specs, shard_id=0)
    line1 = build_fault_line(specs, shard_id=1)
    assert [inj.kind for inj in line0.injectors] == ["stall"]
    assert [inj.kind for inj in line1.injectors] == ["crash"]
    assert build_fault_line(specs, shard_id=2) is None
    assert build_fault_line(None, shard_id=0) is None


def test_config_normalizes_fault_strings():
    cfg = ServingConfig(smr="IBR", num_pages=16, page_size=4,
                        faults=("stall:shard=0,at_step=5,duration_s=0.1",))
    assert isinstance(cfg.faults[0], FaultSpec)
    assert cfg.summary()["faults"] == ("stall@0",)
    with pytest.raises(ValueError):
        ServingConfig(smr="IBR", num_pages=16, page_size=4,
                      faults=("meteor:shard=0",))
    with pytest.raises(ValueError):
        ServingConfig(smr="IBR", num_pages=16, page_size=4, watchdog="huh")
    with pytest.raises(ValueError):
        ServingConfig(smr="IBR", num_pages=16, page_size=4,
                      default_timeout_s=0.0)


# ------------------------------------------------------------ purge (unit)
class _Q:
    def __init__(self, rid, doomed=False, priority=0):
        self.rid, self.doomed, self.priority = rid, doomed, priority


@pytest.mark.parametrize("policy_cls", [FifoAdmission, PriorityAdmission])
def test_admission_purge_preserves_order(policy_cls):
    pol = policy_cls()
    q = pol.new_queue()
    reqs = [_Q(0), _Q(1, doomed=True), _Q(2), _Q(3, doomed=True), _Q(4)]
    for r in reqs:
        pol.push(q, r)
    purged = pol.purge(q, lambda r: r.doomed)
    assert sorted(r.rid for r in purged) == [1, 3]
    rest = []
    while True:
        r = pol.pop(q)
        if r is None:
            break
        rest.append(r.rid)
    assert rest == [0, 2, 4]
    assert pol.purge(pol.new_queue(), lambda r: True) == []


def test_priority_purge_keeps_heap_invariant():
    pol = PriorityAdmission()
    q = pol.new_queue()
    for r in (_Q(0, priority=1), _Q(1, doomed=True, priority=9),
              _Q(2, priority=5), _Q(3, priority=3)):
        pol.push(q, r)
    purged = pol.purge(q, lambda r: r.doomed)
    assert [r.rid for r in purged] == [1]
    assert [pol.pop(q).rid for _ in range(3)] == [2, 3, 0]


# ----------------------------------------------------------- crash guard
def test_crash_guard_fails_requests_traceback_and_pool_clean(small_model):
    """Satellite (a): an engine-loop crash fails every in-flight and queued
    request out with the traceback — no hung clients — and releases every
    page back to the pool."""
    model, params = small_model
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_pages=64, page_size=8, max_batch=4,
                      max_seq_len=64, watchdog="off",
                      faults=(FaultSpec(kind="crash", after_done=1),)))
    rng = np.random.RandomState(3)
    probe = session.submit(list(rng.randint(1, 200, size=8)),
                           max_new_tokens=2)
    victims = [session.submit(list(rng.randint(1, 200, size=8)),
                              max_new_tokens=24) for _ in range(3)]
    assert probe.result(timeout=300) is not None
    shard = session.engine.shards[0]
    for h in victims:
        assert h.wait(timeout=60), "crash guard left a client hanging"
        assert h.req.status == "failed"
        with pytest.raises(RuntimeError, match="InjectedFault"):
            h.result()
    assert shard.crashed
    assert "injected crash" in shard.error
    # the guard's own invariant, re-checked from outside: every page home
    assert shard.pool.free_count() == shard.config.num_pages
    assert session.stats()["totals"]["crashed_shards"] == 1
    # a crashed shard rejects new work with the crash cause up front
    with pytest.raises(RuntimeError, match="InjectedFault"):
        shard.submit(Request(prompt=list(rng.randint(1, 200, size=8)),
                             max_new_tokens=2))
    session.close()


# ----------------------------------------------- stall -> live migration
def test_stall_migrates_live_sequences_token_exact(small_model):
    """Tentpole: a stalled shard is degraded by heartbeat, its queued AND
    decode-active sequences move to the healthy shard through the SMR-safe
    handoff, and every output is token-for-token what an unfaulted run
    would have produced (replay-based migration + deterministic greedy)."""
    model, params = small_model
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_shards=2, num_pages=128, page_size=8,
                      max_batch=4, max_seq_len=64,
                      heartbeat_timeout_s=0.25, watchdog_interval_s=0.02,
                      faults=(FaultSpec(kind="stall", shard=0,
                                        after_done=2, duration_s=2.0),)))
    rng = np.random.RandomState(11)
    router = session.engine.router
    _warm_shards(session, rng)
    _settle(session)
    # trip wire: one short request on shard 0 completes (n_completed=2),
    # then the stall fires with the long requests still decoding
    short = session.submit(_prompt_for_shard(router, rng, 0, 10),
                           max_new_tokens=3)
    longs = [(_prompt_for_shard(router, rng, 0, 10), 20) for _ in range(2)]
    handles = [session.submit(p, max_new_tokens=n) for p, n in longs]
    assert short.result(timeout=300) is not None
    outs = [h.result(timeout=300) for h in handles]
    for (p, n), out in zip(longs, outs):
        assert out == _reference_greedy(model, params, p, n), \
            "migrated continuation diverged from the unfaulted decode"
    totals = session.stats()["totals"]
    assert totals["migrations"] >= 1, "stall never forced a migration"
    assert totals["failed"] == 0
    assert totals["heartbeat_misses"] >= 1
    # the stalled shard recovers once its loop beats again
    deadline = time.perf_counter() + 10.0
    while time.perf_counter() < deadline and \
            session.engine.shards[0].degraded:
        time.sleep(0.02)
    assert not session.engine.shards[0].degraded, "shard 0 never recovered"
    session.close()


def test_degraded_shard_loses_router_slot_then_rejoins(small_model):
    """watchdog="observe": degradation re-routes NEW prompts away from the
    stalled shard (no migration), and recovery restores its placement."""
    model, params = small_model
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_shards=2, num_pages=128, page_size=8,
                      max_batch=4, max_seq_len=64, watchdog="observe",
                      heartbeat_timeout_s=0.2, watchdog_interval_s=0.02,
                      faults=(FaultSpec(kind="stall", shard=0,
                                        after_done=2, duration_s=1.5),)))
    rng = np.random.RandomState(17)
    router = session.engine.router
    _warm_shards(session, rng)
    _settle(session)
    trip = session.submit(_prompt_for_shard(router, rng, 0, 10),
                          max_new_tokens=2)
    assert trip.result(timeout=300) is not None     # n_completed=2 -> stall
    deadline = time.perf_counter() + 10.0
    while time.perf_counter() < deadline and \
            not session.engine.shards[0].degraded:
        time.sleep(0.01)
    assert session.engine.shards[0].degraded, "stall never degraded shard 0"
    # a shard-0 prompt lands on shard 1 while 0 is out of the rotation
    rerouted = session.submit(_prompt_for_shard(router, rng, 0, 10),
                              max_new_tokens=3)
    assert rerouted.shard == 1
    assert rerouted.result(timeout=300) is not None
    _settle(session)                                # stall over: rejoined
    back = session.submit(_prompt_for_shard(router, rng, 0, 10),
                          max_new_tokens=3)
    assert back.shard == 0
    assert back.result(timeout=300) is not None
    session.close()


# ------------------------------------------------------- delay is benign
def test_delay_fault_slows_but_never_degrades(small_model):
    """A slow device is not a dead thread: per-dispatch delays inside the
    window must not cost the shard its router slot (the generous default
    heartbeat exists exactly for this)."""
    model, params = small_model
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_pages=64, page_size=8, max_batch=4,
                      max_seq_len=64,
                      faults=(FaultSpec(kind="delay", after_done=1,
                                        delay_s=0.01, duration_s=0.5,
                                        seed=5),)))
    rng = np.random.RandomState(23)
    prompts = [list(rng.randint(1, 200, size=9)) for _ in range(3)]
    handles = [session.submit(p, max_new_tokens=5) for p in prompts]
    outs = [h.result(timeout=300) for h in handles]
    for p, out in zip(prompts, outs):
        assert out == _reference_greedy(model, params, p, 5)
    totals = session.stats()["totals"]
    assert totals["degraded_steps"] == 0
    assert totals["heartbeat_misses"] == 0
    assert totals["migrations"] == 0
    session.close()


# ------------------------------------------------- pool exhaustion window
def test_pool_exhaust_requeues_then_recovers(small_model):
    """Admission under a fully-claimed pool requeues (bounded damage),
    then drains normally when the pages come back — no wedge, no leak."""
    model, params = small_model
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_pages=32, page_size=8, max_batch=2,
                      max_seq_len=64, watchdog="off",
                      faults=(FaultSpec(kind="pool_exhaust", after_done=1,
                                        duration_s=0.6),)))
    rng = np.random.RandomState(29)
    probe = session.submit(list(rng.randint(1, 200, size=8)),
                           max_new_tokens=2)
    assert probe.result(timeout=300) is not None    # arms the window
    time.sleep(0.05)                                 # pool now drained
    prompts = [list(rng.randint(1, 200, size=8)) for _ in range(2)]
    handles = [session.submit(p, max_new_tokens=4) for p in prompts]
    outs = [h.result(timeout=300) for h in handles]
    for p, out in zip(prompts, outs):
        assert out == _reference_greedy(model, params, p, 4)
    session.close()


# ------------------------------------------------------------- deadlines
def test_deadline_expires_through_cancel_path(small_model):
    """Satellite (b): a request whose deadline passes while it is stuck
    behind a stalled shard is cancelled through the normal cancel path —
    terminal status "cancelled", error says deadline — and the shard keeps
    serving fresh work afterwards."""
    model, params = small_model
    session = serving.serve(
        model, params,
        ServingConfig(smr="IBR", num_pages=64, page_size=8, max_batch=4,
                      max_seq_len=64, watchdog="off",
                      default_timeout_s=0.4,
                      faults=(FaultSpec(kind="stall", after_done=1,
                                        duration_s=1.5),)))
    rng = np.random.RandomState(31)
    probe = session.submit(list(rng.randint(1, 200, size=8)),
                           max_new_tokens=2)
    assert probe.result(timeout=300) is not None    # next beat stalls 1.5s
    # explicit per-request deadline and the config default both expire
    # inside the stall window; the no-deadline control must survive it
    doomed = session.submit(list(rng.randint(1, 200, size=8)),
                            max_new_tokens=4, timeout_s=0.2)
    doomed_default = session.submit(list(rng.randint(1, 200, size=8)),
                                    max_new_tokens=4)
    control = session.submit(list(rng.randint(1, 200, size=8)),
                             max_new_tokens=4, timeout_s=60.0)
    for h in (doomed, doomed_default):
        assert h.wait(timeout=300), "expired request never went terminal"
        assert h.req.status == "cancelled"
        assert "deadline" in (h.req.error or "")
        assert h.result() == []     # cancel semantics: tokens-so-far
    assert control.result(timeout=300) is not None
    assert session.stats()["totals"]["cancelled"] >= 2
    # deadline is stamped at submit: an expired-at-admission request is
    # swept before it ever costs a page
    session.close()
