"""Host swap tier + priority preemption (DESIGN.md §15).

Four layers, bottom up:

1. :class:`SwapArena` unit contracts — store/load roundtrip, CRC-32
   validation, all-or-nothing admission on a full arena, idempotent
   release, alignment validation.
2. ``BlockPool.import_claim`` hardening (the handoff validation the swap
   tier's ordering argument leans on): a foreign or unpinned page is a
   protocol violation, not a silent pass.
3. Config surface: the ``swap`` eviction policy requires a host arena,
   priority classes parse/validate, unknown classes fail at submit.
4. Engine end-to-end: preemption under pressure is BIT-IDENTICAL — a
   preempted-and-resumed sequence emits exactly the tokens the
   uncontended reference decode emits; TTFT SLOs cancel waiting requests
   that cannot be rescued; the ``pool_exhaust`` chaos fault composes with
   the swap tier (preemption rescues the high-priority request that the
   no-swap config must cancel); and a randomized preempt/resume property
   (pinned ``ci`` hypothesis profile) checks token-exactness plus
   zero page / zero arena-slot leaks after ``close()``.
"""

import time

import jax
import numpy as np
import pytest

from repro import api, serving
from repro.configs import get_config
from repro.models import build_model
from repro.runtime.block_pool import BlockPool
from repro.runtime.swap import (
    SwapArena,
    SwapArenaFullError,
    SwapChecksumError,
    page_nbytes,
)
from repro.serving import FaultSpec, ServingConfig, parse_priority_class

from test_serving import _reference_greedy

# tiny arena geometry for the unit layer (matches nothing on purpose —
# the arena is model-agnostic)
_AKW = dict(n_layers=2, page_size=4, n_kv_heads=2, head_dim=4,
            dtype="float32")
_PAGE_SHAPE = (2, 4, 2, 4)     # (L, page_size, kv, dh)


def _pages(rng, n):
    k = rng.standard_normal((n,) + _PAGE_SHAPE).astype(np.float32)
    v = rng.standard_normal((n,) + _PAGE_SHAPE).astype(np.float32)
    return k, v


def _arena(slots):
    return SwapArena(slots * page_nbytes(**_AKW), **_AKW)


# ===================================================== 1. SwapArena unit
def test_page_nbytes():
    # 2 planes * L * page * kv * dh * 4B
    assert page_nbytes(**_AKW) == 2 * 2 * 4 * 2 * 4 * 4
    assert page_nbytes(2, 4, 2, 4, "float16") == page_nbytes(**_AKW) // 2


def test_arena_too_small_for_one_page():
    with pytest.raises(ValueError, match="holds no page"):
        SwapArena(page_nbytes(**_AKW) - 1, **_AKW)


def test_store_load_roundtrip():
    arena = _arena(8)
    rng = np.random.default_rng(0)
    k, v = _pages(rng, 3)
    man = arena.store(7, k, v, n_tokens=12)
    assert man.n_pages == 3 and man.n_tokens == 12
    assert arena.slots_used() == 3
    assert arena.bytes_used() == 3 * arena.slot_nbytes
    kk, vv = arena.load(7)
    np.testing.assert_array_equal(kk, k)
    np.testing.assert_array_equal(vv, v)
    # from_page slicing: pages before the offset were re-covered by a
    # fresh prefix-cache hit and are not reloaded
    kk, vv = arena.load(7, from_page=2)
    np.testing.assert_array_equal(kk, k[2:])
    np.testing.assert_array_equal(vv, v[2:])
    # load leaves the slots allocated (copy-before-free): only release
    # frees them
    assert arena.slots_used() == 3
    assert arena.release(7) is True
    assert arena.slots_used() == 0
    st = arena.stats()
    assert st["swapped_out"] == 3 and st["swapped_in"] == 3 + 1
    assert st["checksum_failures"] == 0 and st["sequences"] == 0


def test_store_is_all_or_nothing_when_full():
    arena = _arena(4)
    rng = np.random.default_rng(1)
    k, v = _pages(rng, 3)
    arena.store(1, k, v, n_tokens=12)
    with pytest.raises(SwapArenaFullError):
        arena.store(2, *_pages(rng, 2), n_tokens=8)
    # nothing leaked: the failed store claimed no slots, no manifest
    assert arena.slots_used() == 3
    assert arena.manifest(2) is None
    # one page still fits
    arena.store(3, *_pages(rng, 1), n_tokens=4)
    assert arena.slots_used() == 4


def test_checksum_corruption_detected():
    arena = _arena(4)
    rng = np.random.default_rng(2)
    k, v = _pages(rng, 2)
    man = arena.store(5, k, v, n_tokens=8)
    arena._k[man.slots[1]][0, 0, 0, 0] += 1.0     # flip one host byte
    with pytest.raises(SwapChecksumError, match="page 1"):
        arena.load(5)
    assert arena.stats()["checksum_failures"] == 1
    # release still works: corruption poisons the data, not the slots
    assert arena.release(5) is True
    assert arena.slots_used() == 0


def test_release_is_idempotent():
    arena = _arena(4)
    rng = np.random.default_rng(3)
    arena.store(9, *_pages(rng, 2), n_tokens=8)
    assert arena.release(9) is True
    assert arena.release(9) is False
    assert arena.release(12345) is False


def test_misaligned_tokens_rejected():
    arena = _arena(4)
    rng = np.random.default_rng(4)
    k, v = _pages(rng, 2)
    with pytest.raises(ValueError, match="page-aligned"):
        arena.store(1, k, v, n_tokens=7)          # not a multiple of 4
    with pytest.raises(ValueError, match="page-aligned"):
        arena.store(1, k, v, n_tokens=12)         # > 2 pages' worth
    assert arena.slots_used() == 0


def test_duplicate_manifest_rejected():
    arena = _arena(8)
    rng = np.random.default_rng(5)
    arena.store(4, *_pages(rng, 1), n_tokens=4)
    with pytest.raises(ValueError, match="already has a manifest"):
        arena.store(4, *_pages(rng, 1), n_tokens=4)
    with pytest.raises(KeyError):
        arena.load(99)


# =================================== 2. import_claim hardening (pool)
def _pool(num_pages=8):
    smr = api.scheme("IBR", retire_scan_freq=4, epoch_freq=4)
    return BlockPool(smr, num_pages)


def test_import_claim_rejects_foreign_page():
    pool_a, pool_b = _pool(), _pool()
    pg = pool_b.alloc(0)
    pool_b.pin(pg)
    with pytest.raises(ValueError, match="belongs to pool"):
        pool_a.import_claim([pg])


def test_import_claim_rejects_unpinned_page():
    pool = _pool()
    pg = pool.alloc(0)
    assert pg.pin_count.load() == 0
    with pytest.raises(ValueError, match="pin_count"):
        pool.import_claim([pg])


def test_import_claim_accepts_pinned_own_page():
    pool = _pool()
    pg = pool.alloc(0)
    pool.pin(pg)
    pool.import_claim([pg])                        # no raise


# ======================================================= 3. config layer
def test_swap_eviction_requires_arena_bytes():
    with pytest.raises(ValueError, match="swap_bytes"):
        ServingConfig(eviction="swap")
    ServingConfig(eviction="swap", swap_bytes=1 << 20)   # fine


def test_swap_in_eviction_registry():
    assert "swap" in api.eviction_policies()


def test_parse_priority_class():
    c = parse_priority_class("interactive:priority=10,ttft_slo_s=2.5")
    assert (c.name, c.priority, c.ttft_slo_s) == ("interactive", 10, 2.5)
    assert c.itl_slo_s is None
    assert parse_priority_class("batch").priority == 0
    with pytest.raises(ValueError, match="unknown priority-class field"):
        parse_priority_class("x:nope=1")
    with pytest.raises(ValueError, match="ttft_slo_s"):
        parse_priority_class("x:ttft_slo_s=0")


def test_duplicate_class_names_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        ServingConfig(priority_classes=("hi:priority=1", "hi:priority=2"))


def test_unknown_class_resolution_fails():
    cfg = ServingConfig(priority_classes=("hi:priority=1",))
    assert cfg.priority_class("hi").priority == 1
    with pytest.raises(ValueError, match="unknown priority class"):
        cfg.priority_class("nope")


# ================================================ 4. engine end-to-end
@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("tinyllama-1.1b").reduced().replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    return model, params


_REF = {}


def _ref(model, params, prompt, n_new):
    key = (tuple(prompt), n_new)
    if key not in _REF:
        _REF[key] = _reference_greedy(model, params, prompt, n_new)
    return _REF[key]


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


def test_preempt_resume_token_exact(small_model):
    """The ISSUE's core acceptance: under pressure a high-priority
    arrival preempts low-priority decoders into the host arena, the
    victims park as ``swapped``, resume, and every request's output is
    bit-identical to the uncontended reference decode."""
    model, params = small_model
    rng = np.random.RandomState(42)
    # 4 lows of 8 pages each fill the 32-page pool AND the 4-slot batch
    lows_p = [list(rng.randint(1, 200, size=16)) for _ in range(6)]
    highs_p = [list(rng.randint(1, 200, size=16)) for _ in range(2)]
    session = serving.serve(model, params, _swap_config(model))
    session.warm()
    lows = [session.submit(p, max_new_tokens=48, priority_class="lo")
            for p in lows_p]
    assert _wait_decoding(lows, 4), "lows never saturated the batch"
    highs = [session.submit(p, max_new_tokens=32, priority_class="hi")
             for p in highs_p]
    # the parked state is externally visible while the highs decode
    saw_swapped = False
    deadline = time.perf_counter() + 30.0
    while time.perf_counter() < deadline and not saw_swapped:
        saw_swapped = any(h.status == "swapped" for h in lows)
        time.sleep(0.0005)
    for h in lows + highs:
        assert h.wait(timeout=300), "request hung under preemption"
    shard = session.engine.shards[0]
    totals = session.stats()["totals"]
    session.close()
    assert saw_swapped, "no low was ever observed in 'swapped' status"
    assert totals["preemptions"] >= 1 and totals["resumed"] >= 1
    assert totals["swapped_out"] >= 1 and totals["swapped_in"] >= 0
    assert sum(h.preemptions for h in lows) >= 1
    assert all(h.preemptions == 0 for h in highs)
    for p, h in zip(lows_p + highs_p, lows + highs):
        n_new = 48 if h in lows else 32
        assert h.status == "done", (h.status, h.req.error)
        assert h.result() == _ref(model, params, p, n_new), \
            f"preempted decode diverged (preemptions={h.preemptions})"
    # nothing leaks: every device page home, every arena slot free
    assert shard.pool.free_count() == shard.config.num_pages
    assert shard.swap_arena.slots_used() == 0
    assert shard.swap_arena.stats()["sequences"] == 0


def test_ttft_slo_cancels_unrescuable_waiting(small_model):
    """Without the swap tier there is no rescue: a high-priority request
    behind a full pool of long decoders blows its TTFT SLO and is
    cancelled through the normal cancel path (counted in
    ``slo_cancelled``), instead of silently waiting forever."""
    model, params = small_model
    rng = np.random.RandomState(43)
    # 2 lows * 27 pages = the whole 54-page pool; 200-step decodes hold
    # it far longer than the SLO on any box
    config = ServingConfig(
        smr="IBR", num_pages=54, page_size=8, max_batch=2,
        max_seq_len=256, admission="priority", eviction="pressure",
        priority_classes=("hi:priority=10,ttft_slo_s=0.025",
                          "lo:priority=0"))
    session = serving.serve(model, params, config)
    session.warm()
    lows = [session.submit(list(rng.randint(1, 200, size=16)),
                           max_new_tokens=200, priority_class="lo")
            for _ in range(2)]
    assert _wait_decoding(lows, 2)
    hi = session.submit(list(rng.randint(1, 200, size=16)),
                        max_new_tokens=8, priority_class="hi")
    assert hi.wait(timeout=60), "SLO expiry never fired"
    assert hi.status == "cancelled", hi.status
    assert "TTFT SLO exceeded" in (hi.req.error or "")
    totals = session.stats()["totals"]
    assert totals["slo_cancelled"] >= 1
    assert totals["preemptions"] == 0          # no arena, no rescue
    for h in lows:                             # don't wait out 200 steps
        h.cancel()
        h.wait(timeout=60)
    session.close()


def test_pool_exhaust_chaos_composes_with_swap(small_model):
    """Satellite: the ``pool_exhaust`` chaos fault composes with the swap
    tier.  The fault grabs every free page for 3s.  Without swap a
    high-priority request with a TTFT SLO has no rescue path and is
    cancelled; with swap it preempts an active low-priority decoder and
    completes inside the SLO — zero failed, zero cancelled — and the
    preempted victim still finishes token-exact."""
    model, params = small_model
    rng = np.random.RandomState(44)
    low_p = list(rng.randint(1, 200, size=16))
    ctl_p = list(rng.randint(1, 200, size=16))
    hi_p = list(rng.randint(1, 200, size=16))
    classes = ("hi:priority=10,ttft_slo_s=0.75", "lo:priority=0")
    # fires after the control request completes, holding every free page
    # for 3s — far past the high's 0.75s TTFT SLO on any box
    fault = FaultSpec(kind="pool_exhaust", shard=0, after_done=1,
                      duration_s=3.0)

    def _run(eviction, swap_bytes, with_low):
        session = serving.serve(model, params, ServingConfig(
            smr="IBR", num_pages=32, page_size=8, max_batch=4,
            max_seq_len=128, admission="priority", eviction=eviction,
            swap_bytes=swap_bytes, priority_classes=classes,
            faults=(fault,)))
        session.warm()
        low = None
        if with_low:
            # one long low holds 8 pages — the preemption victim
            low = session.submit(low_p, max_new_tokens=48,
                                 priority_class="lo")
            assert _wait_decoding([low], 1)
        # completing the control request trips the fault.  Wait on the
        # injector's fired flag, NOT free_count()==0: pages the control
        # released may sit in SMR retire limbo during the grab and come
        # back free after it — at most ~3, which cannot cover the high's
        # 7-page need, so the scenario is unchanged.
        session.submit(ctl_p, max_new_tokens=2,
                       priority_class="lo").result(timeout=300)
        shard = session.engine.shards[0]
        t0 = time.perf_counter()
        while not all(inj.fired for inj in shard.fault_line.injectors):
            assert time.perf_counter() - t0 < 30, "fault never fired"
            time.sleep(0.002)
        # 7 pages: more than pressure-evicting the control request's
        # cached prefix can ever free, so only preemption can rescue it
        hi = session.submit(hi_p, max_new_tokens=40,
                            priority_class="hi")
        assert hi.wait(timeout=60)
        if low is not None:
            low.wait(timeout=300)
        totals = session.stats()["totals"]
        session.close()
        return hi, low, totals

    # WITHOUT swap there is no rescue path at all: whether or not
    # victims exist, waiting out the fault window is the only option,
    # and the SLO expires first.  (No low here: a completing low would
    # hand its pages to the high and make the outcome a wall-clock race
    # instead of a property.)
    hi, _, totals = _run("pressure", 0, with_low=False)
    assert hi.status == "cancelled", (hi.status, hi.req.error)
    assert "TTFT SLO exceeded" in (hi.req.error or "")
    assert totals["preemptions"] == 0
    assert totals["slo_cancelled"] >= 1

    # WITH swap the active low IS the rescue: preempted to the arena,
    # its pages serve the high inside the SLO, and it still finishes
    # bit-identically after the fault lets go.
    hi, low, totals = _run("swap", _arena_bytes(model), with_low=True)
    assert hi.status == "done", (hi.status, hi.req.error)
    assert hi.result() == _ref(model, params, hi_p, 40)
    assert totals["preemptions"] >= 1
    assert totals["failed"] == 0
    assert low.status == "done", (low.status, low.req.error)
    assert low.result() == _ref(model, params, low_p, 48)


# ------------------------------------------- randomized (hypothesis)
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                                   # pragma: no cover
    pass
else:

    @settings(max_examples=5)
    @given(n_lows=st.integers(2, 5),
           low_new=st.sampled_from([24, 40, 48]),
           n_highs=st.integers(1, 3),
           burst_at=st.integers(1, 4),
           salt=st.integers(0, 3))
    def test_random_preempt_resume_schedules(small_model, n_lows,
                                             low_new, n_highs, burst_at,
                                             salt):
        """Property (pinned ``ci`` profile: derandomized, bounded): for
        ANY schedule — low-priority fleet size, decode lengths, burst
        size and burst timing — every completed request is token-exact
        against the unpreempted reference, and after ``close()`` the
        device pool and the host arena are both empty."""
        model, params = small_model
        rng = np.random.RandomState(500 + salt)
        lows_p = [list(rng.randint(1, 200, size=16))
                  for _ in range(n_lows)]
        highs_p = [list(rng.randint(1, 200, size=16))
                   for _ in range(n_highs)]
        session = serving.serve(model, params, _swap_config(model))
        session.warm()
        lows = [session.submit(p, max_new_tokens=low_new,
                               priority_class="lo") for p in lows_p]
        _wait_decoding(lows, min(burst_at, n_lows))
        highs = [session.submit(p, max_new_tokens=8,
                                priority_class="hi") for p in highs_p]
        for h in lows + highs:
            assert h.wait(timeout=300), "hung schedule"
        shard = session.engine.shards[0]
        session.close()
        for p, h in zip(lows_p + highs_p, lows + highs):
            n_new = low_new if h in lows else 8
            assert h.status == "done", (h.status, h.req.error)
            assert h.result() == _ref(model, params, p, n_new)
        assert shard.pool.free_count() == shard.config.num_pages
        assert shard.swap_arena.slots_used() == 0
        assert shard.swap_arena.stats()["sequences"] == 0
