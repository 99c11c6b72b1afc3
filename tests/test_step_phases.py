"""The engine step loop's phase clock: host seconds per phase and the TTFT
split in ``stats()``, the ``engine.*`` profiler spans they are taken at,
and the benchmark readers that turn the counters into per-layer metrics."""

import glob
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import serving
from repro.configs import get_config
from repro.models import build_model
from repro.serving import ServingConfig
from repro.serving.phases import PHASES

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import harness  # noqa: E402

COUNTERS = [f"host_s_{p}" for p in PHASES] + [
    "prefill_wait_s", "prefill_s", "first_tokens"]


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("tinyllama-1.1b").reduced().replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    return model, params


def _serve(model, params, n=6, **overrides):
    """Serve ``n`` prompts of mixed lengths; returns (handles, totals, wall
    seconds from before the session opened to after the last result)."""
    t0 = time.perf_counter()
    session = serving.serve(model, params, ServingConfig(
        smr="IBR", num_pages=128, page_size=8, max_batch=4, max_seq_len=96,
        prefill_chunk_tokens=16, **overrides))
    rng = np.random.RandomState(3)
    handles = [session.submit(
        list(rng.randint(1, 200, size=int(rng.randint(5, 40)))),
        max_new_tokens=5) for _ in range(n)]
    for h in handles:
        h.result(timeout=300)
    totals = session.stats()["totals"]
    wall = time.perf_counter() - t0
    session.close()
    return handles, totals, wall


@pytest.mark.parametrize("overrides", [
    {"scheduler": "packed"}, {"scheduler": "chunked"},
    {"scheduler": "packed", "spec_k": 2}],
    ids=["packed", "chunked", "spec"])
def test_totals_hold_phase_seconds_and_the_ttft_split(small_model,
                                                      overrides):
    model, params = small_model
    handles, totals, wall = _serve(model, params, **overrides)
    assert set(COUNTERS) <= set(totals)
    assert totals["first_tokens"] == len(handles)
    ttft = sum(h.req.out_times[0] - h.req.t_submit for h in handles)
    assert totals["prefill_wait_s"] + totals["prefill_s"] == \
        pytest.approx(ttft, abs=1e-9)
    assert totals["prefill_wait_s"] >= 0 and totals["prefill_s"] > 0
    phases = [totals[f"host_s_{p}"] for p in PHASES]
    assert all(s >= 0 for s in phases)
    for p in ("admit", "plan", "dispatch", "sync", "emit"):
        assert totals[f"host_s_{p}"] > 0, p
    assert sum(phases) <= wall


def _host_spans(log_dir):
    """``{thread: [(name, start, end, stats)]}`` of every ``engine.*``
    host span in the trace under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.setdefault(line.name, []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def _parent(spans, span):
    """The shortest other span on the same thread that covers ``span``."""
    _, a, b, _ = span
    covers = [s for s in spans if s is not span and s[1] <= a and b <= s[2]]
    return min(covers, key=lambda s: s[2] - s[1])[0] if covers else None


def test_engine_spans_nest_in_the_profiler_trace(small_model, tmp_path):
    model, params = small_model
    with jax.profiler.trace(str(tmp_path)):
        handles, _, _ = _serve(model, params, n=4, scheduler="packed")
    threads = _host_spans(str(tmp_path))
    assert len(threads) == 1, "one shard, one engine thread"
    spans = sorted(next(iter(threads.values())), key=lambda s: s[1])
    names = {s[0] for s in spans}
    assert {"engine.step", "engine.admit", "engine.admit.one",
            "engine.plan", "engine.dispatch", "engine.sync",
            "engine.sync.wait", "engine.sync.copy", "engine.emit"} <= names
    parents = {"engine.admit.one": {"engine.admit"},
               "engine.sync.wait": {"engine.sync"},
               "engine.sync.copy": {"engine.sync"},
               "engine.plan": {"engine.step"},
               "engine.dispatch": {"engine.step"},
               "engine.sync": {"engine.step"},
               "engine.emit": {"engine.step"},
               # an iteration with nothing queued admits outside a step
               "engine.admit": {"engine.step", None},
               "engine.idle": {None},
               "engine.step": {None}}
    for span in spans:
        assert _parent(spans, span) in parents[span[0]], span
    ids = [s[3]["req_id"] for s in spans if s[0] == "engine.admit.one"]
    assert sorted(ids) == sorted(h.req.req_id for h in handles)
    steps = [s[3]["step"] for s in spans if s[0] == "engine.step"]
    assert steps == sorted(set(steps))
    # the counted phases never overlap one another
    phased = [s for s in spans if s[0] in {f"engine.{p}" for p in PHASES}]
    assert all(a[2] <= b[1] for a, b in zip(phased, phased[1:]))


# ------------------------------------------------------------- the readers

BEFORE = {"steps": 10, "first_tokens": 2, "prefill_wait_s": 1.0,
          "prefill_s": 2.0, "sampler_calls": 12, "sampler_argmax_calls": 9,
          **{f"host_s_{p}": 1.0 for p in PHASES}}
AFTER = {"steps": 60, "first_tokens": 6, "prefill_wait_s": 3.0,
         "prefill_s": 2.6, "host_s_idle": 2.5, "host_s_admit": 1.05,
         "host_s_plan": 1.1, "host_s_dispatch": 1.5, "host_s_sync": 5.0,
         "host_s_emit": 1.2, "sampler_calls": 52,
         "sampler_argmax_calls": 39}
WINDOW_S = 10.0


@pytest.mark.parametrize("metric,value,denominator", [
    ("host_ms_per_step.admit", 1.0, "steps"),
    ("host_ms_per_step.plan", 2.0, "steps"),
    ("host_ms_per_step.dispatch", 10.0, "steps"),
    ("host_ms_per_step.sync", 80.0, "steps"),
    ("host_ms_per_step.emit", 4.0, "steps"),
    ("engine_idle_share", 15.0, None),
    ("prefill_wait_ms.mean", 500.0, "first_tokens"),
    ("prefill_ms.mean", 150.0, "first_tokens"),
    ("sampler_argmax_share", 75.0, "sampler_calls")])
def test_phase_readers(metric, value, denominator):
    read = harness.load_reader(metric)
    dims = harness.load_cell("qwen3-8b-l12.decode").dims

    def run(before, after, window_s=WINDOW_S):
        return harness.Run(window_s=window_s, requests=[], before=before,
                           after=after, samples=[], dims=dims)

    assert read(run(BEFORE, AFTER)) == pytest.approx(value)
    if denominator:             # the counter it divides by did not move
        assert read(run(BEFORE, {**AFTER, denominator:
                                 BEFORE[denominator]})) is None
    else:
        assert read(run(BEFORE, AFTER, window_s=0.0)) is None
    # a program without the counters (the window reads nothing)
    assert read(run({"steps": 1}, {"steps": 9})) is None
