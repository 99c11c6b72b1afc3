"""The comparison that decides ``correct`` has to fail what it guards
against.  At a size the CPU holds:

* the control — the reference computed in float8 in the program's place —
  reads a wider gap than the sound program, by the margin the limit needs,
  and, put through a whole run's comparison, comes out not correct;
* a run with the timed path broken underneath comes out not correct,
  through the whole harness path with the chip check skipped: once with
  the served tokens altered where they are produced (the sampler's output
  shifted by one id), once with a decode step that returns the KV pool as
  it got it (the step's state left unchanged).
"""

import pytest

from perfbench import check, harness, run
from perfbench.tests.tiny import LIMITS, tiny_cell

SEED = 2 ** 31 + 7


def serve_window(cell, seed):
    session, _params = harness.open_session(cell, seed, backend="xla")
    try:
        sched = cell.schedule(seed, 2.0)
        if sched.prefixes:
            harness.prime_prefixes(session, sched, 60)
        rec = harness.drive(session, sched, 2.0, 60)
    finally:
        session.close()
    return rec["requests"]


@pytest.mark.parametrize("seed", [SEED, 11, 12])
def test_control_reads_wider_than_the_program(seed):
    cell = tiny_cell("qwen3-8b-l12.prefix")
    sample = check.choose_sample(serve_window(cell, seed), seed, 64)
    program = max(check.gaps(cell.dims, seed, sample))
    control = max(check.gaps(cell.dims, seed, sample, control=True))
    assert control > 3 * max(program, 1e-2)


def test_control_run_is_not_correct():
    cell = tiny_cell("qwen3-8b-l12.decode")
    line = run.execute(cell, LIMITS, SEED, 2.0, 0, 1, [], backend="xla",
                       control=True)
    assert line["correct"] is False
    assert line["checks"]["logit_gap_max"]["value"] > \
        LIMITS["logit_gap_max"]["limit"]


def _shift_tokens(monkeypatch):
    from repro.kernels import ops
    sample_tokens = ops.sample_tokens

    def shifted(logits, *a, **k):
        toks, lps = sample_tokens(logits, *a, **k)
        return (toks + 1) % logits.shape[-1], lps

    monkeypatch.setattr(ops, "sample_tokens", shifted)


def _drop_kv_writes(monkeypatch):
    from repro.serving.engine import _ShardEngine
    step = _ShardEngine._paged_decode_step

    def stale(self, params, k_pages, v_pages, *a):
        toks, lps, _, _ = step(self, params, k_pages, v_pages, *a)
        return toks, lps, k_pages, v_pages

    monkeypatch.setattr(_ShardEngine, "_paged_decode_step", stale)


@pytest.mark.parametrize("fault", [_shift_tokens, _drop_kv_writes])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    cell = tiny_cell("qwen3-8b-l12.decode")
    line = run.execute(cell, LIMITS, SEED, 2.0, 0, 1, [], backend="xla")
    assert line["correct"] is False
    assert line["checks"]["logit_gap_max"]["value"] > 0.5
