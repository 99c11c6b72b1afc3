"""One CPU rehearsal of each cell's path through the harness, at a tiny
size, and the files-only extension points."""

import json

import pytest

from perfbench import harness, run
from perfbench.tests.tiny import CELLS, LIMITS, tiny_cell

SEED = 2 ** 31 + 99


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    cell = tiny_cell(name)
    line = run.execute(cell, LIMITS, SEED, 2.0, 0, 1, [], backend="xla")
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    reported = set(line["metrics"])
    assert reported == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["checks"]["checked_tokens"]["value"] >= 24
    assert {"itl_p95_ms", "setup_s"} <= reported
    assert ("tokens_per_s" in reported) == (cell.mix["kind"] == "closed")
    assert "ttft_p50_ms" not in reported or cell.mix["kind"] == "open"


def test_every_named_file_exists():
    bench = json.loads((harness.BENCH).read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert cell.end_to_end and cell.per_layer
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_a_mix_and_a_metric_are_added_by_files_alone(tmp_path):
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (root / sub).mkdir(parents=True)
    src = harness.HERE
    (root / "configs" / "qwen3-8b-l12.json").write_text(
        (src / "configs" / "qwen3-8b-l12.json").read_text())
    (root / "traffic" / "burst.json").write_text(json.dumps({
        "kind": "open", "rate_per_s": 3.0,
        "prompt": {"dist": "uniform", "min": 8, "max": 16},
        "answer": {"dist": "uniform", "min": 2, "max": 4},
        "warm_seconds": 1, "warm_drain_s": 1, "grace_s": 5}))
    # a mix whose arrivals come from a generator of its own: every request
    # at the window's start
    (root / "traffic" / "flood.json").write_text(json.dumps({
        "kind": "open", "generator": "all_at_once", "count": 5,
        "warm_seconds": 1, "warm_drain_s": 1, "grace_s": 5}))
    (root / "traffic" / "all_at_once.py").write_text(
        "from perfbench.traffic import Schedule, Spec\n\n\n"
        "def make_schedule(mix, seed, seconds, vocab):\n"
        "    return Schedule('open', [Spec([seed % vocab + 1] * 8, 2, 0.0)\n"
        "                             for _ in range(mix['count'])])\n")
    (root / "metrics" / "sent.count.py").write_text(
        "def read(run):\n    return float(len(run.requests))\n")
    bench = {"workloads": [{"name": "qwen3-8b-l12.burst",
                            "config": "qwen3-8b-l12", "traffic": "burst",
                            "chips": 1, "why": "x"},
                           {"name": "qwen3-8b-l12.flood",
                            "config": "qwen3-8b-l12", "traffic": "flood",
                            "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "itl_p95_ms", "unit": "ms"}],
             "per_layer": [{"name": "sent.count", "unit": "requests",
                            "workloads": ["qwen3-8b-l12.burst"]}]}
    cell = harness.load_cell("qwen3-8b-l12.burst", bench, root=root)
    assert cell.mix["rate_per_s"] == 3.0
    assert len(cell.schedule(SEED, 2.0).specs) == 6
    assert [m["name"] for m in cell.per_layer] == ["sent.count"]
    read = harness.load_reader("sent.count", root / "metrics")
    fake = harness.Run(window_s=1.0, requests=[1, 2, 3], before={},
                       after={}, samples=[], dims=cell.dims)
    assert read(fake) == 3.0
    flood = harness.load_cell("qwen3-8b-l12.flood", bench, root=root)
    specs = flood.schedule(SEED, 2.0).specs
    assert [(s.due_s, s.max_new) for s in specs] == [(0.0, 2)] * 5
