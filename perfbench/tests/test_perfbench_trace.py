"""The trace reduction against hand counts, on a hand-made trace and on a
small excerpt recorded from a chip run (``data/trace_excerpt.json``)."""

import json
from pathlib import Path

import pytest

from perfbench import trace

MS = 1_000_000          # nanoseconds


def hand_trace():
    # window [0, 100 ms); ops overlap, one starts before the window and one
    # runs past its end
    return {
        "window": [0, 100 * MS], "devices": 1,
        "device": [
            ["fusion.1", -5 * MS, 10 * MS],          # [0, 5) inside
            ["packed_prefill_kernel", 10 * MS, 20 * MS],   # [10, 30)
            ["fusion.1", 25 * MS, 10 * MS],          # [25, 35) overlaps
            ["paged_attention_kernel", 60 * MS, 10 * MS],  # [60, 70)
            ["sort", 95 * MS, 20 * MS],              # [95, 100) clipped
        ],
        "host": [
            ["perfbench.window", 0, 100 * MS, "main"],
            ["engine step", 30 * MS, 40 * MS, "engine"],      # [30, 70)
            ["np.asarray", 40 * MS, 15 * MS, "engine"],       # [40, 55)
            ["submit", 80 * MS, 10 * MS, "main"],             # [80, 90)
        ],
    }


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce(hand_trace())
    # [0,5) + [10,35) + [60,70) + [95,100) = 5 + 25 + 10 + 5 = 45 ms
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.045)
    assert r.idle_share == pytest.approx(0.55)


def test_kernel_time_by_name():
    r = trace.reduce(hand_trace())
    assert r.op_s["fusion.1"] == pytest.approx(0.015)     # 5 + 10 ms
    assert r.kernel_s("packed_prefill") == pytest.approx(0.020)
    assert r.kernel_s("paged_attention") == pytest.approx(0.010)
    assert r.op_s["sort"] == pytest.approx(0.005)
    assert r.top_ops(1) == [["packed_prefill_kernel", pytest.approx(0.02)]]


def test_gaps_go_to_the_innermost_host_span():
    r = trace.reduce(hand_trace())
    gaps = dict((round(s * 1e3), name) for name, s in r.gaps)
    # gaps: [5,10) 5 ms, [35,60) 25 ms, [70,95) 25 ms
    assert sorted(gaps) == [5, 25]
    got = sorted((round(s * 1e3), n) for n, s in r.gaps)
    assert got == [(5, "no host span"), (25, "np.asarray"), (25, "submit")]
    assert r.top_gaps(1)[0][1] == pytest.approx(0.025)


def test_two_devices_average_their_busy_time():
    tr = hand_trace()
    tr["devices"] = 2
    tr["device"] = [e + [0] for e in tr["device"]] + \
        [["fusion.9", 0, 100 * MS, 1]]
    r = trace.reduce(tr)
    assert r.busy_s == pytest.approx((0.045 + 0.100) / 2)


def test_recorded_excerpt():
    tr = json.loads((Path(__file__).parent / "data" /
                     "trace_excerpt.json").read_text())
    r = trace.reduce(tr)
    w0, w1 = tr["window"]
    # busy by a 1 us timeline, counted independently of the interval union
    us = (w1 - w0) // 1000
    on = bytearray(int(us))
    for _name, start, dur, *_ in tr["device"]:
        a = max(0, int((start - w0) // 1000))
        b = min(int(us), int(-(-(start + dur - w0) // 1000)))
        for i in range(a, b):
            on[i] = 1
    assert r.busy_s == pytest.approx(sum(on) * 1e-6, abs=len(tr["device"])
                                      * 2e-6)
    assert 0.0 < r.busy_s <= r.window_s
    total = sum(r.op_s.values())
    assert total >= r.busy_s - 1e-9
