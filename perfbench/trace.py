"""From a profiler trace to the numbers the readers use.

Two steps, kept apart so the second can be checked on a small recorded
trace without a chip:

1. :func:`extract` reads the ``.xplane.pb`` the JAX profiler wrote into a
   plain dict: the traced window (the benchmark's own ``perfbench.window``
   host span), every device operation as ``[name, start_ns, dur_ns]`` and
   every host span as ``[name, start_ns, dur_ns, thread]``.
2. :func:`reduce` turns that dict into a :class:`Reduction`: busy time is
   the union of the device operations' intervals inside the window, idle
   share is one minus busy over the window, kernel time is summed by
   operation name, and each gap between device operations is attributed to
   the innermost host span that covers its middle.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_SPAN = "perfbench.window"
# device lines holding one event per operation, by the profiler's name
DEVICE_OP_LINES = ("XLA Ops",)


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]                      # device seconds by op name
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, needle: str) -> float:
        """Device seconds of every operation whose name holds ``needle``."""
        return sum(s for n, s in self.op_s.items() if needle in n)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps, key=lambda g: -g[1])[:n]]


def op_key(name: str) -> str:
    """An HLO op's trace name, ``%fusion.3 = f32[16]{0:T(128)} fusion(...)``,
    as ``%fusion = f32[16] fusion``: its instruction without the numeric
    suffix, its result type without layout, and its opcode, so every layer's
    and every program's copy of one operation sums under one name."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    lhs = re.sub(r"\.\d+$", "", lhs)
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    cut = rhs.find(")") + 1 if rhs.startswith("(") else 0
    rhs = rhs[:cut] + rhs[cut:].split("(", 1)[0]
    return f"{lhs} = {rhs.strip()}"[:160]


def _device_planes(planes):
    return [p for p in planes if p.name.startswith("/device:TPU:")]


def extract(log_dir: str) -> dict:
    """The trace under ``log_dir`` as plain lists (see module doc)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(files)}")
    data = ProfileData.from_file(files[0])
    planes = list(data.planes)
    dev_planes = _device_planes(planes)
    if not dev_planes:
        raise RuntimeError("the trace holds no TPU device plane; planes: "
                           f"{[p.name for p in planes]}")
    device = []
    for i, plane in enumerate(dev_planes):
        for line in plane.lines:
            if line.name in DEVICE_OP_LINES:
                device += [[op_key(e.name), e.start_ns, e.duration_ns, i]
                           for e in line.events]
    host, window = [], None
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                if e.name == WINDOW_SPAN:
                    window = [e.start_ns, e.start_ns + e.duration_ns]
                host.append([e.name, e.start_ns, e.duration_ns, line.name])
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    return {"window": window, "devices": len(dev_planes),
            "device": device, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _attribute(host, t: float) -> str:
    """The innermost host span (shortest) that covers time ``t``."""
    best, best_dur = "no host span", float("inf")
    for name, start, dur, _thread in host:
        if name == WINDOW_SPAN:
            continue
        if start <= t <= start + dur and dur < best_dur:
            best, best_dur = name, dur
    return best


def reduce(tr: dict, top_gaps: int = 10) -> Reduction:
    """Busy, idle, time by operation and attributed gaps inside the window.
    Busy time and time by operation are averaged over the traced devices;
    the ``top_gaps`` longest gaps of the first device are attributed."""
    w0, w1 = tr["window"]
    n_dev = max(1, tr.get("devices", 1))
    clipped: Dict[int, list] = {}
    op_s: Dict[str, float] = {}
    for name, start, dur, *dev in tr["device"]:
        a, b = max(start, w0), min(start + dur, w1)
        if b <= a:
            continue
        clipped.setdefault(dev[0] if dev else 0, []).append((a, b))
        op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9 / n_dev
    busy = {d: _union(iv) for d, iv in clipped.items()}
    busy_ns = sum(b - a for iv in busy.values() for a, b in iv)
    first = busy.get(min(busy), []) if busy else []
    edges = [w0] + [x for ab in first for x in ab] + [w1]
    spans = sorted(((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a), reverse=True)[:top_gaps]
    gaps = [(_attribute(tr["host"], (a + b) / 2), d * 1e-9)
            for d, a, b in spans]
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n_dev,
                     op_s=op_s, gaps=gaps)
