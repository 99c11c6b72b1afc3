#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (timed as ``setup_s``): the cell's configuration and mix are read
from the files ``BENCHMARK.json`` names, the weights are made on the
device from ``--seed``, a ``repro.serving`` session is opened and warmed,
the shared prefixes (if the mix has them) are served once, and the first
seconds of the cell's own traffic are replayed from another seed.  Then the
window: the mix is offered for ``--seconds``, open or closed loop, and what
was sent is waited for.  With ``--trace 1`` the window runs under the JAX
profiler and the per-layer metrics are read; otherwise the end-to-end ones.
Last, the program is closed and ``correct`` is decided against the plain
reference (``check.py``).

Standard output ends with one JSON line; standard error ends with each
number compared beside its limit.  A run that finds no TPU, or fewer chips
than the cell asks for, exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import check, common, counts, harness  # noqa: E402
from perfbench import trace as tracing  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _event_totals(events) -> str:
    """``name: count/seconds`` for each JAX monitoring event name."""
    tot = {}
    for _, name, secs in events:
        n, s = tot.get(name, (0, 0.0))
        tot[name] = (n + 1, s + secs)
    return "; ".join(f"{k.rsplit('/', 1)[-1]}: {n}/{s:.2f}s"
                     for k, (n, s) in sorted(tot.items()))


def main(argv=None) -> int:
    args = _args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    cell = harness.load_cell(args.workload, bench)
    limits = check.load_limits(cell.name)

    import jax
    common.enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips[cell.name]:
        _log(f"perfbench: needs {chips[cell.name]} TPU chip(s), JAX found "
             f"{len(devices)} {dev.platform} device(s); nothing was run")
        return 2
    compiles = []                       # (perf_counter, event, seconds)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(
            (time.perf_counter(), event, secs)))
    jax.monitoring.register_event_listener(
        lambda event, **_: compiles.append((time.perf_counter(), event, 0.0))
        if event.startswith("/jax/compilation_cache/") else None)

    line = execute(cell, limits, args.seed, args.seconds, args.trace,
                   chips[cell.name], compiles)
    print(json.dumps(line), flush=True)
    return 0


def execute(cell, limits, seed: int, seconds: float, trace: int,
            chips: int, compiles: list, backend=None,
            control: bool = False) -> dict:
    """Everything a run does after finding its chip: set-up, the window,
    the readings and the check.  Returns the result line as a dict.  With
    ``control``, the check reads the control in the program's place
    (``check.gaps``), and ``correct`` has to come out false."""
    import jax
    dev = jax.devices()[0]
    peaks = counts.peaks(dev.device_kind) if dev.platform == "tpu" else \
        None
    marks = [("start", T_START), ("imports", time.perf_counter())]
    session, params = harness.open_session(cell, seed, backend)
    marks.append(("session warmed", time.perf_counter()))
    try:
        window = cell.schedule(seed, seconds)
        if window.prefixes:
            harness.prime_prefixes(session, window, cell.mix["grace_s"])
        marks.append(("prefixes primed", time.perf_counter()))
        harness.warm_traffic(session, cell, seed)
        marks.append(("warm traffic", time.perf_counter()))
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") \
            if trace else None
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        rec = harness.drive(session, window, seconds,
                            float(cell.mix["grace_s"]),
                            annotate=jax.profiler.TraceAnnotation)
        if trace_dir:
            jax.profiler.stop_trace()
        setup_s = rec["t0"] - T_START
        t1 = rec["t0"] + rec["window_s"]
        in_window = [c for c in compiles if rec["t0"] <= c[0] <= t1 and (
            c[1].endswith("backend_compile_duration")
            or c[1].startswith("/jax/compilation_cache/"))]
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        session.close()
    del session, params
    gc.collect()
    _log(f"device bytes in use before the reference: "
         f"{(dev.memory_stats() or {}).get('bytes_in_use')}")

    run = harness.Run(window_s=rec["window_s"], requests=rec["requests"],
                      before=rec["before"], after=rec["after"],
                      samples=rec["samples"], dims=cell.dims, peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    breakdown = None
    if trace_dir:
        tr = tracing.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = tracing.reduce(tr)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.top_ops(),
                     "idle_gaps": run.trace.top_gaps()}

    attempted, failed = harness.outcome(run)
    late = max((r.sent - r.due for r in run.requests if r.due is not None),
               default=0.0)
    _log(f"window: {run.window_s:.3f} s, {attempted} requests sent, "
         f"{failed} failed, {run.emitted()} tokens emitted; generator at "
         f"most {late * 1e3:.1f} ms late; setup {setup_s:.2f} s")
    _log("host calls in the window, count/longest/total s: " + "; ".join(
        f"{k} {len(v)}/{max(v, default=0.0):.4f}/{sum(v):.4f}"
        for k, v in rec["host_s"].items()))
    _log(f"compiles inside the window: {len(in_window)} "
         f"({sum(c[2] for c in in_window):.3f} s)")
    _log("setup: " + ", ".join(f"{b[0]} {b[1] - a[1]:.2f} s"
                               for a, b in zip(marks, marks[1:])))
    _log("setup compile events: " + _event_totals(
        [c for c in compiles if c[0] < rec["t0"]]))

    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = harness.load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = harness.end_to_end(run)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    t_check = time.perf_counter()
    sample = check.choose_sample(run.requests, seed, check.CHECK_TOKENS)
    gaps = check.gaps(cell.dims, seed, sample, control) if sample else []
    widest = max(gaps) if gaps else math.inf
    limit = limits["logit_gap_max"]["limit"]
    checks = {"logit_gap_max": {"value": widest, "limit": limit},
              "checked_tokens": {"value": len(gaps),
                                 "limit": check.CHECK_TOKENS}}
    correct = bool(widest <= limit and len(gaps) >= check.CHECK_TOKENS)
    _log(f"reference check{' of the control' if control else ''}: "
         f"{len(sample)} requests, {len(gaps)} tokens, "
         f"{time.perf_counter() - t_check:.1f} s")
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for k, v in checks.items():
        _log(f"check {k}: {v['value']} (limit {v['limit']})")
    return line


if __name__ == "__main__":
    sys.exit(main())
