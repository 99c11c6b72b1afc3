"""Benchmark harness: one function per paper table/figure (+ atomics, kernel
and serving benches).  Prints ``name,us_per_call,derived`` CSV, and with
``--json OUT.json`` additionally writes the same rows machine-readable so
successive PRs can track the perf trajectory (BENCH_ATOMICS.json /
BENCH_PAPER.json live at the repo root).

Quick mode (default) sizes every bench for minutes-total on one CPU core;
``--full`` approaches the paper's §5 grid.  GIL caveat: absolute Mops are
not EPYC-scale — scheme ordering, SCOT speedup direction and mechanism
counters are the reproducible signal (DESIGN.md §2/§9)."""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def _parse_row(row: str) -> dict:
    """'name,us_per_call,derived' → dict (derived 'k=v;k=v' unpacked)."""
    name, us, derived = row.split(",", 2)
    out = {"name": name, "us_per_call": float(us)}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            try:
                out[k] = float(v.rstrip("x"))
            except ValueError:
                out[k] = v
        elif part:
            out["derived"] = part
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grid (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench families "
                         "(atomics,batch,pool,paper,kernels,serving)")
    ap.add_argument("--workload", default="50r-50w",
                    choices=["50r-50w", "90r-10w", "0r-100w"],
                    help="workload mix for fig8/fig9 (appendix figures)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write results as JSON to OUT (one file; "
                         "rows grouped by bench family)")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="compare the rows measured in this run against a "
                         "previously written --json snapshot and exit "
                         "non-zero if any shared row regressed by an order "
                         "of magnitude (us_per_call ratio >= 10x); rows "
                         "only on one side are ignored")
    args = ap.parse_args()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else \
        {"atomics", "batch", "pool", "paper", "kernels", "serving"}

    if only & {"kernels", "serving"}:
        # the JAX families compile: keep those programs across runs
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.time()
    families: dict = {}
    collect = bool(args.json or args.compare)

    def emit(family: str, row: str) -> None:
        print(row)
        sys.stdout.flush()
        if collect:
            families.setdefault(family, []).append(_parse_row(row))

    if "atomics" in only:
        from .bench_atomics import bench_atomics
        for row in bench_atomics(quick=quick):
            emit("atomics", row)

    if "batch" in only:
        from .bench_batch import bench_batch
        for row in bench_batch(quick=quick):
            emit("batch", row)

    if "pool" in only:
        from .bench_pool import bench_pool
        for row in bench_pool(quick=quick):
            emit("pool", row)

    if "paper" in only:
        from . import bench_paper as bp
        for name, fn in bp.ALL_FIGS.items():
            kwargs = {"quick": quick}
            if name in ("fig8", "fig9"):
                kwargs["workload"] = args.workload
            for row in fn(**kwargs):
                emit("paper", row)

    if "kernels" in only:
        from . import bench_kernels as bk
        for name, fn in bk.ALL.items():
            for row in (fn() if name == "oracle" else fn(quick=quick)):
                emit("kernels", row)

    if "serving" in only:
        from .bench_serving import bench_serving
        for row in bench_serving(quick=quick):
            emit("serving", row)

    wall = time.time() - t0
    if args.json:
        payload = {
            "argv": sys.argv[1:],
            "mode": "full" if args.full else "quick",
            "python": platform.python_version(),
            "wall_s": round(wall, 1),
            "families": families,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)

    print(f"# total_wall_s={wall:.1f}", file=sys.stderr)

    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        regressions = []
        compared = 0
        for fam, rows in families.items():
            base_rows = {r["name"]: r
                         for r in baseline.get("families", {}).get(fam, [])}
            for r in rows:
                b = base_rows.get(r["name"])
                if not b or b.get("us_per_call", 0) <= 0:
                    continue
                compared += 1
                ratio = r["us_per_call"] / b["us_per_call"]
                if ratio >= 10.0:
                    regressions.append(
                        f"{r['name']}: {b['us_per_call']:.4f}us -> "
                        f"{r['us_per_call']:.4f}us ({ratio:.1f}x)")
        print(f"# compare: {compared} shared rows vs {args.compare}, "
              f"{len(regressions)} order-of-magnitude regressions",
              file=sys.stderr)
        for line in regressions:
            print(f"# REGRESSION {line}", file=sys.stderr)
        if regressions:
            sys.exit(1)


if __name__ == "__main__":
    main()
