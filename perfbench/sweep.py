#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest offered rate whose
backlog does not grow over the window.

    python3 perfbench/sweep.py --workload <cell> --seconds <s> \
        --rates <r> [<r> ...]

One session serves every rate in turn (each from its own seed, so no rate
hits another's prefixes), after the same set-up as a run.  Per rate it
prints one JSON line: requests sent and finished in time, the TTFT median
and 90th percentile, and the TTFT median of the last third of arrivals
over that of the first third; a backlog that grows through the window shows
as that ratio climbing well above 1.  The cell's mix then runs at about
four fifths of the knee (``rate_per_s`` in ``traffic/<mix>.json``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import common, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    common.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    session, _params = harness.open_session(cell, args.seed)
    try:
        harness.warm_traffic(session, cell, args.seed)
        for i, rate in enumerate(args.rates):
            mix = dict(cell.mix, rate_per_s=rate)
            seed = args.seed + 1000 * (i + 1)
            sched = cell.schedule(seed, args.seconds, mix)
            if sched.prefixes:
                harness.prime_prefixes(session, sched, mix["grace_s"])
            rec = harness.drive(session, sched, args.seconds,
                                float(mix["grace_s"]))
            reqs = rec["requests"]
            ttft = [(r.times[0] - r.due) * 1e3 if r.times else np.inf
                    for r in reqs]
            third = max(1, len(ttft) // 3)
            on_time = sum(1 for r in reqs if r.status == "done")
            print(json.dumps({
                "workload": cell.name, "rate_per_s": rate,
                "sent": len(reqs), "done": on_time,
                "ttft_p50_ms": float(np.percentile(ttft, 50)),
                "ttft_p90_ms": float(np.percentile(ttft, 90)),
                "growth": float(np.median(ttft[-third:]) /
                                np.median(ttft[:third]))}), flush=True)
    finally:
        session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
