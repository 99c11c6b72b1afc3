#!/usr/bin/env python3
"""The control's readings on the chip, for the upper side of a cell's limit.

    python3 perfbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process: a run of the cell as ``run.py`` makes it
(set-up, the mix offered for ``--seconds`` at the cell's own load, what was
sent waited for, the program closed), with the check reading the control —
the reference in float8 — in the program's place.  It goes through the same
comparison that decides a run's ``correct``, which has to come out false.
One JSON line per seed.  The benchmark's own runs do not run this;
``limits/<cell>.json`` records what it read.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import check, common, harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    common.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    limits = check.load_limits(cell.name)
    for seed in args.seeds:
        t = time.perf_counter()
        line = run.execute(cell, limits, seed, args.seconds, 0, 1, [],
                           control=True)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "correct": line["correct"],
            "requests": line["attempted"], "checks": line["checks"],
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
