"""Run a cell with a fault or control planted under the window and print
the numbers its check compares.

    python3 bench/tools/control.py --workload <cell> --fault <name> \
        --seeds 11,12,13 [--seconds 10] [--scale S]

``--fault`` names an entry of ``bench.harness.faults.FAULTS`` (or
``none`` for the program as it is).  One JSON line per seed:
``{"seed", "fault", "correct", "checks"}``.  Needs a TPU unless
``--scale`` is given (a rehearsal at reduced size on the CPU).  The
benchmark's own runs never plant anything; this is how the readings that
set each limit in ``PERF.md`` were taken.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--fault", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--scale", type=float, default=None)
    args = p.parse_args(argv)

    from bench.harness import cell as runner
    from bench.harness import device, faults, spec

    cell = spec.resolve(args.workload, ROOT)
    dev = None
    if args.scale is None:
        try:
            dev = device.require_tpu(cell.chips)
        except device.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 1
    planted = None if args.fault == "none" else faults.FAULTS[args.fault]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = runner.run(cell, seed, args.seconds, False, time.perf_counter(),
                         device=dev, scale=args.scale, planted=planted)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
