"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell's configuration from the seed, warms up every program the
cell's traffic uses, measures for ``--seconds`` wall seconds, checks what
the window produced against the plain reference, and prints one JSON
object as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``, then ``checks``: every number compared with its limit,
which also end standard error.  Exits 1 without a TPU or with fewer
chips than the cell asks for, printing no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench.harness import device, spec

    cell = spec.resolve(args.workload, ROOT)
    try:
        dev = device.require_tpu(cell.chips)
    except device.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    from bench.harness import cell as runner

    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     T_START, device=dev)
    for name, c in out["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
