"""Spreads of sets of runs, and the bounds they give.

    python3 bench/tools/spread.py SET_A_FILES... -- SET_B_FILES...

Each file holds one run's standard output; its last line is the result
object.  For every end-to-end metric: each set's median and quartile
spread (``stats.spread``: the distance between the first and third
quartile over the median), the wider of the two spreads, and five times
it as the bound (never under 1%), with the shift of set B's median
against set A's.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def results(paths):
    out = []
    for p in paths:
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        out.append(json.loads(lines[-1]))
    return out


def main(argv=None) -> int:
    from bench.harness import stats

    argv = sys.argv[1:] if argv is None else argv
    cut = argv.index("--")
    sets = [results(argv[:cut]), results(argv[cut + 1:])]
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        vals = [[r["metrics"][name]["value"] for r in s
                 if name in r["metrics"]] for s in sets]
        sp = [stats.spread(v) for v in vals]
        med = [statistics.median(v) for v in vals]
        print(json.dumps({
            "metric": name, "medians": med, "spreads": sp,
            "widest": max(sp), "bound": max(0.01, 5 * max(sp)),
            "median_shift": med[1] / med[0] - 1.0,
            "correct": [r["correct"] for s in sets for r in s]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
