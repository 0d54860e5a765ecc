"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,metric,value`` CSV lines (simulated time; deterministic).

  snapshot       — snapshot materialization: columnar cold/delta vs seed
  nodeprog       — frontier-batched vs per-vertex node programs
  writepath      — group-commit write engine vs per-tx commits
  recovery       — WAL-replay vs store-walk MTTR; goodput dip on failure
  replication    — change-feed read replicas: read-throughput scaling
                   with replica count (bit-identical results), in-pod vs
                   cross-pod read latency, goodput through primary kill
                   with replica promotion
  serving        — windowed read admission vs per-program; offered-load
                   sweep past saturation with backpressure; SLO curves
  block_query    — Fig. 7 / Table 2 (CoinGraph vs relational explorer)
  social         — Fig. 9 / Fig. 10 (TAO mix, Weaver vs 2PL)
  traversal      — Fig. 11 (node programs vs BSP sync/async)
  scalability    — Fig. 12 / Fig. 13 (gatekeeper & shard scaling)
  coordination   — Fig. 14 (tau sweep: announce vs oracle)
  scaling        — forced host-device sweep: sharded columnar snapshot
                   equivalence + modeled device scaling; columnar BSP
                   vs interpreted; Weaver vs columnar BSP (Fig. 11
                   at the columnar baseline)

A benchmark that raises is reported, the remaining modules still run,
and the harness exits non-zero at the end — failures are loud, never
silently skipped.

``--smoke`` (used by ``scripts/ci.sh``) sets ``REPRO_BENCH_SMOKE=1``
(modules shrink their graph sizes / iteration counts) and runs only the
snapshot + nodeprog + writepath + recovery + replication + serving +
coordination + scaling modules — a
minutes-scale end-to-end check that the data-plane benchmarks still
build, run, and meet their equivalence bits (coordination rides along
so the tau sweep's aggressive-concurrency corner — the historical
oracle ``CycleError`` — stays covered in CI; scaling asserts the
sharded-vs-host bit-identity through real forced-multi-device
``shard_map`` launches).
"""

from __future__ import annotations

import os
import sys
import time


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    if smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from repro.kernels import backend
    backend.use_compile_cache()

    from . import (block_query, coordination, nodeprog, recovery,
                   replication, scalability, scaling, serving, snapshot,
                   social, traversal, writepath)

    modules = [("snapshot", snapshot), ("nodeprog", nodeprog),
               ("writepath", writepath), ("recovery", recovery),
               ("replication", replication),
               ("serving", serving), ("block_query", block_query),
               ("social", social), ("traversal", traversal),
               ("scalability", scalability),
               ("coordination", coordination), ("scaling", scaling)]
    if smoke:
        modules = [("snapshot", snapshot), ("nodeprog", nodeprog),
                   ("writepath", writepath), ("recovery", recovery),
                   ("replication", replication),
                   ("serving", serving), ("coordination", coordination),
                   ("scaling", scaling)]
    t00 = time.time()
    failures = []
    for name, mod in modules:
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            mod.main()
        except Exception as e:  # keep the harness going, fail at the end
            failures.append((name, f"{type(e).__name__}: {e}"))
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            import traceback
            traceback.print_exc(limit=3)
        print(f"# {name} took {time.time()-t0:.1f}s wall", flush=True)
    print(f"# total {time.time()-t00:.1f}s wall")
    if failures:
        for name, err in failures:
            print(f"# FAILED {name}: {err}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
