"""Record a small profiler trace of the data plane's device programs.

    python bench/tools/trace_probe.py OUT_DIR

On a TPU: runs the ``mv_visibility`` ``before`` kernel at two row
counts, the column plane's sharded launch, and ``pagerank``/``bfs`` on a
small graph, inside a ``bench.window`` annotation, under the profiler
with Python tracing off.  Writes the ``.xplane.pb`` to
``OUT_DIR/probe.xplane.pb`` and a listing of its planes, lines and first
events (with every stat) to ``OUT_DIR/probe_listing.json``.  A cut of
such a trace is ``bench/tests/fixtures/v5e_probe.pbtxt``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import trace as btrace
    from repro.core import analytics
    from repro.dist.columns import visibility_launch
    from repro.kernels.mv_visibility import ops
    from repro.launch.mesh import make_columns_mesh

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"trace_probe: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    c = 5
    q = np.array([1, 50, 50, 50, 50], np.int32)
    rows = {n: rng.integers(0, 100, (n, c)).astype(np.int32)
            for n in (1024, 4096)}
    blocks = rng.integers(0, 100, (8, 4, 2048, c)).astype(np.int32)
    launch = visibility_launch(make_columns_mesh(1), True)
    n = 4096
    src = rng.integers(0, n, 60_000).astype(np.int32)
    dst = rng.integers(0, n, 60_000).astype(np.int32)
    order = np.lexsort((src, dst))
    csc_src, csc_dst = jnp.asarray(src[order]), jnp.asarray(dst[order])

    def work():
        for r in rows.values():
            np.asarray(ops.before_mask(r, q))
        np.asarray(launch(jnp.asarray(blocks), jnp.asarray(q)))
        np.asarray(analytics.pagerank(csc_src, csc_dst, n, 20, 0.85,
                                      dst_sorted=True))
        np.asarray(analytics.bfs_levels(csc_src, csc_dst, n,
                                        jnp.asarray([0]), dst_sorted=True))

    work()                                  # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="probe-")
    try:
        with btrace.capture(tmp):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(2):
                    with jax.profiler.TraceAnnotation("bench.kernels"):
                        work()
        path = btrace.find_xplane(tmp)
        shutil.copy(path, os.path.join(out_dir, "probe.xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    pd = jax.profiler.ProfileData.from_file(
        os.path.join(out_dir, "probe.xplane.pb"))
    listing = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            evs = list(ln.events)
            lines.append({"line": ln.name, "n": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "duration_ns": e.duration_ns,
                 "stats": {k: str(v) for k, v in e.stats}}
                for e in evs[:40]]})
        listing.append({"plane": pl.name,
                        "stats": {k: str(v) for k, v in pl.stats},
                        "lines": lines})
    with open(os.path.join(out_dir, "probe_listing.json"), "w") as f:
        json.dump(listing, f, indent=1)
    summary = btrace.reduce(os.path.join(out_dir, "probe.xplane.pb"))
    print(json.dumps({k: v for k, v in summary.items() if k != "events"},
                     default=str)[:4000])
    print("device_kind", dev.device_kind, "memory_stats",
          sorted((dev.memory_stats() or {}).keys()))
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    sys.exit(main(sys.argv[1]))
