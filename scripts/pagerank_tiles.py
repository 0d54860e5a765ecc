"""Time PageRank's edge pass on the chip at the analytics cell's shapes.

    python scripts/pagerank_tiles.py OUT_DIR [BN:BE ...]

On a TPU: builds the ``graph500-s15`` R-MAT graph (32,768 vertices,
880,000 directed edges, CSC order) from seed 0, then for XLA's
``pagerank(..., dst_sorted=True)``, for ``pagerank_tiled`` at each block
size ``BN:BE`` (default ``128:1024``) and for ``pagerank_tiled`` with
XLA's gather in place of ``table_gather`` prints one JSON line: the
host-clock time of a whole call (median of 5, after a warm-up call),
device milliseconds per operation and call from a profiler trace of 3
calls, the tile layout's host cost, the masked-slot share and the
largest relative error against a float64 loop.  Then ``table_gather``
alone against XLA's gather, bit for bit and timed, for tables of 32,768
and ``GATHER_MAX_N`` entries.  The compiled HLO of each program goes to
``OUT_DIR/<name>.hlo.txt``, so the trace's fusion names can be read.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def _timed(fn, reps=5):
    import jax

    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _device_ops(fn, calls=3):
    import jax

    from bench.harness import trace as btrace

    tmp = tempfile.mkdtemp(prefix="prtiles-")
    try:
        with btrace.capture(tmp):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(calls):
                    jax.block_until_ready(fn())
        red = btrace.reduce(btrace.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {k: v / calls * 1e3 for k, v in red["device_ops"][:12]}


def main(out_dir: str, sizes) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import data, reference
    from repro.core import analytics
    from repro.kernels.segment_sum import kernel, ops as seg

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"pagerank_tiles: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(ROOT, "bench", "configs",
                           "graph500-s15.json")) as f:
        g = json.load(f)["data"]
    gr = data.graph(np.random.default_rng(0), g)
    src, dst = gr.edges()
    order = np.lexsort((src, dst))
    src, dst = src[order].astype(np.int32), dst[order].astype(np.int32)
    n, iters, damping = gr.n, 20, 0.85
    want = reference.pagerank(src, dst, n, iters, damping)

    def report(name, fn, lowered, extra):
        with open(os.path.join(out_dir, f"{name}.hlo.txt"), "w") as f:
            f.write(lowered.compile().as_text())
        pr = np.asarray(jax.block_until_ready(fn()), np.float64)
        line = {"name": name, "device": dev.device_kind, "n": n,
                "e": int(src.size), "call_ms": _timed(fn) * 1e3,
                "device_ms": _device_ops(fn),
                "rel_err": float(np.max(np.abs(pr - want) / want))}
        line.update(extra)
        print(json.dumps(line), flush=True)

    cs, cd = jnp.asarray(src), jnp.asarray(dst)
    xla = lambda: analytics.pagerank(cs, cd, n, iters, damping,
                                     dst_sorted=True)
    report("xla", xla, analytics.pagerank.lower(
        cs, cd, n, iters, damping, dst_sorted=True), {})

    max_n = kernel.GATHER_MAX_N
    for size in sizes + ["xla_gather"]:
        if size == "xla_gather":
            kernel.GATHER_MAX_N = 0        # traced below: XLA's gather
            jax.clear_caches()
            bn, be, name = lay.block_n, lay.block_e, "tiled_xla_gather"
        else:
            bn, be = (int(x) for x in size.split(":"))
            name = f"tiled_{bn}_{be}"
        t0 = time.perf_counter()
        for _ in range(10):
            lay = seg.tile_layout(dst, n, bn, be)
            deg = np.maximum(np.bincount(src, minlength=n), 1).astype(
                np.float32)
            args = (seg.pad_edges(src, lay, 0), seg.pad_edges(dst, lay, -1),
                    deg, lay.tile_block, lay.tile_chunk, lay.n_used)
        host_ms = (time.perf_counter() - t0) * 100
        t0 = time.perf_counter()
        for _ in range(10):
            dargs = jax.block_until_ready([jnp.asarray(a) for a in args])
        put_ms = (time.perf_counter() - t0) * 100
        fn = lambda: analytics.pagerank_tiled(
            *dargs, n, iters, damping, block_n=bn, block_e=be)
        report(name, fn, analytics.pagerank_tiled.lower(
            *dargs, n, iters, damping, block_n=bn, block_e=be),
            {"layout_host_ms": host_ms, "layout_put_ms": put_ms,
             "tiles": int(lay.n_used[0]), "t_max": int(lay.tile_block.size),
             "masked_share": lay.masked_slots / lay.n_edges})
    kernel.GATHER_MAX_N = max_n

    rng = np.random.default_rng(1)
    for n_tab in (n, kernel.GATHER_MAX_N):
        tab = jnp.asarray((rng.random(n_tab) / n_tab).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, n_tab, 1024 * 860, np.int32))
        xla_gather = jax.jit(lambda t, i: t[i])
        want_g = np.asarray(xla_gather(tab, idx))
        got_g = np.asarray(kernel.table_gather(tab, idx))
        print(json.dumps({
            "name": "table_gather", "n_table": n_tab, "e": int(idx.size),
            "bit_equal": bool(np.array_equal(want_g.view(np.int32),
                                             got_g.view(np.int32))),
            "table_gather_ms": _device_ops(
                lambda: kernel.table_gather(tab, idx)),
            "xla_gather_ms": _device_ops(lambda: xla_gather(tab, idx))},
            default=float), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) < 2:
        sys.exit(__doc__)
    sys.exit(main(sys.argv[1], sys.argv[2:] or ["128:1024"]))
