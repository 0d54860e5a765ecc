"""Check and time PageRank and BFS split by destination range over chips.

    python scripts/split_probe.py OUT_DIR [--chips 4]

On a TPU host with at least ``--chips`` chips: builds the
``graph500-s15`` R-MAT graph (32,768 vertices, 880,000 directed edges,
CSC order) from seed 0 and runs ``pagerank_ga`` and ``bfs_levels_ga`` on
snapshots whose mesh has one device and ``--chips`` devices.  Prints one
JSON line, also written to ``OUT_DIR/split_probe.json``:

* ``pagerank_bit_equal`` and ``bfs_equal``: the split results against
  the one-device ones; ``pagerank_rel_err`` against the float64
  reference of ``bench/harness/reference.py``;
* ``ms``: host-clock ms of a call, median of 5 after a warm-up call,
  with the split layout already on the chips (``*_kernels``) and made
  anew (``*_with_layout``); ``layout_ms``: the layout alone;
* ``edges``: each chip's edges, and ``skew``, the largest over the mean;
* ``devices``: from a profiler trace of 3 calls of each split kernel, per
  device, device ms per call of the operations under the name scopes
  ``weaver.pagerank``, ``weaver.bfs`` and ``weaver.exchange`` (read from
  each operation's ``tf_op`` stat) and of the collective operations
  (``collective_ms``), with their names and the stat keys the operations
  carry.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

SCOPES = ("weaver.pagerank", "weaver.bfs", "weaver.exchange")
COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|collective-permute"
                        r"|reduce-scatter|all-to-all)")


def _median_ms(fn, reps=5):
    import jax

    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3


def _per_device(fns, calls=3):
    """Device ms per call, per device, of the name scopes and of the
    collective operations, from a profiler trace of ``calls`` calls of
    each of ``fns``."""
    import jax

    from bench.harness import trace as btrace

    tmp = tempfile.mkdtemp(prefix="split-probe-")
    try:
        with btrace.capture(tmp):
            for fn in fns:
                for _ in range(calls):
                    jax.block_until_ready(fn())
        pd = jax.profiler.ProfileData.from_file(btrace.find_xplane(tmp))
        out = {}
        for pl in pd.planes:
            if not re.match(r"^/device:TPU:\d+$", pl.name):
                continue
            row = {k: 0.0 for k in SCOPES + ("collective_ms",)}
            names, keys = set(), set()
            for ln in pl.lines:
                if ln.name != "XLA Ops":
                    continue
                for e in ln.events:
                    st = dict(e.stats)
                    keys.update(st)
                    op = str(st.get("tf_op", ""))
                    ms = e.duration_ns * 1e-6 / calls
                    for k in SCOPES:
                        if k in op:
                            row[k] += ms
                    name = btrace.short_name(e.name)
                    if COLLECTIVE.match(name):
                        row["collective_ms"] += ms
                        names.add(re.sub(r"\.\d+$", "", name))
            row["collective_ops"] = sorted(names)
            row["stat_keys"] = sorted(keys)
            out[pl.name] = row
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("out_dir")
    p.add_argument("--chips", type=int, default=4)
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from bench.harness import data, reference, spec
    from repro.core import analytics as A
    from repro.launch.mesh import make_columns_mesh

    if jax.default_backend() != "tpu" or len(jax.devices()) < args.chips:
        print(f"split_probe: needs a TPU host with {args.chips} chips; JAX "
              f"found {len(jax.devices())} {jax.default_backend()} devices",
              file=sys.stderr)
        return 1
    cfg = spec.load_json(os.path.join(ROOT, "bench", "configs",
                                      "graph500-s15.json"))
    g = data.graph(np.random.default_rng([0, 0]), cfg["data"])
    s, d = g.edges()
    o = np.lexsort((s, d))
    s, d = s[o].astype(np.int32), d[o].astype(np.int32)
    n = g.n
    meshes = {1: make_columns_mesh(1), args.chips: make_columns_mesh(
        args.chips)}

    def ga(chips):
        return A.GraphArrays(vids=list(range(n)), index={}, edge_src=s,
                             edge_dst=d, n_nodes=n, _csc=(s, d),
                             mesh=meshes[chips])

    one, split = ga(1), ga(args.chips)
    root = int(s[0])
    pr1 = np.asarray(A.pagerank_ga(one))
    pr = np.asarray(A.pagerank_ga(split))
    lv1 = np.asarray(A.bfs_levels_ga(one, [root]))
    lv = np.asarray(A.bfs_levels_ga(split, [root]))
    want = reference.pagerank(s, d, n, 20, 0.85)
    lay = split._split[0]
    ms = {
        "pagerank_one": _median_ms(lambda: A.pagerank_ga(one)),
        "pagerank_split_kernels": _median_ms(lambda: A.pagerank_ga(split)),
        "pagerank_split_with_layout": _median_ms(
            lambda: A.pagerank_ga(ga(args.chips))),
        "bfs_one": _median_ms(lambda: A.bfs_levels_ga(one, [root])),
        "bfs_split_kernels": _median_ms(
            lambda: A.bfs_levels_ga(split, [root])),
        "layout_ms": _median_ms(
            lambda: A._put_split(ga(args.chips), meshes[args.chips])[1]),
    }
    out = {
        "chips": args.chips, "n": n, "e": int(s.size),
        "pagerank_bit_equal": bool(np.array_equal(pr.view(np.int32),
                                                  pr1.view(np.int32))),
        "pagerank_rel_err": float(np.max(np.abs(pr - want) / want)),
        "bfs_equal": bool(np.array_equal(lv, lv1)),
        "bfs_levels": int(lv[lv < A.INF].max()),
        "edges": lay.edges.tolist(),
        "skew": float(lay.edges.max() / lay.edges.mean()),
        "slots_per_chip": int(lay.src.size // args.chips),
        "ms": ms,
        "devices": _per_device([lambda: A.pagerank_ga(split),
                                lambda: A.bfs_levels_ga(split, [root])]),
    }
    line = json.dumps(out)
    os.makedirs(args.out_dir, exist_ok=True)
    with open(os.path.join(args.out_dir, "split_probe.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    ok = out["pagerank_bit_equal"] and out["bfs_equal"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
