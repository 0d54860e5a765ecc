"""Compile the data plane's Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler lowers each kernel for a
``v5e:2x2`` topology that is described, not attached, so Mosaic rejects
here what it would reject on the chip (unsupported vector types,
misaligned tiles, too much VMEM).  This is the only file that describes
the chip; the description happens inside a fixture, never at import
time, so every pytest-xdist worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from bench.harness.kernel_cost import mv_visibility_bytes
from repro.core.analytics import bfs_split, bfs_tiled, pagerank_split, \
    pagerank_tiled
from repro.dist.columns import TABLES, visibility_launch
from repro.kernels import backend
from repro.kernels.mv_visibility.kernel import before_pallas, \
    visibility_pallas
from repro.kernels.segment_mp.kernel import DEFAULT_BLOCK_E, \
    DEFAULT_BLOCK_N, segment_mp_pallas
from repro.kernels.segment_sum import ops as seg_ops
from repro.kernels.segment_sum.kernel import GATHER_MAX_N, table_gather, \
    tiled_segment_sum

#: the analytics cell's snapshot: R-MAT scale 15, 440k undirected edges
ANALYTICS_N, ANALYTICS_E = 32_768, 880_000

#: rows per (table, shard) block of the smoke deployment: 1M edges over
#: 8 shards rounds up to 2^18, and 2^20 is the per-launch row count
SMOKE_BLOCK = 1 << 18


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip are written to a persistent cache
    # but cannot be read back without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR",
                      os.environ.get("TPU_LOG_DIR", "disabled"))
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described "
                            f"here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n", [1024, 1 << 20])
@pytest.mark.parametrize("c", [2, 5, 9])
@pytest.mark.parametrize("kernel", ["visibility", "before"])
def test_mv_visibility_compiles(one_chip, kernel, c, n):
    rows = jax.ShapeDtypeStruct((c, n), jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one_chip)
    if kernel == "visibility":
        fn = lambda cr, de, q: visibility_pallas(cr, de, q, interpret=False)
        args = (rows, rows, q)
    else:
        fn = lambda r, q: before_pallas(r, q, interpret=False)
        args = (rows, q)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d,f", [(128, 128), (64, 64)])
def test_segment_mp_compiles(one_chip, d, f):
    n_nodes, tiles = 8 * DEFAULT_BLOCK_N, 4
    n_edges = (n_nodes // DEFAULT_BLOCK_N) * tiles * DEFAULT_BLOCK_E
    xs = jax.ShapeDtypeStruct((n_edges, d), jnp.float32, sharding=one_chip)
    dst = jax.ShapeDtypeStruct((n_edges,), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((d, f), jnp.float32, sharding=one_chip)
    fn = lambda xs, dst, w: segment_mp_pallas(xs, dst, w, n_nodes,
                                              interpret=False)
    compiled = jax.jit(fn).lower(xs, dst, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_column_plane_launch_compiles_on_four_chips(topo):
    """The device plane's one sharded launch with the Pallas branch, at
    the smoke deployment's resident size (8 shards, 4 gatekeepers)."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    n_shards, c = 8, 5
    blocks = jax.ShapeDtypeStruct((n_shards, TABLES, SMOKE_BLOCK, c),
                                  jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    q = jax.ShapeDtypeStruct((c,), jnp.int32,
                             sharding=NamedSharding(mesh, P()))
    compiled = visibility_launch(mesh, use_pallas=True).lower(
        blocks, q).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # each chip holds a quarter of the blocks (plus the replicated q)
    per_chip = n_shards * TABLES * SMOKE_BLOCK * c * 4 // 4
    assert per_chip <= compiled.memory_analysis().argument_size_in_bytes \
        < per_chip + 1024


@pytest.mark.parametrize("block_n,block_e", [(256, 1024), (128, 2048)])
@pytest.mark.parametrize("what", ["kernel", "pagerank", "bfs"])
def test_segment_sum_compiles_at_analytics_shapes(one_chip, monkeypatch,
                                                  what, block_n, block_e):
    """The tiled segment sum alone and inside ``pagerank_tiled`` and
    ``bfs_tiled`` at the analytics cell's shapes; its launch is not an
    ``mv_visibility`` one, so ``mv_visibility_roofline`` keeps reading
    only those."""
    # the process runs on the CPU: make the kernel's interpret=None
    # resolve as it does on the chip
    monkeypatch.setattr(backend, "platform", lambda: "tpu")
    n, e = ANALYTICS_N, ANALYTICS_E
    e_pad = -(-e // block_e) * block_e
    t_max = -(-e // block_e) + -(-n // block_n)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    tiles = (spec((t_max,), jnp.int32), spec((t_max,), jnp.int32),
             spec((1,), jnp.int32))
    after = ()
    if what == "kernel":
        fn = lambda v, d, tb, tc, nu: tiled_segment_sum(
            v, d, tb, tc, nu, n, block_n=block_n, block_e=block_e,
            interpret=False)
        args = (spec((e_pad,), jnp.float32), spec((e_pad,), jnp.int32))
    elif what == "pagerank":
        fn = lambda s, d, deg, tb, tc, nu: pagerank_tiled(
            s, d, deg, tb, tc, nu, n, 20, 0.85, block_n=block_n,
            block_e=block_e)
        args = (spec((e_pad,), jnp.int32), spec((e_pad,), jnp.int32),
                spec((n,), jnp.float32))
    else:
        fn = lambda s, d, tb, tc, nu, roots: bfs_tiled(
            s, d, tb, tc, nu, roots, n, block_n=block_n, block_e=block_e)
        args = (spec((e_pad,), jnp.int32), spec((e_pad,), jnp.int32))
        after = (spec((1,), jnp.int32),)
    compiled = jax.jit(fn).lower(*args, *tiles, *after).compile()
    launches = [ln.strip().removeprefix("ROOT ")
                for ln in compiled.as_text().splitlines()
                if "tpu_custom_call" in ln]
    names = {"kernel": ["%tiled_segment_sum"],
             "pagerank": ["%table_gather", "%tiled_segment_sum"],
             "bfs": ["%table_gather", "%tiled_segment_sum"]}[what]
    assert sorted(ln.split(".", 1)[0] for ln in launches) == names
    assert all(mv_visibility_bytes(ln) is None for ln in launches)


@pytest.mark.parametrize("n", [ANALYTICS_N, GATHER_MAX_N])
def test_table_gather_compiles(one_chip, n):
    """The MXU gather at the cell's table and at the largest it takes
    (VMEM holds the three-part table and the one-hot)."""
    e = -(-ANALYTICS_E // 1024) * 1024
    table = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip)
    fn = lambda t, i: table_gather(t, i, interpret=False)
    compiled = jax.jit(fn).lower(table, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_split_pagerank_compiles_on_four_chips(topo, monkeypatch):
    """``pagerank_split`` over the four chips of a described ``v5e:2x2``
    at the four-chip analytics cell's shapes: both kernels on every chip,
    an all-gather between iterations, and each chip's arguments a quarter
    of the edges (with ``split_layout``'s room of a quarter of that) plus
    the whole out-degree table."""
    monkeypatch.setattr(backend, "platform", lambda: "tpu")
    n, e, chips = ANALYTICS_N, ANALYTICS_E, 4
    bn, be = seg_ops.DEFAULT_BLOCK_N, seg_ops.DEFAULT_BLOCK_E
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    # an evenly spread dst column gives the layout's slot count, which
    # depends on E and the chip count alone while the chips stay within
    # a quarter of the mean
    dst = np.arange(e, dtype=np.int64) * n // e
    lay = seg_ops.split_layout(np.zeros(e, np.int32), dst, n, chips)
    slots = lay.src.size // chips
    part = NamedSharding(mesh, P("data"))
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=part)
    deg = jax.ShapeDtypeStruct((lay.n_local * chips,), jnp.float32,
                               sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(lambda *a: pagerank_split(
        *a, n, 20, 0.85, mesh=mesh, block_n=bn, block_e=be)).lower(
        spec(lay.src), spec(lay.dst), deg, spec(lay.tile_block),
        spec(lay.tile_chunk), spec(lay.n_used)).compile()
    text = compiled.as_text()
    launches = sorted(ln.strip().removeprefix("ROOT ").split(".", 1)[0]
                      for ln in text.splitlines() if "tpu_custom_call" in ln)
    assert launches == ["%table_gather", "%tiled_segment_sum"]
    assert "all-gather" in text
    # per chip: src and dst (int32) over its slots, its tile list, the
    # whole degree table, n_used and damping, each buffer aligned
    quarter = e // chips * 8
    args = compiled.memory_analysis().argument_size_in_bytes
    need = slots * 8 + lay.tile_block.size // chips * 8 \
        + deg.shape[0] * 4 + 8
    assert need <= args < need + 4096
    assert quarter < slots * 8 <= quarter * 5 // 4 + 2 * be * 8


def test_split_bfs_compiles_on_four_chips(topo, monkeypatch):
    """``bfs_split`` over the four chips of a described ``v5e:2x2`` at
    the four-chip analytics cell's shapes: both kernels on every chip and
    an all-gather of the reached bits between levels, which also decides
    the loop's end, so no all-reduce is left."""
    monkeypatch.setattr(backend, "platform", lambda: "tpu")
    n, e, chips = ANALYTICS_N, ANALYTICS_E, 4
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    dst = np.arange(e, dtype=np.int64) * n // e
    lay = seg_ops.split_layout(np.zeros(e, np.int32), dst, n, chips)
    part = NamedSharding(mesh, P("data"))
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=part)
    roots = jax.ShapeDtypeStruct((1,), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(lambda *a: bfs_split(
        *a, n, mesh=mesh, n_local=lay.n_local, block_n=lay.block_n,
        block_e=lay.block_e)).lower(
        spec(lay.src), spec(lay.dst), spec(lay.tile_block),
        spec(lay.tile_chunk), spec(lay.n_used), roots).compile()
    text = compiled.as_text()
    launches = sorted(ln.strip().removeprefix("ROOT ").split(".", 1)[0]
                      for ln in text.splitlines() if "tpu_custom_call" in ln)
    assert launches == ["%table_gather", "%tiled_segment_sum"]
    assert "all-gather" in text
    assert "all-reduce" not in text
