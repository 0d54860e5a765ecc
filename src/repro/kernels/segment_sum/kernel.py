"""Pallas TPU kernel: float32 segment sum over dst-sorted edges.

The TPU replacement for a sorted ``scatter-add`` into a vertex vector.
The edges stay in their dst-sorted (CSC) order and are read in aligned
chunks of ``BE`` slots.  The vertices are cut into blocks of ``BN``.  A
*tile* is one (block, chunk) pair whose chunk holds some of the block's
edges, so a chunk that straddles a block boundary is read by both
blocks; each tile counts only the edges whose dst lies in its block.
The grid runs over the tiles in block order:

* a scalar-prefetched ``tile_block[t]`` picks the output block and
  ``tile_chunk[t]`` the chunk (consecutive tiles on one chunk share its
  DMA);
* the block's ``(BN, 128)`` float32 accumulator stays in VMEM across
  its consecutive tiles, zeroed on the block's first tile;
* the reduction is a one-hot compare-and-select on the VPU, 128 edges at
  a time, summed lane-wise into the accumulator; the block's last tile
  sums the lanes and writes the ``(1, BN)`` row;
* tiles past ``n_used[0]`` (the grid has a fixed length, so shapes
  depend on E and N only) repeat the last tile's block and chunk and do
  nothing.

Every value and every partial sum is float32: no bf16 pass anywhere.

The two kernels make one edge pass of the graph kernels on the TPU
(``repro.core.analytics``): a PageRank iteration gathers ``pr / deg``
and sums it per destination (``pagerank_tiled``), and a BFS level
gathers the frontier's bits (``dist == level`` as 0.0/1.0) and counts
them per destination (``bfs_tiled``), a vertex being reached where its
count is positive.  The bits gather exactly and the counts are exact
integers in float32 (in-degrees below 2**24), so each level holds the
same vertices as XLA's ``segment_min`` relaxation, which the CPU keeps.
Split over several chips (``pagerank_split``, ``bfs_split``) each chip
runs both kernels on its destination range.

:func:`table_gather` is the edge pass's other half, ``table[idx]`` for a
float32 table small enough for VMEM, as a two-level one-hot on the MXU.
With ``idx = hi * 128 + lo``, the table is held as ``(128, B)``
(``[lo, hi]``) in three bfloat16 parts whose sum is each value exactly
(:func:`split_bf16`).  For 128 indices at a time, one matmul of the
stacked ``(3 * 128, B)`` parts by the exact 0/1 one-hot of ``hi``
``(B, 128)`` gives, per index, the table's column ``hi`` in each part
(one nonzero product per output: exact in float32); the parts are summed
low to high, which is exact, and the one-hot of ``lo`` selects the
value.  Every gathered value is bit-equal to ``table[idx]``.  Its work
grows with the table (``6 * 128 * B`` multiply-adds per index), so it
serves tables up to :data:`GATHER_MAX_N`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend

LANES = 128
# measured on a v5e at the analytics cell's shapes (PERF.md, section 6): the
# fastest pair whose masked slots stay under 15% of the edges
DEFAULT_BLOCK_N = 256     # vertices per output block
DEFAULT_BLOCK_E = 1024    # edge slots per chunk
#: largest table :func:`table_gather` takes: its matmul work grows with
#: the table, and at 32,768 entries it gathers in about a ninth of XLA's
#: time on a v5e (PERF.md, section 6), so 2**17 keeps it ahead with room
GATHER_MAX_N = 1 << 17


def _tile_sum_kernel(tile_block_ref, tile_chunk_ref, n_used_ref,
                     vals_ref, dst_ref, out_ref, acc_ref, *,
                     block_n: int, block_e: int):
    del tile_chunk_ref                  # read by the index maps only
    t = pl.program_id(0)
    last_t = pl.num_programs(0) - 1
    blk = tile_block_ref[t]
    first = jnp.logical_or(
        t == 0, tile_block_ref[jnp.maximum(t - 1, 0)] != blk)
    last = jnp.logical_or(
        t == last_t, tile_block_ref[jnp.minimum(t + 1, last_t)] != blk)

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < n_used_ref[0])
    def _accumulate():
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, LANES), 0)
        base = blk * block_n
        acc = acc_ref[...]
        for k in range(block_e // LANES):
            sl = slice(k * LANES, (k + 1) * LANES)
            hit = (dst_ref[:, sl] - base) == rows          # (BN, 128)
            acc += jnp.where(hit, vals_ref[:, sl], 0.0)
        acc_ref[...] = acc

    @pl.when(last)
    def _emit():
        col = jnp.sum(acc_ref[...], axis=1, keepdims=True)      # (BN, 1)
        # column -> row: each output lane keeps its diagonal term, adding
        # only exact zeros
        eye = (jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1))
        out_ref[...] = jnp.sum(jnp.where(eye, col, 0.0), axis=0,
                               keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "block_n", "block_e",
                                    "interpret"))
def tiled_segment_sum(vals: jnp.ndarray, dst: jnp.ndarray,
                      tile_block: jnp.ndarray, tile_chunk: jnp.ndarray,
                      n_used: jnp.ndarray, n_nodes: int,
                      block_n: int = DEFAULT_BLOCK_N,
                      block_e: int = DEFAULT_BLOCK_E,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """vals (E_pad,) float32 and dst (E_pad,) int32 in dst-sorted order,
    padded to a whole number of ``block_e`` chunks with dst = -1;
    ``tile_block``/``tile_chunk`` (T,) int32 and ``n_used`` (1,) int32
    from :func:`repro.kernels.segment_sum.ops.tile_layout` ->
    (n_nodes,) float32 with ``out[v] = sum(vals[dst == v])``.
    ``interpret=None`` takes the choice of :mod:`repro.kernels.backend`.
    """
    e_pad = vals.shape[0]
    assert e_pad % block_e == 0, (e_pad, block_e)
    n_pad = pl.cdiv(n_nodes, block_n) * block_n
    kernel = functools.partial(_tile_sum_kernel, block_n=block_n,
                               block_e=block_e)
    chunk = lambda t, tb, tc, nu: (0, tc[t])
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tile_block.shape[0],),
            in_specs=[pl.BlockSpec((1, block_e), chunk),
                      pl.BlockSpec((1, block_e), chunk)],
            out_specs=pl.BlockSpec((1, block_n),
                                   lambda t, tb, tc, nu: (0, tb[t])),
            scratch_shapes=[pltpu.VMEM((block_n, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=backend.interpret(interpret),
    )(tile_block, tile_chunk, n_used, vals[None, :], dst[None, :])
    return out[0, :n_nodes]


def split_bf16(x: jnp.ndarray) -> jnp.ndarray:
    """(N,) float32 -> (3, N) bfloat16 parts ``p0 + p1 + p2 == x`` exactly
    (summed as ``(p2 + p1) + p0``).

    Each part is the float32 remainder truncated to its top 16 bits, so
    a bit mask does the rounding and no compiler can fold a
    float32 -> bfloat16 -> float32 round trip away: ``p0`` holds the top
    8 significant bits, ``p1`` the next 8 and ``p2`` the last 8.  Exact
    where ``p2`` is a normal number or zero (``|x| >= 2**-103``): the TPU
    flushes subnormals.
    """
    parts = []
    r = x
    for _ in range(3):
        top = jax.lax.bitcast_convert_type(r, jnp.uint32) >> 16
        parts.append(jax.lax.bitcast_convert_type(top.astype(jnp.uint16),
                                                  jnp.bfloat16))
        r = r - jax.lax.bitcast_convert_type(top << 16, jnp.float32)
    return jnp.stack(parts)


def _table_gather_kernel(tab_ref, idx_ref, out_ref, *, n_hi: int,
                         block_e: int):
    tab = tab_ref[...]                                   # (3 * 128, B)
    hi_rows = jax.lax.broadcasted_iota(jnp.int32, (n_hi, LANES), 0)
    lo_rows = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    for k in range(block_e // LANES):
        sl = slice(k * LANES, (k + 1) * LANES)
        idx = idx_ref[:, sl]                             # (1, 128)
        onehot = (hi_rows == idx // LANES).astype(jnp.bfloat16)
        g = jnp.dot(tab, onehot, preferred_element_type=jnp.float32)
        col = (g[2 * LANES:] + g[LANES:2 * LANES]) + g[:LANES]
        out_ref[:, sl] = jnp.sum(
            jnp.where(lo_rows == idx % LANES, col, 0.0), axis=0,
            keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def table_gather(table: jnp.ndarray, idx: jnp.ndarray,
                 block_e: int = DEFAULT_BLOCK_E,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """table (N,) float32, idx (E,) int32 in ``[0, N)`` with E a multiple
    of ``block_e`` -> (E,) float32 bit-equal to ``table[idx]``, for
    ``N <= GATHER_MAX_N``.  ``interpret=None`` takes the choice of
    :mod:`repro.kernels.backend`."""
    n, e = table.shape[0], idx.shape[0]
    assert n <= GATHER_MAX_N and e % block_e == 0, (n, e, block_e)
    n_hi = pl.cdiv(pl.cdiv(n, LANES), LANES) * LANES
    parts = split_bf16(jnp.pad(table, (0, n_hi * LANES - n)))
    # part p's [lo, hi] entry is table[hi * 128 + lo]
    tab = parts.reshape(3, n_hi, LANES).transpose(0, 2, 1).reshape(
        3 * LANES, n_hi)
    out = pl.pallas_call(
        functools.partial(_table_gather_kernel, n_hi=n_hi, block_e=block_e),
        grid=(e // block_e,),
        in_specs=[pl.BlockSpec((3 * LANES, n_hi), lambda i: (0, 0)),
                  pl.BlockSpec((1, block_e), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block_e), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=backend.interpret(interpret),
    )(tab, idx[None, :])
    return out[0]
