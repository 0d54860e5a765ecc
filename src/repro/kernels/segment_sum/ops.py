"""Tile layout for the ``segment_sum`` kernel.

:func:`tile_layout` turns a dst-sorted ``dst`` column into the kernel's
tile list, vectorised over blocks: block ``b``'s edges are the sorted
range ``[lo_b, hi_b)`` (one ``searchsorted``), its tiles are the aligned
chunks that range touches (one tile for a block with no edges, so its
output row is written), and the list is padded to the fixed length
``T_max = ceil(E / BE) + ceil(N / BN)``, which bounds the chunks plus one
extra per block boundary.  Compiled shapes then depend on E and N alone.
A snapshot's layout is built once on one device and shared by PageRank
and BFS (``repro.core.analytics.pagerank_tiled`` and ``bfs_tiled``).

:func:`split_layout` cuts the same dst-sorted edges by destination range
over several chips, one :func:`tile_layout` per chip, for the graph
kernels split across chips (``repro.core.analytics.pagerank_split`` and
``bfs_split``), also built once per snapshot.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from .kernel import DEFAULT_BLOCK_E, DEFAULT_BLOCK_N


class TileLayout(NamedTuple):
    """The kernel's tile list for one dst column (host arrays)."""
    tile_block: np.ndarray    # (T_max,) int32 output block of each tile
    tile_chunk: np.ndarray    # (T_max,) int32 edge chunk of each tile
    n_used: np.ndarray        # (1,) int32 tiles that do work
    n_edges: int
    block_n: int
    block_e: int

    @property
    def e_pad(self) -> int:
        """Edge slots the kernel reads: whole chunks, at least one."""
        return max(1, -(-self.n_edges // self.block_e)) * self.block_e

    @property
    def masked_slots(self) -> int:
        """Slots the used tiles read but do not count: chunk overhang and
        the neighbour blocks' edges in straddled chunks."""
        return int(self.n_used[0]) * self.block_e - self.n_edges


def tile_layout(dst: np.ndarray, n_nodes: int,
                block_n: int = DEFAULT_BLOCK_N,
                block_e: int = DEFAULT_BLOCK_E) -> TileLayout:
    """Tiles for a non-decreasing ``dst`` over ``n_nodes >= 1`` vertices."""
    dst = np.asarray(dst)
    e = dst.size
    if e and np.any(dst[1:] < dst[:-1]):
        raise ValueError("tile_layout needs dst in non-decreasing order "
                         "(the CSC orientation)")
    n_blocks = -(-n_nodes // block_n)
    n_chunks = max(1, -(-e // block_e))
    bounds = np.searchsorted(dst, np.arange(n_blocks + 1) * block_n)
    lo, hi = bounds[:-1], bounds[1:]
    first = np.minimum(lo // block_e, n_chunks - 1)
    last = np.minimum(np.maximum(hi - 1, lo) // block_e, n_chunks - 1)
    tiles = last - first + 1
    used = int(tiles.sum())
    t_max = -(-e // block_e) + n_blocks
    blk = np.repeat(np.arange(n_blocks), tiles)
    start = np.cumsum(tiles) - tiles
    chunk = first[blk] + np.arange(used) - start[blk]
    tile_block = np.full(t_max, n_blocks - 1, np.int32)
    tile_chunk = np.full(t_max, chunk[-1], np.int32)
    tile_block[:used] = blk
    tile_chunk[:used] = chunk
    return TileLayout(tile_block, tile_chunk, np.array([used], np.int32),
                      e, block_n, block_e)


def pad_edges(a: np.ndarray, lay: TileLayout, fill) -> np.ndarray:
    """``a`` (E,) padded with ``fill`` to the layout's whole chunks."""
    a = np.asarray(a)
    return np.concatenate([a, np.full(lay.e_pad - a.size, fill, a.dtype)])


#: local dst of a split's padding slots: past every chip's range, and
#: above every real local dst, so each chip's slice stays non-decreasing
PAD_DST = np.iinfo(np.int32).max // 2


class DstSplit(NamedTuple):
    """CSC edges cut by destination range over ``n_chips`` chips (host
    arrays).  Chip ``d`` owns vertices ``[d * n_local, (d + 1) * n_local)``
    and reads ``slots`` edge slots at ``[d * slots, (d + 1) * slots)`` of
    ``src``/``dst``: the CSC's whole chunks that hold its range's edges,
    so a chunk that straddles two ranges is read by both chips, as it is
    by both blocks in :func:`tile_layout`; ``dst`` is local to the
    range (the straddled chunks' other edges fall outside it) and
    :data:`PAD_DST` past the slice."""
    src: np.ndarray           # (D * slots,) int32
    dst: np.ndarray           # (D * slots,) int32, local
    tile_block: np.ndarray    # (D * T,) int32, each chip's tile list
    tile_chunk: np.ndarray    # (D * T,) int32
    n_used: np.ndarray        # (D,) int32
    edges: np.ndarray         # (D,) int64 edges into each chip's range
    n_local: int
    block_n: int
    block_e: int


def split_layout(src: np.ndarray, dst: np.ndarray, n_nodes: int,
                 n_chips: int, block_n: int = DEFAULT_BLOCK_N,
                 block_e: int = DEFAULT_BLOCK_E) -> DstSplit:
    """Equal ranges of whole ``block_n`` blocks over ``n_chips`` chips for
    the CSC edges ``(src, dst)`` (``dst`` non-decreasing), each chip's
    slice with its own :func:`tile_layout`.

    Every chip's slice is padded to one slot count, so the chips run one
    program: the mean chunk count per chip plus a quarter of it, or more
    such quarters while the largest chip needs them.  Until a chip holds
    a quarter more than the mean, the count depends on E and the chip
    count alone, so edits to the graph that keep E and N compile
    nothing anew."""
    src, dst = np.asarray(src), np.asarray(dst)
    e = dst.size
    n_local = -(-n_nodes // (n_chips * block_n)) * block_n
    lo = np.searchsorted(dst, np.arange(n_chips + 1) * n_local)
    first = lo[:-1] // block_e
    end = np.maximum(-(-lo[1:] // block_e), first)
    n_chunks = -(-e // block_e)
    mean = -(-n_chunks // n_chips)
    step = max(1, -(-mean // 4))
    need = int((end - first).max())
    chunks = mean + step * max(1, -(-(need - mean) // step))
    slots = chunks * block_e
    n_tiles = chunks + n_local // block_n
    out_src = np.zeros(n_chips * slots, np.int32)
    out_dst = np.full(n_chips * slots, PAD_DST, np.int32)
    tile_block = np.empty(n_chips * n_tiles, np.int32)
    tile_chunk = np.empty(n_chips * n_tiles, np.int32)
    n_used = np.empty(n_chips, np.int32)
    for d in range(n_chips):
        a, b = first[d] * block_e, min(end[d] * block_e, e)
        local = dst[a:b].astype(np.int64) - d * n_local
        out_src[d * slots:d * slots + b - a] = src[a:b]
        out_dst[d * slots:d * slots + b - a] = local
        lay = tile_layout(local, n_local, block_n, block_e)
        k, t0 = lay.tile_block.size, d * n_tiles
        tile_block[t0:t0 + n_tiles] = lay.tile_block[-1]
        tile_chunk[t0:t0 + n_tiles] = lay.tile_chunk[-1]
        tile_block[t0:t0 + k] = lay.tile_block
        tile_chunk[t0:t0 + k] = lay.tile_chunk
        n_used[d] = lay.n_used[0]
    return DstSplit(out_src, out_dst, tile_block, tile_chunk, n_used,
                    np.diff(lo), n_local, block_n, block_e)
