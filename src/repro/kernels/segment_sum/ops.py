"""Tile layout for the ``segment_sum`` kernel.

:func:`tile_layout` turns a dst-sorted ``dst`` column into the kernel's
tile list, vectorised over blocks: block ``b``'s edges are the sorted
range ``[lo_b, hi_b)`` (one ``searchsorted``), its tiles are the aligned
chunks that range touches (one tile for a block with no edges, so its
output row is written), and the list is padded to the fixed length
``T_max = ceil(E / BE) + ceil(N / BN)``, which bounds the chunks plus one
extra per block boundary.  Compiled shapes then depend on E and N alone.
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
