"""Layout preparation + public wrappers for the segment_mp kernel.

``pack_edges`` converts a dst-sorted edge list into the block-ELL layout
the kernel wants: for each destination-node block, its edges padded to a
whole number of ``block_e`` tiles; every block padded to the max tile
count (regular grid).  Pad slots carry src=0 / dst=-1.

``segment_reduce_sorted`` is the scalar sibling used by the
frontier-batched node-program runtime (``repro.core.frontier``) for
per-hop neighbour aggregation: it reduces values over *pre-sorted*
segment keys, returning the compressed ``(unique_keys, reduced)`` form a
frontier exchange wants (the next hop's packed frontier IS the unique
key set).  It is a numpy ``reduceat`` over the sorted runs on every
platform: its inputs and outputs live on the host, and ``sssp`` reduces
float64 distances that a device reduction (x64 off) would round to
float32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .kernel import (DEFAULT_BLOCK_E, DEFAULT_BLOCK_N, segment_mp_pallas)
from .ref import segment_matmul_reduce_ref


def pack_edges(edge_src: np.ndarray, edge_dst: np.ndarray, n_nodes: int,
               block_n: int = DEFAULT_BLOCK_N,
               block_e: int = DEFAULT_BLOCK_E
               ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Returns (src_packed, dst_packed, n_nodes_padded); edges need not be
    pre-sorted."""
    edge_src = np.asarray(edge_src)
    edge_dst = np.asarray(edge_dst)
    order = np.argsort(edge_dst, kind="stable")
    src = edge_src[order]
    dst = edge_dst[order]
    n_pad = -(-n_nodes // block_n) * block_n
    n_blocks = n_pad // block_n
    # edges per block
    blk = dst // block_n
    counts = np.bincount(blk, minlength=n_blocks)
    max_tiles = max(1, int(-(-counts.max() // block_e))) if counts.size \
        else 1
    cap = max_tiles * block_e
    src_packed = np.zeros((n_blocks * cap,), np.int32)
    dst_packed = np.full((n_blocks * cap,), -1, np.int32)
    starts = np.zeros(n_blocks + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    for b in range(n_blocks):
        lo, hi = starts[b], starts[b + 1]
        m = hi - lo
        src_packed[b * cap: b * cap + m] = src[lo:hi]
        dst_packed[b * cap: b * cap + m] = dst[lo:hi]
    return src_packed, dst_packed, n_pad


_REDUCERS = {"min": np.minimum, "max": np.maximum, "sum": np.add}


def segment_starts(keys: np.ndarray) -> np.ndarray:
    """Run starts of a sorted key array: positions where a new segment
    begins (``keys`` must be non-decreasing)."""
    if keys.size == 0:
        return np.zeros(0, np.int64)
    return np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])


def segment_reduce_sorted(values: np.ndarray, keys: np.ndarray,
                          op: str = "min"):
    """Reduce ``values`` over equal runs of the SORTED ``keys``.

    Returns ``(unique_keys, reduced)`` — compressed form, one entry per
    distinct key in ascending order, reduced exactly in ``values``'
    dtype.
    """
    ufunc = _REDUCERS[op]
    values = np.asarray(values)
    keys = np.asarray(keys)
    if keys.size == 0:
        return keys, values[:0]
    starts = segment_starts(keys)
    return keys[starts], ufunc.reduceat(values, starts)


def segment_count_sorted(keys: np.ndarray):
    """(unique_keys, run_lengths) of a sorted key array."""
    keys = np.asarray(keys)
    if keys.size == 0:
        return keys, np.zeros(0, np.int64)
    starts = segment_starts(keys)
    return keys[starts], np.diff(np.r_[starts, keys.size])


def segment_matmul_reduce(x: jnp.ndarray, w: jnp.ndarray,
                          edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                          n_nodes: int,
                          block_n: int = DEFAULT_BLOCK_N,
                          block_e: int = DEFAULT_BLOCK_E,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Drop-in equivalent of the jnp reference (repro.models.mp seam).
    ``interpret=None`` takes the choice of :mod:`repro.kernels.backend`."""
    src_packed, dst_packed, n_pad = pack_edges(
        np.asarray(edge_src), np.asarray(edge_dst), n_nodes,
        block_n, block_e)
    xs = jnp.asarray(x)[src_packed]        # gather (XLA); kernel fuses
    y = segment_mp_pallas(xs, jnp.asarray(dst_packed), jnp.asarray(w),
                          n_pad, block_n=block_n, block_e=block_e,
                          interpret=interpret)
    return y[:n_nodes]
