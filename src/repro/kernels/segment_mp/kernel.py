"""Pallas TPU kernel: fused (gather -> matmul -> segment-reduce).

TPU adaptation of the FusedMM/GE-SpMM GPU pattern (DESIGN.md §3):
instead of warp-per-row scatter with atomics (no TPU analogue), edges are
**pre-sorted by destination** and packed into a block-ELL layout so that

* each grid row ``i`` owns a contiguous destination-node block
  ``[i*BN, (i+1)*BN)`` and the edge tiles that target it,
* the inner grid dim ``j`` streams that block's edge tiles; the gathered
  source features ``xs`` arrive as ``(BE, D)`` VMEM tiles,
* the segment reduction is a **one-hot matmul on the MXU**:
  ``out += onehot(dst_local) @ (xs @ W)`` — a (BN, BE) x (BE, F) product,
  which is the TPU-idiomatic replacement for scatter-add,
* the output block lives in VMEM across all ``j`` iterations (its
  BlockSpec index ignores ``j``) and accumulates.

Padding edges carry ``dst = -1`` and never match a one-hot row.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend

DEFAULT_BLOCK_N = 128     # destination nodes per block
DEFAULT_BLOCK_E = 256     # edges per tile


def _mp_kernel(xs_ref, dst_ref, w_ref, out_ref, acc_ref, *,
               block_n: int):
    j = pl.program_id(1)
    n_tiles = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs = xs_ref[...]                   # (BE, D) gathered source features
    dst = dst_ref[...]                 # (1, BE) global dst ids (-1 = pad)
    w = w_ref[...]                     # (D, F)
    h = jnp.dot(xs, w, preferred_element_type=jnp.float32)   # (BE, F)
    i = pl.program_id(0)
    row_base = i * block_n
    rows = row_base + jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
    onehot = (dst == rows).astype(h.dtype)                   # (BN, BE)
    # fp32 accumulation across edge tiles (better than the bf16 ref)
    acc_ref[...] += jnp.dot(onehot, h,
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_tiles - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "block_n", "block_e",
                                    "interpret"))
def segment_mp_pallas(xs_packed: jnp.ndarray, dst_packed: jnp.ndarray,
                      w: jnp.ndarray, n_nodes: int,
                      block_n: int = DEFAULT_BLOCK_N,
                      block_e: int = DEFAULT_BLOCK_E,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """xs_packed (n_blocks*max_tiles*BE, D) gathered + padded features,
    dst_packed (n_blocks*max_tiles*BE,) global dst ids (-1 pad),
    w (D, F) -> y (n_nodes_padded, F) with n_nodes_padded = n_blocks*BN.
    ``interpret=None`` takes the choice of :mod:`repro.kernels.backend`.
    """
    d = xs_packed.shape[1]
    f = w.shape[1]
    n_blocks = n_nodes // block_n
    assert n_nodes % block_n == 0
    total_e = xs_packed.shape[0]
    max_tiles = total_e // (n_blocks * block_e)
    assert max_tiles * n_blocks * block_e == total_e, \
        (total_e, n_blocks, block_e)

    kernel = functools.partial(_mp_kernel, block_n=block_n)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks, max_tiles),
        in_specs=[
            pl.BlockSpec((block_e, d), lambda i, j: (i * max_tiles + j, 0)),
            pl.BlockSpec((1, block_e), lambda i, j: (0, i * max_tiles + j)),
            pl.BlockSpec((d, f), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, f), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_nodes, f), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, f), jnp.float32)],
        interpret=backend.interpret(interpret),
    )(xs_packed, dst_packed[None, :], w)
    return out
