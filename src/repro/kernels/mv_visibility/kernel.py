"""Pallas TPU kernel: batched refinable-timestamp visibility masks.

Layout: stamps component-major ``(C, N)`` so the object axis N rides the
128-wide lanes and the tiny component axis C (epoch + G gatekeeper
counters, typically 2-9) rides sublanes; the all/any reductions are
sublane reductions, and each grid step streams a ``(C, BLOCK_N)`` tile of
creates + deletes through VMEM.  Every tile compares against the same
``(C, 1)`` query block.  Masks leave the kernel as 0/1 int32 rows and
become bool in the wrappers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import backend

NO_STAMP = np.iinfo(np.int32).max

DEFAULT_BLOCK_N = 1024


def _before_block(q, rows):
    """``rows ≺ q`` for one tile: rows (C, BN), q (C, 1) -> (1, BN) int32.

    Every value stays rank 2 and every reduction keeps its axis: Mosaic
    cannot truncate a rank-1 vector to i1, so the gatekeeper-component
    all() reductions are written as int32 max() over sublanes and the
    result leaves the kernel as a 0/1 int32 row.
    """
    e, qe = rows[0:1, :], q[0:1, :]
    comp, qc = rows[1:, :], q[1:, :]
    gt = jnp.max((comp > qc).astype(jnp.int32), axis=0, keepdims=True)
    ne = jnp.max((comp != qc).astype(jnp.int32), axis=0, keepdims=True)
    before = (e < qe) | ((e == qe) & (gt == 0) & (ne != 0))
    return jnp.where(e == NO_STAMP, 0, before.astype(jnp.int32))


def _visibility_kernel(q_ref, create_ref, delete_ref, out_ref):
    q = q_ref[...]                      # (C, 1)
    out_ref[...] = (_before_block(q, create_ref[...])
                    * (1 - _before_block(q, delete_ref[...])))


def _before_kernel(q_ref, rows_ref, out_ref):
    out_ref[...] = _before_block(q_ref[...], rows_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def before_pallas(rows_cm: jnp.ndarray, q: jnp.ndarray,
                  block_n: int = DEFAULT_BLOCK_N,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """rows (C, N) int32, q (C,) -> (N,) bool ``row ≺ q``.

    The single-table half of :func:`visibility_pallas` — same (C, N)
    layout, block specs and grid; the device-sharded column plane
    launches it per mesh device where create and delete tables live in
    one stacked block and want independent masks.
    """
    c_dim, n = rows_cm.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    out = pl.pallas_call(
        _before_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c_dim, 1), lambda i: (0, 0)),      # q (broadcast)
            pl.BlockSpec((c_dim, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=backend.interpret(interpret),
    )(q[:, None], rows_cm)
    return out[0] != 0


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def visibility_pallas(create_cm: jnp.ndarray, delete_cm: jnp.ndarray,
                      q: jnp.ndarray, block_n: int = DEFAULT_BLOCK_N,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """create/delete (C, N) int32, q (C,) -> (N,) bool.

    N must be a multiple of ``block_n`` (ops.py pads).  ``interpret=None``
    takes the choice of :mod:`repro.kernels.backend` (compiled on the
    TPU, interpreted on the CPU); it is a static arg, so the branch
    resolves at trace time.
    """
    c_dim, n = create_cm.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    out = pl.pallas_call(
        _visibility_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c_dim, 1), lambda i: (0, 0)),      # q (broadcast)
            pl.BlockSpec((c_dim, block_n), lambda i: (0, i)),
            pl.BlockSpec((c_dim, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=backend.interpret(interpret),
    )(q[:, None], create_cm, delete_cm)
    return out[0] != 0
