"""Public wrapper: accepts the (N, C) row-major layout used by
``repro.core.clock.pack_many``, pads N to the block size, and dispatches
to the Pallas kernel.

Backend selection: ``interpret=None`` (the default) takes the choice of
:mod:`repro.kernels.backend` — compiled Pallas on the TPU, interpreter
mode on the CPU (where no Mosaic backend exists).  Pass an explicit bool
to override (tests force ``interpret=True`` to exercise the kernel body
on CPU).  :data:`CALLS` counts host dispatches by the mode they ran in.
"""

from __future__ import annotations

from collections import Counter
from typing import Optional

import jax.numpy as jnp

from .. import backend
from .kernel import DEFAULT_BLOCK_N, NO_STAMP, before_pallas, \
    visibility_pallas
from .ref import before_cm, visibility_ref

#: host dispatches by mode: "compiled", "interpreted" or "ref"
CALLS: Counter = Counter()


def _mode(interpret: Optional[bool], use_ref: bool) -> bool:
    """Resolve ``interpret`` and count the dispatch."""
    interpret = backend.interpret(interpret)
    CALLS["ref" if use_ref else
          "interpreted" if interpret else "compiled"] += 1
    return interpret


def visibility_mask(create_rows: jnp.ndarray, delete_rows: jnp.ndarray,
                    q: jnp.ndarray, block_n: int = DEFAULT_BLOCK_N,
                    interpret: Optional[bool] = None,
                    use_ref: bool = False) -> jnp.ndarray:
    """(N, C) stamp rows + (C,) query -> (N,) bool visibility mask."""
    interpret = _mode(interpret, use_ref)
    n, c = create_rows.shape
    create_cm = jnp.asarray(create_rows).T
    delete_cm = jnp.asarray(delete_rows).T
    q = jnp.asarray(q)
    if use_ref:
        return visibility_ref(create_cm, delete_cm, q)
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        pad = n_pad - n
        create_cm = jnp.pad(create_cm, ((0, 0), (0, pad)),
                            constant_values=NO_STAMP)
        delete_cm = jnp.pad(delete_cm, ((0, 0), (0, pad)),
                            constant_values=NO_STAMP)
    mask = visibility_pallas(create_cm, delete_cm, q, block_n=block_n,
                             interpret=interpret)
    return mask[:n]


def before_mask(rows: jnp.ndarray, q: jnp.ndarray,
                block_n: int = DEFAULT_BLOCK_N,
                interpret: Optional[bool] = None,
                use_ref: bool = False) -> jnp.ndarray:
    """(N, C) stamp rows + (C,) query -> (N,) bool ``row ≺ q`` mask
    (the single-table half of :func:`visibility_mask`).  Traced inside
    an outer ``jit`` it counts once per trace, not per launch."""
    interpret = _mode(interpret, use_ref)
    n, c = rows.shape
    rows_cm = jnp.asarray(rows).T
    q = jnp.asarray(q)
    if use_ref:
        return before_cm(rows_cm, q)
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        rows_cm = jnp.pad(rows_cm, ((0, 0), (0, n_pad - n)),
                          constant_values=NO_STAMP)
    return before_pallas(rows_cm, q, block_n=block_n,
                         interpret=interpret)[:n]
