"""The data plane's backend choice, made in one place.

Two platforms are supported:

* ``tpu`` — Pallas kernels compile through Mosaic, and batched stamp
  comparisons run on the device;
* ``cpu`` — batched stamp comparisons run in numpy, and Pallas kernels
  run with ``interpret=True`` (there is no Mosaic backend).

Any other platform raises: a GPU would otherwise be handed a TPU Mosaic
kernel and fail without saying why.

:func:`pow2` is the one bucketing rule for the row counts of device
launches and resident blocks (:func:`pad_pow2` applies it), and
:func:`use_compile_cache` places JAX's persistent compilation cache for
the entry points (``chip_smoke.py``, ``benchmarks/run.py``).
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

SUPPORTED = ("tpu", "cpu")

#: the checkout's fixed cache directory (the path is part of the cache
#: key, so it must not move between runs); ignored by git
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def platform() -> str:
    """``jax.default_backend()``, checked against :data:`SUPPORTED`."""
    import jax

    p = jax.default_backend()
    if p not in SUPPORTED:
        raise RuntimeError(
            f"the data plane runs on {' or '.join(SUPPORTED)}, not {p!r}; "
            f"set JAX_PLATFORMS to one of them")
    return p


def on_device() -> bool:
    """True on the TPU, where batched stamp work runs on the device."""
    return platform() == "tpu"


def interpret(requested: Optional[bool] = None) -> bool:
    """Resolve a Pallas ``interpret`` argument: an explicit bool wins,
    ``None`` means compiled on the TPU and interpreted on the CPU."""
    if requested is not None:
        return requested
    return platform() == "cpu"


def pow2(n: int) -> int:
    """The smallest power of two at or above ``n`` (1 for ``n <= 1``)."""
    return 1 << max(0, n - 1).bit_length()


def pad_pow2(a: np.ndarray, fill, floor: int = 1) -> np.ndarray:
    """Pad ``a``'s rows with ``fill`` (a scalar, or one row) up to
    :func:`pow2` of its length (at least ``floor``), so device launches
    over batches of every size compile one program per bucket instead
    of one per size."""
    m = a.shape[0]
    pad = max(floor, pow2(m)) - m
    if not pad:
        return a
    return np.concatenate([a, np.full((pad,) + a.shape[1:], fill, a.dtype)])


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads
    it itself), else :data:`DEFAULT_CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
