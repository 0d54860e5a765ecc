"""The chip a run is on, and the table of its peaks.

:data:`PEAKS` is keyed by ``device_kind`` as JAX reports it.  A device
that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: per-chip peaks; source: Google Cloud documentation, "TPU v5e"
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> Tuple[str, str, int]:
    """(platform, device_kind, device count) of a TPU host holding at
    least ``chips`` chips of a kind in :data:`PEAKS`; raises otherwise."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    peaks(dev.device_kind)
    return dev.platform, dev.device_kind, len(devices)


def peaks(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise NoChip(f"no peaks known for device kind {kind!r}")
    return PEAKS[kind]
