"""Mesh factory for the device-sharded column plane.

Defined as a FUNCTION so importing this module never touches jax device
state.
"""

from __future__ import annotations


def make_columns_mesh(n_devices=None):
    """1-D ``("data",)`` mesh over (up to) all local devices for the
    device-sharded columnar data plane (``repro.dist.columns``).

    Deliberately NOT registered through ``repro.dist.set_mesh`` — the
    plane passes it to ``shard_map`` explicitly, so the process-global
    mesh that the model helpers consult stays whatever the deployment
    installed (usually unset on CPU).
    """
    import jax

    devices = jax.devices()
    n = len(devices) if n_devices is None else min(int(n_devices),
                                                   len(devices))
    return jax.make_mesh((n,), ("data",), devices=devices[:n])
