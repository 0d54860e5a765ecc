"""Host wall-clock self time per stage, from the program's host profile.

While a JAX profiler trace is captured (a traced run), the program's
simulator installs ``repro.core.obs.HostProfile``, which adds the self
time of each stage to ``Counters.host_ns`` (stage -> ns).  The readers
below take the difference of the counters at the window's two ends.  A
program without a host profile, or an untraced run, leaves ``host_ns``
absent or empty and the readers return None.
"""

from __future__ import annotations

from typing import Iterable, Optional

#: stages by layer (docs/OBSERVABILITY.md, "Host wall-clock profile")
GATEKEEPER = ("gatekeeper", "gk_stamp", "gk_nop", "oracle")
STORE = ("store_commit", "wal_append", "store_gc")
SHARD = ("shard", "shard_order", "shard_apply", "shard_nop", "shard_gc")
FRONTIER = ("frontier_hop", "plan_build", "plan_refresh", "coordinator")
PLAN = ("plan_build", "plan_refresh")
PLANE = ("plane_sync", "plane_scatter", "plane_launch")


def stage_s(run, stages: Iterable[str]) -> Optional[float]:
    """Seconds of self time the window spent in ``stages``."""
    c1 = run.counters1.get("host_ns")
    if not c1:
        return None
    c0 = run.counters0.get("host_ns") or {}
    return sum(c1.get(s, 0) - c0.get(s, 0) for s in stages) * 1e-9


def completed(run) -> int:
    """Requests of the window that completed (those in flight at its
    close were waited for, and the counters include their work)."""
    return sum(1 for t in run.latency_s if t is not None)


def per_request(run, stages: Iterable[str], scale: float) -> Optional[float]:
    """``stages``' self time per completed request, times ``scale``."""
    s = stage_s(run, stages)
    n = completed(run)
    return s / n * scale if s is not None and n else None
