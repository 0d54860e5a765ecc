"""Host self time of the device column plane per snapshot, in ms: the
host profile's plane_sync (reading the partitions' change feeds),
plane_scatter (dispatching the row scatters) and plane_launch (the
visibility launch until its masks are on the host), wherever the plane
is synced, over the snapshots taken."""

from bench.harness import host


def read(run):
    s = host.stage_s(run, host.PLANE)
    n = run.spans.count.get("snapshot", 0) if run.spans else 0
    return s / n * 1e3 if s is not None and n else None
