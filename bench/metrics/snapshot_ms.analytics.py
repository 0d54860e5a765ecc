"""Mean host-clock time of SnapshotEngine.snapshot per analytics request
(the benchmark's span around the call)."""


def read(run):
    n = run.spans.count.get("snapshot", 0)
    return run.spans.total["snapshot"] / n * 1e3 if n else None
