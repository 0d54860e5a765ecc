"""Mean host-clock time of pagerank_ga and bfs_levels_ga per analytics
request, to block_until_ready and the results on the host (the
benchmark's span around the calls)."""


def read(run):
    n = run.spans.count.get("kernels", 0)
    return run.spans.total["kernels"] / n * 1e3 if n else None
