"""Host self time of the shards per completed request, in µs: the host
profile's shard stages (queue-head ordering, apply, NOP receipt, GC of
the partitions, and the rest of each shard event)."""

from bench.harness import host


def read(run):
    return host.per_request(run, host.SHARD, 1e6)
