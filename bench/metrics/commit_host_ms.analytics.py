"""Host self time of the write path per analytics request, in ms: the
host profile's gatekeeper, store and shard stages (the churn commit, its
apply, and the stamp's admission)."""

from bench.harness import host


def read(run):
    return host.per_request(run, host.GATEKEEPER + host.STORE + host.SHARD,
                            1e3)
