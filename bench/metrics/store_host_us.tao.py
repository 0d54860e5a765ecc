"""Host self time of the backing store per completed request, in µs: the
host profile's commit, WAL append and store GC stages."""

from bench.harness import host


def read(run):
    return host.per_request(run, host.STORE, 1e6)
