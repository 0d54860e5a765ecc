"""Host self time of the frontier runtime per completed request, in µs:
the host profile's frontier hops, plan builds and refreshes, and the
node-program coordinator."""

from bench.harness import host


def read(run):
    return host.per_request(run, host.FRONTIER, 1e6)
