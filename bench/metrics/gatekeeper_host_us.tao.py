"""Host self time of the gatekeepers per completed request, in µs: the
host profile's gatekeeper stages (admission, stamping and batch
validation, NOP and announce traffic, the oracle server)."""

from bench.harness import host


def read(run):
    return host.per_request(run, host.GATEKEEPER, 1e6)
