"""Host self time of frontier plan builds and delta refreshes per
analytics request, in ms (the host profile's plan_build and
plan_refresh stages)."""

from bench.harness import host


def read(run):
    return host.per_request(run, host.PLAN, 1e3)
