"""Host self time of the graph kernels' split layout per completed
analytics request, in ms: the host profile's ``kernel_layout`` stage
(the CSC cut by destination range, each chip's tile list, padding and
the transfers to the chips).  None where the program has no such
stage."""

from bench.harness import host


def read(run):
    if "kernel_layout" not in (run.counters1.get("host_ns") or {}):
        return None
    return host.per_request(run, ("kernel_layout",), 1e3)
