"""Share of its bandwidth roofline the mv_visibility kernel reached over
the traced window: the least time the chip's HBM bandwidth allows for the
bytes its launches move (bench.harness.kernel_cost), over the kernel's
device time from the trace."""

from bench.harness import device, kernel_cost


def read(run):
    t = run.trace
    if not t:
        return None
    need = dur = 0.0
    for name, seconds, stats in t["events"]:
        b = kernel_cost.mv_visibility_bytes(name, stats)
        if b is None:
            continue
        need += b
        dur += seconds
    if not dur:
        return None
    bw = device.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / dur
