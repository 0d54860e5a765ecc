"""95th percentile (nearest rank) of the host-clock latency of every read
issued in the window, from issue to completion callback; reads still in
flight at the close are waited for and counted."""

from bench.harness import reference, stats


def read(run):
    lat = [t for k, t in zip(run.kinds, run.latency_s)
           if k in reference.READS and t is not None]
    return stats.percentile(lat, 95) * 1e3 if lat else None
