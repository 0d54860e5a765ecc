"""Frontier plan cold builds plus delta refreshes (Counters) per 1,000
requests completed in the window."""


def read(run):
    n = run.done_in_window
    c0, c1 = run.counters0, run.counters1
    builds = (c1["plan_cold_builds"] - c0["plan_cold_builds"]
              + c1["plan_delta_refreshes"] - c0["plan_delta_refreshes"])
    return 1e3 * builds / n if n else None
