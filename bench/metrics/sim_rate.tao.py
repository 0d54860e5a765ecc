"""Simulated seconds the cluster advanced per host second of the window
(the simulator's pace; below 1 the host, not the wall clock, sets it)."""


def read(run):
    return (run.sim1 - run.sim0) / run.window_s if run.window_s else None
