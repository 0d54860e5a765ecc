"""Host-clock time from the window's start to the completion of the last
analytics request started in it, over the number of those requests."""


def read(run):
    n = sum(1 for k in run.kinds if k == "analytics")
    return run.span_s / n * 1e3 if n else None
