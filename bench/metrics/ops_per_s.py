"""Requests of every kind completed inside the window, over the window's
host-clock length."""


def read(run):
    return run.done_in_window / run.window_s if run.done_in_window else None
