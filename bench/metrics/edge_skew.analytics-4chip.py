"""Edges of the largest chip's destination range over the mean chip's,
over the window's graph kernel calls split by destination range (the
program's ``split_*`` counters at the window's two ends).  None where the
program has no such counters or made no such call."""


def read(run):
    c0, c1 = run.counters0, run.counters1
    if "split_calls" not in c1:
        return None
    d = {k: c1[k] - c0.get(k, 0) for k in ("split_calls", "split_chips",
                                           "split_edge_slots",
                                           "split_edge_slots_max")}
    if not d["split_calls"] or not d["split_edge_slots"]:
        return None
    return (d["split_edge_slots_max"] / d["split_calls"]) / \
        (d["split_edge_slots"] / d["split_chips"])
