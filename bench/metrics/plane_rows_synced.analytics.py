"""Rows the device column plane scattered to the device
(DeviceColumnPlane.stats["row_updates"]) per snapshot taken."""


def read(run):
    n = run.spans.count.get("snapshot", 0)
    if not n or "row_updates" not in run.plane1:
        return None
    return (run.plane1["row_updates"] - run.plane0["row_updates"]) / n
