"""Device time of the collective operations (all-gather, all-reduce,
collective-permute, reduce-scatter, all-to-all, with their ``-start`` and
``-done`` forms) over the traced window, averaged over the devices, per
completed analytics request, in ms: the exchange between chips of the
graph kernels split by destination range.  None where the window ran no
collective."""

import re

from bench.harness import host
from bench.harness.trace import short_name

COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|collective-permute"
                        r"|reduce-scatter|all-to-all)(-start|-done)?\b")


def read(run):
    t = run.trace
    n = host.completed(run)
    if not t or not t.get("devices") or not n:
        return None
    s = sum(sec for name, sec, _ in t["events"]
            if COLLECTIVE.match(short_name(name)))
    return s / t["devices"] / n * 1e3 if s else None
