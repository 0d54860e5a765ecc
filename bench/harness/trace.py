"""Profiler capture and the reduction from a trace to numbers.

:func:`capture` records a JAX profiler trace with Python tracing off (a
traced function call per Python call would swamp a host-bound run).
:func:`reduce` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
(:func:`reduce_profile` takes one already read) and returns, for the span named ``bench.window`` on the host:

* ``window_s`` — its length;
* ``busy_s`` — the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices that ran any;
* ``device_ops`` — device seconds per operation (:func:`short_name`),
  largest first;
* ``idle_gaps`` — device idle seconds per host ``bench.*`` span that
  covered them (``"none"`` where no span did), largest first;
* ``events`` — every device operation in the window as
  ``(text, seconds, stats)``, its text the HLO instruction with its
  shapes, for the kernels' roofline readers.

Device operations are read from each ``/device:TPU:<n>`` plane's ``XLA
Ops`` line (every line but ``Steps``/``XLA Modules`` where a plane has
none).  Host
and device events share the profiler's clock.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
_OP_LINE = "XLA Ops"
_NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe")


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


_DEVICE = re.compile(r"^/device:TPU:\d+$")


def short_name(op: str) -> str:
    """``%fusion.3`` of an HLO instruction's text (``%fusion.3 = ...``),
    the name the trace gives a device operation."""
    return op.split(" = ", 1)[0]


def reduce(path: str) -> Dict:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), path)


def reduce_profile(pd, what: str = "the trace") -> Dict:
    host_spans: List[Tuple[float, float, str]] = []
    window = None
    dev_ops: Dict[str, List[Tuple[float, float, str, dict]]] = {}
    for pl in pd.planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    if not e.name.startswith("bench."):
                        continue
                    s, t = e.start_ns, e.start_ns + e.duration_ns
                    if e.name == WINDOW:
                        if window is None or e.duration_ns > \
                                window[1] - window[0]:
                            window = (s, t)
                    else:
                        host_spans.append((s, t, e.name[len("bench."):]))
        elif _DEVICE.match(pl.name):
            lines = list(pl.lines)
            ops_lines = [ln for ln in lines if ln.name == _OP_LINE] or \
                [ln for ln in lines if ln.name not in _NOT_OPS]
            evs = []
            for ln in ops_lines:
                for e in ln.events:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
            if evs:
                dev_ops[pl.name] = evs
    if window is None:
        raise RuntimeError(f"no {WINDOW} span in {what}")
    w0, w1 = window
    window_s = (w1 - w0) * 1e-9
    busy = []
    per_op: Dict[str, float] = {}
    events = []
    idle: Dict[str, float] = {}
    segments = _innermost(host_spans)
    for evs in dev_ops.values():
        clipped = [(max(a, w0), min(b, w1), n, st) for a, b, n, st in evs
                   if b > w0 and a < w1]
        iv = _union([(a, b) for a, b, _, _ in clipped])
        busy.append(sum(b - a for a, b in iv) * 1e-9)
        for a, b, n, st in clipped:
            k = short_name(n)
            per_op[k] = per_op.get(k, 0.0) + (b - a) * 1e-9
            events.append((n, (b - a) * 1e-9, st))
        gaps, prev = [], w0
        for a, b in iv + [(w1, w1)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        _attribute(gaps, segments, idle)
    if not dev_ops:
        _attribute([(w0, w1)], segments, idle)
    n_dev = max(1, len(busy))
    scale = 1.0 / n_dev
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "devices": len(dev_ops),
        "device_ops": sorted(([k, v * scale] for k, v in per_op.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v * scale] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
        "events": events,
    }


def _innermost(spans: List[Tuple[float, float, str]]):
    """Cut the host timeline into segments, each labelled with the
    innermost ``bench.*`` span open over it (spans of one thread nest)."""
    points = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                    + [(t, 0, i) for i, (_, t, _) in enumerate(spans)])
    segments, active, prev = [], [], None
    for x, is_start, i in points:
        if active and prev is not None and x > prev:
            segments.append((prev, x, spans[active[-1]][2]))
        if is_start:
            active.append(i)
        elif i in active:
            active.remove(i)
        prev = x
    return segments


def _attribute(gaps, segments, out: Dict[str, float]) -> None:
    """Add each idle gap's time to the segments' labels that cover it
    (``"none"`` where no span is open)."""
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(segments) and segments[k][0] < b:
            s, t, name = segments[k]
            x, y = max(s, a), min(t, b)
            if y > x:
                out[name] = out.get(name, 0.0) + (y - x) * 1e-9
                covered += y - x
            k += 1
        rest = (b - a) - covered
        if rest > 0:
            out["none"] = out.get("none", 0.0) + rest * 1e-9
