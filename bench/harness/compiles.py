"""Counts what JAX traces, compiles and loads from its persistent cache.

A program the window uses for the first time shows as a trace, and as a
backend compilation or a persistent-cache hit; warm-up runs until none
come.  JAX reports a cache hit as a backend compilation too, so
``compiles`` counts the others.
"""

from __future__ import annotations


class CompileCounter:
    _EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
               "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self) -> None:
        import jax

        self.n = {"traces": 0, "compiles": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_) -> None:
        key = self._EVENTS.get(event)
        if key:
            self.n[key] += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1

    def snapshot(self) -> dict:
        out = dict(self.n)
        out["compiles"] -= out["cache_hits"]
        return out

    @staticmethod
    def since(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}
