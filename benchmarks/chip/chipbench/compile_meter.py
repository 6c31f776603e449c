"""Compile time and persistent-cache traffic, from JAX's monitoring events.

A copy of the meter in the repository's chip smoke check. While entered,
it sums the trace, lowering and backend-compile durations JAX reports and
counts persistent compilation-cache hits and misses.
"""

from __future__ import annotations

from dataclasses import dataclass

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclass
class CompileMeter:
    """Sums JAX compile time, backend compiles and cache hits while entered."""

    compile_s: float = 0.0
    backend_compiles: int = 0
    cache_hits: int = 0
    cache_misses: int = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += duration
        if event == _BACKEND_COMPILE:
            self.backend_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple[float, int, int, int]:
        return (self.compile_s, self.backend_compiles, self.cache_hits,
                self.cache_misses)

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
