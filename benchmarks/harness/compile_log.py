# Copied from chip_smoke.py's CompileLog (the original stays there for
# the smoke; PERF.md lists it under Open questions).
"""CompileLog: count lowerings, backend compiles and cache hits."""
import jax


class CompileLog:
    """jax.monitoring listener: backend-compile seconds, lowerings per
    function name and persistent-cache hits/misses. `mark()` returns a
    snapshot; `lowerings_since(mark)` names what lowered after it."""

    def __init__(self):
        self.compile_seconds = 0.0
        self.lowerings = {}
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += seconds
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            name = str(kwargs.get("fun_name", "?"))
            self.lowerings[name] = self.lowerings.get(name, 0) + 1

    def _event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> dict:
        return dict(self.lowerings)

    def lowerings_since(self, mark: dict) -> dict:
        return {name: count - mark.get(name, 0)
                for name, count in self.lowerings.items()
                if count > mark.get(name, 0)}
