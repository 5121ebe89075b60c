"""Seconds JAX spends tracing, lowering and compiling, from its own
monitoring events (persistent-cache reads included), and the number of
backend compiles and persistent-cache hits and misses."""
from __future__ import annotations

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = self.misses = 0
        self.names: list[str] = []      # programs compiled, in order
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, fun_name="", **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.names.append(str(fun_name))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "compiles": self.compiles,
                "hits": self.hits, "misses": self.misses,
                "names": list(self.names)}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        d = {k: b[k] - a[k] for k in a if k != "names"}
        d["names"] = b["names"][len(a["names"]):]
        return d
