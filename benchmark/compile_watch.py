"""Every backend compile of the process, from JAX's own monitoring events.

A copy of ``chip_smoke.py``'s ``CompileWatch`` (see PERF.md, Open
questions): the benchmark may not lean on a file outside its own paths.
A persistent-cache hit still counts as a (near-zero-second) compile; JAX
records a miss only when it writes the entry.  Listeners cannot be
unregistered: one watch per process.
"""

import threading


class CompileWatch:
    def __init__(self):
        import jax.monitoring as monitoring
        self._lock = threading.Lock()
        self._compiles = 0
        self._compile_s = 0.0
        self._hits = self._misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._compiles += 1
                self._compile_s += float(secs)

    def _on_event(self, event, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self._hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self._misses += 1

    def snapshot(self) -> dict:
        """Running totals; diff two around a phase."""
        with self._lock:
            return {"compiles": self._compiles,
                    "compile_s": self._compile_s,
                    "cache_hits": self._hits,
                    "cache_misses": self._misses}


def diff(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
