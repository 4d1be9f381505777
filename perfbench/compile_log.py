"""Which programs JAX built while a job ran, and when: every executable it
compiled or read from the persistent cache (both end in
`/jax/core/compile/backend_compile_duration`), and which of them the cache
held (`/jax/compilation_cache/cache_hits`). The serve jobs count those of the
measured window, which has to hold none, and say what set-up spent on them.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

BUILT = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Listens between `with` and its end; the times are the host's
    `perf_counter`, as the window's edges are."""

    def __init__(self) -> None:
        self.built: List[Tuple[float, float]] = []   # (when it ended, seconds)
        self.hits: List[float] = []

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == BUILT:
            self.built.append((time.perf_counter(), float(seconds)))

    def _on_event(self, event: str, **_kw) -> None:
        if event == HIT:
            self.hits.append(time.perf_counter())

    def __enter__(self) -> "CompileLog":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def between(self, lo: float, hi: float) -> Dict[str, float]:
        """Programs built in `[lo, hi)`: how many, how many of them came
        from the cache, and the seconds they took together."""
        mine = [s for t, s in self.built if lo <= t < hi]
        return {"programs": len(mine),
                "cache_hits": sum(1 for t in self.hits if lo <= t < hi),
                "seconds": sum(mine)}
