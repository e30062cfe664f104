"""XLA-level compile counts from ``jax.monitoring`` (a copy of
``chip_smoke.CompileLedger``): every lowering handed to the backend,
persistent-cache hits included.  The program's own ``compile_metrics``
counts traces; a recompile without a retrace shows only here."""

from __future__ import annotations

from typing import Any, Dict


class CompileLedger:
    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests = self.hits = self.misses = 0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, secs: float, **kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def snapshot(self) -> Dict[str, int]:
        return {"xla_compile_requests": self.requests,
                "persistent_cache_hits": self.hits,
                "persistent_cache_misses": self.misses}
