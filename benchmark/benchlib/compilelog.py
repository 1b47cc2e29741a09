"""Every executable JAX asks its backend for, and how many of those the
persistent cache answered (a copy of `chip_smoke.CompileLog`). JAX has no
way to take a listener back, so a process has one log, made on first use."""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        import jax.monitoring

        self._mu = threading.Lock()
        self.requests: List[Tuple[str, float]] = []  # (fun_name, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == _COMPILE_EVENT:
            with self._mu:
                self.requests.append((str(kw.get("fun_name", "?")), seconds))

    def _event(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            with self._mu:
                self.cache_hits += 1

    def counts(self) -> Tuple[int, int]:
        """(executables asked for, of those answered by the cache)."""
        with self._mu:
            return len(self.requests), self.cache_hits

    def since(self, mark: int) -> List[Tuple[str, float]]:
        with self._mu:
            return list(self.requests[mark:])


_LOG: Optional[CompileLog] = None


def get() -> CompileLog:
    global _LOG
    if _LOG is None:
        _LOG = CompileLog()
    return _LOG
