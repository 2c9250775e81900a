"""Process-wide count of JAX backend compiles, for callers that snapshot it
around a window of their own work.

JAX reports every backend compile (a load from the persistent compile cache
included) and every persistent-cache hit through ``jax.monitoring``.
:func:`listen` registers one listener pair, once per process; from then on
:func:`snapshot` returns the running totals, and two snapshots subtract.
Under a profiler capture each compile is also a zero-length ``jax/compile``
annotation carrying its duration, wherever in the process it happened.
Without JAX nothing is counted.
"""
from __future__ import annotations

from typing import NamedTuple

from .trace import annotate_instant

__all__ = ["Compiles", "listen", "snapshot"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Compiles(NamedTuple):
    """Backend compiles, their seconds, and how many of them the persistent
    cache answered."""

    compiles: int = 0
    seconds: float = 0.0
    cache_hits: int = 0

    def __sub__(self, other: "Compiles") -> "Compiles":
        return Compiles(
            self.compiles - other.compiles,
            self.seconds - other.seconds,
            self.cache_hits - other.cache_hits,
        )


_totals = [0, 0.0, 0]
_listening = False


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        _totals[0] += 1
        _totals[1] += duration_secs
        annotate_instant("jax/compile", duration_s=duration_secs)


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        _totals[2] += 1


def listen() -> None:
    """Start counting; compiles before the first call are not counted."""
    global _listening
    if _listening:
        return
    try:
        from jax import monitoring
    except ImportError:  # pragma: no cover - exercised only without JAX
        return
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _listening = True


def snapshot() -> Compiles:
    """The process's totals since :func:`listen` was first called."""
    return Compiles(*_totals)
