"""Runtime recompile sentry.

The engine's compile-once contract (PR 1: one jitted masked-γ step per
``(gamma_max[, b_max])`` shape family; PR 7: one tree program across the
whole (γ, b) grid) used to be re-checked per bench with ad-hoc
``engine.compiled_programs()`` deltas. This module is the one shared
counter: a process-global listener on jax's monitoring events counts
actual XLA backend compilations, and :func:`compile_guard` turns "this
region must not compile" into a context manager that raises on exit.

Two counters, two purposes:

- :func:`total_backend_compiles` — backend compiles since the listener
  was installed. What :func:`compile_guard` snapshots; also what
  ``tests/conftest.py`` reports when the jit-cache teardown workaround
  is disabled.
- :func:`jit_cache_programs` — traced-program count of an explicit jit
  cache (the engine's ``_jit_cache``). Per-engine, survives unrelated
  compiles elsewhere in the process; what ``engine.compiled_programs()``
  delegates to.

The same listener counts persistent compilation-cache hits
(:func:`persistent_cache_hits`), which ``chip_smoke.py`` reports per
phase.
"""

from __future__ import annotations

import threading

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_installed = False
_count = 0
_cache_hits = 0


def _on_event(event: str, duration: float, **kwargs) -> None:
    global _count
    if event == _COMPILE_EVENT:
        with _lock:
            _count += 1


def _on_cache_event(event: str, **kwargs) -> None:
    global _cache_hits
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _cache_hits += 1


def install_compile_listener() -> None:
    """Idempotently hook jax's monitoring stream. jax offers no
    unregistration, so one process-global listener is installed once and
    guards snapshot the counter instead of adding/removing hooks."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_event)
    monitoring.register_event_listener(_on_cache_event)


def total_backend_compiles() -> int:
    """XLA backend compilations observed since the listener was installed
    (0 compiles before :func:`install_compile_listener` are invisible —
    install early, e.g. at bench/conftest import)."""
    install_compile_listener()
    return _count


def persistent_cache_hits() -> int:
    """Programs loaded from the persistent compilation cache since the
    listener was installed."""
    install_compile_listener()
    return _cache_hits


def jit_cache_programs(fns) -> int:
    """Total traced programs across an iterable of jitted callables (an
    engine's ``_jit_cache.values()``)."""
    return sum(fn._cache_size() for fn in fns)


class RecompileError(RuntimeError):
    """A guarded region compiled more XLA programs than it declared."""


class CompileGuard:
    """Context manager asserting a bounded number of compiles.

    ``allowed`` is the number of compilations the region may perform
    (0 for steady-state regions: everything must already be warm;
    ``None`` to only count — benches that *report* recompiles instead of
    crashing). ``.count`` is live inside the region; on a clean exit the
    guard raises :class:`RecompileError` iff ``count > allowed``. An
    exception already propagating out of the region takes precedence.

    Without ``track``, ``.count`` is the process-global backend-compile
    delta — the strictest sentry (any XLA compilation anywhere counts).
    With ``track=[engine, ...]`` (objects exposing ``compiled_programs()``),
    ``.count`` is the tracked engines' program-count delta instead: the
    compile-ONCE invariant on the decode step programs specifically,
    insensitive to incidental host-side utility jits (a ``jnp.mean`` over
    a fresh shape between measured cells compiles a one-op program that
    is not a step recompile). Benches gate on tracked counts and can
    still report :attr:`backend_compiles` for diagnostics.
    """

    def __init__(self, allowed: int | None = 0, what: str = "",
                 track=None):
        self.allowed = None if allowed is None else int(allowed)
        self.what = what
        self.track = list(track) if track else None
        self._start = 0
        self._track_start = 0

    def _tracked_programs(self) -> int:
        return sum(t.compiled_programs() for t in self.track)

    def __enter__(self) -> "CompileGuard":
        install_compile_listener()
        self._start = _count
        if self.track:
            self._track_start = self._tracked_programs()
        return self

    @property
    def backend_compiles(self) -> int:
        """Global XLA backend compilations inside the region."""
        return _count - self._start

    @property
    def count(self) -> int:
        if self.track:
            return self._tracked_programs() - self._track_start
        return self.backend_compiles

    def __exit__(self, exc_type, exc, tb) -> bool:
        if (exc_type is None and self.allowed is not None
                and self.count > self.allowed):
            label = f" in {self.what}" if self.what else ""
            raise RecompileError(
                f"{self.count} XLA compile(s){label} where at most "
                f"{self.allowed} allowed — the compile-once invariant is "
                f"broken (a traced shape/dtype/static arg is varying)")
        return False


def compile_guard(allowed: int | None = 0, what: str = "",
                  track=None) -> CompileGuard:
    """``with compile_guard(allowed=0, what="steady-state decode"): ...``"""
    return CompileGuard(allowed=allowed, what=what, track=track)
