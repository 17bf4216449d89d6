"""Compile-time accounting via ``jax.monitoring`` duration events.

The sim backend's hot paths are jitted programs; "where did the time go"
therefore starts with "how much of it was compilation". JAX emits named
duration events for every lowering stage (``/jax/core/compile/
jaxpr_trace_duration``, ``.../jaxpr_to_mlir_module_duration``,
``.../backend_compile_duration``); this module routes them into telemetry
registries as

- ``jax_compiles_total`` — backend-compile count (a recompile detector:
  a loop whose shapes churn shows this climbing per call),
- ``jax_compile_seconds_total{stage=...}`` — wall time per lowering stage,
- ``jax_cache_load_seconds_total`` — wall time reading executables back
  from the persistent compilation cache (``/jax/compilation_cache/
  cache_retrieval_time_sec``: a cache hit replaces the backend compile
  with this load).

``jax.monitoring`` has no per-listener unregister (only a global
``clear_event_listeners``), so ONE process-wide listener is installed on
first use and fans out to a set of subscribed registries; subscription is
what is added and removed. Import of jax is deferred and failure-tolerant:
a sockets-only install (no jax) just reports hooks unavailable.
"""

from __future__ import annotations

from typing import Optional

from p2pnetwork_tpu import concurrency
from p2pnetwork_tpu.telemetry.registry import Registry, default_registry

__all__ = ["install", "uninstall", "installed", "compile_seconds",
           "compile_count"]

_lock = concurrency.lock()
_registries: set = set()
_listener_registered = False

_BACKEND_COMPILE = "backend_compile"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if "/compile/" not in event and event != _CACHE_RETRIEVAL:
        return
    stage = event.rsplit("/", 1)[-1]
    if stage.endswith("_duration"):
        stage = stage[: -len("_duration")]
    with _lock:
        subscribed = list(_registries)
    # Resolved OUTSIDE the lock: default_registry() takes the registry
    # module's own lock (open-call discipline — no nesting). None
    # subscribes "whatever the process default is NOW", so a test that
    # swaps the default registry keeps receiving compile events.
    targets = {default_registry() if r is None else r for r in subscribed}
    for reg in targets:
        if event == _CACHE_RETRIEVAL:
            reg.counter(
                "jax_cache_load_seconds_total",
                "Wall seconds loading executables from the persistent "
                "compilation cache.",
            ).inc(duration)
            continue
        reg.counter(
            "jax_compile_seconds_total",
            "Wall seconds spent in jit lowering/compilation, by stage.",
            ("stage",),
        ).labels(stage=stage).inc(duration)
        if stage == _BACKEND_COMPILE:
            reg.counter(
                "jax_compiles_total",
                "Number of backend (XLA) compilations.",
            ).inc()


def install(registry: Optional[Registry] = None) -> bool:
    """Subscribe ``registry`` to jit compile events — ``None`` means "the
    process default registry, resolved per event" (survives
    ``set_default_registry`` swaps). Idempotent. Returns False when jax (or
    its monitoring API) is unavailable — callers treat compile metrics as
    absent."""
    global _listener_registered
    try:
        import jax.monitoring as monitoring
    except Exception:
        return False
    with _lock:
        if not _listener_registered:
            try:
                # Registration must be atomic with the flag: two racing
                # installs outside the lock would double-register and
                # double-count every compile. jax.monitoring appends to a
                # plain list without locks of its own, so the nesting is
                # acyclic by construction.
                monitoring.register_event_duration_secs_listener(  # graftlint: ignore[lock-open-call]
                    _on_event_duration)
            except Exception:
                return False
            _listener_registered = True
        _registries.add(registry)
    return True


def uninstall(registry: Optional[Registry] = None) -> None:
    """Unsubscribe ``registry`` from compile events (the process listener
    stays — jax.monitoring cannot remove a single listener)."""
    with _lock:
        _registries.discard(registry)


def installed(registry: Optional[Registry] = None) -> bool:
    with _lock:
        return registry in _registries


def compile_seconds(registry: Optional[Registry] = None,
                    stage: str = _BACKEND_COMPILE) -> float:
    """Total wall seconds recorded for one lowering stage so far (callers
    take before/after deltas around the region they attribute)."""
    return (registry or default_registry()).value(
        "jax_compile_seconds_total", stage=stage)


def compile_count(registry: Optional[Registry] = None) -> float:
    return (registry or default_registry()).value("jax_compiles_total")
