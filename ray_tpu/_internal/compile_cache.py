"""JAX's persistent compilation cache, placed from outside the code.

The serving model traces a Python loop of L blocks and prefill compiles
once per prompt length; every replica process and every cold run would pay
all of it again. One helper, called where a process may first compile on
the chip (a chip-owning worker's start, a script that jits in-process):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
  set in code (worker subprocesses inherit the variable);
- unset: ``jax_compilation_cache_dir`` is ``<checkout>/.jax_cache``. The
  directory is part of what a deployment keeps warm, so it is a fixed
  path: never a temporary name, a pid or a time.

The same call starts counting what this process compiles (JAX's own
monitoring events), which is what ``stats()`` reports: seconds in the
backend compiler, seconds tracing and lowering, and how many compile
requests the persistent cache answered.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_lock = threading.Lock()
_counting = False
_stats = {
    "compile_s": 0.0,  # backend compile, cache retrieval included
    "trace_lower_s": 0.0,  # jaxpr tracing + MLIR lowering
    "programs": 0,
    "cache_requests": 0,
    "cache_hits": 0,
}
_compile_s_by_name: Dict[str, float] = {}  # the event's fun_name, summed

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE_LOWER = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_duration(event: str, duration_secs: float, fun_name: str = "",
                 **_kw) -> None:
    with _lock:
        if event == _BACKEND_COMPILE:
            _stats["compile_s"] += duration_secs
            _stats["programs"] += 1
            if fun_name:
                _compile_s_by_name[fun_name] = (
                    _compile_s_by_name.get(fun_name, 0.0) + duration_secs)
        elif event in _TRACE_LOWER:
            _stats["trace_lower_s"] += duration_secs


def _on_event(event: str, **_kw) -> None:
    with _lock:
        if event == _CACHE_REQUEST:
            _stats["cache_requests"] += 1
        elif event == _CACHE_HIT:
            _stats["cache_hits"] += 1


def configure() -> str:
    """Place the cache (see module docstring) and start counting compiles.
    Returns the directory in use. Idempotent."""
    global _counting
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    with _lock:
        if not _counting:
            _counting = True
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
    return path


def stats() -> Dict[str, float]:
    """What this process compiled since ``configure()``."""
    with _lock:
        return dict(_stats)


def totals_us() -> Dict[str, object]:
    """``stats()`` as ``worker.startup`` carries it (whole microseconds),
    with the three names that cost the backend compiler most (``slowest``:
    ``name:seconds`` joined by ``+``, since a comma ends a stat in the
    profiler's encoding of a region). This JAX passes the jitted function's
    name with the event, so a prompt length's prefill programs add up under
    one. Empty before ``configure()``: nothing was counted."""
    with _lock:
        if not _counting:
            return {}
        worst = sorted(_compile_s_by_name.items(), key=lambda kv: -kv[1])[:3]
        totals = {
            "compile_us": round(_stats["compile_s"] * 1e6),
            "trace_lower_us": round(_stats["trace_lower_s"] * 1e6),
            "programs": _stats["programs"],
            "cache_requests": _stats["cache_requests"],
            "cache_hits": _stats["cache_hits"],
        }
        if worst:
            totals["slowest"] = "+".join(f"{n}:{s:.1f}" for n, s in worst)
        return totals
