"""JAX persistent compilation cache wiring.

Round-block execution trades many small XLA programs for a few large
scanned ones; the large programs are expensive to compile but perfectly
reusable across processes (benchmark grids, CI legs, resumed runs re-trace
byte-identical HLO).  This module turns on JAX's on-disk compilation cache
and exposes hit/miss counters so :func:`repro.telemetry.metrics.jit_cache_stats`
can surface whether a run actually paid for its compiles or loaded them.

``enable_compile_cache(path)`` is idempotent and safe to call before any
program is traced.  The directory is, in order: ``$JAX_COMPILATION_CACHE_DIR``
when it is set (JAX's own variable; no other directory is set then), else
``path``, else the fixed in-checkout ``.jax-compile-cache/`` — a fixed path,
because the path is part of the cache key and a moving directory never hits.
The thresholds are pinned to "cache everything"
(``min_compile_time_secs=0``, ``min_entry_size_bytes=-1``) because the
protocol layer compiles a small, known set of round programs — there is no
long tail of tiny throwaway executables to pollute the cache with.

The counters come from ``jax.monitoring`` events
(``/jax/compilation_cache/cache_hits`` / ``…/cache_misses``); they count
*this process's* lookups, so a warm cache shows hits only after
``jax.clear_caches()`` or in a fresh process.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Any, Dict, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root's ``.jax-compile-cache/`` (listed in .gitignore)
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax-compile-cache")

_lock = threading.Lock()
_state: Dict[str, Any] = {"dir": None, "hits": 0, "misses": 0,
                          "listener": False}


def _on_event(event: str, **kwargs) -> None:  # pragma: no cover - thin shim
    if event == "/jax/compilation_cache/cache_hits":
        _state["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _state["misses"] += 1


def enable_compile_cache(path: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache and start counting
    hits/misses.  Returns the directory in use: ``$JAX_COMPILATION_CACHE_DIR``
    if set, else ``path``, else :data:`DEFAULT_DIR`.  Idempotent: repeated
    calls re-point the directory but register the event listener only
    once."""
    d = os.environ.get(ENV_VAR) or path or DEFAULT_DIR
    import jax

    os.makedirs(d, exist_ok=True)
    with _lock:
        jax.config.update("jax_compilation_cache_dir", d)
        # cache every executable regardless of compile time / size: the
        # protocol layer only builds a handful of round programs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        try:
            # the backend memoises "no cache" at its first compile; if any
            # program was compiled before this call, force a re-read of the
            # (now set) cache dir
            from jax.experimental.compilation_cache import \
                compilation_cache as _jcc
            _jcc.reset_cache()
        except Exception:  # pragma: no cover - private-API drift tolerance
            pass
        if not _state["listener"]:
            jax.monitoring.register_event_listener(_on_event)
            _state["listener"] = True
        _state["dir"] = d
    return d


def compile_cache_stats() -> Dict[str, Any]:
    """Snapshot of the persistent-cache state for telemetry: the directory
    (``None`` = disabled), the number of cache files on disk, and this
    process's lookup hit/miss counters."""
    d = _state["dir"]
    entries = 0
    if d is not None and os.path.isdir(d):
        entries = sum(1 for n in os.listdir(d)
                      if os.path.isfile(os.path.join(d, n)))
    return {"persistent_cache_dir": d,
            "persistent_cache_entries": entries,
            "persistent_cache_hits": _state["hits"],
            "persistent_cache_misses": _state["misses"]}
