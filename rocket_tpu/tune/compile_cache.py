"""Persistent compile cache + AOT executable store (ISSUE 15 tentpole).

Every process lifetime used to pay full XLA compilation for every jit
edge it touched — the dominant cost of a fleet spawn, heal, or scale-up
on the CPU proxy and by far the dominant one on real chips.  This module
makes that cost a one-time event per (executable, topology):

- :func:`cache_dir` resolves the per-host cache directory —
  ``$JAX_COMPILATION_CACHE_DIR`` if set (JAX's own variable: whoever
  launches the process places the cache), else the checkout's
  ``experiments/compile_cache/``.  Never a temp name: the path is part
  of the cache key, so a directory that moves never hits.
- :func:`enable_compile_cache` arms JAX's persistent compilation cache
  there (the min-entry-size / min-compile-time knobs opened all the
  way, so even the tiny CPU-proxy executables persist).  The jax
  monitoring listeners that count cache hits/misses and the
  trace-vs-compile time split, and the ``compile_cache/*`` export
  source, are :func:`install_listeners`'s: every ``Launcher`` and
  ``ServingLoop`` calls it, whoever armed the directory.  Idempotent; safe to call from the Launcher, the serve
  worker, and tests in any order.  A directory that cannot be created
  or armed raises.
- :func:`hit_count` is the cheap counter the
  :class:`~rocket_tpu.observe.ledger.RetraceLedger` samples around each
  dispatch to stamp ``CompileRecord.cache_hit`` — a compile that was
  served from disk is visible per edge, not just in aggregate.
- :func:`save_aot` / :func:`load_aot` persist serialized compiled
  executables (``jax.experimental.serialize_executable``) keyed by an
  explicit shape/config string, for backends whose executables
  round-trip; a failure on either side falls through to the persistent
  cache (counted, never raised).

See docs/performance.md "Warm start & compile cache".
"""

from __future__ import annotations

import logging
import os
import pickle
import re
import threading
from typing import Any, Dict, Optional

import jax

logger = logging.getLogger("rocket_tpu.compile_cache")

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

# jax monitoring event names (jax 0.9).
_EV_HITS = "/jax/compilation_cache/cache_hits"
_EV_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_DUR_COMPILE = "/jax/core/compile/backend_compile_duration"
_DUR_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_DUR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_DUR_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

_lock = threading.Lock()
_state: Dict[str, Any] = {
    "enabled_dir": None,      # the dir currently armed, None when off
    "listeners": False,       # monitoring listeners installed (once ever)
    "hits": 0,
    "requests": 0,
    "retrieval_s": 0.0,
    "saved_s": 0.0,
    "backend_compile_s": 0.0,
    "trace_s": 0.0,
    "aot_saved": 0,
    "aot_hits": 0,
    "aot_fallthrough": 0,
}


def cache_dir() -> str:
    """The persistent cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else the checkout's ``experiments/compile_cache/``."""
    env = os.environ.get(_ENV_DIR)
    if env:
        return env
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "experiments", "compile_cache")


def _on_event(event: str, **kwargs: Any) -> None:
    with _lock:
        if event == _EV_HITS:
            _state["hits"] += 1
        elif event == _EV_REQUESTS:
            _state["requests"] += 1


def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
    with _lock:
        if event == _DUR_COMPILE:
            _state["backend_compile_s"] += duration
        elif event == _DUR_RETRIEVAL:
            _state["retrieval_s"] += duration
        elif event == _DUR_TRACE:
            _state["trace_s"] += duration
        elif event == _DUR_SAVED:
            _state["saved_s"] += duration


def install_listeners() -> None:
    """Count cache hits, requests and the trace / compile / retrieval
    seconds from here on, whoever armed the cache directory: every
    ``Launcher`` and ``ServingLoop`` calls this when it is built, so the
    counters run also where the embedding program (a benchmark, a
    service) set ``jax_compilation_cache_dir`` itself.  Once per process
    — jax keeps listeners forever, a second install would double-count."""
    with _lock:
        if not _state["listeners"]:
            from jax._src import monitoring
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _state["listeners"] = True
    from rocket_tpu.observe import export
    export.register_source("compile_cache", snapshot)


def enable_compile_cache() -> str:
    """Arm JAX's persistent compilation cache at :func:`cache_dir` and
    return that directory.  Idempotent — re-arming the same dir is a
    no-op; a changed ``$JAX_COMPILATION_CACHE_DIR`` re-points the cache.
    Raises when the directory cannot be created."""
    directory = cache_dir()
    install_listeners()
    with _lock:
        if _state["enabled_dir"] == directory:
            return directory
        repointing = _state["enabled_dir"] is not None
    os.makedirs(directory, exist_ok=True)
    if repointing:
        # jax pins its cache backend at first use; a config update alone
        # leaves reads/writes on the OLD dir.  Drop the singleton so the
        # new dir actually takes effect.
        from jax._src import compilation_cache as _jcc
        _jcc.reset_cache()
    # JAX read $JAX_COMPILATION_CACHE_DIR into this option at import; the
    # update only matters for the in-checkout default (and for a variable
    # set after import), and never names another directory.
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _lock:
        _state["enabled_dir"] = directory
    logger.info("persistent compile cache armed at %s", directory)
    return directory


def enabled_dir() -> Optional[str]:
    with _lock:
        return _state["enabled_dir"]


def hit_count() -> int:
    """Cumulative persistent-cache hits this process (cheap; sampled by
    the retrace ledger around each dispatch)."""
    with _lock:
        return int(_state["hits"])


def listening() -> bool:
    """Whether :func:`install_listeners` has run: before it, the counters
    read zero because nothing counts, not because nothing compiled."""
    with _lock:
        return bool(_state["listeners"])


def hits_and_misses() -> "tuple[int, int]":
    """``(hits, misses)`` of the persistent cache so far (cheap: no walk
    of the directory, unlike :func:`snapshot`)."""
    with _lock:
        hits, requests = int(_state["hits"]), int(_state["requests"])
    return hits, max(0, requests - hits)


def reset_stats() -> None:
    """Zero the counters (the armed dir and listener install survive)."""
    with _lock:
        for key in ("hits", "requests", "retrieval_s", "saved_s",
                    "backend_compile_s", "trace_s", "aot_saved",
                    "aot_hits", "aot_fallthrough"):
            _state[key] = 0 if isinstance(_state[key], int) else 0.0


def snapshot() -> Dict[str, float]:
    """Flat float dict for the ``compile_cache/*`` export source:
    hit/miss/request counters, the time split, and the on-disk
    entry/byte footprint."""
    with _lock:
        out = {
            "hits": float(_state["hits"]),
            "requests": float(_state["requests"]),
            "misses": float(max(0, _state["requests"] - _state["hits"])),
            "retrieval_s": float(_state["retrieval_s"]),
            "saved_s": float(_state["saved_s"]),
            "backend_compile_s": float(_state["backend_compile_s"]),
            "trace_s": float(_state["trace_s"]),
            "aot_saved": float(_state["aot_saved"]),
            "aot_hits": float(_state["aot_hits"]),
            "aot_fallthrough": float(_state["aot_fallthrough"]),
        }
        directory = _state["enabled_dir"]
    entries, nbytes = 0, 0
    if directory and os.path.isdir(directory):
        try:
            for dirpath, _dirs, files in os.walk(directory):
                for fname in files:
                    try:
                        nbytes += os.path.getsize(os.path.join(dirpath, fname))
                        entries += 1
                    except OSError:
                        continue
        except OSError:
            pass
    out["entries"] = float(entries)
    out["bytes"] = float(nbytes)
    return out


# -- AOT executable store ----------------------------------------------------

def aot_key(name: str, **shape_config: Any) -> str:
    """A filesystem-safe key for one compiled executable: the edge name
    plus every shape/config field that selects a distinct executable
    (batch, n_draft, dtype, device count...)."""
    parts = [name] + [f"{k}={shape_config[k]}" for k in sorted(shape_config)]
    return re.sub(r"[^A-Za-z0-9_.=-]+", "-", "_".join(parts))


def _aot_path(key: str) -> Optional[str]:
    base = enabled_dir()
    if base is None:
        return None
    return os.path.join(base, "aot", key + ".pkl")


def save_aot(key: str, compiled: Any) -> bool:
    """Serialize a compiled executable under ``key``.  Returns True on
    success; any failure (backend refuses, pickling fails) counts as
    fall-through — the persistent cache still covers the edge."""
    path = _aot_path(key)
    if path is None:
        return False
    try:
        from jax.experimental import serialize_executable
        payload = serialize_executable.serialize(compiled)
        # the ids of the devices it was compiled for: a reload runs on
        # those, not on every device the process has
        devices = [d.id for d in
                   compiled.runtime_executable().local_devices()]
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            pickle.dump((payload, devices), f)
        os.replace(tmp, path)
    except Exception:
        with _lock:
            _state["aot_fallthrough"] += 1
        logger.debug("AOT serialize fell through for %s", key, exc_info=True)
        return False
    with _lock:
        _state["aot_saved"] += 1
    return True


def load_aot(key: str) -> Optional[Any]:
    """Deserialize a compiled executable saved under ``key``; ``None``
    on any failure (missing, version skew, backend mismatch) — callers
    fall through to ``lower().compile()`` against the persistent cache."""
    path = _aot_path(key)
    if path is None or not os.path.exists(path):
        return None
    try:
        from jax.experimental import serialize_executable
        with open(path, "rb") as f:
            payload, devices = pickle.load(f)
        by_id = {d.id: d for d in jax.local_devices()}
        # a KeyError (a device this process lacks) falls through too
        compiled = serialize_executable.deserialize_and_load(
            *payload, execution_devices=[by_id[i] for i in devices])
    except Exception:
        with _lock:
            _state["aot_fallthrough"] += 1
        logger.debug("AOT deserialize fell through for %s", key,
                     exc_info=True)
        return None
    with _lock:
        _state["aot_hits"] += 1
    return compiled
