"""Persistent XLA compilation cache.

Everything under ``jit`` is traced once and compiled; at ML-20M scale
the ALS training program costs ~30s of XLA compile — paid, without this
module, on EVERY train, deploy warm-up, and ``/reload``. The framework's
fixed-shape bucketing discipline (ops/ragged.py) exists precisely so
that repeat runs produce byte-identical programs; this module makes
that pay off by caching compiled executables on disk, keyed by program
fingerprint, so warm trains skip XLA entirely.

The reference has no analogue (Spark jobs are interpreted JVM code);
this is a TPU-economics subsystem: compile time is the TPU world's
job-startup tax, as JVM spin-up + jar shipping is Spark's
(SURVEY.md §3.1 runtime notes).

Config:
  JAX_COMPILATION_CACHE_DIR  where set, JAX itself keeps the cache
                             there and this module sets no directory
                             in code; where not, the cache lives at
                             ``<checkout>/.pio_run/compile_cache`` — a
                             fixed path, because the path is part of
                             the cache key and a directory that moves
                             never hits
  PIO_COMPILE_CACHE=0        disable

Multi-process safe: JAX writes entries atomically (temp + rename), so
N trainers sharing one cache dir (e.g. over NFS) only ever read
complete entries; concurrent writers of the same key are idempotent.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from predictionio_tpu.obs import jaxmon

log = logging.getLogger(__name__)

_enabled_dir: Optional[str] = None

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir_default() -> str:
    return os.path.join(_CHECKOUT, ".pio_run", "compile_cache")


def enable_persistent_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on, at the directory
    ``JAX_COMPILATION_CACHE_DIR`` names or else at the checkout's own.

    Idempotent; returns the active cache directory (None when disabled
    via PIO_COMPILE_CACHE=0 or when the default directory cannot be
    created — the framework must run from a read-only checkout, just
    slower).
    """
    global _enabled_dir
    # hit/miss counters + compile-time histograms (obs/jaxmon.py) come
    # up with the cache: every train/deploy/reload path funnels through
    # here, and the counters are wanted even when the cache dir is
    # disabled (all-miss is exactly the signal an operator needs)
    jaxmon.install()
    if os.environ.get("PIO_COMPILE_CACHE", "1") == "0":
        return None
    if _enabled_dir is not None:
        return _enabled_dir
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = cache_dir_default()
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            log.warning("persistent compilation cache unavailable: %s", e)
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    # the default 1s floor skips small serving/eval programs whose
    # recompiles still dominate /reload latency; cache everything
    # that took meaningful compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled_dir = path
    log.info("persistent compilation cache at %s", path)
    return path
