"""Native (C++) runtime components.

The reference delegates its heavy lifting to external JVM systems
(SURVEY.md §0: Spark, HBase, ES); this package holds the single-binary
native equivalents: the event-log storage engine (eventlog.cpp) and the
host-side ragged-data binning used by the training input pipeline.

Libraries are compiled on first use with the system toolchain and cached
under ``_build/``; loading is via ctypes (no pybind11 dependency).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.environ.get("PIO_NATIVE_BUILD_DIR", os.path.join(_HERE, "_build"))
_CXX = os.environ.get("PIO_CXX", "g++")

_lock = threading.Lock()
_cache: dict = {}


class NativeBuildError(RuntimeError):
    pass


def build_library(name: str, extra_flags: Optional[list] = None) -> str:
    """Compile ``<name>.cpp`` to ``_build/_<name>.so`` (mtime-cached).

    Returns the .so path; raises NativeBuildError if the toolchain is
    missing or compilation fails (callers degrade gracefully).
    """
    src = os.path.join(_HERE, f"{name}.cpp")
    out = os.path.join(_BUILD_DIR, f"_{name}.so")
    # shared headers (binlayout.h) are inlined into every .so: a stale
    # .so must rebuild when the header changed, not only the .cpp
    dep_mtime = max(
        [os.path.getmtime(src)]
        + [os.path.getmtime(os.path.join(_HERE, f))
           for f in os.listdir(_HERE) if f.endswith(".h")]
    )
    with _lock:
        if os.path.exists(out) and os.path.getmtime(out) >= dep_mtime:
            return out
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # a temporary name of this process's own: several processes may
        # build from a fresh checkout at once (test workers, fleet
        # replicas), and each must rename a file it wrote itself
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = [
            _CXX, "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
            *(extra_flags or []), src, "-o", tmp,
        ]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        except FileNotFoundError:
            raise NativeBuildError(f"C++ compiler {_CXX!r} not found") from None
        except subprocess.TimeoutExpired:
            raise NativeBuildError(f"compiling {name} timed out") from None
        if proc.returncode != 0:
            raise NativeBuildError(
                f"compiling {name} failed:\n{proc.stderr[-2000:]}"
            )
        os.replace(tmp, out)
        return out


def load_library(name: str, extra_flags: Optional[list] = None) -> ctypes.CDLL:
    """Build (if needed) and dlopen a native library; process-cached."""
    with _lock:
        if name in _cache:
            return _cache[name]
    path = build_library(name, extra_flags)
    lib = ctypes.CDLL(path)
    with _lock:
        # re-validate under the lock: a concurrent first caller may have
        # cached its own handle while this thread was building — converge
        # on ONE canonical CDLL so per-handle state (restype/argtypes set
        # once by callers) is never split across two live handles
        return _cache.setdefault(name, lib)


def native_available(name: str) -> bool:
    try:
        load_library(name)
        return True
    except NativeBuildError as exc:
        log.debug("native %s unavailable: %s", name, exc)
        return False


class CSide(ctypes.Structure):
    """Mirror of binlayout::CSide (native/binlayout.h) — one side of a
    transfer-compressed binned layout. Every field is 8 bytes, so the
    Python and C layouts are padding-free and identical. Shared by the
    eventlog backend (el_bin_columnar) and ops/ragged
    (rb_bin_compressed)."""

    _fields_ = [
        ("idx_lo", ctypes.c_void_p),
        ("idx_hi", ctypes.c_void_p),
        ("val_u8", ctypes.c_void_p),
        ("val_f32", ctypes.c_void_p),
        ("mask", ctypes.c_void_p),
        ("seg", ctypes.c_void_p),
        ("counts", ctypes.c_void_p),
        ("rows", ctypes.c_int64),
        ("L", ctypes.c_int64),
        ("g_per_shard", ctypes.c_int64),
        ("n_shards", ctypes.c_int64),
        ("row_block", ctypes.c_int64),
        ("group_block", ctypes.c_int64),
        ("n_groups", ctypes.c_int64),
        ("affine", ctypes.c_int64),
        ("affine_a", ctypes.c_double),
        ("affine_b", ctypes.c_double),
        ("kept_entries", ctypes.c_int64),
        ("kept_value_sum", ctypes.c_double),
    ]


def unpack_cside(c: "CSide", owner: "NativeOwner") -> dict:
    """CSide -> kwargs for data.storage.BinnedSide: zero-copy numpy
    views over the native buffers, lifetime-anchored to ``owner`` (the
    side's pointers are also registered on the owner here)."""
    import numpy as np

    slots = c.rows * c.L
    for p in (c.idx_lo, c.idx_hi, c.val_u8, c.val_f32, c.mask,
              c.seg, c.counts):
        owner.add(p)
    coded = bool(c.affine)
    G = c.g_per_shard * c.n_shards
    return dict(
        idx_lo=as_ndarray(c.idx_lo, slots * 2, "uint16", (c.rows, c.L),
                          owner),
        idx_hi=as_ndarray(c.idx_hi, slots, "uint8", (c.rows, c.L), owner),
        val=(as_ndarray(c.val_u8, slots, "uint8", (c.rows, c.L), owner)
             if coded else
             as_ndarray(c.val_f32, slots * 4, "float32", (c.rows, c.L),
                        owner)),
        mask=(None if coded
              else as_ndarray(c.mask, slots, "uint8", (c.rows, c.L),
                              owner)),
        seg=as_ndarray(c.seg, c.rows * 4, "int32", (c.rows,), owner),
        counts=as_ndarray(c.counts, G * 4, "int32", (G,), owner),
        affine=((c.affine_a, c.affine_b) if coded else None),
        row_block=int(c.row_block),
        group_block=int(c.group_block),
        groups_per_shard=int(c.g_per_shard),
        n_shards=int(c.n_shards),
        n_groups=int(c.n_groups),
        kept_entries=int(c.kept_entries),
        kept_value_sum=float(c.kept_value_sum),
    )


class NativeOwner:
    """Frees a set of native buffers when garbage-collected — the
    lifetime anchor of every zero-copy numpy view over native memory
    (``as_ndarray`` ties each view's buffer to its owner, so a view
    kept alive keeps the allocation alive)."""

    def __init__(self, free_fn, ptrs):
        self._free = free_fn
        self._ptrs = [int(p) for p in ptrs if p]

    def add(self, ptr) -> None:
        if ptr:
            self._ptrs.append(int(ptr))

    def __del__(self):
        free = getattr(self, "_free", None)
        for p in getattr(self, "_ptrs", ()):
            try:
                free(p)
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass
        self._ptrs = []


def as_ndarray(ptr, nbytes: int, dtype, shape, owner: NativeOwner):
    """Zero-copy numpy view over a native allocation.

    The returned array's buffer object holds a reference to ``owner``,
    so the memory outlives any view derived from it (slices, reshapes)
    regardless of what happens to the enclosing result object — the
    hand-to-jax contract of the zero-copy data path: ``device_put``
    reads the host bytes with no intermediate copy."""
    import numpy as np

    if not ptr:
        return None
    buf = (ctypes.c_char * nbytes).from_address(int(ptr))
    buf._owner = owner  # lifetime anchor (ctypes instances take attrs)
    return np.frombuffer(buf, dtype=dtype).reshape(shape)
