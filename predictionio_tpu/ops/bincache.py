"""Persistent cache of binned device layouts.

Retraining on unchanged events should not re-pay the host-side
read -> bin pipeline: the segmented layouts the ALS trainer ships to
the device are a pure function of (event-log content, layout knobs),
so they are persisted here keyed by the event store's O(1)
``data_fingerprint`` (generation + bytes + record/tombstone counts —
eventlog.cpp el_fingerprint) plus every layout-affecting parameter.
The cache stores the COMPRESSED device-bound form (uint8 affine value
codes folding the val+mask streams — ops/als.py compress_side), so a
warm hit loads a fraction of the raw COO bytes and goes straight to
device_put.

Storage format (v4, the zero-copy warm lane): ONE file per entry —
``<key>.bin`` = magic + JSON header (meta + array manifest) + the raw
64-byte-aligned array bytes. ``load()`` mmaps the file and returns
numpy VIEWS over the mapping, so a warm start is mmap + device_put:
no npz decompress, no materialized copies, and the chunked H2D
pipeline (ops/als._chunked_device_put) overlaps each chunk's page-in
with the previous chunk's wire transfer. ``save()`` writes a temp
file in the same directory and commits with ``os.replace`` — a
SIGTERM mid-save leaves only an orphaned ``.tmp`` (swept by _prune
once stale), never a torn entry at the final path. The single file
also closes the v3 two-file (npz + json) torn-pair window where a
crash between the two renames left a NEW npz beside an OLD meta.
Entries are machine-local (native byte order), like the eventlog's
index snapshot. v3 ``.npz``+``.json`` pairs remain readable.

Lives next to the persistent XLA compile cache: ``PIO_BIN_CACHE_DIR``
or ``$PIO_FS_BASEDIR/bin_cache`` (default ``~/.pio_store/bin_cache``).
The reference's analogue is Spark RDD caching of the MLlib ALS
in/out-blocks — except this survives process restarts.
"""

from __future__ import annotations

import hashlib
import json
import logging
import mmap
import os
import tempfile
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

_FORMAT_VERSION = 4  # bump when the stored layout shape changes
# v2: value coding is affine (a, b in meta), no table array
# v3: gather indexes stored as wire streams idx_lo (uint16) +
#     optional idx_hi (uint8) instead of one int32 array (r5)
# v4: single-file raw format (header + aligned raw arrays), mmap-backed
#     loads; v3 npz+json pairs still load

_MAGIC = b"PIOBIN4\n"
_ALIGN = 64
#: an orphaned .tmp older than this is a dead save (crashed process);
#: younger ones may be a save in flight from another process
_TMP_TTL_SEC = 3600.0


def cache_dir() -> str:
    d = os.environ.get("PIO_BIN_CACHE_DIR")
    if not d:
        base = os.environ.get("PIO_FS_BASEDIR",
                              os.path.expanduser("~/.pio_store"))
        d = os.path.join(base, "bin_cache")
    return d


def layout_key(fingerprint: str, derivation: str,
               params: Dict[str, Any]) -> str:
    """Stable key: data fingerprint + how the COO was derived from it
    (template/split) + every layout-affecting knob."""
    blob = json.dumps(
        {"v": _FORMAT_VERSION, "fp": fingerprint, "d": derivation,
         "p": {k: params[k] for k in sorted(params)}},
        sort_keys=True, default=str,
    )
    return hashlib.sha1(blob.encode()).hexdigest()


def _paths(key: str) -> Tuple[str, str, str]:
    d = cache_dir()
    return (os.path.join(d, f"{key}.bin"),
            os.path.join(d, f"{key}.npz"),       # legacy v3
            os.path.join(d, f"{key}.json"))      # legacy v3 meta


def _prune(keep: int) -> None:
    """Keep only the ``keep`` most-recently-used entries: fingerprints
    never repeat once the data changes, so without eviction a retrain
    loop would grow the cache without bound (code-review regression).
    LRU by entry-file mtime (load() touches it). Also sweeps dead
    ``.tmp`` files from crashed saves — but SKIPS young ones: a fresh
    temp may be another process's save in flight, and an in-progress
    save must never be yanked out from under its writer."""
    d = cache_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return
    entries = []
    now = time.time()
    for f in names:
        path = os.path.join(d, f)
        if f.endswith(".tmp"):
            try:
                if now - os.path.getmtime(path) > _TMP_TTL_SEC:
                    os.remove(path)  # dead save from a crashed process
            except OSError:
                pass
            continue
        if f.endswith(".bin") or f.endswith(".npz"):
            try:
                entries.append((os.path.getmtime(path), f))
            except OSError:
                pass
    entries.sort(reverse=True)
    for _, stale in entries[keep:]:
        victims = [os.path.join(d, stale)]
        if stale.endswith(".npz"):
            victims.append(os.path.join(d, stale[:-4] + ".json"))
        for path in victims:
            try:
                os.remove(path)
            except OSError:
                pass


def _data_start(header_len: int) -> int:
    return ((len(_MAGIC) + 8 + header_len + _ALIGN - 1)
            // _ALIGN) * _ALIGN


def save(key: str, arrays: Dict[str, np.ndarray],
         meta: Dict[str, Any]) -> None:
    """Atomic single-file write (tmp + os.replace) so a crash/SIGTERM
    mid-save never leaves a torn layout a later load would trust. After
    the write, the cache is pruned to ``PIO_BIN_CACHE_KEEP`` entries
    (default 4)."""
    from predictionio_tpu.obs import perfacct

    t0 = time.perf_counter()
    bin_path, _, _ = _paths(key)
    os.makedirs(cache_dir(), exist_ok=True)
    manifest = []
    offset = 0
    contiguous = {}
    for name, a in arrays.items():
        a = np.ascontiguousarray(a)
        contiguous[name] = a
        offset = ((offset + _ALIGN - 1) // _ALIGN) * _ALIGN
        manifest.append({"name": name, "dtype": a.dtype.str,
                         "shape": list(a.shape), "offset": offset,
                         "nbytes": int(a.nbytes)})
        offset += a.nbytes
    header = json.dumps({"meta": meta, "arrays": manifest}).encode()
    start = _data_start(len(header))
    try:
        fd, tmp = tempfile.mkstemp(dir=cache_dir(), suffix=".bin.tmp")
        with os.fdopen(fd, "wb") as f:
            f.write(_MAGIC)
            f.write(len(header).to_bytes(8, "little"))
            f.write(header)
            f.write(b"\0" * (start - len(_MAGIC) - 8 - len(header)))
            pos = 0
            for m in manifest:
                f.write(b"\0" * (m["offset"] - pos))
                f.write(contiguous[m["name"]])
                pos = m["offset"] + m["nbytes"]
        os.replace(tmp, bin_path)
    except OSError as e:  # a full disk must not fail the training run
        log.warning("bin-cache save failed (%s) — continuing uncached", e)
        try:
            os.remove(tmp)
        except (OSError, UnboundLocalError):
            pass
    _prune(max(1, int(os.environ.get("PIO_BIN_CACHE_KEEP", "4"))))
    # data-path ledger: the bin stage's cache cost sits beside the
    # read/prepare/compile/train stages (obs/perfacct.py)
    perfacct.LEDGER.note_stage("bin_cache_save", time.perf_counter() - t0)


def _load_v4(bin_path: str):
    with open(bin_path, "rb") as f:
        head = f.read(len(_MAGIC) + 8)
        if len(head) != len(_MAGIC) + 8 or head[:len(_MAGIC)] != _MAGIC:
            return None
        header_len = int.from_bytes(head[len(_MAGIC):], "little")
        size = os.fstat(f.fileno()).st_size
        if header_len <= 0 or len(_MAGIC) + 8 + header_len > size:
            return None  # torn header
        doc = json.loads(f.read(header_len).decode("utf-8"))
        start = _data_start(header_len)
        manifest = doc["arrays"]
        # a torn tail (crash mid-write before the replace could never
        # publish it, but belt + suspenders) must degrade, not crash
        end = max((start + m["offset"] + m["nbytes"] for m in manifest),
                  default=start)
        if size < end:
            return None
        mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    arrays = {}
    for m in manifest:
        dtype = np.dtype(m["dtype"])
        count = int(np.prod(m["shape"], dtype=np.int64)) if m["shape"] else 1
        a = np.frombuffer(mm, dtype=dtype, count=count,
                          offset=start + m["offset"])
        arrays[m["name"]] = a.reshape(m["shape"])
    # views hold mm alive via their base; the map outlives this frame.
    # POSIX keeps the mapping valid even if _prune (here or in another
    # process) unlinks the file before the consumer reads the pages.
    return arrays, doc["meta"]


def load(key: str) -> Optional[Tuple[Dict[str, np.ndarray], Dict[str, Any]]]:
    """mmap-backed load: the returned arrays are read-only views over
    the entry file's mapping — the warm lane hands them straight to the
    chunked device_put, so bytes stream disk -> page cache -> device
    with no intermediate materialization. Falls back to the legacy v3
    npz+json pair; returns None on miss or a torn/alien file."""
    from predictionio_tpu.obs import perfacct

    t0 = time.perf_counter()
    bin_path, npz_path, meta_path = _paths(key)
    try:
        out = _load_v4(bin_path)
    except (OSError, ValueError, KeyError, json.JSONDecodeError):
        out = None
    if out is not None:
        try:
            os.utime(bin_path)  # LRU touch for _prune
        except OSError:
            pass  # pruned from under us / read-only dir: the loaded
            # mmap views are still fully valid — never discard them
        perfacct.LEDGER.note_stage("bin_cache_load",
                                   time.perf_counter() - t0)
        return out
    try:  # legacy v3 pair
        with open(meta_path) as f:
            meta = json.load(f)
        data = np.load(npz_path)
        arrays = {k: data[k] for k in data.files}
        os.utime(npz_path)
        perfacct.LEDGER.note_stage("bin_cache_load",
                                   time.perf_counter() - t0)
        return arrays, meta
    except (OSError, ValueError, KeyError):
        return None
