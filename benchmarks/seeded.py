"""Seeded inputs and weights, made by the benchmark and by nothing else.

The same seed gives the same arrays; a seed is any whole number (the driver's
are larger than 32 signed bits hold, numpy's SeedSequence takes them whole).
Large tables are filled chunk by chunk on a few threads (numpy's generators
release the GIL), each chunk from its own spawned stream, so the result does
not depend on the number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 18


def normal_table(seed: int, stream: int, n_rows: int, width: int,
                 scale: float, threads: int = 8) -> np.ndarray:
    """[n_rows, width] float32, N(0, scale^2), from (seed, stream)."""
    out = np.empty((n_rows, width), np.float32)
    n_chunks = max(1, -(-n_rows // CHUNK_ROWS))
    children = np.random.SeedSequence([int(seed), int(stream)]).spawn(n_chunks)

    def fill(c: int) -> None:
        lo, hi = c * CHUNK_ROWS, min(n_rows, (c + 1) * CHUNK_ROWS)
        rng = np.random.Generator(np.random.PCG64(children[c]))
        rng.standard_normal(out=out[lo:hi], dtype=np.float32)
        out[lo:hi] *= np.float32(scale)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(n_chunks)))
    return out


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(stream)])))
