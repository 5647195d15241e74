"""Pallas TPU kernels for the framework's measured hot loops.

Why a kernel subsystem exists: a profile of the two-tower stretch step
taken before this repo's chip runs (PERF.md §5 has today's) read 90%
of its device time outside the matmuls — the blockwise-CE scan body's
per-tile elementwise (56%) and the embedding scatter path (28+%) —
while the matmul window itself ran at ~45-57% of the v5e bf16 peak.
XLA fuses neither across its own loop/scatter boundaries; Pallas
lets the elementwise CE ride in the matmul's shadow (``flash_ce``) and
the table update run as one VMEM-resident gather→update→write pass
(``embed_update``).

Design contract shared by every kernel here:

  - the XLA implementation REMAINS the reference; a kernel is selected
    per-trainer by :func:`decide` (config flag + eligibility).
    ``auto`` is a choice by backend — on a TPU the
    compiled kernel, on the CPU the XLA path — never a fallback: an
    engaged kernel that fails to compile or run raises the compiler's
    own error in the trainer / the index, so a chip run can never be
    measured on a path nobody chose;
  - kernels run under Pallas interpret mode only for the tests, on the
    CPU backend (flag ``on``; ``PIO_PALLAS_INTERPRET=1``), so tier-1
    exercises fwd/bwd numerics with no TPU in the loop. Interpret mode
    is never chosen on a TPU backend;
  - equivalence tests pin each kernel to its XLA reference at <=1e-5
    in f32 (tests/test_pallas_kernels.py), and tests/test_tpu_compile.py
    compiles each for a described v5e at the advertised shapes.

The same contract covers serving: ``topk_dot`` (fused dot + streaming
top-k over the item table in ``[D, Ip]`` tiles of thousands of items,
merging only a tile that can change the top-k — the exact retrieval
index's hot path, selected per-index via ``index_kernel``).

Four kernels have no flag. ``expert_stream`` (the expert layer of a forward
whose tokens fit one tile: every row through every touched expert) and
``expert_groups`` (of a larger forward, a prefill chunk: the rows sorted by
expert, each expert's group gathered, multiplied and added back inside its
grid step), both in ``expert_stream.py``, both streaming each touched
expert's weights once, are the two forms ``ops/moe.moe`` has, chosen by
the forward's tokens; the tile loop they replaced
(``ops/moe.experts_sorted``) is their reference in the tests.
``chunk_attend`` (``chunk_attend.py``: a prefill chunk's latent attention,
the walk over a slot's cached blocks with their expansion, a head a grid
step, scores and probabilities in VMEM only) is the one form
``ops/mla.prefill_chunk`` has, with an index and without; the loop of XLA's
fusions it replaced (``ops/mla.attend_blocks``: ``ops/attention
.attend_over_blocks`` over ``expand``) is its reference in the tests and the
path of every other walk (the absorbed extension, grouped-query attention's
chunks and block-diffusion forwards, the window layers). ``span_walk``
(``span_walk.py``: a causal stack's EXTENSION over a span of keys and values,
each real row of the batch over the blocks of its own reach, read where the
cache holds them, a padding row over none; ``chunk_attend.fold`` a group of
key heads) is the one form ``ops/gqa.extend`` and ``ops/gqa.cross_rows`` have;
``ops/gqa._attend`` (``attend_over_blocks`` over slices of the span, every
row as far as the batch's longest) is its reference in the tests. A path
that has one form runs it compiled on a TPU and under the interpreter
everywhere else, tier-1 included.

Each flag (``flash_ce_kernel``, ``embed_update_kernel``,
``index_kernel``) takes ``on`` / ``off`` / ``auto``;
``PIO_PALLAS_INTERPRET=1`` forces interpret mode off the TPU.
"""

from __future__ import annotations

import logging
import os
from typing import Tuple

log = logging.getLogger(__name__)

_TRUTHY = {"1", "true", "yes", "on"}
_FALSY = {"0", "false", "no", "off"}


def interpret_mode() -> bool:
    """Whether kernels should run under the Pallas interpreter: never
    on a TPU backend; on any other backend ``PIO_PALLAS_INTERPRET``
    wins when set, else interpret (there is no Mosaic compiler to
    target)."""
    import jax

    if jax.default_backend() == "tpu":
        return False
    env = os.environ.get("PIO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() in _TRUTHY
    return True


def resolve_flag(config_value: str) -> str:
    """Normalize a kernel flag to ``on`` / ``off`` / ``auto``. An
    unrecognized value falls back to ``auto`` WITH a warning — a typo'd
    ``"onn"`` in an engine.json must not silently run the other path."""
    value = str(config_value).strip().lower()
    if value in _TRUTHY:
        return "on"
    if value in _FALSY:
        return "off"
    if value != "auto":
        log.warning("unrecognized kernel flag %r; treating as 'auto' — "
                    "valid values: on/off/auto", config_value)
    return "auto"


def decide(
    config_value: str,
    *,
    eligible: bool,
    ineligible_reason: str,
    auto_default: bool,
) -> Tuple[bool, str]:
    """One kernel's engage decision -> (engaged, reason).

    ``on``   engage whenever eligible (interpret mode included — how
             CPU tier-1 exercises the kernels);
    ``off``  never;
    ``auto`` engage when eligible AND ``auto_default`` — the caller
             passes True only on a real TPU backend, so interpret mode
             is never silently slower for CPU users.
    """
    flag = resolve_flag(config_value)
    if flag == "off":
        return False, "disabled by flag"
    if not eligible:
        return False, ineligible_reason
    if flag == "on":
        return True, "forced on"
    if auto_default:
        return True, "auto (tpu backend)"
    return False, "auto defaults off on non-TPU backends (set the flag " \
                  "to 'on' to run under the interpreter)"
