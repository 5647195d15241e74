"""What each kernel NEEDS, from the configuration's shapes, and the least time
the chip could take for it.

A roofline share is the least time over the measured time. The least time is
the larger of operations over the chip's peak rate and bytes over its peak
memory rate (``peaks.json``); the operations and bytes are what the algorithm
needs, not what an implementation spends: padding, a re-tiling copy, logits
recomputed in the backward pass are all on the measured side only.
"""

from __future__ import annotations

from typing import Optional


def topk_dot_bytes(config: dict) -> float:
    """One ``topk_dot`` call: the float32 item table read once, unpadded.
    The query vectors, exclusions and the [B, k] result are noise beside it
    (under a kilobyte a query against gigabytes)."""
    return float(config["n_items"]) * float(config["rank"]) * 4.0


def topk_dot_flops(config: dict, batch: int = 1) -> float:
    """The score matmul of one call: 2 * batch * n_items * rank."""
    return 2.0 * batch * float(config["n_items"]) * float(config["rank"])


def flash_ce_flops_per_step(config: dict) -> float:
    """One training step's in-batch softmax cross-entropy: the [B, B] logits
    product in the forward pass and one product of the same size for each of
    the two gradients, 2 * B * B * D each. The backward kernels recompute the
    logits; recomputation is not needed work and is not counted."""
    batch, dim = float(config["batch_size"]), float(config["dim"])
    return 6.0 * batch * batch * dim


def least_seconds(peaks: dict, flops: float = 0.0,
                  nbytes: float = 0.0) -> float:
    """The least time the chip could take, and so which peak bounds it."""
    return max(flops / float(peaks["bf16_flops_per_s"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))


def roofline_pct(least_s: float, measured_s: float) -> Optional[float]:
    """100 * least / measured; None where nothing was measured, and None
    rather than a share above 100: the need is then counted too high or the
    measured time leaves out part of the work, and neither is a reading."""
    if measured_s <= 0 or least_s <= 0:
        return None
    share = 100.0 * least_s / measured_s
    return share if share <= 100.0 else None
