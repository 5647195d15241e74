"""Two-tower neural retrieval as a DASE Algorithm.

The deep-model counterpart of models.als: same PD (PreparedRatings),
same model container / query surface (top-``num`` itemScores), so the
recommendation engine can swap `"als"` for `"twotower"` — or run both
and let Serving combine them, the reference's distinctive
multi-algorithm contract (SURVEY.md §7 hard part (d), CreateServer
serving combine :472–475). Compute core: ops.twotower (row-sparse towers +
in-batch softmax under jit on the mesh).

Scores are cosine similarities (towers L2-normalize), so multi-algo
averaging with ALS dot-products needs score-scale awareness — the same
caveat the reference leaves to user Serving code.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from predictionio_tpu.core import Algorithm
from predictionio_tpu.core.params import Params
from predictionio_tpu.models.als import ALSAlgorithm, ALSModel, PreparedRatings
from predictionio_tpu.ops.als import ALSFactors
from predictionio_tpu.ops.twotower import TwoTowerConfig, TwoTowerTrainer
from predictionio_tpu.parallel.mesh import MeshContext


@dataclass
class TwoTowerParams(Params):
    dim: int = 64
    embed_dim: Optional[int] = None   # id-embedding width (default: dim)
    hidden: Tuple[int, ...] = ()
    temperature: float = 0.07
    learning_rate: float = 3e-3
    weight_decay: float = 1e-6
    epochs: int = 5
    batch_size: int = 1024
    seed: int = 11
    min_rating: float = 0.0       # keep events with rating >= this as positives
    weight_by_rating: bool = False
    shard_embeddings: bool = False
    checkpoint_dir: Optional[str] = None   # mid-training checkpoint/resume
    checkpoint_every: int = 1
    flash_ce_kernel: str = "auto"          # ops/pallas flash-CE kernel:
    embed_update_kernel: str = "off"       # "auto" | "on" | "off" (see
                                           # TwoTowerConfig)
    index_backend: str = "auto"            # retrieval index backend
                                           # (PIO_INDEX_BACKEND overrides)
    index_kernel: str = "auto"             # Pallas dot+top-k flag


class TwoTowerModel(ALSModel):
    """Same container as ALSModel: (user_vecs, item_vecs, id maps) and
    its one retriever over the retrieval index; vectors here are
    L2-normalized so scores — including the item -> similar answers —
    are cosine similarities."""

    #: device-memory ledger attribution (obs/memacct.py)
    memacct_model = "twotower"


class TwoTowerAlgorithm(Algorithm):
    """DASE wrapper over ops.twotower."""

    def __init__(self, params: TwoTowerParams):
        super().__init__(params)

    def train(self, ctx: MeshContext, pd: PreparedRatings) -> TwoTowerModel:
        p: TwoTowerParams = self.params
        if pd.binned_request is not None:
            # the zero-copy lane's deferred read is ALS-layout-shaped;
            # this trainer consumes host COO — materialize it through
            # the columnar fallback (same rows/codes/value resolution)
            pd = pd.binned_request.read_prepared(pd.fingerprint)
        keep = pd.ratings >= p.min_rating
        u, i, r = pd.user_idx[keep], pd.item_idx[keep], pd.ratings[keep]
        if len(u) == 0:
            raise ValueError(
                f"no events with rating >= {p.min_rating} — nothing to train on"
            )
        cfg = TwoTowerConfig(
            dim=p.dim,
            embed_dim=p.embed_dim,
            hidden=tuple(p.hidden),
            temperature=p.temperature,
            learning_rate=p.learning_rate,
            weight_decay=p.weight_decay,
            epochs=p.epochs,
            batch_size=p.batch_size,
            seed=p.seed,
            shard_embeddings=p.shard_embeddings,
            checkpoint_dir=p.checkpoint_dir,
            checkpoint_every=p.checkpoint_every,
            flash_ce_kernel=p.flash_ce_kernel,
            embed_update_kernel=p.embed_update_kernel,
        )
        trainer = TwoTowerTrainer(
            (u, i, r if p.weight_by_rating else None),
            pd.n_users,
            pd.n_items,
            cfg,
            mesh=ctx.mesh,
        )
        losses = trainer.run()
        emb = trainer.embeddings(losses)
        factors = ALSFactors(user_factors=emb.user_vecs, item_factors=emb.item_vecs)
        model = TwoTowerModel(factors, pd.user_ids, pd.item_ids,
                              index_backend=p.index_backend,
                              index_kernel=p.index_kernel)
        model.train_losses = emb.losses
        return model

    # identical model/query surface -> share ALS's lone and batched
    # serve paths (one retrieval each), its deploy-time warmup, and the
    # streaming model-patch lane (same factor-table container)
    predict = ALSAlgorithm.predict
    batch_predict = ALSAlgorithm.batch_predict
    warmup = ALSAlgorithm.warmup
    apply_patch = ALSAlgorithm.apply_patch
