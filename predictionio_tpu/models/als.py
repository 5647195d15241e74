"""ALS matrix factorization as a DASE Algorithm.

Behavior contract from the reference's recommendation template
(examples/scala-parallel-recommendation/custom-serving/src/main/scala/
ALSAlgorithm.scala:56 — `ALS.train(ratings, rank, iterations, lambda)`
on indexed ratings, model = user/item factor matrices, predict =
top-``num`` item scores for a user). The compute core is
predictionio_tpu.ops.als (mesh-sharded batched normal equations)
instead of MLlib's shuffle-blocked ALS.

Query / result are JSON-shaped dicts, matching the REST contract of the
deployed engine (`POST /queries.json {"user": "1", "num": 4}` ->
`{"itemScores": [{"item": ..., "score": ...}]}`).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core import Algorithm, SanityCheck
from predictionio_tpu.core.params import Params
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.obs import trace
from predictionio_tpu.ops.als import ALSConfig, ALSFactors, als_train
from predictionio_tpu.ops.topk import TopKScorer
from predictionio_tpu.parallel.mesh import MeshContext


@dataclass
class PreparedRatings(SanityCheck):
    """PD for factorization algorithms: indexed COO ratings — or, on
    the zero-copy lane, a DEFERRED ``binned_request`` (the DataSource
    cannot bin at read time because the layout depends on algorithm
    knobs; the fit stage performs the one fused native scan+bin call
    with its own config, and no COO ever materializes)."""

    user_ids: Optional[BiMap] = None   # user id str -> row
    item_ids: Optional[BiMap] = None   # item id str -> row
    user_idx: Optional[np.ndarray] = None    # [nnz] int
    item_idx: Optional[np.ndarray] = None    # [nnz] int
    ratings: Optional[np.ndarray] = None     # [nnz] float32
    #: data+derivation fingerprint from the DataSource (None when the
    #: backend has no cheap one) — keys the binned-layout cache
    fingerprint: Optional[str] = None
    #: deferred zero-copy read (templates.recommendation
    #: .BinnedReadRequest); when set, the COO fields above are None
    binned_request: Optional[Any] = None

    @property
    def n_users(self) -> int:
        return len(self.user_ids)

    @property
    def n_items(self) -> int:
        return len(self.item_ids)

    def sanity_check(self) -> None:
        if self.binned_request is not None:
            return  # emptiness is checked by the fit-stage native read
        if self.user_idx is None or len(self.user_idx) == 0:
            raise ValueError("PreparedRatings is empty — no rating events found")
        if len(self.user_idx) != len(self.item_idx) or len(self.user_idx) != len(self.ratings):
            raise ValueError("COO arrays length mismatch")


@dataclass
class ALSParams(Params):
    rank: int = 32
    num_iterations: int = 10
    lambda_: float = 0.1
    implicit_prefs: bool = False
    alpha: float = 1.0
    block_size: int = 4096
    seed: int = 3
    seg_len: object = "auto"          # virtual-row length (int), or
                                      # "auto": sized from the group-
                                      # size histogram (ops.ragged)
    solver: str = "cg"               # "cg" | "direct"
    cg_iters: int = 6   # warm-started + Jacobi-preconditioned CG needs
                        # far fewer steps than a cold solve (measured
                        # sweep: ops.als.ALSConfig.cg_iters)
    cg_unroll: bool = True           # straight-line CG recurrence
                                     # (False restores the lax.scan form)
    cg_precond: str = "jacobi"       # "jacobi" | "none"; with "none",
                                     # raise cg_iters to >= 8 (see sweep)
    cg_dtype: str = "bfloat16"       # CG matvec dtype ("float32" to opt out)
    compute_dtype: str = "bfloat16"  # Gramian input dtype (f32 accumulate)
    # optional hard caps (None = keep every rating; the segmented layout
    # makes caps unnecessary except as an outlier guard)
    max_ratings_per_user: Optional[int] = None
    max_ratings_per_item: Optional[int] = None
    # retrieval-index knobs (predictionio_tpu/index): backend
    # "auto"/"exact"/"ivf" (PIO_INDEX_BACKEND overrides), and the exact
    # backend's Pallas dot+top-k kernel flag "auto"/"on"/"off"
    # (selection exactly like flash_ce_kernel)
    index_backend: str = "auto"
    index_kernel: str = "auto"


class QueryPlan(NamedTuple):
    """One query against the item table, lone or a row of a batch."""

    vec: np.ndarray          # [D] query vector
    exclude: List[int]       # item rows it excludes, in the order given
    skip: Optional[int]      # the row the answer must not repeat


class ALSModel:
    """Factor matrices + id maps, and ONE retriever over the item table
    (:meth:`retrieve`): the retrieval index with its one device copy,
    for a lone query and for a batch alike; on a mesh
    (:meth:`enable_sharded_serving`) the row-sharded scorer in its
    place."""

    #: ledger attribution label (obs/memacct.py); TwoTowerModel
    #: overrides — the same per-model key perfacct's MFU gauges use
    memacct_model = "als"

    def __init__(self, factors: ALSFactors, user_ids: BiMap, item_ids: BiMap,
                 index_backend: str = "auto", index_kernel: str = "auto"):
        self.user_factors = factors.user_factors
        self.item_factors = factors.item_factors
        self.user_ids = user_ids
        self.item_ids = item_ids
        # the retriever's mesh form (enable_sharded_serving); an
        # unsharded model never builds a scorer of its own
        self._scorer = None
        # retrieval index (predictionio_tpu/index): built lazily /
        # by deploy warm-up, patched in place by the streaming lane
        self._index = None
        self.index_backend = index_backend
        self.index_kernel = index_kernel
        # picklable record that sharded serving was enabled (the mesh
        # itself never pickles); load_persistent_model re-enables it
        self.sharded_axis: Optional[str] = None
        self._register_memory()

    def __getstate__(self):
        d = dict(self.__dict__)
        d["_scorer"] = None  # device buffers never pickle
        d["_index"] = None   # rebuilt at deploy warm-up
        return d

    def __setstate__(self, d):
        d.setdefault("sharded_axis", None)  # models pickled pre-field
        d.setdefault("_index", None)
        d.setdefault("index_backend", "auto")
        d.setdefault("index_kernel", "auto")
        self.__dict__.update(d)
        # model LOAD seam (prepare_deploy unpickle): this instance's
        # residency lands in the device-memory ledger; the hot-swap /
        # replica-stop paths release it (obs/memacct.py)
        self._register_memory()

    def _register_memory(self) -> None:
        """(Re-)price this model's footprints in the device-memory
        ledger: the factor tables and (estimated) id maps. Called at
        construction, load (unpickle) and after every fold-in patch —
        a grown table re-prices itself under the same owner key."""
        from predictionio_tpu.obs import memacct

        memacct.LEDGER.register(
            self, self.memacct_model, "factors",
            int(self.user_factors.nbytes + self.item_factors.nbytes))
        # id maps: a cheap structural estimate (dict slot + interned
        # key + inverse list per entry) — attribution, not malloc truth
        memacct.LEDGER.register(
            self, self.memacct_model, "id_maps",
            (len(self.user_ids) + len(self.item_ids)) * 24)

    def scorer(self):
        """The retriever's mesh form (``ShardedTopKScorer``), or None:
        an unsharded model retrieves through :meth:`retrieval_index`
        alone and keeps no second device copy of the item table."""
        return self._scorer

    def retrieve(self, vecs: np.ndarray, num: int,
                 exclude: Optional[np.ndarray] = None,
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores [B, k], item rows [B, k]) of the best ``num`` items
        by dot product for each row of ``vecs`` ([D] or [B, D]);
        ``exclude`` is [E] or [B, E] item rows, -1 padded. Every
        retrieval of the model, lone or batched, goes through here. The
        index picks its path from the shape and the backend
        (``ExactIndex._kernel_eligible``); a sharded catalogue keeps the
        mesh scorer (no single-device index over it)."""
        if self._scorer is not None:
            return self._scorer.score(vecs, num, exclude)
        return self.retrieval_index().search(vecs, num, exclude)

    def retrieval_index(self):
        """The model's ANN candidate-generation index over the item
        factor table (predictionio_tpu/index): built lazily (the engine
        server's warm-up builds it at model load), kept fresh by
        ``upsert_rows`` — the streaming ``/model/patch`` lane reaches
        retrieval, not just scoring."""
        if self._index is None:
            from predictionio_tpu.index import make_index

            index = make_index(backend=self.index_backend,
                               kernel=self.index_kernel)
            # ledger attribution BEFORE the build registers bytes, so
            # the index's footprints land under this model's label
            index.mem_model = self.memacct_model
            index.build(np.asarray(self.item_factors, np.float32))
            self._index = index
        return self._index

    def retrieval_stats(self) -> Optional[dict]:
        """Stats of the BUILT index, or None (status pages must never
        trigger a build)."""
        return self._index.stats() if self._index is not None else None

    def enable_sharded_serving(self, mesh, axis: str = "data") -> None:
        """Swap in a ShardedTopKScorer: item factors row-sharded over
        ``mesh[axis]``, per-shard top-k merged over ICI — serving for
        catalogs larger than one chip's HBM (ops.topk.make_sharded_topk).
        Same results as the single-device index."""
        from predictionio_tpu.ops.topk import ShardedTopKScorer

        self._scorer = ShardedTopKScorer(self.item_factors, mesh, axis=axis)
        self.sharded_axis = axis

    def upsert_rows(
        self,
        user_rows: Sequence[Tuple[str, "np.ndarray"]] = (),
        item_rows: Sequence[Tuple[str, "np.ndarray"]] = (),
    ) -> Tuple[int, int]:
        """Apply a streaming fold-in patch: overwrite (or append) the
        named factor rows. COPY-ON-WRITE — new arrays are built and the
        attribute references swapped last, so a concurrent ``predict``
        reading ``self.user_factors`` once sees either the old or the
        new table, never a torn row. Item rows land in the built
        retrieval index as an in-place upsert (it drops its device copy
        of the old table; a same-shape re-put hits the compile cache,
        only NEW items change shapes). Returns (n_new_users,
        n_new_items)."""
        rank = self.user_factors.shape[1] if self.user_factors.size else (
            self.item_factors.shape[1])
        if item_rows and self.sharded_axis is not None:
            # the sharded scorer's row placement can't be patched from
            # here (no mesh at hand) — silently downgrading to the
            # single-device index would change serving capacity; the
            # rolling /reload lane is the supported swap for these
            raise ValueError(
                "item-row patches are not supported on a sharded-serving "
                "model; use the rolling /reload fallback")
        new_users = new_items = 0
        if user_rows:
            ids, factors = self.user_ids, self.user_factors
            fresh = [uid for uid, _ in user_rows if uid not in ids]
            if fresh:
                vocab = list(ids.keys()) + fresh
                ids = BiMap.from_vocab(vocab)
                factors = np.vstack(
                    [factors, np.zeros((len(fresh), rank), np.float32)])
                new_users = len(fresh)
            else:
                factors = factors.copy()
            for uid, vec in user_rows:
                vec = np.asarray(vec, np.float32)
                if vec.shape != (rank,):
                    raise ValueError(
                        f"user row {uid!r}: expected a length-{rank} "
                        f"vector, got shape {vec.shape}")
                factors[ids[uid]] = vec
            # factors FIRST: a reader holding the new id map but the old
            # (shorter) table would index past its end on a fresh user
            self.user_factors = factors  # graftlint: disable=JT18 — copy-on-write commit: store is atomic, readers take one local ref (old-or-new, never torn)
            self.user_ids = ids  # graftlint: disable=JT18 — paired with the factors swap; ordering documented above
        if item_rows:
            ids, factors = self.item_ids, self.item_factors
            fresh = [iid for iid, _ in item_rows if iid not in ids]
            if fresh:
                vocab = list(ids.keys()) + fresh
                ids = BiMap.from_vocab(vocab)
                factors = np.vstack(
                    [factors, np.zeros((len(fresh), rank), np.float32)])
                new_items = len(fresh)
            else:
                factors = factors.copy()
            for iid, vec in item_rows:
                vec = np.asarray(vec, np.float32)
                if vec.shape != (rank,):
                    raise ValueError(
                        f"item row {iid!r}: expected a length-{rank} "
                        f"vector, got shape {vec.shape}")
                factors[ids[iid]] = vec
            self.item_factors = factors  # graftlint: disable=JT18 — copy-on-write commit: store is atomic, readers take one local ref (old-or-new, never torn)
            self.item_ids = ids  # graftlint: disable=JT18 — paired with the factors swap; same ordering rule
            # the retrieval index takes the SAME rows as an in-place
            # upsert (no rebuild): streamed items become retrievable
            # without a /reload
            if self._index is not None:
                touched = np.fromiter(
                    (ids[iid] for iid, _ in item_rows), np.int64,
                    count=len(item_rows))
                self._index.upsert(touched, factors[touched])
        if new_users or new_items or user_rows or item_rows:
            # grown/overwritten tables re-price their ledger footprints
            self._register_memory()
        return new_users, new_items

    # -- queries ---------------------------------------------------------------
    # ``user_plan`` / ``item_plan`` turn a query into a QueryPlan,
    # ``answer`` runs any number of plans as ONE retrieval — so a query
    # sent alone and the same query inside a batch are the same code
    # on the same inputs.

    def _exclusion_rows(self, exclude_items: Sequence[str]) -> List[int]:
        """Rows of the known ids of a blacklist, each once, in the
        order given: the retrievers cap an exclusion list keeping its
        NEWEST (rightmost) entries."""
        ids = self.item_ids
        return list(dict.fromkeys(
            ids[i] for i in exclude_items if i in ids))

    def user_plan(self, user_id: str, exclude_items: Sequence[str] = ()
                  ) -> Optional[QueryPlan]:
        """user -> top items: the user's factor, the blacklist's rows.
        None for an unknown user."""
        row = self.user_ids.get(user_id)
        if row is None:
            return None
        return QueryPlan(self.user_factors[row],
                         self._exclusion_rows(exclude_items), None)

    def item_plan(self, item_id: str, exclude_items: Sequence[str] = ()
                  ) -> Optional[QueryPlan]:
        """item -> similar items: the item's factor against the item
        table, itself excluded. None for an unknown item. The
        self-exclusion goes LAST: an oversize blacklist may then drop
        its own oldest entries but never the query item — and
        ``answer``'s filter backstops even that."""
        row = self.item_ids.get(item_id)
        if row is None:
            return None
        excl = [r for r in self._exclusion_rows(exclude_items) if r != row]
        return QueryPlan(self.item_factors[row], excl + [row], row)

    def answer(self, plans: Sequence[QueryPlan], nums: Sequence[int]
               ) -> List[List[Tuple[str, float]]]:
        """[(item id, score), ...] best first for each plan, ``nums[b]``
        of them at most: one retrieval of ``max(nums)`` for all."""
        if not plans:
            return []
        with trace.device_span("engine.prepare"):
            vecs = np.stack([p.vec for p in plans])
            width = max(len(p.exclude) for p in plans)
            excl = None
            if width:
                # right-aligned, -1 padded on the LEFT: a retriever that
                # caps the width keeps the rightmost columns, which must
                # be every row's own newest entries
                excl = np.full((len(plans), width), -1, np.int32)
                for b, p in enumerate(plans):
                    if p.exclude:
                        excl[b, width - len(p.exclude):] = p.exclude
        scores, idx = self.retrieve(vecs, max(nums), excl)
        with trace.device_span("engine.decode"):
            inv = self.item_ids.inverse()
            return [
                [(inv[int(i)], float(s))
                 for s, i in zip(s_row[:n], i_row[:n])
                 if s > -1e29 and int(i) >= 0 and int(i) != p.skip]
                for p, n, s_row, i_row in zip(plans, nums, scores, idx)]

    def recommend(
        self,
        user_id: str,
        num: int,
        exclude_items: Sequence[str] = (),
        candidate_items: Optional[Sequence[str]] = None,
    ) -> List[Tuple[str, float]]:
        with trace.device_span("engine.prepare"):
            plan = self.user_plan(user_id, exclude_items)
        if plan is None:
            return []
        if candidate_items is not None:
            # a whitelist is scored on the host against its candidates
            # alone: the one query shape with no batched form
            cand = np.array(
                sorted(
                    {self.item_ids[i] for i in candidate_items if i in self.item_ids}
                    - set(plan.exclude)
                ),
                dtype=np.int64,
            )
            if len(cand) == 0:
                return []
            scores = self.item_factors[cand] @ plan.vec
            # partial sort: the whitelist can be the whole catalog
            # (JT14 — argsort(...)[:k] full-sorts it per query)
            top_s, top_j = TopKScorer._host_topk(scores[None, :], num)
            inv = self.item_ids.inverse()
            return [(inv[int(cand[j])], float(s))
                    for s, j in zip(top_s[0], top_j[0])]
        return self.answer([plan], [num])[0]

    def similar_items(
        self,
        item_id: str,
        num: int,
        exclude_items: Sequence[str] = (),
    ) -> List[Tuple[str, float]]:
        """item -> top-``num`` similar items: top-k by dot product of
        the item's factor against the item table, the query item
        excluded. Cosine similarity when the table is row-normalized
        (two-tower towers are; raw ALS factors score dot-similarity,
        popularity-weighted)."""
        with trace.device_span("engine.prepare"):
            plan = self.item_plan(item_id, exclude_items)
        return [] if plan is None else self.answer([plan], [num])[0]


def apply_rows_patch(model: ALSModel, patch: dict) -> bool:
    """The one factor-row patch decoder every factor-backed algorithm
    shares (ALS and two-tower models both serve from ALSModel factor
    tables): ``patch`` carries ``userRows`` / ``itemRows`` as
    ``[[id, [floats...]], ...]`` and lands via
    :meth:`ALSModel.upsert_rows` (copy-on-write, index upsert).
    Malformed rows raise ValueError — the engine server maps that to
    400 with nothing partially applied for the failing side."""

    def rows(key):
        out = []
        for entry in patch.get(key) or ():
            if (not isinstance(entry, (list, tuple)) or len(entry) != 2
                    or not isinstance(entry[0], str)):
                raise ValueError(
                    f"{key}: each row must be [id, [floats...]]")
            out.append((entry[0], np.asarray(entry[1], np.float32)))
        return out

    model.upsert_rows(user_rows=rows("userRows"),
                      item_rows=rows("itemRows"))
    return True


class ALSAlgorithm(Algorithm):
    """DASE wrapper over ops.als (ref template: ALSAlgorithm.scala)."""

    def __init__(self, params: ALSParams):
        super().__init__(params)

    def apply_patch(self, model: ALSModel, patch: dict) -> bool:
        """Streaming fold-in rows land in the live factor tables
        (workflow/stream.py's model-patch lane)."""
        return apply_rows_patch(model, patch)

    def train(self, ctx: MeshContext, pd: PreparedRatings) -> ALSModel:
        p: ALSParams = self.params
        cfg = ALSConfig(
            rank=p.rank,
            iterations=p.num_iterations,
            reg=p.lambda_,
            implicit=p.implicit_prefs,
            alpha=p.alpha,
            block_size=p.block_size,
            seed=p.seed,
            seg_len=p.seg_len,
            solver=p.solver,
            cg_iters=p.cg_iters,
            cg_unroll=p.cg_unroll,
            cg_precond=p.cg_precond,
            cg_dtype=p.cg_dtype,
            compute_dtype=p.compute_dtype,
        )
        if pd.binned_request is not None:
            return self._train_binned(ctx, pd, cfg)
        factors = als_train(
            (pd.user_idx, pd.item_idx, pd.ratings),
            pd.n_users,
            pd.n_items,
            cfg,
            mesh=ctx.mesh,
            max_ratings_per_user=p.max_ratings_per_user,
            max_ratings_per_item=p.max_ratings_per_item,
            # retrain-on-unchanged-events skips re-binning (ops.bincache)
            cache_key=pd.fingerprint,
        )
        return ALSModel(factors, pd.user_ids, pd.item_ids,
                        index_backend=p.index_backend,
                        index_kernel=p.index_kernel)

    def _train_binned(self, ctx: MeshContext, pd: PreparedRatings,
                      cfg: ALSConfig) -> ALSModel:
        """The zero-copy lane: warm starts load the compressed layout
        (+ vocabularies) from the bin cache as mmap views; cold starts
        make ONE fused native scan+bin call (store.bin_columnar — no
        COO, no Event objects, no Python row loop) and persist the
        layout WITH the vocabularies so the next warm start skips the
        read entirely. Either way the sides go to
        ``ALSTrainer.from_sides`` and the chunked H2D pipeline."""
        from predictionio_tpu.data.storage import pack_vocab, unpack_vocab
        from predictionio_tpu.obs import perfacct
        from predictionio_tpu.ops import bincache
        from predictionio_tpu.ops.als import (ALSTrainer, SideLayout,
                                              als_row_cost_slots,
                                              layout_cache_key,
                                              side_layout_from_binned)

        p: ALSParams = self.params
        n_shards = ctx.mesh.shape["data"] if ctx.mesh is not None else 1
        # SAME key derivation as ALSTrainer's internal COO-path cache:
        # the layouts are bit-identical, so either lane's entry serves
        # the other
        key = None
        cached = None
        if pd.fingerprint:
            key = layout_cache_key(pd.fingerprint, cfg, n_shards,
                                   p.max_ratings_per_user,
                                   p.max_ratings_per_item)
            cached = bincache.load(key)
        if cached is not None:
            arrays, meta = cached
            if "u_vocab_bytes" in arrays:
                user_vocab = unpack_vocab(arrays["u_vocab_bytes"],
                                          arrays["u_vocab_offs"])
                item_vocab = unpack_vocab(arrays["i_vocab_bytes"],
                                          arrays["i_vocab_offs"])
                trainer = ALSTrainer.from_sides(
                    SideLayout.from_arrays(arrays, "u_", meta),
                    SideLayout.from_arrays(arrays, "i_", meta),
                    int(meta["n_users"]), int(meta["n_items"]),
                    int(meta["total_entries"]), cfg, mesh=ctx.mesh)
                trainer.cache_hit = True
                return ALSModel(trainer.run(),
                                BiMap.from_vocab(user_vocab),
                                BiMap.from_vocab(item_vocab),
                                index_backend=p.index_backend,
                                index_kernel=p.index_kernel)
            # entry saved by the COO lane (no vocab): rebuild below and
            # overwrite it with a vocab-carrying entry

        req = pd.binned_request
        binned = req.bin(
            seg_len=cfg.seg_len,
            max_len_user=p.max_ratings_per_user,
            max_len_item=p.max_ratings_per_item,
            n_shards=n_shards, block_size=cfg.block_size,
            row_cost_slots=als_row_cost_slots(cfg.rank))
        if binned.n_rows == 0:
            raise ValueError(
                "PreparedRatings is empty — no rating events found")
        # ledger sub-stages: the native call's own scan/bin split (the
        # engine's coarse read/prepare stages were ~0 on this lane)
        perfacct.LEDGER.note_stage("read", binned.scan_sec)
        perfacct.LEDGER.note_stage("bin", binned.bin_sec)
        user_side = side_layout_from_binned(binned.user_side)
        item_side = side_layout_from_binned(binned.item_side)
        n_users = len(binned.entity_vocab)
        n_items = len(binned.target_vocab)
        trainer = ALSTrainer.from_sides(
            user_side, item_side, n_users, n_items, binned.n_rows, cfg,
            mesh=ctx.mesh)
        if key is not None:
            import numpy as _np

            uv_b, uv_o = pack_vocab(binned.entity_vocab)
            iv_b, iv_o = pack_vocab(binned.target_vocab)
            arrays = {
                **user_side.to_arrays("u_"), **item_side.to_arrays("i_"),
                "u_vocab_bytes": _np.frombuffer(uv_b, _np.uint8),
                "u_vocab_offs": uv_o,
                "i_vocab_bytes": _np.frombuffer(iv_b, _np.uint8),
                "i_vocab_offs": iv_o,
            }
            bincache.save(key, arrays, {
                "n_users": n_users, "n_items": n_items,
                "n_shards": n_shards, "total_entries": binned.n_rows,
                **user_side.meta("u_"), **item_side.meta("i_"),
            })
        return ALSModel(trainer.run(),
                        BiMap.from_vocab(binned.entity_vocab),
                        BiMap.from_vocab(binned.target_vocab),
                        index_backend=p.index_backend,
                        index_kernel=p.index_kernel)

    @classmethod
    def grid_train(
        cls,
        ctx: MeshContext,
        pd: PreparedRatings,
        params_list: Sequence["ALSParams"],
    ) -> Optional[List[ALSModel]]:
        """Train EVERY candidate in ONE compiled dispatch when the
        candidates differ only in SHAPE-STABLE scalars — lambda_,
        alpha, num_iterations, cg_iters (iteration
        counts ride as per-candidate step budgets: the program runs to
        the max and freezes finished candidates bit-identically to
        their sequential runs). The vmapped tuning path
        (ops.als.als_grid_train) behind MetricEvaluator (reference
        role: MetricEvaluator over engineParamsList,
        controller/MetricEvaluator.scala:177, which trains G times).

        Returns one model per candidate, or None when the grid shape
        does not apply (params differing beyond those scalars, or a
        multi-device mesh — the grid axis occupies the batch dimension,
        so sharded data training keeps the sequential path)."""
        if len(params_list) < 2:
            return None
        if pd.binned_request is not None:
            # the vmapped grid needs host COO; the zero-copy lane has
            # none — sequential per-candidate trains share the binned
            # layout via the cache instead (same key across candidates
            # differing only in the grid scalars)
            return None
        base = params_list[0]
        _GRID_SCALARS = ("lambda_", "alpha", "num_iterations", "cg_iters")
        for p in params_list:
            if not isinstance(p, ALSParams):
                return None
            a, b = dict(vars(p)), dict(vars(base))
            for k in _GRID_SCALARS:
                a.pop(k), b.pop(k)
            if a != b:
                return None
        if (base.max_ratings_per_user is not None
                or base.max_ratings_per_item is not None):
            # als_grid_train builds its sides uncapped; silently
            # training different data than the sequential path would is
            # exactly the kind of divergence grid tuning must not have
            # (code-review regression) — sequential path instead
            return None
        if ctx.mesh is not None and np.prod(
                [ctx.mesh.shape[a] for a in ctx.mesh.axis_names]) > 1:
            return None
        from predictionio_tpu.ops.als import als_grid_train

        cfg = ALSConfig(
            rank=base.rank, iterations=base.num_iterations,
            implicit=base.implicit_prefs, alpha=base.alpha,
            block_size=base.block_size, seed=base.seed,
            seg_len=base.seg_len, solver=base.solver,
            cg_iters=base.cg_iters, cg_unroll=base.cg_unroll,
            cg_precond=base.cg_precond, cg_dtype=base.cg_dtype,
            compute_dtype=base.compute_dtype,
        )
        factors_list = als_grid_train(
            (pd.user_idx, pd.item_idx, pd.ratings),
            pd.n_users, pd.n_items, cfg,
            regs=[p.lambda_ for p in params_list],
            alphas=[p.alpha for p in params_list],
            iterations=[p.num_iterations for p in params_list],
            cg_iters=[p.cg_iters for p in params_list],
        )
        return [ALSModel(f, pd.user_ids, pd.item_ids,
                         index_backend=base.index_backend,
                         index_kernel=base.index_kernel)
                for f in factors_list]

    def load_persistent_model(self, persisted: ALSModel, ctx: MeshContext) -> ALSModel:
        """Re-enable sharded serving after unpickle when the model was
        trained with it (the mesh never pickles; rebuild from ctx)."""
        axis = getattr(persisted, "sharded_axis", None)
        if axis is not None:
            mesh = ctx.require_mesh()
            if axis in mesh.axis_names and mesh.shape[axis] > 1:
                persisted.enable_sharded_serving(mesh, axis=axis)
            else:
                persisted.sharded_axis = None  # single-device deploy
        return persisted

    def warmup(self, model: ALSModel, ctx: MeshContext) -> None:
        """Pre-warm the serve path so the first queries after
        deploy/reload answer at steady-state latency (SURVEY.md §7.5
        hard part #2). The retrieval index is BUILT here, at model load
        (``pio_index_build_seconds`` prices it, never a live query),
        and the model's one retriever is run at every (B, k) bucket the
        server can dispatch: B buckets 1...64 (the default micro-batch
        cap; a lone query is B = 1), k buckets 8 and 16. An item ->
        similar query and a user query without a blacklist are the same
        (B, E = 1, k) shape. On the chip each bucket is one ``topk_dot``
        compile that would otherwise block a LIVE batch; on the CPU
        backend the index's XLA fallback takes them, as a lone search
        does. Deploy/reload warm BEFORE the swap, so this cost never
        blocks traffic. A sharded model warms its mesh scorer at the
        same buckets and builds no index: that would device-put the
        FULL item table onto one chip, the exact thing the sharded
        catalogue can't hold."""
        if len(model.user_ids) == 0 or len(model.item_ids) == 0:
            return
        for b in (1, 2, 4, 8, 16, 32, 64):
            # batch size is bounded by CONCURRENT QUERIES (max_batch),
            # not distinct users — duplicate-user queries coalesce into
            # big batches, so small catalogs still need every bucket
            # warm (tile rows instead of capping at the user count)
            rows = model.user_factors[np.arange(b) % len(model.user_ids)]
            for k in (5, 10):
                model.retrieve(rows, k)

    def predict(self, model: ALSModel, query: Dict[str, Any]) -> Dict[str, Any]:
        num = int(query.get("num", 10))
        if "user" not in query and "item" in query:
            # item -> top-num similar items: candidate generation
            # through the retrieval index (the similarproduct-style
            # query surface on the factor templates)
            ranked = model.similar_items(
                str(query["item"]), num,
                exclude_items=query.get("blacklist") or ())
        else:
            ranked = model.recommend(
                str(query["user"]),
                num,
                exclude_items=query.get("blacklist") or (),
                candidate_items=query.get("whitelist"),
            )
        return _item_scores(ranked)

    def batch_predict(self, model: ALSModel, queries):
        """Many queries as ONE retrieval (micro-batched serving, ref:
        batchPredict for eval): user and item queries, each with its
        own ``num`` and ``blacklist``, become the rows of one search of
        the model's retriever (``ALSModel.answer``), so a query gets
        the answer it would get alone. Unknown users and items get
        empty results; a ``whitelist`` query has no batched form and is
        answered through ``predict``."""
        out, batched, alone = [], [], []
        with trace.device_span("engine.prepare"):
            for i, q in queries:
                if q.get("whitelist") is not None:
                    alone.append((i, q))
                    continue
                blacklist = q.get("blacklist") or ()
                if "user" not in q and "item" in q:
                    plan = model.item_plan(str(q["item"]), blacklist)
                else:
                    plan = model.user_plan(str(q["user"]), blacklist)
                if plan is None:
                    out.append((i, _item_scores(())))
                else:
                    batched.append((i, plan, int(q.get("num", 10))))
        ranked = model.answer([plan for _, plan, _ in batched],
                              [num for _, _, num in batched])
        out.extend((i, _item_scores(r))
                   for (i, _, _), r in zip(batched, ranked))
        out.extend((i, self.predict(model, q)) for i, q in alone)
        return out


def _item_scores(ranked) -> Dict[str, Any]:
    """The recommendation templates' result shape."""
    return {"itemScores": [{"item": i, "score": s} for i, s in ranked]}
