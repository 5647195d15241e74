"""Sequential (next-item) recommendation as a DASE Algorithm.

The long-context model family of the rebuild — no reference counterpart
exists (SURVEY.md §5.7: PredictionIO has no sequence dimension), so the
behavior contract is the recommendation template's query surface
(top-``num`` itemScores, ref: examples/scala-parallel-recommendation
Serving.scala) applied to *ordered* histories: the model answers "what
comes next for this user", not "what does this user like overall".
Compute core: ops.sessionrec (causal transformer; blockwise or ring
attention for histories past one device's HBM).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.core import Algorithm, SanityCheck
from predictionio_tpu.core.params import Params
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.obs import trace
from predictionio_tpu.ops.sessionrec import (
    SessionRecConfig,
    SessionRecModelState,
    SessionRecTrainer,
    SessionScorer,
)
from predictionio_tpu.parallel.mesh import MeshContext


@dataclass
class PreparedSequences(SanityCheck):
    """PD for sequence models: indexed, timestamped interaction triples."""

    user_ids: BiMap
    item_ids: BiMap
    user_idx: np.ndarray     # [n] int
    item_idx: np.ndarray     # [n] int
    times: np.ndarray        # [n] float64 (epoch seconds)

    @property
    def n_users(self) -> int:
        return len(self.user_ids)

    @property
    def n_items(self) -> int:
        return len(self.item_ids)

    def sanity_check(self) -> None:
        if len(self.user_idx) == 0:
            raise ValueError("PreparedSequences is empty — no events found")
        if not (len(self.user_idx) == len(self.item_idx) == len(self.times)):
            raise ValueError("sequence arrays length mismatch")


@dataclass
class SessionRecParams(Params):
    dim: int = 64
    heads: int = 2
    layers: int = 2
    ffn_mult: int = 4
    max_len: int = 64
    dropout: float = 0.1
    learning_rate: float = 1e-3
    weight_decay: float = 1e-6
    epochs: int = 5
    batch_size: int = 256
    seed: int = 13
    attn_block: int = 0              # >0: flash-style blockwise attention
    seq_axis: Optional[str] = None   # mesh axis for ring attention (SP)
    checkpoint_dir: Optional[str] = None   # mid-training checkpoint/resume
    checkpoint_every: int = 1


class SessionRecModel:
    """Params + per-user histories + id maps; scorer compiled lazily."""

    def __init__(self, state: SessionRecModelState, user_ids: BiMap, item_ids: BiMap):
        self.state = state
        self.user_ids = user_ids
        self.item_ids = item_ids
        self._scorer: Optional[SessionScorer] = None

    def __getstate__(self):
        d = dict(self.__dict__)
        d["_scorer"] = None          # device buffers never pickle
        return d

    def scorer(self) -> SessionScorer:
        if self._scorer is None:
            self._scorer = SessionScorer(self.state)
        return self._scorer

    def _sequence_for(self, query: Dict[str, Any]) -> Optional[np.ndarray]:
        """Resolve the history to encode: an explicit ``items`` list in
        the query (session-based, works for anonymous users) wins over
        the stored training history."""
        max_len = self.state.cfg.max_len
        items = query.get("items")
        if items is not None:
            idx = [self.item_ids[i] + 1 for i in map(str, items) if i in self.item_ids]
            if not idx:
                return None
            row = np.zeros(max_len, np.int32)
            tail = idx[-max_len:]
            row[: len(tail)] = tail
            return row
        row_id = self.user_ids.get(str(query.get("user", "")))
        if row_id is None:
            return None
        row = self.state.sequences[row_id]
        return row if (row > 0).any() else None

    def recommend(self, query: Dict[str, Any]) -> List[Tuple[str, float]]:
        seq = self._sequence_for(query)
        if seq is None:
            return []
        num = int(query.get("num", 10))
        scores, idx = self.scorer().top_k(
            seq[None, :], num, exclude_seen=bool(query.get("excludeSeen", False))
        )
        inv = self.item_ids.inverse()
        return [
            (inv[int(i)], float(s))
            for s, i in zip(scores[0], idx[0])
            if i >= 0 and np.isfinite(s)
        ]


class SessionRecAlgorithm(Algorithm):
    """DASE wrapper over ops.sessionrec."""

    def __init__(self, params: SessionRecParams):
        super().__init__(params)

    def train(self, ctx: MeshContext, pd: PreparedSequences) -> SessionRecModel:
        p: SessionRecParams = self.params
        cfg = SessionRecConfig(
            dim=p.dim, heads=p.heads, layers=p.layers, ffn_mult=p.ffn_mult,
            max_len=p.max_len, dropout=p.dropout,
            learning_rate=p.learning_rate, weight_decay=p.weight_decay,
            epochs=p.epochs, batch_size=p.batch_size, seed=p.seed,
            attn_block=p.attn_block, seq_axis=p.seq_axis,
            checkpoint_dir=p.checkpoint_dir,
            checkpoint_every=p.checkpoint_every,
        )
        # ring attention needs a mesh even when the caller didn't build
        # one (same contract as ALSAlgorithm: require on demand)
        mesh = ctx.require_mesh() if p.seq_axis else ctx.mesh
        trainer = SessionRecTrainer(
            (pd.user_idx, pd.item_idx, pd.times),
            pd.n_users, pd.n_items, cfg, mesh=mesh,
        )
        losses = trainer.run()
        state = trainer.state(losses)
        return SessionRecModel(state, pd.user_ids, pd.item_ids)

    def warmup(self, model: SessionRecModel, ctx: MeshContext) -> None:
        """Pre-compile the B=1 encoder + top-k for both excludeSeen
        variants (the flag is jit-static) so the first live session
        query answers at warm latency."""
        if len(model.item_ids) == 0:
            return
        seq = np.zeros((1, model.state.cfg.max_len), np.int32)
        seq[0, 0] = 1  # one real (1-shifted) item position
        for exclude_seen in (False, True):
            model.scorer().top_k(seq, 10, exclude_seen=exclude_seen)

    def predict(self, model: SessionRecModel, query: Dict[str, Any]) -> Dict[str, Any]:
        recs = model.recommend(query)
        return {"itemScores": [{"item": i, "score": s} for i, s in recs]}

    def batch_predict(self, model: SessionRecModel, queries):
        """Batched evaluation: resolve every query's history, encode and
        score them as one fixed-shape device batch per excludeSeen value
        (the flag is jit-static, so mixed batches split in two)."""
        groups: Dict[bool, list] = {False: [], True: []}
        out = []
        for qi, q in queries:
            seq = model._sequence_for(q)
            if seq is None:
                out.append((qi, {"itemScores": []}))
            else:
                groups[bool(q.get("excludeSeen", False))].append((qi, q, seq))
        inv = model.item_ids.inverse()
        for exclude_seen, resolved in groups.items():
            if not resolved:
                continue
            batch = np.stack([seq for _, _, seq in resolved])
            num = max(int(q.get("num", 10)) for _, q, _ in resolved)
            scores, idx = model.scorer().top_k(
                batch, num, exclude_seen=exclude_seen
            )
            for (qi, q, _), s_row, i_row in zip(resolved, scores, idx):
                n = int(q.get("num", 10))
                out.append((qi, {
                    "itemScores": [
                        {"item": inv[int(i)], "score": float(s)}
                        for s, i in zip(s_row[:n], i_row[:n])
                        if i >= 0 and np.isfinite(s)
                    ]
                }))
        return out


# ---------------------------------------------------------------------------
# A latent-attention stack served in steps over a per-session latent cache
# ---------------------------------------------------------------------------

class LatentCache:
    """Which slot holds which session's latents. Host bookkeeping only: the
    latents themselves are the device arrays of ``ops.sessionrec
    .StackPrograms``; a slot here is the list of item rows whose latents it
    holds, in order.

    A query's rows are matched against the free slots by LONGEST COMMON
    PREFIX. A slot is a hit when the shared prefix is at least half of what
    the slot holds (the session grew, went back a little, or diverged late):
    the query then extends from the end of the prefix, and what the slot held
    beyond it is simply written over. Anything less is a miss: the least
    recently used free slot is taken and the history prefilled from its
    start. A slot is busy while a query is being answered from it."""

    def __init__(self, n_slots: int):
        self.rows = [np.zeros(0, np.int32) for _ in range(n_slots)]
        self.busy = [False] * n_slots
        self.used = [0] * n_slots
        self._clock = 0
        self.hit_tokens = self.miss_tokens = self.evictions = 0

    @staticmethod
    def common_prefix(a: np.ndarray, b: np.ndarray) -> int:
        n = min(len(a), len(b))
        differ = np.flatnonzero(a[:n] != b[:n])
        return int(differ[0]) if len(differ) else n

    def acquire(self, rows: np.ndarray) -> Optional[Tuple[int, int]]:
        """``(slot, positions already cached)`` for a query over ``rows``,
        or None while every slot is busy. At least the last position is
        always left to compute: its hidden state is the answer."""
        free = [s for s, b in enumerate(self.busy) if not b]
        if not free:
            return None
        with trace.device_span("seq.cache.lookup", rows=len(rows)):
            shared = [self.common_prefix(self.rows[s], rows) for s in free]
            best = max(range(len(free)), key=shared.__getitem__)
            slot, cached = free[best], min(shared[best], len(rows) - 1)
            if cached < 1 or 2 * shared[best] < len(self.rows[slot]):
                cached = 0
                slot = min(free, key=self.used.__getitem__)
                if len(self.rows[slot]):
                    self.evictions += 1
                    with trace.device_span("seq.cache.evict", slot=slot):
                        self.rows[slot] = np.zeros(0, np.int32)
            # a span's attributes are set as it opens: the outcome rides on
            # a marker inside the lookup
            with trace.device_span("seq.cache.found", slot=slot,
                                   hit_tokens=cached,
                                   miss_tokens=len(rows) - cached):
                pass
        self.hit_tokens += cached
        self.miss_tokens += len(rows) - cached
        self.busy[slot] = True
        return slot, cached

    def release(self, slot: int, rows: np.ndarray) -> None:
        """The slot now holds exactly ``rows``' latents."""
        self._clock += 1
        self.rows[slot] = rows
        self.used[slot] = self._clock
        self.busy[slot] = False


class SeqTicket:
    """One query on its way through the steps."""

    __slots__ = ("rows", "num", "slot", "done", "born", "result",
                 "extension")

    def __init__(self, rows, num, slot, done, born):
        self.rows, self.num, self.slot = rows, num, slot
        self.done, self.born = done, born
        #: whether the query arrived as an extension (a few new positions
        #: beyond what its slot held), as opposed to a history to prefill
        self.extension = False
        self.result: Optional[List[Tuple[str, float]]] = None

    @property
    def remaining(self) -> int:
        return len(self.rows) - self.done


class SeqStackModel:
    """A block stack with latent-attention mixers behind the ``items`` query,
    served in STEPS: each step runs every pending extension (a few new
    positions each) as one batch, and at most one prefill chunk of the
    oldest session that still has a history to encode. The engine server's
    step worker drives :meth:`begin` / :meth:`step` through
    :class:`SeqStackAlgorithm`; :meth:`recommend` drives them to the end for
    one query (``predict``).

    ``params`` are device arrays in the stack's layout (``ops.sessionrec
    .init_stack``); the head is the exact retrieval index over the output
    embedding, as for every other model's final projection."""

    def __init__(self, spec, params, item_ids: BiMap, shape=None):
        from predictionio_tpu.ops.sessionrec import ServeShape

        self.spec, self.params, self.item_ids = spec, params, item_ids
        self.shape = shape or ServeShape()
        self.cache = LatentCache(self.shape.n_slots)
        self._programs = None
        self._index = None
        self._inverse = None
        self.steps = 0
        # what the steps did, summed since deploy. Per program kind
        # ("extend" / "prefill"): runs, real tokens, (token, pick) pairs
        # that reached a held expert, held experts that got any token (per
        # layer, summed), zero-compute picks; extensions: cached positions
        # their attention read
        self.counters = {
            f"{kind}_{what}": 0 for kind in ("extend", "prefill")
            for what in ("runs", "tokens", "held_picks", "experts_touched",
                         "zero_picks")}
        self.counters.update({
            "extend_rows": 0, "extend_latent_positions": 0,
            "extensions_waited": 0,
            # per program run and layer: the fullest held expert's tokens,
            # and the held experts' mean, summed
            "load_max_sum": 0.0, "load_mean_sum": 0.0})

    def __getstate__(self):
        raise TypeError(
            "a SeqStackModel holds device arrays and a live cache and is "
            "not pickled: persist it as a core.persistent_model"
            ".PersistentModel whose load() builds it")

    # -- device side ----------------------------------------------------------
    def programs(self):
        if self._programs is None:
            from predictionio_tpu.index.exact import ExactIndex
            from predictionio_tpu.ops.sessionrec import StackPrograms

            self._programs = StackPrograms(self.spec, self.params, self.shape)
            head = self.params["item_embed" if self.spec.tied_head
                               else "head"]
            head = head["embedding"] if isinstance(head, dict) else head
            self._index = ExactIndex()
            self._index.build(np.asarray(head, np.float32))
            self._inverse = self.item_ids.inverse()
        return self._programs

    def stats(self) -> Dict[str, Any]:
        c = self.cache
        return {**self.counters, "steps": self.steps,
                "hit_tokens": c.hit_tokens, "miss_tokens": c.miss_tokens,
                "evictions": c.evictions,
                "index": self._index.stats() if self._index else None}

    # -- one query, in steps --------------------------------------------------
    def resolve(self, query: Dict[str, Any]) -> np.ndarray:
        ids = self.item_ids
        rows = [ids[i] for i in map(str, query.get("items") or ()) if i in ids]
        return np.asarray(rows[-self.shape.capacity:], np.int32)

    def begin(self, query: Dict[str, Any]) -> Optional[SeqTicket]:
        """A ticket for the query, its slot taken; None while every slot is
        busy (ask again after a step). A query that names no known item is
        finished at once, with nothing."""
        rows = self.resolve(query)
        num = int(query.get("num", 10))
        if len(rows) == 0:
            ticket = SeqTicket(rows, num, None, 0, self.steps)
            ticket.result = []
            return ticket
        got = self.cache.acquire(rows)
        if got is None:
            return None
        ticket = SeqTicket(rows, num, got[0], got[1], self.steps)
        ticket.extension = ticket.remaining <= self.shape.extend_len
        return ticket

    def cancel(self, ticket: SeqTicket) -> None:
        if ticket.slot is not None:
            self.cache.release(ticket.slot, ticket.rows[:ticket.done])

    def step(self, tickets: List[SeqTicket],
             done=lambda ticket: None) -> List[SeqTicket]:
        """One step over the pending ``tickets`` (oldest first): every
        extension (at most ``extend_batch``), then one prefill chunk.
        Returns the tickets it finished, their ``result`` set (a ticket that
        was answered at admission among them); ``done`` is called with each
        as soon as it is, so that an extension's answer leaves before the
        step's prefill chunk begins."""
        programs, sh = self.programs(), self.shape
        finished = [t for t in tickets if t.result is not None]
        for t in finished:                  # answered at admission
            done(t)
        pending = [t for t in tickets if t.result is None]
        if not pending:
            return finished
        ext = [t for t in pending if t.remaining <= sh.extend_len]
        ext = ext[:sh.extend_batch]
        pre = next((t for t in pending if t.remaining > sh.extend_len), None)
        self.steps += 1
        with trace.device_span(
                "seq.step", n_extend=len(ext), seq=self.steps,
                prefill_tokens=(min(pre.remaining, sh.chunk) if pre else 0)):
            if ext:
                with trace.device_span("seq.extend", rows=len(ext)):
                    h, counted = programs.extend(
                        [(t.rows[t.done:], t.slot, t.done) for t in ext])
                    h.block_until_ready()
                self._count("extend", counted)
                self.counters["extend_rows"] += len(ext)
                self.counters["extend_latent_positions"] += sum(
                    len(t.rows) for t in ext)
                for t in ext:
                    # born at step b, first step it could join is b + 1
                    if self.steps - t.born > 1 and t.extension:
                        self.counters["extensions_waited"] += 1
                    t.done = len(t.rows)
                self._answer(ext, h, done)
                finished += ext
            if pre is not None:
                n = min(pre.remaining, sh.chunk)
                with trace.device_span("seq.prefill_chunk", slot=pre.slot,
                                       offset=pre.done, tokens=n):
                    h, counted = programs.prefill(
                        pre.rows[pre.done:pre.done + n], pre.slot, pre.done)
                    h.block_until_ready()
                self._count("prefill", counted)
                pre.done += n
                if pre.remaining == 0:
                    self._answer([pre], h, done)
                    finished.append(pre)
        return finished

    def _count(self, kind: str, counted) -> None:
        c = self.counters
        c[f"{kind}_runs"] += 1
        c[f"{kind}_tokens"] += int(counted["tokens"])
        if "expert_load" in counted:
            load = np.asarray(counted["expert_load"], np.int64)
            c[f"{kind}_held_picks"] += int(load.sum())
            c[f"{kind}_experts_touched"] += int((load > 0).sum())
            c[f"{kind}_zero_picks"] += int(
                np.asarray(counted["zero_picks"]).sum())
            c["load_max_sum"] += float(load.max(axis=1).sum())
            c["load_mean_sum"] += float(load.mean(axis=1).sum())

    def _answer(self, tickets: List[SeqTicket], h_last, done) -> None:
        """``h_last``: a program's whole output (its rows beyond the
        tickets are padding), so that the head sees two shapes only."""
        with trace.device_span("seq.head", size=len(tickets)):
            k = max(t.num for t in tickets)
            scores, idx = self._index.search(h_last, k)
        inv = self._inverse
        for t, s_row, i_row in zip(tickets, scores, idx):
            t.result = [(inv[int(i)], float(s))
                        for s, i in zip(s_row[:t.num], i_row[:t.num])
                        if i >= 0 and np.isfinite(s)]
            self.cache.release(t.slot, t.rows)
            done(t)

    def recommend(self, query: Dict[str, Any]) -> List[Tuple[str, float]]:
        ticket = self.begin(query)
        if ticket is None:
            raise RuntimeError("every cache slot is busy")
        while ticket.result is None:
            self.step([ticket])
        return ticket.result


@dataclass
class SeqStackParams(Params):
    """Serve-time shapes of a stack model (``ops.sessionrec.ServeShape``);
    the stack itself (its ``StackSpec`` and weights) comes with the model."""

    n_slots: int = 32
    capacity: int = 8192
    chunk: int = 512
    extend_len: int = 8
    extend_batch: int = 8

    def shape(self):
        import dataclasses

        from predictionio_tpu.ops.sessionrec import ServeShape

        return ServeShape(**dataclasses.asdict(self))


class SeqStackAlgorithm(Algorithm):
    """Serves a :class:`SeqStackModel`. Training such a stack is not this
    system's yet (``ROADMAP.md`` §2): its model arrives through
    ``core.persistent_model`` from whoever holds its weights."""

    stepwise = True

    def __init__(self, params: SeqStackParams):
        super().__init__(params)

    def train(self, ctx: MeshContext, pd) -> SeqStackModel:
        raise NotImplementedError(
            "training a latent-attention expert stack is not supported; "
            "deploy one through a PersistentModel")

    def warmup(self, model: SeqStackModel, ctx: MeshContext) -> None:
        """Compile both serve programs and every head batch, then run each
        once, so that no query compiles anything."""
        programs, sh = model.programs(), model.shape
        h, _ = programs.prefill(np.zeros(1, np.int32), sh.n_slots, 0)
        hs, _ = programs.extend([(np.zeros(1, np.int32), sh.n_slots, 1)])
        for h_last in (h, hs):
            model._index.search(h_last, 10)

    @staticmethod
    def _prediction(recs) -> Dict[str, Any]:
        return {"itemScores": [{"item": i, "score": s} for i, s in recs]}

    def predict(self, model: SeqStackModel, query: Dict[str, Any]):
        return self._prediction(model.recommend(query))

    # -- in steps (``core.Algorithm.stepwise``) --------------------------------
    def begin(self, model: SeqStackModel, query: Dict[str, Any]):
        return model.begin(query)

    def step(self, model: SeqStackModel, tickets, done) -> None:
        model.step(tickets, lambda t: done(t, self._prediction(t.result)))

    def cancel(self, model: SeqStackModel, ticket) -> None:
        model.cancel(ticket)
