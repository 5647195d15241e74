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

import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from predictionio_tpu.core import Algorithm, SanityCheck
from predictionio_tpu.core.params import Params
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.obs import trace
from predictionio_tpu.ops.gqa import ring_len
from predictionio_tpu.ops.sessionrec import (
    ServeShape,
    SessionRecConfig,
    SessionRecModelState,
    SessionRecTrainer,
    SessionScorer,
    StackPrograms,
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
# A stack served in steps over a per-session cache
# ---------------------------------------------------------------------------

class LatentCache:
    """Which slot holds which session's cache, of whatever kinds the stack's
    mixers keep (latents, keys and values, recurrent state). Host bookkeeping
    only: the values themselves are the device arrays of ``ops.sessionrec
    .StackPrograms``; a slot here is the list of item rows whose positions it
    holds, in order. A slot is busy while a query is being answered from it.

    How a query's rows are matched against the free slots is the stack's to
    say:

    * every mixer keeps a PER-POSITION cache: by longest common prefix. A
      slot is a hit when the shared prefix is at least half of what the slot
      holds (the session grew, went back a little, or diverged late): the
      query then extends from the end of the prefix, and what the slot held
      beyond it is simply written over. ``block``: under a block-causal mask
      a position's cached values depend on every position of its block, so
      only whole shared blocks are reusable: the match rounds DOWN to a block
      boundary;
    * some mixer keeps a RECURRENT state (``recurrent``): the state stands at
      one position, the end of the slot's rows, and cannot be rewound, so a
      slot is a hit only when ALL of its rows are a proper prefix of the
      query's (the session grew). "Went back a little", "diverged late" and
      a repeated query (its last position is always left to compute, and the
      state is already past it) are misses for every layer together, the
      per-position ones too; where the other rule would have hit, a marker
      (``seq.cache.rewind_miss``) says so, and the session starts over in
      the slot it had;
    * some mixer keeps a RING (``ring`` rows for a ``window`` of positions:
      a sliding-window layer beside layers whose spans grow): the slot holds
      its last ``ring`` positions and no earlier one, and a query at position
      ``p`` attends ``p - (window - 1) .. p``. The prefix rule stands, but a
      slot can resume at ``p`` only while its rings still hold ``p - (window
      - 1) .. p - 1`` (``floor``: the earliest position every ring of the
      slot still holds): "grew" always can, "went back a little" can by up
      to ``ring - window`` positions, and past that it is a miss for every
      layer together, the spans too (``seq.cache.ring_miss``), from position
      0 in the slot the session had. A slot's rings and spans are evicted
      and reused together: they are one slot. In a stack that ALSO keeps a
      recurrent state the whole-prefix rule decides alone: a slot resumes
      only from the end of its rows, where its rings hold the whole window
      by construction (``ring >= window``), so the rings' floor is never
      asked and every would-be hit is a ``rewind_miss``.

    A miss takes the least recently used free slot and the history is
    prefilled from its start — from a ZERO state: the programs start any
    call at position 0 so, whatever the slot held."""

    def __init__(self, n_slots: int, block: int = 1, recurrent: bool = False,
                 ring: int = 0, window: int = 0):
        self.rows = [np.zeros(0, np.int32) for _ in range(n_slots)]
        self.busy = [False] * n_slots
        self.used = [0] * n_slots
        self.block, self.recurrent = block, recurrent
        self.ring, self.window = ring, window
        #: the earliest position each slot's rings still hold
        self.floor = [0] * n_slots
        #: queries the prefix rule would have resumed and the rings could
        #: not, and the positions it would have found
        self.ring_misses = self.ring_miss_tokens = 0
        self._clock = 0
        self.hit_tokens = self.miss_tokens = self.evictions = 0
        #: hits of a stack with recurrent state; queries a per-position
        #: cache would have hit and the state could not, and the positions
        #: it would have found
        self.state_resumes = self.rewind_misses = self.rewind_miss_tokens = 0

    @staticmethod
    def common_prefix(a: np.ndarray, b: np.ndarray) -> int:
        n = min(len(a), len(b))
        differ = np.flatnonzero(a[:n] != b[:n])
        return int(differ[0]) if len(differ) else n

    def _match(self, free: List[int], rows: np.ndarray) -> Tuple[int, int]:
        """``(slot, positions of it that the query reuses)``; 0: a miss,
        which takes that slot from its start."""
        shared = [self.common_prefix(self.rows[s], rows) for s in free]
        if self.recurrent:
            whole = [f for f, n in zip(free, shared)
                     if 0 < n == len(self.rows[f]) < len(rows)]
            if whole:
                self.state_resumes += 1
                slot = max(whole, key=lambda f: len(self.rows[f]))
                return slot, len(self.rows[slot])
        best = max(range(len(free)), key=shared.__getitem__)
        slot, cached = free[best], min(shared[best], len(rows) - 1)
        cached -= cached % self.block
        if cached < 1 or 2 * shared[best] < len(self.rows[slot]):
            return min(free, key=self.used.__getitem__), 0
        if (self.ring and not self.recurrent
                and max(cached - (self.window - 1), 0) < self.floor[slot]):
            self.ring_misses += 1
            self.ring_miss_tokens += cached
            with trace.device_span("seq.cache.ring_miss", slot=slot,
                                   shared=shared[best],
                                   floor=self.floor[slot]):
                pass
            return slot, 0
        if not self.recurrent:
            return slot, cached
        self.rewind_misses += 1
        self.rewind_miss_tokens += cached
        with trace.device_span("seq.cache.rewind_miss", slot=slot,
                               shared=shared[best]):
            pass
        return slot, 0

    def acquire(self, rows: np.ndarray) -> Optional[Tuple[int, int]]:
        """``(slot, positions already cached)`` for a query over ``rows``,
        or None while every slot is busy. At least the last position is
        always left to compute: its hidden state is the answer."""
        free = [s for s, b in enumerate(self.busy) if not b]
        if not free:
            return None
        with trace.device_span("seq.cache.lookup", rows=len(rows)):
            slot, cached = self._match(free, rows)
            if not cached:      # written anew from position 0: all is held
                self.floor[slot] = 0
                if len(self.rows[slot]):
                    self.evictions += 1
                    self.rows[slot] = np.zeros(0, np.int32)
        self.hit_tokens += cached
        self.miss_tokens += len(rows) - cached
        self.busy[slot] = True
        return slot, cached

    def release(self, slot: int, rows: np.ndarray) -> None:
        """The slot now holds exactly ``rows``' positions (a recurrent
        state: it stands at their end)."""
        self._clock += 1
        self.rows[slot] = rows
        if self.ring:       # what was written pushed the earliest rows out
            self.floor[slot] = max(self.floor[slot], len(rows) - self.ring)
        self.used[slot] = self._clock
        self.busy[slot] = False


class SeqTicket:
    """One query on its way through the steps. A query that asks a block-
    diffusion stack for ``generate`` items lives through many: its history's
    whole blocks are encoded (``done`` of ``known`` positions), then block
    after block is denoised and committed, ``forwards`` block forwards in
    all, until ``done`` reaches ``end``."""

    __slots__ = ("rows", "num", "slot", "done", "result",
                 "extension", "generate", "known", "end", "block",
                 "denoised", "forwards", "found", "tail", "admitted_ns",
                 "launched_ns")

    def __init__(self, rows, num, slot, done):
        self.rows, self.num, self.slot, self.done = rows, num, slot, done
        #: the host's clock at admission, and where the first program that
        #: carries rows of this ticket was launched (None until then)
        self.admitted_ns = time.perf_counter_ns()
        self.launched_ns: Optional[int] = None
        #: whether the query arrived as an extension (a few new positions
        #: beyond what its slot held), as opposed to a history to prefill
        self.extension = False
        self.result: Optional[list] = None
        self.generate = 0
        #: positions of the history's whole blocks; where the last block to
        #: generate ends
        self.known = self.end = len(rows)
        #: the block being denoised (mask rows where masked), how many
        #: denoise forwards it has had, and the slate's forwards so far
        self.block: Optional[np.ndarray] = None
        self.denoised = self.forwards = 0
        #: position -> (item row, score, confidence, step)
        self.found: Dict[int, tuple] = {}
        self.tail: list = []

    @property
    def remaining(self) -> int:
        """Positions of the query's own rows still to encode."""
        return self.known - self.done

    def held(self) -> np.ndarray:
        """The rows whose cached positions are final: ``done`` of them,
        the history's and then the generated blocks that were committed."""
        beyond = [self.found[p][0] for p in range(len(self.rows), self.done)]
        return np.concatenate(
            [self.rows, np.asarray(beyond, np.int32)])[:self.done]


#: the span names that make up the step worker's cycle, as
#: ``SeqStackModel.stats()`` reports them (benchmarks/WORKER_PHASES.md)
STEP_PHASES = (
    "batch.idle", "batch.collect", "seq.cache.lookup", "seq.step",
    "seq.extend", "seq.prefill_chunk", "seq.block_step", "seq.launch",
    "seq.wait", "seq.count", "seq.decide", "seq.head", "index.search",
    "index.enqueue", "index.fetch", "batch.deliver", "engine.decode", "gc",
    trace.UNSPANNED)


class StepPlan(NamedTuple):
    """What one step runs, as :func:`plan_step` chose it."""

    answered: List[SeqTicket]       # at admission: delivered, nothing to run
    extend: List[SeqTicket]         # the one extension batch
    #: the one block forward's rows ``(ticket, kind, position, n_unmask)``:
    #: ``"known"`` (a whole block of the history, committed as it is),
    #: ``"denoise"`` (the block it generates, masked or to open), ``"commit"``
    block: List[Tuple[SeqTicket, str, int, int]]
    prefill: Optional[SeqTicket]    # who gets the one chunk, and its tokens
    prefill_tokens: int
    #: whether that chunk ENDS its ticket's history: in a stack with a
    #: cross-decoder only such a chunk runs it (for the row that is answered)
    prefill_last: bool = False


def plan_step(tickets: List[SeqTicket], shape, gen=None) -> StepPlan:
    """A step over the pending ``tickets`` (oldest first), chosen from them,
    the ``ServeShape`` and the stack's ``Generation`` (None: it answers
    once) alone: no ticket changes and no device is touched, so the ORDER of
    a stack's work is decided, and tested, here. Every ticket with at most
    ``extend_len`` positions left extends, oldest first, ``extend_batch`` a
    step; in a stack that generates it gives the block forward (``gen_batch``
    rows) the whole blocks its history still lacks and, behind them, the
    block it generates. The OLDEST ticket with more left gets the step's one
    chunk (``chunk`` positions at most): a FIFO of whole prefills; whether
    that chunk ends its history (``prefill_last``) says which chunk program a
    stack with a cross-decoder runs for it."""
    pending = [t for t in tickets if t.result is None]
    short = [t for t in pending if t.remaining <= shape.extend_len]
    pre = next((t for t in pending if t.remaining > shape.extend_len), None)
    rows = []
    for t in short if gen else ():
        at = t.done
        while at < t.known and len(rows) < shape.gen_batch:
            rows.append((t, "known", at, 0))
            at += gen.block_len
        if at < t.known or len(rows) == shape.gen_batch:
            continue
        # a block still to open is all masks behind the history's left-over
        # items (fewer than a block), and no forward has denoised it
        denoised = 0 if t.block is None else t.denoised
        if t.block is None or (t.block == gen.mask_row).any():
            rows.append((t, "denoise", at, gen.unmask_at_least(denoised)))
        else:
            rows.append((t, "commit", at, 0))
    return StepPlan(
        [t for t in tickets if t.result is not None],
        [] if gen else short[:shape.extend_batch], rows,
        pre, min(pre.remaining, shape.chunk) if pre else 0,
        pre is not None and pre.remaining <= shape.chunk)


class SeqStackModel:
    """A block stack with cached mixers (per-position caches, recurrent
    states, or both in one stack) behind the ``items`` query, served in
    STEPS: :func:`plan_step` chooses a step (an extension batch or a block
    forward, and one prefill chunk) and :meth:`step` runs it. The engine
    server's step worker drives :meth:`begin` / :meth:`step` through
    :class:`SeqStackAlgorithm`; :meth:`recommend` drives them to the end for
    one query (``predict``).

    A stack that does not generate answers ``{"items", "num"}`` once, from the
    last position's hidden state through the head (the exact retrieval index over
    the output embedding, as for every other model's final projection). A
    block-diffusion stack (``StackSpec.generation``) answers ``{"items",
    "generate": n}`` with ``n`` items in position order, each with the logit
    and the confidence of the forward that unmasked it and that forward's
    index (``step``); what the last block holds beyond ``n`` comes as
    ``blockTail``, so that every forward can be rebuilt from an answer.

    ``params`` are device arrays in the stack's layout (``ops.sessionrec
    .init_stack``)."""

    def __init__(self, spec, params, item_ids: BiMap, shape=None):
        self.spec, self.params, self.item_ids = spec, params, item_ids
        self.shape = shape or ServeShape()
        self.gen = spec.generation
        #: the kinds of cache the stack's mixers keep
        self.kinds = {b.mixer for b in spec.blocks}
        window = (spec.gqa_window.window if "gqa_window" in self.kinds
                  else 0)
        self.cache = LatentCache(
            self.shape.n_slots, self.gen.block_len if self.gen else 1,
            recurrent=bool(self.kinds & {"mamba2", "mamba1"}), window=window,
            ring=ring_len(window, self.shape.chunk) if window else 0)
        self._programs = None
        self._index = None
        self._inverse = None
        #: the span account of the thread that steps (obs/trace
        #: .ThreadAccount; the engine server's step worker keeps one)
        self._account = None
        self.steps = 0
        # what the steps did, summed since deploy, as the HOST knows it:
        # the cached positions the rows' attention read (extensions:
        # latents, or keys and values; blocks: keys and values) and the
        # rows whose recurrent states an extension read and wrote, each
        # where the stack has such a mixer. What the programs counted
        # (runs, tokens, where the picks went) stays on the device until
        # ``stats()`` is read: ``StackPrograms.totals``
        self.counters = {
            "extend_rows": 0, "extend_latent_positions": 0,
            # blocks of cached latents a layer's attention walked for the
            # extended rows (every row of a batch as far as its longest),
            # and what each row's own reach would have taken
            "extend_latent_blocks_attended": 0,
            "extend_latent_blocks_own": 0,
            # under a learned index the two above stay 0: the walk reads
            # the blocks the scorer scans (``extend_index_blocks``, on the
            # device) and a row attends only what it SELECTED of them: the
            # latents its new positions selected, a layer
            # (``min(index_topk, reach)`` each; the name is from when an
            # extension gathered them)
            "extend_latents_gathered": 0,
            "extend_kv_positions": 0, "extend_state_rows": 0,
            # the cached positions the rows' attention read in a WINDOW
            # layer: each row's new positions and the window before them
            "extend_window_positions": 0,
            # from a ticket's admission to the launch of the first program
            # that carries rows of it, summed, and the tickets summed over:
            # by that program, a prefill chunk (a first query in the FIFO
            # of whole prefills) or an extension (a block row counts as an
            # extension of its slate)
            "prefill_queue_ns": 0, "prefill_tickets": 0,
            "extend_queue_ns": 0, "extend_tickets": 0,
            # chunks whose attention ran in the ``chunk_attend`` kernel: the
            # device's ``prefill_runs`` in a stack of latent mixers, 0 in
            # every other (``StackPrograms.attend_kernel``)
            "prefill_attend_kernel_chunks": 0,
            # extension batches whose rows walked their span of keys and
            # values in the ``span_walk`` kernel: ``extend_runs`` in a
            # causal stack with a ``gqa`` layer, 0 in every other
            # (``StackPrograms.walk_kernel``)
            "extend_walk_kernel_batches": 0,
            # block forwards: rows by kind (a known block of a history is a
            # commit row), positions the rule unmasked, queries answered
            "denoise_rows": 0, "commit_rows": 0, "positions_unmasked": 0,
            "slates_done": 0, "block_kv_positions": 0,
            # device-to-host fetches of the programs' totals: reads of
            # ``stats()`` and drains
            "count_fetches": 0}
        #: what the programs counted (``StackPrograms.TOTAL_KINDS`` x
        #: ``TOTAL_FIELDS``): ``_drained`` of it in Python ints, the rest in
        #: ``_live``, the device's totals as the last counted program left
        #: them (None before the first), over ``_undrained`` runs. They and
        #: ``counters`` change together under the lock, so a reader on
        #: another thread sees all of them after the same runs
        self._drained = np.zeros((len(StackPrograms.TOTAL_KINDS),
                                  len(StackPrograms.TOTAL_FIELDS)), object)
        self._live = None
        self._undrained = 0
        self._lock = threading.Lock()

    def __getstate__(self):
        raise TypeError(
            "a SeqStackModel holds device arrays and a live cache and is "
            "not pickled: persist it as a core.persistent_model"
            ".PersistentModel whose load() builds it")

    # -- device side ----------------------------------------------------------
    def programs(self):
        if self._programs is None:
            from predictionio_tpu.index.exact import ExactIndex

            self._programs = StackPrograms(self.spec, self.params, self.shape)
            self._inverse = self.item_ids.inverse()
            if self.gen is None:    # a generating stack's head is in its
                head = self.params["item_embed" if self.spec.tied_head
                                   else "head"]          # block program
                head = head["embedding"] if isinstance(head, dict) else head
                self._index = ExactIndex()
                self._index.build(np.asarray(head, np.float32))
        return self._programs

    def stats(self) -> Dict[str, Any]:
        """The counters, the cache's, and where the stepping thread's time
        went: ``phase_<span name>_n`` / ``_wall_ns`` / ``_cpu_ns`` (self
        time on two clocks, obs/trace.ThreadAccount; ``_cpu_ns`` None where
        the host's CPU clock is too dear to read), always every name of
        ``STEP_PHASES`` (two snapshots subtract key by key) and what other
        spans that thread opened under ``other``; they add up to the
        thread's time.

        ``extend_*`` / ``prefill_*`` / ``block_*`` of ``StackPrograms
        .TOTAL_FIELDS`` are what that kind's programs counted on the device
        (with ``load_mean_sum``, the held experts' mean load a run and
        layer, summed as ``load_max_sum`` is): reading them is ONE fetch
        (``count_fetches``), from any thread, of what the last counted
        program left, so they and the host's counters are after the same
        runs."""
        with self._lock:
            live, totals = self._live, self._drained.copy()
            if live is not None:
                self.counters["count_fetches"] += 1
            counters = dict(self.counters)
        if live is not None:
            totals += np.asarray(live).tolist()
        counted = {kind: dict(zip(StackPrograms.TOTAL_FIELDS, row))
                   for kind, row in zip(StackPrograms.TOTAL_KINDS,
                                        totals.tolist())}
        picks = sum(got["held_picks"] for got in counted.values())
        counters.update(
            load_max_sum=float(sum(got.pop("load_max_sum")
                                   for got in counted.values())),
            load_mean_sum=(picks / self.spec.moe.held[1] if picks else 0.0))
        counters.update((f"{kind}_{field}", n)
                        for kind, got in counted.items()
                        for field, n in got.items())
        c = self.cache
        phases = {name: [0, 0, 0] for name in STEP_PHASES + ("other",)}
        if self._account is not None:
            for name, got in self._account.snapshot().items():
                into = phases[name if name in phases else "other"]
                for i, v in enumerate(got):
                    into[i] += v or 0
            if self._account.cpu_clock is None:     # not read: not measured
                for got in phases.values():
                    got[2] = None
        return {**counters, "steps": self.steps,
                **{f"phase_{name}_{what}": v
                   for name, got in phases.items()
                   for what, v in zip(("n", "wall_ns", "cpu_ns"), got)},
                "hit_tokens": c.hit_tokens, "miss_tokens": c.miss_tokens,
                "evictions": c.evictions, "state_resumes": c.state_resumes,
                "rewind_misses": c.rewind_misses,
                "rewind_miss_tokens": c.rewind_miss_tokens,
                "ring_misses": c.ring_misses,
                "ring_miss_tokens": c.ring_miss_tokens,
                "index": self._index.stats() if self._index else None}

    # -- one query, in steps --------------------------------------------------
    def resolve(self, query: Dict[str, Any], room: Optional[int] = None
                ) -> np.ndarray:
        ids = self.item_ids
        rows = [ids[i] for i in map(str, query.get("items") or ()) if i in ids]
        if self.gen is not None:        # a mask is never part of a history
            rows = [r for r in rows if r != self.gen.mask_row]
        return np.asarray(rows[-(room or self.shape.capacity):], np.int32)

    def begin(self, query: Dict[str, Any]) -> Optional[SeqTicket]:
        """A ticket for the query, its slot taken; None while every slot is
        busy (ask again after a step). A query that names no known item is
        finished at once, with nothing."""
        generate = int(query.get("generate", 0))
        room = None
        if self.gen is None and generate:
            raise ValueError("this stack generates nothing: ask it for "
                             '{"items", "num"}')
        if self.gen is not None:
            # the last block is denoised whole
            room = self.shape.capacity - generate - self.gen.block_len
            if generate < 1 or room < 1:
                raise ValueError(
                    'a block-diffusion stack answers {"items", "generate": '
                    f"n}} with 1 <= n < {self.shape.capacity}")
        rows = self.resolve(query, room)
        num = int(query.get("num", 10))
        if len(rows) == 0:
            ticket = SeqTicket(rows, num, None, 0)
            ticket.result = []
            return ticket
        got = self.cache.acquire(rows)
        if got is None:
            return None
        ticket = SeqTicket(rows, num, got[0], got[1])
        if self.gen is not None:
            B = self.gen.block_len
            ticket.generate = generate
            ticket.known = len(rows) - len(rows) % B
            ticket.end = -(-(len(rows) + generate) // B) * B
        ticket.extension = ticket.remaining <= self.shape.extend_len
        return ticket

    def cancel(self, ticket: SeqTicket) -> None:
        """The slot goes back holding what the ticket finished: ``done``
        positions, which is also where a recurrent state stands."""
        if ticket.slot is not None:
            self.cache.release(ticket.slot, ticket.held())

    def step(self, tickets: List[SeqTicket],
             done=lambda ticket: None) -> List[SeqTicket]:
        """One step over the pending ``tickets``: what :func:`plan_step`
        chose, in this order: extensions, block forward, prefill chunk.
        Returns the tickets it finished, their ``result`` set (a ticket that
        was answered at admission among them), possibly none: a slate takes
        many steps. ``done`` is called with each as soon as it is, so that
        an answer leaves before the step's prefill chunk begins."""
        self.programs()
        plan = plan_step(tickets, self.shape, self.gen)
        finished = list(plan.answered)
        for t in finished:
            done(t)
        if not (plan.extend or plan.block or plan.prefill):
            return finished
        self.steps += 1
        self._account = trace.thread_account() or self._account
        with trace.device_span(
                "seq.step", n_extend=len(plan.extend),
                n_block=len(plan.block), seq=self.steps,
                prefill_tokens=plan.prefill_tokens):
            if plan.extend:
                finished += self._run_extend(plan.extend, done)
            if plan.block:
                finished += self._block_forward(plan.block, done)
            if plan.prefill is not None:
                finished += self._run_prefill(
                    plan.prefill, plan.prefill_tokens, done,
                    plan.prefill_last)
        return finished

    def _run_extend(self, ext: List[SeqTicket], done) -> List[SeqTicket]:
        """The new positions of ``ext``, one batch; every one is answered."""
        programs, sh = self._programs, self.shape
        self._launching(ext, "extend")
        with trace.device_span("seq.extend", rows=len(ext)):
            with trace.device_span("seq.launch", program="extend"):
                h, _ = programs.extend(
                    [(t.rows[t.done:], t.slot, t.done) for t in ext])
            with trace.device_span("seq.wait", program="extend"):
                h.block_until_ready()
        reach = sum(len(t.rows) for t in ext)
        did = {"extend_rows": len(ext),
               "extend_walk_kernel_batches": int(programs.walk_kernel)}
        did.update((counter, n) for kind, counter, n in (
            ("mla", "extend_latent_positions", reach),
            ("gqa", "extend_kv_positions", reach),
            ("gqa_window", "extend_window_positions", sum(
                len(t.rows) - max(0, t.done - self.cache.window + 1)
                for t in ext)),
            ("mamba2", "extend_state_rows", len(ext)),
            ("mamba1", "extend_state_rows", len(ext)))
            if kind in self.kinds)
        if programs.indexed:
            topk = self.spec.mla.index_topk
            did["extend_latents_gathered"] = sum(
                min(at + 1, topk) for t in ext
                for at in range(t.done, len(t.rows)))
        elif "mla" in self.kinds:
            own = [int(programs.n_blocks(t.done + sh.extend_len))
                   for t in ext]
            did.update(extend_latent_blocks_own=sum(own),
                       extend_latent_blocks_attended=max(own) * len(ext))
        self._count("extend", did)
        for t in ext:
            t.done = len(t.rows)
        self._answer(ext, h, done)
        return ext

    def _run_prefill(self, pre: SeqTicket, n: int, done,
                     last: bool = True) -> List[SeqTicket]:
        """One chunk: the next ``n`` positions of ``pre``'s history;
        ``last``: the chunk ends it (``plan_step``'s ``prefill_last``)."""
        self._launching([pre], "prefill")
        with trace.device_span("seq.prefill_chunk", slot=pre.slot,
                               offset=pre.done, tokens=n, last=int(last)):
            with trace.device_span("seq.launch", program="prefill"):
                h, _ = self._programs.prefill(
                    pre.rows[pre.done:pre.done + n], pre.slot, pre.done,
                    last)
            with trace.device_span("seq.wait", program="prefill"):
                h.block_until_ready()
        self._count("prefill", {"prefill_attend_kernel_chunks": int(
            self._programs.attend_kernel)})
        pre.done += n
        if pre.remaining or self.gen:
            return []
        self._answer([pre], h, done)
        return [pre]

    def _launching(self, tickets: List[SeqTicket], queue: str) -> None:
        """A program that carries rows of ``tickets`` is about to be
        launched: those it is the first for have waited until now."""
        now, c = time.perf_counter_ns(), self.counters
        for t in tickets:
            if t.launched_ns is None:
                t.launched_ns = now
                c[f"{queue}_queue_ns"] += now - t.admitted_ns
                c[f"{queue}_tickets"] += 1

    # -- block diffusion ------------------------------------------------------
    def _block_forward(self, rows: list, done) -> List[SeqTicket]:
        import jax

        gen, B = self.gen, self.gen.block_len
        for t, kind, at, _ in rows:
            if kind != "known" and t.block is None:     # a new block: masks,
                t.block = np.full(B, gen.mask_row, np.int32)
                if at == t.known:       # behind the history's left-over items
                    t.block[:len(t.rows) - at] = t.rows[at:]
                t.denoised = 0
        rows = [(t, kind, t.rows[at:at + B] if kind == "known" else t.block,
                 at, n) for t, kind, at, n in rows]
        n_denoise = sum(1 for r in rows if r[1] == "denoise")
        self._launching([r[0] for r in rows], "extend")
        with trace.device_span("seq.block_step", rows=len(rows),
                               denoise_rows=n_denoise,
                               commit_rows=len(rows) - n_denoise):
            with trace.device_span("seq.launch", program="block"):
                launched, _ = self._programs.block(
                    [(ids, t.slot, at, kind == "denoise", n)
                     for t, kind, ids, at, n in rows])
            with trace.device_span("seq.wait", program="block"):
                decided = jax.device_get(launched)
        self._count("block", {
            "denoise_rows": n_denoise, "commit_rows": len(rows) - n_denoise,
            "block_kv_positions": sum(at + B for _, _, _, at, _ in rows)})
        finished = []
        with trace.device_span("seq.decide", rows=len(rows)):
            c = self.counters
            for b, (t, kind, _, at, _) in enumerate(rows):
                if kind == "known":
                    t.done = at + B
                    continue
                if kind == "denoise":
                    t.block = decided["ids"][b]
                    for i in np.flatnonzero(decided["picked"][b]):
                        t.found[at + int(i)] = (
                            int(t.block[i]), float(decided["score"][b, i]),
                            float(decided["confidence"][b, i]), t.forwards)
                        c["positions_unmasked"] += 1
                    t.denoised += 1
                else:
                    t.done, t.block = at + B, None
                t.forwards += 1
                if t.done == t.end:
                    self._finish_slate(t, done)
                    finished.append(t)
        return finished

    def _finish_slate(self, t: SeqTicket, done) -> None:
        inv, H = self._inverse, len(t.rows)
        items = [(inv[row], score, conf, step) for row, score, conf, step
                 in (t.found[p] for p in range(H, t.end))]
        t.result, t.tail = items[:t.generate], items[t.generate:]
        self.counters["slates_done"] += 1
        self.cache.release(t.slot, t.held())
        done(t)

    def _count(self, kind: str, did: Optional[Dict[str, int]] = None
               ) -> None:
        """A program of ``kind`` has run: what the host knows it ``did``
        joins ``counters``, and the device's totals as that program left
        them are kept, unfetched. Nothing here waits for the device but the
        drain, every ``StackPrograms.drain_every`` runs: the totals go into
        Python ints and start again from zero, exact over any run length."""
        with trace.device_span("seq.count", program=kind):
            programs, drained = self._programs, None
            self._undrained += 1
            if self._undrained >= programs.drain_every:
                drained = np.asarray(programs.take_totals()).tolist()
                self._undrained = 0
            with self._lock:
                for counter, n in (did or {}).items():
                    self.counters[counter] += n
                if drained is not None:
                    self._drained += drained
                    self.counters["count_fetches"] += 1
                self._live = programs.totals

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

    def answer(self, query: Dict[str, Any]) -> SeqTicket:
        """The query's ticket, driven through its steps to the end."""
        ticket = self.begin(query)
        if ticket is None:
            raise RuntimeError("every cache slot is busy")
        while ticket.result is None:
            self.step([ticket])
        return ticket

    def recommend(self, query: Dict[str, Any]) -> list:
        return self.answer(query).result


@dataclass
class SeqStackParams(Params):
    """Serve-time shapes of a stack model (``ops.sessionrec.ServeShape``);
    the stack itself (its ``StackSpec`` and weights) comes with the model."""

    n_slots: int = 32
    capacity: int = 8192
    chunk: int = 512
    extend_len: int = 8
    extend_batch: int = 8
    gen_batch: int = 8

    def shape(self) -> ServeShape:
        return ServeShape(**asdict(self))


class SeqStackAlgorithm(Algorithm):
    """Serves a :class:`SeqStackModel`: a stack whose mixers keep a per-
    session cache (latent attention, grouped-query attention, state-space
    mixers; the stack's ``StackSpec`` says which, block by block, and
    whether it generates). Training such a stack is not this system's yet
    (``ROADMAP.md`` §2): its model arrives through ``core.persistent_model``
    from whoever holds its weights."""

    stepwise = True
    #: an answer's entries: ``(item, score)``, and from a stack that
    #: generates ``(item, score, confidence, step)``
    ENTRY = ("item", "score", "confidence", "step")

    def __init__(self, params: SeqStackParams):
        super().__init__(params)

    def train(self, ctx: MeshContext, pd) -> SeqStackModel:
        raise NotImplementedError(
            "training a stepwise-served expert stack is not supported; "
            "deploy one through a PersistentModel")

    def warmup(self, model: SeqStackModel, ctx: MeshContext) -> None:
        """Compile the serve programs and every head batch, then run each
        once, so that no query compiles anything."""
        programs, sh = model.programs(), model.shape
        if model.gen is not None:
            import jax

            B = model.gen.block_len
            programs.prefill(np.zeros(B, np.int32), sh.n_slots, 0)
            jax.block_until_ready(programs.block(
                [(np.zeros(B, np.int32), sh.n_slots, 0, True, 1)]))
        else:
            if programs.cross_from is not None:    # the chunk that ends none
                programs.prefill(np.zeros(1, np.int32), sh.n_slots, 0, False)
            h, _ = programs.prefill(np.zeros(1, np.int32), sh.n_slots, 0)
            hs, _ = programs.extend([(np.zeros(1, np.int32), sh.n_slots, 1)])
            for h_last in (h, hs):
                model._index.search(h_last, 10)
        programs.take_totals()              # a warm-up run is not counted

    @classmethod
    def _prediction(cls, ticket: SeqTicket) -> Dict[str, Any]:
        out = {"itemScores": [dict(zip(cls.ENTRY, r)) for r in ticket.result]}
        if ticket.tail:
            out["blockTail"] = [dict(zip(cls.ENTRY, r)) for r in ticket.tail]
        return out

    def predict(self, model: SeqStackModel, query: Dict[str, Any]):
        return self._prediction(model.answer(query))

    # -- in steps (``core.Algorithm.stepwise``) --------------------------------
    def begin(self, model: SeqStackModel, query: Dict[str, Any]):
        return model.begin(query)

    def step(self, model: SeqStackModel, tickets, done) -> None:
        model.step(tickets, lambda t: done(t, self._prediction(t)))

    def cancel(self, model: SeqStackModel, ticket) -> None:
        model.cancel(ticket)
