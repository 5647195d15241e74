"""Streaming events→model: delta tailer + fold-in updates (ROADMAP C).

The batch pipeline retrains the world on every event, and most of a
retrain is reading, binning and shipping data that did not change, not
the ALS solve (before the chip, on ML-20M: ~80 s cold and ~35 s warm
against a ~1.5 s train; no cell measures it yet, PERF.md §7). This
module is the incremental path that makes
``pio_model_staleness_seconds`` small:

  tail     ``EventStore.find_columnar_since(cursor)`` (native
           sequence-offset columnar read, eventlog.cpp) returns exactly
           the rows appended since the last fold, dict-encoded, in
           arrival order — no 20M-row re-scan, no re-binning, no
           re-shipping of unchanged data.
  fold     ALS: per-touched-user/item fold-in solves against the fixed
           opposite factor (ops.als.fold_in_solve — the classic
           implicit/explicit ALS fold-in, one exact half-step per
           touched group, reusing the train's Gramian+CG machinery at
           delta scale). Two-tower: bounded online mini-batch steps on
           the delta buffer (ops.twotower.online_delta_step).
  publish  the updated rows post to live engine servers via the
           lightweight model-patch lane (``POST /model/patch``, applied
           between queries under the deployment lock) — the PR 8
           fleet's rolling ``GET /reload`` stays the fallback for full
           retrains — and each successful fold moves the
           ``pio_model_staleness_seconds`` horizon through the same
           perfacct ledger API ``Engine.train`` / ``run_train`` use, so
           the PR 7 gauge, timeline series and ``pio top`` show
           freshness dropping live.

Drive it with ``pio stream`` (one-shot ``--once`` or a daemon polling
every ``PIO_STREAM_INTERVAL_SEC``), or embed a :class:`StreamUpdater`.

Correctness stance (what fold-in is and is not):

  - a NEW user/item's fold-in factor is the exact conditional ALS
    optimum given the fixed opposite factors — the textbook fold-in;
  - an EXISTING group re-solves over its FULL history (fetched once
    per group through a targeted columnar scan, then kept in a bounded
    in-memory history cache that subsequent deltas extend), so the
    result matches a half-step of the full train, not a drifted
    approximation;
  - very large existing groups (a Zipf-popular item touched by one new
    rating) are SKIPPED beyond ``PIO_STREAM_MAX_GROUP`` rows — their
    factor moves negligibly per event and re-solving them would re-read
    the world; the count is exported so the operator can see it;
  - a rebased cursor (compaction renumbered records, or a crash
    truncated appends) means the delta cannot be trusted: the fold is
    skipped, the cursor resets to the tail, and the operator should run
    a full retrain (the rolling-reload lane).

Retrieval drift probe: every ``PIO_STREAM_RECALL_EVERY`` applied folds
the updater measures recall@k of the PATCHED retrieval index (the same
``upsert`` lane the serving patches ride) against brute force over the
current factor tables, exporting ``pio_stream_index_recall``; a value
below ``PIO_STREAM_RECALL_FLOOR`` logs and increments
``pio_stream_recall_breaches_total`` — index drift visible without any
reference model.

Model-quality drift probe (the fold-in quality gate ROADMAP item D
closes): at bind time the updater snapshots a SHADOW reference of each
fold-capable model — the last full-retrain COMPLETED instance, before
any fold touches it (obs/quality.ShadowRef) — and every
``PIO_QUALITY_EVERY`` folds scores the live patched model against it:
recall@k-vs-retrain on sampled users, rmse drift on a held-out slice,
factor-norm drift, exported as the ``pio_model_quality_*`` gauges with
the ``PIO_QUALITY_DRIFT_BAND`` band (obs/quality.py owns the math and
the ``GET /admin/quality`` surface). A breach AUTO-TRIGGERS the
existing rolling ``/reload`` lane (``--reload-url``, normally the
fleet router) exactly once per breach episode — the trigger latches
until a NEW trained instance binds, so a slow retrain cannot be
storm-reloaded — and the updater resyncs its own model to the bound
instance so serving and streamer agree again.

Config (env):
  PIO_STREAM_INTERVAL_SEC   daemon poll cadence (default 1.0)
  PIO_STREAM_MAX_GROUP      max history rows re-solved per group (8192)
  PIO_STREAM_HISTORY_CACHE  groups kept in the history cache (100000)
  PIO_STREAM_MAX_DELTA      max delta rows folded per cycle (200000)
  PIO_STREAM_TT_LR          two-tower online step size (0.05)
  PIO_STREAM_TT_STEPS       two-tower SGD steps per fold (4)
  PIO_STREAM_PATCH_TIMEOUT  per-target HTTP patch timeout sec (10)
  PIO_STREAM_RECALL_EVERY   applied folds between recall probes (20)
  PIO_STREAM_RECALL_FLOOR   breach threshold for the probe (0.95)
  PIO_STREAM_RECALL_SAMPLE  probe query sample size (16)
  PIO_STREAM_RECALL_K       probe k (10)
  PIO_QUALITY_EVERY         applied folds between shadow-drift probes
                            (20; band/sample/k: obs/quality.py env)
"""

from __future__ import annotations

import collections
import json
import logging
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.obs import dataobs, journal, metrics, perfacct, trace

log = logging.getLogger(__name__)

_FOLDS = metrics.counter(
    "pio_stream_folds_total",
    "Streaming fold cycles by outcome (ok / empty / rebased / "
    "patch_failed)",
    ("result",),
)
_FOLD_EVENTS = metrics.counter(
    "pio_stream_fold_events_total",
    "Delta events folded into the live model without a full retrain",
)
_FOLD_SECONDS = metrics.gauge(
    "pio_stream_fold_seconds",
    "Wall seconds of the last fold cycle (delta read + solves + patch)",
)
_PATCH_FAILURES = metrics.counter(
    "pio_stream_patch_failures_total",
    "Model-patch deliveries that failed (per target per cycle)",
)
_GROUPS_SKIPPED = metrics.counter(
    "pio_stream_groups_skipped_total",
    "Touched groups not re-solved, by reason (oversize = history "
    "beyond PIO_STREAM_MAX_GROUP; truncated = user history capped to "
    "the newest rows)",
    ("reason",),
)
_INDEX_RECALL = metrics.gauge(
    "pio_stream_index_recall",
    "Last measured recall@k of the patched retrieval index vs brute "
    "force over the current factors (worst across fold-capable "
    "algorithms)",
)
_RECALL_BREACHES = metrics.counter(
    "pio_stream_recall_breaches_total",
    "Recall probes that landed below PIO_STREAM_RECALL_FLOOR",
)


class StreamUnsupported(RuntimeError):
    """The deployed engine or storage backend cannot stream: no
    sequence-offset delta reads, or no fold-capable algorithm."""


def _max_group() -> int:
    return metrics.env_int("PIO_STREAM_MAX_GROUP", 8192)


def _history_cache_cap() -> int:
    return metrics.env_int("PIO_STREAM_HISTORY_CACHE", 100_000)


def _buy_code(cols, ds) -> int:
    """Dict-code of the buy event in this columnar block (-1: absent)."""
    return (cols.names.index(ds.buy_event)
            if ds.buy_event in cols.names else -1)


def _decode_value(cols, k: int, buy_code: int, buy_rating: float) -> float:
    """One event's rating value: buy events carry the configured
    implicit rating; a NaN rating property decodes to 0.0 (the same
    rules RecoDataSource applies on the batch read path). Shared by the
    delta tail and the targeted history scans so the two lanes can
    never disagree about the same event."""
    if int(cols.name_codes[k]) == buy_code:
        return buy_rating
    v = float(cols.values[k])
    if v != v:
        return 0.0
    return v


class _HistoryCache:
    """Bounded per-group rating history: ``("u"|"i", id) -> (ids,
    values)`` parallel lists. Filled once per group by a targeted
    columnar scan; later deltas EXTEND cached entries (the fetch at
    fill time already includes the delta that triggered it, so the two
    paths never double-count)."""

    def __init__(self, cap: int):
        self._cap = cap
        self._d: "collections.OrderedDict[Tuple[str, str], Tuple[List[str], List[float]]]" = (
            collections.OrderedDict())

    def get(self, key):
        got = self._d.get(key)
        if got is not None:
            self._d.move_to_end(key)
        return got

    def put(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self._cap:
            self._d.popitem(last=False)

    def __contains__(self, key) -> bool:
        return key in self._d

    def __len__(self) -> int:
        return len(self._d)


class ALSFoldIn:
    """Per-touched-group ALS fold-in against the fixed opposite factor.

    Owns the updater's LOCAL authoritative model copy (an
    :class:`~predictionio_tpu.models.als.ALSModel`); each ``fold``
    solves users → items → users (the final user pass sees freshly
    solved new-item factors) and applies the rows in place, returning
    the patch block for the serving side.
    """

    def __init__(self, index: int, params, model, events, app_id: int,
                 channel_id: Optional[int], ds_params):
        from predictionio_tpu.ops.als import ALSConfig

        self.index = index
        self.model = model
        self._events = events
        self._app_id = app_id
        self._channel_id = channel_id
        self._ds = ds_params
        self._hist = _HistoryCache(_history_cache_cap())
        solver = getattr(params, "solver", "cg")
        self.cfg = ALSConfig(
            rank=int(params.rank),
            reg=float(params.lambda_),
            implicit=bool(getattr(params, "implicit_prefs", False)),
            alpha=float(getattr(params, "alpha", 1.0)),
            solver=solver if solver in ("cg", "direct") else "cg",
            cg_iters=int(getattr(params, "cg_iters", 6)),
        )

    # -- history -------------------------------------------------------------
    def _fetch_history(self, side: str, gid: str) -> Tuple[List[str], List[float]]:
        """One targeted columnar scan for a group's complete rating
        history (includes any rows already appended this cycle)."""
        ds = self._ds
        filters: Dict[str, Any] = {
            "entity_type": ds.entity_type,
            "event_names": [ds.rate_event, ds.buy_event],
            "target_entity_type": ds.target_entity_type,
        }
        if side == "u":
            filters["entity_id"] = gid
        else:
            filters["target_entity_id"] = gid
        cols = self._events.find_columnar(
            self._app_id, self._channel_id,
            value_property=ds.value_property, time_ordered=False, **filters)
        ids: List[str] = []
        vals: List[float] = []
        buy_code = _buy_code(cols, ds)
        for k in range(len(cols)):
            tc = int(cols.target_codes[k])
            if tc < 0:
                continue
            other = (cols.target_vocab[tc] if side == "u"
                     else cols.entity_vocab[int(cols.entity_codes[k])])
            ids.append(other)
            vals.append(_decode_value(cols, k, buy_code,
                                      float(ds.buy_rating)))
        return ids, vals

    def invalidate_history(self) -> None:
        """Drop every cached group history. Required whenever delta rows
        were DROPPED without folding (a truncated backlog, or a fold
        that failed mid-way): cached entries extended past that gap
        would quietly re-solve groups against incomplete histories —
        the next touch re-fetches the full history from the log."""
        self._hist = _HistoryCache(_history_cache_cap())

    def _group_rows(self, side: str, gid: str,
                    delta: List[Tuple[str, float]],
                    known_new: bool = False) -> Tuple[List[str], List[float]]:
        """The group's full history AFTER this delta (cache-extend or
        one targeted fetch — the fetch already includes the delta rows,
        which were appended to the log before the tailer read them).
        Called at most once per (side, gid) per fold (the caller builds
        its row sets up front), so cached lists are extended exactly
        once per delta.

        ``known_new`` (group absent from the model vocab): the delta IS
        the history — no targeted scan. Any pre-cursor events such a
        group might have sit in the blind window between the trained
        instance's read horizon and the stream bind, which the cursor
        contract already assigns to a full retrain; scanning the whole
        log per new user would put an O(log) read on the per-event hot
        path for nothing the contract credits."""
        key = (side, gid)
        cached = self._hist.get(key)
        if cached is not None:
            ids, vals = cached
            for other, v in delta:
                ids.append(other)
                vals.append(v)
            return ids, vals
        if known_new:
            ids = [other for other, _ in delta]
            vals = [v for _, v in delta]
        else:
            ids, vals = self._fetch_history(side, gid)
        self._hist.put(key, (ids, vals))
        return ids, vals

    # -- the fold ------------------------------------------------------------
    def fold(self, users: List[str], items: List[str],
             ratings: np.ndarray) -> Optional[dict]:
        from predictionio_tpu.ops.als import fold_in_solve

        if not users:
            return None
        model = self.model
        cap = _max_group()
        delta_by_user: Dict[str, List[Tuple[str, float]]] = {}
        delta_by_item: Dict[str, List[Tuple[str, float]]] = {}
        for u, i, r in zip(users, items, ratings):
            delta_by_user.setdefault(u, []).append((i, float(r)))
            delta_by_item.setdefault(i, []).append((u, float(r)))

        # vocab extension FIRST: every touched new id gets a zero row so
        # index maps are stable for all three solve passes below (the
        # zero factors are transient — the patch publishes only after
        # the passes complete)
        new_users = [u for u in delta_by_user if u not in model.user_ids]
        new_items = [i for i in delta_by_item if i not in model.item_ids]
        rank = self.cfg.rank
        if new_users or new_items:
            zero = np.zeros(rank, np.float32)
            model.upsert_rows(
                user_rows=[(u, zero) for u in new_users],
                item_rows=[(i, zero) for i in new_items])
        new_user_set = set(new_users)
        new_item_set = set(new_items)

        # materialize each touched group's post-delta history EXACTLY
        # once per fold (the user side solves twice below — re-reading
        # the cache-extending _group_rows there would double-append)
        hist_u = {gid: self._group_rows("u", gid, delta,
                                        known_new=gid in new_user_set)
                  for gid, delta in delta_by_user.items()}
        hist_i = {gid: self._group_rows("i", gid, delta,
                                        known_new=gid in new_item_set)
                  for gid, delta in delta_by_item.items()}

        def solve_side(side: str, hist: Dict[str, Tuple[List[str], List[float]]],
                       new_set: set) -> List[Tuple[str, np.ndarray]]:
            if side == "u":
                group_map, other_map = model.user_ids, model.item_ids
                group_factors, Y = model.user_factors, model.item_factors
            else:
                group_map, other_map = model.item_ids, model.user_ids
                group_factors, Y = model.item_factors, model.user_factors
            gids: List[str] = []
            rows: List[Tuple[np.ndarray, np.ndarray]] = []
            x0: List[np.ndarray] = []
            for gid, (ids, vals) in hist.items():
                if len(ids) > cap:
                    if gid not in new_set and side == "i":
                        # a popular item's factor moves negligibly per
                        # event; re-solving it re-reads the world
                        _GROUPS_SKIPPED.labels("oversize").inc()
                        continue
                    _GROUPS_SKIPPED.labels("truncated").inc()
                    ids, vals = ids[-cap:], vals[-cap:]
                # rows whose opposite id the model has never seen (and
                # this delta does not introduce) carry zero factors —
                # dropping them changes the Gramian by nothing
                pairs = [(other_map.get(o), v) for o, v in zip(ids, vals)]
                kept = [(c, v) for c, v in pairs if c is not None]
                if not kept:
                    continue
                gids.append(gid)
                rows.append((
                    np.fromiter((c for c, _ in kept), np.int32,
                                count=len(kept)),
                    np.fromiter((v for _, v in kept), np.float32,
                                count=len(kept)),
                ))
                x0.append(group_factors[group_map[gid]])
            if not gids:
                return []
            solved = fold_in_solve(Y, rows, self.cfg,
                                   x0=np.stack(x0) if x0 else None)
            return [(gid, solved[k]) for k, gid in enumerate(gids)]

        # users → items → users: the final user pass sees the freshly
        # solved item factors (a new user who only rated new items would
        # otherwise keep a zero factor)
        user_rows = solve_side("u", hist_u, new_user_set)
        if user_rows:
            model.upsert_rows(user_rows=user_rows)
        item_rows = solve_side("i", hist_i, new_item_set)
        if item_rows:
            model.upsert_rows(item_rows=item_rows)
            user_rows = solve_side("u", hist_u, new_user_set)
            if user_rows:
                model.upsert_rows(user_rows=user_rows)
        if not user_rows and not item_rows:
            return None
        return {
            "index": self.index,
            "userRows": [[gid, vec.tolist()] for gid, vec in user_rows],
            "itemRows": [[gid, vec.tolist()] for gid, vec in item_rows],
        }


class TwoTowerOnline:
    """Bounded online mini-batch steps on the delta buffer — the
    two-tower lane (ops.twotower.online_delta_step). Updates only the
    touched serving-embedding rows; delta quality gates are a ROADMAP
    item C follow-up."""

    def __init__(self, index: int, params, model, ds_params):
        self.index = index
        self.model = model
        self._params = params
        self._ds = ds_params
        self._rng = np.random.default_rng(
            int(getattr(params, "seed", 11)) + 0x5EED)

    def fold(self, users: List[str], items: List[str],
             ratings: np.ndarray) -> Optional[dict]:
        from predictionio_tpu.ops.twotower import online_delta_step

        p = self._params
        min_rating = float(getattr(p, "min_rating", 0.0))
        keep = [(u, i, r) for u, i, r in zip(users, items, ratings)
                if r >= min_rating]
        if not keep:
            return None
        model = self.model
        rank = model.user_factors.shape[1]

        def fresh_row() -> np.ndarray:
            v = self._rng.normal(size=rank).astype(np.float32)
            return v / max(float(np.linalg.norm(v)), 1e-8)

        new_u = {u for u, _, _ in keep if u not in model.user_ids}
        new_i = {i for _, i, _ in keep if i not in model.item_ids}
        if new_u or new_i:
            model.upsert_rows(
                user_rows=[(u, fresh_row()) for u in sorted(new_u)],
                item_rows=[(i, fresh_row()) for i in sorted(new_i)])
        u_rows = np.fromiter((model.user_ids[u] for u, _, _ in keep),
                             np.int32, count=len(keep))
        i_rows = np.fromiter((model.item_ids[i] for _, i, _ in keep),
                             np.int32, count=len(keep))
        weight = None
        if getattr(p, "weight_by_rating", False):
            weight = np.fromiter((r for _, _, r in keep), np.float32,
                                 count=len(keep))
        uu, new_uvecs, ii, new_ivecs, _losses = online_delta_step(
            model.user_factors, model.item_factors, u_rows, i_rows,
            weight=weight,
            lr=metrics.env_float("PIO_STREAM_TT_LR", 0.05),
            steps=metrics.env_int("PIO_STREAM_TT_STEPS", 4),
            temp=float(getattr(p, "temperature", 0.07)),
        )
        inv_u = model.user_ids.inverse()
        inv_i = model.item_ids.inverse()
        user_rows = [(inv_u[int(r)], new_uvecs[k]) for k, r in enumerate(uu)]
        item_rows = [(inv_i[int(r)], new_ivecs[k]) for k, r in enumerate(ii)]
        model.upsert_rows(user_rows=user_rows, item_rows=item_rows)
        return {
            "index": self.index,
            "userRows": [[gid, vec.tolist()] for gid, vec in user_rows],
            "itemRows": [[gid, vec.tolist()] for gid, vec in item_rows],
        }


class _DSView:
    """The datasource facts the tailer needs, lifted off the deployed
    engine's datasource params (RecoDataSourceParams shape: the
    rate/buy interaction schema every factor template shares)."""

    def __init__(self, params):
        self.app_name = getattr(params, "app_name", None)
        if not self.app_name:
            raise StreamUnsupported(
                "deployed datasource has no app_name — streaming needs "
                "an event-store-backed datasource")
        self.channel_name = getattr(params, "channel_name", None)
        self.rate_event = getattr(params, "rate_event", "rate")
        self.buy_event = getattr(params, "buy_event", "buy")
        self.buy_rating = float(getattr(params, "buy_rating", 4.0))
        self.entity_type = "user"
        self.target_entity_type = "item"
        self.value_property = "rating"


class StreamUpdater:
    """The streaming events→model loop: tail the log since the cursor,
    fold the delta into the local model, publish patches, move the
    freshness horizon.

    ``patch_servers`` are in-process
    :class:`~predictionio_tpu.serving.engine_server.EngineServer`
    objects (tests / single-process deployments);
    ``patch_urls`` are remote engine-server base URLs (``pio stream
    --url``). With neither, the local model copy is still folded and
    the horizon still moves — the embedding caller owns serving.
    """

    def __init__(
        self,
        engine,
        engine_id: str,
        engine_version: str = "0",
        engine_variant: str = "default",
        storage: Optional[Storage] = None,
        ctx=None,
        instance=None,
        patch_urls: Sequence[str] = (),
        patch_servers: Sequence[Any] = (),
        reload_urls: Sequence[str] = (),
        reload_trigger: Optional[Any] = None,
    ):
        from predictionio_tpu.models.als import ALSAlgorithm, ALSModel
        from predictionio_tpu.models.twotower import TwoTowerAlgorithm
        from predictionio_tpu.parallel.mesh import MeshContext
        from predictionio_tpu.workflow.deploy import prepare_deploy

        self.storage = storage or get_storage()
        self._ctx = ctx or MeshContext()
        self.engine = engine
        self.engine_id = engine_id
        self.engine_version = engine_version
        self.engine_variant = engine_variant
        self.patch_urls = [u.rstrip("/") for u in patch_urls]
        self.patch_servers = list(patch_servers)
        #: where a drift-band breach fires the rolling reload: a
        #: callable (tests, embedders) or server/router base URLs whose
        #: GET /reload lane rolls serving back onto the last full
        #: retrain (bearer-authed when PIO_ADMIN_TOKEN is set)
        self.reload_urls = [u.rstrip("/") for u in reload_urls]
        self.reload_trigger = reload_trigger
        self._als_cls = ALSAlgorithm
        self._tt_cls = TwoTowerAlgorithm
        self._als_model_cls = ALSModel

        if instance is None:
            instance = self.storage.engine_instances().get_latest_completed(
                engine_id, engine_version, engine_variant)
            if instance is None:
                raise StreamUnsupported(
                    f"no COMPLETED instance for engine {engine_id} — "
                    "train once before streaming")
        self._bind_instance(instance, prepare_deploy)

    # -- binding to a trained instance --------------------------------------
    def _bind_instance(self, instance, prepare_deploy=None) -> None:
        from predictionio_tpu.data.store import resolve_app

        if prepare_deploy is None:
            from predictionio_tpu.workflow.deploy import prepare_deploy
        old_folders = getattr(self, "_folders", None)
        deployment = prepare_deploy(self.engine, instance, self._ctx,
                                    self.storage)
        prev_instance_id = getattr(self, "instance_id", None)
        self.instance_id = instance.id
        self._ds = _DSView(deployment.engine_params.data_source_params[1])
        app_id, channel_id = resolve_app(
            self._ds.app_name, self._ds.channel_name, self.storage)
        self._app_id, self._channel_id = app_id, channel_id
        self._events = self.storage.events()
        if not hasattr(self._events, "find_columnar_since"):
            raise StreamUnsupported(
                f"event store {type(self._events).__name__} has no "
                "sequence-offset delta reads (find_columnar_since) — "
                "streaming needs the eventlog backend")
        self._folders: List[Any] = []
        for idx, (algo, model) in enumerate(
                zip(deployment.algorithms, deployment.models)):
            if isinstance(algo, self._tt_cls):
                self._folders.append(
                    TwoTowerOnline(idx, algo.params, model, self._ds))
            elif isinstance(algo, self._als_cls):
                self._folders.append(ALSFoldIn(
                    idx, algo.params, model, self._events, app_id,
                    channel_id, self._ds))
        if not self._folders:
            raise StreamUnsupported(
                "no fold-capable algorithm in the deployed engine "
                "(ALS fold-in / two-tower online steps)")
        # the tail from HERE: the loaded instance covers everything up
        # to its train read; rows between that horizon and this call are
        # already-ingested work a full retrain owns (the cursor cannot
        # be rewound to an instant the log does not index by time)
        self.cursor = self._events.delta_cursor(app_id, channel_id)
        # staleness debt (a truncated or rebased delta left unreflected
        # work no fold may credit) clears only when a NEW trained
        # instance binds — its own run_train publish reconciled the log
        if prev_instance_id is None or instance.id != prev_instance_id:
            self._staleness_debt = False
            # the drift→reload trigger re-arms ONLY here: one reload
            # per breach episode, no storm while the retrain that will
            # actually fix the drift is still in flight
            self._quality_reload_fired = False
        self._folds_since_probe = 0
        self._folds_since_quality = 0
        # shadow reference: the freshly loaded COMPLETED instance,
        # snapshotted BEFORE any fold touches it — "drift" is always
        # distance from the last full retrain (obs/quality.py)
        from predictionio_tpu.obs import quality

        self._shadows: Dict[int, quality.ShadowRef] = {}
        for folder in self._folders:
            model = getattr(folder, "model", None)
            if model is not None and quality.ShadowRef.supports(model):
                self._shadows[folder.index] = quality.ShadowRef(
                    model, instance.id)
        # LAST: retire the PREVIOUS bind's fold-lane models from the
        # device-memory ledger (obs/memacct.py) — only once the rebind
        # fully succeeded. resync is advisory (callers catch failures
        # anywhere above — resolve_app, the delta-capability check,
        # delta_cursor — and keep folding on the OLD models), and
        # releasing still-active models would under-report residency,
        # over-report headroom, and let the preflight approve deploys
        # that cannot fit. A failure AFTER _folders was reassigned errs
        # the safe way: the old models stay ledgered until GC sweeps
        # their weakrefs.
        if old_folders:
            from predictionio_tpu.obs import memacct

            for folder in old_folders:
                old_model = getattr(folder, "model", None)
                if old_model is not None:
                    memacct.release_model(old_model)

    def resync(self) -> None:
        """Rebind to the newest COMPLETED instance (after a retrain or
        a 409 from a patched server) and reset the cursor to the tail."""
        instance = self.storage.engine_instances().get_latest_completed(
            self.engine_id, self.engine_version, self.engine_variant)
        if instance is None:
            raise StreamUnsupported(
                f"no COMPLETED instance for engine {self.engine_id}")
        self._bind_instance(instance)
        journal.emit("resync", instance=self.instance_id)

    # -- one cycle -----------------------------------------------------------
    def poll_once(self) -> Dict[str, Any]:
        """One tail→fold→publish cycle; returns its stats dict.

        Each cycle runs under its OWN trace: the fold's spans and the
        patch/reload/drift fan-out to the fleet (traced_headers on
        every lane) correlate under one id, so ``pio trace`` can follow
        an append from the daemon into every replica it patched."""
        with trace.new_trace():
            return self._poll_once_traced()

    def _poll_once_traced(self) -> Dict[str, Any]:
        t0 = time.perf_counter()
        # freshness horizon at read START, exactly like Engine.train: a
        # publish then credits only what this delta read could have seen
        perfacct.LEDGER.note_train_read()
        cols, new_cursor, rebased = self._events.find_columnar_since(
            self._app_id, self._channel_id,
            cursor=self.cursor,
            value_property=self._ds.value_property,
            entity_type=self._ds.entity_type,
            event_names=[self._ds.rate_event, self._ds.buy_event],
            target_entity_type=self._ds.target_entity_type,
        )
        if rebased:
            # the returned rows are a RESYNC of the whole live set, not
            # a delta — folding them would re-solve the world off-cursor.
            # Reset to the tail; a full retrain (rolling /reload) owns
            # reconciling what happened before it — until then no fold
            # may credit the freshness horizon (the skipped backlog is
            # unreflected work a publish would silently mark done).
            self.cursor = new_cursor
            self._staleness_debt = True
            _FOLDS.labels("rebased").inc()
            journal.emit("fold", outcome="rebased")
            log.warning(
                "delta cursor rebased (compaction or truncated appends): "
                "skipping fold; run a full retrain to reconcile")
            return {"events": 0, "rebased": True,
                    "seconds": time.perf_counter() - t0}
        prev_cursor = self.cursor
        self.cursor = new_cursor
        if len(cols):
            # data plane: the tail refreshes entity/name sketches in
            # THIS process (skew, cardinality) — never the ingest
            # counters, which the insert lane already moved
            dataobs.DATAOBS.observe_tail(self._app_id, cols)
        max_delta = metrics.env_int("PIO_STREAM_MAX_DELTA", 200_000)
        n = len(cols)
        truncated = n > max_delta
        if truncated:
            # fold only the newest rows (recent activity stays fresh)
            # but DON'T move the freshness horizon — this cycle or any
            # later one: the dropped backlog is unreflected work only a
            # full retrain reconciles, and a later fold's publish would
            # otherwise silently credit it (the debt flag holds until a
            # new COMPLETED instance binds). Cached histories are also
            # dropped: the dropped rows never extended them, so every
            # entry past this gap would re-solve against missing data.
            self._staleness_debt = True
            for folder in self._folders:
                if hasattr(folder, "invalidate_history"):
                    folder.invalidate_history()
            log.warning("delta of %d rows exceeds PIO_STREAM_MAX_DELTA=%d; "
                        "folding the newest %d — staleness is NOT "
                        "credited until a full retrain reconciles",
                        n, max_delta, max_delta)
        users: List[str] = []
        items: List[str] = []
        vals: List[float] = []
        buy_code = _buy_code(cols, self._ds)
        start = max(0, n - max_delta)
        for k in range(start, n):
            tc = int(cols.target_codes[k])
            if tc < 0:
                continue
            users.append(cols.entity_vocab[int(cols.entity_codes[k])])
            items.append(cols.target_vocab[tc])
            vals.append(_decode_value(cols, k, buy_code,
                                      self._ds.buy_rating))
        if not users:
            _FOLDS.labels("empty").inc()
            return {"events": 0, "rebased": False,
                    "seconds": time.perf_counter() - t0}

        ratings = np.asarray(vals, np.float32)
        try:
            blocks = []
            for folder in self._folders:
                block = folder.fold(users, items, ratings)
                if block is not None:
                    blocks.append(block)
            published = self._publish(blocks)
        except Exception:
            # the delta was NOT folded: rewind so the next tick retries
            # it (run_forever's contract), and drop cached histories — a
            # folder that died mid-fold may have extended them already,
            # so the retry's cache-extend would double-count the delta
            self.cursor = prev_cursor
            for folder in self._folders:
                if hasattr(folder, "invalidate_history"):
                    folder.invalidate_history()
            raise
        seconds = time.perf_counter() - t0
        _FOLD_SECONDS.set(seconds)
        if published and not self._staleness_debt:
            # the fold is servable and covers the whole delta: move the
            # freshness horizon the same way run_train's COMPLETED
            # publish does
            perfacct.LEDGER.note_publish()
        if published:
            _FOLDS.labels("ok").inc()
            _FOLD_EVENTS.inc(len(users))
            journal.emit("fold", outcome="ok", events=len(users),
                         seconds=round(seconds, 3),
                         truncated=truncated or None)
        else:
            _FOLDS.labels("patch_failed").inc()
            journal.emit("fold", outcome="patch_failed",
                         events=len(users))
        out = {
            "events": len(users),
            "rebased": False,
            "truncated": truncated,
            "touched_users": len(set(users)),
            "touched_items": len(set(items)),
            "published": published,
            "seconds": seconds,
        }
        self._folds_since_probe += 1
        if (self._folds_since_probe
                >= metrics.env_int("PIO_STREAM_RECALL_EVERY", 20)):
            self._folds_since_probe = 0
            recall = self.probe_recall()
            if recall is not None:
                out["index_recall"] = recall
        self._folds_since_quality += 1
        if (self._folds_since_quality
                >= metrics.env_int("PIO_QUALITY_EVERY", 20)):
            self._folds_since_quality = 0
            report = self.probe_quality()
            if report is not None:
                out["quality"] = {
                    k: report.get(k)
                    for k in ("recall_vs_retrain", "rmse_drift",
                              "factor_drift", "breached")}
        return out

    # -- retrieval drift probe -----------------------------------------------
    def probe_recall(self) -> Optional[float]:
        """Recall@k of the PATCHED retrieval index against brute force
        over the current factor tables — the minimal fold-in quality
        gate (the carried-over ROADMAP item; item D's shadow retrain is
        the full version). The local models' indexes ride the SAME
        ``upsert_rows`` lane the serving patches do, so a fold that
        corrupts index freshness shows here before users see it.
        Returns the worst recall across fold-capable algorithms, or
        None when nothing is probeable."""
        from predictionio_tpu.index.recall import recall_at_k

        sample_n = metrics.env_int("PIO_STREAM_RECALL_SAMPLE", 16)
        k_cfg = metrics.env_int("PIO_STREAM_RECALL_K", 10)
        rng = np.random.default_rng(0x5CA1E)
        worst: Optional[float] = None
        for folder in self._folders:
            model = getattr(folder, "model", None)
            if model is None or not hasattr(model, "retrieval_index"):
                continue
            n_users = len(model.user_ids)
            n_items = len(model.item_ids)
            if n_users == 0 or n_items == 0:
                continue
            rows = rng.choice(n_users, min(sample_n, n_users),
                              replace=False)
            recall = recall_at_k(
                model.retrieval_index(), model.user_factors[rows],
                min(k_cfg, n_items), vectors=model.item_factors)
            worst = recall if worst is None else min(worst, recall)
        if worst is None:
            return None
        _INDEX_RECALL.set(worst)
        floor = metrics.env_float("PIO_STREAM_RECALL_FLOOR", 0.95)
        if worst < floor:
            _RECALL_BREACHES.inc()
            log.warning(
                "patched retrieval index recall@k %.3f fell below the "
                "floor %.2f — the fold-in lane is drifting from the "
                "factor tables; run a full retrain (rolling /reload)",
                worst, floor)
        return worst

    # -- shadow-retrain drift probe (the fold-in quality gate) ---------------
    def probe_quality(self) -> Optional[Dict[str, Any]]:
        """Score every fold-capable live model against its shadow
        reference (the last full-retrain COMPLETED instance) and
        publish the worst case to the ``pio_model_quality_*`` gauges +
        ``GET /admin/quality`` (obs/quality.py owns the math). A
        drift-band breach fires the rolling ``/reload`` lane exactly
        once per breach episode and resyncs the updater itself — see
        the module docstring. Returns the published report, or None
        when nothing was probeable."""
        from predictionio_tpu.obs import quality

        reports = []
        for folder in self._folders:
            shadow = self._shadows.get(folder.index)
            if shadow is None:
                continue
            report = quality.drift_report(folder.model, shadow)
            if report.get("recall_vs_retrain") is not None:
                reports.append(report)
        if not reports:
            return None
        # worst-case merge across algorithms: one gauge set, the most
        # pessimistic verdict (a healthy ALS must not mask a drifted
        # two-tower)
        merged = dict(min(reports, key=lambda r: r["recall_vs_retrain"]))
        merged["recall_vs_retrain"] = min(r["recall_vs_retrain"]
                                          for r in reports)
        for name, pick in (("rmse_drift", max), ("factor_drift", max)):
            values = [r[name] for r in reports if r.get(name) is not None]
            if values:
                merged[name] = pick(values)
        merged["algorithms_probed"] = len(reports)
        merged = quality.publish_drift(merged)
        # split deployments: this daemon's in-memory STATE is not the
        # fleet's — push the report onto every patch target's quality
        # surface so THEIR /admin/quality, dashboard panel and `pio
        # canary` carry the drift the stream measured (best-effort,
        # same stance as patch delivery; in-process patch_servers share
        # this process's STATE already)
        if self.patch_urls:
            self._push_drift(merged)
        if merged["breached"] and not self._quality_reload_fired:
            self._quality_reload_fired = True
            quality.note_auto_reload()
            journal.emit("drift_breach", band=merged["band"],
                         breached=merged["breached"],
                         recall=merged.get("recall_vs_retrain"),
                         rmse_drift=merged.get("rmse_drift"),
                         factor_drift=merged.get("factor_drift"))
            journal.emit("auto_reload", reason="drift_breach")
            log.warning(
                "model-quality drift breached the band %.2f (%s: "
                "recall_vs_retrain=%s rmse_drift=%s factor_drift=%s) — "
                "triggering the rolling /reload lane and resyncing; a "
                "full retrain owns closing the episode",
                merged["band"], ",".join(merged["breached"]),
                merged.get("recall_vs_retrain"), merged.get("rmse_drift"),
                merged.get("factor_drift"))
            self._trigger_reload()
            try:
                # the updater's OWN model is the drifted one: rebind to
                # the instance serving just rolled back onto, so the
                # next folds extend the reference, not the drift
                self.resync()
            except Exception:  # noqa: BLE001 — resync is advisory
                log.exception("post-breach stream resync failed")
        return merged

    def _push_drift(self, report: Dict[str, Any]) -> None:
        """POST the drift report to each patch target's
        ``/admin/quality`` (bearer-authed like the patch lane; failures
        are logged, never raised — drift delivery is telemetry)."""
        import os as _os

        body = json.dumps({"drift": report}).encode()
        headers = trace.traced_headers({"Content-Type": "application/json"})
        token = _os.environ.get("PIO_ADMIN_TOKEN")
        if token:
            headers["Authorization"] = f"Bearer {token}"
        timeout = metrics.env_float("PIO_STREAM_PATCH_TIMEOUT", 10.0)
        for url in self.patch_urls:
            try:
                req = urllib.request.Request(
                    url + "/admin/quality", data=body, headers=headers,
                    method="POST")
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    resp.read()
            except Exception as e:  # noqa: BLE001 — telemetry delivery
                # must not break the fold loop
                log.warning("drift report push to %s failed: %s", url, e)

    def _trigger_reload(self) -> None:
        """Fire the rolling-reload lane: the injected callable when one
        was given (tests, in-process fleets), else ``GET /reload`` on
        every configured reload URL (a router's route answers 202 and
        rolls the fleet; a single engine server reloads in place)."""
        if self.reload_trigger is not None:
            try:
                self.reload_trigger()
            except Exception:  # noqa: BLE001 — the trigger is operator
                # plumbing; its failure must not kill the fold loop
                log.exception("drift reload trigger failed")
            return
        if not self.reload_urls:
            log.warning("drift band breached but no reload lane is "
                        "configured (pio stream --reload-url) — run a "
                        "full retrain + rolling /reload manually")
            return
        import os as _os

        headers = trace.traced_headers()
        token = _os.environ.get("PIO_ADMIN_TOKEN")
        if token:
            headers["Authorization"] = f"Bearer {token}"
        timeout = metrics.env_float("PIO_STREAM_PATCH_TIMEOUT", 10.0)
        for url in self.reload_urls:
            try:
                req = urllib.request.Request(url + "/reload",
                                             headers=headers)
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    resp.read()
                log.warning("drift breach: rolling reload triggered at "
                            "%s", url)
            except Exception as e:  # noqa: BLE001 — counted+logged, the
                # daemon keeps folding either way
                log.warning("drift-breach reload trigger to %s failed: "
                            "%s", url, e)

    # -- patch delivery ------------------------------------------------------
    def _publish(self, blocks: List[dict]) -> bool:
        if not blocks:
            return True
        from predictionio_tpu.serving.engine_server import EngineServer

        payload = {"instanceId": self.instance_id, "algorithms": blocks}
        ok = True
        resync_needed = False
        for server in self.patch_servers:
            try:
                server.apply_patch(payload)
            except EngineServer.StalePatch:
                # the server rolled to a newer instance — same contract
                # as the HTTP lane's 409: rebind and tail from there
                log.warning("in-process model patch rejected (stale "
                            "instance); resyncing to the latest "
                            "COMPLETED instance")
                _PATCH_FAILURES.inc()
                ok = False
                resync_needed = True
            except Exception:  # noqa: BLE001 — one dead target must not
                # stop the others; the failure is counted and logged
                log.exception("in-process model patch failed")
                _PATCH_FAILURES.inc()
                ok = False
        if resync_needed:
            try:
                self.resync()
            except Exception:  # noqa: BLE001 — resync is advisory
                log.exception("stream resync failed")
        if not self.patch_urls:
            return ok
        import os as _os

        body = json.dumps(payload).encode()
        headers = trace.traced_headers({"Content-Type": "application/json"})
        token = _os.environ.get("PIO_ADMIN_TOKEN")
        if token:
            headers["Authorization"] = f"Bearer {token}"
        timeout = metrics.env_float("PIO_STREAM_PATCH_TIMEOUT", 10.0)
        for url in self.patch_urls:
            try:
                req = urllib.request.Request(
                    url + "/model/patch", data=body, headers=headers,
                    method="POST")
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    resp.read()
            except urllib.error.HTTPError as e:
                e.read()
                _PATCH_FAILURES.inc()
                ok = False
                if e.code == 409:
                    # the server moved to a newer instance (a retrain
                    # published + rolled): rebind and tail from there
                    log.warning("model patch rejected (409: stale "
                                "instance) by %s; resyncing to the "
                                "latest COMPLETED instance", url)
                    try:
                        self.resync()
                    except Exception:  # noqa: BLE001 — resync is advisory
                        log.exception("stream resync failed")
                else:
                    log.warning("model patch to %s failed: HTTP %s",
                                url, e.code)
            except Exception as e:  # noqa: BLE001 — network failure is a
                # counted outcome, not a crash of the fold loop
                log.warning("model patch to %s failed: %s", url, e)
                _PATCH_FAILURES.inc()
                ok = False
        return ok

    # -- daemon --------------------------------------------------------------
    def run_forever(self, interval: Optional[float] = None,
                    stop: Optional[threading.Event] = None) -> None:
        """Poll until ``stop`` is set (the ``pio stream`` daemon)."""
        interval = (interval if interval is not None
                    else metrics.env_float("PIO_STREAM_INTERVAL_SEC", 1.0))
        stop = stop or threading.Event()
        # the stream daemon is a PIO process like any server: it holds
        # the continuous profiler for its lifetime (refcounted — a
        # daemon embedded beside a server shares the one sampler)
        from predictionio_tpu.obs import contprof

        owner = f"StreamUpdater:{id(self):#x}"
        contprof.retain(owner)
        try:
            while not stop.is_set():
                try:
                    self.poll_once()
                except Exception:  # noqa: BLE001 — the daemon must
                    # survive a transient storage/serving failure; the
                    # error is logged and the next tick retries from the
                    # same cursor
                    log.exception("stream fold cycle failed")
                stop.wait(interval)
        finally:
            contprof.release(owner)
