"""Public engine-facing event-store API.

Behavior contract from the reference's PEventStore / LEventStore
(data/.../store/PEventStore.scala:30, store/LEventStore.scala:32,
store/Common.scala:28): engines address data by *app name* (+ optional
channel name); the store resolves the (appId, channelId) pair from
metadata and raises if the app or channel does not exist. ``find`` /
``aggregate_properties`` are the training-read path; ``find_by_entity``
is the low-latency serve-time lookup.

Without Spark there is a single API: results are Python lists of Event
(converted to numpy/JAX buffers by DataSources in ``predictionio_tpu.ops``).
"""

from __future__ import annotations

import datetime as _dt
from typing import Any, Dict, List, Optional

from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import UNSET, Storage, StorageError, get_storage


def resolve_app(
    app_name: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
):
    """app name (+channel name) -> (app_id, channel_id).

    ref: store/Common.scala:28 — errors mirror the reference's messages.
    """
    storage = storage or get_storage()
    app = storage.apps().get_by_name(app_name)
    if app is None:
        raise StorageError(f"App name {app_name} is not valid.")
    channel_id = None
    if channel_name is not None:
        channels = storage.channels().get_by_app_id(app.id)
        ch = next((c for c in channels if c.name == channel_name), None)
        if ch is None:
            raise StorageError(f"Channel name {channel_name} is not valid.")
        channel_id = ch.id
    return app.id, channel_id


def find(
    app_name: str,
    channel_name: Optional[str] = None,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    entity_type: Optional[str] = None,
    entity_id: Optional[str] = None,
    event_names: Optional[List[str]] = None,
    target_entity_type: Any = UNSET,
    target_entity_id: Any = UNSET,
    limit: Optional[int] = None,
    reversed: bool = False,
    storage: Optional[Storage] = None,
) -> List[Event]:
    """ref: PEventStore.find:30."""
    storage = storage or get_storage()
    app_id, channel_id = resolve_app(app_name, channel_name, storage)
    return storage.events().find(
        app_id,
        channel_id=channel_id,
        start_time=start_time,
        until_time=until_time,
        entity_type=entity_type,
        entity_id=entity_id,
        event_names=event_names,
        target_entity_type=target_entity_type,
        target_entity_id=target_entity_id,
        limit=limit,
        reversed=reversed,
    )


def find_columnar(
    app_name: str,
    channel_name: Optional[str] = None,
    value_property: Optional[str] = None,
    time_ordered: bool = True,
    shard_index: Optional[int] = None,
    shard_count: Optional[int] = None,
    storage: Optional[Storage] = None,
    **find_kwargs,
):
    """Bulk training read as dict-encoded columns (storage.EventColumns)
    — the fast path behind DataSources at ML-20M scale (the role of the
    reference's region-parallel HBase scans, hbase/HBPEvents.scala:48).
    ``shard_index``/``shard_count`` select this host's entity-hash read
    shard — N training hosts each fetch only ~1/N of the rows."""
    storage = storage or get_storage()
    app_id, channel_id = resolve_app(app_name, channel_name, storage)
    return storage.events().find_columnar(
        app_id,
        channel_id=channel_id,
        value_property=value_property,
        time_ordered=time_ordered,
        shard_index=shard_index,
        shard_count=shard_count,
        **find_kwargs,
    )


def supports_bin_columnar(
    app_name: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
) -> bool:
    """Whether the app's event store offers the fused native
    ingest->bin lane (``bin_columnar`` — today only the eventlog
    backend, and only when its C++ toolchain is available). Raises
    StorageError for an unknown app/channel, exactly like every other
    store entry point — callers probing capability fall back so the
    read path raises the canonical error message."""
    storage = storage or get_storage()
    resolve_app(app_name, channel_name, storage)
    if getattr(storage.events(), "bin_columnar", None) is None:
        return False
    from predictionio_tpu import native

    return native.native_available("eventlog")


def bin_columnar(
    app_name: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
    **kwargs,
):
    """The zero-copy training read: ONE native call scans the mmap'd
    log and bins BOTH sides into device-ready compressed layouts
    (storage.BinnedInteractions) — no Event objects, no Python row
    loop, no intermediate COO materialization. Callers must check
    :func:`supports_bin_columnar` first (other backends fall back to
    ``find_columnar`` + ops.ragged binning)."""
    storage = storage or get_storage()
    app_id, channel_id = resolve_app(app_name, channel_name, storage)
    return storage.events().bin_columnar(app_id, channel_id=channel_id,
                                         **kwargs)


def data_fingerprint(
    app_name: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
) -> Optional[str]:
    """O(1) content fingerprint of an app's event data, or None when
    the backend has no cheap one (only the native eventlog does —
    el_fingerprint). Changes whenever the data does; the binned-layout
    cache (ops.bincache) keys on it so retraining on unchanged events
    skips the bulk re-read."""
    storage = storage or get_storage()
    app_id, channel_id = resolve_app(app_name, channel_name, storage)
    fn = getattr(storage.events(), "data_fingerprint", None)
    if fn is None:
        return None
    return fn(app_id, channel_id)


def aggregate_properties(
    app_name: str,
    entity_type: str,
    channel_name: Optional[str] = None,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    required: Optional[List[str]] = None,
    storage: Optional[Storage] = None,
) -> Dict[str, PropertyMap]:
    """ref: PEventStore.aggregateProperties."""
    storage = storage or get_storage()
    app_id, channel_id = resolve_app(app_name, channel_name, storage)
    return storage.events().aggregate_properties(
        app_id,
        entity_type,
        channel_id=channel_id,
        start_time=start_time,
        until_time=until_time,
        required=required,
    )


def extract_entity_map(
    app_name: str,
    entity_type: str,
    extract,
    channel_name: Optional[str] = None,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    required: Optional[List[str]] = None,
    storage: Optional[Storage] = None,
):
    """Aggregate properties, then index entities into an EntityMap whose
    payload is ``extract(PropertyMap)`` per entity
    (ref: PEvents.extractEntityMap:109)."""
    from predictionio_tpu.data.bimap import EntityMap

    props = aggregate_properties(
        app_name,
        entity_type,
        channel_name=channel_name,
        start_time=start_time,
        until_time=until_time,
        required=required,
        storage=storage,
    )
    return EntityMap({eid: extract(pm) for eid, pm in props.items()})


def find_by_entity(
    app_name: str,
    entity_type: str,
    entity_id: str,
    channel_name: Optional[str] = None,
    event_names: Optional[List[str]] = None,
    target_entity_type: Any = UNSET,
    target_entity_id: Any = UNSET,
    start_time: Optional[_dt.datetime] = None,
    until_time: Optional[_dt.datetime] = None,
    limit: Optional[int] = None,
    latest: bool = True,
    storage: Optional[Storage] = None,
) -> List[Event]:
    """Serve-time entity lookup (ref: LEventStore.findByEntity:60)."""
    return find(
        app_name,
        channel_name=channel_name,
        start_time=start_time,
        until_time=until_time,
        entity_type=entity_type,
        entity_id=entity_id,
        event_names=event_names,
        target_entity_type=target_entity_type,
        target_entity_id=target_entity_id,
        limit=limit,
        reversed=latest,
        storage=storage,
    )
