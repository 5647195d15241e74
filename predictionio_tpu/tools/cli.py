"""The `pio`-equivalent console.

Behavior contract from the reference CLI (tools/.../console/
Console.scala:128-735 command surface; bin/pio:17-42 wrapper):

  app new|list|show|delete|data-delete|compact|channel-new|channel-delete
  accesskey new|list|delete
  build                 (register the engine manifest; no compile step —
                         engines are Python, ref: RegisterEngine.scala:50)
  train                 (ref: Console.scala:807 -> CreateWorkflow; here
                         in-process — no spark-submit JVM hop)
  eval                  (ref: evaluation branch, CreateWorkflow.scala:263)
  deploy / undeploy     (ref: Console.scala:830 -> CreateServer)
  stream                (streaming events->model daemon: delta tailer +
                         ALS fold-in / two-tower online steps, model
                         patches to live servers — ROADMAP item C)
  eventserver / adminserver / dashboard / storageserver
  import / export       (ref: imprt/FileToEvents, export/EventsToFile)
  template list|get     (egress-free: scaffolds the built-in templates
                         instead of downloading from the gallery,
                         ref: console/Template.scala:198-415)
  status                (ref: Storage.verifyAllDataObjects)
  metrics [--json]      (obs: Prometheus text or flat JSON dump)
  flight / profile      (obs diagnostics: a server's flight-recorder
                         dump; an on-demand JAX profiler window)
  slo                   (obs: SLO burn-rate evaluation, in-process or
                         from a server's /admin/slo)
  top                   (live terminal view of the metric timelines:
                         MFU, staleness, serving p50/p99, request rate
                         — sparklines from a server's /admin/timeline
                         or the in-process rings; --once --json for
                         scripts)
  fleet                 (serving fleet via the router's /admin/fleet:
                         replica states, rolling hot-swap, drain/
                         readmit; `deploy --replicas N` runs one)
  replay                (re-play captured query payloads against a
                         candidate instance, diff answers vs the
                         baseline — workflow/replay.py; report served
                         at /admin/quality)
  canary                (the fleet's canary lane: paired answer diffs,
                         per-lane latency burn, promote/rollback —
                         obs/quality.py's verdict via /admin/quality)
  journal               (the ops journal, obs/journal.py: reloads,
                         canary verdicts, breaker flips, shed
                         episodes, anomalies — /admin/journal, or the
                         member-merged stream with --fleet; --follow
                         tails it)
  anomalies             (the regression sentinel, obs/anomaly.py:
                         active change-points with causal attribution
                         to the journal — exit 1 while any is active)
  data                  (the data & ingest plane, obs/dataobs.py:
                         rates, entity heavy hitters + Zipf skew,
                         cardinality, schema drift, unknown-entity
                         coverage — /admin/data, member-merged with
                         --fleet)

Run as ``python -m predictionio_tpu.tools.cli <command> ...``.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import logging
import sys
from typing import List, Optional

from predictionio_tpu.data.storage import StorageError, get_storage
from predictionio_tpu.tools import commands, eventdata
from predictionio_tpu.tools.commands import CommandError

log = logging.getLogger(__name__)

BUILTIN_TEMPLATES = {
    "recommendation": "predictionio_tpu.templates.recommendation",
    "similarproduct": "predictionio_tpu.templates.similarproduct",
    "ecommercerecommendation": "predictionio_tpu.templates.ecommerce",
    "classification": "predictionio_tpu.templates.classification",
    "vanilla": "predictionio_tpu.templates.vanilla",
    "regression": "predictionio_tpu.templates.regression",
    "twotower": "predictionio_tpu.templates.twotower",
    "twotower-hybrid": "predictionio_tpu.templates.twotower",
    "sessionrec": "predictionio_tpu.templates.sessionrec",
}

TEMPLATE_FACTORIES = {
    "recommendation": "recommendation_engine",
    "similarproduct": "similar_product_engine",
    "ecommercerecommendation": "ecommerce_engine",
    "classification": "classification_engine",
    "vanilla": "vanilla_engine",
    "regression": "regression_engine",
    "twotower": "twotower_engine",
    "twotower-hybrid": "twotower_hybrid_engine",
    "sessionrec": "sessionrec_engine",
}


def _p(*args, **kwargs):
    print(*args, **kwargs)


# -- app / accesskey -----------------------------------------------------------

def cmd_app(args) -> int:
    st = get_storage()
    if args.app_command == "new":
        info = commands.app_new(args.name, args.description, st)
        _p("Created new app:")
        _p(f"      Name: {info.app.name}")
        _p(f"        ID: {info.app.id}")
        _p(f"Access Key: {info.access_keys[0].key}")
    elif args.app_command == "list":
        infos = commands.app_list(st)
        _p(f"{'Name':>20} | {'ID':>4} | {'Access Key':>64} | Allowed Event(s)")
        for info in infos:
            for k in info.access_keys:
                events = ",".join(sorted(k.events)) if k.events else "(all)"
                _p(f"{info.app.name:>20} | {info.app.id:>4} | {k.key:>64} | {events}")
        _p(f"Finished listing {len(infos)} app(s).")
    elif args.app_command == "show":
        info = commands.app_show(args.name, st)
        _p(f"    App Name: {info.app.name}")
        _p(f"      App ID: {info.app.id}")
        _p(f" Description: {info.app.description or ''}")
        for k in info.access_keys:
            events = ",".join(sorted(k.events)) if k.events else "(all)"
            _p(f"  Access Key: {k.key} | {events}")
        for c in info.channels:
            _p(f"     Channel: {c.name} (id {c.id})")
    elif args.app_command == "delete":
        commands.app_delete(args.name, st)
        _p(f"App deleted: {args.name}")
    elif args.app_command == "data-delete":
        commands.app_data_delete(args.name, args.channel, st)
        _p(f"App data deleted: {args.name}")
    elif args.app_command == "compact":
        stats = commands.app_compact(args.name, args.channel, st)
        # a sharded rest source returns one stats dict (or None) per shard
        shard_stats = stats if isinstance(stats, list) else [stats]
        if all(s is None for s in shard_stats):
            _p("Backend stores events in place; nothing to compact.")
        else:
            for i, s in enumerate(shard_stats):
                prefix = f"shard {i}: " if len(shard_stats) > 1 else ""
                if s is None:
                    _p(f"{prefix}stores events in place; nothing to compact.")
                else:
                    _p(f"{prefix}Compacted: dropped {s['dropped']} records, "
                       f"{s['before_bytes']} -> {s['after_bytes']} bytes")
    elif args.app_command == "channel-new":
        ch = commands.channel_new(args.name, args.channel, st)
        _p(f"Channel created: {ch.name} (id {ch.id})")
    elif args.app_command == "channel-delete":
        commands.channel_delete(args.name, args.channel, st)
        _p(f"Channel deleted: {args.channel}")
    return 0


def cmd_accesskey(args) -> int:
    st = get_storage()
    if args.ak_command == "new":
        key = commands.accesskey_new(args.app, args.event, st)
        _p(f"Created new access key: {key.key}")
    elif args.ak_command == "list":
        for k in commands.accesskey_list(args.app, st):
            events = ",".join(sorted(k.events)) if k.events else "(all)"
            _p(f"{k.key} | app {k.appid} | {events}")
    elif args.ak_command == "delete":
        commands.accesskey_delete(args.key, st)
        _p(f"Deleted access key: {args.key}")
    return 0


# -- build / train / eval / deploy --------------------------------------------

def _load_variant(path: str):
    from predictionio_tpu.workflow.variant import EngineVariant

    return EngineVariant.load(path)


def cmd_build(args) -> int:
    """Register the engine manifest (no compile step for Python engines)."""
    from predictionio_tpu.data.metadata import EngineManifest

    variant = _load_variant(args.engine_json)
    engine_id = args.engine_id or variant.raw.get("engineId") or variant.engine_factory
    st = get_storage()
    manifest = EngineManifest(
        id=engine_id,
        version=args.engine_version,
        name=variant.id,
        description=variant.description,
        files=[args.engine_json],
        engine_factory=variant.engine_factory,
    )
    existing = st.engine_manifests().get(engine_id, args.engine_version)
    if existing is None:
        st.engine_manifests().insert(manifest)
    else:
        st.engine_manifests().update(manifest)
    _p(f"Registered engine {engine_id} {args.engine_version} "
       f"({variant.engine_factory})")
    return 0


def cmd_train(args) -> int:
    from predictionio_tpu.workflow.config import WorkflowParams
    from predictionio_tpu.workflow.train import run_train

    variant = _load_variant(args.engine_json)
    engine = variant.create_engine()
    engine_params = variant.engine_params(engine)
    engine_id = args.engine_id or variant.raw.get("engineId") or variant.engine_factory
    wp = WorkflowParams(
        batch=args.batch,
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
    )
    instance = run_train(
        engine,
        engine_params,
        engine_id=engine_id,
        engine_version=args.engine_version,
        engine_variant=variant.id,
        engine_factory=variant.engine_factory,
        batch=args.batch,
        workflow_params=wp,
    )
    _p(f"Training completed: engine instance {instance.id} ({instance.status})")
    # one machine-readable line: a train result always names the device
    # it ran on, where the seconds went and which kernels were engaged
    from predictionio_tpu.obs import jaxmon, perfacct

    runs = perfacct.LEDGER.snapshot().get("runs") or []
    _p(json.dumps({"train_report": {
        "instance": instance.id,
        **jaxmon.device_report(),
        "stages_sec": runs[-1].get("stages") if runs else None,
        "trainers": jaxmon.TRAINER_REPORTS,
    }}))
    return 0 if instance.status == "COMPLETED" else 1


def cmd_eval(args) -> int:
    from predictionio_tpu.core.evaluation import Evaluation, EngineParamsGenerator
    from predictionio_tpu.workflow.evaluate import run_evaluation

    def resolve(dotted: str):
        module_name, _, attr = dotted.rpartition(".")
        if not module_name:
            raise CommandError(f"{dotted!r} must be a dotted module.Attr path")
        try:
            obj = getattr(importlib.import_module(module_name), attr)
        except (ImportError, AttributeError) as e:
            raise CommandError(f"cannot resolve {dotted!r}: {e}") from e
        return obj() if isinstance(obj, type) else obj

    evaluation = resolve(args.evaluation_class)
    if not isinstance(evaluation, Evaluation):
        raise CommandError(f"{args.evaluation_class} is not an Evaluation")
    generator = None
    if args.engine_params_generator_class:
        generator = resolve(args.engine_params_generator_class)
        if not isinstance(generator, EngineParamsGenerator):
            raise CommandError(
                f"{args.engine_params_generator_class} is not an EngineParamsGenerator"
            )
    result = run_evaluation(
        evaluation,
        generator=generator,
        evaluation_class=args.evaluation_class,
        generator_class=args.engine_params_generator_class or "",
        batch=args.batch,
    )
    _p(result.to_one_liner())
    return 0


def cmd_deploy(args) -> int:
    from predictionio_tpu.obs import metrics

    replicas = (args.replicas if args.replicas is not None
                else metrics.env_int("PIO_REPLICAS", 1))
    if getattr(args, "canary", False) and replicas <= 1:
        raise CommandError("--canary needs a fleet (--replicas >= 2): a "
                           "canary is one replica serving the candidate "
                           "while the rest serve the baseline")
    if replicas > 1:
        return _deploy_fleet(args, replicas)
    # imported only on the single-server lane: the engine server pulls
    # in jax, and a fleet's router process must stay off it (a parent
    # that touched jax could take the chip its replicas need)
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.serving.http import install_drain_handler

    variant = _load_variant(args.engine_json)
    engine = variant.create_engine()
    engine_id = args.engine_id or variant.raw.get("engineId") or variant.engine_factory
    server = EngineServer(
        engine,
        engine_id=engine_id,
        engine_version=args.engine_version,
        engine_variant=variant.id,
        host=args.ip,
        port=args.port,
        feedback_url=args.feedback_url,
        feedback_access_key=args.accesskey,
        log_url=args.log_url,
        # the variant's declarative objectives + shedding thresholds
        slo_conf=variant.slo_conf(),
    )
    # SIGTERM drains in-flight queries before the port closes (a fleet
    # supervisor's terminate, or any orchestrator's stop, is graceful)
    install_drain_handler(server)
    _p(f"Engine {engine_id} deployed on {args.ip}:{server.port}")
    server.serve_forever()
    return 0


def _deploy_fleet(args, replicas: int) -> int:
    """`pio deploy --replicas N`: N single-server children on ephemeral
    ports behind the query router on the public port (threaded replicas
    with --replica-mode=thread — same wiring, one process)."""
    from predictionio_tpu.serving.fleet import (
        FleetSupervisor, deploy_fleet_argv, subprocess_fleet,
        threaded_fleet)
    from predictionio_tpu.serving.http import (drain_timeout,
                                               install_drain_handler)
    from predictionio_tpu.serving.router import QueryRouter

    variant = _load_variant(args.engine_json)
    engine_id = (args.engine_id or variant.raw.get("engineId")
                 or variant.engine_factory)
    if args.replica_mode == "thread":
        from predictionio_tpu.serving.engine_server import EngineServer

        engine = variant.create_engine()

        def factory(name):
            return EngineServer(
                engine, engine_id=engine_id,
                engine_version=args.engine_version,
                engine_variant=variant.id, host="127.0.0.1", port=0,
                feedback_url=args.feedback_url,
                feedback_access_key=args.accesskey,
                log_url=args.log_url, slo_conf=variant.slo_conf(),
                chaos_tag=name)

        members = threaded_fleet(replicas, factory)
    else:
        argv = deploy_fleet_argv(args.engine_json)
        if args.engine_id:
            argv += ["--engine-id", args.engine_id]
        if args.engine_version != "0":
            argv += ["--engine-version", args.engine_version]
        # the per-server wiring must survive the subprocess hop — a
        # fleet with silently-dropped feedback/error-log plumbing is
        # not the same deployment
        if args.feedback_url:
            argv += ["--feedback-url", args.feedback_url]
        if args.accesskey:
            argv += ["--accesskey", args.accesskey]
        if args.log_url:
            argv += ["--log-url", args.log_url]
        try:
            members = subprocess_fleet(replicas, argv)
        except ValueError as e:   # fewer chips than replicas
            raise CommandError(str(e)) from e

    from predictionio_tpu.data.storage import get_storage

    storage = get_storage()
    fleet = FleetSupervisor(
        members,
        # workflow.deploy.latest_completed_instance_id, spelled out:
        # importing that module would pull jax into the router process
        version_source=lambda: getattr(
            storage.engine_instances().get_latest_completed(
                engine_id, args.engine_version, variant.id), "id", None),
        canary_mode=True if getattr(args, "canary", False) else None,
    ).start()
    router = QueryRouter(fleet, host=args.ip, port=args.port)
    install_drain_handler(router)
    lane = (" (CANARY mode: new COMPLETED instances land on one "
            "replica and are promoted/rolled back by verdict)"
            if getattr(args, "canary", False) else "")
    _p(f"Engine {engine_id} deployed: {replicas} "
       f"{args.replica_mode} replica(s) behind router on "
       f"{args.ip}:{router.port} (fleet status: /admin/fleet; rolling "
       f"hot-swap: GET /reload){lane}")
    try:
        router.serve_forever()
    finally:
        # serve_forever returns the moment the SIGTERM drain stops the
        # router ACCEPTING — its admitted requests are still draining
        # on the pio-drain thread and need live replicas to answer, so
        # the fleet must outlive them (bounded by the drain window)
        import time as _time

        deadline = _time.monotonic() + drain_timeout() + 5.0
        while (router.inflight_count() > 0
               and _time.monotonic() < deadline):
            _time.sleep(0.05)
        fleet.stop()
    return 0


def cmd_stream(args) -> int:
    """`pio stream`: the streaming events→model daemon (ROADMAP item C)
    — tail the event log since the last fold, fold deltas into the
    deployed model (ALS fold-in / two-tower online steps), and push
    model patches to live engine servers; `--once` runs one cycle."""
    from predictionio_tpu.workflow.stream import (StreamUnsupported,
                                                  StreamUpdater)

    variant = _load_variant(args.engine_json)
    engine = variant.create_engine()
    engine_id = (args.engine_id or variant.raw.get("engineId")
                 or variant.engine_factory)
    urls = [u.strip() for u in (args.url or "").split(",") if u.strip()]
    reload_urls = [u.strip() for u in (args.reload_url or "").split(",")
                   if u.strip()]
    try:
        updater = StreamUpdater(
            engine, engine_id, engine_version=args.engine_version,
            engine_variant=variant.id, patch_urls=urls,
            reload_urls=reload_urls)
    except StreamUnsupported as e:
        raise CommandError(str(e)) from e
    if args.once:
        _p(json.dumps(updater.poll_once()))
        return 0
    _p(f"streaming fold-in for engine {engine_id} "
       f"(instance {updater.instance_id}, cursor {updater.cursor}) -> "
       f"{', '.join(urls) if urls else 'local model only'}; Ctrl-C stops")
    try:
        updater.run_forever(interval=args.interval)
    except KeyboardInterrupt:
        _p("stream stopped")
    return 0


def cmd_undeploy(args) -> int:
    import urllib.request

    req = urllib.request.Request(
        f"http://{args.ip}:{args.port}/stop", method="POST", data=b""
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        _p(resp.read().decode())
    return 0


# -- servers -------------------------------------------------------------------

def cmd_eventserver(args) -> int:
    from predictionio_tpu.serving.event_server import EventServer
    from predictionio_tpu.serving.http import install_drain_handler

    server = EventServer(host=args.ip, port=args.port)
    install_drain_handler(server)
    _p(f"Event server running on {args.ip}:{server.port}")
    server.serve_forever()
    return 0


def cmd_adminserver(args) -> int:
    from predictionio_tpu.tools.admin import AdminServer

    server = AdminServer(host=args.ip, port=args.port)
    _p(f"Admin server running on {args.ip}:{server.port}")
    server.serve_forever()
    return 0


def cmd_dashboard(args) -> int:
    from predictionio_tpu.tools.dashboard import DashboardServer

    server = DashboardServer(host=args.ip, port=args.port)
    _p(f"Dashboard running on {args.ip}:{server.port}")
    server.serve_forever()
    return 0


def cmd_storageserver(args) -> int:
    """Serve this host's configured storage to `rest`-backend peers
    (the scale-out tier: HBase/ES/HDFS roles behind one HTTP service)."""
    from predictionio_tpu.serving.http import install_drain_handler
    from predictionio_tpu.serving.storage_server import StorageServer

    server = StorageServer(host=args.ip, port=args.port, auth_key=args.auth_key)
    install_drain_handler(server)
    _p(f"Storage server running on {args.ip}:{server.port}")
    server.serve_forever()
    return 0


def cmd_storagerepair(args) -> int:
    """Anti-entropy over every replicated tier: the app's events, then
    the metadata/model replica set. A tier that is not replicated is
    reported as skipped; if NEITHER tier is repairable the command
    fails loudly (nothing was checked)."""
    from predictionio_tpu.data.storage import StorageError

    repaired = 0
    try:
        stats = commands.repair_events(args.appname, args.channel)
        _p(f"Event replica repair for app {args.appname}: "
           f"{stats['copied']} rows copied, {stats['deleted']} rows deleted")
        repaired += 1
    except (commands.CommandError, StorageError) as e:
        _p(f"Events: skipped ({e})")
        events_error = e
    try:
        stats = commands.repair_metadata()
        _p(f"Metadata/model replica repair: {stats['copied']} records "
           f"copied, {stats['deleted']} records deleted")
        repaired += 1
    except commands.CommandError as e:
        _p(f"Metadata/models: skipped ({e})")
    if not repaired:
        raise events_error
    return 0


# -- data / misc ---------------------------------------------------------------

def cmd_import(args) -> int:
    n = eventdata.import_events(args.appname, args.input, args.channel,
                                format=args.format)
    _p(f"Imported {n} event(s).")
    return 0


def cmd_export(args) -> int:
    n = eventdata.export_events(args.appname, args.output, args.channel,
                                format=args.format)
    _p(f"Exported {n} event(s).")
    return 0


def cmd_shell(args) -> int:
    """REPL with storage + event store + mesh context bound
    (ref: bin/pio-shell — a Spark shell on the PIO classpath)."""
    import code

    from predictionio_tpu.data import store
    from predictionio_tpu.parallel.mesh import MeshContext

    ns = {
        "storage": get_storage(),
        "store": store,
        "ctx": MeshContext(),
        "commands": commands,
    }
    banner = (
        "predictionio-tpu shell — bound: storage (Storage), store "
        "(PEventStore/LEventStore API), ctx (MeshContext), commands"
    )
    code.interact(banner=banner, local=ns)
    return 0


def cmd_run(args) -> int:
    """Generic entry-point runner (ref: Runner.scala:27 — `pio run
    <mainClass>` spark-submits an arbitrary class on the PIO classpath).
    Here: resolve a dotted `module.callable` (or a bare module, executed
    as __main__) in-process with storage already configured, passing the
    remaining argv through."""
    target = args.target
    passthrough = list(args.args or [])
    module_name, _, attr = target.rpartition(".")
    obj = None
    if module_name:
        try:
            obj = getattr(importlib.import_module(module_name), attr, None)
        except ModuleNotFoundError as e:
            # only swallow "the dotted prefix itself isn't a module"
            # (we then retry the full name via runpy); an import failing
            # *inside* a real module is the user's error — surface it
            if e.name is None or not (
                module_name == e.name or module_name.startswith(e.name + ".")
            ):
                raise
            obj = None
    def exit_code(value, from_exit: bool) -> int:
        if isinstance(value, bool):      # True = success, not exit code 1
            return 0 if value else 1
        if isinstance(value, int):
            return value
        if value is None:
            return 0
        # non-int: a result object from a callable is success; a
        # SystemExit message (sys.exit("msg")) is failure
        return 1 if from_exit else 0

    if obj is not None and callable(obj):
        try:
            return exit_code(obj(passthrough), from_exit=False)
        except SystemExit as e:
            return exit_code(e.code, from_exit=True)
    import runpy

    # resolve existence up front so "target isn't a module" yields the
    # friendly error while ImportErrors raised *inside* a real module
    # (missing dependency, bad code) surface with their own traceback
    try:
        spec = importlib.util.find_spec(target)
    except ModuleNotFoundError as e:
        # the target (or its dotted prefix) is not a module at all
        if e.name and (target == e.name or target.startswith(e.name + ".")):
            spec = None
        else:  # a real module failed on a missing dependency — surface it
            raise
    except ValueError:  # e.g. an already-imported module with no __spec__
        spec = None
    if spec is None:
        raise CommandError(
            f"cannot resolve {target!r} as a callable or module"
        )
    old_argv = sys.argv
    sys.argv = [target] + passthrough
    try:
        runpy.run_module(target, run_name="__main__")
    except SystemExit as e:   # module mains exit; keep their code
        return exit_code(e.code, from_exit=True)
    except ImportError as e:
        # a package without __main__ (or the target itself failing to
        # import) is a resolution failure, not a user-code crash
        name = getattr(e, "name", None)
        if (name and (name == target or name.startswith(target + "."))) or \
                "cannot be directly executed" in str(e):
            raise CommandError(f"cannot run {target!r}: {e}") from e
        raise
    finally:
        sys.argv = old_argv
    return 0


#: `pio status` exit code when every tier still ANSWERS but some
#: endpoint is down (replicas absorbing the failure) — distinct from 1
#: (a tier cannot serve) so operators page on the right thing
#: (ref: Storage.verifyAllDataObjects role, Storage.scala:237).
STATUS_DEGRADED = 2


def cmd_status(args) -> int:
    from predictionio_tpu.data.storage import get_storage

    details = get_storage().serving_status()
    all_up = all(d["serving"] and not d["degraded"] for d in details.values())
    serving = all(d["serving"] for d in details.values())
    for repo, d in sorted(details.items()):
        state = ("OK" if d["serving"] and not d["degraded"]
                 else "DEGRADED" if d["serving"] else "FAILED")
        _p(f"{repo}: {state}")
        if len(d["endpoints"]) > 1 or not d["serving"] or d["degraded"]:
            # sharded source (or a failure): name each endpoint so a
            # down one is identified, not just counted
            for shard, alive in sorted(d["endpoints"].items()):
                if shard:
                    _p(f"  shard {shard}: {'OK' if alive else 'DOWN'}")
    if all_up:
        _p("(sleeping)")
        return 0
    if serving:
        _p("Storage degraded: every tier still serving through replicas, "
           "but some endpoint is down.")
        return STATUS_DEGRADED
    _p("Unable to connect to all storage backends.")
    return 1


def cmd_metrics(args) -> int:
    """Dump telemetry (obs subsystem): from a running server's
    ``GET /metrics`` when --url is given (every PIO server exposes it),
    otherwise the in-process registry — useful after an in-process
    `pio train` to read compile-cache and train timings. Default output
    is Prometheus text format; ``--json`` emits a flat machine-readable
    ``{"name{labels}": value}`` object (same shape in both modes)."""
    if args.url:
        import urllib.request

        url = args.url.rstrip("/")
        if not url.endswith("/metrics"):
            url += "/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            text = resp.read().decode()
    else:
        from predictionio_tpu.obs.metrics import REGISTRY

        text = REGISTRY.render()
    if args.json:
        from predictionio_tpu.obs.metrics import samples_dict

        json.dump(samples_dict(text), sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        sys.stdout.write(text)
    return 0


def _add_admin_auth(req) -> None:
    """Attach the PIO_ADMIN_TOKEN bearer header to an /admin/* request
    when the operator has one configured — the servers 401 those
    routes without it (serving/http.py)."""
    import os

    token = os.environ.get("PIO_ADMIN_TOKEN")
    if token:
        req.add_header("Authorization", f"Bearer {token}")


def cmd_flight(args) -> int:
    """Fetch a server's flight-recorder dump (``GET /admin/flight``,
    obs/flight.py): the last N completed request records with stage
    timings, span trees and trace ids, plus metric snapshots —
    pretty-printed JSON on stdout."""
    import urllib.error
    import urllib.parse
    import urllib.request

    query = {}
    if args.n is not None:
        query["n"] = str(args.n)
    if args.slow:
        query["slow"] = "1"
    url = args.url.rstrip("/") + "/admin/flight"
    if query:
        url += "?" + urllib.parse.urlencode(query)
    req = urllib.request.Request(url)
    _add_admin_auth(req)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            payload = json.load(resp)
    except urllib.error.HTTPError as e:
        raise CommandError(
            f"flight dump failed ({e.code}): "
            f"{e.read().decode(errors='replace')[:200]}")
    except urllib.error.URLError as e:
        raise CommandError(f"cannot reach {args.url}: {e.reason}")
    json.dump(payload, sys.stdout, indent=1, sort_keys=True)
    sys.stdout.write("\n")
    return 0


def cmd_trace(args) -> int:
    """Cross-process stitched trace (obs/collect.py): fan out to the
    fleet's span surfaces (``GET /admin/spans``) and render ONE
    annotated tree — process, replica, parent-edge latency, hedge/
    shadow siblings, and explicit placeholders where a member's ring
    evicted a span. With --url the server assembles (it knows its
    fleet: ``GET /admin/trace?id=``); without, this process assembles
    from its own ring + ACTIVE fleets + PIO_OBS_MEMBERS. Exit 1 when
    no spans were found for the id."""
    from predictionio_tpu.obs import collect

    if args.url:
        import urllib.error
        import urllib.request

        url = (args.url.rstrip("/") + "/admin/trace?id="
               + args.trace_id)
        req = urllib.request.Request(url)
        _add_admin_auth(req)
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                doc = json.load(resp)
        except urllib.error.HTTPError as e:
            raise CommandError(
                f"trace request failed ({e.code}): "
                f"{e.read().decode(errors='replace')[:200]}")
        except urllib.error.URLError as e:
            raise CommandError(f"cannot reach {args.url}: {e.reason}")
    else:
        doc = collect.stitch_trace(args.trace_id,
                                   collect.default_members())
    if args.json:
        json.dump(doc, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        _p(collect.format_trace_tree(doc))
    return 0 if doc.get("span_count") else 1


def cmd_profile(args) -> int:
    """Ask a live server for an on-demand JAX profiler capture
    (``POST /admin/profile?seconds=N``, obs/profiler.py) and print the
    artifact path. The server answers 501 on a CPU backend — there is
    no device timeline to record."""
    import urllib.error
    import urllib.request

    url = (args.url.rstrip("/")
           + f"/admin/profile?seconds={float(args.seconds)}")
    req = urllib.request.Request(url, method="POST", data=b"")
    _add_admin_auth(req)
    try:
        # the server sleeps through the capture window before answering
        with urllib.request.urlopen(
                req, timeout=float(args.seconds) + 30) as resp:
            payload = json.load(resp)
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        try:
            message = json.loads(body).get("message", body)
        except json.JSONDecodeError:
            message = body
        if e.code == 501:
            _p(f"profiler unavailable on the server: {message}")
            try:
                hint = json.loads(body).get("hint")
            except json.JSONDecodeError:
                hint = None
            if hint:
                _p(f"hint: {hint}")
            return 1
        raise CommandError(f"profile request failed ({e.code}): {message}")
    except urllib.error.URLError as e:
        # after HTTPError: a down/unreachable server is an operator
        # error, not a traceback
        raise CommandError(f"cannot reach {args.url}: {e.reason}")
    _p(f"profile captured ({payload['seconds']}s, "
       f"backend {payload.get('backend', '?')})")
    _p(f"artifact: {payload['artifact']}")
    _p("open with TensorBoard/xprof, or parse device time via "
       f"`python -m predictionio_tpu.obs.profiler {payload['artifact']}`")
    return 0


def cmd_prof(args) -> int:
    """Continuous host profiler (obs/contprof.py): fetch a server's
    aggregated wall-clock flame (``GET /admin/prof``; --fleet asks the
    router for the member-merged ``GET /admin/fleet/prof``) and render
    the flame tree + top-N hot frames through the SAME renderer the
    dashboard ``/prof`` view uses. --collapsed emits folded ``stack
    count`` lines for external flamegraph tooling."""
    import urllib.error
    import urllib.parse
    import urllib.request

    from predictionio_tpu.obs import contprof

    path = "/admin/fleet/prof" if args.fleet else "/admin/prof"
    query = {}
    if args.slow:
        query["slow"] = "1"
    if args.endpoint:
        query["endpoint"] = args.endpoint
    url = args.url.rstrip("/") + path
    if query:
        url += "?" + urllib.parse.urlencode(query)
    req = urllib.request.Request(url)
    _add_admin_auth(req)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            payload = json.load(resp)
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        try:
            message = json.loads(body).get("message", body)
        except json.JSONDecodeError:
            message = body[:200]
        raise CommandError(f"profile fetch failed ({e.code}): {message}")
    except urllib.error.URLError as e:
        raise CommandError(f"cannot reach {args.url}: {e.reason}")
    if args.json:
        json.dump(payload, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0
    flame = payload.get("merged", payload) if args.fleet else payload
    if args.collapsed:
        sys.stdout.write(contprof.collapsed_text(flame))
        return 0
    if args.fleet:
        for member in payload.get("members") or []:
            state = ("ok" if member.get("ok")
                     else f"ERROR: {member.get('error')}")
            detail = ""
            if member.get("ok"):
                detail = " ({} sample(s), {:.3g} Hz, overhead {})".format(
                    member.get("samples", 0),
                    member.get("effective_hz") or 0.0,
                    member.get("overhead_ratio"))
            _p(f"member {member.get('name', '?'):<12} {state}{detail}")
        _p("")
    sys.stdout.write(contprof.format_flame(flame, top=args.top))
    if args.slow and payload.get("slow_trace_ids"):
        _p("slow-cohort trace ids (join with `pio flight --slow`):")
        for tid in payload["slow_trace_ids"][-20:]:
            _p(f"  {tid}")
    return 0


def cmd_slo(args) -> int:
    """SLO burn-rate evaluation (obs/slo.py): from a running server's
    ``GET /admin/slo`` when --url is given (sending the
    ``PIO_ADMIN_TOKEN`` bearer header when set), otherwise evaluated
    in-process against this process's registry. ``--json`` dumps the
    raw report; default output is one line per SLO with its state and
    the worst-window burn."""
    import urllib.error
    import urllib.request

    if args.url:
        url = args.url.rstrip("/") + "/admin/slo"
        req = urllib.request.Request(url)
        _add_admin_auth(req)
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                report = json.load(resp)
        except urllib.error.HTTPError as e:
            raise CommandError(
                f"slo request failed ({e.code}): "
                f"{e.read().decode(errors='replace')[:200]}")
        except urllib.error.URLError as e:
            raise CommandError(f"cannot reach {args.url}: {e.reason}")
    else:
        from predictionio_tpu.obs import slo as _slo

        report = _slo.MONITOR.report()
    if args.json:
        json.dump(report, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0
    firing = 0
    for entry in report["slos"]:
        burns = {w: b for w, b in entry["burn_rates"].items()
                 if b is not None}
        worst = max(burns.values()) if burns else None
        target = f"{entry['objective']:.3%}"
        if entry.get("threshold_ms") is not None:
            target += f" <= {entry['threshold_ms']:g}ms"
        _p(f"{entry['name']:>20} [{entry['kind']}] objective {target}  "
           f"state={entry['state']}  "
           + (f"worst-window burn {worst:.2f}" if worst is not None
              else "no data"))
        for alert, info in entry["alerts"].items():
            if info["firing"]:
                _p(f"{'':>20} {alert} page FIRING "
                   f"(burn >= {info['threshold']} over "
                   f"{' and '.join(info['windows'])})")
        firing += entry["state"] == "firing"
    return 1 if firing else 0


def _fetch_admin_json(url: str, timeout: float = 30.0):
    """GET an /admin/* JSON payload with the bearer header; raises
    CommandError with the server's message on failure."""
    import urllib.error
    import urllib.request

    req = urllib.request.Request(url)
    _add_admin_auth(req)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.load(resp)
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        try:
            message = json.loads(body).get("message", body)
        except json.JSONDecodeError:
            message = body[:200]
        raise CommandError(f"request failed ({e.code}): {message}")
    except urllib.error.URLError as e:
        raise CommandError(f"cannot reach {url}: {e.reason}")


def format_journal_event(event) -> str:
    """One journal event as one human line: local wall clock, kind,
    member when federated, then the event's own fields."""
    import datetime

    ts = event.get("ts")
    when = (datetime.datetime.fromtimestamp(ts).strftime("%H:%M:%S")
            if isinstance(ts, (int, float)) else "--:--:--")
    parts = [f"{when}  {event.get('kind', '?'):<18}"]
    member = event.get("fleet_member")
    if member:
        parts.append(f"[{member}]")
    for key, value in event.items():
        if key in ("ts", "mono", "kind", "fleet_member"):
            continue
        if key == "trace":
            value = str(value)[:8]
        parts.append(f"{key}={value}")
    return " ".join(parts)


def cmd_journal(args) -> int:
    """The ops journal (obs/journal.py): what DID the system do and
    when — reloads, patches, canary verdicts, breaker flips, SLO
    alerts, shed episodes, watchdog stalls, anomaly onsets. Reads
    ``GET /admin/journal`` (or the member-merged
    ``GET /admin/fleet/journal`` with --fleet) when --url is given,
    else this process's ring. ``--follow`` polls for new events until
    interrupted; ``--kind``/``--since``/``-n`` slice the page."""
    import time as _time
    import urllib.parse

    def fetch(since):
        if args.url:
            path = ("/admin/fleet/journal" if args.fleet
                    else "/admin/journal")
            query = {"n": str(args.n)}
            if args.kind:
                query["kind"] = args.kind
            if since is not None:
                query["since"] = repr(since)
            url = (args.url.rstrip("/") + path + "?"
                   + urllib.parse.urlencode(query))
            return _fetch_admin_json(url)
        if args.fleet:
            raise CommandError("--fleet needs --url (the router "
                               "assembles the member merge)")
        from predictionio_tpu.obs import journal as _journal

        return _journal.JOURNAL.page(n=args.n, kind=args.kind,
                                     since=since)

    since = args.since
    payload = fetch(since)
    if args.json and not args.follow:
        json.dump(payload, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0
    events = payload.get("events") or []
    for event in events:
        _p(json.dumps(event, sort_keys=True) if args.json
           else format_journal_event(event))
    if not events and not args.follow:
        _p("(journal is empty)")
    if not args.follow:
        return 0
    # follow mode: poll with ?since= just past the newest event we
    # printed — ts is the join key across members, so a merged fleet
    # stream tails the same way a single process does
    last_ts = max((e.get("ts") or 0.0 for e in events), default=0.0)
    try:
        while True:
            _time.sleep(args.interval)
            payload = fetch(last_ts + 1e-3 if last_ts else None)
            for event in payload.get("events") or []:
                ts = event.get("ts") or 0.0
                if ts > last_ts:
                    last_ts = ts
                sys.stdout.write(
                    (json.dumps(event, sort_keys=True) if args.json
                     else format_journal_event(event)) + "\n")
                sys.stdout.flush()
    except KeyboardInterrupt:
        return 0


def cmd_anomalies(args) -> int:
    """The regression sentinel (obs/anomaly.py): active change-points
    over the metric timelines, each attributed to the nearest ops-
    journal event inside the causal window, plus recently resolved
    episodes. Reads ``GET /admin/anomaly`` (or the per-member
    ``GET /admin/fleet/anomaly`` with --fleet) when --url is given,
    else this process's sentinel. Exits 1 while ANY anomaly is active
    — the CI/cron-able "did that deploy regress anything" check."""
    if args.url:
        path = "/admin/fleet/anomaly" if args.fleet else "/admin/anomaly"
        report = _fetch_admin_json(args.url.rstrip("/") + path)
    elif args.fleet:
        raise CommandError("--fleet needs --url (the router assembles "
                           "the member merge)")
    else:
        from predictionio_tpu.obs import anomaly as _anomaly

        report = _anomaly.SENTINEL.report()
    active = report.get("active") or []
    if isinstance(active, dict):
        # the single-process page keys verdicts by series name; the
        # fleet merge already flattens to rows with a member stamp
        active = [dict(entry, series=series)
                  for series, entry in sorted(active.items())]
    if args.json:
        json.dump(report, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 1 if active else 0

    def describe(entry) -> str:
        line = (f"{entry.get('series', '?'):<28} "
                f"{entry.get('mode', '?')}/{entry.get('direction', '?')} "
                f"z={entry.get('z', 0):.1f} "
                f"baseline={entry.get('baseline')} "
                f"now={entry.get('recent')}")
        member = entry.get("fleet_member")
        if member:
            line = f"[{member}] " + line
        cause = entry.get("cause")
        if cause:
            line += (f"\n{'':<30}<- {cause.get('kind', '?')} "
                     f"{cause.get('gap_sec', 0):+.1f}s "
                     + " ".join(f"{k}={v}" for k, v in cause.items()
                                if k not in ("kind", "gap_sec", "ts",
                                             "trace")))
        return line

    if args.fleet:
        for member in report.get("members") or []:
            state = ("ok" if member.get("ok")
                     else f"ERROR: {member.get('error')}")
            _p(f"member {member.get('name', '?'):<12} {state}  "
               f"active={member.get('active', '?')}")
        _p("")
    if not active:
        _p("no active anomalies")
    else:
        _p(f"{len(active)} ACTIVE anomal"
           + ("y" if len(active) == 1 else "ies")
           + f" (window {report.get('window_sec', '?')}s):")
        for entry in active:
            _p("  " + describe(entry))
    resolved = (report.get("recent_resolved") or []
                if not args.fleet else [])
    if resolved:
        _p("recently resolved:")
        for entry in resolved[-5:]:
            _p(f"  {entry.get('series', '?'):<28} "
               f"lasted {entry.get('duration_sec', 0):.0f}s "
               f"(cause: {(entry.get('cause') or {}).get('kind', '-')})")
    return 1 if active else 0


def cmd_data(args) -> int:
    """The data & ingest observability plane (obs/dataobs.py): ingest
    rates per (app, event), entity heavy hitters with the fitted Zipf
    skew, HLL cardinalities, payload/value/inter-arrival quantiles,
    schema drift vs the trained-against profile and the unknown-entity
    coverage ratio. Reads ``GET /admin/data`` (or the member-merged
    ``GET /admin/fleet/data`` with --fleet) when --url is given, else
    this process's plane."""
    if args.url:
        path = "/admin/fleet/data" if args.fleet else "/admin/data"
        report = _fetch_admin_json(args.url.rstrip("/") + path)
    elif args.fleet:
        raise CommandError("--fleet needs --url (the router assembles "
                           "the member merge)")
    else:
        from predictionio_tpu.obs import dataobs

        report = dataobs.DATAOBS.report(top_n=args.top)
    if args.json:
        json.dump(report, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0

    def render_one(rep: dict, indent: str = "") -> None:
        _p(f"{indent}events {int(rep.get('events_total') or 0)} "
           f"({rep.get('eps', 0.0):g}/s)  "
           f"tail {int(rep.get('tail_events_total') or 0)}  "
           f"bytes {int(rep.get('bytes_total') or 0)}")
        entities = rep.get("entities") or {}
        card = entities.get("cardinality") or {}
        _p(f"{indent}entity skew {entities.get('skew', 0.0):g}  "
           f"cardinality " +
           " ".join(f"{k}={v}" for k, v in sorted(card.items())))
        _p(f"{indent}unknown-entity ratio "
           f"{rep.get('unknown_ratio', 0.0):g} "
           f"(over {int(rep.get('queries_seen') or 0)} query refs)")
        breaches = rep.get("breach_active") or {}
        if breaches:
            _p(f"{indent}ACTIVE BREACH: "
               + ", ".join(sorted(k for k, v in breaches.items() if v)))
        rates = rep.get("rates") or []
        if rates:
            _p(f"{indent}rates:")
            for row in rates[:10]:
                _p(f"{indent}  app {row.get('app'):>6} "
                   f"{row.get('event', '?'):<20} {row.get('count')}")
        top = entities.get("top") or []
        if top:
            _p(f"{indent}hot entities:")
            for row in top[:10]:
                _p(f"{indent}  {row.get('id', '?'):<24} "
                   f"{row.get('count')} (±{row.get('err', 0)})")
        quant = rep.get("quantiles") or {}
        for name, summ in sorted(quant.items()):
            if summ and summ.get("n"):
                _p(f"{indent}{name}: p50 {summ.get('p50')} "
                   f"p90 {summ.get('p90')} p99 {summ.get('p99')} "
                   f"(n={summ.get('n')})")
        schema = rep.get("schema") or {}
        changes = schema.get("changes") or []
        if changes:
            _p(f"{indent}schema changes "
               f"({schema.get('changes_total', len(changes))} total, "
               f"frozen at instance "
               f"{schema.get('frozen_instance') or '-'}):")
            for ch in changes[-10:]:
                member = ch.get("fleet_member")
                _p(f"{indent}  "
                   + (f"[{member}] " if member else "")
                   + f"{ch.get('event', '?')}.{ch.get('field', '?')} "
                   f"{ch.get('change', '?')} "
                   + " ".join(f"{k}={ch[k]}" for k in
                              ("old_type", "new_type") if ch.get(k)))

    if args.fleet:
        for member in report.get("members") or []:
            state = ("ok" if member.get("ok")
                     else f"ERROR: {member.get('error')}")
            _p(f"member {member.get('name', '?'):<12} {state}")
        _p("")
        totals = report.get("totals") or {}
        merged = {
            "events_total": totals.get("events_total"),
            "eps": totals.get("eps"),
            "tail_events_total": totals.get("tail_events_total"),
            "bytes_total": totals.get("bytes_total"),
            "entities": {"skew": report.get("skew", 0.0)},
            "unknown_ratio": report.get("unknown_ratio", 0.0),
            "breach_active": report.get("breach_active") or {},
            "schema": {"changes": report.get("schema_changes") or [],
                       "changes_total":
                           len(report.get("schema_changes") or [])},
        }
        render_one(merged)
    else:
        render_one(report)
    return 0


def cmd_chaos(args) -> int:
    """Inspect or toggle a live server's fault injection
    (``/admin/chaos``, resilience/chaos.py): with no mutation flags,
    print the active rule set; ``--set``/``--add``/``--clear`` change
    it. The server applies changes process-wide — every seam (storage,
    batcher, train) sees them immediately."""
    import urllib.error
    import urllib.request

    body = {}
    if args.clear is not None:
        body["clear"] = args.clear
    if args.set_spec is not None:
        body["spec"] = args.set_spec
    if args.add is not None:
        body["add"] = args.add
    url = args.url.rstrip("/") + "/admin/chaos"
    if body:
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json"})
    else:
        req = urllib.request.Request(url)
    _add_admin_auth(req)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            state = json.load(resp)
    except urllib.error.HTTPError as e:
        raise CommandError(
            f"chaos request failed ({e.code}): "
            f"{e.read().decode(errors='replace')[:200]}")
    except urllib.error.URLError as e:
        raise CommandError(f"cannot reach {args.url}: {e.reason}")
    if args.json:
        json.dump(state, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0
    if not state["enabled"]:
        _p("chaos: no active rules")
        return 0
    _p(f"chaos ACTIVE ({len(state['rules'])} rule(s)): {state['spec']}")
    for rule in state["rules"]:
        unit = "" if rule["kind"] == "error" else "s"
        _p(f"  {rule['site']:>10} {rule['kind']:<8} {rule['amount']:g}{unit}")
    return 0


def cmd_replay(args) -> int:
    """`pio replay`: re-play logged query payloads (the flight
    recorder's PIO_FLIGHT_PAYLOADS capture) against a candidate
    instance, diffing every answer against the baseline (top-k overlap,
    score deltas, latency — workflow/replay.py); prints the
    machine-readable report and registers it on the baseline's
    ``/admin/quality`` surface unless --no-push. Exit 1 when
    --fail-under is given and the mean overlap lands below it."""
    import urllib.error

    from predictionio_tpu.workflow import replay as replay_mod

    baseline = args.baseline or args.flight_url
    flight_url = args.flight_url or baseline
    if not baseline:
        raise CommandError("--baseline (or --flight-url) is required: "
                           "the diff needs a reference lane")
    try:
        report = replay_mod.replay_urls(
            args.url, baseline, flight_url=flight_url, n=args.n,
            k=args.k)
    except urllib.error.URLError as e:
        raise CommandError(f"replay failed: {e.reason}") from e
    except RuntimeError as e:
        raise CommandError(str(e)) from e
    if not args.no_push:
        try:
            replay_mod.push_report(report, baseline)
        except Exception as e:  # noqa: BLE001 — the report is already
            # in hand; a failed push must not eat it
            _p(f"(report push to {baseline} failed: {e})")
    if args.json:
        json.dump(report, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        _p(f"replayed {report['n']} logged quer(ies): "
           f"{report['diffed']} diffed, errors {report['errors']}")
        _p(f"  mean top-{report['k']} overlap {report['mean_overlap']}, "
           f"worst {report['worst_overlap']}, mean |score delta| "
           f"{report['mean_score_delta']}")
        for lane in ("baseline", "candidate"):
            lat = report["latency_ms"].get(lane) or {}
            if lat:
                _p(f"  {lane:>9}: p50 {lat['p50_ms']} ms, "
                   f"p99 {lat['p99_ms']} ms")
    if (args.fail_under is not None
            and (report["mean_overlap"] is None
                 or report["mean_overlap"] < args.fail_under)):
        _p(f"FAIL: mean overlap below --fail-under {args.fail_under:g}")
        return 1
    return 0


def cmd_canary(args) -> int:
    """`pio canary`: drive/inspect the fleet's canary lane through the
    router. Default output renders the quality surface's verdict
    (``GET /admin/quality`` — drift gauges, replay report and canary
    analysis all read obs/quality.py's one state); --start/--promote/
    --rollback POST the action to ``/admin/fleet``. Exit 1 while an
    active canary's verdict says rollback."""
    import urllib.error
    import urllib.request

    base = args.url.rstrip("/")
    action = ("start" if args.start else "promote" if args.promote
              else "rollback" if args.rollback else None)
    if action:
        req = urllib.request.Request(
            base + "/admin/fleet",
            data=json.dumps({"canary": action}).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        _add_admin_auth(req)
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                body = json.load(resp)
        except urllib.error.HTTPError as e:
            raise CommandError(
                f"canary {action} failed ({e.code}): "
                f"{e.read().decode(errors='replace')[:200]}")
        except urllib.error.URLError as e:
            raise CommandError(f"cannot reach {args.url}: {e.reason}")
        _p(body.get("message") or json.dumps(body))
        return 0
    req = urllib.request.Request(base + "/admin/quality")
    _add_admin_auth(req)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            report = json.load(resp)
    except urllib.error.HTTPError as e:
        raise CommandError(
            f"quality request failed ({e.code}): "
            f"{e.read().decode(errors='replace')[:200]}")
    except urllib.error.URLError as e:
        raise CommandError(f"cannot reach {args.url}: {e.reason}")
    if args.json:
        json.dump(report, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        canary = report.get("canary") or {}
        verdict = (canary.get("verdict") or {}).get("verdict")
        return 1 if (canary.get("active") and verdict == "rollback") else 0
    drift = report.get("drift")
    if drift:
        breached = drift.get("breached") or []
        _p(f"drift (band {report['band']:g}, shadow "
           f"{str(drift.get('shadow_instance'))[:16]}): "
           f"recall_vs_retrain={drift.get('recall_vs_retrain')} "
           f"rmse_drift={drift.get('rmse_drift')} "
           f"factor_drift={drift.get('factor_drift')}"
           + (f"  BREACHED: {', '.join(breached)}" if breached else ""))
    else:
        _p("drift: no probe yet (run `pio stream` against a trained "
           "instance)")
    rep = report.get("replay")
    if rep:
        _p(f"replay: {rep.get('n')} queries, mean overlap "
           f"{rep.get('mean_overlap')}, worst {rep.get('worst_overlap')}")
    canary = report.get("canary") or {}
    if not canary:
        _p("canary: none")
        return 0
    state = "ACTIVE" if canary.get("active") else (
        canary.get("outcome") or "inactive")
    _p(f"canary [{state}]: replica {canary.get('replica')} candidate "
       f"{str(canary.get('candidate_version'))[:16]} vs baseline "
       f"{str(canary.get('baseline_version'))[:16]}")
    paired = canary.get("paired") or {}
    if paired:
        _p(f"  paired samples: {paired.get('n')} "
           f"(errors {paired.get('errors')}), mean overlap "
           f"{paired.get('mean_overlap')}, worst "
           f"{paired.get('worst_overlap')}")
    verdict = canary.get("verdict") or {}
    if verdict:
        _p(f"  verdict: {verdict.get('verdict', '?').upper()}")
        for lane, info in (verdict.get("latency") or {}).items():
            _p(f"    {lane:>9}: {info.get('answers')} answers, "
               f"over-threshold rate {info.get('over_threshold_rate')} "
               f"(burn {info.get('burn')})")
        for reason in verdict.get("reasons") or []:
            _p(f"    - {reason}")
    return 1 if (canary.get("active")
                 and verdict.get("verdict") == "rollback") else 0


def cmd_fleet(args) -> int:
    """Inspect or control a serving fleet through its router's
    ``/admin/fleet`` (serving/fleet.py): default output is one line per
    replica (state, version, restarts, outstanding); ``--reload``
    starts the rolling zero-downtime hot-swap, ``--drain``/``--readmit``
    move one replica out of / into rotation."""
    import urllib.error
    import urllib.request

    body = {}
    if args.reload:
        body["reload"] = True
        if getattr(args, "force", False):
            # acknowledge a 507 preflight refusal: the operator owns
            # the OOM risk now (obs/memacct.py)
            body["force"] = True
    if args.drain is not None:
        body["drain"] = args.drain
    if args.readmit is not None:
        body["readmit"] = args.readmit
    url = args.url.rstrip("/") + "/admin/fleet"
    if body:
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json"})
    else:
        req = urllib.request.Request(url)
    _add_admin_auth(req)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            state = json.load(resp)
    except urllib.error.HTTPError as e:
        raise CommandError(
            f"fleet request failed ({e.code}): "
            f"{e.read().decode(errors='replace')[:200]}")
    except urllib.error.URLError as e:
        raise CommandError(f"cannot reach {args.url}: {e.reason}")
    if args.json:
        json.dump(state, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0
    if body:
        _p(state.get("message") or json.dumps(state))
        return 0
    _p(f"fleet: {state['ready']}/{state['size']} ready, serving "
       f"version {state['version'] or '(mixed/none)'}")
    for r in state["replicas"]:
        _p(f"  {r['name']:>6} {r['state']:<9} port={r['port'] or '-':<6} "
           f"version={r['version'] or '-':<34} restarts={r['restarts']} "
           f"outstanding={r['outstanding']}")
    from predictionio_tpu.serving.fleet import format_swap

    swap = state.get("swap") or {}
    if swap.get("active") or swap.get("last"):
        _p(format_swap(swap))
    return 0


def _fmt_bytes(n) -> str:
    """Human bytes for the mem report (binary units — HBM is sized in
    GiB); None renders as '-'."""
    if n is None:
        return "-"
    n = float(n)
    sign = "-" if n < 0 else ""
    n = abs(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return (f"{sign}{n:.0f} {unit}" if unit == "B"
                    else f"{sign}{n:.2f} {unit}")
        n /= 1024.0
    return f"{sign}{n:.2f} TiB"


def cmd_mem(args) -> int:
    """Device-memory accounting (obs/memacct.py): headroom + basis,
    the per-model HBM ledger, train high-water peaks and the last OOM
    preflight decision — from a live server's ``GET /admin/memory``
    with --url, else this process's own ledger (useful after an
    in-process `pio train`)."""
    if args.url:
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            args.url.rstrip("/") + "/admin/memory")
        _add_admin_auth(req)
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                report = json.load(resp)
        except urllib.error.HTTPError as e:
            raise CommandError(
                f"memory report failed ({e.code}): "
                f"{e.read().decode(errors='replace')[:200]}")
        except urllib.error.URLError as e:
            raise CommandError(f"cannot reach {args.url}: {e.reason}")
    else:
        from predictionio_tpu.obs import memacct

        report = memacct.report()
    if args.json:
        json.dump(report, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
        return 0
    _p(f"device memory ({report['basis']} basis): "
       f"{_fmt_bytes(report['in_use_bytes'])} in use of "
       f"{_fmt_bytes(report['capacity_bytes'])} — headroom "
       f"{_fmt_bytes(report['headroom_bytes'])}")
    models = report.get("models") or {}
    if not models:
        _p("  (no ledgered model residency in this process)")
    for model in sorted(models):
        block = models[model]
        components = " ".join(
            f"{name}={_fmt_bytes(nbytes)}"
            for name, nbytes in sorted(block["components"].items()))
        _p(f"  {model:>12} {_fmt_bytes(block['total_bytes']):>12}  "
           f"{components}")
    peaks = report.get("train_peaks") or {}
    for model in sorted(peaks):
        peak = peaks[model]
        _p(f"  train peak {model}: {_fmt_bytes(peak['bytes'])} "
           f"({peak['source']})")
    pre = report.get("preflight") or {}
    state = "on" if pre.get("enabled") else "OFF (PIO_MEM_PREFLIGHT=0)"
    line = (f"preflight {state}, estimate scale "
            f"x{pre.get('estimate_scale')}")
    last = pre.get("last")
    if last:
        line += (f"; last: {last.get('result')} instance "
                 f"{last.get('instance')} "
                 f"(est {_fmt_bytes(last.get('estimated_bytes'))} vs "
                 f"headroom {_fmt_bytes(last.get('headroom_bytes'))})")
    _p(line)
    return 0


def _fetch_timeline(url: Optional[str]) -> dict:
    """One timeline payload: a server's ``GET /admin/timeline`` when
    ``url`` is given (PIO_ADMIN_TOKEN bearer attached when set), else
    the in-process rings (sampled now, so a bare `pio top --once` after
    an in-process train still shows data)."""
    if url:
        import urllib.error
        import urllib.request

        req = urllib.request.Request(url.rstrip("/") + "/admin/timeline")
        _add_admin_auth(req)
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return json.load(resp)
        except urllib.error.HTTPError as e:
            raise CommandError(
                f"timeline request failed ({e.code}): "
                f"{e.read().decode(errors='replace')[:200]}")
        except urllib.error.URLError as e:
            raise CommandError(f"cannot reach {url}: {e.reason}")
    from predictionio_tpu.obs import perfacct, timeline

    timeline.TIMELINE.sample(force=True)
    payload = timeline.TIMELINE.series()
    payload["datapath"] = perfacct.LEDGER.snapshot()
    return payload


def _render_top_frame(payload: dict) -> str:
    """One `pio top` frame: a sparkline + latest value per series,
    then the data-path ledger summary."""
    from predictionio_tpu.obs.timeline import sparkline

    lines = []
    series = payload.get("series") or {}
    if not series:
        lines.append("(no samples yet — traffic or a train run feeds "
                     "the timeline)")
    width = max((len(n) for n in series), default=0)
    for name in sorted(series):
        points = series[name]
        if not points:
            continue
        values = [p[1] for p in points]
        lines.append(f"{name:>{width}}  {sparkline(values, 40):<40} "
                     f"{values[-1]:>12.4g}  "
                     f"(min {min(values):.4g} max {max(values):.4g}, "
                     f"n={len(values)})")
    def latest(name):
        points = series.get(name) or []
        return points[-1][1] if points else None

    eps = latest("data.eps")
    unknown = latest("data.unknown_ratio")
    skew = latest("data.skew")
    if any(v is not None for v in (eps, unknown, skew)):
        lines.append("")
        lines.append(
            "ingest: {} ev/s  unknown-entity {}  skew {}".format(
                "–" if eps is None else f"{eps:.4g}",
                "–" if unknown is None else f"{unknown:.2%}",
                "–" if skew is None else f"{skew:.3g}"))
    datapath = payload.get("datapath") or {}
    if datapath:
        lines.append("")
        lines.append(f"model staleness: "
                     f"{datapath.get('staleness_seconds', 0.0):.1f}s")
        runs = datapath.get("runs") or []
        if runs:
            last = runs[-1]
            stages = " ".join(f"{k}={v:.2f}s"
                              for k, v in sorted(last["stages"].items()))
            lines.append(f"last run {last['run']}: {stages or '(no stages)'}")
    return "\n".join(lines)


def _fetch_fleet_report(url: str) -> dict:
    """One federation report off the router's ``GET
    /admin/fleet/metrics`` (obs/collect.py) — the ``pio top --fleet``
    data source."""
    import urllib.error
    import urllib.request

    req = urllib.request.Request(url.rstrip("/") + "/admin/fleet/metrics")
    _add_admin_auth(req)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return json.load(resp)
    except urllib.error.HTTPError as e:
        raise CommandError(
            f"fleet metrics request failed ({e.code}): "
            f"{e.read().decode(errors='replace')[:200]}")
    except urllib.error.URLError as e:
        raise CommandError(f"cannot reach {url}: {e.reason}")


def _render_fleet_frame(report: dict, history: Optional[dict] = None) -> str:
    """One `pio top --fleet` frame: fleet-wide percentiles off the
    MERGED serving histogram, the fleet SLO burn, and a per-member
    table. ``history`` (the live loop's client-side rings) adds
    sparklines — the federated endpoint is the data source, the view
    stays the familiar one."""
    from predictionio_tpu.obs import collect
    from predictionio_tpu.obs.timeline import sparkline

    lines = []
    samples = report.get("samples") or {}
    slo = report.get("slo") or {}
    p50 = collect.quantile_from_flat(
        samples, "pio_serving_request_seconds", 0.5)
    p99 = collect.quantile_from_flat(
        samples, "pio_serving_request_seconds", 0.99)
    requests = sum(v for k, v in samples.items()
                   if k.startswith("pio_http_requests_total"))
    if history is not None:
        for name, value in (("fleet.srv_p50_ms",
                             None if p50 is None else p50 * 1e3),
                            ("fleet.srv_p99_ms",
                             None if p99 is None else p99 * 1e3),
                            ("fleet.http_requests", requests)):
            if value is not None:
                history.setdefault(name, []).append(value)
                del history[name][:-120]
    burn = slo.get("burn")
    lines.append(
        "fleet serving: p50 {} p99 {} — SLO burn {} "
        "(<= {:g}ms objective {:.1%}, {} of {} good)".format(
            "–" if p50 is None else f"{p50 * 1e3:.2f}ms",
            "–" if p99 is None else f"{p99 * 1e3:.2f}ms",
            "–" if burn is None else f"{burn:g}",
            slo.get("threshold_ms", 0.0), slo.get("objective", 0.0),
            int(slo.get("good") or 0), int(slo.get("total") or 0)))
    # the ingest row (obs/dataobs.py gauges): counters sum across the
    # merge; skew/unknown take the fleet max — a hot key or a stale
    # model on ONE replica is the fleet's problem
    ingest_events = sum(v for k, v in samples.items()
                        if k.startswith("pio_data_events_total"))
    fleet_skew = max((v for k, v in samples.items()
                      if k.startswith("pio_data_entity_skew")),
                     default=None)
    fleet_unknown = max(
        (v for k, v in samples.items()
         if k.startswith("pio_query_unknown_entity_ratio")),
        default=None)
    if ingest_events or fleet_skew is not None \
            or fleet_unknown is not None:
        if history is not None:
            history.setdefault("fleet.ingest_events", []).append(
                ingest_events)
            del history["fleet.ingest_events"][:-120]
        lines.append(
            "fleet ingest: events {:.0f}  unknown-entity {}  "
            "skew {}".format(
                ingest_events,
                "–" if fleet_unknown is None else f"{fleet_unknown:.2%}",
                "–" if fleet_skew is None else f"{fleet_skew:.3g}"))
    if history:
        width = max(len(n) for n in history)
        for name in sorted(history):
            values = history[name]
            lines.append(f"{name:>{width}}  "
                         f"{sparkline(values, 40):<40} "
                         f"{values[-1]:>12.4g}")
    lines.append("")
    lines.append(f"{'member':>12} {'role':>10} {'status':>8} "
                 f"{'http_reqs':>10} {'served':>8}")
    for member in report.get("members") or []:
        status = "ok" if member.get("ok") else "ERROR"
        lines.append(
            f"{member.get('name', '?'):>12} "
            f"{member.get('role', ''):>10} {status:>8} "
            f"{int(member.get('http_requests') or 0):>10} "
            f"{int(member.get('serving_requests') or 0):>8}"
            + (f"  ({member.get('error')})" if not member.get("ok")
               else ""))
    return "\n".join(lines)


def cmd_top(args) -> int:
    """Live performance view (obs/timeline.py + obs/perfacct.py): the
    tracked gauge/quantile timelines as terminal sparklines, refreshed
    every ``--interval`` seconds; ``--once`` prints a single frame and
    exits; ``--json`` (with --once) dumps the raw payload. With
    ``--fleet`` the SAME live view is driven from the router's
    federated ``GET /admin/fleet/metrics`` instead of a single
    process: fleet-wide merged percentiles, SLO burn and a per-member
    table."""
    if args.json and not args.once:
        raise CommandError("--json requires --once (one machine-readable "
                           "frame; stream consumers should poll "
                           "/admin/timeline)")
    if args.fleet and not args.url:
        raise CommandError("--fleet needs --url (the fleet's router)")

    def fetch_and_render(history=None):
        if args.fleet:
            report = _fetch_fleet_report(args.url)
            return report, _render_fleet_frame(report, history)
        payload = _fetch_timeline(args.url)
        return payload, _render_top_frame(payload)

    if args.once:
        payload, frame = fetch_and_render()
        if args.json:
            json.dump(payload, sys.stdout, indent=1, sort_keys=True)
            sys.stdout.write("\n")
        else:
            _p(frame)
        return 0
    history: dict = {}
    try:
        while True:
            # a transient fetch failure (server restarting, one poll
            # timing out) shows in the frame and the watch continues —
            # only --once hard-fails
            try:
                _payload, frame = fetch_and_render(history)
            except CommandError as e:
                frame = f"(fetch failed, retrying: {e})"
            # ANSI clear + home, like every terminal top
            sys.stdout.write("\x1b[2J\x1b[H")
            _p(f"pio top — {args.url or 'in-process'}"
               f"{' [fleet]' if args.fleet else ''} "
               f"(interval {args.interval:g}s, ctrl-c to quit)\n")
            _p(frame)
            sys.stdout.flush()
            import time as _time

            _time.sleep(max(0.2, args.interval))
    except KeyboardInterrupt:
        return 0


def cmd_lint(args) -> int:
    """graftlint: the JAX/TPU-aware static analysis over the tree
    (rules JT01-JT17 + JT22-JT23 per file; --project adds the whole-program
    concurrency layer JT18-JT20; tier-1 CI runs the same passes via
    tests/test_lint_clean.py)."""
    from predictionio_tpu.tools.lint import run_cli

    try:
        return run_cli(args.paths, fmt=args.format,
                       show_rules=args.list_rules, project=args.project)
    except FileNotFoundError as e:
        # exit 2, not 1: a bad path must stay distinguishable from
        # "lint ran and found something" for CI wrappers
        print(f"graftlint: {e}", file=sys.stderr)
        return 2


def cmd_template(args) -> int:
    if args.template_command == "list":
        for name, module in sorted(BUILTIN_TEMPLATES.items()):
            _p(f"{name:28} {module}")
        return 0
    # template get <name> <dir>: materialize a WORKING engine project —
    # the template module's full source copied in as user-editable code
    # plus an engine.json whose factory resolves from the project dir
    # (ref: Template.scala:226-415 downloads + package-renames a full
    # source tree; here the source ships in the installed package, so
    # "get" copies and rebinds it — egress-free)
    import importlib
    import inspect
    import os
    import shutil

    name = args.name
    if name not in BUILTIN_TEMPLATES:
        raise CommandError(
            f"Unknown template {name!r} (available: {sorted(BUILTIN_TEMPLATES)})"
        )
    os.makedirs(args.directory, exist_ok=True)
    module = importlib.import_module(BUILTIN_TEMPLATES[name])
    src = inspect.getsourcefile(module)
    if src is None:
        raise CommandError(f"cannot locate source for {BUILTIN_TEMPLATES[name]}")
    mod_name = f"{name.replace('-', '_')}_engine"
    engine_py = os.path.join(args.directory, f"{mod_name}.py")
    shutil.copyfile(src, engine_py)

    engine_json = {
        "id": "default",
        "description": f"{name} template (scaffolded from "
                       f"{BUILTIN_TEMPLATES[name]})",
        "engineFactory": f"{mod_name}.{TEMPLATE_FACTORIES[name]}",
    }
    path = os.path.join(args.directory, "engine.json")
    with open(path, "w") as f:
        json.dump(engine_json, f, indent=2)
        f.write("\n")
    readme = os.path.join(args.directory, "README.md")
    with open(readme, "w") as f:
        f.write(
            f"# {name} engine\n\n"
            f"Scaffolded from `{BUILTIN_TEMPLATES[name]}`.\n\n"
            f"- `{mod_name}.py` — YOUR engine source (DataSource/"
            "Preparator/Algorithm/Serving + factory). Edit freely; it\n"
            "  is resolved from this directory, not the installed "
            "package.\n"
            "- `engine.json` — the variant: fill the per-component "
            "`{\"name\": ..., \"params\": {...}}` blocks (e.g. the "
            "datasource's `app_name`).\n\n"
            "Run `pio build|train|deploy --engine-json engine.json`.\n"
        )
    _p(f"Created {args.directory}: {mod_name}.py (editable engine source), "
       f"engine.json, README.md")
    _p(f"Edit params, then `pio train --engine-json {path}`.")
    return 0


# -- parser --------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="pio", description="PredictionIO-TPU console"
    )
    parser.add_argument("-v", "--verbose", action="store_true")
    sub = parser.add_subparsers(dest="command", required=True)

    p_app = sub.add_parser("app", help="manage apps")
    app_sub = p_app.add_subparsers(dest="app_command", required=True)
    p = app_sub.add_parser("new"); p.add_argument("name")
    p.add_argument("--description", default=None)
    app_sub.add_parser("list")
    p = app_sub.add_parser("show"); p.add_argument("name")
    p = app_sub.add_parser("delete"); p.add_argument("name")
    p = app_sub.add_parser("data-delete"); p.add_argument("name")
    p.add_argument("--channel", default=None)
    p = app_sub.add_parser("compact"); p.add_argument("name")
    p.add_argument("--channel", default=None)
    p = app_sub.add_parser("channel-new"); p.add_argument("name"); p.add_argument("channel")
    p = app_sub.add_parser("channel-delete"); p.add_argument("name"); p.add_argument("channel")
    p_app.set_defaults(func=cmd_app)

    p_ak = sub.add_parser("accesskey", help="manage access keys")
    ak_sub = p_ak.add_subparsers(dest="ak_command", required=True)
    p = ak_sub.add_parser("new"); p.add_argument("app")
    p.add_argument("event", nargs="*", help="allowed events (empty = all)")
    p = ak_sub.add_parser("list"); p.add_argument("--app", default=None)
    p = ak_sub.add_parser("delete"); p.add_argument("key")
    p_ak.set_defaults(func=cmd_accesskey)

    def add_engine_args(p):
        p.add_argument("--engine-json", default="engine.json")
        p.add_argument("--engine-id", default=None)
        p.add_argument("--engine-version", default="0")

    p = sub.add_parser("build", help="register the engine manifest")
    add_engine_args(p); p.set_defaults(func=cmd_build)

    p = sub.add_parser("train", help="train an engine")
    add_engine_args(p)
    p.add_argument("--batch", default="")
    p.add_argument("--skip-sanity-check", action="store_true")
    p.add_argument("--stop-after-read", action="store_true")
    p.add_argument("--stop-after-prepare", action="store_true")
    p.set_defaults(func=cmd_train)

    p = sub.add_parser("eval", help="run an evaluation")
    p.add_argument("evaluation_class")
    p.add_argument("engine_params_generator_class", nargs="?", default=None)
    p.add_argument("--batch", default="")
    p.set_defaults(func=cmd_eval)

    p = sub.add_parser("deploy", help="deploy the latest trained instance")
    add_engine_args(p)
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--feedback-url", default=None)
    p.add_argument("--accesskey", default=None)
    p.add_argument("--log-url", default=None,
                   help="POST serve errors to this URL "
                        "(ref: CreateServer.scala:413-424)")
    p.add_argument("--replicas", type=int, default=None,
                   help="serve from N engine-server replicas behind a "
                        "health-routed query router on --port "
                        "(default: PIO_REPLICAS or 1 = the classic "
                        "single server)")
    p.add_argument("--replica-mode", choices=["subprocess", "thread"],
                   default="subprocess",
                   help="replica isolation: subprocesses on ephemeral "
                        "ports (production) or in-process threaded "
                        "servers (single-host / tests)")
    p.add_argument("--canary", action="store_true",
                   help="canary mode (needs --replicas >= 2): a new "
                        "COMPLETED instance lands on ONE replica; the "
                        "router samples paired answers + per-lane "
                        "latency and the verdict auto-promotes or "
                        "auto-rolls-back (PIO_CANARY_* knobs; watch "
                        "cadence PIO_FLEET_WATCH_SEC)")
    p.set_defaults(func=cmd_deploy)

    p = sub.add_parser(
        "stream",
        help="streaming events->model daemon: tail the event log, fold "
             "deltas into the deployed model (ALS fold-in / two-tower "
             "online steps), push /model/patch to engine servers "
             "(ROADMAP item C; interval: PIO_STREAM_INTERVAL_SEC)")
    add_engine_args(p)
    p.add_argument("--url", default=None,
                   help="comma-separated engine-server base URLs to "
                        "patch (e.g. http://127.0.0.1:8000); omit to "
                        "fold the local model copy only. For fleets, "
                        "patch each replica — the rolling GET /reload "
                        "stays the full-retrain fallback")
    p.add_argument("--interval", type=float, default=None,
                   help="poll seconds (default PIO_STREAM_INTERVAL_SEC "
                        "or 1.0)")
    p.add_argument("--once", action="store_true",
                   help="one tail->fold->publish cycle, print stats JSON")
    p.add_argument("--reload-url", default=None,
                   help="comma-separated base URLs whose GET /reload "
                        "the drift-band breach auto-triggers (normally "
                        "the fleet router; PIO_QUALITY_DRIFT_BAND sets "
                        "the band)")
    p.set_defaults(func=cmd_stream)

    p = sub.add_parser("undeploy", help="stop a deployed engine server")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.set_defaults(func=cmd_undeploy)

    p = sub.add_parser("eventserver")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7070)
    p.set_defaults(func=cmd_eventserver)

    p = sub.add_parser("adminserver")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7071)
    p.set_defaults(func=cmd_adminserver)

    p = sub.add_parser("dashboard")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=9000)
    p.set_defaults(func=cmd_dashboard)

    p = sub.add_parser(
        "storageserver",
        help="serve this host's storage to rest-backend peers",
    )
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7077)
    p.add_argument("--auth-key", default=None,
                   help="require X-PIO-Storage-Key on every request")
    p.set_defaults(func=cmd_storageserver)

    p = sub.add_parser(
        "storagerepair",
        help="reconcile event replicas on a replicated sharded source "
             "(owner-authoritative anti-entropy; run in a maintenance "
             "window — writes to the app must be quiesced)",
    )
    p.add_argument("--appname", required=True)
    p.add_argument("--channel", default=None)
    p.set_defaults(func=cmd_storagerepair)

    p = sub.add_parser("import", help="import events from a JSONL/parquet file")
    p.add_argument("--appname", required=True)
    p.add_argument("--input", required=True)
    p.add_argument("--channel", default=None)
    p.add_argument("--format", default=None, choices=["json", "parquet"])
    p.set_defaults(func=cmd_import)

    p = sub.add_parser("export", help="export events to a JSONL/parquet file")
    p.add_argument("--appname", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--channel", default=None)
    p.add_argument("--format", default=None, choices=["json", "parquet"])
    p.set_defaults(func=cmd_export)

    p = sub.add_parser("status", help="verify storage configuration")
    p.set_defaults(func=cmd_status)

    p = sub.add_parser("shell", help="interactive Python shell with the "
                                     "framework preloaded (ref: bin/pio-shell)")
    p.set_defaults(func=cmd_shell)

    p = sub.add_parser("run", help="run a dotted module.callable (or module "
                                   "as __main__) with storage configured "
                                   "(ref: pio run / Runner.scala)")
    p.add_argument("target")
    p.add_argument("args", nargs=argparse.REMAINDER)
    p.set_defaults(func=cmd_run)

    p = sub.add_parser(
        "metrics",
        help="dump Prometheus metrics (from a server's /metrics with "
             "--url, else the in-process registry)",
    )
    p.add_argument("--url", default=None,
                   help="base URL of any PIO server, e.g. "
                        "http://127.0.0.1:8000")
    p.add_argument("--json", action="store_true",
                   help="machine-readable flat {name{labels}: value} dump")
    p.set_defaults(func=cmd_metrics)

    p = sub.add_parser(
        "flight",
        help="dump a server's flight recorder (GET /admin/flight): the "
             "last completed requests with stage timings + trace ids",
    )
    p.add_argument("--url", required=True,
                   help="base URL of any PIO server, e.g. "
                        "http://127.0.0.1:8000")
    p.add_argument("-n", type=int, default=None,
                   help="only the last N records")
    p.add_argument("--slow", action="store_true",
                   help="only slow/errored records")
    p.set_defaults(func=cmd_flight)

    p = sub.add_parser(
        "trace",
        help="stitch one trace id across the fleet (GET /admin/trace "
             "via --url, else assembled in-process from this process's "
             "ring + ACTIVE fleets + PIO_OBS_MEMBERS) and render the "
             "annotated cross-process tree",
    )
    p.add_argument("trace_id",
                   help="the trace id (X-PIO-Trace-Id of any response)")
    p.add_argument("--url", default=None,
                   help="base URL of the assembling server — normally "
                        "the fleet's router (sends the PIO_ADMIN_TOKEN "
                        "bearer header when set)")
    p.add_argument("--json", action="store_true",
                   help="dump the raw stitched-trace document")
    p.set_defaults(func=cmd_trace)

    p = sub.add_parser(
        "profile",
        help="capture an on-demand JAX profiler window on a live server "
             "(POST /admin/profile); prints the artifact path, exits 1 "
             "with a message on CPU backends",
    )
    p.add_argument("--url", required=True,
                   help="base URL of the server doing the device work")
    p.add_argument("--seconds", type=float, default=3.0,
                   help="capture window length (default 3)")
    p.set_defaults(func=cmd_profile)

    p = sub.add_parser(
        "prof",
        help="continuous host profiler (GET /admin/prof): the always-on "
             "wall-clock flame of a live server — flame tree + hot "
             "frames; --fleet for the member-merged view",
    )
    p.add_argument("--url", default="http://127.0.0.1:8000",
                   help="base URL of any PIO server (sends the "
                        "PIO_ADMIN_TOKEN bearer header when set)")
    p.add_argument("--fleet", action="store_true",
                   help="member-merged profile through the federation "
                        "plane (GET /admin/fleet/prof on the router)")
    p.add_argument("--collapsed", action="store_true",
                   help="emit folded 'stack count' lines for external "
                        "flamegraph tooling")
    p.add_argument("--slow", action="store_true",
                   help="only the above-PIO_SLOW_MS tail cohort's "
                        "samples (also lists their trace ids)")
    p.add_argument("--endpoint", default=None,
                   help="one route's slice, e.g. /queries.json")
    p.add_argument("--top", type=int, default=10,
                   help="hot frames listed under the flame (default 10)")
    p.add_argument("--json", action="store_true",
                   help="dump the raw profile payload")
    p.set_defaults(func=cmd_prof)

    p = sub.add_parser(
        "slo",
        help="SLO burn-rate evaluation (from a server's /admin/slo with "
             "--url, else the in-process registry); exit 1 when firing",
    )
    p.add_argument("--url", default=None,
                   help="base URL of any PIO server, e.g. "
                        "http://127.0.0.1:8000 (sends the "
                        "PIO_ADMIN_TOKEN bearer header when set)")
    p.add_argument("--json", action="store_true",
                   help="dump the raw evaluation report")
    p.set_defaults(func=cmd_slo)

    p = sub.add_parser(
        "chaos",
        help="inspect or toggle fault injection on a live server "
             "(GET/POST /admin/chaos; resilience/chaos.py spec grammar "
             "like storage:latency:50ms,storage:error:0.1)",
    )
    p.add_argument("--url", required=True,
                   help="base URL of any PIO server (sends the "
                        "PIO_ADMIN_TOKEN bearer header when set)")
    p.add_argument("--set", dest="set_spec", default=None, metavar="SPEC",
                   help="replace the active rule set with SPEC "
                        "('' clears everything)")
    p.add_argument("--add", default=None, metavar="SPEC",
                   help="append SPEC's rules to the active set")
    p.add_argument("--clear", nargs="?", const=True, default=None,
                   metavar="SITE",
                   help="drop every rule, or only SITE's")
    p.add_argument("--json", action="store_true",
                   help="dump the raw rule-set JSON")
    p.set_defaults(func=cmd_chaos)

    p = sub.add_parser(
        "fleet",
        help="inspect or control a serving fleet through its router "
             "(GET/POST /admin/fleet; serving/fleet.py): replica "
             "states, rolling hot-swap, drain/readmit",
    )
    p.add_argument("--url", default="http://127.0.0.1:8000",
                   help="base URL of the fleet's router (sends the "
                        "PIO_ADMIN_TOKEN bearer header when set)")
    p.add_argument("--reload", action="store_true",
                   help="start a rolling zero-downtime hot-swap onto "
                        "the newest COMPLETED instance")
    p.add_argument("--drain", default=None, metavar="REPLICA",
                   help="take REPLICA out of rotation")
    p.add_argument("--readmit", default=None, metavar="REPLICA",
                   help="put REPLICA back into rotation (readiness "
                        "probes permitting)")
    p.add_argument("--force", action="store_true",
                   help="with --reload: override the replicas' "
                        "device-memory preflight (a 507-refused swap)")
    p.add_argument("--json", action="store_true",
                   help="dump the raw fleet snapshot JSON")
    p.set_defaults(func=cmd_fleet)

    p = sub.add_parser(
        "mem",
        help="device-memory accounting (obs/memacct.py): per-model "
             "HBM ledger, headroom, train peaks and the OOM-preflight "
             "state (GET /admin/memory)",
    )
    p.add_argument("--url", default=None,
                   help="base URL of any PIO server (sends the "
                        "PIO_ADMIN_TOKEN bearer header when set); "
                        "default: this process's own ledger")
    p.add_argument("--json", action="store_true",
                   help="dump the raw /admin/memory payload")
    p.set_defaults(func=cmd_mem)

    p = sub.add_parser(
        "replay",
        help="re-play captured query payloads (PIO_FLIGHT_PAYLOADS) "
             "against a candidate instance and diff the answers vs the "
             "baseline (workflow/replay.py); report lands on "
             "/admin/quality",
    )
    p.add_argument("--url", required=True,
                   help="base URL of the CANDIDATE server")
    p.add_argument("--baseline", default=None,
                   help="base URL of the baseline server (default: "
                        "--flight-url)")
    p.add_argument("--flight-url", default=None,
                   help="server whose /admin/flight holds the captured "
                        "payloads (default: --baseline; requires "
                        "PIO_ADMIN_TOKEN — payloads only travel under "
                        "the bearer gate)")
    p.add_argument("-n", type=int, default=None,
                   help="replay only the newest N captured payloads")
    p.add_argument("--k", type=int, default=None,
                   help="top-k depth for the overlap diff (default "
                        "PIO_QUALITY_K)")
    p.add_argument("--no-push", action="store_true",
                   help="do not register the report on the baseline's "
                        "/admin/quality")
    p.add_argument("--fail-under", type=float, default=None,
                   help="exit 1 when mean overlap is below this floor")
    p.add_argument("--json", action="store_true",
                   help="dump the raw comparison report")
    p.set_defaults(func=cmd_replay)

    p = sub.add_parser(
        "canary",
        help="inspect or drive the fleet's canary lane through the "
             "router (GET /admin/quality, POST /admin/fleet): paired "
             "answer diffs, per-lane latency burn, promote/rollback",
    )
    p.add_argument("--url", default="http://127.0.0.1:8000",
                   help="base URL of the fleet's router (sends the "
                        "PIO_ADMIN_TOKEN bearer header when set)")
    p.add_argument("--start", action="store_true",
                   help="deploy the newest COMPLETED instance onto one "
                        "replica as the canary")
    p.add_argument("--promote", action="store_true",
                   help="roll the whole fleet onto the candidate")
    p.add_argument("--rollback", action="store_true",
                   help="restore the canary replica to the baseline "
                        "instance")
    p.add_argument("--json", action="store_true",
                   help="dump the raw /admin/quality report")
    p.set_defaults(func=cmd_canary)

    p = sub.add_parser(
        "top",
        help="live terminal view of the metric timelines (MFU, "
             "staleness, serving quantiles, request rate) from a "
             "server's /admin/timeline or the in-process rings",
    )
    p.add_argument("--url", default=None,
                   help="base URL of any PIO server (sends the "
                        "PIO_ADMIN_TOKEN bearer header when set); "
                        "default: this process's own timeline")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh cadence in seconds (default 2)")
    p.add_argument("--once", action="store_true",
                   help="print one frame and exit")
    p.add_argument("--json", action="store_true",
                   help="with --once: dump the raw timeline payload")
    p.add_argument("--fleet", action="store_true",
                   help="drive the view from the router's federated "
                        "GET /admin/fleet/metrics (requires --url): "
                        "fleet-wide merged percentiles, SLO burn and "
                        "a per-member table")
    p.set_defaults(func=cmd_top)

    p = sub.add_parser(
        "journal",
        help="the ops journal: what the system DID and when (reloads, "
             "canary verdicts, breaker flips, shed episodes, anomaly "
             "onsets) — one line per event, newest last",
    )
    p.add_argument("--url", default=None,
                   help="server base URL (default: this process's ring)")
    p.add_argument("--fleet", action="store_true",
                   help="member-merged stream via the router's "
                        "GET /admin/fleet/journal (requires --url)")
    p.add_argument("-n", type=int, default=200,
                   help="events to show (default 200)")
    p.add_argument("--kind", default=None,
                   help="only this event kind (reload, breaker, "
                        "canary_verdict, shed_episode, anomaly, ...)")
    p.add_argument("--since", type=float, default=None,
                   help="unix-seconds floor")
    p.add_argument("--follow", "-f", action="store_true",
                   help="keep polling for new events until interrupted")
    p.add_argument("--interval", type=float, default=2.0,
                   help="--follow poll interval in seconds (default 2)")
    p.add_argument("--json", action="store_true",
                   help="raw JSON (one object per line with --follow)")
    p.set_defaults(func=cmd_journal)

    p = sub.add_parser(
        "anomalies",
        help="the regression sentinel: active metric change-points "
             "attributed to journal events; exit 1 while any is active",
    )
    p.add_argument("--url", default=None,
                   help="server base URL (default: this process's "
                        "sentinel)")
    p.add_argument("--fleet", action="store_true",
                   help="per-member reports + the active union via the "
                        "router's GET /admin/fleet/anomaly (requires "
                        "--url)")
    p.add_argument("--json", action="store_true",
                   help="raw sentinel report")
    p.set_defaults(func=cmd_anomalies)

    p = sub.add_parser(
        "data",
        help="the data & ingest observability plane: ingest rates, "
             "entity heavy hitters + Zipf skew, cardinality, schema "
             "drift, unknown-entity coverage",
    )
    p.add_argument("--url", default=None,
                   help="server base URL (default: this process's "
                        "data plane)")
    p.add_argument("--fleet", action="store_true",
                   help="member-merged report via the router's "
                        "GET /admin/fleet/data (requires --url)")
    p.add_argument("--top", type=int, default=20,
                   help="heavy-hitter rows to show (default 20)")
    p.add_argument("--json", action="store_true",
                   help="raw data-plane report")
    p.set_defaults(func=cmd_data)

    p = sub.add_parser("lint", help="run graftlint (JAX/TPU-aware static "
                                    "analysis, rules JT01-JT23) over the tree")
    p.add_argument("paths", nargs="*", default=[],
                   help="files/dirs (default: the installed package)")
    p.add_argument("--project", action="store_true",
                   help="add the whole-program concurrency pass "
                        "(JT18-JT20: lock discipline, races, deadlocks)")
    p.add_argument("--format", choices=["human", "json"], default="human")
    p.add_argument("--json", action="store_const", const="json",
                   dest="format", help="shorthand for --format json")
    p.add_argument("--list-rules", action="store_true")
    p.set_defaults(func=cmd_lint)

    p_t = sub.add_parser("template", help="list or scaffold templates")
    t_sub = p_t.add_subparsers(dest="template_command", required=True)
    t_sub.add_parser("list")
    p = t_sub.add_parser("get"); p.add_argument("name"); p.add_argument("directory")
    p_t.set_defaults(func=cmd_template)

    return parser


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    # structured logging with trace-id correlation (obs/logging.py):
    # the interactive console stays human-readable unless PIO_LOG_JSON
    # opts in; server subcommands inherit the same handler
    from predictionio_tpu.obs import logging as obs_logging

    obs_logging.setup(
        level=logging.DEBUG if args.verbose else logging.INFO,
        default_json=False,
    )
    try:
        return args.func(args)
    except (CommandError, StorageError, RuntimeError, FileNotFoundError, ValueError) as e:
        # operator errors (bad app name, unconfigured storage, no trained
        # instance, malformed import line / engine.json) exit cleanly
        # like the reference CLI; --verbose restores the traceback so
        # framework bugs surfacing as ValueError/RuntimeError stay
        # diagnosable
        if args.verbose:
            import traceback

            traceback.print_exc()
        print(f"ERROR: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
