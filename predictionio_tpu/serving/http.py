"""Shared HTTP plumbing for the framework's servers.

One copy of the JSON response writer, body reader, bind-retry loop and
thread lifecycle used by the event server, engine server, dashboard and
admin API (the reference gets this from spray; each server here is a
stdlib ThreadingHTTPServer).

Every server also inherits the shared operator surface from the
``_instrument`` wrapper:

  GET  /healthz          liveness (cheap, no probes)
  GET  /readyz           readiness (health probes; 503 on any FAILED)
  GET  /metrics          Prometheus text, or OpenMetrics with
                         exemplars under ``Accept:
                         application/openmetrics-text``
  GET  /admin/flight     flight-recorder dump        } bearer-token
  POST /admin/profile    on-demand profiler window   } guarded when
  GET  /admin/slo        SLO burn-rate evaluation    } PIO_ADMIN_TOKEN
  GET/POST /admin/chaos  fault-injection rule set    } is set
  GET  /admin/resilience breaker/admission/chaos     }
                         snapshot                    }
  GET  /admin/timeline   metric timelines + the      }
                         data-path ledger            }
  GET  /admin/tail       tail-latency attribution    }
                         (above-p95 stage shares)    }
  GET/POST /admin/fleet  replica fleet snapshot /    }
                         rolling-swap + canary       }
                         control (404 on servers     }
                         without a fleet)            }
  GET/POST /admin/quality model-quality report:      }
                         drift gauges' source, last  }
                         replay diff, canary verdict }
  GET  /admin/memory     device-memory accounting:   }
                         per-model HBM ledger,       }
                         headroom, train peaks,      }
                         preflight state             }
  GET  /admin/spans      this process's span ring    }
                         (?trace=&n=; the federation }
                         collector's query surface)  }
  GET  /admin/trace      cross-process stitched      }
                         trace (?id=; obs/collect.py }
                         fans out to the fleet)      }
  GET  /admin/fleet/metrics merged member /metrics   }
                         (counters sum, histograms   }
                         bucket-wise, gauges get a   }
                         member label) + fleet SLO   }
                         burn (404 without a fleet)  }
  GET  /admin/fleet/tail fleet-wide tail attribution }
                         over every member's flight  }
                         recorder (404 w/o a fleet)  }
  GET  /admin/prof       continuous host profiler    }
                         flame (?format=collapsed,   }
                         ?endpoint=, ?slow=1 slices) }
  GET  /admin/fleet/prof member-merged continuous    }
                         profile (404 w/o a fleet)   }
  GET  /admin/journal    ops journal ring (?n=&kind= }
                         &since=): reloads, canary   }
                         verdicts, breaker flips,    }
                         shed episodes, anomalies    }
  GET  /admin/anomaly    regression sentinel report: }
                         active change-points with   }
                         causal attribution to the   }
                         journal + recent resolves   }
  GET  /admin/fleet/journal member-merged journal    }
                         stream (404 w/o a fleet)    }
  GET  /admin/fleet/anomaly per-member sentinel      }
                         reports + active union      }
                         (404 w/o a fleet)           }
  GET  /admin/data       data-plane report (?top=):  }
                         ingest rates, entity heavy  }
                         hitters + Zipf skew, HLL    }
                         cardinality, quantiles,     }
                         schema drift, unknown-      }
                         entity coverage             }
  GET  /admin/fleet/data per-member data reports +   }
                         merged totals (404 w/o a    }
                         fleet)                      }

``/healthz``, ``/readyz`` and ``/metrics`` stay unauthenticated — a
liveness prober or scraper holds no operator secrets; the ``/admin/*``
diagnostics expose request payloads/traces and so require
``Authorization: Bearer $PIO_ADMIN_TOKEN`` once the operator sets it.
"""

from __future__ import annotations

import functools
import hmac
import json
import logging
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional
from urllib.parse import parse_qs, urlparse

from predictionio_tpu.obs import (anomaly, contprof, flight, health,
                                  journal, metrics, perfacct, profiler,
                                  push, slo, timeline, trace)
from predictionio_tpu.resilience import alerts, chaos
from predictionio_tpu.resilience import policy as respolicy

log = logging.getLogger(__name__)

# -- built-in request telemetry (tentpole: every server inherits these) -------

_REQUESTS_TOTAL = metrics.counter(
    "pio_http_requests_total",
    "HTTP requests answered, by server, method, route and status",
    ("server", "method", "route", "status"),
)
_REQUEST_SECONDS = metrics.histogram(
    "pio_http_request_duration_seconds",
    "HTTP request handling wall time (request parsed -> response written)",
    ("server", "method", "route"),
)
_IN_FLIGHT = metrics.gauge(
    "pio_http_requests_in_flight",
    "Requests currently being handled, by server",
    ("server",),
)

#: path segments that are data ids (event/model/scan ids, uuid hexes):
#: collapsed to ":id" so metric label cardinality stays bounded
_ID_SEGMENT = re.compile(r"^[0-9a-fA-F-]{16,}$")

#: hard cap on distinct route labels per process: the real servers have
#: ~25 routes; beyond this, new paths (scanners probing random 404s)
#: collapse to ":other" instead of growing the registry forever
_MAX_ROUTES = 64
_routes_seen: set = set()


def metrics_route(path: str) -> str:
    """A bounded-cardinality route label for a request path."""
    out = []
    for seg in path.split("/"):
        if not seg:
            continue
        stem, dot, ext = seg.rpartition(".")
        base = stem if dot else seg
        if _ID_SEGMENT.match(base) or len(base) > 48:
            seg = ":id" + (dot + ext if dot else "")
        out.append(seg)
    route = "/" + "/".join(out)
    if route in _routes_seen:
        return route
    if len(_routes_seen) < _MAX_ROUTES:  # benign race: cap is approximate
        _routes_seen.add(route)
        return route
    return ":other"


def _admin_authorized(handler) -> bool:
    """Bearer-token gate for the ``/admin/*`` diagnostics: with
    ``PIO_ADMIN_TOKEN`` unset everything stays open (trusted-network
    default, the pre-auth behavior); once set, requests must carry
    ``Authorization: Bearer <token>`` (constant-time compare)."""
    token = os.environ.get("PIO_ADMIN_TOKEN")
    if not token:
        return True
    supplied = handler.headers.get("Authorization") or ""
    return hmac.compare_digest(supplied, f"Bearer {token}")


def _server_storage(server_ref) -> Any:
    """The serving object's storage, wherever the server keeps it (the
    event server nests it inside its core)."""
    storage = getattr(server_ref, "storage", None)
    if storage is None:
        storage = getattr(getattr(server_ref, "core", None), "storage", None)
    return storage


def _serve_readyz(handler) -> None:
    """``GET /readyz``: run the process health probes plus THIS
    server's storage probe; 200 while nothing FAILED (DEGRADED still
    serves — readiness is "can answer", not "is pristine"), 503 with
    the same per-probe detail otherwise. A server may override its
    storage probe via a ``storage_readyz_probe`` method — the engine
    server does, mapping storage loss to DEGRADED (it can still answer
    queries from the last-loaded model)."""
    health.install_default_probes()
    override = getattr(handler.server_ref, "storage_readyz_probe", None)
    if override is not None:
        extra = {"storage": override}
    else:
        storage = _server_storage(handler.server_ref)
        extra = {"storage": lambda: health.storage_probe(storage)}
    overall, detail = health.REGISTRY.run(extra=extra)
    status = 503 if overall == health.FAILED else 200
    handler._send(status, {"status": overall, "probes": detail})


def _serve_metrics(handler, query: str) -> None:
    """``GET /metrics``: Prometheus text by default; the OpenMetrics
    document (counter `_total` families, histogram exemplars, `# EOF`)
    under ``Accept: application/openmetrics-text`` or
    ``?format=openmetrics``."""
    accept = handler.headers.get("Accept") or ""
    fmt = (parse_qs(query).get("format") or [""])[0]
    if "application/openmetrics-text" in accept or fmt == "openmetrics":
        handler._send(200, metrics.REGISTRY.render_openmetrics(),
                      content_type=metrics.OPENMETRICS_CONTENT_TYPE)
    else:
        handler._send(200, metrics.REGISTRY.render(),
                      content_type=metrics.CONTENT_TYPE)


def _serve_admin_flight(handler, query: str) -> None:
    """``GET /admin/flight``: the flight-recorder dump as JSON.
    ``?n=N`` limits to the last N records, ``?slow=1`` keeps only
    slow/errored ones. Captured query payloads (PIO_FLIGHT_PAYLOADS)
    are included only when an admin token is CONFIGURED — the bearer
    gate above then guarantees it was presented; on a token-less
    (trusted-network-default) server the payload bodies stay redacted,
    only the capture counts show."""
    params = parse_qs(query)
    try:
        n = int(params["n"][0]) if "n" in params else None
    except ValueError:
        handler._send(400, {"message": "n must be an integer"})
        return
    slow_only = (params.get("slow") or ["0"])[0].lower() in ("1", "true")
    include_payloads = bool(os.environ.get("PIO_ADMIN_TOKEN"))
    handler._send(200, flight.RECORDER.dump(
        n, slow_only=slow_only, include_payloads=include_payloads))


def _serve_admin_quality(handler) -> None:
    """``GET /admin/quality``: the model-quality report (obs/quality.py
    STATE) — latest drift probe, latest replay comparison, canary
    progress + verdict. ``POST /admin/quality`` with ``{"replay":
    {...}}`` and/or ``{"drift": {...}}`` registers an
    externally-computed report — the ``pio replay`` CLI pushes its
    result here, and a split-deployment ``pio stream`` daemon pushes
    its drift probes to the fleet it patches, so the fleet's one
    quality surface carries both even when measured in another
    process."""
    from predictionio_tpu.obs import quality

    if handler.command == "GET":
        handler._send(200, quality.STATE.report())
        return
    if handler.command != "POST":
        handler._send(405, {"message": "GET or POST"})
        return
    try:
        payload = handler._read_json()
    except json.JSONDecodeError as e:
        handler._send(400, {"message": f"invalid JSON: {e}"})
        return
    registered = []
    if isinstance(payload, dict):
        if isinstance(payload.get("replay"), dict):
            quality.STATE.set_replay(payload["replay"])
            registered.append("replay")
        if isinstance(payload.get("drift"), dict):
            quality.STATE.set_drift(payload["drift"])
            registered.append("drift")
    if not registered:
        handler._send(400, {"message": 'body needs a "replay" and/or '
                                       '"drift" object'})
        return
    handler._send(200, {"message": "registered: " + ", ".join(registered)})


def _serve_admin_profile(handler, query: str) -> None:
    """``POST /admin/profile?seconds=N``: record a JAX profiler window
    of THIS process and answer the artifact path; 501 on CPU backends
    (no device timeline to record), 409 while a capture is running.
    The handler thread sleeps through the window by design — the
    capture is of the OTHER threads doing device work."""
    params = parse_qs(query)
    try:
        seconds = float((params.get("seconds") or ["3"])[0])
    except ValueError:
        handler._send(400, {"message": "seconds must be a number"})
        return
    # echo the EFFECTIVE window (capture clamps a typo'd N): the answer
    # must describe the trace the operator actually holds
    seconds = profiler.clamp_seconds(seconds)
    try:
        artifact = profiler.capture(seconds)
    except profiler.ProfilerUnavailable as e:
        # actionable, not a bare status line: on CPU backends the
        # continuous HOST profiler is the one that has the answer
        handler._send(501, {
            "message": str(e),
            "backend": profiler.backend(),
            "hint": "no device timeline on this backend; the "
                    "continuous host profiler is always on — use "
                    "GET /admin/prof (?format=collapsed, ?endpoint=, "
                    "?slow=1) or `pio prof`",
            "host_profiler": "/admin/prof",
        })
        return
    except profiler.ProfilerBusy as e:
        handler._send(409, {"message": str(e)})
        return
    handler._send(200, {"artifact": artifact, "seconds": seconds,
                        "backend": profiler.backend()})


def _serve_admin_chaos(handler) -> None:
    """``GET /admin/chaos``: the active fault-injection rule set.
    ``POST /admin/chaos``: mutate it — ``{"spec": "..."}`` replaces,
    ``{"add": "..."}`` appends, ``{"clear": true | "site"}`` drops
    (resilience/chaos.py spec grammar). Admin-token-guarded like every
    ``/admin/*`` route: fault injection against a production server is
    an operator action, not a drive-by."""
    if handler.command == "GET":
        handler._send(200, chaos.describe())
        return
    if handler.command != "POST":
        handler._send(405, {"message": "GET or POST"})
        return
    try:
        payload = handler._read_json()
        result = chaos.apply_admin(payload)
    except (json.JSONDecodeError, ValueError) as e:
        handler._send(400, {"message": str(e)})
        return
    handler._send(200, result)


def _serve_admin_timeline(handler) -> None:
    """``GET /admin/timeline``: the bounded metric-timeline rings
    (obs/timeline.py) plus the data-path ledger + staleness clock
    (obs/perfacct.py). The read itself ticks the sampler (rate-limited
    by the cadence), so watching a server builds its history."""
    timeline.TIMELINE.sample()
    payload = timeline.TIMELINE.series()
    payload["datapath"] = perfacct.LEDGER.snapshot()
    handler._send(200, payload)


def _serve_admin_tail(handler, query: str) -> None:
    """``GET /admin/tail``: tail-latency attribution over the flight
    recorder's stage timings — for requests above ``?q=`` (default
    0.95), which stage dominates vs the median request."""
    params = parse_qs(query)
    try:
        q = float((params.get("q") or ["0.95"])[0])
        report = perfacct.tail_report(q=q)
    except ValueError as e:
        handler._send(400, {"message": str(e)})
        return
    handler._send(200, report)


def _serve_admin_spans(handler, query: str) -> None:
    """``GET /admin/spans?trace=<id>&n=N``: THIS process's span ring
    (obs/trace.py) — the federation collector's (obs/collect.py)
    span-query surface, served on every server like ``/metrics``. The
    payload carries the ring capacity (``PIO_SPAN_RING``) and the
    eviction counter so a partial trace comes with its why."""
    from predictionio_tpu.obs import collect

    params = parse_qs(query)
    trace_id = (params.get("trace") or [None])[0]
    if trace_id is not None and not trace.valid_trace_id(trace_id):
        handler._send(400, {"message": "trace must be id-shaped"})
        return
    try:
        n = int(params["n"][0]) if "n" in params else None
    except ValueError:
        handler._send(400, {"message": "n must be an integer"})
        return
    server = handler.server_version.split("/", 1)[0]
    handler._send(200, collect.span_page(server, trace_id, n))


def _serve_admin_trace(handler, query: str) -> None:
    """``GET /admin/trace?id=<trace>``: the CROSS-PROCESS stitched
    trace — this server fans out to its federation members (its fleet's
    replicas, the ACTIVE supervisors of this process, and the
    ``PIO_OBS_MEMBERS`` extras), dedupes and assembles one annotated
    tree (obs/collect.py). ``pio trace <id>`` and the dashboard's
    ``/trace`` view render the same document."""
    from predictionio_tpu.obs import collect

    params = parse_qs(query)
    trace_id = (params.get("id") or params.get("trace") or [None])[0]
    if not trace_id or not trace.valid_trace_id(trace_id):
        handler._send(400, {"message": "need an id-shaped ?id=<trace>"})
        return
    members = collect.default_members(handler.server_ref)
    handler._send(200, collect.stitch_trace(trace_id, members))


def _fleet_federation_members(handler):
    """The member list for the fleet-scoped federations (metrics,
    tail): the supervised fleet's replicas plus configured extras —
    None (-> 404) on a server with neither, mirroring /admin/fleet."""
    from predictionio_tpu.obs import collect

    fleet = getattr(handler.server_ref, "fleet", None)
    members = collect.fleet_members(fleet) + collect.env_members()
    # first occurrence wins (same contract as collect.default_members):
    # a replica ALSO listed in PIO_OBS_MEMBERS must not be scraped
    # twice — the merge would double-sum its counters and buckets
    seen: set = set()
    deduped = []
    for m in members:
        key = (m.name, m.url)
        if m.name in seen or m.url in seen:
            continue
        seen.update(key)
        deduped.append(m)
    return deduped or None


def _serve_fleet_metrics(handler, query: str) -> None:
    """``GET /admin/fleet/metrics``: the members' /metrics snapshots
    merged (counters sum, histograms bucket-wise, gauges keep a
    ``member`` label) + the fleet-level SLO burn over the merged
    serving histogram. ``?format=prom`` answers the merged document in
    Prometheus text form for a fleet-level scraper; default is the
    JSON report. A member mid-restart degrades the merge, never fails
    it."""
    from predictionio_tpu.obs import collect

    members = _fleet_federation_members(handler)
    if members is None:
        handler._send(404, {"message": "no fleet supervised by this "
                                       "server and no PIO_OBS_MEMBERS "
                                       "configured"})
        return
    report = collect.federate_metrics(members)
    merged = report.pop("_merged")
    fmt = (parse_qs(query).get("format") or [""])[0]
    if fmt in ("prom", "prometheus", "text"):
        handler._send(200, collect.render_merged(merged),
                      content_type=metrics.CONTENT_TYPE)
        return
    handler._send(200, report)


def _serve_fleet_tail(handler, query: str) -> None:
    """``GET /admin/fleet/tail?q=``: tail attribution over the WHOLE
    fleet's flight recorders — the members' stage timings merged
    through the same perfacct.tail_report a single process serves at
    /admin/tail, plus the per-member tail split."""
    from predictionio_tpu.obs import collect

    members = _fleet_federation_members(handler)
    if members is None:
        handler._send(404, {"message": "no fleet supervised by this "
                                       "server and no PIO_OBS_MEMBERS "
                                       "configured"})
        return
    params = parse_qs(query)
    try:
        q = float((params.get("q") or ["0.95"])[0])
        n = int(params["n"][0]) if "n" in params else None
        report = collect.federate_tail(members, q=q, n=n)
    except ValueError as e:
        handler._send(400, {"message": str(e)})
        return
    handler._send(200, report)


def _parse_prof_slices(query: str):
    """Shared ?slow=1 / ?endpoint= / ?format= parsing for the local and
    fleet profile routes."""
    params = parse_qs(query)
    slow = (params.get("slow") or ["0"])[0].lower() in ("1", "true")
    endpoint = (params.get("endpoint") or [None])[0]
    fmt = (params.get("format") or [""])[0]
    return slow, endpoint, fmt


def _serve_admin_prof(handler, query: str) -> None:
    """``GET /admin/prof``: the continuous host profiler's aggregated
    flame (obs/contprof.py) — the answer ``POST /admin/profile`` cannot
    give on CPU backends. ``?format=collapsed`` emits folded
    ``stack count`` lines for external flamegraph tools; ``?endpoint=``
    slices one route's trie; ``?slow=1`` the above-``PIO_SLOW_MS`` tail
    cohort, whose payload also names the slow requests' trace ids (they
    join against the flight recorder's slow ring)."""
    slow, endpoint, fmt = _parse_prof_slices(query)
    payload = contprof.snapshot(endpoint=endpoint, slow=slow)
    if fmt == "collapsed":
        handler._send(200, contprof.collapsed_text(payload),
                      content_type="text/plain; charset=UTF-8")
        return
    handler._send(200, payload)


def _serve_fleet_prof(handler, query: str) -> None:
    """``GET /admin/fleet/prof``: the members' continuous profiles
    member-merged through the federation plane (obs/collect.py) —
    folded stacks summed, per-member sample counts and errors
    annotated; a dead member degrades the merge, never fails it. Same
    ``?slow=1`` / ``?endpoint=`` / ``?format=collapsed`` slices as the
    single-process route."""
    from predictionio_tpu.obs import collect

    members = _fleet_federation_members(handler)
    if members is None:
        handler._send(404, {"message": "no fleet supervised by this "
                                       "server and no PIO_OBS_MEMBERS "
                                       "configured"})
        return
    slow, endpoint, fmt = _parse_prof_slices(query)
    report = collect.federate_prof(members, endpoint=endpoint, slow=slow)
    if fmt == "collapsed":
        handler._send(200, contprof.collapsed_text(report["merged"]),
                      content_type="text/plain; charset=UTF-8")
        return
    handler._send(200, report)


def _serve_admin_journal(handler, query: str) -> None:
    """``GET /admin/journal?n=&kind=&since=``: this process's ops
    journal ring, newest last — reloads, canary verdicts, breaker
    flips, shed episodes, anomaly onsets (obs/journal.py). ``kind``
    filters one event kind exactly; ``since`` is a unix-seconds floor;
    ``n`` caps the page (default 200)."""
    params = parse_qs(query)
    try:
        n = int((params.get("n") or ["200"])[0])
        since = float(params["since"][0]) if "since" in params else None
    except ValueError as e:
        handler._send(400, {"message": f"bad n/since: {e}"})
        return
    kind = (params.get("kind") or [None])[0]
    handler._send(200, journal.JOURNAL.page(n=n, kind=kind, since=since))


def _serve_fleet_journal(handler, query: str) -> None:
    """``GET /admin/fleet/journal``: the members' journals merged into
    one member-annotated, time-ordered stream (same ?n=&kind=&since=
    slices); a dead member degrades the merge, never fails it."""
    from predictionio_tpu.obs import collect

    members = _fleet_federation_members(handler)
    if members is None:
        handler._send(404, {"message": "no fleet supervised by this "
                                       "server and no PIO_OBS_MEMBERS "
                                       "configured"})
        return
    params = parse_qs(query)
    try:
        n = int((params.get("n") or ["200"])[0])
        since = float(params["since"][0]) if "since" in params else None
    except ValueError as e:
        handler._send(400, {"message": f"bad n/since: {e}"})
        return
    kind = (params.get("kind") or [None])[0]
    handler._send(200, collect.federate_journal(members, n=n, kind=kind,
                                                since=since))


def _serve_admin_anomaly(handler) -> None:
    """``GET /admin/anomaly``: the regression sentinel's report —
    active change-points per timeline series (direction, z, CUSUM,
    onset, the journal event each is attributed to) plus recently
    resolved episodes (obs/anomaly.py). The read itself scans, so an
    idle server still verdicts while someone is watching."""
    handler._send(200, anomaly.SENTINEL.scan())


def _serve_fleet_anomaly(handler) -> None:
    """``GET /admin/fleet/anomaly``: every member's sentinel report
    side by side + the union of active anomalies (a regression on ANY
    replica is a fleet regression)."""
    from predictionio_tpu.obs import collect

    members = _fleet_federation_members(handler)
    if members is None:
        handler._send(404, {"message": "no fleet supervised by this "
                                       "server and no PIO_OBS_MEMBERS "
                                       "configured"})
        return
    handler._send(200, collect.federate_anomaly(members))


def _serve_admin_data(handler, query: str) -> None:
    """``GET /admin/data``: the data plane's report (obs/dataobs.py) —
    ingest rates per (app, event), entity heavy hitters with the
    fitted Zipf skew, HLL cardinalities, payload/value/inter-arrival
    quantiles, the live-vs-frozen schema diff and the unknown-entity
    coverage ratio. ``?top=`` sizes the heavy-hitter table."""
    from predictionio_tpu.obs import dataobs

    params = parse_qs(query)
    try:
        top = int((params.get("top") or ["20"])[0])
    except ValueError as e:
        handler._send(400, {"message": f"bad top: {e}"})
        return
    handler._send(200, dataobs.DATAOBS.report(top_n=top))


def _serve_fleet_data(handler) -> None:
    """``GET /admin/fleet/data``: every member's data-plane report side
    by side plus fleet-merged totals (summed counters, max skew, the
    union of schema changes); a dead member degrades, never fails."""
    from predictionio_tpu.obs import collect

    members = _fleet_federation_members(handler)
    if members is None:
        handler._send(404, {"message": "no fleet supervised by this "
                                       "server and no PIO_OBS_MEMBERS "
                                       "configured"})
        return
    handler._send(200, collect.federate_data(members))


def _serve_admin_fleet(handler) -> None:
    """``GET /admin/fleet``: the replica fleet's snapshot (states,
    versions, restart counts, swap progress). ``POST /admin/fleet``:
    control — ``{"reload": true}`` starts a rolling zero-downtime
    hot-swap, ``{"drain"|"readmit": "<replica>"}`` takes a replica out
    of / back into rotation. 404 on servers that supervise no fleet."""
    fleet = getattr(handler.server_ref, "fleet", None)
    if fleet is None:
        handler._send(404, {"message": "no fleet supervised by this "
                                       "server"})
        return
    if handler.command == "GET":
        handler._send(200, fleet.snapshot())
        return
    if handler.command != "POST":
        handler._send(405, {"message": "GET or POST"})
        return
    try:
        result = fleet.apply_admin(handler._read_json())
    except (json.JSONDecodeError, ValueError) as e:
        handler._send(400, {"message": str(e)})
        return
    if "started" in result:
        # mirror the router's GET /reload: 202 on a freshly started
        # swap, 409 when one is already running (a 200 here read as
        # "done" to callers probing either route)
        handler._send(202 if result["started"] else 409, result)
        return
    handler._send(200, result)


def _instrument(fn):
    """Wrap a do_METHOD handler: serve the shared routes (``GET
    /metrics``, ``GET /admin/flight``, ``POST /admin/profile``),
    activate the request's trace context (minting or accepting an
    ``X-PIO-Trace-Id``), open a flight-recorder record, and record the
    built-in request metrics. Applied once to every handler subclass
    via ``__init_subclass__`` — servers inherit all of it without
    touching their routing code."""
    if getattr(fn, "_pio_instrumented", False):
        return fn

    @functools.wraps(fn)
    def wrapper(self):
        parsed = urlparse(self.path)
        path = parsed.path
        server = self.server_version.split("/", 1)[0]
        # shared operator routes: before any per-server auth (a
        # scraper/diagnoser holds no storage keys) and outside their
        # own request counts, traces and flight records
        if self.command == "GET" and path == "/healthz":
            # liveness: no probes, no locks beyond _send — a wedged
            # process fails this by not answering, nothing else does
            self._send(200, {"status": "alive"})
            return
        if self.command == "GET" and path == "/readyz":
            _serve_readyz(self)
            return
        if self.command == "GET" and path == "/metrics":
            _serve_metrics(self, parsed.query)
            return
        if path.startswith("/admin/"):
            # diagnostics expose payloads and traces: bearer-gated once
            # PIO_ADMIN_TOKEN is set (liveness/metrics stay open above)
            if not _admin_authorized(self):
                self._send(401, {"message": "missing or invalid bearer "
                                            "token (PIO_ADMIN_TOKEN)"},
                           extra_headers={"WWW-Authenticate": "Bearer"})
                return
            if self.command == "GET" and path == "/admin/flight":
                _serve_admin_flight(self, parsed.query)
                return
            if self.command == "POST" and path == "/admin/profile":
                _serve_admin_profile(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/slo":
                self._send(200, slo.MONITOR.report())
                return
            if path == "/admin/chaos":
                _serve_admin_chaos(self)
                return
            if self.command == "GET" and path == "/admin/timeline":
                _serve_admin_timeline(self)
                return
            if self.command == "GET" and path == "/admin/tail":
                _serve_admin_tail(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/spans":
                _serve_admin_spans(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/trace":
                _serve_admin_trace(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/fleet/metrics":
                _serve_fleet_metrics(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/fleet/tail":
                _serve_fleet_tail(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/prof":
                _serve_admin_prof(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/fleet/prof":
                _serve_fleet_prof(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/journal":
                _serve_admin_journal(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/anomaly":
                _serve_admin_anomaly(self)
                return
            if self.command == "GET" and path == "/admin/fleet/journal":
                _serve_fleet_journal(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/fleet/anomaly":
                _serve_fleet_anomaly(self)
                return
            if self.command == "GET" and path == "/admin/data":
                _serve_admin_data(self, parsed.query)
                return
            if self.command == "GET" and path == "/admin/fleet/data":
                _serve_fleet_data(self)
                return
            if path == "/admin/fleet":
                _serve_admin_fleet(self)
                return
            if path == "/admin/quality":
                _serve_admin_quality(self)
                return
            if self.command == "GET" and path == "/admin/memory":
                # device-memory accounting plane (obs/memacct.py):
                # per-model ledger attribution, headroom + basis,
                # train peaks and the last preflight decision
                from predictionio_tpu.obs import memacct

                self._send(200, memacct.report())
                return
            if self.command == "GET" and path == "/admin/resilience":
                # breaker states + admission snapshot (when the server
                # has one) + active chaos: the one-stop degraded-mode
                # diagnosis surface
                admission = getattr(self.server_ref, "admission", None)
                self._send(200, {
                    "circuits": respolicy.breakers_snapshot(),
                    "admission": (admission.snapshot()
                                  if admission is not None else None),
                    "chaos": chaos.describe(),
                })
                return
        # the inbound id is untrusted: anything not id-shaped (header
        # injection attempts, oversized strings) is re-minted, never
        # echoed into response headers or span logs
        raw_id = self.headers.get(trace.TRACE_HEADER, "")
        accepted = trace.valid_trace_id(raw_id)
        trace_id = raw_id if accepted else trace.new_trace_id()
        # cross-process parenting (obs/collect.py stitching): the
        # caller's span id rides X-PIO-Parent-Span; this edge's span
        # parents to it so the per-process rings assemble into ONE
        # tree. Only honored beside an ACCEPTED trace id — a parent
        # with no trace is noise, same shape discipline as the id.
        raw_parent = self.headers.get(trace.PARENT_HEADER, "")
        parent_span = raw_parent if (
            accepted and trace.valid_span_id(raw_parent)) else None
        token = trace.activate(trace_id, parent_span)
        route = metrics_route(path)
        fkey = flight.begin(trace_id, server, self.command, route)
        # register this handler thread with the continuous profiler:
        # samples taken during the request carry its trace id + route
        # (per-endpoint and slow-cohort flame slices)
        contprof.request_begin(trace_id, route)
        inflight = _IN_FLIGHT.labels(server)
        inflight.inc()
        t0 = time.perf_counter()
        name = server.lower()
        name = name.removeprefix("pio") or name
        error: Optional[str] = None
        try:
            # server= stamps the owning process on the edge span: the
            # trace collector attributes every descendant span to the
            # nearest ancestor edge's server (a shared-ring threaded
            # fleet cannot attribute by which member answered). On the
            # device trace's clock the same span is pio:http.request:
            # request line and headers read -> response written
            with trace.span(f"http.{name}", device="http.request",
                            method=self.command, route=route, server=name):
                fn(self)
        except BaseException as e:
            # an exception ESCAPING a handler (their own except blocks
            # already answered anything they understood) is exactly the
            # evidence the flight recorder exists for
            error = f"{type(e).__name__}: {e}"
            raise
        finally:
            # the request's bookkeeping after its answer is written: the
            # connection's next request waits behind it
            with trace.device_span("http.finish"):
                inflight.dec()
                status = getattr(self, "_metrics_status", None)
                # the dominant host frame the sampler observed during this
                # request's window stamps the record BEFORE it seals, so a
                # slow record names code, not just stages
                dominant = contprof.request_end()
                if dominant is not None:
                    flight.note_field("dominant_frame", dominant)
                # seal the flight record while the trace is still active so
                # the slow-request log line carries the trace id
                flight.finish(fkey, status, error)
                trace.deactivate(token)
                if status is not None:
                    _REQUESTS_TOTAL.labels(server, self.command, route,
                                           str(status)).inc()
                    # the trace id rides along as an OpenMetrics exemplar:
                    # a collector can jump from a latency bucket straight
                    # to this request's trace
                    _REQUEST_SECONDS.labels(
                        server, self.command, route).observe(
                            time.perf_counter() - t0,
                            exemplar={"trace_id": trace_id})

    wrapper._pio_instrumented = True
    return wrapper


class JSONRequestHandler(BaseHTTPRequestHandler):
    """Base handler: JSON responses, body parsing, quiet logging."""

    server_version = "PIOServer/0.1"
    server_ref: Any = None  # set via subclass attribute by each server
    # HTTP/1.1 keep-alive: every response carries Content-Length via
    # _send (which also drains unread request bodies), so persistent
    # connections are safe — serving clients skip per-request TCP setup.
    # Idle connections release their handler thread after `timeout`.
    protocol_version = "HTTP/1.1"
    timeout = 120
    # TCP_NODELAY (socketserver.StreamRequestHandler knob): without it,
    # Nagle + the client's delayed ACK add a flat ~40ms to every small
    # request/response pair — 4x the entire serving latency budget
    # (BASELINE north-star: p50 < 10ms)
    disable_nagle_algorithm = True

    def __init_subclass__(cls, **kwargs):
        # telemetry is attached HERE, once: any subclass's do_* routing
        # methods are wrapped with the /metrics route, trace-context
        # activation and request metrics — the event server, engine
        # server, storage server, dashboard and admin API inherit the
        # whole observability surface without per-server wiring
        super().__init_subclass__(**kwargs)
        for mname in ("do_GET", "do_POST", "do_PUT", "do_DELETE"):
            fn = cls.__dict__.get(mname)
            if fn is not None:
                setattr(cls, mname, _instrument(fn))

    def log_message(self, fmt, *args):
        log.debug("%s: " + fmt, self.server_version, *args)

    def handle_one_request(self):
        # per-request state: the handler object lives for a whole
        # keep-alive connection, and routes that stream their response
        # without _send (NDJSON finds, scan fetches) would otherwise
        # leave a stale True that makes the NEXT request's drain guard
        # skip an unread body and desynchronize the connection
        self._body_consumed = False
        self._metrics_status = None  # captured by send_response
        super().handle_one_request()

    def send_response(self, code, message=None):
        # every response path (including streamed NDJSON/scan bodies
        # that never go through _send) funnels through here — the one
        # place the final status is always known for request metrics
        self._metrics_status = code
        super().send_response(code, message)

    def _send(self, status: int, body: Any,
              content_type: str = "application/json; charset=UTF-8",
              extra_headers: Optional[dict] = None) -> None:
        with trace.device_span("http.respond"):
            t_ser = time.perf_counter()
            if isinstance(body, bytes):
                data = body
            elif isinstance(body, str):
                data = body.encode()
            else:
                data = json.dumps(body).encode()
            # Consume any unread request body before responding: under
            # HTTP/1.1 keep-alive an unread body desynchronizes the
            # connection — the next request would be parsed from leftover
            # body bytes (matters for short-circuit responses: auth denial,
            # unknown route). Cheap no-op when the handler already read it.
            # Oversized undrained bodies (> 1 MB — only short-circuit paths
            # leave bodies unread) and chunked request bodies (no length to
            # drain by) close the connection instead.
            try:
                unread = int(self.headers.get("Content-Length") or 0)
            except (TypeError, ValueError):
                unread = 0
            if not getattr(self, "_body_consumed", False):
                if self.headers.get("Transfer-Encoding"):
                    self.close_connection = True
                elif unread > (1 << 20):
                    self.close_connection = True
                elif unread:
                    self.rfile.read(unread)
            self._body_consumed = True  # this request's body is settled
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(data)))
            trace_id = trace.current_trace_id()
            if trace_id:
                # echo the request's trace id so clients can join their logs
                self.send_header(trace.TRACE_HEADER, trace_id)
            if self.close_connection:
                self.send_header("Connection", "close")
            for name, value in (extra_headers or {}).items():
                self.send_header(name, value)
            # headers and body leave in ONE write (one sendall through the
            # unbuffered wfile): end_headers() would flush the headers in a
            # system call of their own, and a system call is a release of
            # the interpreter that a handler gets back behind the others
            # (PERF.md §6, PR 38). The bytes are the same. An HTTP/0.9
            # request line gets no headers, as in end_headers().
            if self.request_version == "HTTP/0.9":
                self.wfile.write(data)
            else:
                self._headers_buffer += (b"\r\n", data)
                self.flush_headers()
            # response encode+write billed to the request's flight record
            # (no-op when no record is open, e.g. the shared /metrics route)
            flight.note_stage("serialize", time.perf_counter() - t_ser)

    def _read_body(self) -> bytes:
        t0 = time.perf_counter()
        length = int(self.headers.get("Content-Length", 0))
        self._body_consumed = True
        data = self.rfile.read(length) if length else b""
        flight.note_stage("parse", time.perf_counter() - t0)
        return data

    def _read_json(self) -> Any:
        """Parsed JSON body; raises json.JSONDecodeError."""
        with trace.device_span("http.parse"):
            return json.loads(self._read_body() or b"{}")

    def _do_get_fallback(self):
        self._send(404, {"message": "Not Found"})

    def _do_post_fallback(self):
        self._send(404, {"message": "Not Found"})

    # servers that define no do_GET/do_POST of their own still expose
    # the shared routes (/metrics, /admin/flight, /admin/profile —
    # served by the _instrument wrapper) and 404 everything else
    do_GET = _instrument(_do_get_fallback)
    do_POST = _instrument(_do_post_fallback)


class _ThreadingHTTPServer(ThreadingHTTPServer):
    # the stdlib default backlog of 5 drops connections under serving
    # bursts (micro-batched engines legitimately queue dozens)
    request_queue_size = 128


class HTTPServerBase:
    """Bind (with retry), run on a daemon thread, stop cleanly.

    Bind-retry contract from the reference engine server
    (CreateServer.scala:340-350): ``bind_retries`` attempts, 1s apart.
    """

    def __init__(self, host: str, port: int, handler_cls: type,
                 bind_retries: int = 1):
        # the in-flight gauge's label for THIS server class — drain
        # derives it the same way _instrument does, so a rename cannot
        # silently point the drain wait at an untouched child
        self._server_label = handler_cls.server_version.split("/", 1)[0]
        handler = type("Handler", (handler_cls,), {"server_ref": self})
        attempts = max(1, bind_retries)
        for attempt in range(attempts):
            try:
                self.httpd = _ThreadingHTTPServer((host, port), handler)
                break
            except OSError as e:
                log.warning("bind attempt %d failed: %s", attempt + 1, e)
                if attempt + 1 == attempts:
                    raise
                time.sleep(1)
        self._thread: Optional[threading.Thread] = None
        self._serving = False
        # this instance's hold on the process-global continuous
        # profiler: retained on start, released exactly once on stop
        # (drain_stop -> stop must not double-release the refcount)
        self._prof_owner = f"{type(self).__name__}:{id(self):#x}"
        self._prof_retained = False

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    @staticmethod
    def _start_env_services() -> None:
        """Env-driven process services every server boot wires up:
        the metrics pusher, the SLO alert webhook sink, declarative
        SLO objectives, and the chaos harness (all no-ops without
        their env vars)."""
        push.start_from_env()
        alerts.start_from_env()
        slo.configure_from_env()
        chaos.configure_from_env()

    def _retain_profiler(self) -> None:
        """Hold the continuous profiler while this server serves —
        refcounted and idempotent in contprof, so multi-server
        processes share ONE sampler and a /reload (stop + start of the
        same instance) never leaves a second one behind."""
        if not self._prof_retained:
            self._prof_retained = True
            contprof.retain(self._prof_owner)

    def _release_profiler(self) -> None:
        if self._prof_retained:
            self._prof_retained = False
            contprof.release(self._prof_owner)

    def start(self):
        # flag set BEFORE the thread is scheduled so a stop() racing
        # start() still runs shutdown() (which blocks until the serve
        # loop has run and exited) instead of closing the socket under it
        self._serving = True
        self._start_env_services()
        self._retain_profiler()
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._thread.start()
        log.info("%s listening on %s", type(self).__name__, self.port)
        return self

    def serve_forever(self) -> None:
        self._serving = True
        self._start_env_services()
        self._retain_profiler()
        self.httpd.serve_forever()

    def stop(self) -> None:
        """Stop serving and close the socket; the port is free on return.

        Safe from handler threads (they are daemons, so server_close
        does not join them) and from threads that never started serving.
        """
        if self._serving:
            self.httpd.shutdown()
            self._serving = False
        self.httpd.server_close()
        self._release_profiler()

    def inflight_count(self) -> float:
        """Requests currently inside handlers of THIS server class
        (shared-process caveat: the gauge is labeled per server CLASS,
        so two same-class servers in one process read a joint count —
        the drain then waits for both, which errs safe)."""
        family = metrics.REGISTRY.get("pio_http_requests_in_flight")
        if family is None:
            return 0.0
        return family.labels(self._server_label).value

    def drain_stop(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop ACCEPTING first (serve loop halted,
        listening socket closed so new connections are refused instead
        of rotting in the backlog), then wait — bounded by ``timeout``
        (default ``PIO_DRAIN_TIMEOUT``, 30s) — for in-flight handlers
        to write their responses, then ``stop()`` (which also stops
        per-server subsystems, e.g. the engine server's batcher).
        Returns True when everything drained inside the window."""
        if timeout is None:
            timeout = drain_timeout()
        if self._serving:
            self.httpd.shutdown()
            self._serving = False
        self.httpd.server_close()
        deadline = time.monotonic() + max(0.0, timeout)
        while self.inflight_count() > 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        leftover = int(self.inflight_count())
        if leftover:
            log.warning(
                "%s drain window (%.1fs) expired with %d request(s) "
                "still in flight — stopping anyway", type(self).__name__,
                timeout, leftover)
        self.stop()
        return leftover == 0


DEFAULT_DRAIN_TIMEOUT_SEC = 30.0


def drain_timeout() -> float:
    """The SIGTERM drain window (``PIO_DRAIN_TIMEOUT`` seconds)."""
    return max(0.0, metrics.env_float("PIO_DRAIN_TIMEOUT",
                                      DEFAULT_DRAIN_TIMEOUT_SEC))


def install_drain_handler(*servers, timeout: Optional[float] = None):
    """SIGTERM -> drain-then-stop for every server of this process.

    The one graceful-shutdown path shared by the engine, event and
    storage server mains (previously a kill mid-request dropped the
    connection on the floor): on SIGTERM each server stops accepting,
    finishes what it already admitted (bounded by ``PIO_DRAIN_TIMEOUT``)
    and stops — after which ``serve_forever`` returns and the main
    exits normally. The drain runs on its OWN NON-daemon thread, and
    both properties are load-bearing: the signal fires in the main
    thread — usually the one blocked inside ``serve_forever`` — so
    calling ``shutdown()`` there would deadlock waiting for a serve
    loop that cannot advance under the handler; and the very first
    thing ``drain_stop`` does is unblock that ``serve_forever``, after
    which the main returns and the interpreter starts exiting — a
    DAEMON drain thread (and the daemon handler threads still writing
    responses) would be killed mid-drain, dropping exactly the
    connections this handler exists to protect. Non-daemon, the
    interpreter waits for the drain to finish before finalizing.

    Returns the installed handler so tests can invoke it directly
    (``handler()``) without delivering a real signal. Must be called
    from the main thread (CPython signal contract)."""
    import signal

    def _drain(signum=None, frame=None):
        def run():
            log.info("SIGTERM: draining %d server(s), window %.1fs",
                     len(servers),
                     drain_timeout() if timeout is None else timeout)
            for server in servers:
                try:
                    server.drain_stop(timeout)
                except Exception:  # noqa: BLE001 — one server's failed
                    # drain must not strand its siblings un-stopped
                    log.exception("drain failed for %r", server)

        # non-daemon: holds the interpreter open until the drain
        # completes (see docstring) — bounded by drain_stop's window
        threading.Thread(target=run, daemon=False,
                         name="pio-drain").start()

    signal.signal(signal.SIGTERM, _drain)
    return _drain
