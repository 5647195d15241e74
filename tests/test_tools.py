"""Tools layer tests: commands, CLI, import/export, admin API, dashboard.

Reference coverage model: tools/src/test/.../admin/AdminAPISpec.scala
(route-level) plus console behaviors asserted in App.scala/AccessKey.scala
docstrings (SURVEY.md §2.7).
"""

import datetime as dt
import json
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.metadata import EvaluationInstance
from predictionio_tpu.tools import commands, eventdata
from predictionio_tpu.tools.admin import AdminServer
from predictionio_tpu.tools.cli import main as cli_main
from predictionio_tpu.tools.commands import CommandError
from predictionio_tpu.tools.dashboard import DashboardServer

UTC = dt.timezone.utc


def http(method, url, body=None):
    req = urllib.request.Request(
        url, method=method,
        data=None if body is None else json.dumps(body).encode(),
    )
    try:
        with urllib.request.urlopen(req) as resp:
            raw = resp.read()
            return resp.status, json.loads(raw) if raw.startswith(b"{") or raw.startswith(b"[") else raw
    except urllib.error.HTTPError as e:
        raw = e.read()
        return e.code, json.loads(raw) if raw else {}


class TestCommands:
    def test_app_lifecycle(self, memory_storage):
        info = commands.app_new("myapp", "desc", memory_storage)
        assert info.app.name == "myapp"
        assert len(info.access_keys) == 1
        assert len(info.access_keys[0].key) == 64
        # duplicate name rejected (ref: App.scala:37)
        with pytest.raises(CommandError):
            commands.app_new("myapp", storage=memory_storage)
        assert [i.app.name for i in commands.app_list(memory_storage)] == ["myapp"]
        # event store was initialized: inserts work
        memory_storage.events().insert(
            Event(event="e", entity_type="user", entity_id="u"), info.app.id)
        commands.app_delete("myapp", memory_storage)
        assert commands.app_list(memory_storage) == []
        with pytest.raises(CommandError):
            commands.app_show("myapp", memory_storage)

    def test_app_data_delete(self, memory_storage):
        info = commands.app_new("a1", storage=memory_storage)
        memory_storage.events().insert(
            Event(event="e", entity_type="user", entity_id="u"), info.app.id)
        assert len(memory_storage.events().find(info.app.id)) == 1
        commands.app_data_delete("a1", storage=memory_storage)
        assert memory_storage.events().find(info.app.id) == []

    def test_channels(self, memory_storage):
        info = commands.app_new("capp", storage=memory_storage)
        ch = commands.channel_new("capp", "mobile", memory_storage)
        assert ch.name == "mobile"
        with pytest.raises(CommandError):
            commands.channel_new("capp", "mobile", memory_storage)
        memory_storage.events().insert(
            Event(event="e", entity_type="user", entity_id="u"), info.app.id, ch.id)
        assert len(memory_storage.events().find(info.app.id, channel_id=ch.id)) == 1
        commands.app_data_delete("capp", "mobile", memory_storage)
        assert memory_storage.events().find(info.app.id, channel_id=ch.id) == []
        commands.channel_delete("capp", "mobile", memory_storage)
        assert commands.app_show("capp", memory_storage).channels == []

    def test_accesskeys(self, memory_storage):
        commands.app_new("kapp", storage=memory_storage)
        key = commands.accesskey_new("kapp", ["rate", "buy"], memory_storage)
        assert sorted(key.events) == ["buy", "rate"]
        keys = commands.accesskey_list("kapp", memory_storage)
        assert len(keys) == 2  # default + new
        commands.accesskey_delete(key.key, memory_storage)
        assert len(commands.accesskey_list("kapp", memory_storage)) == 1
        with pytest.raises(CommandError):
            commands.accesskey_delete("nope", memory_storage)

    def test_status(self, memory_storage):
        assert commands.status(memory_storage) == {
            "METADATA": True, "EVENTDATA": True, "MODELDATA": True}


class TestImportExport:
    def test_round_trip(self, memory_storage, tmp_path):
        info = commands.app_new("ioapp", storage=memory_storage)
        for n in range(5):
            memory_storage.events().insert(
                Event(event="rate", entity_type="user", entity_id=f"u{n}",
                      target_entity_type="item", target_entity_id="i1",
                      properties={"rating": n},
                      event_time=dt.datetime(2026, 1, 1, 0, n, tzinfo=UTC)),
                info.app.id)
        out = tmp_path / "events.jsonl"
        assert eventdata.export_events("ioapp", str(out), storage=memory_storage) == 5
        assert len(out.read_text().strip().splitlines()) == 5

        commands.app_new("ioapp2", storage=memory_storage)
        assert eventdata.import_events("ioapp2", str(out), storage=memory_storage) == 5
        app2 = memory_storage.apps().get_by_name("ioapp2")
        events = memory_storage.events().find(app2.id)
        assert {e.entity_id for e in events} == {f"u{n}" for n in range(5)}

    def test_parquet_round_trip(self, memory_storage, tmp_path):
        info = commands.app_new("pqapp", storage=memory_storage)
        for n in range(4):
            memory_storage.events().insert(
                Event(event="rate", entity_type="user", entity_id=f"u{n}",
                      target_entity_type="item", target_entity_id="i1",
                      properties={"rating": float(n), "tags_test": ["a", "b"]},
                      tags=("t1", "t2"),
                      event_time=dt.datetime(2026, 1, 1, 0, n, tzinfo=UTC)),
                info.app.id)
        # no target / no properties event too
        memory_storage.events().insert(
            Event(event="$set", entity_type="user", entity_id="u9",
                  properties={"plan": "pro"},
                  event_time=dt.datetime(2026, 1, 2, tzinfo=UTC)),
            info.app.id)
        out = tmp_path / "events.parquet"
        assert eventdata.export_events("pqapp", str(out), storage=memory_storage) == 5

        commands.app_new("pqapp2", storage=memory_storage)
        assert eventdata.import_events("pqapp2", str(out), storage=memory_storage) == 5
        app2 = memory_storage.apps().get_by_name("pqapp2")
        events = {e.entity_id: e for e in memory_storage.events().find(app2.id)}
        assert events["u2"].properties.get("rating") == 2.0
        assert events["u2"].properties.get("tags_test") == ["a", "b"]
        assert events["u2"].tags == ("t1", "t2")
        assert events["u9"].event == "$set"
        assert events["u9"].target_entity_type is None
        assert events["u9"].event_time == dt.datetime(2026, 1, 2, tzinfo=UTC)

    def test_import_invalid_line(self, memory_storage, tmp_path):
        commands.app_new("bad", storage=memory_storage)
        f = tmp_path / "bad.jsonl"
        f.write_text('{"event": "e", "entityType": "user", "entityId": "u"}\n'
                     '{"event": "$set"}\n')
        with pytest.raises(ValueError, match="bad.jsonl:2"):
            eventdata.import_events("bad", str(f), storage=memory_storage)


_RUN_ARGS = None


def _run_target(argv):
    global _RUN_ARGS
    _RUN_ARGS = list(argv)
    return 0


class TestCLI:
    def test_app_and_template_commands(self, memory_storage, tmp_path, capsys):
        assert cli_main(["app", "new", "cliapp"]) == 0
        out = capsys.readouterr().out
        assert "Access Key:" in out
        assert cli_main(["app", "list"]) == 0
        assert cli_main(["status"]) == 0
        # duplicate app -> exit 1 with error message
        assert cli_main(["app", "new", "cliapp"]) == 1
        assert "already exists" in capsys.readouterr().err
        # template scaffold
        assert cli_main(["template", "list"]) == 0
        tdir = str(tmp_path / "eng")
        assert cli_main(["template", "get", "vanilla", tdir]) == 0
        variant = json.load(open(f"{tdir}/engine.json"))
        assert variant["engineFactory"].endswith("vanilla_engine")

    def test_lint_command(self, tmp_path, capsys):
        # clean file -> exit 0 with the summary line
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert cli_main(["lint", str(clean)]) == 0
        assert "clean" in capsys.readouterr().out
        # a jit'd host sync -> exit 1, finding on stdout
        dirty = tmp_path / "dirty.py"
        dirty.write_text(
            "import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n"
        )
        assert cli_main(["lint", str(dirty)]) == 1
        out = capsys.readouterr().out
        assert "JT01" in out and "dirty.py" in out
        assert cli_main(["lint", "--list-rules"]) == 0
        assert "JT06" in capsys.readouterr().out
        # bad path -> exit 2, distinguishable from "findings found" (1)
        assert cli_main(["lint", str(tmp_path / "missing")]) == 2
        # no args -> lints the installed package from any cwd
        import os
        old = os.getcwd()
        os.chdir(str(tmp_path))
        try:
            assert cli_main(["lint"]) == 0
        finally:
            os.chdir(old)
        assert "clean" in capsys.readouterr().out

    def test_run_command(self, memory_storage, tmp_path, capsys):
        # dotted callable: gets passthrough argv, return value is exit code
        import tests.test_tools as me
        assert cli_main(["run", "tests.test_tools._run_target", "a", "b"]) == 0
        assert me._RUN_ARGS == ["a", "b"]
        # bare module executed as __main__ (prints the platform string)
        assert cli_main(["run", "platform"]) == 0
        assert capsys.readouterr().out.strip()

    def test_build_train_via_cli(self, memory_storage, tmp_path, capsys):
        tdir = str(tmp_path / "eng")
        cli_main(["template", "get", "vanilla", tdir])
        ej = f"{tdir}/engine.json"
        assert cli_main(["build", "--engine-json", ej]) == 0
        assert cli_main(["train", "--engine-json", ej]) == 0
        assert "COMPLETED" in capsys.readouterr().out
        manifests = memory_storage.engine_manifests().get_all()
        assert len(manifests) == 1
        instances = memory_storage.engine_instances().get_all()
        assert instances and instances[0].status == "COMPLETED"


class TestAdminServer:
    @pytest.fixture()
    def admin(self, memory_storage):
        server = AdminServer(storage=memory_storage, host="127.0.0.1", port=0)
        server.start()
        yield f"http://127.0.0.1:{server.port}"
        server.stop()

    def test_routes(self, admin, memory_storage):
        assert http("GET", f"{admin}/")[1] == {"status": "alive"}
        status, body = http("POST", f"{admin}/cmd/app", {"name": "adminapp"})
        assert status == 200 and body["name"] == "adminapp"
        assert body["accessKeys"]
        # duplicate -> 409
        assert http("POST", f"{admin}/cmd/app", {"name": "adminapp"})[0] == 409
        status, body = http("GET", f"{admin}/cmd/app")
        assert [a["name"] for a in body["apps"]] == ["adminapp"]
        # wipe data then delete
        assert http("DELETE", f"{admin}/cmd/app/adminapp/data")[0] == 200
        assert http("DELETE", f"{admin}/cmd/app/adminapp")[0] == 200
        assert http("GET", f"{admin}/cmd/app")[1]["apps"] == []
        assert http("DELETE", f"{admin}/cmd/app/ghost")[0] == 404
        assert http("POST", f"{admin}/cmd/app", {"nope": 1})[0] == 400


class TestDashboard:
    def test_listing_and_results(self, memory_storage):
        memory_storage.evaluation_instances().insert(EvaluationInstance(
            id="ev1", status="EVALCOMPLETED",
            start_time=dt.datetime(2026, 1, 1, tzinfo=UTC),
            end_time=dt.datetime(2026, 1, 1, 1, tzinfo=UTC),
            evaluation_class="my.Eval", batch="b1",
            evaluator_results="best: x",
            evaluator_results_html="<html>r</html>",
            evaluator_results_json='{"best": "x"}',
        ))
        server = DashboardServer(storage=memory_storage, host="127.0.0.1", port=0)
        server.start()
        base = f"http://127.0.0.1:{server.port}"
        try:
            status, body = http("GET", f"{base}/")
            assert status == 200 and b"ev1" in body
            assert http("GET", f"{base}/engine_instances/ev1/evaluator_results.txt")[1] == b"best: x"
            assert http("GET", f"{base}/engine_instances/ev1/evaluator_results.json")[1] == {"best": "x"}
            assert http("GET", f"{base}/engine_instances/ev1/evaluator_results.html")[1] == b"<html>r</html>"
            assert http("GET", f"{base}/engine_instances/ghost/evaluator_results.txt")[0] == 404
        finally:
            server.stop()


class TestTemplateScaffold:
    def test_get_materializes_editable_source(self, memory_storage, tmp_path,
                                              capsys):
        """`pio template get` must produce a WORKING project whose source
        the user can edit before training (ref: Template.scala:226-415
        materializes a renamed source tree)."""
        import sys

        from predictionio_tpu.data.event import Event

        app = memory_storage.apps().insert("scaffold")
        memory_storage.events().init(app.id)
        events = [
            Event(event="buy", entity_type="user", entity_id=f"u{k % 6}",
                  target_entity_type="item", target_entity_id=f"i{k % 4}")
            for k in range(40)
        ]
        memory_storage.events().insert_batch(events, app.id)

        tdir = tmp_path / "myreco"
        assert cli_main(["template", "get", "recommendation", str(tdir)]) == 0
        src_path = tdir / "recommendation_engine.py"
        assert src_path.exists() and (tdir / "README.md").exists()

        # the user EDITS the scaffolded source: different buy rating
        src = src_path.read_text()
        assert "buy_rating: float = 4.0" in src
        src_path.write_text(
            src.replace("buy_rating: float = 4.0", "buy_rating: float = 2.5")
        )
        # and fills the variant params
        ej = tdir / "engine.json"
        variant = json.load(open(ej))
        assert variant["engineFactory"] == "recommendation_engine.recommendation_engine"
        variant["datasource"] = {"params": {"app_name": "scaffold"}}
        variant["algorithms"] = [
            {"name": "als", "params": {"rank": 4, "num_iterations": 2,
                                       "block_size": 8}}
        ]
        json.dump(variant, open(ej, "w"))

        assert cli_main(["train", "--engine-json", str(ej)]) == 0
        assert "COMPLETED" in capsys.readouterr().out
        # the edited project-local module was loaded (path-keyed, never
        # the installed package nor another project's same-named file)
        mod = next(
            m for k, m in sys.modules.items()
            if k.startswith("_pio_project_")
            and getattr(m, "__file__", None) == str(src_path)
        )
        assert mod.RecoDataSourceParams().buy_rating == 2.5
        inst = memory_storage.engine_instances().get_all()[0]
        assert inst.engine_factory.startswith("recommendation_engine.")

        # a SECOND project with the same module name must not collide
        tdir2 = tmp_path / "other"
        assert cli_main(["template", "get", "recommendation", str(tdir2)]) == 0
        from predictionio_tpu.workflow.variant import EngineVariant

        v2 = EngineVariant.load(str(tdir2 / "engine.json"))
        engine2 = v2.create_engine()
        ds_cls = next(iter(engine2.data_source_classes.values()))
        # unedited copy keeps the 4.0 default even though project 1's
        # edited 2.5 version is already loaded in this process
        assert ds_cls.__module__ != mod.__name__
        import inspect as _inspect

        assert _inspect.getmodule(ds_cls).RecoDataSourceParams().buy_rating == 4.0


class TestColumnarImport:
    """Parquet files with a pure interaction shape bulk-load through the
    columnar path; anything richer falls back to the row path — both
    must land identical events."""

    def _write_ratings_parquet(self, path, n=50):
        import numpy as np

        from predictionio_tpu.tools.eventdata import _write_parquet

        rng = np.random.default_rng(4)
        dicts = [
            {
                "event": "rate" if k % 3 else "buy",
                "entityType": "user",
                "entityId": f"u{rng.integers(8)}",
                "targetEntityType": "item",
                "targetEntityId": f"i{rng.integers(5)}",
                "properties": {"rating": float(k % 5) + 0.5} if k % 3 else None,
                "eventTime": f"2026-01-01T00:{k % 60:02d}:00+00:00",
            }
            for k in range(n)
        ]
        for d in dicts:
            if d["properties"] is None:
                del d["properties"]
        _write_parquet(path, dicts)
        return dicts

    def test_interaction_parquet_takes_columnar_path(self, memory_storage,
                                                     tmp_path, monkeypatch):
        from predictionio_tpu.tools import eventdata

        app = memory_storage.apps().insert("colimp")
        memory_storage.events().init(app.id)
        path = str(tmp_path / "ratings.parquet")
        dicts = self._write_ratings_parquet(path)

        # prove the fast path ran (row path would call insert_batch with
        # Event objects built from dicts)
        spy = {"columnar": 0}
        real = memory_storage.events().insert_columnar

        def counting(*a, **kw):
            spy["columnar"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(memory_storage.events(), "insert_columnar", counting)
        n = eventdata.import_events("colimp", path, storage=memory_storage)
        assert n == len(dicts) and spy["columnar"] == 1

        got = memory_storage.events().find(app.id)
        assert len(got) == len(dicts)
        want = {
            (d["event"], d["entityId"], d["targetEntityId"],
             d.get("properties", {}).get("rating"))
            for d in dicts
        }
        have = {
            (e.event, e.entity_id, e.target_entity_id,
             e.properties.get_opt("rating"))
            for e in got
        }
        assert have == want

    def test_rich_properties_fall_back_to_row_path(self, memory_storage,
                                                   tmp_path):
        from predictionio_tpu.tools import eventdata
        from predictionio_tpu.tools.eventdata import _write_parquet

        app = memory_storage.apps().insert("rowimp")
        memory_storage.events().init(app.id)
        path = str(tmp_path / "rich.parquet")
        _write_parquet(path, [
            {
                "event": "$set", "entityType": "item", "entityId": "i1",
                "properties": {"categories": ["a", "b"], "price": 9.5},
                "eventTime": "2026-01-01T00:00:00+00:00",
            },
            {
                "event": "view", "entityType": "user", "entityId": "u1",
                "targetEntityType": "item", "targetEntityId": "i1",
                "eventTime": "2026-01-01T00:01:00+00:00",
            },
        ])
        n = eventdata.import_events("rowimp", path, storage=memory_storage)
        assert n == 2
        got = memory_storage.events().find(app.id)
        assert got[0].properties.get_opt("categories") == ["a", "b"]
        assert got[1].event == "view"

    def test_columnar_rejects_invalid_events_via_row_path(self, memory_storage,
                                                          tmp_path):
        """A shape-conforming file with INVALID events must not bulk-load:
        the fast path declines and the row path raises with position."""
        from predictionio_tpu.tools import eventdata
        from predictionio_tpu.tools.eventdata import _write_parquet

        commands.app_new("badimp", storage=memory_storage)
        path = str(tmp_path / "bad.parquet")
        _write_parquet(path, [
            {   # reserved event WITH a target: validation must reject
                "event": "$set", "entityType": "user", "entityId": "u1",
                "targetEntityType": "item", "targetEntityId": "i1",
                "eventTime": "2026-01-01T00:00:00+00:00",
            },
        ])
        with pytest.raises(ValueError, match="bad.parquet:1"):
            eventdata.import_events("badimp", path, storage=memory_storage)

    def test_columnar_handles_mixed_no_target_rows(self, memory_storage,
                                                   tmp_path):
        from predictionio_tpu.tools import eventdata
        from predictionio_tpu.tools.eventdata import _write_parquet

        app = commands.app_new("miximp", storage=memory_storage).app
        path = str(tmp_path / "mix.parquet")
        _write_parquet(path, [
            {"event": "view", "entityType": "user", "entityId": "u1",
             "targetEntityType": "item", "targetEntityId": "i1",
             "eventTime": "2026-01-01T00:00:00+00:00"},
            {"event": "login", "entityType": "user", "entityId": "u2",
             "eventTime": "2026-01-01T00:01:00+00:00"},
        ])
        assert eventdata.import_events("miximp", path, storage=memory_storage) == 2
        got = {e.entity_id: e for e in memory_storage.events().find(app.id)}
        assert got["u1"].target_entity_id == "i1"
        assert got["u2"].target_entity_id is None
        assert got["u2"].target_entity_type is None


class TestJournalCLI:
    """`pio journal` over this process's ring (no --url)."""

    def test_empty_journal(self, capsys):
        assert cli_main(["journal"]) == 0
        assert "(journal is empty)" in capsys.readouterr().out

    def test_human_lines_and_kind_filter(self, capsys):
        from predictionio_tpu.obs import journal

        journal.emit("reload", instance="i-7")
        journal.emit("breaker", target="svc", state="open", failures=3)
        assert cli_main(["journal"]) == 0
        out = capsys.readouterr().out
        assert "reload" in out and "instance=i-7" in out
        assert "breaker" in out and "state=open" in out
        assert cli_main(["journal", "--kind", "breaker"]) == 0
        out = capsys.readouterr().out
        assert "breaker" in out and "reload" not in out

    def test_json_page_shape(self, capsys):
        from predictionio_tpu.obs import journal

        journal.emit("swap", phase="start")
        assert cli_main(["journal", "--json"]) == 0
        page = json.loads(capsys.readouterr().out)
        assert set(page) == {"capacity", "path", "dropped_total",
                             "events"}
        assert page["events"][-1]["kind"] == "swap"

    def test_fleet_without_url_is_an_error(self, capsys):
        assert cli_main(["journal", "--fleet"]) == 1
        assert "--fleet needs --url" in capsys.readouterr().err

    def test_format_event_renders_member_and_trace(self):
        from predictionio_tpu.tools.cli import format_journal_event

        line = format_journal_event(
            {"ts": 1754500000.0, "mono": 1.0, "kind": "reload",
             "fleet_member": "r1", "trace": "a" * 32,
             "instance": "i-1"})
        assert "[r1]" in line
        assert "trace=" + "a" * 8 in line and "a" * 9 not in line
        assert "instance=i-1" in line


class TestAnomaliesCLI:
    """`pio anomalies`: exit 1 while anything is active, 0 when quiet;
    --json is the pinned machine contract."""

    def _arm(self, cause=True):
        from predictionio_tpu.obs import anomaly

        verdict = {"mode": "step", "direction": "up", "baseline": 10.0,
                   "sigma": 0.3, "recent": 15.0, "delta": 5.0,
                   "z": 16.9, "cusum": 45.0, "onset_ts": 1450.0,
                   "since": 1540.0}
        if cause:
            verdict["cause"] = {"kind": "reload", "ts": 1445.0,
                                "instance": "i-9", "gap_sec": 5.0}
        anomaly.SENTINEL._active["serve_p99_ms.e"] = verdict

    def test_quiet_exits_0(self, capsys):
        assert cli_main(["anomalies"]) == 0
        assert "no active anomalies" in capsys.readouterr().out

    def test_active_exits_1_with_attribution(self, capsys):
        self._arm()
        assert cli_main(["anomalies"]) == 1
        out = capsys.readouterr().out
        assert "1 ACTIVE anomaly" in out
        assert "serve_p99_ms.e" in out
        assert "step/up" in out
        assert "z=16.9" in out
        assert "<- reload" in out and "instance=i-9" in out

    def test_json_shape_pin(self, capsys):
        """The machine contract CI scripts consume: top-level keys,
        the active block keyed by series, exit code semantics."""
        self._arm()
        assert cli_main(["anomalies", "--json"]) == 1
        report = json.loads(capsys.readouterr().out)
        assert set(report) == {"window_sec", "active",
                               "recent_resolved", "scan_ms"}
        entry = report["active"]["serve_p99_ms.e"]
        assert {"mode", "direction", "baseline", "recent", "z",
                "onset_ts", "since", "cause"} <= set(entry)
        assert entry["cause"]["kind"] == "reload"
        # quiet process -> same shape, exit 0
        from predictionio_tpu.obs import anomaly

        anomaly.SENTINEL.reset()
        assert cli_main(["anomalies", "--json"]) == 0
        report = json.loads(capsys.readouterr().out)
        assert report["active"] == {}

    def test_fleet_without_url_is_an_error(self, capsys):
        assert cli_main(["anomalies", "--fleet"]) == 1
        assert "--fleet needs --url" in capsys.readouterr().err
