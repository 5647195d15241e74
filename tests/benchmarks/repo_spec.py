"""The repo's ``BENCHMARK.json`` as the tests under ``tests/benchmarks`` read
it: as it stands, and with an addition appended to every list, as a later PR
makes one (``benchmarks/README.md``, "Adding a per-layer metric" and "Adding a
cell"). A test that checks the repo's file takes its specification from
``load`` and runs over both ``CASES``; it finds what it checks by name
(``by_name``), never by position, and says nothing about entries it does not
know. So the PR that writes a position-bound assertion sees it fail, not the
PR after it."""

import copy
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ADDED_CONFIG = {
    "name": "added-config", "source": "https://example.org/added-config",
    "file": "benchmarks/configs/added-config.json", "reduced": [],
    "why": "a configuration a later PR appends"}
ADDED_CELL = {
    "name": "added-config.added-traffic", "config": "added-config",
    "traffic": "added-traffic", "chips": 1,
    "why": "a cell a later PR appends"}
#: the end-to-end metrics whose ``workloads`` lists the added cell joins
ADDED_CELL_REPORTS = ("query_p50_ms", "query_p95_ms", "query_rate")
ADDED_PER_LAYER = [
    {"name": "added_step_ms.added", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "added layer",
     "moves": "query_p50_ms", "workloads": [ADDED_CELL["name"]]},
    {"name": "added_share_pct.added", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "added layer",
     "moves": "query_rate", "workloads": [ADDED_CELL["name"]]}]


def with_an_addition(spec: dict) -> dict:
    """``spec`` with one configuration, one cell (named in the three query
    metrics' lists) and two per-layer entries appended, each at the END of
    its list; nothing that was there moves or changes."""
    out = copy.deepcopy(spec)
    out["configs"].append(dict(ADDED_CONFIG))
    out["workloads"].append(dict(ADDED_CELL))
    for metric in out["end_to_end"]:
        if metric["name"] in ADDED_CELL_REPORTS:
            metric["workloads"].append(ADDED_CELL["name"])
    out["per_layer"] += copy.deepcopy(ADDED_PER_LAYER)
    return out


#: the two specifications every test of the repo's file runs over
CASES = {"as_it_stands": lambda spec: spec,
         "with_an_addition": with_an_addition}


def load(case: str = "as_it_stands") -> dict:
    """The specification the repo's ``BENCHMARK.json`` gives in ``case``."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return CASES[case](json.load(f))


def by_name(entries: list, name: str) -> dict:
    """The one entry of a list of ``BENCHMARK.json`` that has this name."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def assert_names_the_reader(spec: dict, entry: dict) -> None:
    """``per_layer`` holds ``entry`` under its name, key for key; its cells
    report the end-to-end metric it moves; its reader is in its place."""
    assert by_name(spec["per_layer"], entry["name"]) == entry
    moved = by_name(spec["end_to_end"], entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert os.path.isfile(os.path.join(
        REPO, "benchmarks", "layer_metrics", entry["name"] + ".py"))
