"""The cell `httplogs.dash-panels` on the CPU at 2,000 documents, through the
committed `BENCHMARK.json`: its traced run reports every per-layer metric the
panel lane's spans and counters feed and compiles nothing in its window; an
answer altered where it is produced is not correct; its files keep the
source's shapes. (`test_harness_run.py` drives the plain run and the
control for every cell, this one included.)"""

import json
import os

import pytest

import harness
from conftest import B, BENCH

CELL = "httplogs.dash-panels"
SMALL = {"documents": 2000, "chips": 8, "rate_per_s": 12.0}


class _OwnDirCell(harness.Cell):
    """The cell with a run directory of this file's own:
    `test_harness_run.py` drives the same cell, in another worker at the
    same time, under `benchmark/.run/<cell>/`."""

    own_dir = None

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.run_dir = os.path.join(self.own_dir, self.name)


def _run(monkeypatch, own_dir, trace=False, **over):
    _OwnDirCell.own_dir = own_dir
    monkeypatch.setattr(harness, "Cell", _OwnDirCell)
    procs = []
    try:
        return harness.run(CELL, 2 ** 31 + 27, 3.0, trace, platform="cpu",
                           overrides={**SMALL, **over}, procs=procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()


@pytest.fixture(scope="module")
def own_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("dash-run"))


@pytest.fixture(scope="module")
def traced(own_dir):
    with pytest.MonkeyPatch.context() as mp:
        return _run(mp, own_dir, trace=True)


def test_traced_run_is_correct_and_compiles_nothing_in_its_window(traced):
    assert traced["correct"] is True, traced["compared"]
    assert traced["failed"] == 0
    assert traced["compared"]["buckets_wrong"]["value"] == 0
    assert traced["compared"]["totals_wrong"]["value"] == 0
    assert traced["metrics"]["compiles_in_window.lat"]["value"] == 0
    assert traced["notes"]["warmup_compiles_left"] == 0


@pytest.mark.parametrize("name", [
    "agg_plan_ms.dash", "agg_reduce_ms.dash", "agg_render_ms.dash",
    "agg_program_wall_ms.dash", "programs_per_request.dash",
    "device_lane_share.dash"])
def test_traced_run_reports_the_panel_lanes_metrics(traced, name):
    assert traced["metrics"][name]["value"] > 0
    if name == "device_lane_share.dash":
        # every request was a panel's leader or one of its followers
        assert traced["metrics"][name]["value"] == 100.0


def test_no_device_plane_on_the_cpu_means_no_roofline(traced):
    assert "agg_roofline_share.dash" not in traced["metrics"]


def test_an_altered_bucket_is_not_correct(monkeypatch, own_dir):
    from elasticsearch_tpu.node import NodeService
    search = NodeService.search

    def skewed(self, index, body=None, **kw):
        out = search(self, index, body, **kw)
        for agg in (out.get("aggregations") or {}).values():
            if agg.get("buckets"):
                agg["buckets"][0]["doc_count"] += 1
        return out
    monkeypatch.setattr(NodeService, "search", skewed)
    out = _run(monkeypatch, own_dir)
    assert out["correct"] is False
    over = [k for k, c in out["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over == ["buckets_wrong"], out["compared"]


def test_the_configuration_keeps_the_sources_shapes():
    """Letter for letter what `httplogs-5s.json` states of fields, laws,
    guarantees and settings; only the name, the source line and the note
    on the cut differ, and the waiting file's `status` is gone."""
    with open(os.path.join(BENCH, "configs", "httplogs-5s.json")) as f:
        waiting = json.load(f)
    with open(os.path.join(BENCH, "configs", "httplogs-dash-5s.json")) as f:
        cfg = json.load(f)
    for key in ("fields", "guarantees", "index_settings", "assumed",
                "ingest", "published", "documents", "number_of_shards",
                "number_of_replicas", "index", "deployment"):
        assert cfg[key] == waiting[key], key
    assert "status" not in cfg
    entry, = [c for c in B["configs"] if c["name"] == "httplogs-dash-5s"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["documents"]


def test_the_traffic_is_the_dashboards_mix():
    with open(os.path.join(BENCH, "workloads",
                           "httplogs.dashboard.json")) as f:
        waiting = json.load(f)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    assert w["mix"] == waiting["mix"]
    assert [m["weight"] for m in w["mix"]] == [50, 30, 20]
    assert w["loop"] == "open" and w["connections"] == 32
    assert w["sample"] == "all" and w["shape_seed"] == 104
    assert w["warmup"]["copies"] == 32 and len(w["warmup"]["pilots"]) == 3
    cell, = [c for c in B["workloads"] if c["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == "httplogs-dash-5s"


def test_the_warm_up_has_no_cap_short_of_the_watchdog():
    """A window opens only after a replay round that compiled nothing and
    was refused nothing: the rounds allowed outlast the harness's longest
    watchdog, so a program that never settles (the commit before the panel
    lane, on these files) ends there and is not measured unsettled."""
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        warmup = json.load(f)["warmup"]
    with open(os.path.join(BENCH, "harness.json")) as f:
        watchdog = json.load(f)["watchdog_s"]
    assert warmup["rounds"] * warmup["replay_s"] > max(watchdog.values())
