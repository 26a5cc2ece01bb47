"""The cell `httplogs.dashboard-mesh` on the CPU at 2,000 documents, through
the committed `BENCHMARK.json`, on the suite's 8 virtual devices (5 shards
over 8 chips, three of which hold nothing): its traced run is correct,
compiles nothing in its window, runs every request on the collective form of
the panel lane and about one program a batch; an altered bucket is not
correct; its files keep `httplogs-dash-5s`' shapes and `httplogs.dash-panels`'
mix; the two metrics it adds read what they say; and a perfect four-chip
program reads 100 % of its roofline, not 400 %. (`test_harness_run.py` drives
the plain run and the control for every cell, this one included.)"""

import json
import os
from types import SimpleNamespace

import pytest

import harness
import work
import xtrace
from conftest import B, BENCH, entries_through
from readers import hbm_balance, lane_share, roofline
from reference import Reference

CELL = "httplogs.dashboard-mesh"
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
        return harness.run(CELL, 2 ** 31 + 31, 3.0, trace, platform="cpu",
                           overrides={**SMALL, **over}, procs=procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()


@pytest.fixture(scope="module")
def own_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("mesh-run"))


@pytest.fixture(scope="module")
def traced(own_dir):
    with pytest.MonkeyPatch.context() as mp:
        return _run(mp, own_dir, trace=True)


def test_traced_run_is_correct_and_compiles_nothing_in_its_window(traced):
    assert traced["correct"] is True, traced["compared"]
    assert traced["failed"] == 0 and traced["device"]["count"] == 8
    assert traced["compared"]["buckets_wrong"]["value"] == 0
    assert traced["compared"]["totals_wrong"]["value"] == 0
    assert traced["metrics"]["compiles_in_window.lat"]["value"] == 0
    assert traced["notes"]["warmup_compiles_left"] == 0


def test_every_request_ran_the_collective_form(traced):
    assert traced["metrics"]["mesh_lane_share.mesh"]["value"] == 100.0
    assert traced["metrics"]["device_lane_share.dash"]["value"] == 100.0


def test_a_batch_is_one_program(traced):
    """One dispatch a batch, followers share it: at most one a request
    (the one-chip form before this lane ran one a segment, 7.6)."""
    assert 0 < traced["metrics"]["programs_per_request.dash"]["value"] <= 1.0


@pytest.mark.parametrize("name", [
    "agg_plan_ms.dash", "agg_reduce_ms.dash", "agg_render_ms.dash",
    "agg_program_wall_ms.dash"])
def test_traced_run_reports_the_panel_lanes_spans(traced, name):
    assert traced["metrics"][name]["value"] > 0


def test_no_device_plane_and_no_memory_stats_on_the_cpu(traced):
    """The CPU backend has neither: the readers return nothing and the
    line leaves the metrics out."""
    assert "agg_roofline_share.dash" not in traced["metrics"]
    assert "hbm_chip_balance.mesh" not in traced["metrics"]


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


# -- the files ------------------------------------------------------------------

def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_the_configuration_is_the_one_chip_deployments_but_for_its_layout():
    one, cfg = _load("configs", "httplogs-dash-5s.json"), \
        _load("configs", "httplogs-5s-mesh.json")
    for key in ("fields", "guarantees", "index_settings", "assumed",
                "ingest", "published", "documents", "number_of_shards",
                "number_of_replicas", "index"):
        assert cfg[key] == one[key], key
    assert cfg["number_of_shards"] == 5     # the source's, not the chips'
    assert cfg["deployment"] != one["deployment"] \
        and "four chips" in cfg["deployment"]
    entry, = [c for c in B["configs"] if c["name"] == "httplogs-5s-mesh"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["documents"]


def test_the_traffic_is_the_one_chip_cells_mix():
    one, w = _load("workloads", "httplogs.dash-panels.json"), \
        _load("workloads", CELL + ".json")
    for key in ("mix", "loop", "endpoint", "connections", "shape_seed"):
        assert w[key] == one[key], key
    for key in ("pilots", "copies", "rounds", "replay_s"):
        assert w["warmup"][key] == one["warmup"][key], key
    assert w["sample"] == {"requests": 2400}
    cell, = [c for c in B["workloads"] if c["name"] == CELL]
    assert cell["chips"] == 4 and cell["config"] == "httplogs-5s-mesh"
    assert cell["traffic"] == "dash-panels" and len(cell["why"]) <= 200


def test_the_cell_joins_the_one_chip_cells_metrics_and_adds_two():
    held = entries_through("hbm_chip_balance.mesh")
    listed = {m["name"] for m in held if CELL in m.get("workloads", [])}
    beside = {m["name"] for m in held
              if "httplogs.dash-panels" in m.get("workloads", [])}
    assert listed - beside == {"mesh_lane_share.mesh",
                               "hbm_chip_balance.mesh"}
    assert beside <= listed and "latency_p95_ms" not in listed
    own = [m for m in held if m["name"].endswith(".mesh")]
    assert len(own) == 2 and all(CELL in m["workloads"] for m in own)
    assert {m["moves"] for m in own} == {"latency_p50_ms"}


# -- the two new metrics, on made-up counters -----------------------------------

def _hbm(*in_use):
    return {"after": {"hbm": {f"tpu:{i}": {
        "bytes_in_use": b, "limit_bytes": 16 << 30, "supported": True}
        for i, b in enumerate(in_use)}}}


def test_hbm_chip_balance_is_the_least_full_chip_over_the_fullest():
    assert hbm_balance.read(_hbm(400, 100, 200, 300), {}) == 25.0
    assert hbm_balance.read(_hbm(562_000_000, 0, 0, 0), {}) == 0.0
    assert hbm_balance.read(_hbm(7, 7, 7, 7), {}) == 100.0
    assert hbm_balance.read(_hbm(0, 0), {}) is None
    unsupported = {"after": {"hbm": {"cpu:0": {
        "bytes_in_use": 0, "limit_bytes": 0, "supported": False}}}}
    assert hbm_balance.read(unsupported, {}) is None


def test_mesh_lane_share_tells_the_collective_form_from_the_one_chip_form():
    params = _load("metrics", "mesh_lane_share.mesh.json")["params"]
    both = _load("metrics", "device_lane_share.dash.json")["params"]

    def ctx(**chosen):
        return {"before": {"lane_decisions": {}}, "after": {
            "lane_decisions": {f"{k}:chosen": v for k, v in chosen.items()}}}
    assert lane_share.read(ctx(panels_mesh=30), params) == 100.0
    assert lane_share.read(ctx(panels=30), params) == 0.0   # the parent
    assert lane_share.read(ctx(panels_mesh=30, batched=10), params) == 75.0
    assert lane_share.read(ctx(panels_mesh=30), both) == 100.0
    assert lane_share.read(ctx(), params) is None


# -- a perfect four-chip program reads 100 % of its roofline ----------------------

def test_a_perfect_four_chip_program_reads_100_per_cent_not_400():
    """`xtrace` sums a program's time over the device planes, and
    `work.body_bytes` counts the same bytes whatever implements them: four
    chips that each stream a quarter of a body's bytes at the chip's peak
    read 100 %, one chip that streams them all at peak reads 100 % too."""
    cfg = {**_load("configs", "httplogs-5s-mesh.json"), "documents": 2000}
    ref = Reference(cfg, 7)
    base = cfg["fields"]["@timestamp"]["base_millis"]
    body = {"size": 0, "query": {"range": {"@timestamp": {
        "gte": base, "lt": base + 5 * 86_400_000}}}, "aggs": {"h": {
            "date_histogram": {"field": "@timestamp", "interval": "hour"}}}}
    peaks = _load("peaks.json")["TPU v5 lite"]
    least_ns = work.least_seconds(peaks, work.body_bytes(ref, body)) * 1e9
    assert least_ns > 0
    params = _load("metrics", "agg_roofline_share.dash.json")["params"]

    def share(chips):
        planes = [(f"/device:TPU:{c}", [
            ("XLA Modules", [("jit_panel_hist(123)", 1e9, least_ns / chips)]),
            ("XLA Ops", [("fusion.1", 1e9, least_ns / chips)])])
            for c in range(chips)]
        planes.append(("/host:CPU", [("python", [("es:program", 0.0, 4e9)])]))
        trace = xtrace.reduce_planes(planes)
        assert trace["device_planes"] == chips
        assert trace["modules"]["jit_panel_hist"][0] == chips
        return roofline.read({
            "trace": trace, "trace_span": (0.0, 4.0), "reference": ref,
            "requests": [{"bodies": [body]}],
            "records": [{"i": 0, "status": 200, "item_errors": 0,
                         "sent": 1.0, "done": 1.5}],
            "device": SimpleNamespace(device_kind="TPU v5 lite")}, params)
    assert share(4) == pytest.approx(100.0)
    assert share(1) == pytest.approx(100.0)
