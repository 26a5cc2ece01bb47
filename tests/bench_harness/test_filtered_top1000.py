"""The cell `wiki.filtered-top1000` on the CPU: its configuration is
`wiki-bm25-5s` plus two columns (the same text for the same seed), its traffic
the three filtered templates in their weights with every range inside the
field's span, its roofline bytes reckoned by hand, the reader of its batch
counter on made-up counters, and a traced run at 2,000 documents through the
committed `BENCHMARK.json`: correct, every batch the filtered program, nothing
compiled in the window, and the low-precision control not correct.
(`test_harness_run.py` drives the plain run and the control for every cell,
this one included.)"""

import json
import os

import numpy as np
import pytest

import corpus
import harness
import traffic
import work
from conftest import B, BENCH, entries_through
from readers import label_share, span_ms
from reference import Reference

CELL = "wiki.filtered-top1000"
SMALL = {"documents": 2000, "chips": 8}
SEED = 2 ** 31 + 33


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _load("configs", "wiki-filtered-5s.json")
PLAIN = _load("configs", "wiki-bm25-5s.json")
WORKLOAD = _load("workloads", CELL + ".json")


# -- the files ------------------------------------------------------------------

def test_the_configuration_is_wiki_bm25_5s_plus_two_columns():
    assert list(CFG["fields"]) == ["title", "body", "timestamp", "month"]
    for field in ("title", "body"):
        assert CFG["fields"][field] == PLAIN["fields"][field], field
    for key in ("similarity", "index_settings", "score_dtype", "documents",
                "published", "number_of_shards", "number_of_replicas",
                "ingest", "reduced_why"):
        assert CFG[key] == PLAIN[key], key
    for name, text in PLAIN["guarantees"].items():
        assert CFG["guarantees"][name] == text, name
    assert set(CFG["guarantees"]) - set(PLAIN["guarantees"]) == {"filters"}
    assert "float32" in CFG["guarantees"]["filters"]
    assert CFG["assumed"][:len(PLAIN["assumed"])] == PLAIN["assumed"]
    assert len(CFG["assumed"]) == len(PLAIN["assumed"]) + 4
    assert CFG["fields"]["timestamp"] == {
        "kind": "date", "base_millis": 1020556800000, "span_days": 3650}
    month = CFG["fields"]["month"]
    assert month["kind"] == "choice" and month["values"] == list(range(1, 13))
    assert len(set(month["weights"])) == 1 and len(month["weights"]) == 12
    entry, = [c for c in B["configs"] if c["name"] == "wiki-filtered-5s"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["documents"] and CFG["index"] == "wikif"


@pytest.mark.parametrize("k", [0, 7])
def test_both_configurations_draw_the_same_text_for_one_seed(k):
    """`title` and `body` come first in `fields`, so `corpus.chunk` has
    drawn them before it draws a column: the two cells differ by the filter
    alone."""
    ours, theirs = corpus.chunk(CFG, SEED, k), corpus.chunk(PLAIN, SEED, k)
    for field in ("title", "body"):
        assert np.array_equal(ours[field][0], theirs[field][0])
        assert np.array_equal(ours[field][1], theirs[field][1])
    assert corpus.payload(CFG, SEED, k) != corpus.payload(PLAIN, SEED, k)
    lo = CFG["fields"]["timestamp"]["base_millis"]
    ts = ours["timestamp"]
    assert ts.dtype == np.int64 and ts.min() >= lo - 1
    assert ts.max() < lo + 3650 * 86_400_000
    assert set(ours["month"].tolist()) == set(range(1, 13))


def test_the_traffic_is_the_rerank_cells_loop_with_a_filter_in_every_body():
    rerank = _load("workloads", "wiki.rerank-top1000.json")
    for key in ("loop", "clients", "endpoint", "bodies_per_request",
                "order_block"):
        assert WORKLOAD[key] == rerank[key], key
    assert WORKLOAD["warmup"]["replay_s"] == rerank["warmup"]["replay_s"]
    assert "pilots" not in WORKLOAD["warmup"]
    assert WORKLOAD["warmup"]["rounds"] == rerank["warmup"]["rounds"] == 4
    assert WORKLOAD["shape_seed"] == 106
    assert WORKLOAD["sample"] == {"every": 32}    # ISSUE 33's, both
    match = rerank["mix"][0]["body"]["query"]
    for m in WORKLOAD["mix"]:
        body = m["body"]
        assert body["size"] == 1000 and body["_source"] is False
        assert body["query"]["bool"]["must"] == [match]
        assert body["query"]["bool"]["filter"]
    assert [m["weight"] for m in WORKLOAD["mix"]] == [5, 3, 2]
    cell, = [c for c in B["workloads"] if c["name"] == CELL]
    assert cell == {**cell, "config": "wiki-filtered-5s", "chips": 1,
                    "traffic": "filtered-top1000"}
    assert traffic.n_requests(WORKLOAD, 40) >= 644


def _kind(body) -> str:
    return "+".join(sorted(next(iter(f)) for f in
                           body["query"]["bool"]["filter"]))


def test_build_yields_the_three_templates_in_their_weights():
    small = {**CFG, "documents": 2000}
    requests = traffic.build(WORKLOAD, small, SEED, 3.0)
    assert len(requests) == traffic.n_requests(WORKLOAD, 3.0) == 52
    bodies = [b for r in requests for b in r["bodies"]]
    assert len(bodies) == 52 * 256
    share = {k: sum(_kind(b) == k for b in bodies) / len(bodies)
             for k in ("range", "range+term", "term")}
    assert share["range"] == pytest.approx(0.5, abs=0.02)
    assert share["range+term"] == pytest.approx(0.3, abs=0.02)
    assert share["term"] == pytest.approx(0.2, abs=0.02)
    f = CFG["fields"]["timestamp"]
    lo, hi = f["base_millis"], f["base_millis"] + f["span_days"] * 86_400_000
    day = 86_400_000
    for b in bodies:
        filters = {next(iter(x)): next(iter(x.values()))
                   for x in b["query"]["bool"]["filter"]}
        if "range" in filters:
            r = filters["range"]["timestamp"]
            assert lo <= r["gte"] < r["lt"] <= hi
            width = r["lt"] - r["gte"]
            assert (365 * day <= width <= 1825 * day) if "term" in filters \
                else (30 * day <= width <= 365 * day)
        if "term" in filters:
            assert filters["term"]["month"] in range(1, 13)
        assert 2 <= len(b["query"]["bool"]["must"][0]["match"]["body"]
                        .split()) <= 5
    # the same bodies for every seed, in another order inside runs of 4
    again = traffic.build(WORKLOAD, small, SEED + 1, 3.0)
    assert sorted(r["payload"] for r in again[:48]) \
        == sorted(r["payload"] for r in requests[:48])
    assert [r["payload"] for r in again] != [r["payload"] for r in requests]


def test_body_bytes_of_each_template_by_hand():
    """Postings (12 B each) + the documents inside the range x the bytes of
    the columns the body reads (8 B the date, 4 B `month`; a body without a
    range reads its columns over every document) + 8 B a hit of `size`."""
    cfg = {**CFG, "documents": 2000}
    ref = Reference(cfg, SEED)
    rng = np.random.default_rng(3)
    by_kind = {}
    while len(by_kind) < 3:
        k = int(rng.integers(0, 3))
        body = traffic._expand(WORKLOAD["mix"][k]["body"], cfg, rng)
        by_kind[_kind(body)] = body
    for kind, body in by_kind.items():
        words = body["query"]["bool"]["must"][0]["match"]["body"].split()
        postings = 12 * int(ref.df("body", [int(w[1:]) for w in words]).sum())
        filters = {next(iter(x)): next(iter(x.values()))
                   for x in body["query"]["bool"]["filter"]}
        inside = ref.n
        if "range" in filters:
            r = filters["range"]["timestamp"]
            ts = ref.cols["timestamp"]
            inside = int(((ts >= r["gte"]) & (ts < r["lt"])).sum())
            assert 0 < inside < ref.n
        per_doc = {"range": 8, "range+term": 12, "term": 4}[kind]
        assert work.body_bytes(ref, body) == \
            postings + inside * per_doc + 8 * 1000, kind


# -- the two metrics the cell adds, on made-up counters ---------------------------

def _ctx(before: dict, after: dict) -> dict:
    def snap(rows):
        return {"metrics": {"es_packed_batches_total": [
            ({"node": "n", "program": p}, float(v)) for p, v in rows.items()]}}
    return {"before": snap(before), "after": snap(after)}


def test_packed_filtered_share_is_the_filtered_batches_over_all():
    params = _load("metrics", "packed_filtered_share.qps.json")["params"]
    read = label_share.read
    assert read(_ctx({"plain": 5, "filtered": 2},
                     {"plain": 5, "filtered": 42}), params) == 100.0
    assert read(_ctx({"plain": 0, "filtered": 0},
                     {"plain": 30, "filtered": 10}), params) == 25.0
    assert read(_ctx({"plain": 7, "filtered": 0},
                     {"plain": 9, "filtered": 0}), params) == 0.0
    # no batch in the window, or a program without the counter (the parent)
    assert read(_ctx({"plain": 7, "filtered": 1},
                     {"plain": 7, "filtered": 1}), params) is None
    assert read({"before": {"metrics": {}}, "after": {"metrics": {}}},
                params) is None


def test_packed_filter_prep_reads_nothing_from_a_program_without_the_span():
    params = _load("metrics", "packed_filter_prep_ms.qps.json")["params"]
    assert params["spans"] == [params["per"]] == ["packed.filter_descriptors"]
    assert span_ms.read({"before": {"metrics": {}}, "after": {"metrics": {}}},
                        params) is None


def test_the_cell_joins_the_rerank_cells_metrics_and_adds_two():
    held = entries_through("packed_filtered_share.qps")
    listed = {m["name"] for m in held if CELL in m.get("workloads", [])}
    beside = {m["name"] for m in held
              if "wiki.rerank-top1000" in m.get("workloads", [])}
    assert listed - beside == {"packed_filter_prep_ms.qps",
                               "packed_filtered_share.qps"}
    assert beside <= listed and len(beside) == 13
    own = [m for m in held if m["name"] in listed - beside]
    assert len(own) == 2 and all(CELL in m["workloads"] for m in own)
    assert {m["moves"] for m in own} == {"queries_per_s"}
    roof, = [m for m in B["per_layer"]
             if m["name"] == "packed_roofline_share.qps"]
    assert {m["layer"] for m in own} == {roof["layer"]}


# -- a traced run and its control -------------------------------------------------

class _OwnDirCell(harness.Cell):
    """The cell with a run directory of this file's own:
    `test_harness_run.py` drives the same cell, in another worker at the
    same time, under `benchmark/.run/<cell>/`."""

    own_dir = None

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.run_dir = os.path.join(self.own_dir, self.name)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    _OwnDirCell.own_dir = str(tmp_path_factory.mktemp("filtered-run"))
    procs = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "Cell", _OwnDirCell)
        try:
            return harness.run(CELL, SEED, 3.0, True, platform="cpu",
                               overrides={**SMALL, "sample": "all"},
                               control=True, procs=procs)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p.wait()


def test_traced_run_is_correct_and_its_control_is_not(traced):
    assert traced["correct"] is True, traced["compared"]
    assert traced["failed"] == 0 and traced["attempted"] > 0
    assert traced["compared"]["totals_wrong"]["value"] == 0
    assert traced["compared"]["hits_wrong"]["value"] == 0
    assert traced["notes"]["hits_checked"] > 0
    control = traced["control"]
    assert control["correct"] is False
    over = {k for k, c in control["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]}
    # bfloat16 scores, and a date narrowed to float32 moves a bound
    assert "score_rel_err_max" in over, control


def test_every_batch_ran_the_filtered_program_and_nothing_compiled(traced):
    m = traced["metrics"]
    assert m["packed_filtered_share.qps"]["value"] == 100.0
    assert m["device_lane_share.qps"]["value"] == 100.0
    assert m["compiles_in_window.qps"]["value"] == 0
    assert traced["notes"]["warmup_compiles_left"] == 0


@pytest.mark.parametrize("name", [
    "packed_filter_prep_ms.qps", "packed_prep_ms.qps",
    "packed_respond_ms.qps", "program_wall_ms.qps", "rest_self_ms.qps",
    "d2h_bytes_per_request.qps"])
def test_traced_run_reports_the_lanes_spans(traced, name):
    assert traced["metrics"][name]["value"] > 0
    if name == "packed_filter_prep_ms.qps":     # a part of the whole prep
        assert traced["metrics"][name]["value"] \
            < traced["metrics"]["packed_prep_ms.qps"]["value"]
    if name == "d2h_bytes_per_request.qps":     # one download: i32[256, 2k+1]
        assert traced["metrics"][name]["value"] == 256 * (2 * 1024 + 1) * 4


def test_no_device_plane_on_the_cpu_means_no_roofline(traced):
    assert "packed_roofline_share.qps" not in traced["metrics"]
    assert "device_idle_share.qps" not in traced["metrics"]
