"""The readers of the waits ISSUE 35 times where they happen: the host
threads' off-CPU share (`span_offcpu_share`, new), and the device queue,
the dispatch lock and a follower's wake-up through `span_ms`; each on a
hand-made `ctx`: the value, and what a program without the spans reads."""

import json
import os

import pytest

from conftest import B, BENCH
from readers import span_ms, span_offcpu_share

HOST = ["rest.parse_body", "rest.serialize", "search.plan",
        "packed.build_slots", "packed.respond", "aggs.plan", "aggs.render"]


def _snapshot(**families) -> dict:
    """{family: {span: number}} -> what `harness.counters` gives."""
    return {"metrics": {
        name: [({"node": "n", "span": k}, v) for k, v in series.items()]
        for name, series in families.items()}}


BEFORE = _snapshot(
    es_span_total={"program": 10, "batcher.follow": 4, "rest.parse_body": 10},
    es_span_seconds_total={"rest.parse_body": 0.010, "packed.respond": 0.5,
                           "search.plan": 0.002, "program": 1.0,
                           "program.queue": 0.2, "exec.lock_wait": 0.01,
                           "batcher.wake": 0.004},
    es_span_cpu_seconds_total={"rest.parse_body": 0.002,
                               "packed.respond": 0.1, "search.plan": 0.0},
    es_span_cpu_wall_seconds_total={"rest.parse_body": 0.003,
                                    "packed.respond": 0.125,
                                    "search.plan": 0.001})
AFTER = _snapshot(
    es_span_total={"program": 110, "batcher.follow": 44,
                   "rest.parse_body": 110},
    es_span_seconds_total={"rest.parse_body": 0.110, "packed.respond": 2.5,
                           "search.plan": 0.402, "program": 11.0,
                           "program.queue": 3.2, "exec.lock_wait": 0.21,
                           "batcher.wake": 0.084},
    es_span_cpu_seconds_total={"rest.parse_body": 0.015,
                               "packed.respond": 0.35, "search.plan": 0.075},
    es_span_cpu_wall_seconds_total={"rest.parse_body": 0.028,
                                    "packed.respond": 0.625,
                                    "search.plan": 0.101})
CTX = {"before": BEFORE, "after": AFTER, "window_s": 40.0, "records": []}
# the parent: the same spans, no CPU family and none of the new waits
PARENT = {**CTX, "before": _snapshot(
    es_span_total={"program": 10, "batcher.follow": 4},
    es_span_seconds_total={"rest.parse_body": 0.010, "program": 1.0}),
    "after": _snapshot(
    es_span_total={"program": 110, "batcher.follow": 44},
    es_span_seconds_total={"rest.parse_body": 0.110, "program": 11.0})}


def test_offcpu_share_is_wall_less_cpu_over_wall_of_the_spans_that_read():
    # the spans that read the CPU clock: wall 0.025 + 0.5 + 0.1 = 0.625 s,
    # CPU 0.013 + 0.25 + 0.075 = 0.338 s (not the 2.5 s of all the spans)
    got = span_offcpu_share.read(CTX, {"spans": HOST})
    assert got == pytest.approx(100 * (0.625 - 0.338) / 0.625)
    assert span_offcpu_share.read(CTX, {"spans": ["search.plan"]}) \
        == pytest.approx(25.0)


@pytest.mark.parametrize("ctx, spans", [
    (PARENT, HOST), (CTX, ["aggs.plan", "aggs.render"]),
    ({**CTX, "after": BEFORE}, HOST)],
    ids=["parent", "spans-never-opened", "nothing-moved"])
def test_offcpu_share_reads_nothing_without_cpu_time_or_wall(ctx, spans):
    assert span_offcpu_share.read(ctx, {"spans": spans}) is None


@pytest.mark.parametrize("name, want", [
    ("program_queue_ms.lat", 1000 * 3.0 / 100),
    ("dispatch_lock_wait_ms.dash", 1000 * 0.2 / 100),
    ("batcher_wake_ms.lat", 1000 * 0.08 / 40)])
def test_each_wait_is_its_span_per_occurrence(name, want):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "span_ms"
    assert span_ms.read(CTX, spec["params"]) == pytest.approx(want)
    # the parent opens `program` and `batcher.follow` but none of the
    # waits: `span_ms` reads 0 there, not nothing
    assert span_ms.read(PARENT, spec["params"]) == 0.0


NEW = {"host_offcpu_share.lat": ("host threads (common/tracing.py)",
                                 ["wiki.match-top10", "httplogs.dash-panels",
                                  "httplogs.dashboard-mesh"]),
       "program_queue_ms.lat": ("device (XLA programs, HBM)",
                                ["wiki.match-top10"]),
       "dispatch_lock_wait_ms.dash": (None, ["httplogs.dash-panels",
                                             "httplogs.dashboard-mesh"]),
       "batcher_wake_ms.lat": ("request batching (serving/batcher.py)",
                               ["wiki.match-top10", "httplogs.dash-panels",
                                "httplogs.dashboard-mesh"])}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_reads_what_this_pr_adds_where_it_is(name):
    entry, = [m for m in B["per_layer"] if m["name"] == name]
    layer, cells = NEW[name]
    if layer is None:       # the panel lane's own layer, letter for letter
        layer, = {m["layer"] for m in B["per_layer"]
                  if m["name"] == "agg_program_wall_ms.dash"}
    # a later cell may join the entry, and later entries follow it
    assert entry["layer"] == layer and set(cells) <= set(entry["workloads"])
    assert (entry["source"], entry["better"], entry["moves"]) \
        == ("program_span", "lower", "latency_p50_ms")
    assert entry["unit"] == ("%" if "share" in name else "ms")
    names = [m["name"] for m in B["per_layer"]]
    assert names.index(name) > names.index("packed_filtered_share.qps")
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    if name.startswith("host_offcpu_share"):
        assert spec == {"name": name, "reader": "span_offcpu_share",
                        "params": {"spans": HOST}}
