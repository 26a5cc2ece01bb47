"""Device telemetry + lane-decision flight recorder (ISSUE 16).

Covers the acceptance surface: `GET /_nodes/device_stats` is non-empty
after one search + one kNN query, with None-safe cost fields; the
`es_xla_program_*` / `es_device_hbm_*` / `es_search_lane_decisions_total`
families ride the strict OpenMetrics scrape with the right types (the
metric-exposure lint); a query forced down the fan-out yields profile
lane records whose decline reasons exactly match the counter family's
labels; two interleaved profiled requests never cross-contaminate their
lane records; and `?format=chrome` traces carry the ladder walk as lane
span events.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from test_metrics_exposition import parse_openmetrics

from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.rest import HttpServer

DENSE_BODY = {"size": 5, "query": {"bool": {
    "should": [{"match": {"body": "quick"}}, {"match": {"body": "fox"}}]}}}

KNN_BODY = {"size": 5, "knn": {"field": "vec",
                               "query_vector": [0.1] * 8, "k": 5}}


@pytest.fixture(scope="module")
def http(tmp_path_factory):
    node = NodeService(str(tmp_path_factory.mktemp("devstats")))
    srv = HttpServer(node, port=0).start()
    base = f"http://127.0.0.1:{srv.port}"

    def req(method, path, body=None):
        data = json.dumps(body).encode() if body is not None else None
        r = urllib.request.Request(base + path, data=data, method=method)
        try:
            resp = urllib.request.urlopen(r)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, raw.decode()

    mappings = {"_doc": {"properties": {
        "body": {"type": "string"},
        "vec": {"type": "dense_vector", "dims": 8}}}}
    # "ds" rides the default ladder (mesh on); "fan" is forced down the
    # per-shard fan-out, so its profile carries a mesh decline
    req("PUT", "/ds", {"settings": {"number_of_shards": 2},
                       "mappings": mappings})
    req("PUT", "/fan", {"settings": {"number_of_shards": 2,
                                     "index.search.mesh.enable": False},
                        "mappings": mappings})
    for i in range(30):
        doc = {"body": f"quick brown fox {i}",
               "vec": [((i * 7 + d) % 13) / 13.0 for d in range(8)]}
        req("PUT", f"/ds/_doc/{i}", doc)
        req("PUT", f"/fan/_doc/{i}", doc)
    req("POST", "/ds/_refresh")
    req("POST", "/fan/_refresh")
    # the acceptance preamble: ONE search + ONE kNN query
    req("POST", "/ds/_search", DENSE_BODY)
    req("POST", "/ds/_search", KNN_BODY)
    yield node, req
    srv.stop()
    node.close()


# -- GET /_nodes/device_stats ------------------------------------------------

def test_device_stats_nonempty_after_search_and_knn(http):
    """Acceptance: after one search + one kNN query the program registry
    is non-empty, costs are present (float or None — never an error) and
    the HBM + lane blocks are shape-stable on CPU."""
    node, req = http
    code, out = req("GET", "/_nodes/device_stats")
    assert code == 200
    payload = out["nodes"]["tpu-node-0"]
    progs = payload["programs"]
    assert progs["program_count"] > 0
    assert progs["invocations_total"] >= 1
    assert progs["device_time_in_millis"] > 0
    assert progs["programs"], "top-N program list is empty"
    for p in progs["programs"]:
        for key in ("name", "key", "invocations", "device_time_in_millis",
                    "compile_time_in_millis", "compiles", "flops",
                    "bytes_accessed"):
            assert key in p, f"[{key}] missing from {p}"
        assert p["flops"] is None or isinstance(p["flops"], float)
        assert p["bytes_accessed"] is None \
            or isinstance(p["bytes_accessed"], float)
    # top-N ordering: cumulative device time, descending
    times = [p["device_time_in_millis"] for p in progs["programs"]]
    assert times == sorted(times, reverse=True)
    # HBM block: one entry per device, zeros-with-supported=False on CPU
    assert payload["hbm"], "no devices polled"
    for ident, st in payload["hbm"].items():
        assert ":" in ident
        for key in ("bytes_in_use", "peak_bytes", "high_water_bytes",
                    "limit_bytes", "supported"):
            assert key in st
    # the ladder walked at least once
    assert payload["lane_decisions"]
    assert all(":" in k for k in payload["lane_decisions"])


def test_device_stats_top_n_param(http):
    node, req = http
    code, out = req("GET", "/_nodes/device_stats?top_n=1")
    assert code == 200
    progs = out["nodes"]["tpu-node-0"]["programs"]
    assert len(progs["programs"]) == 1
    # rollups still cover the whole registry
    assert progs["program_count"] > 1


# -- metric-exposure lint (satellite a) --------------------------------------

def _scrape(req):
    code, text = req("GET", "/_metrics")
    assert code == 200 and isinstance(text, str)
    return parse_openmetrics(text)


def test_xla_program_families_exposed(http):
    node, req = http
    families = _scrape(req)
    for fam, mtype in (("es_xla_program_invocations_total", "counter"),
                       ("es_xla_program_device_time_millis_total",
                        "counter"),
                       ("es_xla_program_compile_time_millis_total",
                        "counter"),
                       ("es_xla_program_compiles_total", "counter"),
                       ("es_xla_program_programs", "gauge")):
        assert fam in families, fam
        assert families[fam]["type"] == mtype, fam
    sites = {lb["program"] for lb, _
             in families["es_xla_program_invocations_total"]["samples"]}
    assert sites, "no program sites labeled"
    # the fixture's searches dispatched SOMETHING through the registry
    total = sum(v for _, v
                in families["es_xla_program_invocations_total"]["samples"])
    assert total >= 1


def test_device_hbm_families_exposed(http):
    node, req = http
    families = _scrape(req)
    for fam in ("es_device_hbm_bytes_in_use", "es_device_hbm_peak_bytes",
                "es_device_hbm_high_water_bytes",
                "es_device_hbm_limit_bytes"):
        assert fam in families, fam
        assert families[fam]["type"] == "gauge", fam
    devs = {lb["device"] for lb, _
            in families["es_device_hbm_bytes_in_use"]["samples"]}
    assert devs, "no device labels"
    import jax
    assert len(devs) == len(jax.devices())


def test_lane_decision_family_exposed(http):
    node, req = http
    families = _scrape(req)
    fam = families["es_search_lane_decisions_total"]
    assert fam["type"] == "counter"
    for labels, v in fam["samples"]:
        assert "lane" in labels and "reason" in labels, labels
        assert v >= 1
    lanes = {lb["lane"] for lb, _ in fam["samples"]}
    assert lanes, "ladder never recorded a decision"


# -- profile <-> counter parity (acceptance) ---------------------------------

def _lane_samples(families):
    return {(lb["lane"], lb["reason"]): v for lb, v
            in families["es_search_lane_decisions_total"]["samples"]}


def test_forced_fanout_profile_matches_counters(http):
    """A query forced down the fan-out (mesh opt-out index) yields
    profile lane records whose (lane, reason) pairs EXACTLY match the
    labels the counter family incremented for this request."""
    node, req = http
    before = _lane_samples(_scrape(req))
    code, out = req("POST", "/fan/_search",
                    {**json.loads(json.dumps(DENSE_BODY)), "profile": True})
    assert code == 200
    lanes = out["profile"]["lanes"]
    assert lanes, "profiled request recorded no lane decisions"
    seen = set()
    for comp in lanes:
        for d in comp["declines"]:
            seen.add((d["lane"], d["reason"]))
        if comp["lane"] is not None:
            seen.add((comp["lane"], "chosen"))
    # the mesh lane declined with the opt-out reason, by name
    assert ("mesh", "opt_out") in seen, lanes
    # some lane served the query
    assert any(r == "chosen" for _, r in seen), lanes
    after = _lane_samples(_scrape(req))
    for key in seen:
        assert after.get(key, 0) - before.get(key, 0) >= 1, \
            f"profile recorded {key} but the counter family did not move"


def test_profile_device_section_has_programs(http):
    node, req = http
    code, out = req("POST", "/ds/_search",
                    {**json.loads(json.dumps(DENSE_BODY)), "profile": True})
    assert code == 200
    dev = out["profile"]["device"]
    assert "programs" in dev
    for name, rec in dev["programs"].items():
        assert isinstance(name, str)
        assert rec["invocations"] >= 1
        assert rec["device_time_in_millis"] >= 0


# -- recorder concurrency (satellite d) --------------------------------------

def test_interleaved_requests_do_not_cross_contaminate(http):
    """Two concurrent profiled requests — one text on the fan-out index,
    one kNN — must each see ONLY their own ladder walk: the recorder is
    contextvar-scoped per request, shared by reference only across that
    request's shard jobs."""
    node, req = http
    results: dict = {}
    barrier = threading.Barrier(2)

    def run(tag, path, body):
        barrier.wait()
        for _ in range(4):
            code, out = req("POST", path,
                            {**json.loads(json.dumps(body)),
                             "profile": True})
            assert code == 200
            comps = {c["component"] for c in out["profile"]["lanes"]}
            results.setdefault(tag, []).append(comps)

    t1 = threading.Thread(
        target=run, args=("text", "/fan/_search", DENSE_BODY))
    t2 = threading.Thread(target=run, args=("knn", "/ds/_search", KNN_BODY))
    t1.start(); t2.start(); t1.join(); t2.join()
    for comps in results["text"]:
        assert not any("knn" in c for c in comps), \
            f"text request saw kNN lane records: {comps}"
    for comps in results["knn"]:
        assert any("knn" in c for c in comps), \
            f"kNN request lost its own lane records: {comps}"
        assert not any(c.endswith(".query") for c in comps), \
            f"kNN request saw text-query lane records: {comps}"


# -- lane events on traces (satellite d) -------------------------------------

def test_chrome_trace_carries_lane_events(http):
    node, req = http
    code, _ = req("POST", "/fan/_search?trace=true",
                  json.loads(json.dumps(DENSE_BODY)))
    assert code == 200
    code, lst = req("GET", "/_traces")
    assert code == 200
    tid = next(t["trace_id"] for t in lst["traces"]
               if "/fan/_search" in t["root"])
    code, ch = req("GET", f"/_traces/{tid}?format=chrome")
    assert code == 200
    lane_events = [e for e in ch["traceEvents"]
                   if e.get("name") == "lane" and e["ph"] == "X"]
    assert lane_events, "trace carries no lane span events"
    for e in lane_events:
        assert "component" in e["args"] and "lane" in e["args"] \
            and "reason" in e["args"], e
    assert any(e["args"]["lane"] == "mesh"
               and e["args"]["reason"] == "opt_out" for e in lane_events)
    assert any(e["args"]["reason"] == "chosen" for e in lane_events)


# -- sampler ring gauges -----------------------------------------------------

def test_sampler_carries_hbm_gauges(http):
    node, req = http
    snap = node._sampler_snapshot()
    assert "hbm_bytes_in_use" in snap
    assert "hbm_peak_bytes" in snap
    # CPU backend: zeros, never an error
    assert snap["hbm_bytes_in_use"] >= 0


def test_in_trace_calls_pass_through_top_level_calls_count():
    """An instrumented program called under an outer jit is part of that
    trace, not a dispatch: it must not be counted (or synced). The same
    program called from eager code is."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.common.device_stats import instrument

    inner = instrument("test:inner", jax.jit(lambda x: x * 2.0),
                       key="in_trace")
    x = jnp.arange(4.0)
    outer = jax.jit(lambda v: inner(v) + 1.0)
    assert outer(x).tolist() == [1.0, 3.0, 5.0, 7.0]
    assert jax.vmap(inner)(x[:, None]).shape == (4, 1)
    assert inner.record.invocations == 0
    assert inner(x).tolist() == [0.0, 2.0, 4.0, 6.0]
    assert inner.record.invocations == 1
    assert inner.record.compiles >= 1
