"""Strict OpenMetrics exposition tripwire (`GET /_metrics`).

A minimal parser validates the document's grammar (every family declared
with `# TYPE` before its samples, no duplicate family declarations,
counters end in `_total`, gauges never do, values parse as floats) and the
coverage assertions pin every registry — a new stats section that forgets
to join `NodeService.metric_sections()` fails here, not in production.
"""

import json
import re
import urllib.error
import urllib.request

import pytest

from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.rest import HttpServer

SAMPLE_RX = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'\{(?P<labels>[^}]*)\}\s+(?P<value>\S+)$')
LABEL_RX = re.compile(r'^([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"$')


def parse_openmetrics(text: str) -> dict:
    """-> {family: {"type": t, "help": h, "samples": [(labels, value)]}}.
    Raises AssertionError on any grammar violation."""
    assert text.endswith("# EOF\n"), "exposition must end with # EOF"
    families: dict = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            fam = families.setdefault(name, {"type": None, "help": None,
                                             "samples": []})
            assert fam["help"] is None, f"duplicate HELP for [{name}]"
            fam["help"] = line.split(None, 3)[3]
        elif line.startswith("# TYPE "):
            _, _, name, mtype = line.split()
            fam = families.setdefault(name, {"type": None, "help": None,
                                             "samples": []})
            assert fam["type"] is None, f"duplicate TYPE for [{name}]"
            assert not fam["samples"], \
                f"TYPE for [{name}] must precede its samples"
            assert mtype in ("counter", "gauge"), \
                f"unknown type [{mtype}] for [{name}]"
            fam["type"] = mtype
        elif line.startswith("#"):
            continue                        # free-form comment (EOF, notes)
        else:
            m = SAMPLE_RX.match(line)
            assert m, f"malformed sample line: {line!r}"
            name = m.group("name")
            assert name in families and families[name]["type"], \
                f"sample for undeclared family [{name}]"
            labels = {}
            for part in m.group("labels").split(","):
                lm = LABEL_RX.match(part)
                assert lm, f"malformed label in {line!r}"
                labels[lm.group(1)] = lm.group(2)
            value = float(m.group("value"))     # raises on junk
            families[name]["samples"].append((labels, value))
    for name, fam in families.items():
        assert fam["type"] is not None, f"[{name}] has HELP but no TYPE"
        assert fam["samples"], f"family [{name}] declared but empty"
        if fam["type"] == "counter":
            assert name.endswith("_total"), \
                f"counter [{name}] must end in _total"
            assert all(v >= 0 for _, v in fam["samples"]), \
                f"counter [{name}] has a negative sample"
        else:
            assert not name.endswith("_total"), \
                f"gauge [{name}] must not end in _total"
    return families


@pytest.fixture(scope="module")
def http(tmp_path_factory):
    node = NodeService(str(tmp_path_factory.mktemp("expo")))
    srv = HttpServer(node, port=0).start()
    base = f"http://127.0.0.1:{srv.port}"

    def req(method, path, body=None):
        data = json.dumps(body).encode() if body is not None else None
        r = urllib.request.Request(base + path, data=data, method=method)
        resp = urllib.request.urlopen(r)
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, raw.decode()

    # traffic so every subsystem has non-trivial samples
    req("PUT", "/expo", {"mappings": {"_doc": {"properties": {
        "body": {"type": "string"}}}}})
    for i in range(10):
        req("PUT", f"/expo/_doc/{i}", {"body": f"quick brown fox {i}"})
    req("POST", "/expo/_refresh")
    req("POST", "/expo/_search", {"query": {"match": {"body": "quick"}}})
    req("POST", "/expo/_search", {"query": {"match": {"body": "fox"}},
                                  "size": 0})
    req("GET", "/expo/_doc/1")
    yield node, req
    srv.stop()
    node.close()


def scrape(req):
    code, text = req("GET", "/_metrics")
    assert code == 200
    assert isinstance(text, str)
    return parse_openmetrics(text)


def test_exposition_is_valid_and_broad(http):
    node, req = http
    families = scrape(req)
    n_series = sum(len(f["samples"]) for f in families.values())
    subsystems = {name.split("_")[1] for name in families}
    # acceptance floor: ≥200 series (ISSUE-9 re-anchored it from 60 — the
    # fixture scrape measures ~247 once the qos/hedge/batcher registries
    # joined; a regression that silently drops a registry lands far below)
    assert n_series >= 200, f"only {n_series} series"
    for want in ("threadpool", "breaker", "search", "timer", "jit",
                 "transfer", "index", "tasks", "rate", "process", "os",
                 "cache", "tracing", "qos"):
        assert want in subsystems, f"subsystem [{want}] missing"
    # every sample carries the node label
    for fam in families.values():
        for labels, _ in fam["samples"]:
            assert labels.get("node") == "tpu-node-0"


def test_every_registry_is_scraped(http):
    """Drift guard: pools, breakers and histogram timers appear in the
    exposition with one sample per registered entry."""
    node, req = http
    families = scrape(req)

    pool_labels = {lb["pool"] for lb, _
                   in families["es_threadpool_rejected_total"]["samples"]}
    assert pool_labels == set(node.thread_pool.stats())

    breaker_labels = {lb["breaker"] for lb, _ in
                      families["es_breaker_estimated_size_bytes"]["samples"]}
    assert breaker_labels == set(node.breakers.stats())

    timer_labels = {lb["timer"] for lb, _
                    in families["es_timer_count_total"]["samples"]}
    assert timer_labels == set(node.metrics.stats())

    index_labels = {lb["index"] for lb, _
                    in families["es_index_docs"]["samples"]}
    assert index_labels == set(node.indices)

    cache_labels = {lb["cache"] for lb, _
                    in families["es_cache_hits_total"]["samples"]}
    assert cache_labels >= {"request", "query_plan", "fielddata"}
    # request-cache byte/eviction families ride the per-index section
    assert "es_index_request_cache_memory_bytes" in families
    assert "es_index_request_cache_evictions_total" in families

    # the tracing registry (ISSUE 5): counters typed as counters, live
    # gauges as gauges
    for fam, mtype in (("es_tracing_traces_started_total", "counter"),
                       ("es_tracing_dropped_traces_total", "counter"),
                       ("es_tracing_dropped_spans_total", "counter"),
                       ("es_tracing_spans_total", "counter"),
                       ("es_tracing_active_traces", "gauge"),
                       ("es_tracing_retained_traces", "gauge")):
        assert fam in families, fam
        assert families[fam]["type"] == mtype, fam


def test_blockwise_families_exposed(http):
    """ISSUE 8: the blockwise dispatch counter and the peak score-matrix
    gauge join the search section with the right metric types."""
    node, req = http
    families = scrape(req)
    assert families["es_search_blockwise_dispatches_total"]["type"] \
        == "counter"
    assert families["es_search_peak_score_matrix_bytes"]["type"] == "gauge"
    # the dense size=0 search in the fixture materialized SOME score state
    (_, peak), = families["es_search_peak_score_matrix_bytes"]["samples"]
    assert peak >= 0


def test_qos_families_exposed(http):
    """ISSUE 9: the serving-QoS registries ride the scrape — per-class
    shed/admission counters, the pressure gauges, hedge outcomes and the
    batcher anomaly counters, each with the right metric type."""
    node, req = http
    families = scrape(req)
    for fam, mtype in (("es_qos_shed_total", "counter"),
                       ("es_qos_admitted_total", "counter"),
                       ("es_qos_inflight", "gauge"),
                       ("es_qos_node_pressure", "gauge"),
                       ("es_search_hedged_total", "counter"),
                       ("es_search_batcher_stranded_total", "counter"),
                       ("es_search_batcher_wait_timeouts_total", "counter"),
                       ("es_search_batcher_run_errors_total", "counter")):
        assert fam in families, fam
        assert families[fam]["type"] == mtype, fam
    classes = {lb["class"] for lb, _
               in families["es_qos_shed_total"]["samples"]}
    assert classes == {"search", "bulk", "recovery", "state", "ping"}


def test_new_timer_joins_the_scrape_automatically(http):
    node, req = http
    node.metrics.record("custom.drift_guard", 1.25)
    families = scrape(req)
    timer_labels = {lb["timer"] for lb, _
                    in families["es_timer_count_total"]["samples"]}
    assert "custom.drift_guard" in timer_labels


def test_one_path_and_its_content(http):
    node, req = http
    code, a = req("GET", "/_metrics")
    assert code == 200
    # the `/_prometheus/metrics` alias is gone (ROADMAP D9): one path
    with pytest.raises(urllib.error.HTTPError) as gone:
        req("GET", "/_prometheus/metrics")
    assert gone.value.code in (400, 404, 405)
    # indexed docs + searches are visible in the scrape
    fams = parse_openmetrics(a)
    total = sum(v for _, v in fams["es_index_docs"]["samples"])
    assert total >= 10
    searches = sum(v for _, v
                   in fams["es_index_search_total"]["samples"])
    assert searches >= 2


def test_reverse_search_families_exposed(http):
    """ISSUE 18: the percolate dispatch ladder, the script-compile
    counter and the registry cache tier all join the scrape with the
    right types — and the script family is pre-seeded so the family is
    never declared-but-empty before the first compile."""
    node, req = http
    req("PUT", "/expo/.percolator/pq1",
        {"query": {"match": {"body": "quick"}}})
    req("POST", "/expo/_doc/_percolate", {"doc": {"body": "quick fox"}})
    req("POST", "/expo/_search", {"query": {"function_score": {
        "query": {"match": {"body": "fox"}},
        "script_score": {"script": "_score * 2.0"},
        "boost_mode": "replace"}}})
    families = scrape(req)
    for fam, mtype in (("es_search_percolate_dispatches_total", "counter"),
                       ("es_percolate_docs_total", "counter"),
                       ("es_percolate_matrix_cells_total", "counter"),
                       ("es_percolate_residual_queries_total", "counter"),
                       ("es_script_compiles_total", "counter")):
        assert fam in families, fam
        assert families[fam]["type"] == mtype, fam
    lanes = {lb["lane"]: v for lb, v in
             families["es_search_percolate_dispatches_total"]["samples"]}
    assert set(lanes) == {"dense", "loop", "mesh"}
    assert lanes["dense"] >= 1
    targets = {lb["target"] for lb, _ in
               families["es_script_compiles_total"]["samples"]}
    assert "function_score" in targets
    cache_labels = {lb["cache"] for lb, _
                    in families["es_cache_hits_total"]["samples"]}
    assert "percolator_registry" in cache_labels
