"""Mesh-sharded query lane (ISSUE 6): equivalence vs the fan-out,
single-fetch/zero-host-merge counters, the mesh-stack cache lifecycle
and the fallback ladder.

The mesh lane replaces the coordinator's thread-pool fan-out (S device
fetches + a host-side cross-shard merge per multi-shard query) with ONE
shard_map program over the ("replica", "shard") mesh: per-shard stacked
execution, in-shard merge AND the cross-shard top-k reduce fused on
device. These tests pin the contract:

  * mesh results are bitwise-identical to the concurrent fan-out across
    the mesh-native query-shape matrix (same stable merge order, same
    score dtype promotion);
  * a multi-shard mesh query performs exactly ONE device_fetch and ZERO
    host-side per-shard merges (counter-asserted);
  * the mesh stack is fielddata-breaker-charged and invalidated by
    refresh/merge/`_cache/clear`/close;
  * the fallback ladder — sorted bodies, unsupported plans, opt-out
    settings, more shards than devices, oversized/declined stacks,
    cross-host clusters — lands on the fan-out, never errors.
"""

import json

import numpy as np
import pytest

from elasticsearch_tpu.node import NodeService

N_SHARDS = 4
WORDS = ["quick", "brown", "fox", "jumps", "lazy", "dog", "sleeps",
         "swift", "river", "stone"]

# mesh-native query shapes (every node type with a typed mesh handler)
MESH_QUERIES = [
    {"match_all": {}},
    {"bool": {"should": [{"match": {"body": "fox"}},
                         {"match": {"body": "dog"}}]}},
    {"bool": {"should": [{"match": {"body": "quick"}}],
              "filter": [{"range": {"n": {"gte": 2, "lt": 60}}}]}},
    {"term": {"tag": "t1"}},
    {"terms": {"tag": ["t0", "t2"]}},
    {"term": {"n": 4}},
    {"term": {"price": 6.5}},
    {"range": {"n": {"gt": 30}}},
    {"range": {"price": {"gte": 2.0, "lt": 50.0}}},
    {"range": {"tag": {"gte": "t0", "lte": "t1"}}},
    {"exists": {"field": "price"}},
    {"exists": {"field": "body"}},
    {"ids": {"values": ["1", "5", "8", "77"]}},
    {"ids": {"values": ["zzz-absent"]}},
    {"constant_score": {"filter": {"term": {"tag": "t1"}}, "boost": 2.5}},
    {"dis_max": {"queries": [{"match": {"body": "fox"}},
                             {"match": {"body": "dog"}}],
                 "tie_breaker": 0.4}},
    {"bool": {"must": [{"match": {"body": "fox"}}],
              "must_not": [{"term": {"tag": "t2"}}],
              "should": [{"match": {"body": "brown"}}]}},
    {"bool": {"should": [{"match": {"body": {"query": "fox brown",
                                             "operator": "and"}}}]}},
    {"bool": {"should": [{"match": {"body": "quick"}},
                         {"match": {"body": "river"}}],
              "minimum_should_match": 2}},
    # one term, and a term no shard holds: every shard's candidates are
    # padding and the cross-shard reduce must still agree (total 0, no hit)
    {"bool": {"should": [{"match": {"body": "quick"}}]}},
    {"bool": {"should": [{"match": {"body": "zzzabsent"}}]}},
]

DENSE_Q = {"size": 5, "query": {"bool": {
    "should": [{"match": {"body": "quick"}}, {"match": {"body": "fox"}}]}}}

MAPPING = {"_doc": {"properties": {
    "body": {"type": "string"},
    "tag": {"type": "string", "index": "not_analyzed"},
    "n": {"type": "long"},
    "price": {"type": "double"}}}}


def _fill(n, names, shards=N_SHARDS, rounds=3, per_round=16):
    for name in names:
        if name not in n.indices:
            n.create_index(name, settings={"number_of_shards": shards},
                           mappings=MAPPING)
    di = 0
    for _ in range(rounds):
        for _ in range(per_round):
            doc = {"body": f"{WORDS[di % 10]} {WORDS[(di * 3 + 1) % 10]} "
                           f"{WORDS[(di * 7 + 2) % 10]}",
                   "tag": f"t{di % 3}", "n": di}
            if di % 2 == 0:
                doc["price"] = di / 2.0
            for name in names:
                n.index_doc(name, str(di), dict(doc))
            di += 1
        for name in names:
            n.refresh(name)
    return di


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    """Two identical 4-shard corpora: "m" on the mesh lane, "f" pinned to
    the concurrent fan-out (`index.search.mesh.enable: false`). Same doc
    ids -> same routing -> identical shard layouts."""
    n = NodeService(str(tmp_path_factory.mktemp("mesh")))
    n.create_index("m", settings={"number_of_shards": N_SHARDS},
                   mappings=MAPPING)
    n.create_index("f", settings={"number_of_shards": N_SHARDS,
                                  "index.search.mesh.enable": False},
                   mappings=MAPPING)
    _fill(n, ["m", "f"])
    yield n
    n.close()


def _hits(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def _search(n, name, q, **extra):
    return n.search(name, json.loads(json.dumps(
        {"size": 10, "query": q, **extra})))


class TestMeshEquivalence:
    @pytest.mark.parametrize("q", MESH_QUERIES,
                             ids=[json.dumps(q)[:48] for q in MESH_QUERIES])
    def test_bitwise_identical_to_fanout(self, pair, q):
        n = pair
        before = n.indices["m"].search_stats.get("mesh", 0)
        got = _search(n, "m", q)
        assert n.indices["m"].search_stats.get("mesh", 0) == before + 1, \
            f"mesh lane did not engage for {q}"
        want = _search(n, "f", q)
        assert n.indices["f"].search_stats.get("mesh", 0) == 0
        assert got["hits"]["total"] == want["hits"]["total"], q
        assert got["hits"]["max_score"] == want["hits"]["max_score"], q
        assert _hits(got) == _hits(want), q

    def test_deep_pagination_identical(self, pair):
        n = pair
        q = {"match_all": {}}
        got = _search(n, "m", q, size=40, **{"from": 5})
        want = _search(n, "f", q, size=40, **{"from": 5})
        assert _hits(got) == _hits(want)
        assert got["hits"]["total"] == want["hits"]["total"]
        assert len(got["hits"]["hits"]) == 40

    def test_tombstones_identical(self, pair):
        n = pair
        for name in ("m", "f"):
            n.delete_doc(name, "7")
            n.refresh(name)
        q = {"bool": {"should": [{"match": {"body": "fox"}},
                                 {"match": {"body": "dog"}}]}}
        got = _search(n, "m", q, size=96)
        want = _search(n, "f", q, size=96)
        assert _hits(got) == _hits(want)
        assert "7" not in [h for h, _s in _hits(got)]

    def test_shards_section_all_successful(self, pair):
        out = _search(pair, "m", {"match_all": {}})
        assert out["_shards"] == {"total": N_SHARDS,
                                  "successful": N_SHARDS, "failed": 0}


class TestMeshCounters:
    def test_one_fetch_zero_host_merges(self, pair):
        from elasticsearch_tpu.common.metrics import (host_merge_count,
                                                      transfer_snapshot)
        n = pair
        n.search("m", json.loads(json.dumps(DENSE_Q)))        # warm
        f0 = transfer_snapshot()["device_fetches_total"]
        h0 = host_merge_count()
        n.search("m", json.loads(json.dumps(DENSE_Q)))
        assert transfer_snapshot()["device_fetches_total"] - f0 == 1, \
            "a multi-shard mesh query must pay exactly ONE device fetch"
        assert host_merge_count() - h0 == 0, \
            "the mesh lane must not run the host-side cross-shard merge"

    def test_fanout_pays_per_shard(self, pair):
        from elasticsearch_tpu.common.metrics import (host_merge_count,
                                                      transfer_snapshot)
        n = pair
        n.search("f", json.loads(json.dumps(DENSE_Q)))        # warm
        f0 = transfer_snapshot()["device_fetches_total"]
        h0 = host_merge_count()
        n.search("f", json.loads(json.dumps(DENSE_Q)))
        assert transfer_snapshot()["device_fetches_total"] - f0 == N_SHARDS
        assert host_merge_count() - h0 == 1

    def test_profile_query_paths_mesh(self, pair):
        out = pair.search("m", {"profile": True,
                                **json.loads(json.dumps(DENSE_Q))})
        assert out["profile"]["device"]["query_paths"].get("mesh", 0) == 1

    def test_trace_mesh_reduce_span(self, pair):
        n = pair
        with n.tracer.request("mesh-span-test", force=True):
            n.search("m", json.loads(json.dumps(DENSE_Q)))
        trace = n.tracer.list()[0]
        full = n.tracer.get(trace["trace_id"])
        assert any(s["name"] == "mesh_reduce" for s in full["spans"])
        # zero shard fan-out subtrees: the collective replaced them
        assert not any(s["name"] == "shard" for s in full["spans"])


class TestFallbackLadder:
    def test_sorted_rides_the_mesh_but_score_sort_declines(self, pair):
        """ISSUE 17: encoded-key sorts no longer decline the mesh — the
        cross-shard merge ranks by key on device. Sorts the encoding
        can't bitwise-reproduce (a `_score` key) still fall back."""
        n = pair
        before = n.indices["m"].search_stats.get("mesh", 0)
        body = {"size": 10, "query": {"match_all": {}},
                "sort": [{"n": {"order": "desc"}}]}
        out = n.search("m", json.loads(json.dumps(body)))
        ids = [h["_id"] for h in out["hits"]["hits"]]
        assert ids == sorted(ids, key=int, reverse=True)[:len(ids)]
        assert n.indices["m"].search_stats.get("mesh", 0) == before + 1
        before = n.indices["m"].search_stats.get("mesh", 0)
        declined = {"size": 10, "query": {"match": {"body": "quick"}},
                    "sort": [{"n": "asc"}, "_score"]}
        n.search("m", json.loads(json.dumps(declined)))
        assert n.indices["m"].search_stats.get("mesh", 0) == before

    def test_unsupported_plan_falls_back(self, pair):
        n = pair
        before = n.indices["m"].search_stats.get("mesh", 0)
        out = _search(n, "m", {"prefix": {"body": "qu"}})
        assert out["hits"]["total"] > 0
        assert n.indices["m"].search_stats.get("mesh", 0) == before

    def test_supported_aggs_ride_the_mesh(self, pair):
        """ISSUE 11: terms/histogram/metric aggs no longer decline — the
        partials collect INSIDE the mesh program and merge identically to
        the fan-out's per-shard collect."""
        n = pair
        body = {"size": 5, "query": {"match_all": {}},
                "aggs": {"tags": {"terms": {"field": "tag"}},
                         "ns": {"histogram": {"field": "n",
                                              "interval": 10}},
                         "ps": {"stats": {"field": "price"}}}}
        before = n.indices["m"].search_stats.get("mesh_agg_dispatches", 0)
        got = n.search("m", json.loads(json.dumps(body)),
                       request_cache=False)
        assert n.indices["m"].search_stats.get("mesh_agg_dispatches", 0) \
            == before + 1
        want = n.search("f", json.loads(json.dumps(body)),
                        request_cache=False)
        assert got["aggregations"] == want["aggregations"]
        assert _hits(got) == _hits(want)
        assert got["hits"]["total"] == want["hits"]["total"]

    def test_unsupported_aggs_fall_back(self, pair):
        """Specs without a mesh form (HLL cardinality, sub-aggs) keep the
        fan-out — counted as mesh_agg_fallbacks."""
        n = pair
        before = n.indices["m"].search_stats.get("mesh", 0)
        fb = n.indices["m"].search_stats.get("mesh_agg_fallbacks", 0)
        body = {"size": 0, "query": {"match_all": {}},
                "aggs": {"card": {"cardinality": {"field": "tag"}}}}
        out = n.search("m", json.loads(json.dumps(body)),
                       request_cache=False)
        assert out["aggregations"]["card"]["value"] == 3
        assert n.indices["m"].search_stats.get("mesh", 0) == before
        assert n.indices["m"].search_stats.get("mesh_agg_fallbacks", 0) \
            == fb + 1

    def test_more_shards_than_devices_falls_back(self, tmp_path):
        import jax
        n = NodeService(str(tmp_path / "wide"))
        try:
            shards = len(jax.devices()) * 2     # S_pad > device count
            n.create_index("w", settings={"number_of_shards": shards},
                           mappings=MAPPING)
            for i in range(32):
                n.index_doc("w", str(i), {"body": f"quick fox {i}", "n": i})
            n.refresh("w")
            out = n.search("w", json.loads(json.dumps(DENSE_Q)))
            assert out["hits"]["total"] > 0
            assert n.indices["w"].search_stats.get("mesh", 0) == 0
        finally:
            n.close()

    def test_oversized_stack_declined(self, tmp_path):
        from elasticsearch_tpu.common.settings import Settings
        n = NodeService(str(tmp_path / "tiny"),
                        settings=Settings({"indices.mesh.cache.size": 64}))
        try:
            _fill(n, ["t"], rounds=2, per_round=8)
            out = n.search("t", json.loads(json.dumps(DENSE_Q)))
            assert out["hits"]["total"] > 0
            assert n.indices["t"].search_stats.get("mesh", 0) == 0
            assert n.caches.mesh_stacks.stats()["oversized"] >= 1
        finally:
            n.close()

    def test_node_level_opt_out(self, tmp_path):
        from elasticsearch_tpu.common.settings import Settings
        n = NodeService(str(tmp_path / "off"), settings=Settings(
            {"node.search.mesh.enable": False}))
        try:
            _fill(n, ["t"], rounds=2, per_round=8)
            out = n.search("t", json.loads(json.dumps(DENSE_Q)))
            assert out["hits"]["total"] > 0
            assert n.indices["t"].search_stats.get("mesh", 0) == 0
        finally:
            n.close()

    def test_cross_host_cluster_falls_back(self, tmp_path):
        """Shards spread over cluster nodes never see the mesh lane: the
        cluster driver fans out over the transport and merges host-side
        (the inter-host RPC half of SURVEY §5.8's topology)."""
        from elasticsearch_tpu.cluster import TestCluster
        from elasticsearch_tpu.parallel import mesh_exec
        cluster = TestCluster(2, str(tmp_path / "cluster"))
        try:
            client = cluster.client()
            client.create_index("docs", {"number_of_shards": 2,
                                         "number_of_replicas": 0})
            cluster.ensure_green()
            for i in range(20):
                client.index_doc("docs", str(i),
                                 {"body": f"quick brown fox {i}"})
            client.refresh("docs")
            st0 = mesh_exec.program_cache_stats()
            lookups0 = st0["hits_total"] + st0["misses_total"]
            out = client.search("docs", json.loads(json.dumps(DENSE_Q)))
            assert out["hits"]["total"] == 20
            st1 = mesh_exec.program_cache_stats()
            assert st1["hits_total"] + st1["misses_total"] == lookups0, \
                "no mesh program may run for cluster-spread shards"
        finally:
            cluster.close()


@pytest.fixture()
def node(tmp_path):
    n = NodeService(str(tmp_path / "node"))
    yield n
    n.close()


class TestMeshStackCache:
    def test_breaker_charged_and_released(self, node):
        _fill(node, ["t"])
        br = node.breakers.breaker("fielddata")
        used0 = br.used
        node.search("t", json.loads(json.dumps(DENSE_Q)))
        st = node.caches.mesh_stacks.stats()
        assert st["entries"] == 1
        assert st["memory_size_in_bytes"] > 0
        assert br.used >= used0 + st["memory_size_in_bytes"]
        cleared = node.caches.clear(query=True)
        assert cleared["mesh_stack"] == 1
        assert node.caches.mesh_stacks.stats()["entries"] == 0
        assert br.used <= used0 + 1

    def test_refresh_invalidates(self, node):
        _fill(node, ["t"])
        node.search("t", json.loads(json.dumps(DENSE_Q)))
        assert node.caches.mesh_stacks.stats()["entries"] == 1
        node.index_doc("t", "zzz", {"body": "new doc", "n": 999})
        node.refresh("t")
        assert node.caches.mesh_stacks.stats()["entries"] == 0
        node.search("t", json.loads(json.dumps(DENSE_Q)))
        assert node.caches.mesh_stacks.stats()["entries"] == 1

    def test_merge_invalidates(self, node):
        _fill(node, ["t"])
        node.search("t", json.loads(json.dumps(DENSE_Q)))
        node.force_merge("t")
        assert node.caches.mesh_stacks.stats()["entries"] == 0
        out = node.search("t", json.loads(json.dumps(DENSE_Q)))
        assert out["hits"]["total"] > 0

    def test_cache_clear_http(self, node):
        import http.client

        from elasticsearch_tpu.rest import HttpServer
        _fill(node, ["t"])
        node.search("t", json.loads(json.dumps(DENSE_Q)))
        assert node.caches.mesh_stacks.stats()["entries"] == 1
        server = HttpServer(node, port=0).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", server.port)
            conn.request("POST", "/t/_cache/clear?query=true")
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert resp.status == 200
            assert out["cleared"]["mesh_stack"] == 1
        finally:
            server.stop()
        assert node.caches.mesh_stacks.stats()["entries"] == 0

    def test_index_close_clears(self, node):
        _fill(node, ["t"])
        node.search("t", json.loads(json.dumps(DENSE_Q)))
        assert node.caches.mesh_stacks.stats()["entries"] == 1
        node.close_index("t")
        assert node.caches.mesh_stacks.stats()["entries"] == 0

    def test_delete_serves_via_liveness_not_rebuild(self, node):
        _fill(node, ["t"])
        out1 = node.search("t", json.loads(json.dumps(DENSE_Q)))
        total1 = out1["hits"]["total"]
        victim = out1["hits"]["hits"][0]["_id"]
        node.delete_doc("t", victim)
        node.indices["t"].refresh()
        out2 = node.search("t", json.loads(json.dumps(DENSE_Q)))
        assert out2["hits"]["total"] == total1 - 1
        assert victim not in [h["_id"] for h in out2["hits"]["hits"]]


class TestMeshMetrics:
    def test_scrape_families_and_sampler(self, node):
        _fill(node, ["t"])
        node.search("t", json.loads(json.dumps(DENSE_Q)))
        from elasticsearch_tpu.common.metrics import render_openmetrics
        text = render_openmetrics(node.metric_sections())
        assert "es_search_mesh_dispatches_total" in text
        assert "es_search_host_merges_total" in text
        assert 'cache="mesh_stack"' in text
        snap = node._sampler_snapshot()
        assert snap["mesh_stack_cache_memory_bytes"] > 0
        assert node.stats()["caches"]["mesh_stack"]["entries"] == 1


def test_dryrun_multichip_rides_the_mesh_lane(capsys):
    """The driver's multi-chip dry run (`__graft_entry__`, called from
    outside this repo): 8 virtual CPU devices in a child process, a
    2 x 4 mesh, `match` bodies answered by the mesh lane as the fan-out
    answers them."""
    import ast
    import re

    import __graft_entry__
    __graft_entry__.dryrun_multichip(8)
    out = capsys.readouterr().out
    ok = re.search(r"^dryrun_multichip ok: mesh=(\{.*\}) totals=(\[.*\])$",
                   out, re.M)
    assert ok, out
    assert ast.literal_eval(ok[1]) == {"replica": 2, "shard": 4}
    totals = ast.literal_eval(ok[2])
    assert len(totals) == 4 and all(t > 0 for t in totals)


class TestMeshKnn:
    """IVF kNN through the mesh program (ISSUE 11): one collective
    program + one fetch for a multi-shard kNN body, bitwise-identical to
    the per-shard fan-out; exact/mixed lanes keep the fan-out."""

    D = 8

    @pytest.fixture(scope="class")
    def knn_pair(self, tmp_path_factory):
        n = NodeService(str(tmp_path_factory.mktemp("meshknn")))
        mapping = {"_doc": {"properties": {
            "body": {"type": "string"},
            "tag": {"type": "string", "index": "not_analyzed"},
            "vec": {"type": "dense_vector", "dims": self.D}}}}
        base = {"number_of_shards": 4, "index.knn.ivf.nlist": 8,
                "index.knn.ivf.min_docs": 16, "index.knn.precision": "f32"}
        n.create_index("vm", settings=dict(base), mappings=mapping)
        n.create_index("vf", settings={**base,
                                       "index.search.mesh.enable": False},
                       mappings=mapping)
        rng = np.random.RandomState(11)
        for i in range(360):
            doc = {"body": f"w{i % 7}", "tag": f"t{i % 3}",
                   "vec": [float(x) for x in rng.randn(self.D)]}
            for name in ("vm", "vf"):
                n.index_doc(name, str(i), dict(doc))
        for name in ("vm", "vf"):
            n.refresh(name)
        n._qv = [float(x) for x in rng.randn(self.D)]
        yield n
        n.close()

    def _both(self, n, knn, size=10):
        body = {"size": size, "knn": knn}
        got = n.search("vm", json.loads(json.dumps(body)))
        want = n.search("vf", json.loads(json.dumps(body)))
        return _hits(got), _hits(want), got, want

    @pytest.mark.parametrize("metric", ["cosine", "dot", "l2"])
    def test_ivf_knn_bitwise_identical(self, knn_pair, metric):
        n = knn_pair
        before = n.indices["vm"].search_stats.get("mesh_ann_dispatches", 0)
        g, w, got, want = self._both(
            n, {"field": "vec", "query_vector": n._qv, "k": 10,
                "metric": metric})
        assert n.indices["vm"].search_stats.get(
            "mesh_ann_dispatches", 0) == before + 1
        assert g == w
        assert got["hits"]["total"] == want["hits"]["total"]
        assert got["hits"]["max_score"] == want["hits"]["max_score"]

    def test_filtered_knn_identical(self, knn_pair):
        n = knn_pair
        g, w, *_ = self._both(
            n, {"field": "vec", "query_vector": n._qv, "k": 10,
                "filter": {"term": {"tag": "t1"}}}, size=5)
        assert g == w

    def test_one_fetch_for_the_whole_index(self, knn_pair):
        from elasticsearch_tpu.common.metrics import transfer_snapshot
        n = knn_pair
        body = {"size": 10, "knn": {"field": "vec",
                                    "query_vector": n._qv, "k": 10}}
        n.search("vm", json.loads(json.dumps(body)))          # warm
        f0 = transfer_snapshot()["device_fetches_total"]
        n.search("vm", json.loads(json.dumps(body)))
        assert transfer_snapshot()["device_fetches_total"] - f0 == 1

    def test_exact_pinned_falls_back(self, knn_pair):
        n = knn_pair
        fb0 = n.indices["vm"].search_stats.get("mesh_ann_fallbacks", 0)
        g, w, *_ = self._both(
            n, {"field": "vec", "query_vector": n._qv, "k": 10,
                "exact": True})
        assert g == w
        assert n.indices["vm"].search_stats.get(
            "mesh_ann_fallbacks", 0) == fb0 + 1

    @pytest.mark.parametrize("n_queries", [1, 3, 5])
    def test_batch_the_replica_axis_does_not_divide(self, knn_pair,
                                                    n_queries):
        """Q not divisible by the replica axis pads with all-zero query
        vectors: the pad rows must stay inside the program (never NaN
        through cosine 0/0) and the [:Q] rows come back NaN-free, with
        real doc keys, each as its own solo search answers."""
        from elasticsearch_tpu.parallel import mesh_knn
        n = knn_pair
        svc = n.indices["vm"]
        searchers = svc.searchers()
        vstack = n.caches.mesh_vector_stacks.get_or_build(
            "vm", svc._incarnation, "vec",
            [list(s.segments) for s in searchers],
            breaker=n.breakers.breaker("fielddata"), pool=n.device_pool)
        assert vstack.n_replicas == 2 and n_queries % 2 == 1
        qv = np.random.RandomState(5).randn(n_queries, self.D) \
            .astype(np.float32)

        def run(vectors):
            return mesh_knn.execute(
                vstack, vectors, k=5, metric="cosine",
                knn_opts=searchers[0].knn_opts, nprobe=None, exact=False,
                acquire_ivf=lambda si, seg, vc: searchers[si]._acquire_ivf(
                    seg, vc, "vec", None, False))

        keys, shard_of, scores, totals, _mx, used_ivf, _q = run(qv)
        assert used_ivf
        assert keys.shape == shard_of.shape == scores.shape == (n_queries, 5)
        assert totals.shape == (4, n_queries)
        assert not np.isnan(scores).any()
        assert (keys >= 0).all()
        for qi in range(n_queries):
            k1, s1, sc1, *_ = run(qv[qi])
            assert np.array_equal(keys[qi], k1[0]), qi
            assert np.array_equal(shard_of[qi], s1[0]), qi
            assert np.array_equal(scores[qi], sc1[0]), qi
