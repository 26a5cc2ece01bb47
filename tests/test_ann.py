"""IVF-clustered ANN vector serving (ISSUE 10): recall vs a numpy
brute-force oracle, nprobe sweep monotonicity, nprobe>=nlist bitwise-exact
parity with the exact kernel, the fallback ladder, tombstones, the
breaker-charged cluster-index cache tier, hybrid `"rank"` fusion (RRF +
weighted), the LM similarity providers, `index.knn.precision`, and the
refresh→query zero-retrace tripwire."""

import json

import numpy as np
import pytest

from elasticsearch_tpu.mapping.mapper import MapperService
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.search.shard_searcher import LOCAL_MASK, ShardSearcher

DIMS = 16
N_DOCS = 2048
N_TOPICS = 8
OPTS = {"min_docs": 256, "nlist": 32, "nprobe": 16}

MAPPING = {"_doc": {"properties": {
    "body": {"type": "string"},
    "vec": {"type": "dense_vector", "dims": DIMS},
    "cat": {"type": "keyword"},
}}}


def clustered_vecs(n, dims=DIMS, topics=N_TOPICS, seed=0, sigma=0.1):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 1, (topics, dims)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    topic = rng.integers(0, topics, n)
    v = centers[topic] + sigma * rng.normal(0, 1, (n, dims)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v.astype(np.float32), topic


def local_ids(result, row=0):
    return [int(k) & LOCAL_MASK for k in result.doc_keys[row] if k >= 0]


def recall_at(result, oracle, k=10):
    hits = 0
    want = 0
    for qi in range(result.doc_keys.shape[0]):
        got = set(local_ids(result, qi)[:k])
        w = set(oracle[qi][:k].tolist())
        hits += len(got & w)
        want += len(w)
    return hits / max(want, 1)


@pytest.fixture(scope="module")
def corpus():
    vecs, topic = clustered_vecs(N_DOCS)
    rng = np.random.default_rng(3)
    qv = vecs[rng.integers(0, N_DOCS, 8)] \
        + 0.02 * rng.normal(0, 1, (8, DIMS)).astype(np.float32)
    qv = (qv / np.linalg.norm(qv, axis=1, keepdims=True)).astype(np.float32)
    return vecs, topic, qv


@pytest.fixture(scope="module")
def searcher(tmp_path_factory, corpus):
    vecs, topic, _qv = corpus
    ms = MapperService(mappings=MAPPING)
    eng = Engine(str(tmp_path_factory.mktemp("annshard")), ms)
    for i in range(N_DOCS):
        eng.index(str(i), {"body": f"topic{topic[i]}",
                           "vec": vecs[i].tolist(),
                           "cat": "even" if i % 2 == 0 else "odd"})
    eng.refresh()
    s = ShardSearcher(0, eng.segments, ms, knn_opts=dict(OPTS))
    s._engine = eng
    return s


class TestIvfRecall:
    @pytest.mark.parametrize("metric", ["cosine", "dot", "l2"])
    def test_recall_at_10_vs_numpy_oracle(self, searcher, corpus, metric):
        vecs, _t, qv = corpus
        if metric == "l2":
            d2 = (np.sum(qv * qv, 1)[:, None] + np.sum(vecs * vecs, 1)[None]
                  - 2.0 * qv @ vecs.T)
            oracle = np.argsort(d2, axis=1, kind="stable")[:, :10]
        else:
            oracle = np.argsort(-(qv @ vecs.T), axis=1, kind="stable")[:, :10]
        res = searcher.execute_knn("vec", qv.tolist(), k=10, metric=metric)
        assert searcher.last_knn_mode == "ann"
        assert recall_at(res, oracle) >= 0.95

    def test_nprobe_sweep_recall_is_monotone(self, searcher, corpus):
        vecs, _t, qv = corpus
        oracle = np.argsort(-(qv @ vecs.T), axis=1, kind="stable")[:, :10]
        recalls = []
        for nprobe in (1, 4, 16):
            r = searcher.execute_knn("vec", qv.tolist(), k=10, nprobe=nprobe)
            assert searcher.last_knn_mode == "ann"
            recalls.append(recall_at(r, oracle))
        # growing the probe set grows the candidate superset: an oracle
        # doc retrieved at nprobe=p stays retrieved at every larger p
        assert recalls == sorted(recalls)
        assert recalls[-1] >= 0.95

    def test_total_hits_is_live_count_like_exact(self, searcher, corpus):
        _v, _t, qv = corpus
        ann = searcher.execute_knn("vec", qv[:2].tolist(), k=5)
        exact = searcher.execute_knn("vec", qv[:2].tolist(), k=5, exact=True)
        assert (ann.total_hits == exact.total_hits).all()
        assert int(ann.total_hits[0]) == N_DOCS


class TestExactParity:
    @pytest.mark.parametrize("metric", ["cosine", "dot", "l2"])
    @pytest.mark.parametrize("nq", [1, 4])
    def test_nprobe_ge_nlist_bitwise_exact(self, searcher, corpus,
                                           metric, nq):
        """Full-coverage requests route to the exact kernel: scores AND
        keys bitwise-identical across the metric x batch matrix."""
        _v, _t, qv = corpus
        q = qv[:nq].tolist()
        full = searcher.execute_knn("vec", q, k=10, metric=metric,
                                    nprobe=OPTS["nlist"])
        assert searcher.last_knn_mode == "exact"
        exact = searcher.execute_knn("vec", q, k=10, metric=metric,
                                     exact=True)
        assert np.array_equal(full.doc_keys, exact.doc_keys)
        assert np.array_equal(np.nan_to_num(full.scores),
                              np.nan_to_num(exact.scores))

    def test_nprobe_ge_nlist_with_filter(self, searcher, corpus):
        _v, _t, qv = corpus
        fnode = searcher.parse([{"term": {"cat": "odd"}}])
        full = searcher.execute_knn("vec", qv[:1].tolist(), k=8,
                                    filter_node=fnode,
                                    nprobe=OPTS["nlist"] + 5)
        exact = searcher.execute_knn("vec", qv[:1].tolist(), k=8,
                                     filter_node=fnode, exact=True)
        assert np.array_equal(full.doc_keys, exact.doc_keys)
        assert np.array_equal(np.nan_to_num(full.scores),
                              np.nan_to_num(exact.scores))


class TestFallbackLadder:
    def test_disabled_setting_uses_exact(self, tmp_path, corpus):
        vecs, topic, qv = corpus
        ms = MapperService(mappings=MAPPING)
        eng = Engine(str(tmp_path / "s"), ms)
        for i in range(512):
            eng.index(str(i), {"vec": vecs[i].tolist()})
        eng.refresh()
        s = ShardSearcher(0, eng.segments, ms,
                          knn_opts={**OPTS, "ivf_enable": False})
        s.execute_knn("vec", qv[:1].tolist(), k=5)
        assert s.last_knn_mode == "exact"
        assert s._path_stats.get("ann_dispatches", 0) == 0

    def test_undersized_segment_uses_exact(self, tmp_path, corpus):
        vecs, _t, qv = corpus
        ms = MapperService(mappings=MAPPING)
        eng = Engine(str(tmp_path / "s"), ms)
        for i in range(128):
            eng.index(str(i), {"vec": vecs[i].tolist()})
        eng.refresh()
        s = ShardSearcher(0, eng.segments, ms,
                          knn_opts={**OPTS, "min_docs": 4096})
        s.execute_knn("vec", qv[:1].tolist(), k=5)
        assert s.last_knn_mode == "exact"

    def test_declined_build_counts_fallback(self, searcher, corpus,
                                            monkeypatch):
        _v, _t, qv = corpus
        from elasticsearch_tpu.index import segment as segment_mod
        monkeypatch.setattr(segment_mod.VectorColumn, "build_ivf",
                            lambda self, *a, **k: None)
        searcher._ivf_local.clear()
        before = searcher._path_stats.get("ann_fallbacks", 0)
        r = searcher.execute_knn("vec", qv[:1].tolist(), k=5)
        assert searcher.last_knn_mode == "exact"
        assert searcher._path_stats.get("ann_fallbacks", 0) == before + 1
        assert local_ids(r)          # still serves results

    def test_failed_build_is_the_requests_error(self, searcher, corpus,
                                                monkeypatch):
        """A build that raises (a device failure) is never hidden behind
        the exact lane."""
        _v, _t, qv = corpus
        from elasticsearch_tpu.index import segment as segment_mod
        monkeypatch.setattr(segment_mod.VectorColumn, "build_ivf",
                            lambda self, *a, **k: (_ for _ in ()).throw(
                                RuntimeError("boom")))
        searcher._ivf_local.clear()
        with pytest.raises(RuntimeError, match="boom"):
            searcher.execute_knn("vec", qv[:1].tolist(), k=5)

    def test_tombstones_are_excluded(self, tmp_path, corpus):
        vecs, _t, qv = corpus
        ms = MapperService(mappings=MAPPING)
        eng = Engine(str(tmp_path / "s"), ms)
        for i in range(512):
            eng.index(str(i), {"vec": vecs[i].tolist()})
        eng.refresh()
        s = ShardSearcher(0, eng.segments, ms, knn_opts=dict(OPTS))
        top = local_ids(s.execute_knn("vec", qv[:1].tolist(), k=3))[0]
        eng.delete(str(top))
        eng.refresh()
        s2 = ShardSearcher(0, eng.segments, ms, knn_opts=dict(OPTS))
        r = s2.execute_knn("vec", qv[:1].tolist(), k=10)
        assert s2.last_knn_mode == "ann"
        assert top not in local_ids(r)
        assert int(r.total_hits[0]) == 511

    def test_filtered_ann_respects_filter(self, searcher, corpus):
        _v, _t, qv = corpus
        fnode = searcher.parse([{"term": {"cat": "odd"}}])
        r = searcher.execute_knn("vec", qv[:1].tolist(), k=8,
                                 filter_node=fnode)
        assert searcher.last_knn_mode == "ann"
        assert all(i % 2 == 1 for i in local_ids(r))


# ---------------------------------------------------------------------------
# node-level: cache tier, settings, batched lane, metrics, retrace
# ---------------------------------------------------------------------------

ANN_SETTINGS = {"number_of_shards": 1,
                "index.knn.ivf.min_docs": 256,
                "index.knn.ivf.nlist": 16,
                "index.knn.ivf.nprobe": 4}


@pytest.fixture(scope="module")
def node(tmp_path_factory, corpus):
    vecs, topic, _qv = corpus
    n = NodeService(str(tmp_path_factory.mktemp("annnode")))
    n.create_index("ann", settings=dict(ANN_SETTINGS),
                   mappings=json.loads(json.dumps(MAPPING)))
    for i in range(1024):
        n.index_doc("ann", str(i), {"body": f"topic{topic[i]}",
                                    "vec": vecs[i].tolist()})
    n.refresh("ann")
    yield n
    n.close()


class TestNodeLane:
    def test_knn_body_rides_the_ann_lane(self, node, corpus):
        _v, _t, qv = corpus
        out = node.search("ann", {
            "knn": {"field": "vec", "query_vector": qv[0].tolist(),
                    "k": 5}, "size": 5})
        assert len(out["hits"]["hits"]) == 5
        assert node.indices["ann"].search_stats.get("ann_dispatches", 0) >= 1

    def test_ann_cache_tier_in_stats_and_clear(self, node, corpus):
        _v, _t, qv = corpus
        node.search("ann", {"knn": {"field": "vec",
                                    "query_vector": qv[0].tolist(),
                                    "k": 5}, "size": 5})
        st = node.caches.stats()["ann_index"]
        assert st["entries"] == 1 and st["memory_size_in_bytes"] > 0
        cleared = node.caches.clear(query=True)
        assert cleared["ann_index"] == 1
        assert node.caches.stats()["ann_index"]["entries"] == 0
        # next search rebuilds the cluster index
        node.search("ann", {"knn": {"field": "vec",
                                    "query_vector": qv[0].tolist(),
                                    "k": 5}, "size": 5})
        assert node.caches.stats()["ann_index"]["entries"] == 1

    def test_merge_drops_dead_segment_entries(self, node, corpus):
        vecs, _t, qv = corpus
        node.search("ann", {"knn": {"field": "vec",
                                    "query_vector": qv[0].tolist(),
                                    "k": 5}, "size": 5})
        assert node.caches.stats()["ann_index"]["entries"] >= 1
        for i in range(1024, 1536):
            node.index_doc("ann", str(i), {"vec": vecs[i].tolist()})
        node.refresh("ann")
        node.indices["ann"].force_merge(1)
        # the source segments died with the merge: their entries are gone
        # (the searcher rebuilds against the merged segment on demand)
        assert node.caches.stats()["ann_index"]["entries"] == 0

    def test_per_request_nprobe_and_exact_override(self, node, corpus):
        _v, _t, qv = corpus
        before = node.indices["ann"].search_stats.get("ann_dispatches", 0)
        node.search("ann", {"knn": {"field": "vec",
                                    "query_vector": qv[0].tolist(),
                                    "k": 5, "exact": True}, "size": 5})
        assert node.indices["ann"].search_stats.get(
            "ann_dispatches", 0) == before
        node.search("ann", {"knn": {"field": "vec",
                                    "query_vector": qv[0].tolist(),
                                    "k": 5, "nprobe": 8}, "size": 5})
        assert node.indices["ann"].search_stats.get(
            "ann_dispatches", 0) == before + 1

    def test_msearch_batched_knn_rides_ann(self, node, corpus):
        """Q>1 kNN batches (the QoS batcher's replica-axis lane) serve
        the whole group through ONE IVF program per segment."""
        _v, _t, qv = corpus
        items = []
        for qi in range(4):
            items.append(({"index": "ann"},
                          {"knn": {"field": "vec",
                                   "query_vector": qv[qi].tolist(),
                                   "k": 5}, "size": 5}))
        before = node.indices["ann"].search_stats.get("ann_dispatches", 0)
        out = node.msearch(items)
        assert len(out["responses"]) == 4
        assert all(r["hits"]["hits"] for r in out["responses"])
        after = node.indices["ann"].search_stats.get("ann_dispatches", 0)
        assert after == before + 1        # one batched program, not 4

    def test_ann_metric_families_exposed(self, node):
        from elasticsearch_tpu.common.metrics import render_openmetrics
        text = render_openmetrics(node.metric_sections())
        assert "# TYPE es_search_ann_dispatches_total counter" in text
        assert "# TYPE es_search_ann_fallbacks_total counter" in text
        assert 'es_cache_memory_size_bytes{cache="ann_index"' in text

    def test_sampler_gains_vector_memory_gauge(self, node):
        snap = node._sampler_snapshot()
        assert "ann_index_cache_memory_bytes" in snap
        assert snap["ann_index_cache_memory_bytes"] >= 0

    def test_refresh_query_cycle_zero_retraces(self, tmp_path_factory,
                                               corpus):
        """refresh→query cycles whose segment shapes stay inside one pow2
        bucket compile ZERO new ANN programs (the test_no_retrace
        contract for the IVF lane)."""
        from elasticsearch_tpu.common.metrics import device_events_snapshot
        vecs, _t, qv = corpus
        n = NodeService(str(tmp_path_factory.mktemp("annretrace")))
        n.create_index("r", settings=dict(ANN_SETTINGS),
                       mappings=json.loads(json.dumps(MAPPING)))
        body = {"knn": {"field": "vec", "query_vector": qv[0].tolist(),
                        "k": 5}, "size": 5}

        def add_segment(base):
            for i in range(512):
                n.index_doc("r", str(base + i),
                            {"vec": vecs[(base + i) % N_DOCS].tolist()})
            n.refresh("r")

        add_segment(0)
        n.search("r", json.loads(json.dumps(body)))      # warm: compiles
        n.search("r", json.loads(json.dumps(body)))
        assert n.indices["r"].search_stats.get("ann_dispatches", 0) >= 2
        before = device_events_snapshot()[0]
        add_segment(10000)       # same-size segment: same pow2 buckets
        n.search("r", json.loads(json.dumps(body)))
        assert device_events_snapshot()[0] == before, \
            "refresh→query cycle inside the pow2 bucket retraced the ANN lane"
        n.close()


# ---------------------------------------------------------------------------
# hybrid "rank" fusion
# ---------------------------------------------------------------------------

class TestHybridRank:
    def _solo_lists(self, node, qv, window):
        text = node.search("ann", {"query": {"match": {"body": "topic3"}},
                                   "size": window})
        knn = node.search("ann", {"knn": {"field": "vec",
                                          "query_vector": qv.tolist(),
                                          "k": window},
                                  "size": window})
        return ([h["_id"] for h in text["hits"]["hits"]],
                [h["_id"] for h in knn["hits"]["hits"]])

    def test_rrf_matches_numpy_reference(self, node, corpus):
        _v, _t, qv = corpus
        window, const = 20, 60.0
        ta, kb = self._solo_lists(node, qv[0], window)
        expect = {}
        for r, did in enumerate(ta):
            expect[did] = expect.get(did, 0.0) + 1.0 / (const + r + 1)
        for r, did in enumerate(kb):
            expect[did] = expect.get(did, 0.0) + 1.0 / (const + r + 1)
        want = sorted(expect.items(), key=lambda kv: -kv[1])[:5]
        out = node.search("ann", {
            "query": {"match": {"body": "topic3"}},
            "knn": {"field": "vec", "query_vector": qv[0].tolist(),
                    "k": window},
            "rank": {"rrf": {"rank_constant": const,
                             "window_size": window}},
            "size": 5})
        got = [(h["_id"], h["_score"]) for h in out["hits"]["hits"]]
        assert [g[0] for g in got] == [w[0] for w in want]
        for (gid, gs), (wid, ws) in zip(got, want):
            assert gs == pytest.approx(ws, rel=1e-5)

    def test_weighted_mode_normalizes_and_fuses(self, node, corpus):
        _v, _t, qv = corpus
        out = node.search("ann", {
            "query": {"match": {"body": "topic3"}},
            "knn": {"field": "vec", "query_vector": qv[0].tolist(),
                    "k": 20},
            "rank": {"weighted": {"query_weight": 0.0, "knn_weight": 1.0,
                                  "window_size": 20}},
            "size": 5})
        knn_only = node.search("ann", {
            "knn": {"field": "vec", "query_vector": qv[0].tolist(),
                    "k": 20}, "size": 5})
        # text weight 0: the fused order IS the vector order
        assert [h["_id"] for h in out["hits"]["hits"]] == \
            [h["_id"] for h in knn_only["hits"]["hits"]]
        assert out["hits"]["hits"][0]["_score"] == pytest.approx(1.0)

    def test_rank_validations(self, node, corpus):
        _v, _t, qv = corpus
        from elasticsearch_tpu.search.query_dsl import QueryParsingException
        knn = {"field": "vec", "query_vector": qv[0].tolist(), "k": 5}
        with pytest.raises(QueryParsingException, match="requires a knn"):
            node.search("ann", {"query": {"match_all": {}},
                                "rank": {"rrf": {}}, "size": 5})
        with pytest.raises(QueryParsingException, match="rescore"):
            node.search("ann", {
                "query": {"match_all": {}}, "knn": knn,
                "rank": {"rrf": {}},
                "rescore": {"window_size": 5,
                            "query": {"rescore_query": {"match_all": {}}}},
                "size": 5})
        with pytest.raises(QueryParsingException, match="rank mode"):
            node.search("ann", {"query": {"match_all": {}}, "knn": knn,
                                "rank": {"nope": {}}, "size": 5})
        with pytest.raises(QueryParsingException):
            node.search("ann", {"query": {"match_all": {}}, "knn": knn,
                                "rank": {"rrf": {}, "weighted": {}},
                                "size": 5})

    def test_rank_with_aggs_rejected(self, node, corpus):
        _v, _t, qv = corpus
        from elasticsearch_tpu.search.query_dsl import QueryParsingException
        with pytest.raises(QueryParsingException, match="aggregations"):
            node.search("ann", {
                "query": {"match_all": {}},
                "knn": {"field": "vec", "query_vector": qv[0].tolist()},
                "rank": {"rrf": {}},
                "aggs": {"c": {"terms": {"field": "cat"}}}, "size": 5})


# ---------------------------------------------------------------------------
# LM similarity providers (satellite: VERDICT missing #3)
# ---------------------------------------------------------------------------

LM_MAPPINGS = {"_doc": {"properties": {
    "d": {"type": "string", "similarity": "LMDirichlet"},
    "j": {"type": "string", "similarity": "LMJelinekMercer"},
    "b": {"type": "string"},
}}}


@pytest.fixture(scope="module")
def lm_node(tmp_path_factory):
    n = NodeService(str(tmp_path_factory.mktemp("lmnode")))
    n.create_index("lm", settings={"number_of_shards": 1},
                   mappings=json.loads(json.dumps(LM_MAPPINGS)))
    docs = [
        "rare common common common",        # 0: one rare, lots of common
        "rare rare rare common",            # 1: high rare tf, short
        "common common common common common common common common",
        "other words entirely here",
        "rare common other words",
    ]
    for i, text in enumerate(docs):
        n.index_doc("lm", str(i), {"d": text, "j": text, "b": text})
    n.refresh("lm")
    yield n
    n.close()


class TestLmSimilarities:
    @pytest.mark.parametrize("field", ["d", "j"])
    def test_higher_tf_of_rare_term_ranks_higher(self, lm_node, field):
        out = lm_node.search("lm", {"query": {"match": {field: "rare"}},
                                    "size": 5})
        hits = out["hits"]["hits"]
        assert hits[0]["_id"] == "1"        # tf=3 over a short field wins
        assert {h["_id"] for h in hits} == {"0", "1", "4"}
        assert all(h["_score"] is not None and h["_score"] > 0
                   for h in hits)

    @pytest.mark.parametrize("field", ["d", "j"])
    def test_lm_fields_decline_the_sparse_lane(self, lm_node, field):
        svc = lm_node.indices["lm"]
        before_dense = svc.search_stats.get("dense", 0)
        lm_node.search("lm", {"query": {"match": {field: "rare"}},
                              "size": 3})
        assert svc.search_stats.get("dense", 0) == before_dense + 1

    def test_bm25_field_keeps_fast_lanes(self, lm_node):
        svc = lm_node.indices["lm"]
        before_sparse = svc.search_stats.get("sparse", 0) \
            + svc.search_stats.get("packed", 0)
        lm_node.search("lm", {"query": {"match": {"b": "rare"}},
                              "size": 3})
        after = svc.search_stats.get("sparse", 0) \
            + svc.search_stats.get("packed", 0)
        assert after == before_sparse + 1

    def test_lm_dirichlet_matches_reference_math(self, lm_node):
        """Row-0 score equals the Lucene LMDirichlet formula computed by
        hand from corpus stats (mu default 2000)."""
        import math
        out = lm_node.search("lm", {"query": {"match": {"d": "rare"}},
                                    "size": 5})
        by_id = {h["_id"]: h["_score"] for h in out["hits"]["hits"]}
        # corpus: sum_dl over field d, ttf("rare") from the docs above
        sum_dl = 4 + 4 + 8 + 4 + 4
        # ttf counts every occurrence the analyzer kept; the standard
        # analyzer emits all tokens above, so rare appears 1 + 3 + 1 times
        ttf = 1 + 3 + 1
        pc = (ttf + 1.0) / (sum_dl + 1.0)
        mu = 2000.0
        for did, tf, dl in (("1", 3, 4), ("0", 1, 4), ("4", 1, 4)):
            want = math.log1p(tf / (mu * pc)) + math.log(mu / (dl + mu))
            # the kernel computes in f32; the tiny log terms round at
            # ~1e-3 relative — ranking-irrelevant, tolerated here
            assert by_id[did] == pytest.approx(max(want, 0.0), rel=5e-3)

    def test_named_similarity_settings_parse(self):
        from elasticsearch_tpu.common.settings import Settings
        from elasticsearch_tpu.index.similarity import SimilarityService
        svc = SimilarityService(Settings({
            "index.similarity.my_lmd.type": "LMDirichlet",
            "index.similarity.my_lmd.mu": "500",
            "index.similarity.my_jm.type": "LMJelinekMercer",
            "index.similarity.my_jm.lambda": "0.3"}))
        assert svc.resolve("my_lmd").type == "LMDirichlet"
        assert svc.resolve("my_lmd").mu == 500.0
        assert svc.resolve("my_jm").lam == pytest.approx(0.3)

    def test_plan_keys_group_by_similarity_params(self):
        from elasticsearch_tpu.search.query_dsl import MatchNode
        a = MatchNode(field_name="f", terms_per_query=[["x"]],
                      sim="lm_dirichlet", mu=2000.0)
        b = MatchNode(field_name="f", terms_per_query=[["x"]],
                      sim="lm_dirichlet", mu=500.0)
        assert a.plan_key() != b.plan_key()


# ---------------------------------------------------------------------------
# index.knn.precision (satellite bugfix)
# ---------------------------------------------------------------------------

class TestKnnPrecision:
    def test_f32_matches_numpy_exactly(self, tmp_path, corpus):
        vecs, _t, qv = corpus
        ms = MapperService(mappings=MAPPING)
        eng = Engine(str(tmp_path / "s"), ms)
        for i in range(256):
            eng.index(str(i), {"vec": vecs[i].tolist()})
        eng.refresh()
        s32 = ShardSearcher(0, eng.segments, ms,
                            knn_opts={"precision": "f32"})
        r = s32.execute_knn("vec", qv[:1].tolist(), k=5, metric="dot")
        want = np.sort(qv[:1] @ vecs[:256].T, axis=1)[:, ::-1][:, :5]
        got = np.nan_to_num(r.scores)
        assert np.allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_precision_setting_threads_from_index_settings(self, tmp_path):
        n = NodeService(str(tmp_path / "n"))
        n.create_index("p", settings={"number_of_shards": 1,
                                      "index.knn.precision": "f32"},
                       mappings=json.loads(json.dumps(MAPPING)))
        assert n.indices["p"]._knn_opts["precision"] == "f32"
        s = n.indices["p"].searchers()[0]
        assert s.knn_opts["precision"] == "f32"
        n.close()

    def test_bf16_and_f32_both_serve(self, searcher, corpus):
        _v, _t, qv = corpus
        r16 = searcher.execute_knn("vec", qv[:1].tolist(), k=5)
        searcher.knn_opts["precision"] = "f32"
        try:
            r32 = searcher.execute_knn("vec", qv[:1].tolist(), k=5)
        finally:
            searcher.knn_opts["precision"] = "bf16"
        assert local_ids(r16) and local_ids(r32)
