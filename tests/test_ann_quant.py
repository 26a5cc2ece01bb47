"""Quantized ANN tier (ISSUE 12): int8 scalar + IVF-PQ cluster scans
with full-precision rescore — recall vs the numpy brute-force oracle
across the metric matrix, the rescore-improves-recall contract, the
fallback ladder back to the f32 IVF scan, the breaker-charged
`ann_quant` cache tier (codes + codebooks as separate entries), the
mesh-lane int8 parity with the per-shard fan-out, and the metric /
sampler exposition."""

import json

import numpy as np
import pytest

from elasticsearch_tpu.mapping.mapper import MapperService
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.search.shard_searcher import LOCAL_MASK, ShardSearcher

DIMS = 16
N_DOCS = 2048
N_PROTOS = 128            # near-duplicate tier: ~16 docs per prototype
OPTS = {"min_docs": 256, "nlist": 32, "nprobe": 16, "precision": "f32",
        "rescore_window": 40}

MAPPING = {"_doc": {"properties": {
    "body": {"type": "string"},
    "vec": {"type": "dense_vector", "dims": DIMS},
    "cat": {"type": "keyword"},
}}}


def proto_corpus(n=N_DOCS, dims=DIMS, protos=N_PROTOS, seed=0):
    """Docs cluster around prototypes (clear neighbor margins — the
    regime ANN retrieval serves); queries perturb a prototype."""
    rng = np.random.default_rng(seed)
    p = rng.normal(0, 1, (protos, dims)).astype(np.float32)
    p /= np.linalg.norm(p, axis=1, keepdims=True)
    proto_of = np.repeat(np.arange(protos), -(-n // protos))[:n]
    v = p[proto_of] + 0.05 * rng.normal(0, 1, (n, dims)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    q = p[rng.integers(0, protos, 8)] \
        + 0.05 * rng.normal(0, 1, (8, dims)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    return v.astype(np.float32), proto_of, q.astype(np.float32)


def oracle_for(vecs, qv, metric):
    if metric == "l2":
        d2 = (np.sum(qv * qv, 1)[:, None] + np.sum(vecs * vecs, 1)[None]
              - 2.0 * qv @ vecs.T)
        return np.argsort(d2, axis=1, kind="stable")[:, :10]
    return np.argsort(-(qv @ vecs.T), axis=1, kind="stable")[:, :10]


def recall_at(result, oracle, k=10):
    hits = want = 0
    for qi in range(result.doc_keys.shape[0]):
        got = {int(key) & LOCAL_MASK
               for key in result.doc_keys[qi][:k] if key >= 0}
        w = set(oracle[qi][:k].tolist())
        hits += len(got & w)
        want += len(w)
    return hits / max(want, 1)


@pytest.fixture(scope="module")
def corpus():
    return proto_corpus()


@pytest.fixture(scope="module")
def engine(tmp_path_factory, corpus):
    vecs, proto_of, _qv = corpus
    ms = MapperService(mappings=MAPPING)
    eng = Engine(str(tmp_path_factory.mktemp("quantshard")), ms)
    for i in range(N_DOCS):
        eng.index(str(i), {"body": f"p{proto_of[i]}",
                           "vec": vecs[i].tolist(),
                           "cat": "even" if i % 2 == 0 else "odd"})
    eng.refresh()
    return eng, ms


def make_searcher(engine, **opts):
    eng, ms = engine
    return ShardSearcher(0, eng.segments, ms, knn_opts={**OPTS, **opts})


class TestQuantRecall:
    @pytest.mark.parametrize("metric", ["cosine", "dot", "l2"])
    @pytest.mark.parametrize("mode", ["int8", "pq"])
    def test_recall_at_10_vs_numpy_oracle(self, engine, corpus, mode,
                                          metric):
        vecs, _p, qv = corpus
        s = make_searcher(engine, quantization=mode, pq_m=8)
        res = s.execute_knn("vec", qv.tolist(), k=10, metric=metric)
        assert s.last_knn_mode == "ann"
        assert s.last_quant_mode == mode
        assert s._path_stats.get("ann_quantized_dispatches", 0) >= 1
        assert s._path_stats.get(f"ann_quantized_{mode}", 0) >= 1
        assert recall_at(res, oracle_for(vecs, qv, metric)) >= 0.95

    def test_rescore_strictly_improves_recall(self, engine, corpus):
        """The quantized scan ranks, the f32 rescore corrects: a coarse
        PQ (m=2 -> 8-dim subspaces) must retrieve strictly more oracle
        neighbors with a real rescore window than with rw == k (which
        can reorder but never change the retrieved SET)."""
        vecs, _p, qv = corpus
        oracle = oracle_for(vecs, qv, "cosine")
        base = make_searcher(engine, quantization="pq", pq_m=2,
                             rescore_window=10)
        wide = make_searcher(engine, quantization="pq", pq_m=2,
                             rescore_window=256)
        r_base = recall_at(base.execute_knn("vec", qv.tolist(), k=10),
                           oracle)
        r_wide = recall_at(wide.execute_knn("vec", qv.tolist(), k=10),
                           oracle)
        assert base.last_quant_mode == wide.last_quant_mode == "pq"
        assert r_wide >= 0.95
        assert r_wide > r_base

    def test_filtered_quantized_respects_filter(self, engine, corpus):
        _v, _p, qv = corpus
        s = make_searcher(engine, quantization="int8")
        fnode = s.parse([{"term": {"cat": "odd"}}])
        res = s.execute_knn("vec", qv[:2].tolist(), k=8,
                            filter_node=fnode)
        assert s.last_quant_mode == "int8"
        for qi in range(2):
            for key in res.doc_keys[qi]:
                if key >= 0:
                    assert (int(key) & LOCAL_MASK) % 2 == 1

    def test_total_hits_matches_exact(self, engine, corpus):
        _v, _p, qv = corpus
        s = make_searcher(engine, quantization="pq", pq_m=8)
        quant = s.execute_knn("vec", qv[:2].tolist(), k=5)
        exact = s.execute_knn("vec", qv[:2].tolist(), k=5, exact=True)
        assert (quant.total_hits == exact.total_hits).all()


class TestQuantFallback:
    def test_default_is_unquantized(self, engine, corpus):
        _v, _p, qv = corpus
        s = make_searcher(engine)
        s.execute_knn("vec", qv[:1].tolist(), k=5)
        assert s.last_knn_mode == "ann"
        assert s.last_quant_mode is None
        assert s._path_stats.get("ann_quantized_dispatches", 0) == 0

    def test_per_request_override_quantizes(self, engine, corpus):
        _v, _p, qv = corpus
        s = make_searcher(engine)              # index default: none
        s.execute_knn("vec", qv[:1].tolist(), k=5, quantization="int8")
        assert s.last_quant_mode == "int8"
        s.execute_knn("vec", qv[:1].tolist(), k=5, quantization="none")
        assert s.last_quant_mode is None

    def test_exact_pins_exact_kernel(self, engine, corpus):
        _v, _p, qv = corpus
        s = make_searcher(engine, quantization="int8")
        s.execute_knn("vec", qv[:1].tolist(), k=5, exact=True)
        assert s.last_knn_mode == "exact"
        assert s.last_quant_mode is None

    def test_pq_dims_not_divisible_falls_back(self, engine, corpus):
        _v, _p, qv = corpus
        s = make_searcher(engine, quantization="pq", pq_m=3)  # 16 % 3
        s.execute_knn("vec", qv[:1].tolist(), k=5)
        assert s.last_knn_mode == "ann"        # f32 IVF still serves
        assert s.last_quant_mode is None
        assert s._path_stats.get("ann_quantized_fallbacks", 0) >= 1
        assert s._path_stats.get("ann_quantized_dispatches", 0) == 0

    def test_pq_undersized_column_falls_back(self, tmp_path, corpus):
        """IVF engages (>= 2*nlist docs) but PQ can't train 256 codes."""
        vecs, _p, qv = corpus
        ms = MapperService(mappings=MAPPING)
        eng = Engine(str(tmp_path / "s"), ms)
        for i in range(200):
            eng.index(str(i), {"vec": vecs[i].tolist()})
        eng.refresh()
        s = ShardSearcher(0, eng.segments, ms,
                          knn_opts={**OPTS, "min_docs": 64, "nlist": 16,
                                    "nprobe": 4, "quantization": "pq",
                                    "pq_m": 8})
        s.execute_knn("vec", qv[:1].tolist(), k=5)
        assert s.last_knn_mode == "ann"
        assert s.last_quant_mode is None
        assert s._path_stats.get("ann_quantized_fallbacks", 0) >= 1

    def test_declined_build_counts_fallback(self, engine, corpus,
                                            monkeypatch):
        from elasticsearch_tpu.index.segment import VectorColumn
        _v, _p, qv = corpus
        monkeypatch.setattr(VectorColumn, "build_quant",
                            lambda self, *a, **kw: None)
        s = make_searcher(engine, quantization="int8")
        res = s.execute_knn("vec", qv[:1].tolist(), k=5)
        assert s.last_knn_mode == "ann"        # f32 IVF still serves
        assert s.last_quant_mode is None
        assert s._path_stats.get("ann_quantized_fallbacks", 0) >= 1
        assert (res.doc_keys[0] >= 0).any()

    def test_failed_build_is_the_requests_error(self, engine, corpus,
                                                monkeypatch):
        """A build that raises (a device failure) is never hidden behind
        the f32 scan (ISSUE 21)."""
        from elasticsearch_tpu.index.segment import VectorColumn
        _v, _p, qv = corpus

        def boom(self, *a, **kw):
            raise RuntimeError("quant build failed")
        monkeypatch.setattr(VectorColumn, "build_quant", boom)
        s = make_searcher(engine, quantization="int8")
        with pytest.raises(RuntimeError, match="quant build failed"):
            s.execute_knn("vec", qv[:1].tolist(), k=5)


ANN_SETTINGS = {"number_of_shards": 1,
                "index.knn.ivf.nlist": 32,
                "index.knn.ivf.nprobe": 16,
                "index.knn.ivf.min_docs": 256,
                "index.knn.precision": "f32",
                "index.knn.quantization": "int8",
                "index.knn.rescore_window": 40}


@pytest.fixture(scope="module")
def node(tmp_path_factory, corpus):
    vecs, proto_of, _qv = corpus
    n = NodeService(str(tmp_path_factory.mktemp("quantnode")))
    n.create_index("qi", settings=dict(ANN_SETTINGS),
                   mappings=json.loads(json.dumps(MAPPING)))
    for i in range(1024):
        n.index_doc("qi", str(i), {"body": f"p{proto_of[i]}",
                                   "vec": vecs[i].tolist()})
    n.refresh("qi")
    yield n
    n.close()


class TestQuantCacheTier:
    def _search(self, n, qv, mode=None):
        knn = {"field": "vec", "query_vector": qv[0].tolist(), "k": 5}
        if mode is not None:
            knn["quantization"] = mode
        return n.search("qi", {"size": 5, "knn": knn})

    def test_quant_tier_in_stats_and_breaker(self, node, corpus):
        _v, _p, qv = corpus
        self._search(node, qv)                 # index default: int8
        st = node.caches.stats()["ann_quant"]
        assert st["entries"] == 2              # codes + books entries
        assert st["code_bytes"] > 0
        assert st["codebook_bytes"] > 0
        assert st["memory_size_in_bytes"] == st["code_bytes"] \
            + st["codebook_bytes"]
        assert node.indices["qi"].search_stats.get(
            "ann_quantized_dispatches", 0) >= 1

    def test_both_modes_coexist_and_clear_releases(self, node, corpus):
        _v, _p, qv = corpus
        self._search(node, qv, mode="pq")
        st = node.caches.stats()["ann_quant"]
        assert st["entries"] == 4              # int8 + pq, codes + books
        br = node.breakers.breaker("fielddata")
        used_before = br.used
        assert used_before > 0
        cleared = node.caches.clear(query=True)
        assert cleared["ann_index"] >= 4       # quant entries ride `query`
        assert node.caches.stats()["ann_quant"]["entries"] == 0
        assert node.caches.stats()["ann_quant"]["code_bytes"] == 0
        assert br.used < used_before

    def test_merge_drops_dead_segment_entries(self, node, corpus):
        vecs, _p, qv = corpus
        self._search(node, qv)
        assert node.caches.stats()["ann_quant"]["entries"] >= 2
        for i in range(1024, 1200):
            node.index_doc("qi", str(i), {"vec": vecs[i].tolist()})
        node.refresh("qi")
        node.indices["qi"].force_merge(1)      # merge kills old segments
        assert node.caches.stats()["ann_quant"]["entries"] == 0

    def test_invalid_quantization_rejected(self, node, corpus):
        _v, _p, qv = corpus
        from elasticsearch_tpu.search.query_parser import \
            QueryParsingException
        with pytest.raises(QueryParsingException):
            self._search(node, qv, mode="int4")

    def test_metric_families_and_sampler(self, node, corpus):
        _v, _p, qv = corpus
        self._search(node, qv)
        from elasticsearch_tpu.common.metrics import render_openmetrics
        text = render_openmetrics(node.metric_sections())
        assert "es_search_ann_quantized_dispatches_total" in text
        assert 'mode="int8"' in text
        assert 'mode="pq"' in text
        assert "es_search_ann_quantized_fallbacks_total" in text
        assert 'es_cache_memory_size_bytes{cache="ann_quant"' in text
        snap = node._sampler_snapshot()
        assert snap["ann_quant_cache_memory_bytes"] > 0
        assert snap["ann_quant_code_bytes"] > 0
        assert snap["ann_quant_codebook_bytes"] > 0

    def test_profiler_query_path(self, node, corpus):
        _v, _p, qv = corpus
        out = node.search("qi", {
            "size": 5, "profile": True,
            "knn": {"field": "vec", "query_vector": qv[0].tolist(),
                    "k": 5}})
        prof = json.dumps(out.get("profile", {}))
        assert "ann_quantized" in prof


class TestMeshQuantParity:
    """int8 through the mesh program (the quantized rider of the ISSUE 11
    lane): bitwise-identical to the per-shard fan-out, one device fetch;
    pq declines to the fan-out with the counter."""

    D = 8

    @pytest.fixture(scope="class")
    def knn_pair(self, tmp_path_factory):
        n = NodeService(str(tmp_path_factory.mktemp("meshquant")))
        mapping = {"_doc": {"properties": {
            "body": {"type": "string"},
            "vec": {"type": "dense_vector", "dims": self.D}}}}
        base = {"number_of_shards": 4, "index.knn.ivf.nlist": 8,
                "index.knn.ivf.min_docs": 16,
                "index.knn.precision": "f32",
                "index.knn.quantization": "int8",
                "index.knn.rescore_window": 20}
        n.create_index("vm", settings=dict(base), mappings=mapping)
        n.create_index("vf", settings={**base,
                                       "index.search.mesh.enable": False},
                       mappings=mapping)
        rng = np.random.RandomState(7)
        for i in range(360):
            doc = {"body": f"w{i % 7}",
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
        hits = lambda r: [(h["_id"], h["_score"])  # noqa: E731
                          for h in r["hits"]["hits"]]
        return hits(got), hits(want), got, want

    @pytest.mark.parametrize("metric", ["cosine", "dot", "l2"])
    def test_int8_mesh_bitwise_identical(self, knn_pair, metric):
        n = knn_pair
        before = n.indices["vm"].search_stats.get("mesh_ann_dispatches", 0)
        g, w, got, want = self._both(
            n, {"field": "vec", "query_vector": n._qv, "k": 10,
                "metric": metric})
        assert n.indices["vm"].search_stats.get(
            "mesh_ann_dispatches", 0) == before + 1
        assert n.indices["vm"].search_stats.get(
            "ann_quantized_int8", 0) >= 1
        assert g == w
        assert got["hits"]["total"] == want["hits"]["total"]
        assert got["hits"]["max_score"] == want["hits"]["max_score"]

    def test_one_fetch_for_the_whole_index(self, knn_pair):
        from elasticsearch_tpu.common.metrics import transfer_snapshot
        n = knn_pair
        body = {"size": 10, "knn": {"field": "vec",
                                    "query_vector": n._qv, "k": 10}}
        n.search("vm", json.loads(json.dumps(body)))          # warm
        f0 = transfer_snapshot()["device_fetches_total"]
        n.search("vm", json.loads(json.dumps(body)))
        assert transfer_snapshot()["device_fetches_total"] - f0 == 1

    def test_pq_undersized_declines_to_fanout(self, knn_pair):
        """PQ rides the mesh since ISSUE 19, but only when every segment
        built its codebook tier — 90 docs/shard is under the 256-doc
        floor, so the lane still declines down the ladder with the
        counter (never an error)."""
        n = knn_pair
        fb0 = n.indices["vm"].search_stats.get("mesh_ann_fallbacks", 0)
        g, w, *_ = self._both(
            n, {"field": "vec", "query_vector": n._qv, "k": 10,
                "quantization": "pq", "nprobe": 4})
        assert n.indices["vm"].search_stats.get(
            "mesh_ann_fallbacks", 0) == fb0 + 1
        assert g == w


class TestMeshPQParity:
    """IVF-PQ through the mesh program (ISSUE 19 satellite): the ADC
    scan (replicated per-subspace LUT operand, in-program coarse
    routing) is bitwise-identical to the per-shard fan-out's
    `ivf_search_pq`, rides in ONE device fetch, and counts into
    es_search_ann_quantized_dispatches_total{mode="pq"}."""

    D = 8
    N = 768             # ~384/shard: over the 256-doc per-segment floor

    @pytest.fixture(scope="class")
    def pq_pair(self, tmp_path_factory):
        n = NodeService(str(tmp_path_factory.mktemp("meshpq")))
        mapping = {"_doc": {"properties": {
            "body": {"type": "string"},
            "vec": {"type": "dense_vector", "dims": self.D}}}}
        base = {"number_of_shards": 2, "index.knn.ivf.nlist": 8,
                "index.knn.ivf.min_docs": 16,
                "index.knn.precision": "f32",
                "index.knn.pq.m": 4,
                "index.knn.rescore_window": 20}
        n.create_index("pm", settings=dict(base), mappings=mapping)
        n.create_index("pf", settings={**base,
                                       "index.search.mesh.enable": False},
                       mappings=mapping)
        rng = np.random.RandomState(11)
        for i in range(self.N):
            doc = {"body": f"w{i % 7}",
                   "vec": [float(x) for x in rng.randn(self.D)]}
            for name in ("pm", "pf"):
                n.index_doc(name, str(i), dict(doc))
        for name in ("pm", "pf"):
            n.refresh(name)
        n._qv = [float(x) for x in rng.randn(self.D)]
        yield n
        n.close()

    def _both(self, n, knn, size=10):
        body = {"size": size, "knn": knn}
        got = n.search("pm", json.loads(json.dumps(body)))
        want = n.search("pf", json.loads(json.dumps(body)))
        hits = lambda r: [(h["_id"], h["_score"])  # noqa: E731
                          for h in r["hits"]["hits"]]
        return hits(got), hits(want), got, want

    @pytest.mark.parametrize("metric", ["cosine", "dot", "l2"])
    def test_pq_mesh_bitwise_identical(self, pq_pair, metric):
        n = pq_pair
        before = n.indices["pm"].search_stats.get("mesh_ann_dispatches", 0)
        pq0 = n.indices["pm"].search_stats.get("ann_quantized_pq", 0)
        g, w, got, want = self._both(
            n, {"field": "vec", "query_vector": n._qv, "k": 10,
                "quantization": "pq", "nprobe": 4, "metric": metric})
        assert n.indices["pm"].search_stats.get(
            "mesh_ann_dispatches", 0) == before + 1
        assert n.indices["pm"].search_stats.get(
            "ann_quantized_pq", 0) == pq0 + 1
        assert g == w
        assert got["hits"]["total"] == want["hits"]["total"]
        assert got["hits"]["max_score"] == want["hits"]["max_score"]

    def test_pq_one_fetch_for_the_whole_index(self, pq_pair):
        from elasticsearch_tpu.common.metrics import transfer_snapshot
        n = pq_pair
        body = {"size": 10, "knn": {"field": "vec",
                                    "query_vector": n._qv, "k": 10,
                                    "quantization": "pq", "nprobe": 4}}
        n.search("pm", json.loads(json.dumps(body)))          # warm
        f0 = transfer_snapshot()["device_fetches_total"]
        n.search("pm", json.loads(json.dumps(body)))
        assert transfer_snapshot()["device_fetches_total"] - f0 == 1

    def test_pq_mode_rides_metric_walk(self, pq_pair):
        """es_search_ann_quantized_dispatches_total{mode="pq"} (ISSUE 19
        acceptance): the labeled family reflects the mesh-lane rides."""
        from elasticsearch_tpu.common.metrics import render_openmetrics
        n = pq_pair
        n.search("pm", {"size": 5, "knn": {
            "field": "vec", "query_vector": n._qv, "k": 5,
            "quantization": "pq", "nprobe": 4}})
        text = render_openmetrics(n.metric_sections())
        line = [ln for ln in text.splitlines()
                if ln.startswith("es_search_ann_quantized_dispatches_total")
                and 'mode="pq"' in ln]
        assert line and float(line[0].rsplit(" ", 1)[1]) >= 1
