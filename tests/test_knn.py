"""Vector search tests: exact kNN, filters, rescore pipeline, hybrid
BM25->dense (BASELINE configs #4/#5 workload shapes). The mesh kNN lane is
pinned in tests/test_mesh.py::TestMeshKnn."""

import numpy as np
import pytest

from elasticsearch_tpu.mapping.mapper import MapperService
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.search.shard_searcher import ShardSearcher
from elasticsearch_tpu.node import NodeService

DIMS = 8


def unit(v):
    v = np.asarray(v, np.float32)
    return (v / np.linalg.norm(v)).tolist()


MAPPING = {"_doc": {"properties": {
    "title": {"type": "text"},
    "vec": {"type": "dense_vector", "dims": DIMS},
    "cat": {"type": "keyword"},
}}}


@pytest.fixture(scope="module")
def searcher(tmp_path_factory):
    rng = np.random.default_rng(0)
    ms = MapperService(mappings=MAPPING)
    eng = Engine(str(tmp_path_factory.mktemp("vecshard")), ms)
    for i in range(64):
        base = np.zeros(DIMS)
        base[i % DIMS] = 1.0
        noise = rng.normal(0, 0.05, DIMS)
        eng.index(str(i), {
            "title": f"doc number {i} " + ("quick " if i % 2 == 0 else "slow "),
            "vec": unit(base + noise),
            "cat": "even" if i % 2 == 0 else "odd"})
        if i == 31:
            eng.refresh()
    eng.refresh()
    return ShardSearcher(0, eng.segments, ms)


class TestExactKnn:
    def test_nearest_axis(self, searcher):
        q = np.zeros(DIMS)
        q[3] = 1.0
        res = searcher.execute_knn("vec", [unit(q)], k=5)
        keys = [int(k) for k in res.doc_keys[0] if k >= 0]
        hits = searcher.execute_fetch_phase(keys, res.scores[0], None)
        # nearest docs are those with base axis 3: ids 3, 11, 19, ...
        assert all(int(h.doc_id) % DIMS == 3 for h in hits)
        assert res.scores[0][0] > 0.98      # cosine ~1 to its own axis

    def test_metrics_agree_on_unit_vectors(self, searcher):
        q = np.zeros(DIMS)
        q[1] = 1.0
        r_cos = searcher.execute_knn("vec", [unit(q)], k=3, metric="cosine")
        r_dot = searcher.execute_knn("vec", [unit(q)], k=3, metric="dot")
        r_l2 = searcher.execute_knn("vec", [unit(q)], k=3, metric="l2")
        ids = lambda r: [int(k) for k in r.doc_keys[0] if k >= 0]  # noqa: E731
        assert ids(r_cos) == ids(r_dot) == ids(r_l2)

    def test_knn_filter(self, searcher):
        q = np.zeros(DIMS)
        q[2] = 1.0
        fnode = searcher.parse([{"term": {"cat": "odd"}}])
        res = searcher.execute_knn("vec", [unit(q)], k=4, filter_node=fnode)
        keys = [int(k) for k in res.doc_keys[0] if k >= 0]
        hits = searcher.execute_fetch_phase(keys, res.scores[0], None)
        assert all(int(h.doc_id) % 2 == 1 for h in hits)

    def test_exactness_vs_numpy(self, searcher):
        rng = np.random.default_rng(7)
        q = unit(rng.normal(0, 1, DIMS))
        res = searcher.execute_knn("vec", [q], k=10)
        # brute force over stored vectors
        all_vecs = {}
        for seg in searcher.segments:
            vc = seg.vectors["vec"]
            v = np.asarray(vc.vecs)
            for local in range(seg.n_docs):
                all_vecs[seg.ids[local]] = v[local]
        sims = {d: float(np.dot(q, v) / (np.linalg.norm(q) * np.linalg.norm(v)))
                for d, v in all_vecs.items()}
        expect = sorted(sims, key=lambda d: -sims[d])[:10]
        keys = [int(k) for k in res.doc_keys[0] if k >= 0]
        got = [h.doc_id for h in searcher.execute_fetch_phase(
            keys, res.scores[0], None)]
        assert set(got) == set(expect)       # bf16 may swap near-ties
        # recall@10 == 1.0 for exact search
        for d, s in zip(got, res.scores[0]):
            assert abs(sims[d] - float(s)) < 5e-3   # bf16 matmul tolerance


class TestRescoreHybrid:
    def test_bm25_then_vector_rescore(self, searcher):
        """Hybrid: BM25 'quick' docs, re-ranked by vector sim to axis 5."""
        q = np.zeros(DIMS)
        q[5] = 1.0
        node = searcher.parse([{"match": {"title": "quick"}}])
        first = searcher.execute_query_phase(node, size=32)
        res = searcher.rescore(first, {
            "window_size": 32,
            "query": {"rescore_query": {"function_score": {
                "query": {"match_all": {}},
                "cosine": {"field": "vec", "query_vectors": [unit(q)]},
                "boost_mode": "replace"}},
                "query_weight": 0.0, "rescore_query_weight": 1.0,
                "score_mode": "total"}})
        keys = [int(k) for k in res.doc_keys[0] if k >= 0]
        hits = searcher.execute_fetch_phase(keys, res.scores[0], None)
        # top hit: even doc (matches 'quick') whose base axis is 5... even
        # ids with i%8==5 are 13,21,... wait those are odd; even docs with
        # axis 5: none (5,13,21 odd) -> the best even doc aligns partially;
        # just assert ordering matches the rescore scores descending
        scores = [h.score for h in hits]
        assert scores == sorted(scores, reverse=True)
        assert res.total_hits[0] == first.total_hits[0]

    def test_rescore_respects_window(self, searcher):
        node = searcher.parse([{"match": {"title": "doc"}}])
        first = searcher.execute_query_phase(node, size=10)
        res = searcher.rescore(first, {
            "window_size": 3,
            "query": {"rescore_query": {"term": {"cat": "odd"}},
                      "score_mode": "total"}})
        # outside the window, keys keep their original order
        assert list(res.doc_keys[0][3:]) == list(first.doc_keys[0][3:])


class TestNodeKnnApi:
    def test_knn_via_node_search(self, tmp_path):
        node = NodeService(str(tmp_path / "n"))
        node.create_index("vecs", mappings=MAPPING)
        for i in range(16):
            base = np.zeros(DIMS)
            base[i % 4] = 1.0
            node.index_doc("vecs", str(i), {"title": f"d{i}",
                                            "vec": unit(base),
                                            "cat": "c"})
        node.refresh("vecs")
        q = np.zeros(DIMS)
        q[2] = 1.0
        out = node.search("vecs", {"knn": {"field": "vec",
                                           "query_vector": unit(q),
                                           "k": 4}})
        ids = [int(h["_id"]) for h in out["hits"]["hits"]]
        assert all(i % 4 == 2 for i in ids)
        node.close()
