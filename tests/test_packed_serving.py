"""The packed one-program serving lane (serving/packed_view.py).

Round-3 contract: eligible match/bool queries serve through ONE device
program over all shards/segments (the one-sync fast path), with results
identical to the per-segment general path. ref: the per-shard scatter-gather
of TransportSearchTypeAction + SearchPhaseController collapses into a packed
global top-k.
"""

import json

import numpy as np
import pytest

from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.serving.packed_view import PackedIndexView, PackedQuery

DOCS = [
    "the quick brown fox",
    "quick red fox jumps",
    "lazy brown dog",
    "quick quick quick fox",
    "unrelated text entirely",
    "fox fox fox fox brown",
    "a quick story about a dog and a fox",
    "brown brown brown",
]


def make_node(tmp_path, n_shards=1, segments=1, name="idx"):
    node = NodeService(str(tmp_path / "node"))
    node.create_index(name, settings={"number_of_shards": n_shards})
    per_seg = max(1, len(DOCS) // segments)
    for i, d in enumerate(DOCS):
        node.index_doc(name, str(i), {"title": d, "rank": i})
        if (i + 1) % per_seg == 0:
            node.refresh(name)
    node.refresh(name)
    return node


def general_path(node, index, body, size=10):
    """Force the per-segment general path by adding a benign non-packed key."""
    b = dict(body)
    b["track_scores"] = True        # not in PACKED_BODY_KEYS
    return node.search(index, b, size=size)


@pytest.mark.parametrize("n_shards,segments", [(1, 1), (1, 3), (2, 2), (3, 1)])
class TestPackedParity:
    def test_match_parity(self, tmp_path, n_shards, segments):
        node = make_node(tmp_path, n_shards, segments)
        body = {"query": {"match": {"title": "quick fox"}}}
        packed = node.search("idx", body)
        assert node.indices["idx"].search_stats["packed"] >= 1
        general = general_path(node, "idx", body)
        assert packed["hits"]["total"] == general["hits"]["total"]
        # multi-shard general path scores with per-shard IDF; the packed path
        # is index-global (a DFS phase for free) — compare the doc sets, and
        # exact scores only in the single-shard case
        assert {h["_id"] for h in packed["hits"]["hits"]} \
            == {h["_id"] for h in general["hits"]["hits"]}
        if n_shards == 1:
            for hp, hg in zip(packed["hits"]["hits"], general["hits"]["hits"]):
                assert hp["_id"] == hg["_id"]
                assert hp["_score"] == pytest.approx(hg["_score"], rel=1e-5)
        node.close()

    def test_operator_and_msm(self, tmp_path, n_shards, segments):
        node = make_node(tmp_path, n_shards, segments)
        for body in [
            {"query": {"match": {"title": {"query": "quick fox",
                                           "operator": "and"}}}},
            {"query": {"match": {"title": {
                "query": "quick brown fox",
                "minimum_should_match": 2}}}},
        ]:
            packed = node.search("idx", body)
            general = general_path(node, "idx", body)
            assert packed["hits"]["total"] == general["hits"]["total"]
            assert {h["_id"] for h in packed["hits"]["hits"]} \
                == {h["_id"] for h in general["hits"]["hits"]}
        node.close()

    def test_deletes_respected(self, tmp_path, n_shards, segments):
        node = make_node(tmp_path, n_shards, segments)
        before = node.search("idx", {"query": {"match": {"title": "fox"}}})
        ids = {h["_id"] for h in before["hits"]["hits"]}
        assert "5" in ids
        node.delete_doc("idx", "5")
        node.refresh("idx")   # NRT: deletes visible to search after refresh
        after = node.search("idx", {"query": {"match": {"title": "fox"}}})
        assert "5" not in {h["_id"] for h in after["hits"]["hits"]}
        assert after["hits"]["total"] == before["hits"]["total"] - 1
        node.close()


class TestPackedBehavior:
    def test_pagination(self, tmp_path):
        node = make_node(tmp_path)
        body = {"query": {"match": {"title": "fox brown quick"}}}
        full = node.search("idx", body, size=10)
        page = node.search("idx", {**body, "from": 2}, size=2)
        assert [h["_id"] for h in page["hits"]["hits"]] \
            == [h["_id"] for h in full["hits"]["hits"]][2:4]
        # max_score reports the global max even past the first page
        assert page["hits"]["max_score"] == full["hits"]["max_score"]
        node.close()

    def test_boost_scales_scores(self, tmp_path):
        node = make_node(tmp_path)
        base = node.search("idx", {"query": {"match": {"title": "fox"}}})
        boosted = node.search("idx", {"query": {"match": {"title": {
            "query": "fox", "boost": 2.5}}}})
        for hb, h in zip(boosted["hits"]["hits"], base["hits"]["hits"]):
            assert hb["_score"] == pytest.approx(h["_score"] * 2.5, rel=1e-5)
        node.close()

    def test_missing_terms(self, tmp_path):
        node = make_node(tmp_path)
        out = node.search("idx", {"query": {"match": {"title": "zzz"}}})
        assert out["hits"]["total"] == 0 and out["hits"]["hits"] == []
        # operator=and with one unknown term can never match
        out = node.search("idx", {"query": {"match": {"title": {
            "query": "fox zzz", "operator": "and"}}}})
        assert out["hits"]["total"] == 0
        # unknown field entirely
        out = node.search("idx", {"query": {"match": {"nope": "fox"}}})
        assert out["hits"]["total"] == 0
        node.close()

    def test_msearch_raw_bytes_parity(self, tmp_path):
        node = make_node(tmp_path)
        reqs = [({"index": "idx"},
                 {"query": {"match": {"title": q}}, "size": 5,
                  "_source": False})
                for q in ["quick fox", "brown", "dog story", "zzz"]]
        raw = node.msearch(reqs, raw=True)
        assert isinstance(raw, bytes)
        cooked = node.msearch(reqs)
        parsed = json.loads(raw)
        assert len(parsed["responses"]) == 4
        for rr, rc in zip(parsed["responses"], cooked["responses"]):
            assert rr["hits"]["total"] == rc["hits"]["total"]
            assert [h["_id"] for h in rr["hits"]["hits"]] \
                == [h["_id"] for h in rc["hits"]["hits"]]
            for hr, hc in zip(rr["hits"]["hits"], rc["hits"]["hits"]):
                assert hr["_score"] == pytest.approx(hc["_score"], rel=1e-4)
                assert "_source" not in hr
        node.close()

    def test_msearch_mixed_batch(self, tmp_path):
        """Packed-eligible and general requests mix in one msearch call."""
        node = make_node(tmp_path)
        reqs = [
            ({"index": "idx"}, {"query": {"match": {"title": "fox"}}}),
            ({"index": "idx"}, {"query": {"match": {"title": "fox"}},
                                "sort": [{"rank": "desc"}]}),
            ({"index": "missing_index"}, {"query": {"match_all": {}}}),
        ]
        out = node.msearch(reqs)
        assert out["responses"][0]["hits"]["total"] == 5
        ranks = [h["_source"]["rank"]
                 for h in out["responses"][1]["hits"]["hits"]]
        assert ranks == sorted(ranks, reverse=True)
        assert "error" in out["responses"][2]
        node.close()

    def test_source_filtering(self, tmp_path):
        node = make_node(tmp_path)
        out = node.search("idx", {"query": {"match": {"title": "fox"}},
                                  "_source": ["rank"]})
        h = out["hits"]["hits"][0]
        assert "rank" in h["_source"] and "title" not in h["_source"]
        out = node.search("idx", {"query": {"match": {"title": "fox"}},
                                  "_source": False})
        assert "_source" not in out["hits"]["hits"][0]
        node.close()

    def test_fallback_shapes_still_work(self, tmp_path):
        node = make_node(tmp_path)
        # bool+filter now rides the packed kernel's filter slots (r4);
        # shapes it can't express (aggs/sort/...) still take the general path
        out = node.search("idx", {"query": {"bool": {
            "must": [{"match": {"title": "fox"}}],
            "filter": [{"range": {"rank": {"lte": 3}}}]}}})
        assert {h["_id"] for h in out["hits"]["hits"]} <= {"0", "1", "2", "3"}
        stats = node.indices["idx"].search_stats
        assert stats["packed"] >= 1
        out = node.search("idx", {"query": {"match": {"title": "fox"}},
                                  "aggs": {"r": {"max": {"field": "rank"}}}})
        assert stats["sparse"] >= 1
        node.close()

    def test_unsafe_ids_use_dict_path(self, tmp_path):
        node = NodeService(str(tmp_path / "n2"))
        node.index_doc("idx", 'we"ird\\id', {"title": "quick fox"})
        node.refresh("idx")
        raw = node.msearch(
            [({"index": "idx"}, {"query": {"match": {"title": "fox"}},
                                 "_source": False})], raw=True)
        parsed = json.loads(raw)   # must still be valid JSON
        assert parsed["responses"][0]["hits"]["hits"][0]["_id"] == 'we"ird\\id'
        node.close()

    def test_view_reuse_and_live_refresh(self, tmp_path):
        node = make_node(tmp_path)
        svc = node.indices["idx"]
        v1 = svc.packed_view()
        node.search("idx", {"query": {"match": {"title": "fox"}}})
        assert svc.packed_view() is v1          # cached across requests
        node.delete_doc("idx", "0")             # tombstone only: same view,
        node.search("idx", {"query": {"match": {"title": "fox"}}})
        assert svc.packed_view() is v1          # refreshed liveness in place
        node.index_doc("idx", "99", {"title": "new fox"})
        node.refresh("idx")                     # segment set changed
        assert svc.packed_view() is not v1
        out = node.search("idx", {"query": {"match": {"title": "fox"}}})
        ids = {h["_id"] for h in out["hits"]["hits"]}
        assert "99" in ids and "0" not in ids
        node.close()


class TestPackedViewUnit:
    def test_chunking_splits_long_postings(self):
        from elasticsearch_tpu.mapping.mapper import MapperService
        from elasticsearch_tpu.index.segment import SegmentBuilder
        import elasticsearch_tpu.serving.packed_view as pv

        ms = MapperService()
        mapper = ms.document_mapper("_doc")
        b = SegmentBuilder(seg_id=1)
        n = 1500   # > 2 * CHUNK(512) postings for one term
        for i in range(n):
            b.add(mapper.parse({"t": "common word%d" % (i % 7)},
                               doc_id=str(i)), "_doc")
        seg = b.build()
        view = PackedIndexView([(0, seg)])
        scores, docs, hits = view.search(
            "t", [PackedQuery(terms=["common"])], k=8)
        assert int(hits[0]) == n               # every doc matches
        assert (scores[0] > -np.inf).all()
        pf = view.field("t")
        tid = pf.term_ids(["common"])[0]
        assert pf.lens[tid].sum() == n and pf.lens[tid].max() > pv.CHUNK


class TestReviewRegressions:
    """Round-3 code-review findings."""

    def test_overlong_doc_leaves_no_ghost(self, tmp_path):
        """A rejected overlong doc must not remain half-indexed."""
        from elasticsearch_tpu.index.segment import (_MAX_DOC_POSITIONS,
                                                     SegmentBuilder)
        from elasticsearch_tpu.mapping.mapper import MapperService
        ms = MapperService()
        mapper = ms.document_mapper("_doc")
        b = SegmentBuilder(seg_id=1)
        huge = " ".join("w" for _ in range(_MAX_DOC_POSITIONS + 1))
        import pytest as _pt
        with _pt.raises(ValueError):
            b.add(mapper.parse({"ok": "fine", "body": huge}, doc_id="1"),
                  "_doc")
        assert b.n_docs == 0 and not b.ids and not b.id_to_local
        seg = b.build()
        assert seg.n_docs == 0

    def test_mixed_types_use_dict_lane(self, tmp_path):
        """raw lane must not stamp '_doc' on a multi-type index."""
        node = NodeService(str(tmp_path / "n"))
        node.index_doc("idx", "1", {"t": "quick fox"}, type_name="tweet")
        node.index_doc("idx", "2", {"t": "quick dog"}, type_name="user")
        node.refresh("idx")
        raw = node.msearch([({"index": "idx"},
                             {"query": {"match": {"t": "quick"}},
                              "_source": False})], raw=True)
        parsed = json.loads(raw)
        types = {h["_id"]: h["_type"]
                 for h in parsed["responses"][0]["hits"]["hits"]}
        assert types == {"1": "tweet", "2": "user"}
        node.close()

    def test_newline_id_stays_valid_json(self, tmp_path):
        node = NodeService(str(tmp_path / "n"))
        node.index_doc("idx", "a\nb", {"t": "quick fox"})
        node.refresh("idx")
        raw = node.msearch([({"index": "idx"},
                             {"query": {"match": {"t": "quick"}},
                              "_source": False})], raw=True)
        parsed = json.loads(raw)    # must parse
        assert parsed["responses"][0]["hits"]["hits"][0]["_id"] == "a\nb"
        node.close()

    def test_packed_group_failure_is_the_items_error(self, tmp_path,
                                                     monkeypatch):
        """An exception inside the packed lane must not 500 the whole
        msearch, and must not be served by a slower lane either: each
        member of the group carries the error (per-item contract)."""
        node = make_node(tmp_path)
        import elasticsearch_tpu.node as node_mod

        def boom(*a, **k):
            raise RuntimeError("packed lane exploded")
        monkeypatch.setattr(node_mod.NodeService, "_packed_search", boom)
        out = node.msearch([({"index": "idx"},
                             {"query": {"match": {"title": "fox"}}}),
                            ({"index": "missing"}, {})])
        assert out["responses"][0] == {
            "error": "RuntimeError[packed lane exploded]", "status": 500}
        assert "error" in out["responses"][1]
        node.close()

    def test_raising_packed_program_is_a_500_not_another_lane(
            self, tmp_path, monkeypatch):
        """ISSUE 21: a device program that raises (a compile the backend
        refuses, an out-of-memory) reaches the REST caller as HTTP 500
        with the text; no slower lane serves the request and no decline
        is booked for it."""
        import urllib.error
        import urllib.request

        from elasticsearch_tpu.common import device_stats
        from elasticsearch_tpu.rest import HttpServer
        from elasticsearch_tpu.serving import packed_view

        node = make_node(tmp_path)
        srv = HttpServer(node, port=0).start()
        try:
            def post(body):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/idx/_search",
                    data=json.dumps(body).encode(), method="POST")
                with urllib.request.urlopen(req, timeout=60) as r:
                    return json.loads(r.read())

            body = {"query": {"match": {"title": "quick fox"}}}
            assert post(body)["hits"]["total"] == 5     # view built + warm

            def refused(*a, **k):
                raise RuntimeError("RESOURCE_EXHAUSTED: compile refused")
            monkeypatch.setattr(packed_view, "bm25_serve_packed", refused)
            before = device_stats.lane_decisions_snapshot()
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(body)
            assert ei.value.code == 500
            assert "RESOURCE_EXHAUSTED: compile refused" in \
                json.loads(ei.value.read())["error"]
            after = device_stats.lane_decisions_snapshot()
            moved = {k for k in after if after[k] != before.get(k, 0)}
            assert not moved, f"another lane ran or declined: {moved}"
        finally:
            srv.stop()
            node.close()
