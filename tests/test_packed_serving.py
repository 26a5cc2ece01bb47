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


# -- the liveness fold (ISSUE 25) --------------------------------------------
# Tombstones and nested rows are folded into the packed postings when they
# change; the program takes no liveness row.

WORDS = ["fox", "dog", "quick", "brown", "lazy", "red", "story", "jumps"]


def fold_node(tmp_path, n_shards, segments, n=60):
    """n documents; "fox" in two of three, "dog" in half, ranks 0..n-1."""
    node = NodeService(str(tmp_path / "fold"))
    node.create_index("idx", settings={"number_of_shards": n_shards},
                      mappings={"_doc": {"properties": {
                          "title": {"type": "text"},
                          "tag": {"type": "keyword"},
                          "rank": {"type": "long"}}}})
    per_seg = -(-n // segments)
    for i in range(n):
        text = " ".join(w for j, w in enumerate(WORDS)
                        if (i + j) % 3 != 0 or (i * j) % 5 == 1)
        text += " fox" * (i % 4)
        node.index_doc("idx", str(i), {"title": text, "tag": f"t{i % 3}",
                                       "rank": i})
        if (i + 1) % per_seg == 0:
            node.refresh("idx")
    node.refresh("idx")
    return node


def hits_of(out):
    return [(h["_id"], h["_score"]) for h in out["hits"]["hits"]]


def fold_spans():
    from elasticsearch_tpu.common import tracing
    return tracing.AGGREGATE.stats().get("packed.live_fold",
                                         {"total": 0})["total"]


def unit_view(n, text=lambda i: "common word%d" % (i % 7)):
    from elasticsearch_tpu.index.segment import SegmentBuilder
    from elasticsearch_tpu.mapping.mapper import MapperService
    mapper = MapperService().document_mapper("_doc")
    b = SegmentBuilder(seg_id=1)
    for i in range(n):
        b.add(mapper.parse({"t": text(i)}, doc_id=str(i)), "_doc")
    seg = b.build()
    return seg, PackedIndexView([(0, seg)])


class TestLivenessFold:
    @pytest.mark.parametrize("n_shards,segments",
                             [(1, 1), (1, 3), (5, 1), (5, 3)])
    def test_deletes_inside_the_top_k(self, tmp_path, n_shards, segments):
        node = fold_node(tmp_path, n_shards, segments)
        body = {"query": {"match": {"title": "quick fox"}}}
        before = node.search("idx", body, size=10)
        top = hits_of(before)
        gone = [top[0][0], top[3][0], top[7][0]]
        for doc_id in gone:
            node.delete_doc("idx", doc_id)
        view = node.indices["idx"].packed_view()
        node.refresh("idx")         # the engine applies its pending deletes
        assert node.indices["idx"].packed_view() is view
        packed = node.search("idx", body, size=60)
        general = general_path(node, "idx", body, size=60)
        assert packed["hits"]["total"] == general["hits"]["total"] \
            == before["hits"]["total"] - len(gone)
        got = dict(hits_of(packed))
        assert set(got) == {i for i, _ in hits_of(general)}
        assert not set(got) & set(gone)
        # term statistics still count the deleted documents (as Lucene's do
        # until a merge): whoever is left scores what it scored
        for doc_id, score in top:
            if doc_id not in gone:
                assert got[doc_id] == pytest.approx(score, abs=1e-6)
        if n_shards == 1:           # else the general lane's IDF is per shard
            assert [i for i, _ in hits_of(packed)] \
                == [i for i, _ in hits_of(general)]
            for (_, sp), (_, sg) in zip(hits_of(packed), hits_of(general)):
                assert sp == pytest.approx(sg, abs=1e-6)
        node.close()

    def test_tombstone_is_seen_by_the_next_search(self):
        """No refresh and no new view: the segment's `live_gen` moved."""
        seg, view = unit_view(40)
        q = [PackedQuery(terms=["word3"])]
        _, docs, hits = view.search("t", q, k=16)
        assert int(hits[0]) == 6 and 3 in docs[0]
        assert seg.delete_local(3)
        _, docs, hits = view.search("t", q, k=16)
        assert int(hits[0]) == 5 and 3 not in docs[0]

    @pytest.mark.parametrize("order", ["fold_then_extend",
                                       "extend_then_fold"])
    def test_extended_view_returns_the_old_pad_doc(self, tmp_path, order):
        """The first document a refresh appends takes the global id that was
        the base view's document count. A posting folded to THAT would come
        back as this document."""
        node = fold_node(tmp_path, 1, 1, n=20)
        body = {"query": {"match": {"title": "fox"}}}
        first = node.search("idx", body, size=40)
        base = node.indices["idx"].packed_view()
        gone = [h[0] for h in hits_of(first)[:3]]
        for doc_id in gone:
            node.delete_doc("idx", doc_id)
        if order == "fold_then_extend":
            node.refresh("idx")
            node.search("idx", body)            # folds into the base's ids
            assert node.indices["idx"].packed_view() is base
        for i in range(100, 104):
            node.index_doc("idx", str(i), {"title": "fox fox", "rank": i})
        node.refresh("idx")
        view = node.indices["idx"].packed_view()
        assert view is not base and view.extended_from_base
        assert view.ids_packed[base.n_total] == "100"
        out = node.search("idx", body, size=40)
        ids = [i for i, _ in hits_of(out)]
        assert {"100", "101", "102", "103"} <= set(ids)
        assert not set(ids) & set(gone)
        assert out["hits"]["total"] == first["hits"]["total"] - 3 + 4 \
            == general_path(node, "idx", body, size=40)["hits"]["total"]
        assert len(ids) == len(set(ids)) == out["hits"]["total"]
        node.close()

    def test_nested_rows_are_never_hits(self, tmp_path):
        node = NodeService(str(tmp_path / "nested"))
        node.create_index("blog", mappings={"_doc": {"properties": {
            "title": {"type": "string"},
            "comments": {"type": "nested", "properties": {
                "text": {"type": "string"}}}}}})
        for i in range(6):
            node.index_doc("blog", str(i), {
                "title": "great post" if i % 2 else "dull post",
                "comments": [{"text": "great post"}, {"text": "post"}]})
        node.refresh("blog")
        view = node.indices["blog"].packed_view()
        assert view.n_total >= 18           # the nested rows are in the space
        _, docs, hits = view.search("title", [PackedQuery(terms=["post"])],
                                    k=32)
        assert int(hits[0]) == 6
        assert {view.ids_packed[d] for d in docs[0] if d >= 0} \
            == {str(i) for i in range(6)}
        # every posting of the nested field belongs to a nested row
        _, docs, hits = view.search(
            "comments.text", [PackedQuery(terms=["post"])], k=32)
        assert int(hits[0]) == 0 and (docs[0] == -1).all()
        node.delete_doc("blog", "1")        # cascades to its nested rows
        node.refresh("blog")
        out = node.search("blog", {"query": {"match": {"title": "great"}}})
        assert {h["_id"] for h in out["hits"]["hits"]} == {"3", "5"}
        node.close()

    @pytest.mark.parametrize("flt", [
        {"term": {"tag": "t1"}}, {"range": {"rank": {"gte": 10, "lt": 40}}}])
    def test_filtered_program_with_tombstones(self, tmp_path, flt):
        node = fold_node(tmp_path, 2, 2)
        body = {"query": {"bool": {"must": [{"match": {"title": "fox dog"}}],
                                   "filter": [flt]}}}
        before = node.search("idx", body, size=60)
        gone = [h[0] for h in hits_of(before)[:4]]
        for doc_id in gone:
            node.delete_doc("idx", doc_id)
        node.refresh("idx")
        packed = node.search("idx", body, size=60)
        general = general_path(node, "idx", body, size=60)
        assert packed["hits"]["total"] == general["hits"]["total"] \
            == before["hits"]["total"] - 4
        assert {i for i, _ in hits_of(packed)} \
            == {i for i, _ in hits_of(general)} \
            == {i for i, _ in hits_of(before)} - set(gone)
        node.close()

    @pytest.mark.parametrize("d", ["1", "switch", "switch+1", "tenth"])
    def test_incremental_fold_equals_full_fold(self, d):
        """Array for array, either side of the length that switches the
        programs; the reference is the gather written in numpy."""
        from elasticsearch_tpu.ops import bm25_sparse as K
        n = K.FOLD_IDS_MAX + 600
        d = {"1": 1, "switch": K.FOLD_IDS_MAX, "switch+1": K.FOLD_IDS_MAX + 1,
             "tenth": n // 10}[d]
        seg, view = unit_view(n)
        q = [PackedQuery(terms=["common", "word2"])]
        view.search("t", q, k=8)
        pf = view.field("t")
        ids0 = np.asarray(pf.doc_ids)
        assert (ids0[:pf.total_p] < n).all() \
            and (ids0[pf.total_p:] == K.PACKED_PAD_DOC).all()
        ran = {p: p.record.invocations
               for p in (K.packed_fold_ids, K.packed_fold_live)}
        dead = np.random.default_rng(d).permutation(n)[:d]
        for local in dead:
            seg.delete_local(int(local))
        _, _, hits = view.search("t", q, k=8)
        assert int(hits[0]) == n - d
        live = np.ones(n + 1, bool)
        live[dead] = False
        live[n] = False
        want = np.where(live[np.minimum(ids0, n)], ids0, K.PACKED_PAD_DOC)
        np.testing.assert_array_equal(np.asarray(pf.doc_ids), want)
        np.testing.assert_array_equal(pf.folded_live[:n], live[:n])
        ran = {p.record.name: p.record.invocations - n0
               for p, n0 in ran.items()}
        short = d <= K.FOLD_IDS_MAX
        assert ran == {"ops:packed_fold_ids": int(short),
                       "ops:packed_fold_live": int(not short)}
        # and the two programs against each other on the same input
        pad = np.full(-(-d // K.FOLD_IDS_BLOCK) * K.FOLD_IDS_BLOCK,
                      K.PACKED_PAD_DOC, np.int32)
        pad[:d] = dead
        import jax.numpy as jnp
        by_list = K.packed_fold_ids(
            jnp.asarray(ids0), jnp.asarray(pad),
            jnp.int32(len(pad) // K.FOLD_IDS_BLOCK))
        by_row = K.packed_fold_live(jnp.asarray(ids0), jnp.asarray(live))
        np.testing.assert_array_equal(np.asarray(by_list),
                                      np.asarray(by_row))

    def test_searches_race_a_deleter(self):
        """Two threads search while a third deletes: the fold donates the
        postings' buffer, and no search may dispatch the old one; a document
        whose delete had returned before a search began is not in it."""
        import sys
        import threading
        import time
        n = 160
        seg, view = unit_view(n)
        q = [PackedQuery(terms=["common"])]
        view.search("t", q, k=256)                  # packed and warm
        done_at: dict[int, float] = {}
        errors: list = []
        searches = [0]
        stop = threading.Event()

        def searcher():
            try:
                while not stop.is_set():
                    began = time.perf_counter()
                    dead = {i for i, t in list(done_at.items()) if t < began}
                    _, docs, hits = view.search("t", q, k=256)
                    got = {int(x) for x in docs[0] if x >= 0}
                    assert not got & dead, sorted(got & dead)
                    assert int(hits[0]) == len(got) <= n - len(dead)
                    searches[0] += 1
            except BaseException as e:      # noqa: BLE001 — reported below
                errors.append(e)
                stop.set()

        def deleter():
            try:
                for i in range(0, n, 2):
                    if stop.is_set():
                        return
                    seg.delete_local(i)
                    done_at[i] = time.perf_counter()
                    time.sleep(0.002)
            except BaseException as e:      # noqa: BLE001
                errors.append(e)
            finally:
                stop.set()

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        threads = [threading.Thread(target=f)
                   for f in (searcher, searcher, deleter)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            stop.set()
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert len(done_at) == n // 2 and searches[0] > 0
        _, docs, hits = view.search("t", q, k=256)
        assert int(hits[0]) == n // 2
        assert {int(x) for x in docs[0] if x >= 0} == set(range(1, n, 2))
        pf = view.field("t")
        assert pf.in_use == 0

    def test_one_fold_for_a_delete_and_two_searches(self, tmp_path):
        node = make_node(tmp_path)
        body = {"query": {"match": {"title": "fox"}}}
        node.search("idx", body)
        node.delete_doc("idx", "5")
        node.refresh("idx")
        before = fold_spans()
        a = node.search("idx", body)
        b = node.search("idx", body)
        assert fold_spans() == before + 1
        assert hits_of(a) == hits_of(b) and "5" not in dict(hits_of(a))
        node.close()

    @pytest.mark.parametrize("program", ["plain", "filtered"])
    def test_program_gathers_no_liveness(self, program):
        """At the shape of `wiki.rerank-top1000`: no operand of the program
        is a liveness row or any other array over the doc space, and
        nothing is gathered element by element at the Q x S x CHUNK
        candidate slots, by either program: the filtered one reads its
        ranks from streams aligned with the postings."""
        import re
        import jax
        import jax.numpy as jnp
        from elasticsearch_tpu.ops import bm25_sparse as K
        from elasticsearch_tpu.serving.packed_view import (
            CHUNK, F_RANGE, F_TERM, F_TERM_VALS)
        Q, S, P, N = 256, 256, 1 << 24, 262144
        sd = jax.ShapeDtypeStruct
        args = [sd((Q, 3 * S + 1), jnp.int32), sd((P,), jnp.int32),
                sd((P,), jnp.float32), sd((P,), jnp.float32)] \
            + [sd((), jnp.float32)] * 4
        if program == "plain":
            low = K.bm25_serve_packed.jit.lower(
                *args, S=S, CHUNK=CHUNK, R=8, k=1024)
        else:
            low = K.bm25_serve_packed_filtered.jit.lower(
                *args, (sd((P,), jnp.int32),),
                *[sd((Q, F_RANGE), jnp.int32)] * 4,
                sd((Q, F_TERM), jnp.int32),
                sd((Q, F_TERM, F_TERM_VALS), jnp.int32),
                sd((Q, F_TERM), jnp.int32), S=S, CHUNK=CHUNK, R=8, k=1024,
                FR=F_RANGE, FT=F_TERM, TV=F_TERM_VALS)
        text = low.as_text()
        params = re.search(r"func\.func public @main\((.*?)\)\s*->", text,
                           re.S).group(1)
        assert "xi1>" not in params and f"{N}x" not in params
        assert params.count(f"tensor<{P}xi32>") == \
            (1 if program == "plain" else 2)        # doc ids | and ranks
        per_slot = []           # (operand type, result type) of such gathers
        for m in re.finditer(
                r'"stablehlo\.gather"\(.*?slice_sizes = array<i64: ([\d, ]+)>'
                r'.*?:\s*\((tensor<[^>]+>), tensor<[^>]+>\)\s*->\s*'
                r'tensor<([\dx]+)x(\w+)>', text):
            sizes, operand, dims, dtype = m.groups()
            elements = int(np.prod([int(x) for x in dims.split("x")]))
            # an element at every candidate slot
            if elements >= Q * S * CHUNK and set(sizes.split(", ")) == {"1"}:
                per_slot.append((operand, dtype))
        assert per_slot == []


# -- a batch's operands ride the program's own dispatch (ISSUE 32) ------------
# The slot table and the filter descriptors go to the jitted call as host
# arrays, the four BM25 scalars stay on the chip for the view's life, and
# nothing is made on the device one value at a time before take-off.

def _slots_view():
    """Three segments of 700 documents: "common" in every one (two CHUNKs a
    segment), a Zipf-like tail so that df takes many values, tombstones in
    the first and the last segment folded by a search."""
    from elasticsearch_tpu.index.segment import SegmentBuilder
    from elasticsearch_tpu.mapping.mapper import MapperService
    mapper = MapperService().document_mapper("_doc")
    rng = np.random.default_rng(32)
    segs = []
    for si in range(3):
        b = SegmentBuilder(seg_id=si + 1)
        for i in range(700):
            tail = rng.zipf(1.3, size=int(rng.integers(1, 9))) % 400
            text = "common " + " ".join(f"w{t}" for t in tail)
            b.add(mapper.parse({"t": text, "rank": si * 700 + i},
                               doc_id=f"{si}-{i}"), "_doc")
        segs.append((si, b.build()))
    view = PackedIndexView(segs)
    for si, local in ((0, 3), (0, 44), (2, 699)):
        segs[si][1].delete_local(local)
    view.search("t", [PackedQuery(["common"])], k=8)      # folds them
    return view


@pytest.fixture(scope="module")
def slots_view():
    return _slots_view()


def _random_bodies(n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        terms = [f"w{t}" for t in rng.zipf(1.3, size=int(rng.integers(2, 6)))
                 % 420]                     # w400..w419 are not in the index
        out.append(PackedQuery(terms, boost=float(rng.choice([1.0, 2.5, 0.3])),
                               operator=str(rng.choice(["or", "and"])),
                               msm=int(rng.integers(1, 3))))
    return out


SLOT_CASES = {
    "absent-terms": [PackedQuery(["common", "zzz-absent", "w3", "w401"])],
    "no-term-in-the-index": [PackedQuery(["nope", "nada"]),
                             PackedQuery(["w9999"])],
    "a-body-without-terms": [PackedQuery([]), PackedQuery(["w1"])],
    "term-longer-than-the-widest": [PackedQuery(["common", "w1" + "x" * 60]),
                                    PackedQuery(["w2"])],
    "operator-and": [PackedQuery(["common", "w1", "w2"], operator="and"),
                     PackedQuery(["w1", "absent"], operator="and")],
    "minimum-should-match": [PackedQuery(["common", "w1", "w2"], msm=2),
                             PackedQuery(["w5"], msm=3)],
    "boosts": [PackedQuery(["common", "w1"], boost=2.5),
               PackedQuery(["w1", "w7"], boost=0.3),
               PackedQuery(["w2"], boost=2)],
    "1-body": _random_bodies(1, 1), "2-bodies": _random_bodies(2, 2),
    "33-bodies": _random_bodies(33, 33), "256-bodies": _random_bodies(256, 256),
}


@pytest.mark.parametrize("k1,b", [(1.2, 0.75), (0.9, 0.4)])
@pytest.mark.parametrize("case", SLOT_CASES)
def test_slot_table_is_the_reference_bit_for_bit(slots_view, case, k1, b):
    """`_build_slots` (one term lookup a batch, vectors over its (query,
    term) pairs) against the loop it replaced, kept in slots_reference.py."""
    import slots_reference
    queries = SLOT_CASES[case]
    pf = slots_view.field("t")
    assert pf.starts.shape[1] == 3 and int(pf.df.max()) == 2100
    got, S, R = slots_view._build_slots(pf, queries, "t", k1, b)
    want, S_ref, R_ref = slots_reference.build_slots(
        slots_view, pf, queries, "t", k1, b)
    assert (S, R) == (S_ref, R_ref)
    assert got.dtype == want.dtype == np.int32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)       # the weights' bits too


def test_idf_is_math_logs_on_every_df(slots_view):
    """Every df a term of the index has, alone in a body: the weight's bits
    are `math.log`'s (what the per-segment lane and the reference print)."""
    import math
    pf = slots_view.field("t")
    terms = [str(t) for t in pf.terms]
    table, S, _ = slots_view._build_slots(
        pf, [PackedQuery([t]) for t in terms], "t", 1.2, 0.75)
    N = slots_view.doc_count
    want = np.array([math.log(1 + (N - d + 0.5) / (d + 0.5)) * 2.2
                     for d in pf.df.tolist()], np.float32)
    np.testing.assert_array_equal(table[:len(terms), 2 * S].view(np.float32),
                                  want)
    assert len(set(pf.df.tolist())) > 40


@pytest.fixture()
def device_calls(monkeypatch):
    """What a search asks of the device from Python besides its program:
    eager operations (every primitive applied outside a jit is built by
    `dispatch.xla_primitive_callable`: `apply_primitive`'s first line) and
    transfers of its own (`jnp.asarray`, `jax.device_put`)."""
    import jax
    import jax.numpy as jnp
    from jax._src import dispatch
    seen = {"eager": [], "transfers": 0}
    real = dispatch.xla_primitive_callable

    def callable_of(prim, **params):
        seen["eager"].append(prim.name)
        return real(prim, **params)

    def counted(fn):
        def call(*a, **kw):
            seen["transfers"] += 1
            return fn(*a, **kw)
        return call
    monkeypatch.setattr(dispatch, "xla_primitive_callable", callable_of)
    monkeypatch.setattr(jnp, "asarray", counted(jnp.asarray))
    monkeypatch.setattr(jax, "device_put", counted(jax.device_put))
    return seen


def _traced_search(view, queries, k=10, field="t"):
    """-> (the search's result, its `packed.build_slots` attributes, the
    `program` spans it opened, the bytes it reported as uploaded)."""
    from elasticsearch_tpu.common import tracing
    with tracing.Tracer().request("test") as trace:
        out = view.search(field, queries, k=k)
    prep, = [s.attrs for s in trace.spans if s.name == "packed.build_slots"]
    programs = [s.attrs["site"] for s in trace.spans if s.name == "program"]
    return out, prep, programs, trace.h2d_bytes


def _range_filter(lo, hi):
    from elasticsearch_tpu.search.query_dsl import RangeNode
    return ((False, RangeNode(field_name="rank",
                              bounds_per_query=[(lo, hi, True, False)])),)


WARM_CASES = {
    "solo": ([PackedQuery(["common", "w1"])], 1, "ops:bm25_serve_packed"),
    "five-in-the-32-row-bucket": (
        [PackedQuery(["common", f"w{i}"]) for i in range(5)], 1,
        "ops:bm25_serve_packed"),
    "filtered": ([PackedQuery(["common"], filters=_range_filter(0, 900))],
                 8, "ops:bm25_serve_packed_filtered"),
}


@pytest.mark.parametrize("case", WARM_CASES)
def test_warm_search_is_one_program_and_nothing_else(case, device_calls):
    queries, operands, site = WARM_CASES[case]
    view = _slots_view()
    first, prep, _, _ = _traced_search(view, queries)
    # the fold's search made the constants; a filter column is built once
    assert prep["consts"] == "reused" and prep["operands"] == operands
    device_calls["eager"].clear()
    device_calls["transfers"] = 0
    calls0 = view.device_calls
    again, prep, programs, uploaded = _traced_search(view, queries)
    assert device_calls == {"eager": [], "transfers": 0}
    assert programs == [site] and view.device_calls == calls0 + 1
    assert prep["consts"] == "reused" and prep["operands"] == operands
    Q_pad = 1 if len(queries) == 1 else 32
    table = 4 * Q_pad * (3 * 32 + 1)
    descriptors = Q_pad * (2 * (4 + 4 + 4 + 4) + 2 * (4 + 4 * 4 + 4))
    assert prep["h2d_bytes"] == uploaded \
        == table + (descriptors if operands == 8 else 0)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    if operands == 8:
        assert int(again[2][0]) == 900 - 2      # two tombstones in range


def test_constants_are_made_once_a_view_and_anew_after_a_refresh(tmp_path):
    node = make_node(tmp_path, n_shards=2, segments=2)
    svc = node.indices["idx"]
    queries = [PackedQuery(["quick", "fox"])]

    def search(view):
        return _traced_search(view, queries, k=8, field="title")[:2]

    old = svc.packed_view()
    _, prep = search(old)
    assert prep["consts"] == "made" and prep["operands"] == 1
    table = 4 * (3 * 32 + 1)
    assert prep["h2d_bytes"] == table + 16       # the four scalars, once
    _, prep = search(old)
    assert prep["consts"] == "reused" and prep["h2d_bytes"] == table
    k1, b, avgdl, zero = old._consts[("title", 1.2, 0.75)]
    assert [x.dtype for x in (k1, b, avgdl, zero)] == [np.float32] * 4
    assert (float(k1), float(b), float(zero)) \
        == (float(np.float32(1.2)), 0.75, 0.0)
    assert float(avgdl) == float(np.float32(old.avgdl("title")))

    node.index_doc("idx", "long", {"title": "fox " * 40, "rank": 99})
    node.refresh("idx")
    new = svc.packed_view()
    assert new is not old and new.avgdl("title") > old.avgdl("title")
    (scores, docs, hits), prep = search(new)
    assert prep["consts"] == "made" and int(hits[0]) == 6
    assert float(new._consts[("title", 1.2, 0.75)][2]) \
        == float(np.float32(new.avgdl("title")))
    assert old._consts[("title", 1.2, 0.75)][2] is avgdl   # the old view's own
    # the scores are the per-segment lane's, which reads avgdl for itself
    body = {"query": {"match": {"title": "quick fox"}}}
    assert hits_of(node.search("idx", body)) \
        == hits_of(general_path(node, "idx", body))
    node.close()
