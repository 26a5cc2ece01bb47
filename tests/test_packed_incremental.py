"""Incremental packed-view extension: an NRT refresh appends segment blocks
to the cached view (O(new postings)) instead of repacking the index, with
exact parity against a from-scratch build (advisor r3 medium finding).
"""

import numpy as np
import pytest

from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.serving.packed_view import PackedIndexView

MAPPING = {"_doc": {"properties": {
    "body": {"type": "text"},
    "tag": {"type": "keyword"},
    "price": {"type": "long"},
}}}


@pytest.fixture()
def node(tmp_path):
    n = NodeService(data_path=str(tmp_path))
    n.create_index("inc", mappings=MAPPING)
    yield n
    n.close()


def _index_batch(node, lo, hi, tag="a"):
    for i in range(lo, hi):
        node.index_doc("inc", str(i),
                       {"body": f"common word{i % 7} filler",
                        "tag": f"{tag}{i % 3}", "price": i})
    node.refresh("inc")


def _fresh_view(node):
    svc = node.indices["inc"]
    entries = [(si, seg) for si, e in enumerate(svc.shards)
               for seg in e.segments]
    return PackedIndexView(entries)


class TestIncrementalExtension:
    def test_refresh_extends_instead_of_repacking(self, node):
        _index_batch(node, 0, 20)
        v1 = node.indices["inc"].packed_view()
        node.search("inc", {"query": {"match": {"body": "common"}}})
        assert "body" in v1._fields            # packed by the search
        _index_batch(node, 20, 30)
        v2 = node.indices["inc"].packed_view()
        assert v2 is not v1
        assert v2.extended_from_base, "refresh must extend, not repack"
        assert v2._fields["body"].total_p > v1._fields["body"].total_p

    def test_extended_view_search_parity(self, node):
        _index_batch(node, 0, 25)
        node.search("inc", {"query": {"match": {"body": "common"}}})
        _index_batch(node, 25, 40)
        v2 = node.indices["inc"].packed_view()
        assert v2.extended_from_base
        fresh = _fresh_view(node)
        from elasticsearch_tpu.serving.packed_view import PackedQuery
        for terms in (["common"], ["word3"], ["word3", "filler"]):
            q = [PackedQuery(terms=terms)]
            s_ext, d_ext, h_ext = v2.search("body", q, k=50)
            s_fr, d_fr, h_fr = fresh.search("body", q, k=50)
            assert int(h_ext[0]) == int(h_fr[0]), terms
            np.testing.assert_allclose(
                np.sort(s_ext[0][s_ext[0] > -np.inf]),
                np.sort(s_fr[0][s_fr[0] > -np.inf]), rtol=1e-5)

    def test_extended_filter_columns_with_vocab_growth(self, node):
        _index_batch(node, 0, 20, tag="a")
        # build the filter column on the first view
        out1 = node.search("inc", {"query": {"bool": {
            "must": [{"match": {"body": "common"}}],
            "filter": [{"term": {"tag": "a1"}}]}}, "size": 50})
        # new segment introduces NEW keyword vocab ("z*") -> ordinal remap
        _index_batch(node, 20, 32, tag="z")
        v2 = node.indices["inc"].packed_view()
        assert v2.extended_from_base
        out2 = node.search("inc", {"query": {"bool": {
            "must": [{"match": {"body": "common"}}],
            "filter": [{"term": {"tag": "a1"}}]}}, "size": 50})
        ids1 = {h["_id"] for h in out1["hits"]["hits"]}
        ids2 = {h["_id"] for h in out2["hits"]["hits"]}
        assert ids1 <= ids2
        out3 = node.search("inc", {"query": {"bool": {
            "must": [{"match": {"body": "common"}}],
            "filter": [{"term": {"tag": "z1"}}]}}, "size": 50})
        want = {str(i) for i in range(20, 32) if i % 3 == 1}
        assert {h["_id"] for h in out3["hits"]["hits"]} == want

    @pytest.mark.parametrize("field", ["price", "tag"])
    @pytest.mark.parametrize("second,reranked", [
        # values between, below and above the old ones: every old rank moves
        ([(5, "a"), (15, "c"), (25, "e"), (35, "g"), (20, "d")], True),
        # only values the base holds: the old rows come over as they are
        ([(30, "b"), (10, "f"), (20, "d")], False)],
        ids=["new-values", "known-values"])
    def test_extended_filter_column_equals_one_built_anew(
            self, node, field, second, reranked):
        """A refresh extends the column: ordinals and distinct values as a
        view built from all segments has them, and the span says how many
        head rows went through the lookup table."""
        from elasticsearch_tpu.common import tracing

        def index(rows, at):
            for i, (price, tag) in enumerate(rows):
                node.index_doc("inc", str(at + i), {
                    "body": "common", "price": price, "tag": tag})
            node.refresh("inc")

        def ids(flt):
            out = node.search("inc", {"query": {"bool": {
                "must": [{"match": {"body": "common"}}],
                "filter": [flt]}}, "size": 50})
            return {int(h["_id"]) for h in out["hits"]["hits"]}

        first = [(10, "b"), (20, "d"), (30, "f"), (20, "d")]
        index(first, 0)
        assert ids({"range": {"price": {"gt": 10, "lte": 30}}}) == {1, 2, 3}
        assert ids({"range": {"tag": {"gt": "b", "lte": "f"}}}) == {1, 2, 3}
        base = node.indices["inc"].packed_view()
        index(second, len(first))
        with tracing.Tracer().request("test") as trace:
            view = node.indices["inc"].packed_view()
        assert view is not base and view.extended_from_base
        spans = {s.attrs["field"]: s.attrs for s in trace.spans
                 if s.name == "packed.filter_column"}
        rows = first + second
        want = sorted({r[field == "tag"] for r in rows})
        assert spans[field]["reranked"] == \
            (base.n_total if reranked else 0)
        assert spans[field]["distinct"] == len(want)
        assert spans[field]["host_bytes"] == 8 * len(want)
        assert spans[field]["bytes"] == 4 * view.n_pad_total
        col, anew = view._filter_cols[field], _fresh_view(node) \
            .filter_column(field)
        assert col.distinct.tolist() == anew.distinct.tolist() == want
        assert col.distinct.dtype == anew.distinct.dtype
        assert col.vals.dtype == anew.vals.dtype == np.int32
        np.testing.assert_array_equal(np.asarray(col.vals),
                                      np.asarray(anew.vals))
        # every row's rank names its own value; padding rows hold -1
        held = np.asarray(col.vals)[:view.n_total]
        real = [r[field == "tag"] for r in rows]
        assert sorted(col.distinct[held[held >= 0]].tolist()) == sorted(real)
        assert (np.asarray(col.vals)[view.n_total:] == -1).all()
        lo, hi = (("gt", 10), ("lte", 30)) if field == "price" \
            else (("gt", "b"), ("lte", "f"))
        assert ids({"range": {field: dict([lo, hi])}}) == {
            i for i, r in enumerate(rows)
            if r[field == "tag"] > lo[1] and r[field == "tag"] <= hi[1]}

    def test_merge_triggers_full_rebuild(self, node):
        _index_batch(node, 0, 10)
        node.search("inc", {"query": {"match": {"body": "common"}}})
        _index_batch(node, 10, 20)
        node.force_merge("inc")
        v = node.indices["inc"].packed_view()
        assert not v.extended_from_base
        out = node.search("inc", {"query": {"match": {"body": "common"}},
                                  "size": 30})
        assert out["hits"]["total"] == 20

    def test_deletes_visible_through_extended_view(self, node):
        _index_batch(node, 0, 12)
        node.search("inc", {"query": {"match": {"body": "common"}}})
        _index_batch(node, 12, 18)
        node.delete_doc("inc", "3")
        node.refresh("inc")
        out = node.search("inc", {"query": {"match": {"body": "common"}},
                                  "size": 30})
        ids = {h["_id"] for h in out["hits"]["hits"]}
        assert "3" not in ids
        assert out["hits"]["total"] == 17
