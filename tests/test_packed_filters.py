"""Packed-lane columnar filters: bool{match + filter/must_not} served by the
ONE-program kernel (BASELINE config #2 shape), with exact parity against the
general path (VERDICT r3 task 2a).
"""

import copy
import json
import os
import re
import sys

import numpy as np
import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.metrics import render_openmetrics
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.ops.bm25_sparse import PACKED_PAD_DOC as K_PAD
from elasticsearch_tpu.rest.http_server import _parse_bulk

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare    # noqa: E402 — the benchmark's own modules
import corpus     # noqa: E402
import traffic    # noqa: E402
from reference import Reference    # noqa: E402

MAPPING = {"_doc": {"properties": {
    "body": {"type": "text"},
    "tag": {"type": "keyword"},
    "price": {"type": "long"},
    "rating": {"type": "double"},
}}}

DOCS = [
    {"body": "quick fox",          "tag": "a", "price": 10, "rating": 1.5},
    {"body": "quick dog",          "tag": "b", "price": 20, "rating": 2.5},
    {"body": "quick cat",          "tag": "a", "price": 30, "rating": 3.5},
    {"body": "quick bird",         "tag": "c", "price": 40},
    {"body": "quick quick fish",   "tag": "b", "price": 50, "rating": 4.5},
    {"body": "slow worm",          "tag": "a", "price": 60, "rating": 0.5},
    {"body": "quick snail",                    "price": 70, "rating": 5.0},
    {"body": "quick horse",        "tag": "c"},
]


@pytest.fixture()
def node(tmp_path):
    n = NodeService(data_path=str(tmp_path))
    n.create_index("px", settings={"number_of_shards": 2}, mappings=MAPPING)
    for i, d in enumerate(DOCS):
        n.index_doc("px", str(i), d)
        if i == 3:
            n.refresh("px")      # several segments
    n.refresh("px")
    yield n
    n.close()


def _both_lanes(node, query, size=10):
    """(packed_response, general_response) for the same query; asserts the
    packed lane actually served the first one."""
    svc = node.indices["px"]
    before = svc.search_stats.get("packed", 0)
    packed = node.search("px", {"query": query, "size": size})
    assert svc.search_stats.get("packed", 0) == before + 1, \
        f"packed lane must serve {query}"
    general = node.search("px", {"query": query, "size": size,
                                 "track_scores": True})
    return packed, general


def _check_parity(packed, general):
    ph = {h["_id"]: h["_score"] for h in packed["hits"]["hits"]}
    gh = {h["_id"]: h["_score"] for h in general["hits"]["hits"]}
    assert ph.keys() == gh.keys()
    for k in ph:
        assert ph[k] == pytest.approx(gh[k], rel=1e-5)
    assert packed["hits"]["total"] == general["hits"]["total"]
    return set(ph)


class TestPackedTermFilter:
    def test_term_filter(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"term": {"tag": "a"}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"0", "2"}

    def test_terms_filter_multi_value(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"terms": {"tag": ["a", "c"]}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"0", "2", "3", "7"}

    def test_numeric_term_filter(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"term": {"price": 20}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"1"}

    def test_must_not(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "must_not": [{"term": {"tag": "b"}}]}}
        p, g = _both_lanes(node, q)
        # must_not matches docs missing the field too (6 has no tag)
        assert _check_parity(p, g) == {"0", "2", "3", "6", "7"}


class TestPackedRangeFilter:
    def test_long_range_inclusive(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"range": {"price": {"gte": 20,
                                                      "lte": 40}}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"1", "2", "3"}

    def test_strict_bounds(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"range": {"price": {"gt": 20,
                                                      "lt": 50}}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"2", "3"}

    def test_double_range_excludes_missing(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"range": {"rating": {"gte": 2.0}}}]}}
        p, g = _both_lanes(node, q)
        # docs 3 and 7 have no rating: a range filter never matches missing
        assert _check_parity(p, g) == {"1", "2", "4", "6"}

    def test_keyword_range(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"range": {"tag": {"gte": "b"}}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"1", "3", "4", "7"}

    def test_combined_term_and_range(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"term": {"tag": "b"}},
                                 {"range": {"price": {"gte": 30}}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"4"}


class TestPackedFilterEdges:
    def test_filter_on_unmapped_field_matches_nothing(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"term": {"nope": "x"}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == set()

    def test_must_not_on_unmapped_field_matches_all(self, node):
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "must_not": [{"term": {"nope": "x"}}]}}
        p, g = _both_lanes(node, q)
        assert len(_check_parity(p, g)) == 7   # all quick docs

    def test_pure_filter_query_stays_on_general_path(self, node):
        svc = node.indices["px"]
        before = svc.search_stats.get("packed", 0)
        out = node.search("px", {"query": {"bool": {
            "filter": [{"term": {"tag": "a"}}]}}})
        assert svc.search_stats.get("packed", 0) == before
        assert out["hits"]["total"] == 3

    def test_too_many_filters_fall_back(self, node):
        svc = node.indices["px"]
        before = svc.search_stats.get("packed", 0)
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"range": {"price": {"gte": 0}}},
                                 {"range": {"price": {"lte": 100}}},
                                 {"range": {"rating": {"gte": 0}}}]}}
        out = node.search("px", {"query": q})
        assert svc.search_stats.get("packed", 0) == before
        assert out["hits"]["total"] > 0

    def test_filters_with_deletes(self, node):
        node.delete_doc("px", "2")
        node.refresh("px")
        q = {"bool": {"must": [{"match": {"body": "quick"}}],
                      "filter": [{"term": {"tag": "a"}}]}}
        p, g = _both_lanes(node, q)
        assert _check_parity(p, g) == {"0"}


# -- the rank streams' life: made once a view, read with the postings -------
# A filtered batch hands its program one stream a column, the rank of each
# posting's document (`PackedIndexView._filter_stream`). They are the view's:
# a refresh makes a new view and new streams, a delete folds the postings
# under them, and the request breaker is charged for them.

STREAM_BODIES = [
    {"bool": {"must": [{"match": {"body": "quick"}}],
              "filter": [{"range": {"price": {"lte": 20}}}]}},
    {"bool": {"must": [{"match": {"body": "quick"}}],
              "filter": [{"term": {"tag": "a"}}],
              "must_not": [{"range": {"price": {"gt": 30}}}]}},
    {"bool": {"must": [{"match": {"body": "quick"}}],
              "must_not": [{"terms": {"tag": ["b", "c"]}}]}},
]


def _stream_counts(node) -> dict:
    """`/_metrics` -> {state: streams} of es_packed_filter_streams_total."""
    text = render_openmetrics(node.metric_sections(), node="n")
    return {m.group(1): int(float(m.group(2))) for m in re.finditer(
        r'^es_packed_filter_streams_total\{[^}]*state="(\w+)"[^}]*\} (\S+)$',
        text, re.M)}


def _msearch_px(node, queries):
    """The packed lane's answers to one `_msearch` of `queries`, each held to
    the general lane's answer to the same query (`_check_parity`); -> the
    ids of each."""
    svc = node.indices["px"]
    before = svc.search_stats.get("packed", 0)
    out = node.msearch([({"index": "px"}, {"query": q, "size": 10})
                        for q in queries])["responses"]
    assert svc.search_stats.get("packed", 0) == before + len(queries)
    return [_check_parity(p, node.search("px", {
        "query": q, "size": 10, "track_scores": True}))
        for p, q in zip(out, queries)]


def _delta(a, b):
    return {s: b.get(s, 0) - a.get(s, 0) for s in ("made", "reused")}


class TestPackedFilterStreams:
    def test_made_once_a_field_and_column_then_reused(self, node):
        c0 = _stream_counts(node)
        with tracing.Tracer().request("test") as trace:
            _msearch_px(node, STREAM_BODIES)
        c1 = _stream_counts(node)
        # (body, price) and (body, tag): made for the batch that first names
        # each, and every later batch reuses them
        assert _delta(c0, c1) == {"made": 2, "reused": 0}
        view = node.indices["px"].packed_view()
        assert sorted(view._rank_streams) == [("body", "price"),
                                              ("body", "tag")]
        pf = view.field("body")
        made = {s.attrs["column"]: s.attrs for s in trace.spans
                if s.name == "packed.filter_stream"}
        assert made.keys() == {"price", "tag"}
        assert all(a["field"] == "body" and a["postings"] == pf.total_p
                   and a["bytes"] == 4 * pf.doc_ids.size
                   for a in made.values())
        prep = [s.attrs for s in trace.spans if s.name == "packed.build_slots"]
        assert [a["streams"] for a in prep] == [2]
        _msearch_px(node, STREAM_BODIES)
        _msearch_px(node, STREAM_BODIES[2:])
        node.search("px", {"query": {"match": {"body": "quick"}}})
        assert _delta(c1, _stream_counts(node)) == {"made": 0, "reused": 3}

    def test_each_stream_is_its_column_at_every_posting(self, node):
        _msearch_px(node, STREAM_BODIES)
        view = node.indices["px"].packed_view()
        ids = np.asarray(view.field("body").doc_ids)
        assert (ids == K_PAD).sum() > 0         # the padding reads -1
        for (field, name), stream in view._rank_streams.items():
            col = np.asarray(view.filter_column(name).vals)
            held = col[np.minimum(ids, len(col) - 1)]
            np.testing.assert_array_equal(
                np.asarray(stream), np.where(ids == K_PAD, -1, held))

    def test_a_refresh_that_reranks_a_column_makes_fresh_streams(self, node):
        """A value below every old one moves every old rank up by one: a
        stream of the old view read by the new one would pass and fail the
        wrong documents."""
        assert _msearch_px(node, STREAM_BODIES)[0] == {"0", "1"}
        old = node.indices["px"].packed_view()
        node.index_doc("px", "8", {"body": "quick newt", "tag": "A",
                                   "price": 5, "rating": 0.25})
        node.refresh("px")
        view = node.indices["px"].packed_view()
        assert view is not old and view.extended_from_base
        assert view.filter_column("price").distinct[0] == 5
        assert view._rank_streams == {}
        c0 = _stream_counts(node)
        assert _msearch_px(node, STREAM_BODIES) == [
            {"0", "1", "8"}, {"0", "2"}, {"0", "2", "6", "8"}]
        assert _delta(c0, _stream_counts(node)) == {"made": 2, "reused": 0}

    def test_a_delete_after_the_stream_was_made_is_excluded(self, node):
        before = _msearch_px(node, STREAM_BODIES)
        view = node.indices["px"].packed_view()
        streams = dict(view._rank_streams)
        node.delete_doc("px", "0")
        node.refresh("px")
        assert node.indices["px"].packed_view() is view     # folded in place
        c0 = _stream_counts(node)
        after = _msearch_px(node, STREAM_BODIES)
        assert after == [ids - {"0"} for ids in before] != before
        assert _delta(c0, _stream_counts(node)) == {"made": 0, "reused": 2}
        assert all(view._rank_streams[k] is s for k, s in streams.items())

    def test_streams_are_charged_and_a_refused_one_takes_the_segments(
            self, node):
        q = STREAM_BODIES[1]
        svc = node.indices["px"]
        node.search("px", {"query": {"match": {"body": "quick"}}})
        view = svc.packed_view()
        stream_bytes = 4 * view.field("body").doc_ids.size
        brk = node.breakers.breaker("request")
        limit = brk.limit
        # room for the two columns (4 B a document), not for a stream
        brk.limit = brk.used + 2 * 4 * view.n_pad_total + stream_bytes // 2
        try:
            before = svc.search_stats.get("packed", 0)
            refused = node.search("px", {"query": q, "size": 10})
            assert svc.search_stats.get("packed", 0) == before
        finally:
            brk.limit = limit
        assert view._rank_streams == {}
        used, held = brk.used, view.memory_bytes
        packed = node.search("px", {"query": q, "size": 10})
        assert svc.search_stats.get("packed", 0) == before + 1
        assert brk.used - used == view.memory_bytes - held == 2 * stream_bytes
        general = node.search("px", {"query": q, "size": 10,
                                     "track_scores": True})
        assert _check_parity(packed, general) == \
            _check_parity(refused, general) == {"0", "2"}


# -- BASELINE config #2 as the benchmark states it ---------------------------
# `benchmark/configs/wiki-filtered-5s.json` at 3,000 documents from
# `benchmark/corpus.py`, the three body templates of the cell
# `wiki.filtered-top1000` through `NodeService.msearch`, against the
# benchmark's plain reference (`benchmark/reference.py`).

SEED = 2 ** 31 + 33
N_DOCS = 3000


def _bench_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = {**_bench_json("configs", "wiki-filtered-5s.json"), "documents": N_DOCS}
CELL = _bench_json("workloads", "wiki.filtered-top1000.json")
TOL = compare.load_limits(CELL)["score_rel_err_max"]
# the cell's templates by the columns they filter on
TEMPLATES = {"timestamp": [0], "month": [2], "both": [0, 1, 2]}


def _load(node, cfg, seed=SEED):
    made = corpus.mapping(cfg)
    node.create_index(cfg["index"], settings=made["settings"],
                      mappings=made["mappings"])
    for k in range(corpus.n_chunks(cfg)):
        items = node.bulk(_parse_bulk(corpus.payload(cfg, seed, k),
                                      cfg["index"]))
        assert not any(i["index"].get("status", 201) >= 300 for i in items)
    node.refresh(cfg["index"])


@pytest.fixture(scope="module")
def wikif(tmp_path_factory):
    n = NodeService(data_path=str(tmp_path_factory.mktemp("wikif")))
    _load(n, CFG)
    yield n, Reference(CFG, SEED)
    n.close()


def _bodies(which: str, q: int, seed: int) -> list[dict]:
    """`q` bodies drawn from the cell's own templates `which`."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice(TEMPLATES[which], size=q)
    kinds[0] = TEMPLATES[which][len(TEMPLATES[which]) // 2]  # "both": both
    return [traffic._expand(CELL["mix"][k]["body"], CFG, rng) for k in kinds]


def _msearch(node, bodies, raw=False):
    svc = node.indices[CFG["index"]]
    before = svc.search_stats.get("packed", 0)
    out = node.msearch([({"index": CFG["index"]}, copy.deepcopy(b))
                        for b in bodies], raw=raw)
    assert svc.search_stats.get("packed", 0) == before + len(bodies), \
        "the packed lane must serve every body"
    return out


def _check_against_reference(body, resp, ref):
    """ids, order, total and scores of one answer against the reference's
    own (`Reference.respond`); a tie in float32 may stand in another order,
    so the order is held on the reference's scores within the limit."""
    want = ref.respond(body)["hits"]
    hits = resp["hits"]
    assert hits["total"] == want["total"]
    got = [int(h["_id"]) for h in hits["hits"]]
    assert len(got) == len(want["hits"]) and len(set(got)) == len(got)
    if want["total"] <= body["size"]:       # everything that matches
        assert sorted(got) == sorted(int(h["_id"]) for h in want["hits"])
    answer = ref.answer(body)
    assert answer["mask"][got].all() if got else True
    ref_scores = answer["score"][got]
    scores = np.array([h["_score"] for h in hits["hits"]])
    assert np.all(np.abs(scores - ref_scores) <= TOL * ref_scores)
    assert np.all(np.diff(scores) <= 0)
    assert np.all(np.diff(ref_scores) <= TOL * ref_scores[:-1])
    tally = compare.Tally()                 # and the benchmark's own check
    compare.compare_answer(tally, "body", body, resp, ref, TOL)
    assert tally.verdict(compare.load_limits(CELL))[0], tally.notes


@pytest.mark.parametrize("which", list(TEMPLATES))
@pytest.mark.parametrize("q", [1, 32, 256])
def test_msearch_of_the_cells_templates_equals_the_reference(wikif, q, which):
    node, ref = wikif
    bodies = _bodies(which, q, seed=q + len(which))
    columns = {f for b in bodies for flt in b["query"]["bool"]["filter"]
               for spec in flt.values() for f in spec}
    assert columns == ({"timestamp", "month"} if which == "both"
                       else {which})
    out = _msearch(node, bodies)["responses"]
    assert len(out) == q
    short = 0
    for body, resp in zip(bodies, out):
        _check_against_reference(body, resp, ref)
        short += len(resp["hits"]["hits"]) < body["size"]
    assert short        # a filter leaves some bodies fewer hits than `size`


def _on_the_bound(ref):
    """(a body term, a document that holds it, the document's timestamp)."""
    post_doc, _, start, _ = ref.postings("body")
    term = int(np.argmax(np.diff(start)[64:]) + 64)   # the template's skip_top
    doc = int(post_doc[start[term]])
    return "t%06d" % term, doc, int(ref.cols["timestamp"][doc])


@pytest.mark.parametrize("bound,shift,inside", [
    ("gte", 0, True), ("gte", 1, False), ("gte", -1, True),
    ("lt", 0, False), ("lt", 1, True), ("lt", -1, False),
    ("gt", 0, False), ("gt", -1, True), ("lte", 0, True), ("lte", -1, False),
])
def test_a_bound_is_exact_to_the_millisecond(wikif, bound, shift, inside):
    """A document exactly on `gte` is in and exactly on `lt` is out, and
    one millisecond to either side turns each: 64-bit, never float32."""
    node, ref = wikif
    word, doc, ts = _on_the_bound(ref)
    assert float(np.float32(ts)) != ts      # float32 cannot tell them apart
    assert np.float32(ts + shift) == np.float32(ts)
    body = {"query": {"bool": {"must": [{"match": {"body": word}}], "filter": [
        {"range": {"timestamp": {bound: ts + shift}}}]}},
        "size": 1000, "_source": False}
    resp, = _msearch(node, [body])["responses"]
    assert (str(doc) in {h["_id"] for h in resp["hits"]["hits"]}) is inside
    assert resp["hits"]["total"] == ref.answer(body)["total"]


# -- ordinals: every compare decides as the 64-bit one does (ISSUE 34) -------
# The chip holds a row's rank among the column's sorted distinct values; the
# host finds each bound's and target's rank in the column's own type. Held to
# a NumPy int64 / float64 reference over the same rows.

T0 = 1211699326344                      # a date in milliseconds
ULP = float(np.nextafter(1.0, 2.0))     # the double next to 1.0
EXACT_MAPPING = {"_doc": {"properties": {
    "body": {"type": "text"}, "n": {"type": "long"}, "ts": {"type": "date"},
    "x": {"type": "double"}, "tag": {"type": "keyword"}}}}
# what the rows hold, None = no value: two timestamps 1 ms apart, int64
# 2^53 and 2^53 + 1 (one float64), two doubles one ulp apart
HELD = {
    "n": [-5, 0, 7, None, 2 ** 53, 2 ** 53 + 1, 2 ** 62, 7, None, -5],
    "ts": [T0, T0 + 1, None, T0 + 86_400_000, T0, T0 + 1, None,
           T0 - 3, T0 + 2, T0 + 86_400_000],
    "x": [-1.5, 1.0, ULP, 2.5, None, 1e300, 1.0, None, ULP, -0.0],
    "tag": ["b", None, "d", "b", "f", None, "d", "bb", "f", "b"],
}
N_ROWS = len(HELD["n"])


@pytest.fixture(scope="module")
def exact(tmp_path_factory):
    n = NodeService(data_path=str(tmp_path_factory.mktemp("exact")))
    n.create_index("ex", settings={"number_of_shards": 2},
                   mappings=EXACT_MAPPING)
    for i in range(N_ROWS):
        doc = {"body": "word", **{f: v[i] for f, v in HELD.items()
                                  if v[i] is not None}}
        n.index_doc("ex", str(i), doc)
        if i in (2, 6):
            n.refresh("ex")             # several segments a shard
    n.refresh("ex")
    yield n
    n.close()


def _held(field):
    """(values in the column's own 64-bit type, which rows hold one)."""
    has = np.array([v is not None for v in HELD[field]])
    dtype = {"n": np.int64, "ts": np.int64, "x": np.float64}[field]
    return np.array([v if v is not None else 0 for v in HELD[field]],
                    dtype), has


def _step(field, v, by):
    """The value next to `v` in the column's type: 1 for a whole number
    (a millisecond for a date), one ulp for a double."""
    if field == "x":
        return float(np.nextafter(v, np.inf if by > 0 else -np.inf))
    return v + by


OPS = {"gt": np.greater, "gte": np.greater_equal,
       "lt": np.less, "lte": np.less_equal}


def _packed_ids(node, index, query, size=50):
    svc = node.indices[index]
    before = svc.search_stats.get("packed", 0)
    out = node.search(index, {"query": query, "size": size})
    assert svc.search_stats.get("packed", 0) == before + 1, query
    assert out["hits"]["total"] == len(out["hits"]["hits"])
    return {int(h["_id"]) for h in out["hits"]["hits"]}


def _bounds():
    """(field, bound): on every held value, one step below and one above
    it, below the least and above the greatest."""
    for field in ("n", "ts", "x"):
        held = sorted({v for v in HELD[field] if v is not None})
        picks = {held[0], held[len(held) // 2], held[-2], held[-1]} \
            if field == "n" else set(held[:3]) | {held[-1]}
        near = set()
        for v in picks:
            near |= {v, _step(field, v, -1), _step(field, v, +1)}
        for b in sorted(near | {_step(field, held[0], -1),
                                _step(field, held[-1], +1)}):
            yield field, b


@pytest.mark.parametrize("negated", [False, True], ids=["filter", "must_not"])
@pytest.mark.parametrize("op", list(OPS))
@pytest.mark.parametrize("field,bound", list(_bounds()),
                         ids=lambda v: v if isinstance(v, str) else repr(v))
def test_a_range_decides_as_the_64_bit_compare_does(exact, field, bound, op,
                                                    negated):
    vals, has = _held(field)
    inside = has & OPS[op](vals, vals.dtype.type(bound))
    want = set(np.flatnonzero(~inside if negated else inside).tolist())
    clause = "must_not" if negated else "filter"
    got = _packed_ids(exact, "ex", {"bool": {
        "must": [{"match": {"body": "word"}}],
        clause: [{"range": {field: {op: bound}}}]}})
    # a row without a value fails every range and passes its negation
    assert got == want and (set(np.flatnonzero(~has)) <= got) is negated


def test_the_reference_tells_apart_what_a_float64_column_could_not():
    assert float(2 ** 53) == float(2 ** 53 + 1) and 1.0 < ULP
    vals, has = _held("n")
    assert (vals[has] > 2 ** 53).sum() == 2      # 2^53 + 1 and 2^62


@pytest.mark.parametrize("field,bounds,want", [
    # an empty interval matches nothing, and its negation everything
    ("n", {"gte": 7, "lte": 0}, set()),
    ("n", {"gt": 2 ** 53, "lt": 2 ** 53 + 1}, set()),
    ("ts", {"gt": T0, "lt": T0 + 1}, set()),
    ("x", {"gt": 1.0, "lt": ULP}, set()),
    ("tag", {"gt": "b", "lt": "bb"}, set()),
    # between two held values, a millisecond or an ulp apart
    ("n", {"gt": 2 ** 53, "lte": 2 ** 53 + 1}, {5}),
    ("ts", {"gte": T0 + 1, "lt": T0 + 2}, {1, 5}),
    ("x", {"gt": 1.0, "lte": ULP}, {2, 8}),
    ("x", {"gte": -0.0, "lte": 0.0}, {9}),
    ("tag", {"gte": "bb", "lt": "f"}, {2, 6, 7}),
    # a fraction over a whole-number column, and beyond int64
    ("n", {"gt": 6.5, "lt": 7.5}, {2, 7}),
    ("n", {"gte": 2 ** 63}, set()),
    ("n", {"lt": 2 ** 70, "gt": -(2 ** 70)}, {0, 1, 2, 4, 5, 6, 7, 9}),
    ("n", {"gte": "7", "lte": "7"}, {2, 7}),
])
def test_an_interval_and_its_negation(exact, field, bounds, want):
    must = [{"match": {"body": "word"}}]
    assert _packed_ids(exact, "ex", {"bool": {
        "must": must, "filter": [{"range": {field: bounds}}]}}) == want
    assert _packed_ids(exact, "ex", {"bool": {
        "must": must, "must_not": [{"range": {field: bounds}}]}}) \
        == set(range(N_ROWS)) - want


@pytest.mark.parametrize("field,values,want", [
    ("n", [2 ** 53 + 1], {5}),
    ("n", [2 ** 53], {4}),
    ("n", [8], set()),                  # a value no document holds
    ("n", [7.5], set()),
    ("n", [8, 7, "0", 2 ** 64], {1, 2, 7}),
    ("ts", [T0 + 1], {1, 5}),
    ("ts", [T0 - 1], set()),
    ("x", [ULP], {2, 8}),
    ("x", [1.0], {1, 6}),
    ("x", [0], {9}),
    ("tag", ["bb"], {7}),
    ("tag", ["c"], set()),
    ("tag", ["a", "b", "z"], {0, 3, 9}),
])
def test_a_term_and_its_negation(exact, field, values, want):
    must = [{"match": {"body": "word"}}]
    assert _packed_ids(exact, "ex", {"bool": {
        "must": must, "filter": [{"terms": {field: values}}]}}) == want
    # a row without a value equals no target: its negation keeps it
    assert _packed_ids(exact, "ex", {"bool": {
        "must": must, "must_not": [{"terms": {field: values}}]}}) \
        == set(range(N_ROWS)) - want


def test_a_keyword_and_a_numeric_column_in_one_batch(exact):
    """One `_msearch`: a body on a keyword column, one on a date, one on a
    double and a long, one on a keyword and a long, one with no filter."""
    must = [{"match": {"body": "word"}}]
    filters = [
        [{"term": {"tag": "d"}}],
        [{"range": {"ts": {"gt": T0, "lte": T0 + 2}}}],
        [{"range": {"x": {"gte": 1.0}}}, {"range": {"n": {"lt": 2 ** 53}}}],
        [{"range": {"tag": {"lte": "bb"}}}, {"term": {"n": -5}}],
        []]
    want = [{2, 6}, {1, 5, 8}, {1, 2}, {0, 9}, set(range(N_ROWS))]
    svc = exact.indices["ex"]
    before = svc.search_stats.get("packed", 0)
    out = exact.msearch([({"index": "ex"}, {"query": {"bool": {
        "must": must, "filter": f}}, "size": 50}) for f in filters])
    assert svc.search_stats.get("packed", 0) == before + len(filters)
    assert [{int(h["_id"]) for h in r["hits"]["hits"]}
            for r in out["responses"]] == want
    view = svc.packed_view()
    assert {f: (c.kind, c.vals.dtype.name, c.distinct.dtype.name)
            for f, c in view._filter_cols.items()} == {
        "tag": ("keyword", "int32", "object"),
        "ts": ("numeric", "int32", "int64"),
        "x": ("numeric", "int32", "float64"),
        "n": ("numeric", "int32", "int64")}


def _described(node, index, query):
    """A body's filter descriptors as `_filter_descriptors` makes them."""
    from elasticsearch_tpu.search.query_parser import QueryParser
    from elasticsearch_tpu.serving.executor import packed_spec_of
    svc = node.indices[index]
    spec = packed_spec_of(QueryParser(svc.mappers), {"query": query})
    return svc.packed_view(), svc.packed_view()._filter_descriptors(
        [spec[0]], 1)


@pytest.mark.parametrize("clause,field,bounds,ends,neg", [
    # (least rank inside, greatest rank inside) among the distinct values:
    # n: -5 0 7 2^53 2^53+1 2^62; ts: T0-3 T0 T0+1 T0+2 T0+1d;
    # x: -1.5 -0.0 1.0 ULP 2.5 1e300; tag: b bb d f
    ("filter", "ts", {"gt": T0, "lt": T0 + 2}, (2, 2), 0),
    ("filter", "ts", {"gte": T0, "lte": T0 + 2}, (1, 3), 0),
    ("filter", "ts", {"gt": T0 - 2, "lt": T0 - 1}, (1, 0), 0),   # empty
    ("filter", "x", {"gte": 1.0, "lt": 2.5}, (2, 3), 0),
    ("filter", "x", {"gt": 1.0}, (3, 5), 0),
    ("must_not", "n", {"gt": 7}, (3, 5), 1),
    ("filter", "n", {"gte": 2 ** 53 + 1}, (4, 5), 0),
    ("filter", "n", {"gt": 6.5, "lte": 2.0 ** 53}, (2, 3), 0),
    ("filter", "n", {"lt": -5}, (0, -1), 0),                     # empty
    ("filter", "n", {"gt": 2 ** 62}, (6, 5), 0),                 # empty
    ("filter", "n", {"gte": float("-inf"), "lte": float("inf")}, (0, 5), 0),
    ("filter", "tag", {"gt": "b", "lte": "e"}, (1, 2), 0),
    ("filter", "nope", {"gte": 1}, None, 0),         # no such column: -2
])
def test_the_host_resolves_a_range_to_an_inclusive_interval_of_ranks(
        exact, clause, field, bounds, ends, neg):
    """An open end, a bound between two values and a missing bound are
    decided on the host, where the values are exact: the program is handed
    ordinals, and no bit says which end is open."""
    view, (fields, fr_col, fr_lo, fr_hi, fr_neg, ft_col, ft_targets, _) = \
        _described(exact, "ex", {"bool": {
            "must": [{"match": {"body": "word"}}],
            clause: [{"range": {field: bounds}}]}})
    assert all(a.dtype == np.int32 for a in (fr_col, fr_lo, fr_hi, fr_neg,
                                             ft_col, ft_targets))
    assert fr_neg[0].tolist() == [neg, 0] and fr_col[0, 1] == -1
    if ends is None:
        assert fields == () and fr_col[0, 0] == -2
        return
    assert fields == (field,) and fr_col[0, 0] == 0
    assert (int(fr_lo[0, 0]), int(fr_hi[0, 0])) == ends
    want = sorted({v for v in HELD[field] if v is not None})
    assert view.filter_column(field).distinct.tolist() == want


def test_the_host_resolves_a_term_to_its_rank_or_to_no_ordinal(exact):
    from elasticsearch_tpu.ops.bm25_sparse import NO_ORDINAL
    _, (fields, *_, ft_col, ft_targets, ft_neg) = _described(exact, "ex", {
        "bool": {"must": [{"match": {"body": "word"}}],
                 "filter": [{"terms": {"n": [2 ** 53 + 1, 8, 7.0, "x"]}}],
                 "must_not": [{"term": {"tag": "d"}}]}})
    assert fields == ("n", "tag")
    assert ft_col[0].tolist() == [0, 1] and ft_neg[0].tolist() == [0, 1]
    assert ft_targets[0].tolist() == [
        [4, NO_ORDINAL, 2, NO_ORDINAL], [2] + [NO_ORDINAL] * 3]
    assert NO_ORDINAL < -1      # a row without a value (-1) equals no target


def _equations(jaxpr, inside=()):
    """Every equation of a jaxpr and of the jaxprs in its parameters, with
    the primitives it lies inside."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(
                        sub, inside + (eqn.primitive.name,))


def _traced_on_the_chip(monkeypatch, S, NC):
    """(equations, the program's arguments) of `bm25_serve_packed` (NC None)
    or `bm25_serve_packed_filtered` on NC rank streams, traced as the chip
    runs it (the Pallas slot gather, not its CPU stand-in) at a shape of
    the two top-1000 cells: Q 256, S slots, k 1024, 2^25 postings."""
    import jax
    import jax.numpy as jnp
    from elasticsearch_tpu.ops import bm25_sparse as K
    from elasticsearch_tpu.serving.packed_view import (
        CHUNK, F_RANGE, F_TERM, F_TERM_VALS)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    Q, P = 256, 1 << 25
    sd = jax.ShapeDtypeStruct
    args = (sd((Q, 3 * S + 1), jnp.int32), sd((P,), jnp.int32),
            sd((P,), jnp.float32), sd((P,), jnp.float32),
            *[sd((), jnp.float32)] * 4)
    if NC is None:
        traced = K.bm25_serve_packed.jit.trace(
            *args, S=S, CHUNK=CHUNK, R=8, k=1024)
    else:
        args += ((sd((P,), jnp.int32),) * NC,
                 *[sd((Q, F_RANGE), jnp.int32)] * 4,
                 sd((Q, F_TERM), jnp.int32),
                 sd((Q, F_TERM, F_TERM_VALS), jnp.int32),
                 sd((Q, F_TERM), jnp.int32))
        traced = K.bm25_serve_packed_filtered.jit.trace(
            *args, S=S, CHUNK=CHUNK, R=8, k=1024,
            FR=F_RANGE, FT=F_TERM, TV=F_TERM_VALS)
    return list(_equations(traced.jaxpr.jaxpr)), args


def _scopes(eqns):
    """The `packed.*` scopes of the equations, in the order they come."""
    seen = []
    for eqn, _ in eqns:
        for scope in re.findall(r"packed\.\w+",
                                str(eqn.source_info.name_stack)):
            if scope not in seen:
                seen.append(scope)
    return seen


@pytest.mark.parametrize("S", [128, 256])
def test_the_filtered_program_holds_no_float64_and_gathers_once_a_block(
        S, monkeypatch):
    """The jaxpr at the cell's two shapes (Q 256, S 128 | 256, k 1024, two
    columns): int32 filter operands, no float64 value anywhere, and no
    gather of any per-document array. The filter's ranks come once a slot
    block, copied with the postings by the one slot gather (3 + NC streams
    of one kernel), and are compared before the score, with no loop: the
    serial gather of the columns at every candidate was four fifths of the
    program on the chip (PERF.md §5)."""
    import jax.numpy as jnp
    from elasticsearch_tpu.serving.packed_view import CHUNK
    NC, Q = 2, 256
    eqns, args = _traced_on_the_chip(monkeypatch, S, NC)
    assert {a.dtype.name for a in args[9:]} == {"int32"}
    assert [a.dtype.name for a in args[8]] == ["int32"] * NC
    values = [v.aval for eqn, _ in eqns
              for v in (*eqn.invars, *eqn.outvars) if hasattr(v, "aval")]
    # (a Python literal of the shared phases, `0.0` or `-inf`, is a weakly
    # typed scalar until the next equation makes it a float32: no value)
    assert not [a for a in values if a.dtype == jnp.float64
                and not (a.weak_type and a.shape == ())]
    # the one gather left is the top-k's take of its positions' doc ids
    gathers = [eqn.invars[0].aval.shape for eqn, _ in eqns
               if eqn.primitive.name == "gather"]
    assert gathers == [(Q, S * CHUNK)]
    # (the kernel's own two loops over its block of slots aside)
    assert not [eqn for eqn, inside in eqns if "pallas_call" not in inside
                and eqn.primitive.name == "while"]
    kernel, = [eqn for eqn, _ in eqns if eqn.primitive.name == "pallas_call"]
    assert len(kernel.invars) == 1 + 3 + NC         # the starts, the streams
    scopes = _scopes(eqns)
    assert scopes.index("packed.gather") < scopes.index("packed.filters") \
        < scopes.index("packed.score")


@pytest.mark.parametrize("S", [128, 256])
def test_the_plain_program_copies_three_streams_and_filters_nothing(
        S, monkeypatch):
    """The plain program (`wiki.rerank-top1000`, `wiki.match-top10`) is the
    one it was: three streams through the slot gather, no filter scope."""
    eqns, _ = _traced_on_the_chip(monkeypatch, S, None)
    kernel, = [eqn for eqn, _ in eqns if eqn.primitive.name == "pallas_call"]
    assert len(kernel.invars) == 1 + 3
    assert "packed.filters" not in _scopes(eqns)
    assert "packed.gather" in _scopes(eqns)


@pytest.mark.parametrize("clause,field,bounds,want", [
    # `rating` is a double: 1.5, 2.5, 3.5, -, 4.5, (slow), 5.0, -
    ("filter", "rating", {"gt": 2.5, "lt": 4.5}, {"2"}),
    ("filter", "rating", {"gte": 2.5, "lte": 4.5}, {"1", "2", "4"}),
    ("filter", "rating", {"gt": 2.4999, "lt": 4.5001}, {"1", "2", "4"}),
    ("must_not", "rating", {"gt": 2.5}, {"0", "1", "3", "7"}),
    # `price` is a long: 10, 20, 30, 40, 50, (slow), 70, -
    ("filter", "price", {"gt": 10, "lt": 30}, {"1"}),
    ("filter", "price", {"gt": 9.5, "lt": 30.5}, {"0", "1", "2"}),
    ("must_not", "price", {"gte": 20, "lt": 70}, {"0", "6", "7"}),
    # a bound that is no finite number stays what it is
    ("filter", "price", {"gt": 10, "lte": float("inf")},
     {"1", "2", "3", "4", "6"}),
    ("filter", "price", {"gte": float("-inf"), "lt": 20}, {"0"}),
    ("filter", "price", {"gt": float("inf")}, set()),
])
def test_open_and_unbounded_range_ends_are_exact(node, clause, field,
                                                 bounds, want):
    q = {"bool": {"must": [{"match": {"body": "quick"}}],
                  clause: [{"range": {field: bounds}}]}}
    # held to `want` and not to the general lane, which reads a fractional
    # bound over a long as the whole number below it and raises on inf
    svc = node.indices["px"]
    before = svc.search_stats.get("packed", 0)
    p = node.search("px", {"query": q})
    assert svc.search_stats.get("packed", 0) == before + 1
    assert {h["_id"] for h in p["hits"]["hits"]} == want
    assert p["hits"]["total"] == len(want)


def test_raw_render_of_bodies_with_fewer_hits_than_size(wikif):
    """The bytes of a raw `_msearch` say what the dicts say, also for
    bodies whose filter leaves fewer hits than `size`, or none."""
    node, ref = wikif
    bodies = _bodies("both", 32, seed=5)
    none = copy.deepcopy(bodies[0])
    none["query"]["bool"]["filter"] = [{"range": {"timestamp": {
        "gte": CFG["fields"]["timestamp"]["base_millis"] - 10, "lt":
        CFG["fields"]["timestamp"]["base_millis"] - 5}}}]
    bodies.insert(7, none)
    raw = _msearch(node, bodies, raw=True)
    assert isinstance(raw, bytes)
    got = json.loads(raw)["responses"]
    want = _msearch(node, bodies)["responses"]
    counts = [len(r["hits"]["hits"]) for r in got]
    assert counts[7] == 0 and got[7]["hits"]["max_score"] is None
    assert 0 < min(c for c in counts if c) < 1000
    for g, w in zip(got, want):
        g.pop("took"), w.pop("took")
        for h in w["hits"]["hits"]:         # the raw form prints `%.9g`
            h["_score"] = float("%.9g" % h["_score"])
        if w["hits"]["max_score"] is not None:
            w["hits"]["max_score"] = float("%.9g" % w["hits"]["max_score"])
        assert g == w
    for body, resp in zip(bodies, got):
        _check_against_reference(body, resp, ref)


def _families(node) -> dict:
    """`/_metrics` -> {program: batches} of es_packed_batches_total."""
    text = render_openmetrics(node.metric_sections(), node="n")
    return {m.group(1): float(m.group(2)) for m in re.finditer(
        r'^es_packed_batches_total\{[^}]*program="(\w+)"[^}]*\} (\S+)$',
        text, re.M)}


def test_spans_and_counter_of_a_filtered_batch_and_never_of_a_plain_one(
        tmp_path):
    cfg = {**CFG, "documents": 600}
    node = NodeService(data_path=str(tmp_path))
    try:
        _load(node, cfg)
        plain = [{"query": b["query"]["bool"]["must"][0], "size": 1000,
                  "_source": False} for b in _bodies("both", 32, seed=9)]

        def spans():
            rows = tracing.AGGREGATE.stats()
            return {s: rows.get(s, {}).get("total", 0) for s in (
                "packed.filter_descriptors", "packed.filter_column",
                "packed.build_slots")}

        s0, c0 = spans(), _families(node)
        _msearch(node, plain)
        s1, c1 = spans(), _families(node)
        assert s1["packed.build_slots"] == s0["packed.build_slots"] + 1
        assert s1["packed.filter_descriptors"] == \
            s0["packed.filter_descriptors"]
        assert s1["packed.filter_column"] == s0["packed.filter_column"]
        assert (c1["plain"] - c0["plain"], c1["filtered"] - c0["filtered"]) \
            == (1, 0)
        seen = []
        flight = tracing.flight

        def noting(site):
            ctx = flight(site)
            seen.append(ctx.attrs)
            return ctx
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(tracing, "flight", noting)
            _msearch(node, _bodies("both", 32, seed=9))
            _msearch(node, _bodies("month", 32, seed=10))
        s2, c2 = spans(), _families(node)
        # two filtered batches; the columns are built once a view, by the
        # first batch that names them
        assert s2["packed.filter_descriptors"] == \
            s1["packed.filter_descriptors"] + 2
        assert s2["packed.filter_column"] == s1["packed.filter_column"] + 2
        assert (c2["plain"] - c1["plain"], c2["filtered"] - c1["filtered"]) \
            == (0, 2)
        programs = [a for a in seen if "program" in a]
        assert [(a["program"], a["columns"]) for a in programs] == \
            [("filtered", 2), ("filtered", 1)]
        assert all(a["site"] == "ops:bm25_serve_packed_filtered"
                   for a in programs)
    finally:
        node.close()
