"""The packed lane's raw render (serving/executor.response_raw: one vector
pass over a batch's hits, `bytes` out) against the renderer it replaced,
kept in tests/raw_reference.py as the plain twin: equal byte for byte.
"""

import json
import re
import types
import urllib.request

import numpy as np
import pytest

import raw_reference
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.rest import HttpServer
from elasticsearch_tpu.serving import executor
from elasticsearch_tpu.serving.packed_view import _utf8_rows

N_DOCS = 600
ODD_ID = "zürich-東京-7"
# every document has "common"; one in 97 "rare"; doc lengths and term
# frequencies vary, so the scores of a page spread over two exponents
DOCS = {f"d{i}": "common " * (1 + i % 5) + ("rare " if i % 97 == 0 else "")
        + " ".join(f"w{j}" for j in range(i % 11)) for i in range(N_DOCS - 1)}
DOCS[ODD_ID] = "common rare umlaut"


def match(q, size, from_=None, **more):
    body = {"query": {"match": {"body": q}}, "size": size, "_source": False,
            **more}
    if from_ is not None:
        body["from"] = from_
    return body


# name -> the bodies of one `_msearch`; `None` marks a body of the general
# lane (a key the packed lane does not take)
REQUESTS = {
    "k hits a body": [match(q, 50) for q in
                      ("common", "common w3", "w1 w2 common", "common rare")],
    "fewer than k": [match("rare", 50), match("umlaut", 50)],
    "none": [match("zzz", 50), match("nope nada", 50)],
    "k, fewer and none in one batch": [
        match("common", 50), match("rare", 50), match("zzz", 50),
        match("w9 rare", 50), match("umlaut", 50)],
    "more than the index holds": [match("common", 1000), match("w5", 1000)],
    "from > 0": [match("w1 w2 common", 10, 3), match("rare", 10, 3),
                 match("zzz", 10, 3)],
    "from past the last hit": [match("rare", 10, 5), match("umlaut", 10, 5)],
    "a non-ASCII id": [match("umlaut", 5), match("rare", 20)],
    "two sizes, two batches": [match("common", 7), match("rare", 30),
                               match("common w1", 7)],
    "a packed and a general-lane body": [
        match("common", 20), match("rare", 20, track_scores=True),
        match("w2", 20)],
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    node = NodeService(str(tmp_path_factory.mktemp("rawrender")))
    srv = HttpServer(node, port=0).start()
    base = f"http://127.0.0.1:{srv.port}"

    def req(method, path, body=None):
        data = body.encode() if body is not None else None
        r = urllib.request.Request(base + path, data=data, method=method)
        return urllib.request.urlopen(r).read()

    req("PUT", "/rr", json.dumps({
        "settings": {"number_of_shards": 2},
        "mappings": {"doc": {"properties": {"body": {"type": "string"}}}}}))
    req("POST", "/_bulk?refresh=true", "".join(
        json.dumps({"index": {"_index": "rr", "_type": "doc", "_id": i}})
        + "\n" + json.dumps({"body": text}) + "\n"
        for i, text in DOCS.items()))
    assert node.indices["rr"].packed_view().ids_json_safe
    yield node, req
    srv.stop()
    node.close()


@pytest.fixture
def calls(monkeypatch):
    """Every call of the raw render, with what it was given and gave."""
    seen = []
    render = executor.response_raw

    def recording(view, index_name, scores, docs, totals, **kw):
        out = render(view, index_name, scores, docs, totals, **kw)
        seen.append((view, index_name, scores, docs, totals, kw, out))
        return out

    monkeypatch.setattr(executor, "response_raw", recording)
    return seen


def twin_bodies(call) -> list[str]:
    view, index_name, scores, docs, totals, kw, _ = call
    return [raw_reference.response_raw(
        view, index_name, scores[i], docs[i], totals[i],
        n_shards=kw["n_shards"], took=kw["tooks"][i], from_=kw["from_"],
        size=kw["size"]) for i in range(scores.shape[0])]


@pytest.mark.parametrize("name", list(REQUESTS))
def test_raw_msearch_equals_the_old_renderer_byte_for_byte(name, served,
                                                           calls):
    node, _ = served
    bodies = REQUESTS[name]
    payload = node.msearch([({"index": "rr"}, b) for b in bodies], raw=True)
    assert isinstance(payload, bytes)
    # every batch: each body's bytes are the twin's text
    twins = {}
    for call in calls:
        rendered, hits, patched = call[6]
        want = twin_bodies(call)
        assert [r.decode() for r in rendered] == want
        assert all(isinstance(r, bytes) for r in rendered)
        assert hits == sum(w.count('"_id"') for w in want) and patched == 0
        key = (call[5]["size"], call[5]["from_"])
        twins[key] = iter(want)
    # the whole answer: the old join of the twin's strings, and of the
    # general lane's dicts where a body took that lane
    parsed = json.loads(payload)["responses"]
    responses = [
        parsed[i] if "track_scores" in b
        else next(twins[(b["size"], b.get("from", 0))])
        for i, b in enumerate(bodies)]
    assert payload == raw_reference.msearch_payload(responses)
    n_packed = sum("track_scores" not in b for b in bodies)
    assert sum(c[2].shape[0] for c in calls) == n_packed
    # and what the case is named for is in it
    hits = [len(r["hits"]["hits"]) for r in parsed]
    if name == "k hits a body":
        assert hits == [50] * 4
    elif name == "none":
        assert hits == [0, 0] and parsed[0]["hits"]["max_score"] is None
    elif name == "k, fewer and none in one batch":
        assert hits[0] == 50 and 0 < hits[1] < 50 and hits[2] == 0
    elif name == "more than the index holds":
        assert hits[0] == N_DOCS
    elif name == "from > 0":
        assert hits[0] == 10 and 0 < hits[1] < 10 and hits[2] == 0
        assert parsed[0]["hits"]["max_score"] \
            >= parsed[0]["hits"]["hits"][0]["_score"]
    elif name == "from past the last hit":
        assert hits[1] == 0 and parsed[1]["hits"]["total"] == 1
    elif name == "a non-ASCII id":
        assert parsed[0]["hits"]["hits"][0]["_id"] == ODD_ID
        assert ODD_ID.encode() in payload
    elif name == "a packed and a general-lane body":
        assert hits[1] > 0 and len(calls) == 1


def _fake_view(ids):
    ids = np.asarray(ids)
    return types.SimpleNamespace(ids_packed=ids, ids_bytes=_utf8_rows(ids),
                                 single_type="doc", ids_json_safe=True)


def _sorted_scores(rng, q, k, scale):
    s = np.sort(rng.gamma(2.0, scale, (q, k)).astype(np.float32), axis=1)
    return np.ascontiguousarray(s[:, ::-1])


# the shapes the traffic sends (`size` 10 and 1000 a body), and the values
# the vector pass hands to the scalar `%.9g`
@pytest.mark.parametrize("q,k,from_,size,odd", [
    (256, 10, 0, 10, False), (3, 1000, 0, 1000, False), (1, 1, 0, 1, False),
    (5, 40, 10, 20, False), (4, 1, 0, 0, False), (6, 30, 0, 30, True)])
def test_any_batch_equals_the_old_renderer(q, k, from_, size, odd):
    rng = np.random.default_rng(q * 1000 + k)
    view = _fake_view([str(i) for i in range(5_000)] + ["ünï-1", "x"])
    scores = _sorted_scores(rng, q, k, 4.0)
    if odd:     # exponent forms, a negative, a zero, rows with no hit
        scores[0, :4] = [3e10, 2.5e9, 1e9, 999999999.0]
        scores[1, -3:] = [1e-5, 1.5e-7, 1e-45]
        scores[2, -2:] = [0.0, -1.25]
        scores[3, 5:] = -np.inf
        scores[4] = -np.inf
    docs = rng.integers(0, 5_002, (q, k)).astype(np.int64)
    docs[scores == -np.inf] = -1
    totals = rng.integers(0, 10_000, q)
    tooks = list(range(q))
    rendered, hits, patched = executor.response_raw(
        view, "ix", scores, docs, totals, n_shards=5, tooks=tooks,
        from_=from_, size=size)
    want = [raw_reference.response_raw(
        view, "ix", scores[i], docs[i], totals[i], n_shards=5,
        took=tooks[i], from_=from_, size=size) for i in range(q)]
    assert [r.decode() for r in rendered] == want
    assert b",".join(rendered) == ",".join(want).encode()
    live = scores[:, from_:from_ + size] > -np.inf
    assert hits == int(live.sum())
    assert patched == (7 if odd else 0)


def _render_counter(req) -> dict:
    out = {}
    for line in req("GET", "/_metrics").decode().splitlines():
        m = re.match(r'^es_packed_render_hits_total\{form="(\w+)"[^}]*\} '
                     r'(\S+)$', line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def _respond_span(req, root_part):
    lst = json.loads(req("GET", "/_traces"))
    trace = next(t for t in lst["traces"] if root_part in t["root"])
    full = json.loads(req("GET", f"/_traces/{trace['trace_id']}"))

    def find(node):
        if node["name"] == "packed.respond":
            return node
        for c in node["children"]:
            got = find(c)
            if got:
                return got

    return find(full["tree"])


def test_counter_and_span_say_what_was_rendered_and_how(served):
    _, req = served
    before = _render_counter(req)
    assert set(before) == {"vector", "patched", "dict"}
    bodies = [match("common", 50), match("rare", 50), match("zzz", 50)]
    out = json.loads(req("POST", "/_msearch?trace=true", "".join(
        json.dumps({"index": "rr"}) + "\n" + json.dumps(b) + "\n"
        for b in bodies)))
    n_raw = sum(len(r["hits"]["hits"]) for r in out["responses"])
    assert n_raw > 50
    span = _respond_span(req, "_msearch")
    assert span["attributes"] == {"form": "raw", "hits": n_raw, "patched": 0}
    mid = _render_counter(req)
    assert mid["vector"] - before["vector"] == n_raw
    assert mid["patched"] == before["patched"]
    assert mid["dict"] == before["dict"]
    # a solo `_search` with `_source` is rendered as dicts
    solo = json.loads(req("POST", "/rr/_search?trace=true", json.dumps(
        {"query": {"match": {"body": "rare"}}, "size": 4})))
    assert len(solo["hits"]["hits"]) == 4
    span = _respond_span(req, "/rr/_search")
    assert span["attributes"] == {"form": "dict", "hits": 4, "patched": 0}
    after = _render_counter(req)
    assert after["dict"] - mid["dict"] == 4
    assert after["vector"] == mid["vector"]


def test_a_refresh_that_widens_the_ids_is_rendered_from_the_new_column(
        served, calls):
    """The view of the next refresh extends the last one's postings; its
    id columns, text and bytes, are built for all its segments."""
    node, req = served
    width = node.indices["rr"].packed_view().ids_bytes.shape[1]
    long_id = "a-much-longer-id-than-any-before-ü"
    req("POST", "/_bulk?refresh=true",
        json.dumps({"index": {"_index": "rr", "_type": "doc",
                              "_id": long_id}})
        + "\n" + json.dumps({"body": "common rare latecomer"}) + "\n")
    view = node.indices["rr"].packed_view()
    assert view.ids_bytes.shape == (view.ids_packed.shape[0],
                                    len(long_id.encode()))
    assert view.ids_bytes.shape[1] > width
    payload = node.msearch([({"index": "rr"}, match(q, 30))
                            for q in ("latecomer", "rare", "common")],
                           raw=True)
    (call,) = calls
    assert [r.decode() for r in call[6][0]] == twin_bodies(call)
    assert payload == raw_reference.msearch_payload(twin_bodies(call))
    assert json.loads(payload)["responses"][0]["hits"]["hits"][0]["_id"] \
        == long_id
