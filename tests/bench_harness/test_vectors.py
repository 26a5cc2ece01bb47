"""The harness's dense-vector vocabulary, on the CPU at small sizes: the
`vector` field kind, the `$vector` placeholder, the reference's `knn`,
`cosine` `function_score`, `rescore` and `dis_max` worked by hand, the
`_id` routing the rescore window needs, `work.request_work` and the
`roofline_mixed` reader on a synthetic run, the warm-up that gives up, and
one harness run of the fixture cell under `fixtures/` (a hybrid BM25 ->
cosine rescore mix and exact kNN bodies over 768-d vectors)."""

import json
import math
import os

import numpy as np
import pytest

import compare
import corpus
import harness
import traffic
import work
from conftest import B, BENCH, FIXTURES, with_entries
from readers import roofline_mixed
from reference import Reference, bf16, djb2, e4m3, shard_of

with open(os.path.join(FIXTURES, "vectors-768d-fixture.json")) as f:
    FIXTURE = json.load(f)
with open(os.path.join(FIXTURES, "vectors.hybrid-rescore.json")) as f:
    FIXTURE_MIX = json.load(f)
with open(os.path.join(FIXTURES, "vector-entries.json")) as f:
    B_VEC = with_entries(B, json.load(f))
CELL = B_VEC["workloads"][-1]["name"]

VEC = {"kind": "vector", "dims": 8, "clusters": 3, "zipf": 1.0,
       "centres_seed": 11, "spread": 0.5, "normalize": False,
       "dtype": "float32"}
TINYV = {
    "name": "tinyv", "index": "tinyv", "documents": 60,
    "similarity": {"k1": 1.2, "b": 0.75},
    "index_settings": {"number_of_shards": 5},
    "fields": {
        "body": {"kind": "text", "vocab": 12, "zipf": 1.0,
                 "length": {"dist": "uniform", "min": 3, "max": 9}},
        "emb": VEC},
}
SEED = 2 ** 31 + 21


@pytest.fixture(scope="module")
def ref():
    return Reference(TINYV, SEED)


# -- the vector field kind ----------------------------------------------------

def test_vector_chunks_are_made_from_seed_and_chunk_alone():
    a = corpus.chunk(FIXTURE, SEED, 0)["emb"]
    assert a.dtype == np.float32 and a.shape == (2000, 768)
    assert np.array_equal(a, corpus.chunk(FIXTURE, SEED, 0)["emb"])
    assert not np.array_equal(a, corpus.chunk(FIXTURE, SEED + 1, 0)["emb"])
    assert np.allclose(np.linalg.norm(a, axis=1), 1.0, atol=1e-6)
    # the text columns come first and are wiki-bm25-5s' draws letter for
    # letter: the vector field changes no posting
    with open(os.path.join(BENCH, "configs", "wiki-bm25-5s.json")) as f:
        wiki = json.load(f)
    small = {**wiki, "documents": 2000}
    for name in ("title", "body"):
        for x, y in zip(corpus.chunk(small, SEED, 0)[name],
                        corpus.chunk(FIXTURE, SEED, 0)[name]):
            assert np.array_equal(x, y)


def test_source_round_trips_every_component_bit_for_bit():
    cols = corpus.chunk(FIXTURE, SEED, 0)
    lines = corpus.payload(FIXTURE, SEED, 0).split(b"\n")
    assert lines[0] == b'{"index":{"_id":"0"}}' and lines[-1] == b""
    for i in (0, 1, 777, 1999):
        doc = json.loads(lines[2 * i + 1])
        assert list(doc) == ["title", "body", "emb"]
        sent = np.array(doc["emb"], np.float64).astype(np.float32)
        assert np.array_equal(sent.view(np.uint32),
                              cols["emb"][i].view(np.uint32))
        assert corpus.source(FIXTURE, SEED, i) == doc
    assert corpus.mapping(FIXTURE)["mappings"]["_doc"]["properties"][
        "emb"] == {"type": "dense_vector", "dims": 768}


def test_f32_text_is_the_shortest_decimal_that_reads_back():
    rng = np.random.default_rng(3)
    x = np.concatenate([
        rng.standard_normal(20000).astype(np.float32) * 0.05,
        np.float32([0.0, -0.0, 1.0, -1.0, 0.5, 0.1, 0.09999999, 0.99999994,
                    1e-9, 5e-10, 3e-20, 123456.7, 1e20, -2.5e-7])])
    text, bounds = corpus.f32_text(x)
    parts = [text[bounds[k]:bounds[k + 1] - 1].decode()
             for k in range(len(x))]
    back = np.array(json.loads("[" + ",".join(parts) + "]"),
                    np.float64).astype(np.float32)
    assert np.array_equal(back.view(np.uint32), x.view(np.uint32))
    assert parts[-14:-4] == ["0.0", "-0.0", "1.0", "-1.0", "0.5", "0.1",
                             "0.09999999", "0.99999994", "1e-09", "5e-10"]

    def digits(s):
        return len(s.split("e")[0].replace("-", "").replace(".", "")
                   .strip("0"))
    for k in range(0, len(x), 7):
        assert digits(parts[k]) == digits(np.format_float_positional(
            x[k], unique=True)), (x[k], parts[k])


def test_vector_placeholder_gives_every_seed_the_same_bodies():
    w = {k: v for k, v in FIXTURE_MIX.items() if k != "order_block"}
    a = traffic.build(w, FIXTURE, 1, 3.0)
    b = traffic.build(w, FIXTURE, 2 ** 31 + 9, 3.0)
    assert sorted(r["payload"] for r in a) == sorted(r["payload"] for r in b)
    assert [r["payload"] for r in a] != [r["payload"] for r in b]
    knn = [body["knn"]["query_vector"] for r in a for body in r["bodies"]
           if "knn" in body]
    resc = [body["rescore"]["query"]["rescore_query"]["function_score"][
        "functions"][0]["cosine"]["query_vectors"][0]
        for r in a for body in r["bodies"] if "rescore" in body]
    assert knn and resc
    for v in knn + resc:
        v32 = np.float32(v)
        assert v32.shape == (768,) and abs(np.linalg.norm(v32) - 1) < 1e-6
        # printed short: each float is the float32's shortest decimal
        assert v == json.loads(b"[" + corpus.f32_text(v32)[0][:-1] + b"]")
        assert len(json.dumps(v)) < 768 * 14
    # the cluster law: queries land near documents
    docs = corpus.chunk(FIXTURE, 5, 0)["emb"].astype(np.float64)
    assert np.median(np.max(docs @ np.array(knn).T, axis=0)) > 0.5


# -- the reference, by hand ---------------------------------------------------

def _cos(a, b):
    return sum(x * y for x, y in zip(a, b)) / math.sqrt(
        sum(x * x for x in a) * sum(y * y for y in b))


def _doc_vec(d):
    return [float(v) for v in corpus.chunk(TINYV, SEED, 0)["emb"][d]]


def test_knn_scores_by_hand(ref):
    q = [0.25, -0.5, 1.0, 0.0, 2.0, -1.5, 0.125, 0.75]
    for metric, by_hand in (
            ("cosine", _cos),
            ("dot", lambda a, b: sum(x * y for x, y in zip(a, b))),
            ("l2", lambda a, b: -sum((x - y) ** 2 for x, y in zip(a, b)))):
        ans = ref.answer({"knn": {"field": "emb", "query_vector": q, "k": 3,
                                  "metric": metric}, "size": 10})
        assert ans["size"] == 3 and ans["total"] == 60
        for d in (0, 17, 59):
            assert ans["score"][d] == pytest.approx(by_hand(q, _doc_vec(d)),
                                                    rel=1e-12, abs=1e-12)
    # a filter narrows the candidates and the total
    m = ref.evaluate({"match": {"body": "t000002"}})[0]
    ans = ref.answer({"knn": {"field": "emb", "query_vector": q, "k": 3,
                              "filter": {"match": {"body": "t000002"}}}})
    assert np.array_equal(ans["mask"], m) and ans["total"] == int(m.sum())


def test_an_unanswered_knn_body_leaves_nothing_for_the_next_request():
    r = Reference(TINYV, SEED)

    def knn(q):
        return {"knn": {"field": "emb", "query_vector": q, "k": 3}}
    first = [knn([1.0] * 8), knn([0.5, -1.0] * 4)]
    r.prepare(first)
    tally = compare.Tally()
    compare.compare_answer(tally, "a[0]", first[0], {"error": "timed out"},
                           r, 1e-4)
    assert tally.n["unanswered"] == 1
    r.similarity("emb", first[1]["knn"]["query_vector"])   # answered
    assert len(r._memo) == 1           # the unanswered body's row
    r.prepare([knn([0.0, 2.0] * 4)])
    assert len(r._memo) == 1           # only the new request's row
    [row] = r._memo.values()
    assert np.array_equal(row, r.similarity("emb", [0.0, 2.0] * 4,
                                            docs=np.arange(60)))


def test_function_score_cosine_by_hand(ref):
    q = [1.0, 0.5, -0.25, 0.0, 0.0, 2.0, -1.0, 0.5]
    fn = {"cosine": {"field": "emb", "query_vectors": [q]}}
    inner = {"match": {"body": "t000001 t000003"}}
    m, bm25 = ref.evaluate(inner)
    for spec, by_hand in (
            ({"query": inner, "functions": [fn]}, lambda s, c: s * c),
            ({"query": inner, "functions": [fn], "boost_mode": "replace"},
             lambda s, c: c),
            ({"query": inner, "functions": [{**fn, "weight": 2.0}],
              "boost_mode": "sum", "boost": 3.0},
             lambda s, c: 3.0 * (s + 2.0 * c)),
            ({"query": inner, "functions": [{**fn, "weight": 3.0},
                                            {"weight": 0.5}],
              "score_mode": "avg", "boost_mode": "replace"},
             lambda s, c: (3.0 * c + 0.5) / 3.5)):
        mask, score = ref.evaluate({"function_score": spec})
        assert np.array_equal(mask, m)
        for d in np.flatnonzero(m)[:4]:
            assert score[d] == pytest.approx(
                by_hand(bm25[d], _cos(q, _doc_vec(d))), rel=1e-12)
        assert not score[~m].any()


def test_rescore_reranks_each_shards_window_by_hand(ref):
    q = [0.5, 1.0, -1.0, 0.25, 0.0, 0.5, 1.5, -0.5]
    body = {"query": {"match": {"body": "t000001 t000002"}}, "size": 4,
            "rescore": {"window_size": 2, "query": {
                "rescore_query": {"function_score": {
                    "functions": [{"cosine": {"field": "emb",
                                              "query_vectors": [q]}}],
                    "boost_mode": "replace"}},
                "query_weight": 0.5, "rescore_query_weight": 2.0}}}
    ans = ref.answer(body)
    mask, prim = ref.evaluate(body["query"])
    want = {}
    for s in range(5):
        docs = [d for d in range(60)
                if mask[d] and djb2(str(d)) % 5 == s]
        docs.sort(key=lambda d: (-prim[d], d))
        assert len(docs) > 4          # every shard cuts its window and keep
        for rank, d in enumerate(docs[:4]):
            want[d] = 0.5 * prim[d] + (2.0 * _cos(q, _doc_vec(d))
                                       if rank < 2 else 0.0)
    assert set(np.flatnonzero(ans["reach"])) == set(want)
    for d, v in want.items():
        assert ans["score"][d] == pytest.approx(v, rel=1e-12)
    assert ans["total"] == int(mask.sum())
    # the control answers it in the program's shape: the four best kept
    got = ref.respond(body)["hits"]["hits"]
    assert [int(h["_id"]) for h in got] == sorted(
        want, key=lambda d: -want[d])[:4]


def test_a_tie_at_the_window_edge_accepts_either_side(ref):
    from reference import _near_edge
    ps = np.array([5.0, 3.0002, 3.0001, 2.0, 1.0])     # first-stage order
    near = _near_edge(ps, np.arange(5), 2, 1e-4)
    assert near.tolist() == [False, True, True, False, False]


def test_dis_max_by_hand(ref):
    a, b = {"match": {"body": "t000001"}}, {"match": {"body": "t000004"}}
    (ma, sa), (mb, sb) = ref.evaluate(a), ref.evaluate(b)
    mask, score = ref.evaluate({"dis_max": {"queries": [a, b],
                                            "tie_breaker": 0.3}})
    assert np.array_equal(mask, ma | mb)
    both = np.flatnonzero(ma & mb)
    assert len(both)
    for d in both[:3]:
        hi, lo = max(sa[d], sb[d]), min(sa[d], sb[d])
        assert score[d] == pytest.approx(hi + 0.3 * lo, rel=1e-12)
    body = {"query": {"dis_max": {"queries": [a, b]}}, "size": 10}
    assert work.body_bytes(ref, body) == \
        12 * int(ref.df("body", [1]).sum() + ref.df("body", [4]).sum()) + 80


@pytest.mark.parametrize("dtype, precision, rounding", [
    ("float32", "stated", lambda v: np.asarray(v, np.float32)),
    ("float32", "low", bf16),
    ("bfloat16", "stated", bf16),
    ("bfloat16", "low", e4m3)])
def test_vectors_are_rounded_as_the_precision_states(dtype, precision,
                                                      rounding):
    cfg = {**TINYV, "fields": {**TINYV["fields"],
                               "emb": {**VEC, "dtype": dtype}}}
    r = Reference(cfg, SEED, precision=precision)
    q = [0.3, 0.1, 0.7, -0.2, 0.9, 0.4, -0.6, 0.05]
    got = r.similarity("emb", q, "dot", docs=[3])[0]
    x = rounding(corpus.chunk(cfg, SEED, 0)["emb"][3]).astype(np.float64)
    want = float(np.dot(x, rounding(np.float32(q)).astype(np.float64)))
    assert got == pytest.approx(want, rel=1e-12)


# -- routing -------------------------------------------------------------------

def test_routing_is_pinned():
    # djb2 over UTF-16 units by hand: "0" -> 5381 x 33 + 48 = 177621,
    # "7" -> 177628, "10" -> (5381 x 33 + 49) x 33 + 48 = 5861574
    assert [djb2(i) for i in ("0", "7", "10")] == [177621, 177628, 5861574]
    assert [shard_of(i, 5) for i in ("0", "7", "10")] == [1, 3, 4]
    assert [shard_of(str(i), 5) for i in range(10)] == \
        [1, 2, 3, 4, 0, 1, 2, 3, 4, 0]
    # a surrogate pair is two units: (5381 x 33 + 0xD83D) x 33 + 0xDE00
    assert djb2("\U0001F600") == 7743522
    # the hash is a Java int and the modulus a floor one (MathUtils.mod):
    # -1020202163 = -204040433 x 5 + 2, where Java's % would give -3
    assert djb2("99999999") == -1020202163
    assert shard_of("99999999", 5) == 2


# -- least work and the mixed roofline -----------------------------------------

def _fixture_ref(n):
    return Reference({**FIXTURE, "documents": n}, SEED)


def test_request_work_counts_the_matrix_once_a_request():
    r = _fixture_ref(2000)
    bodies = [b for req in traffic.build(FIXTURE_MIX, FIXTURE, 1, 30.0)
              for b in req["bodies"] if "knn" in b][:256]
    bodies = (bodies * 256)[:256]
    n_bytes, flops = work.request_work(r, bodies)
    assert n_bytes == 4 * 768 * 2000
    assert flops == 256 * 2 * 768 * 2000
    text = {"query": {"match": {"body": "t000100 t000200"}}, "size": 10}
    assert work.request_work(r, [text, text]) == \
        (2 * work.body_bytes(r, text), 0.0)


def test_request_work_of_a_rescore_is_its_windows():
    r = _fixture_ref(2000)
    body, = [b for req in traffic.build(FIXTURE_MIX, FIXTURE, 1, 3.0)
             for b in req["bodies"]
             if "rescore" in b and r.answer(b)["total"] > 300][:1]
    mask, _ = r.evaluate(body["query"])
    per_shard = np.bincount(r.shards()[mask], minlength=5)
    rows = int(np.minimum(per_shard, 50).sum())
    assert 50 < rows <= 250
    n_bytes, flops = work.request_work(r, [body])
    assert n_bytes == work.body_bytes(r, body) + rows * 768 * 4
    assert flops == 2 * rows * 768
    # a request whose windows outgrow the matrix reads the matrix once
    n_bytes, _ = work.request_work(r, [body] * 40)
    assert n_bytes == 40 * work.body_bytes(r, body) + 4 * 768 * 2000


def test_mixed_roofline_reads_100_where_the_device_takes_the_least_time():
    r = _fixture_ref(2000)
    reqs = traffic.build(FIXTURE_MIX, FIXTURE, 1, 3.0)
    pk = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    least = sum(work.least_seconds_mixed(pk, *work.request_work(
        r, q["bodies"])) for q in reqs)
    assert least > 0
    ctx = {"reference": r, "requests": reqs, "trace_span": (0.0, 10.0),
           "device": type("D", (), {"device_kind": "TPU v5 lite"})(),
           "records": [{"i": i, "status": 200, "item_errors": 0,
                        "sent": 1.0, "done": 2.0}
                       for i in range(len(reqs))],
           "trace": {"device_planes": 1,
                     "modules": {"jit__cosine_scores": (1, least),
                                 "jit_other": (1, 5.0)}}}
    share = roofline_mixed.read(ctx, {"programs": ["cosine_scores"]})
    assert share == pytest.approx(100.0, rel=1e-9) and share <= 100.0 + 1e-9
    ctx["trace"]["modules"] = {"jit_other": (1, 5.0)}
    assert roofline_mixed.read(ctx, {"programs": ["cosine_scores"]}) is None


# -- the warm-up gives up ------------------------------------------------------

def test_a_warm_up_that_never_settles_opens_no_window():
    serving = harness.Serving.__new__(harness.Serving)
    serving.cell = type("C", (), {"workload": {
        **FIXTURE_MIX, "warmup": {"replay_s": 0.5, "rounds": 3}},
        "cfg": FIXTURE})()
    serving.seed, serving.client = 5, None

    def window(requests, keep, seconds, trace):
        return {"before": {"metrics": {}}, "records": [{"status": 200}],
                "after": {"metrics": {"es_jit_compiles_total": [({}, 2.0)]}}}

    serving.window = window
    with pytest.raises(harness.Unsettled, match="last of 3 replay rounds "
                                                "compiled 2 programs"):
        serving.warm_up()


# -- the fixture cell end to end on the CPU ------------------------------------

@pytest.fixture(scope="module")
def bench_vec_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("benchvec") / "BENCHMARK.json"
    path.write_text(json.dumps(B_VEC))
    return str(path)


@pytest.fixture(scope="module")
def run(bench_vec_file):
    def _run(**kw):
        procs = []
        try:
            return harness.run(CELL, 2 ** 31 + 11, 3.0, False,
                               platform="cpu", overrides={"chips": 8},
                               procs=procs, bench_file=bench_vec_file, **kw)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p.wait()
    return _run


@pytest.fixture(scope="module")
def plain(run):
    return run(control=True)


def _over(compared):
    return [k for k, c in compared.items()
            if c["limit"] is not None and c["value"] > c["limit"]]


def test_the_fixture_cell_is_read_from_its_own_file(bench_vec_file):
    cell = harness.Cell(CELL, bench_file=bench_vec_file)
    assert cell.workload == FIXTURE_MIX and cell.cfg == FIXTURE
    assert B_VEC["workloads"][-1]["file"].startswith(
        "tests/bench_harness/fixtures/")
    assert not os.path.exists(os.path.join(harness.HERE, "workloads",
                                           CELL + ".json"))


def test_the_fixture_run_is_correct(plain):
    assert plain["correct"] is True, plain["compared"]
    assert plain["failed"] == 0 and plain["attempted"] > 0
    assert plain["compared"]["answers_checked"]["value"] >= 16
    assert plain["compared"]["source_wrong"]["value"] == 0
    assert plain["notes"]["hits_checked"] > 100
    assert set(plain["metrics"]) == {"queries_per_s", "setup_s"}


def test_the_bfloat16_control_is_not_correct(plain):
    control = plain["control"]
    assert control["correct"] is False
    assert "score_rel_err_max" in _over(control["compared"]), control


def test_an_altered_hit_is_not_correct(run, monkeypatch):
    """The timed path broken underneath: each shard's best rescored and
    each shard's nearest kNN hit scored 0.1 % high where it is produced."""
    from elasticsearch_tpu.search.shard_searcher import ShardSearcher
    rescore_batch, execute_knn = ShardSearcher.rescore_batch, \
        ShardSearcher.execute_knn

    def skew(result):
        result.scores[:, 0] = result.scores[:, 0] * np.float32(1.001)
        return result

    monkeypatch.setattr(ShardSearcher, "rescore_batch",
                        lambda self, *a, **k: skew(rescore_batch(self, *a,
                                                                 **k)))
    monkeypatch.setattr(ShardSearcher, "execute_knn",
                        lambda self, *a, **k: skew(execute_knn(self, *a,
                                                               **k)))
    out = run()
    assert out["correct"] is False
    assert _over(out["compared"]) == ["score_rel_err_max"], out["compared"]


def test_compare_takes_a_rescore_hit_on_either_side_of_its_edge():
    """A document at its shard's window edge may score either way, and one
    at its keep edge may be missing; one that must be kept may not."""
    n = 6
    want = {"mask": np.ones(n, bool), "total": n,
            "score": np.array([9.0, 8.0, 7.5, 7.0, 1.0, 0.5]),
            "alt": np.array([9.0, 8.0, 5.0, 7.0, 1.0, 0.5]),
            "reach": np.array([1, 1, 1, 1, 1, 0], bool),
            "sure": np.array([1, 1, 1, 0, 1, 0], bool)}
    ref = type("R", (), {"answer": lambda self, body, tol: want,
                         "source": lambda self, d: {}})()
    body = {"size": 3, "_source": False}

    def check(ids, scores):
        tally = compare.Tally()
        compare.compare_answer(tally, "x", body, {"hits": {
            "total": n, "hits": [{"_id": str(i), "_score": s}
                                 for i, s in zip(ids, scores)]}}, ref, 1e-4)
        return tally.n
    # doc 2 rescored or not, doc 3 (unsure) left out: both fine
    assert check([0, 1, 4], [9.0, 8.0, 1.0])["hits_wrong"] == 1   # 2 missing
    ok = check([0, 1, 2], [9.0, 8.0, 5.0])
    assert ok["hits_wrong"] == 0 and ok["score_rel_err_max"] == 0.0
    assert check([0, 1, 2], [9.0, 8.0, 7.5])["score_rel_err_max"] == 0.0
    assert check([0, 1, 2], [9.0, 8.0, 6.0])["score_rel_err_max"] > 0.1
    # a document no shard keeps is no hit
    assert check([0, 1, 5], [9.0, 8.0, 0.5])["hits_wrong"] == 1
