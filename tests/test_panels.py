"""The dashboard-panel lane (search/aggs/panels.py): `size: 0` range +
date_histogram, terms under a one-term match, filtered count — exact against
a plain numpy reference and against the general driver's per-segment loop,
at every batch size and index layout, from a closed set of programs that
compiles nothing once warm; and the time-out path that keeps packed-eligible
bodies out of the lane. The node owns the suite's 8 virtual devices, so the
lane runs its collective form (`panels_mesh`): one program a batch over the
chip axis (`tests/test_panels_mesh.py` holds it to every other axis)."""

import json
import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common.device_stats import lane_decisions_snapshot
from elasticsearch_tpu.common.metrics import device_events_snapshot
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.search.aggs import panels

HOUR = 3_600_000
BASE = 893_964_617_000            # rally-tracks http_logs' first event
N_DOCS = 600
LANE = "panels_mesh"              # the lane's name on more than one chip
WORDS = ["get", "images", "english", "french", "index", "html", "gif"]
STATUS = [200, 200, 200, 200, 304, 304, 404, 500]

MAPPING = {"_doc": {"properties": {
    "@timestamp": {"type": "date"}, "request": {"type": "string"},
    "status": {"type": "integer"}, "size": {"type": "integer"},
    "ratio": {"type": "double"}}}}


def corpus():
    """Six days of events with an empty stretch (hours 30..40 hold
    nothing), a tenth of them on an hour's edge."""
    rng = np.random.default_rng(27)
    hours = rng.integers(0, 144, N_DOCS)
    hours = np.where((hours >= 30) & (hours <= 40), hours + 20, hours)
    ts = BASE + hours * HOUR + rng.integers(0, HOUR, N_DOCS)
    edge = rng.random(N_DOCS) < 0.1
    ts = np.where(edge, (ts // HOUR) * HOUR - rng.integers(0, 2, N_DOCS), ts)
    status = np.asarray(STATUS)[rng.integers(0, len(STATUS), N_DOCS)]
    words = [[WORDS[j] for j in rng.choice(len(WORDS), 3, replace=False)]
             for _ in range(N_DOCS)]
    return ts.astype(np.int64), status.astype(np.int64), words


TS, ST, WD = corpus()
LAYOUTS = [(shards, segs, deleted) for shards in (1, 5)
           for segs in (1, 4) for deleted in (False, True)]


def layout_id(layout):
    shards, segs, deleted = layout
    return f"{shards}sh-{segs}seg-{'del' if deleted else 'nodel'}"


@pytest.fixture(scope="module")
def nodes(tmp_path_factory):
    """One index for each layout: {1, 5} shards x {one, several} segments
    x {no deletes, every seventh document deleted}."""
    node = NodeService(str(tmp_path_factory.mktemp("panels")))
    live = {}
    for layout in LAYOUTS:
        shards, segs, deleted = layout
        name = layout_id(layout)
        node.create_index(name, settings={
            "number_of_shards": shards,
            "index.requests.cache.enable": False}, mappings=MAPPING)
        per = -(-N_DOCS // segs)
        for i in range(N_DOCS):
            node.index_doc(name, str(i), {
                "@timestamp": int(TS[i]), "request": " ".join(WD[i]),
                "status": int(ST[i]), "size": i, "ratio": i / 7.0})
            if (i + 1) % per == 0:
                node.refresh(name)
        node.refresh(name)
        alive = np.ones(N_DOCS, bool)
        if deleted:
            for i in range(0, N_DOCS, 7):
                node.delete_doc(name, str(i))
                alive[i] = False
            node.refresh(name)
        live[name] = alive
    yield node, live
    node.close()


# -- the three shapes and the plain reference -------------------------------

def body_of(kind, lo, hi, word="images", status=200):
    rng = {"range": {"@timestamp": {"gte": int(lo), "lt": int(hi)}}}
    if kind == "hist":
        return {"size": 0, "query": rng, "aggs": {"per_hour": {
            "date_histogram": {"field": "@timestamp", "interval": "hour"}}}}
    if kind == "terms":
        return {"size": 0, "query": {"bool": {
            "must": [{"match": {"request": word}}], "filter": [rng]}},
            "aggs": {"by_status": {"terms": {"field": "status",
                                             "size": 20}}}}
    return {"size": 0, "query": {"bool": {"filter": [
        rng, {"term": {"status": status}}]}}}


def bodies_of(kind, n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        lo = BASE + int(rng.integers(-12 * HOUR, 120 * HOUR))
        hi = lo + int(rng.integers(HOUR // 2, 7 * 24 * HOUR))
        out.append(body_of(kind, lo, hi, WORDS[int(rng.integers(0, 7))],
                           STATUS[int(rng.integers(0, 8))]))
    return out


def expected(body, alive):
    """(hits.total, {bucket key: doc_count}) by numpy over the corpus."""
    q = body["query"]
    clauses = [q] if "range" in q else \
        q["bool"].get("must", []) + q["bool"]["filter"]
    mask = alive.copy()
    for c in clauses:
        (kind, spec), = c.items()
        if kind == "range":
            b = spec["@timestamp"]
            mask &= (TS >= b["gte"]) & (TS < b["lt"])
        elif kind == "term":
            mask &= ST == spec["status"]
        else:
            mask &= np.array([spec["request"] in w for w in WD])
    buckets = None
    if "aggs" in body:
        (agg,), = [list(a.items()) for a in body["aggs"].values()]
        keys = (TS[mask] // HOUR) * HOUR if agg[0] == "date_histogram" \
            else ST[mask]
        k, c = np.unique(keys, return_counts=True)
        buckets = dict(zip(k.tolist(), c.tolist()))
    return int(mask.sum()), buckets


def check(resp, body, alive):
    total, buckets = expected(body, alive)
    assert resp["hits"]["total"] == total, body
    assert resp["hits"]["hits"] == []
    if buckets is None:
        assert "aggregations" not in resp
        return
    (got,) = resp["aggregations"].values()
    assert {b["key"]: b["doc_count"] for b in got["buckets"]} == buckets


def general(node, index, body):
    """The same body through the general driver (the per-segment loop)."""
    plan = node._search_plan(index, body, None, None, None, False)
    return node._search_general(index, plan.names, plan.body, plan.size,
                                plan.from_, plan.sort, plan.alias_flt, None,
                                time.perf_counter_ns(), 0)


def panel_of(node, index, body):
    """The body's row of the panel lane as `_search_plan` works it out."""
    return node._search_plan(index, json.loads(json.dumps(body)), None, None,
                             None, False).panel


def same_answer(a, b):
    a, b = json.loads(json.dumps(a)), json.loads(json.dumps(b))
    for r in (a, b):
        r.pop("took")
        r["hits"]["max_score"] = None   # the general driver scores size: 0
    return a == b


def concurrently(node, index, bodies):
    out = [None] * len(bodies)

    def one(i):
        try:
            out[i] = node.search(index, json.loads(json.dumps(bodies[i])))
        except Exception as e:  # noqa: BLE001 — the assertion shows it
            out[i] = e
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    return out


# -- exactness at every shape, batch size and layout --------------------------

@pytest.mark.parametrize("layout", LAYOUTS, ids=layout_id)
@pytest.mark.parametrize("q", [1, 2, 5, 32, 33])
@pytest.mark.parametrize("kind", ["hist", "terms", "count"])
def test_panels_are_exact(nodes, kind, q, layout):
    node, live = nodes
    index = layout_id(layout)
    bodies = bodies_of(kind, q, seed=q * 7 + len(kind))
    assert all(panel_of(node, index, b) is not None for b in bodies)
    chosen0 = _chosen()
    # the real path: q concurrent solo requests (a leader, its followers)
    for body, resp in zip(bodies, concurrently(node, index, bodies)):
        check(resp, body, live[index])
    moved = _moved(chosen0)
    assert moved == {LANE: q}       # leaders and followers alike
    # one batch of exactly q rows, and the first of them through the
    # general driver's per-segment loop
    outs = node._search_batched([(index, b) for b in bodies])
    assert len(outs) == q           # padded rows are never rendered
    for body, resp in zip(bodies, outs):
        check(resp, body, live[index])
    assert same_answer(outs[0], general(node, index, bodies[0]))


def _chosen():
    return {k.split(":")[0]: v for k, v in lane_decisions_snapshot().items()
            if k.endswith(":chosen")}


def _moved(chosen0):
    """Lanes chosen since `chosen0`, those that did not move left out."""
    return {k: v - chosen0.get(k, 0) for k, v in _chosen().items()
            if v != chosen0.get(k, 0)}


RANGES = {
    "nothing": (BASE + 31 * HOUR, BASE + 40 * HOUR),
    "everything": (BASE - HOUR, BASE + 167 * HOUR),
    "ends-outside-the-data": (BASE - 100 * HOUR, BASE + 150 * HOUR),
    "an-empty-hour-between-full-ones": (BASE + 20 * HOUR, BASE + 60 * HOUR),
    "one-millisecond": (int(TS[3]), int(TS[3]) + 1),
    "upside-down": (BASE + 50 * HOUR, BASE + 40 * HOUR),
}


@pytest.mark.parametrize("kind", ["hist", "terms", "count"])
@pytest.mark.parametrize("name", list(RANGES))
def test_edge_ranges(nodes, name, kind):
    node, live = nodes
    index = layout_id((5, 4, True))
    body = body_of(kind, *RANGES[name])
    assert panel_of(node, index, body) is not None
    resp = node.search(index, body)
    check(resp, body, live[index])
    assert same_answer(resp, general(node, index, body))
    total, buckets = expected(body, live[index])
    if name in ("nothing", "upside-down"):
        assert total == 0 and not buckets
    if name == "everything":
        assert total == (int(live[index].sum()) if kind == "hist" else total)
    if name == "an-empty-hour-between-full-ones" and kind == "hist":
        keys = sorted(buckets)
        assert max(np.diff(keys)) > HOUR    # zero-document buckets inside


def test_padded_rows_are_never_rendered(nodes):
    node, live = nodes
    index = layout_id((1, 4, False))
    bodies = bodies_of("hist", 2, seed=5)          # the Q = 4 program
    rows = [panel_of(node, index, b) for b in bodies]
    view = node.indices[index].panel_view(node._panel_pool())
    totals, partials = panels.execute(rows, view)
    assert totals.shape == (2,) and len(partials) == 2
    for body, t in zip(bodies, totals):
        assert t == expected(body, live[index])[0]


NOT_PANELS = {
    "open-range": {"size": 0, "query": {"range": {"@timestamp": {
        "gte": BASE}}}, "aggs": {"h": {"date_histogram": {
            "field": "@timestamp", "interval": "hour"}}}},
    "too-wide": body_of("hist", BASE, BASE + 300 * HOUR),
    "month": {"size": 0, "query": body_of("hist", BASE, BASE + HOUR)["query"],
              "aggs": {"h": {"date_histogram": {"field": "@timestamp",
                                                "interval": "month"}}}},
    "sized": {**body_of("count", BASE, BASE + HOUR), "size": 3},
    "two-term-match": body_of("terms", BASE, BASE + HOUR, "get images"),
    "sub-aggregation": {"size": 0, "query": body_of(
        "hist", BASE, BASE + HOUR)["query"], "aggs": {"h": {
            "date_histogram": {"field": "@timestamp", "interval": "hour"},
            "aggs": {"s": {"terms": {"field": "status"}}}}}},
    "double-column": {"size": 0, "query": {"bool": {"filter": [
        {"range": {"ratio": {"gte": 1, "lt": 5}}},
        {"term": {"status": 200}}]}}},
    "min-doc-count": {"size": 0, "query": body_of(
        "hist", BASE, BASE + HOUR)["query"], "aggs": {"h": {
            "date_histogram": {"field": "@timestamp", "interval": "hour",
                               "min_doc_count": 0}}}},
    "packed-match": {"query": {"match": {"request": "images"}}, "size": 10},
}


@pytest.mark.parametrize("name", list(NOT_PANELS))
def test_other_bodies_keep_their_path(nodes, name):
    node, _ = nodes
    index = layout_id((5, 1, False))
    body = NOT_PANELS[name]
    row = panel_of(node, index, body)
    if name == "double-column":     # a panel's shape over a column that
        assert row is not None      # the lane's programs do not read
        assert node._search_panels(index, [row], 0) is None
    else:
        assert row is None
    chosen0 = _chosen().get(LANE, 0)
    resp = node.search(index, json.loads(json.dumps(body)))
    assert "hits" in resp and _chosen().get(LANE, 0) == chosen0


def test_more_distinct_values_than_the_program_counts(nodes):
    """`terms` over a column with more than TERM_BINS values has a panel's
    shape but these segments are not the lane's; the general path answers
    it, alone and behind a leader."""
    node, _ = nodes
    index = layout_id((1, 1, False))
    body = body_of("terms", BASE, BASE + 144 * HOUR)
    body["aggs"]["by_status"]["terms"]["field"] = "size"
    row = panel_of(node, index, body)
    assert row is not None and node._search_panels(index, [row], 0) is None
    chosen0 = _chosen()
    for resp in concurrently(node, index, [body] * 4):
        assert len(resp["aggregations"]["by_status"]["buckets"]) == 20
    assert LANE not in _moved(chosen0)


@pytest.mark.parametrize("kind", ["hist", "terms", "count"])
@pytest.mark.parametrize("how", ["qos-off", "cacheable", "msearch-group",
                                 "url-size-0"])
def test_a_panel_takes_the_lane_whatever_else_holds(nodes, monkeypatch, how,
                                                    kind):
    """The lane follows from the body and the index alone: not from QoS
    being on, the request cache being off or the endpoint."""
    node, live = nodes
    index = layout_id((5, 4, True))
    bodies = bodies_of(kind, 3, seed=17)
    chosen0 = _chosen()
    if how == "qos-off":
        monkeypatch.setattr(node.qos, "enabled", lambda: False)
        outs = concurrently(node, index, bodies)
    elif how == "cacheable":
        outs = [node.search(index, b, request_cache=True) for b in bodies]
        again = node.search(index, bodies[0], request_cache=True)
        assert again == outs[0]                 # the cache's own answer
    elif how == "msearch-group":
        outs = node.msearch([({"index": index}, b) for b in bodies]
                            )["responses"]
    else:
        bodies = [{k: v for k, v in b.items() if k != "size"}
                  for b in bodies]
        outs = [node.search(index, b, size=0) for b in bodies]
    for body, resp in zip(bodies, outs):
        check(resp, body, live[index])
    moved = _moved(chosen0)
    assert moved == ({} if how == "msearch-group" else {LANE: 3})
    assert node.indices[index].search_stats["panels"] >= 3


# -- the closed set --------------------------------------------------------------

def test_the_set_is_enumerable_and_nothing_compiles_once_warm(nodes):
    """After the lane warmed itself for an index's segments, 200 requests
    of the mix with fresh ranges, terms and statuses at mixed concurrency
    compile nothing."""
    node, live = nodes
    index = layout_id((5, 4, True))
    view = node.indices[index].panel_view(node._panel_pool())
    members = panels.program_set(view)
    # one (row bucket, segments-a-chip bucket) a view: the segment loop is
    # inside the program
    assert {m[2:4] for m in members} == {(view.n_pad, view.G)}
    assert len(members) >= 3 * len(panels.Q_BUCKETS)
    assert {m[0] for m in members} == {"hist", "terms", "count"}
    panels.ensure_warm(view)
    compiles0 = device_events_snapshot()[0]
    rng = np.random.default_rng(200)
    sent = 0
    while sent < 200:
        n = int(rng.choice([1, 1, 2, 3, 7, 16, 40]))
        kinds = rng.choice(["hist", "terms", "count"], n, p=[.5, .3, .2])
        bodies = [bodies_of(k, 1, seed=int(rng.integers(1 << 30)))[0]
                  for k in kinds]
        for body, resp in zip(bodies, concurrently(node, index, bodies)):
            check(resp, body, live[index])
        sent += n
    assert device_events_snapshot()[0] == compiles0


def test_a_new_segment_of_a_known_bucket_compiles_nothing(nodes):
    node, _ = nodes
    index = layout_id((1, 4, False))
    body = body_of("hist", BASE, BASE + 100 * HOUR)
    before = node.search(index, body)["hits"]["total"]
    node.index_doc(index, "extra", {"@timestamp": BASE + HOUR,
                                    "request": "get html gif",
                                    "status": 200, "size": 1})
    node.refresh(index)
    sig = node.indices[index].panel_view(node._panel_pool()).signature()
    compiles0 = device_events_snapshot()[0]
    new = sig not in panels._WARM
    assert node.search(index, body)["hits"]["total"] == before + 1
    assert sig in panels._WARM
    if not new:     # same row, segment and postings buckets
        assert device_events_snapshot()[0] == compiles0
    node.delete_doc(index, "extra")
    node.refresh(index)


# -- the time-out path ------------------------------------------------------------

def _hold_first_call(monkeypatch, node, method):
    """The first call of `node.<method>` waits for the returned event."""
    release, entered = threading.Event(), threading.Event()
    real = getattr(node, method)
    first = threading.Lock()

    def held(*a, **kw):
        if first.acquire(blocking=False):
            entered.set()
            release.wait(30)
        return real(*a, **kw)
    monkeypatch.setattr(node, method, held)
    return release, entered


def _run_behind_a_held_leader(node, index, bodies, release, entered):
    out = [None] * len(bodies)

    def one(i):
        try:
            out[i] = node.search(index, json.loads(json.dumps(bodies[i])))
        except Exception as e:  # noqa: BLE001
            out[i] = e
    leader = threading.Thread(target=one, args=(0,))
    leader.start()
    assert entered.wait(30)
    rest = [threading.Thread(target=one, args=(i,))
            for i in range(1, len(bodies))]
    for t in rest:
        t.start()
    for t in rest:
        t.join(60)          # they time out and are served while it is held
    assert all(o is not None for o in out[1:])
    release.set()
    leader.join(60)
    return out


def test_timed_out_packed_followers_never_enter_the_coalesced_lane(
        nodes, monkeypatch):
    """A stalled packed leader: its followers pass QoS's follower wait,
    are declined by the batcher and answered solo by the general driver;
    none of them joins the coalesced lane."""
    node, _ = nodes
    index = layout_id((5, 4, False))
    bodies = [{"query": {"match": {"request": WORDS[i % 7]}}, "size": 10,
               "_source": True} for i in range(16)]
    want = [node.search(index, json.loads(json.dumps(b))) for b in bodies]
    monkeypatch.setattr(node.qos, "follower_wait_s", lambda: 0.05)
    release, entered = _hold_first_call(monkeypatch, node, "_packed_search")
    before, stats0 = lane_decisions_snapshot(), node._batcher.stats()
    out = _run_behind_a_held_leader(node, index, bodies, release, entered)
    after, stats = lane_decisions_snapshot(), node._batcher.stats()
    for got, ref in zip(out, want):
        assert isinstance(got, dict), got
        assert got["hits"]["total"] == ref["hits"]["total"]
        assert [h["_id"] for h in got["hits"]["hits"]] == \
            [h["_id"] for h in ref["hits"]["hits"]]
        assert [h["_source"] for h in got["hits"]["hits"]] == \
            [h["_source"] for h in ref["hits"]["hits"]]
        np.testing.assert_allclose(
            [h["_score"] for h in got["hits"]["hits"]],
            [h["_score"] for h in ref["hits"]["hits"]], rtol=1e-5)

    def moved(key):
        return after.get(key, 0) - before.get(key, 0)
    assert moved("packed:batcher_declined") == 15
    assert moved("packed:chosen") == 1
    assert moved("batched:chosen") == 0 and moved(LANE + ":chosen") == 0
    assert stats["wait_timeouts_total"] - stats0["wait_timeouts_total"] == 15
    assert stats["run_errors_total"] == stats0["run_errors_total"]


@pytest.mark.parametrize("kind", ["hist", "terms", "count"])
def test_timed_out_panels_run_their_own_program_alone(nodes, monkeypatch,
                                                      kind):
    node, live = nodes
    index = layout_id((5, 4, True))
    bodies = bodies_of(kind, 6, seed=91)
    node.search(index, bodies[0])                       # warm
    monkeypatch.setattr(node.qos, "follower_wait_s", lambda: 0.05)
    release, entered = _hold_first_call(monkeypatch, node, "_search_panels")
    before, stats0 = lane_decisions_snapshot(), node._batcher.stats()
    compiles0 = device_events_snapshot()[0]
    out = _run_behind_a_held_leader(node, index, bodies, release, entered)
    after, stats = lane_decisions_snapshot(), node._batcher.stats()
    for body, resp in zip(bodies, out):
        assert isinstance(resp, dict), resp
        check(resp, body, live[index])
    assert after.get(LANE + ":chosen", 0) \
        - before.get(LANE + ":chosen", 0) == 6
    assert after.get("batched:chosen", 0) == before.get("batched:chosen", 0)
    assert stats["wait_timeouts_total"] - stats0["wait_timeouts_total"] == 5
    assert stats["run_errors_total"] == stats0["run_errors_total"]
    assert device_events_snapshot()[0] == compiles0


def test_a_failing_panel_program_is_its_members_error(nodes, monkeypatch):
    node, _ = nodes
    index = layout_id((1, 1, False))

    def boom(*a, **kw):
        raise RuntimeError("device fell over")
    monkeypatch.setattr(panels, "execute", boom)
    chosen0 = _chosen()
    with pytest.raises(RuntimeError, match="device fell over"):
        node.search(index, body_of("hist", BASE, BASE + HOUR))
    assert set(_moved(chosen0)) <= {LANE}


# -- the packed lane's absent-term body (serving/packed_view._build_slots) ---------

def test_a_body_without_indexed_terms_takes_the_batch_floor_of_slots(nodes):
    """No term of the body is in the index: S is the batch's floor (32 for
    a batch of at most 32 rows), the shape every other solo body runs, so
    it compiles nothing alone or in a batch, and answers nothing."""
    node, _ = nodes
    index = layout_id((5, 1, False))
    warm = {"query": {"match": {"request": "images"}}, "size": 10}
    node.search(index, dict(warm))                          # Q = 1
    node.msearch([({"index": index}, dict(warm))] * 3)      # Q = 32
    absent = {"query": {"match": {"request": "zzzabsent qqqabsent"}},
              "size": 10}
    view = node.indices[index].packed_view()
    pf = view.field("request")
    from elasticsearch_tpu.serving.executor import packed_spec_of
    from elasticsearch_tpu.search.query_parser import QueryParser
    spec = packed_spec_of(QueryParser(node.indices[index].mappers), absent)
    for n, floor in ((1, 32), (3, 32), (40, 4)):
        _, S, _ = view._build_slots(pf, [spec[0]] * n, "request", 1.2, 0.75)
        assert S == floor
    compiles0 = device_events_snapshot()[0]
    resp = node.search(index, dict(absent))
    assert resp["hits"]["total"] == 0 and resp["hits"]["hits"] == []
    out = node.msearch([({"index": index}, dict(absent))] * 3)
    for item in out["responses"]:
        assert item["hits"]["total"] == 0 and item["hits"]["hits"] == []
    assert device_events_snapshot()[0] == compiles0
