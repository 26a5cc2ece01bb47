"""Serving-QoS subsystem (ISSUE 9): coalesced cross-request batching
parity, admission control + load shedding (429, never 5xx), hedged
replica reads, transport traffic classes, and the observability plumbing.

Contract pins:
  * follower-served coalesced batches are BITWISE-identical to solo
    execution across the query-shape matrix (the `_search_batched`
    replica-axis executor is the seam);
  * overload sheds as 429 + Retry-After — at the QoS admission gate
    (class budgets, EWMA pressure with a fake clock) and at the bounded
    search pool (EsRejectedExecutionException at the REST boundary);
  * a slow replica's query hedges onto another copy, completes under the
    injected delay, and the loser's cancellation is observed;
  * saturating the bulk transport class leaves a reg-class round-trip
    under deadline (per-class connection budgets, NettyTransport's five
    connection types);
  * batcher anomalies (stranded followers, wait timeouts, swallowed run
    errors) are counted, and the qos/hedge/transport-class registries
    ride `/_metrics` + the sampler ring with correct exposition types.
"""

import json
import threading
import time

import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.serving.qos import (Ewma, QosController,
                                           QosShedException, hedge_snapshot)
from tests.test_batcher import batcher_alone, queued, served

WORDS = ["quick", "brown", "fox", "jumps", "lazy", "dog", "sleeps",
         "swift", "river", "stone"]

MAPPING = {"_doc": {"properties": {
    "body": {"type": "string"},
    "tag": {"type": "string", "index": "not_analyzed"},
    "n": {"type": "long"},
    "price": {"type": "double"}}}}

# the query-shape matrix (tests/test_mesh.py's 19 shapes): every shape the
# coalesced general lane may batch must serve followers bitwise-identically
QUERY_SHAPES = [
    {"match_all": {}},
    {"bool": {"should": [{"match": {"body": "fox"}},
                         {"match": {"body": "dog"}}]}},
    {"bool": {"should": [{"match": {"body": "quick"}}],
              "filter": [{"range": {"n": {"gte": 2, "lt": 60}}}]}},
    {"term": {"tag": "t1"}},
    {"terms": {"tag": ["t0", "t2"]}},
    {"term": {"n": 4}},
    {"term": {"price": 6.5}},
    {"range": {"n": {"gt": 30}}},
    {"range": {"price": {"gte": 2.0, "lt": 50.0}}},
    {"range": {"tag": {"gte": "t0", "lte": "t1"}}},
    {"exists": {"field": "price"}},
    {"exists": {"field": "body"}},
    {"ids": {"values": ["1", "5", "8", "77"]}},
    {"ids": {"values": ["zzz-absent"]}},
    {"constant_score": {"filter": {"term": {"tag": "t1"}}, "boost": 2.5}},
    {"dis_max": {"queries": [{"match": {"body": "fox"}},
                             {"match": {"body": "dog"}}],
                 "tie_breaker": 0.4}},
    {"bool": {"must": [{"match": {"body": "fox"}}],
              "must_not": [{"term": {"tag": "t2"}}],
              "should": [{"match": {"body": "brown"}}]}},
    {"bool": {"should": [{"match": {"body": {"query": "fox brown",
                                             "operator": "and"}}}]}},
    {"bool": {"should": [{"match": {"body": "quick"}},
                         {"match": {"body": "river"}}],
              "minimum_should_match": 2}},
]


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    n = NodeService(str(tmp_path_factory.mktemp("qos")))
    n.create_index("q", settings={"number_of_shards": 4},
                   mappings=MAPPING)
    di = 0
    for _ in range(3):
        for _ in range(16):
            doc = {"body": f"{WORDS[di % 10]} {WORDS[(di * 3 + 1) % 10]} "
                           f"{WORDS[(di * 7 + 2) % 10]}",
                   "tag": f"t{di % 3}", "n": di}
            if di % 2 == 0:
                doc["price"] = di / 2.0
            n.index_doc("q", str(di), doc)
            di += 1
        n.refresh("q")
    yield n
    n.close()


def _strip_took(resp: dict) -> dict:
    out = json.loads(json.dumps(resp))
    out.pop("took", None)
    return out


def _search(n, body):
    return n.search("q", json.loads(json.dumps(body)))


# ---------------------------------------------------------------------------
# 1. coalesced cross-request batching: bitwise parity with solo execution
# ---------------------------------------------------------------------------


class TestCoalescedBatchParity:
    @pytest.mark.parametrize("q", QUERY_SHAPES,
                             ids=[json.dumps(q)[:48] for q in QUERY_SHAPES])
    def test_batched_rows_bitwise_identical_to_solo(self, node, q):
        """Every matrix shape served through the coalesced lane's executor
        (Q=2 batch) must match its solo execution byte for byte."""
        body = {"size": 10, "query": q, "_source": False}
        solo = _strip_took(_search(node, body))
        outs = node._search_batched([("q", json.loads(json.dumps(body))),
                                     ("q", json.loads(json.dumps(body)))])
        assert len(outs) == 2
        for row in outs:
            assert _strip_took(row) == solo, q

    def test_followers_ride_one_batch_and_match_solo(self, node):
        """End-to-end through the lane: a held leader accumulates
        followers; drain serves them as ONE Q>1 batch whose responses are
        bitwise-identical to their solo responses."""
        bodies = [{"size": 10, "query": {"match": {"body": w}},
                   "_source": True, "from": 0}
                  for w in ("quick", "river", "stone", "lazy")]
        # packed-ineligible twist (so the packed lane can't intercept):
        # _source: True bodies with a bool wrapper share one plan shape
        bodies = [{"size": 10, "_source": True,
                   "query": {"bool": {"should": [{"match": {"body": w}}],
                                      "filter": [{"range": {
                                          "n": {"gte": 0}}}]}}}
                  for w in ("quick", "river", "stone", "lazy")]
        solos = [_strip_took(_search(node, b)) for b in bodies]
        keys = [node._msearch_batch_key("q", b) for b in bodies]
        assert all(k is not None and k == keys[0] for k in keys), \
            "same-shape bodies must share one coalescing group"

        # this thread leads (its own answer is beside the point) until the
        # three searches have queued behind it, then drains them
        key = ("gen", *keys[0])
        results: dict[int, dict] = {}
        threads = [threading.Thread(
            target=lambda i=i: results.__setitem__(
                i, _search(node, bodies[i])))
            for i in range(1, 4)]
        before = node._batcher.stats()

        def lead():
            for t in threads:
                t.start()
            deadline = time.time() + 5
            while time.time() < deadline:
                with node._batcher._lock:
                    qd = len(node._batcher._queues.get(key, []))
                if qd == 3:
                    break
                time.sleep(0.01)
            return qd

        qd, shared = node._batcher.coalesce(
            key, bodies[0],
            lambda items, _t: node._search_batched(
                [("q", b) for b in items]), lead=lead)
        assert (qd, shared) == (3, False), \
            "followers did not queue behind the leader"
        for t in threads:
            t.join()
        after = node._batcher.stats()
        assert after["batches"] == before["batches"] + 1, \
            "three followers must share ONE device batch"
        assert after["batched_requests"] == before["batched_requests"] + 3
        for i in range(1, 4):
            assert _strip_took(results[i]) == solos[i], bodies[i]

    def test_solo_path_unchanged_when_lane_disabled(self, node):
        body = {"size": 10, "query": {"term": {"tag": "t1"}}}
        on = _strip_took(_search(node, body))
        node.settings._map["node.search.qos.enable"] = False
        try:
            off = _strip_took(_search(node, body))
        finally:
            node.settings._map.pop("node.search.qos.enable", None)
        assert on == off


# ---------------------------------------------------------------------------
# 2. admission control + load shedding
# ---------------------------------------------------------------------------


class TestAdmissionControl:
    def _controller(self, overrides=None, clock=None):
        s = Settings({"node.search.qos.max_inflight": 10,
                      **(overrides or {})})
        return QosController(s, clock=clock or (lambda: 0.0))

    def test_ewma_latency_pressure_sheds_search(self):
        """Fake-clock EWMA: sustained device latency above the shed
        ceiling drives pressure to 1.0 and search admission sheds with a
        Retry-After hint; control-plane classes stay admitted."""
        t = [0.0]
        qos = self._controller({"node.search.qos.shed_latency_ms": 1000},
                               clock=lambda: t[0])
        for _ in range(8):
            t[0] += 1.0
            qos.record_latency(2000.0)     # way past the 1000 ms ceiling
        assert qos.latency_frac() == 1.0
        assert qos.pressure() >= 1.0
        with pytest.raises(QosShedException) as ei:
            qos.admit("search")
        assert ei.value.retry_after_s >= 1.0
        assert qos.class_stats()["search"]["shed_total"] == 1
        # state/ping are never shed — a cluster must keep its heartbeats
        with qos.admit("state"):
            pass
        with qos.admit("ping"):
            pass

    def test_stale_latency_signal_decays_and_unsheds(self):
        """Shed livelock tripwire (ISSUE 12 satellite): one compile-heavy
        request spikes the EWMA past the ceiling; because shed requests
        never execute, no new sample can arrive — the stale signal must
        DECAY with idle time so probe traffic gets admitted again."""
        t = [0.0]
        qos = self._controller({"node.search.qos.shed_latency_ms": 1000},
                               clock=lambda: t[0])
        qos.record_latency(30_000.0)       # one 30s compile+train query
        assert qos.latency_frac() == 1.0
        with pytest.raises(QosShedException):
            qos.admit("search")
        t[0] += 120.0                      # two minutes idle: 4 half-lives
        assert qos.latency_frac() < 0.1
        with qos.admit("search"):          # admitted: signal re-measures
            pass
        # <=0 half-life restores the undecayed (pre-fix) signal
        qos2 = self._controller(
            {"node.search.qos.shed_latency_ms": 1000,
             "node.search.qos.latency_halflife_s": 0},
            clock=lambda: t[0])
        qos2.record_latency(30_000.0)
        t[0] += 600.0
        assert qos2.latency_frac() == 1.0

    def test_degrade_band_shrinks_batch_window_before_shedding(self):
        t = [0.0]
        qos = self._controller({"node.search.qos.shed_latency_ms": 1000,
                                "node.search.qos.degrade_threshold": 0.5,
                                "node.search.qos.shed_threshold": 0.95},
                               clock=lambda: t[0])
        for _ in range(8):
            qos.record_latency(700.0)      # ~0.7 of the ceiling: degrade
        with qos.admit("search"):          # admitted, but degraded
            pass
        assert qos.degraded
        assert qos.batch_window(32) < 32
        assert qos.follower_wait_s() <= 30.0
        # healthy latencies recover the full window
        qos2 = self._controller({"node.search.qos.shed_latency_ms": 1000})
        qos2.record_latency(5.0)
        with qos2.admit("search"):
            pass
        assert not qos2.degraded
        assert qos2.batch_window(32) == 32

    def test_class_budget_isolation(self):
        """Saturating the bulk class budget sheds BULK, not search."""
        qos = self._controller({"node.search.qos.bulk.share": 0.2})
        holds = [qos.admit("bulk"), qos.admit("bulk")]   # 2 = 10 * 0.2
        with pytest.raises(QosShedException):
            qos.admit("bulk")
        with qos.admit("search"):          # search budget untouched
            pass
        for h in holds:
            h.__exit__(None, None, None)
        with qos.admit("bulk"):            # slots released -> admitted
            pass

    def test_http_shed_is_429_with_retry_after_never_5xx(self, tmp_path):
        """The REST boundary: a shed search is 429 + Retry-After (the
        client-visible backpressure signal), and flipping the budget back
        restores 200 — no 5xx anywhere."""
        import urllib.error
        import urllib.request
        from elasticsearch_tpu.rest import HttpServer
        n = NodeService(str(tmp_path / "shed"))
        n.create_index("s", mappings={"_doc": {"properties": {
            "body": {"type": "string"}}}})
        n.index_doc("s", "1", {"body": "hello world"})
        n.refresh("s")
        srv = HttpServer(n, port=0).start()
        base = f"http://127.0.0.1:{srv.port}"
        body = json.dumps({"query": {"match": {"body": "hello"}}}).encode()

        def post():
            req = urllib.request.Request(base + "/s/_search", data=body,
                                         method="POST")
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read()), dict(r.headers)
        try:
            status, _, _ = post()
            assert status == 200
            n.settings._map["node.search.qos.search.share"] = 0   # 0 slots
            with pytest.raises(urllib.error.HTTPError) as ei:
                post()
            assert ei.value.code == 429
            assert int(ei.value.headers["Retry-After"]) >= 1
            payload = json.loads(ei.value.read())
            assert payload["status"] == 429
            assert "QosShed" in payload["error"]
            assert n.qos.class_stats()["search"]["shed_total"] >= 1
            n.settings._map.pop("node.search.qos.search.share")
            status, _, _ = post()          # recovery: back to 200
            assert status == 200
        finally:
            srv.stop()
            n.close()

    def test_search_pool_rejection_maps_to_429_with_retry_after(
            self, tmp_path):
        """ISSUE 9 satellite: bounded-pool overflow
        (EsRejectedExecutionException) surfaces at the REST boundary as
        EXACTLY 429 + Retry-After, not a raise/5xx."""
        import urllib.error
        import urllib.request
        from elasticsearch_tpu.rest import HttpServer
        # QoS admission off: the point is the POOL's rejection path (the
        # admission gate would otherwise shed first on queue pressure)
        n = NodeService(str(tmp_path / "rej"),
                        settings=Settings({
                            "node.search.qos.enable": False,
                            "threadpool.search.size": 1,
                            "threadpool.search.queue_size": 1}))
        n.create_index("s", mappings={"_doc": {"properties": {
            "body": {"type": "string"}}}})
        n.index_doc("s", "1", {"body": "hello"})
        n.refresh("s")
        srv = HttpServer(n, port=0).start()
        base = f"http://127.0.0.1:{srv.port}"
        release = threading.Event()
        started = threading.Event()

        def plug():
            started.set()
            release.wait(10)
        try:
            pool = n.thread_pool.pools["search"]
            assert pool.size == 1 and pool.queue_size == 1
            pool.execute(plug)             # occupies the single worker
            assert started.wait(5)
            pool.execute(lambda: None)     # fills the queue of 1
            body = json.dumps({"query": {"match_all": {}}}).encode()
            req = urllib.request.Request(base + "/s/_search", data=body,
                                         method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 429
            assert "Retry-After" in ei.value.headers
            assert json.loads(ei.value.read())["status"] == 429
            assert pool.rejected >= 1
        finally:
            release.set()
            srv.stop()
            n.close()


# ---------------------------------------------------------------------------
# 3. hedged replica reads
# ---------------------------------------------------------------------------


@pytest.fixture()
def cluster2(tmp_path):
    from elasticsearch_tpu.cluster import TestCluster
    c = TestCluster(2, str(tmp_path))
    yield c
    c.close()


A_QUERY = "indices:data/read/search[phase/query]"


class TestHedgedReads:
    def _prime(self, cluster):
        client = cluster.client()
        client.create_index("h", {"number_of_shards": 1,
                                  "number_of_replicas": 1})
        cluster.ensure_green()
        for i in range(20):
            client.index_doc("h", str(i),
                             {"body": f"{WORDS[i % 10]} common"})
        client.refresh("h")
        # warm BOTH copies' latency EWMAs (round-robin alternates them)
        for _ in range(6):
            client.search("h", {"query": {"match": {"body": "common"}}})
        return client

    def test_hedge_beats_injected_slow_replica(self, cluster2):
        client = self._prime(cluster2)
        client.hedge_settings["cluster.search.hedge.min_ms"] = 30
        state = client.cluster.current()
        copies = state.started_copies("h", 0)
        assert len(copies) == 2
        rr = client._read_rr.get(("h", 0), 0)
        slow = copies[rr % len(copies)]["node"]   # the NEXT serving copy
        before = dict(client.hedge_stats)
        base = hedge_snapshot()
        cluster2.network.add_delay(slow, A_QUERY, 1.5)
        try:
            t0 = time.perf_counter()
            out = client.search("h", {"query": {"match": {"body":
                                                          "common"}}})
            took = time.perf_counter() - t0
        finally:
            cluster2.network.clear_delay(slow, A_QUERY)
        assert out["hits"]["total"] == 20
        assert took < 1.2, \
            f"hedged query must complete under the healthy copy's " \
            f"latency, took {took:.2f}s against a 1.5s-slow copy"
        assert client.hedge_stats["fired"] == before["fired"] + 1
        assert client.hedge_stats["win_backup"] == \
            before["win_backup"] + 1
        # the loser (the delayed copy) eventually answers and its
        # cancellation is OBSERVED, not silently leaked
        deadline = time.time() + 5
        while time.time() < deadline \
                and client.hedge_stats["canceled"] <= before["canceled"]:
            time.sleep(0.05)
        assert client.hedge_stats["canceled"] == before["canceled"] + 1
        snap = hedge_snapshot()
        assert snap["fired"] >= base["fired"] + 1
        assert snap["win_backup"] >= base["win_backup"] + 1

    def test_hedge_disabled_setting_means_no_hedge(self, cluster2):
        client = self._prime(cluster2)
        client.hedge_settings["cluster.search.hedge.enable"] = False
        client.hedge_settings["cluster.search.hedge.min_ms"] = 30
        state = client.cluster.current()
        copies = state.started_copies("h", 0)
        rr = client._read_rr.get(("h", 0), 0)
        slow = copies[rr % len(copies)]["node"]
        before = dict(client.hedge_stats)
        cluster2.network.add_delay(slow, A_QUERY, 0.4)
        try:
            t0 = time.perf_counter()
            out = client.search("h", {"query": {"match": {"body":
                                                          "common"}}})
            took = time.perf_counter() - t0
        finally:
            cluster2.network.clear_delay(slow, A_QUERY)
        assert out["hits"]["total"] == 20
        assert took >= 0.4                  # ate the full delay: no hedge
        assert client.hedge_stats == before

    def test_hedge_span_parents_under_query_span(self, cluster2):
        client = self._prime(cluster2)
        client.hedge_settings["cluster.search.hedge.min_ms"] = 30
        state = client.cluster.current()
        copies = state.started_copies("h", 0)
        rr = client._read_rr.get(("h", 0), 0)
        slow = copies[rr % len(copies)]["node"]
        cluster2.network.add_delay(slow, A_QUERY, 1.0)
        try:
            with client.tracer.request("POST /h/_search", force=True):
                client.search("h", {"query": {"match": {"body":
                                                        "common"}}})
        finally:
            cluster2.network.clear_delay(slow, A_QUERY)
        from elasticsearch_tpu.common.tracing import span_tree
        traces = client.tracer.list()
        assert traces
        tree = span_tree(
            client.tracer.get(traces[0]["trace_id"]))["tree"]

        def find(node, name):
            if node["name"] == name:
                return node
            for ch in node.get("children", []):
                got = find(ch, name)
                if got is not None:
                    return got
            return None
        query = find(tree, "query")
        assert query is not None, "coordinator query span missing"
        hedge = find(query, "hedge")
        assert hedge is not None, "hedge span must sit under query"
        assert hedge["attributes"]["backup"] != slow


# ---------------------------------------------------------------------------
# 4. transport traffic classes
# ---------------------------------------------------------------------------


class TestTrafficClasses:
    def test_class_of_action_mapping(self):
        from elasticsearch_tpu.cluster.transport import class_of_action
        assert class_of_action(
            "internal:index/shard/recovery/chunk") == "recovery"
        assert class_of_action("indices:data/write/op[p]") == "bulk"
        assert class_of_action("indices:data/write/op[r]") == "bulk"
        assert class_of_action(
            "internal:discovery/zen/fd/ping") == "ping"
        assert class_of_action("internal:cluster/shard/started") == "state"
        assert class_of_action("indices:admin/create") == "state"
        assert class_of_action(
            "indices:data/read/search[phase/query]") == "reg"
        assert class_of_action("indices:data/read/get") == "reg"

    def test_bulk_saturation_leaves_reg_class_under_deadline(self):
        """NettyTransport.java:180-184's point: the bulk class's 3
        connections saturate and queue, while a reg-class (query)
        round-trip on the SAME node pair completes immediately."""
        from elasticsearch_tpu.cluster import (LocalTransport,
                                               TransportService)
        net = LocalTransport()
        a = TransportService("a", net)
        b = TransportService("b", net)
        b.register_handler("indices:data/write/op[p]",
                           lambda frm, req: "ok")
        b.register_handler("indices:data/read/search[phase/query]",
                           lambda frm, req: {"hits": 1})
        net.add_delay("b", "indices:data/write", 0.4)
        done = []
        threads = [threading.Thread(
            target=lambda: done.append(
                a.send("b", "indices:data/write/op[p]", {})))
            for _ in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.1)                 # 3 in flight, 3 queued
        st = net.class_stats()
        assert st["bulk"]["queue_depth"] >= 1, \
            "bulk sends past the connection budget must queue"
        t0 = time.perf_counter()
        out = a.send("b", "indices:data/read/search[phase/query]", {})
        took = time.perf_counter() - t0
        assert out == {"hits": 1}
        assert took < 0.3, \
            f"reg-class round-trip must not queue behind bulk ({took:.2f}s)"
        for t in threads:
            t.join()
        assert len(done) == 6           # saturation delayed, never dropped
        st = net.class_stats()
        assert st["bulk"]["max_queue_depth"] >= 2
        assert st["bulk"]["sent_total"] >= 6
        assert st["reg"]["sent_total"] >= 1
        assert st["bulk"]["queue_depth"] == 0   # drained clean

    def test_nested_same_pair_sends_reenter_held_connection(self):
        """state class has ONE connection; a handler that sends another
        state-class message to the same pair must re-enter, not deadlock."""
        from elasticsearch_tpu.cluster import (LocalTransport,
                                               TransportService)
        net = LocalTransport()
        a = TransportService("a", net)

        def outer(frm, req):
            if req.get("depth", 0) < 2:
                return a.send("a", "internal:cluster/nested",
                              {"depth": req.get("depth", 0) + 1})
            return "bottom"
        a.register_handler("internal:cluster/nested", outer)
        assert a.send("a", "internal:cluster/nested", {}) == "bottom"


# ---------------------------------------------------------------------------
# 5. batcher anomaly accounting (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


class TestBatcherAccounting:
    """The anomaly counters, on the batcher alone (`batcher_alone`,
    tests/test_batcher.py: plain callables, a stub qos, no node)."""

    def test_follower_wait_timeout_counted_and_falls_back(self):
        b = batcher_alone(wait_s=0.05)
        got = []

        def lead():
            th = threading.Thread(target=lambda: got.append(
                b.coalesce(("k",), {"q": 1}, served)))
            th.start()
            th.join(5)          # the leader does not drain: it times out
            return "led"

        assert b.coalesce(("k",), {"q": 0}, served, lead=lead) \
            == ("led", False)
        assert got == [(None, False)], \
            "a timed-out follower gets no answer from the batcher"
        assert b.stats()["wait_timeouts_total"] == 1
        # the abandoned entry did not spend a row of the leader's drain
        assert b.stats()["batches"] == 0

    def test_stranded_followers_counted_and_released(self):
        b = batcher_alone(wait_s=5.0)
        got = []

        def lead():
            th = threading.Thread(target=lambda: got.append(
                b.coalesce(("k",), {"q": 1}, served)))
            th.start()
            assert queued(b, ("k",), 1)
            # the leader exits WITHOUT draining (the leftover path): the
            # follower is released to serve itself and counted as stranded
            b._release(("k",))
            th.join(5)
            return "led"

        b.coalesce(("k",), {"q": 0}, served, lead=lead)
        assert got == [(None, False)]
        assert b.stats()["stranded_total"] == 1

    def test_run_error_recorded_not_discarded(self):
        b = batcher_alone(wait_s=5.0)
        got = []

        def boom(items, t_taken):
            raise RuntimeError("device fell over")

        def follower():
            try:
                got.append(b.coalesce(("k",), {"q": 1}, boom))
            except RuntimeError as e:
                got.append(e)
        th = threading.Thread(target=follower)

        def lead():
            th.start()
            assert queued(b, ("k",), 1)

        b.coalesce(("k",), {"q": 0}, boom, lead=lead)
        th.join(5)
        assert not th.is_alive()
        # a failing batch is its members' error, never a slower lane
        assert len(got) == 1 and isinstance(got[0], RuntimeError)
        st = b.stats()
        assert st["run_errors_total"] == 1
        assert "device fell over" in st["last_error"]


# ---------------------------------------------------------------------------
# 6. observability plumbing: /_metrics exposition, sampler ring
# ---------------------------------------------------------------------------


class TestQosObservability:
    def test_qos_families_exposed_with_correct_types(self, node):
        from elasticsearch_tpu.common.metrics import render_openmetrics
        from tests.test_metrics_exposition import parse_openmetrics
        _search(node, {"query": {"match": {"body": "quick"}}})
        families = parse_openmetrics(
            render_openmetrics(node.metric_sections()))
        for fam, mtype in (("es_qos_shed_total", "counter"),
                           ("es_qos_admitted_total", "counter"),
                           ("es_qos_inflight", "gauge"),
                           ("es_qos_node_pressure", "gauge"),
                           ("es_qos_node_ewma_latency_ms", "gauge"),
                           ("es_qos_node_degraded_total", "counter"),
                           ("es_search_hedged_total", "counter"),
                           ("es_search_batcher_stranded_total", "counter"),
                           ("es_search_batcher_wait_timeouts_total",
                            "counter"),
                           ("es_search_batcher_run_errors_total",
                            "counter")):
            assert fam in families, fam
            assert families[fam]["type"] == mtype, fam
        classes = {lb["class"] for lb, _ in
                   families["es_qos_shed_total"]["samples"]}
        assert classes == {"search", "bulk", "recovery", "state", "ping"}
        outcomes = {lb["outcome"] for lb, _ in
                    families["es_search_hedged_total"]["samples"]}
        assert {"fired", "win_backup", "win_primary",
                "canceled"} <= outcomes

    def test_transport_class_families_exposed(self, cluster2):
        from elasticsearch_tpu.common.metrics import render_openmetrics
        from tests.test_metrics_exposition import parse_openmetrics
        n = cluster2.client()
        families = parse_openmetrics(
            render_openmetrics(n.metric_sections(), node=n.node_id))
        assert families["es_transport_class_queue_depth"]["type"] == "gauge"
        assert families["es_transport_class_sent_total"]["type"] \
            == "counter"
        classes = {lb["class"] for lb, _ in
                   families["es_transport_class_queue_depth"]["samples"]}
        # "dcn" is the sixth class (ISSUE 19): cross-host latency traffic
        assert classes == {"recovery", "bulk", "reg", "state", "ping",
                           "dcn"}

    def test_sampler_ring_gains_qos_gauges(self, node):
        snap = node._sampler_snapshot()
        for key in ("qos_pressure", "qos_queue_depth", "qos_shed_rate_1m",
                    "qos_shed_total", "qos_degraded", "hedge_rate_1m",
                    "hedged_fired_total", "batcher_stranded_total",
                    "batcher_wait_timeouts_total"):
            assert key in snap, key

    def test_ewma_deadline_tracks_tail(self):
        e = Ewma()
        for _ in range(50):
            e.observe(10.0)
        assert 9.0 < e.value < 11.0
        assert e.deadline_ms() < 30.0       # tight latencies, tight deadline
        e2 = Ewma()
        for v in (10.0, 200.0, 10.0, 300.0, 15.0, 250.0):
            e2.observe(v)
        assert e2.deadline_ms() > e2.value  # jitter widens the deadline
