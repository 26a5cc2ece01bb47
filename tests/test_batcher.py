"""Dynamic search batcher: concurrent solo requests coalesce into shared
device batches with correct per-request responses (VERDICT r3 task 2b).
"""

import pathlib
import re
import threading
import time

import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.serving import batcher as batcher_mod
from elasticsearch_tpu.serving.batcher import SearchBatcher

MAPPING = {"_doc": {"properties": {
    "body": {"type": "text"}, "n": {"type": "long"},
}}}


@pytest.fixture()
def node(tmp_path):
    n = NodeService(data_path=str(tmp_path))
    n.create_index("bt", mappings=MAPPING)
    words = ["alpha", "beta", "gamma", "delta", "epsilon"]
    for i in range(40):
        n.index_doc("bt", str(i),
                    {"body": f"{words[i % 5]} {words[(i + 1) % 5]} common",
                     "n": i})
    n.refresh("bt")
    yield n
    n.close()


class TestBatcher:
    def test_solo_request_served_with_no_batching_overhead(self, node):
        out = node.search("bt", {"query": {"match": {"body": "alpha"}}})
        assert out["hits"]["total"] == 16
        assert node._batcher.stats()["batches"] >= 1

    def test_concurrent_solo_requests_coalesce(self, node):
        words = ["alpha", "beta", "gamma", "delta", "epsilon"]
        # warm the shapes so batched execution is fast and threads overlap
        node.search("bt", {"query": {"match": {"body": "common"}}})
        results: dict[int, dict] = {}
        errs: list = []

        def one(i):
            try:
                results[i] = node.search(
                    "bt", {"query": {"match": {"body": words[i % 5]}}})
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(32)]
        before = node._batcher.stats()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        after = node._batcher.stats()
        assert not errs
        assert len(results) == 32
        # every word matches 16 docs; responses must be per-request correct
        for i, out in results.items():
            assert out["hits"]["total"] == 16, words[i % 5]
            assert all(words[i % 5] in h["_source"]["body"]
                       for h in out["hits"]["hits"])
        served = after["batched_requests"] - before["batched_requests"]
        batches = after["batches"] - before["batches"]
        assert served == 32
        assert batches < 32, "concurrent requests must share device batches"

    def test_mixed_eligibility_batches_and_falls_back(self, node):
        results: dict[int, dict] = {}

        def one(i):
            if i % 2:
                body = {"query": {"match": {"body": "common"}}}
            else:   # sort makes it packed-ineligible -> general path
                body = {"query": {"match": {"body": "common"}},
                        "sort": [{"n": "asc"}]}
            results[i] = node.search("bt", body)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, out in results.items():
            assert out["hits"]["total"] == 40
            if i % 2 == 0:
                assert out["hits"]["hits"][0]["sort"] == [0]

    def test_filtered_queries_batch_together(self, node):
        results = {}

        def one(i):
            results[i] = node.search("bt", {"query": {"bool": {
                "must": [{"match": {"body": "common"}}],
                "filter": [{"range": {"n": {"gte": i, "lte": i + 9}}}]}}})

        threads = [threading.Thread(target=one, args=(i,)) for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, out in results.items():
            assert out["hits"]["total"] == 10, i
            ids = {int(h["_id"]) for h in out["hits"]["hits"]}
            assert ids == set(range(i, i + 10))

    def test_took_of_a_batched_search_is_the_members_own(self, node):
        """Two followers coalesced into ONE batch report their own `took`
        (ISSUE 24): from their own arrival, not the leader's, and not
        each other's."""
        body = {"query": {"match": {"body": "common"}}}
        node.search("bt", body)                      # warm the shapes
        leader_in, release = threading.Event(), threading.Event()
        real = node._packed_search
        batch_sizes = []

        def held(name, bodies, **kw):
            batch_sizes.append(len(bodies))
            if len(batch_sizes) == 1:                # the leader's own batch
                leader_in.set()
                assert release.wait(10)
            return real(name, bodies, **kw)

        node._packed_search = held
        results: dict[str, tuple] = {}

        def one(tag):
            t = time.perf_counter()
            out = node.search("bt", body)
            results[tag] = (out["took"], (time.perf_counter() - t) * 1000)

        follows0 = tracing.AGGREGATE.stats().get(
            "batcher.follow", {"total": 0})["total"]
        leader = threading.Thread(target=one, args=("leader",))
        leader.start()
        assert leader_in.wait(10)
        early = threading.Thread(target=one, args=("early",))
        early.start()
        time.sleep(0.25)                             # arrivals differ
        late = threading.Thread(target=one, args=("late",))
        late.start()
        time.sleep(0.05)                             # both are queued
        release.set()
        for t in (leader, early, late):
            t.join(10)
            assert not t.is_alive()
        assert batch_sizes == [1, 2], "the followers must share one batch"
        for tag, (took, client_ms) in results.items():
            assert 0 <= took <= client_ms, (tag, took, client_ms)
        assert results["early"][0] - results["late"][0] >= 200
        # each follower waited in a `batcher.follow` span
        assert tracing.AGGREGATE.stats()["batcher.follow"]["total"] \
            == follows0 + 2


# ---------------------------------------------------------------------------
# the batcher alone: plain callables and a stub qos, no node (ISSUE 29)
# ---------------------------------------------------------------------------


class _StubQos:
    def __init__(self, wait_s, window):
        self.wait_s, self.window = wait_s, window

    def batch_window(self, base):
        return base if self.window is None else self.window

    def follower_wait_s(self):
        return self.wait_s


class _StubMetrics:
    def __init__(self):
        self.recorded = []

    def record(self, name, ms):
        self.recorded.append(name)


def batcher_alone(wait_s=5.0, window=None) -> SearchBatcher:
    return SearchBatcher(_StubQos(wait_s, window), _StubMetrics())


def served(items, t_taken):
    """A lane's batch runner: one answer an item, in their order."""
    return [{"served": x} for x in items]


def queued(b: SearchBatcher, key: tuple, n: int) -> bool:
    """Wait until `n` entries are queued under `key`."""
    deadline = time.time() + 5
    while time.time() < deadline:
        with b._lock:
            if len(b._queues.get(key, [])) == n:
                return True
        time.sleep(0.005)
    return False


def _followers(b, key, items, run, lead=None):
    """One thread an item, started in order, each queued before the next
    starts. -> (threads, {item: its `coalesce` return or exception})."""
    got = {}

    def one(item):
        try:
            got[item] = b.coalesce(key, item, run, lead=lead)
        except Exception as e:  # noqa: BLE001 — the test reads it
            got[item] = e

    threads = []
    for n, item in enumerate(items, 1):
        th = threading.Thread(target=one, args=(item,))
        th.start()
        threads.append(th)
        assert queued(b, key, n)
    return threads, got


class TestCoalesceAlone:
    KEY = ("lane", "k")

    def test_leader_without_lead_is_a_member_of_its_first_batch(self):
        b = batcher_alone()
        batches = []

        def run(items, t_taken):
            batches.append(list(items))
            return served(items, t_taken)

        assert b.coalesce(self.KEY, "a", run) == ({"served": "a"}, True)
        assert batches == [["a"]]
        # the member booked its own queue wait (a leader's: about none)
        assert b.metrics.recorded == ["batcher.queue_wait"]
        assert b.stats()["occupancy"] == {1: 1}
        assert not b._busy and not b._queues

    def test_leader_with_lead_answers_itself_and_holds_no_entry(self):
        b = batcher_alone()
        seen = {}

        def lead():
            seen["busy"] = self.KEY in b._busy
            seen["queued"] = list(b._queues.get(self.KEY, []))
            return "led"

        waits0 = tracing.AGGREGATE.stats().get(
            "batcher.queue_wait", {"total": 0})["total"]
        out = b.coalesce(self.KEY, "a", served, lead=lead)
        assert out == ("led", False)
        assert seen == {"busy": True, "queued": []}
        assert b.metrics.recorded == []
        assert tracing.AGGREGATE.stats().get(
            "batcher.queue_wait", {"total": 0})["total"] == waits0
        assert b.stats()["batches"] == 0
        assert not b._busy and not b._queues

    @pytest.mark.parametrize("window, sizes", [(None, [5]), (3, [3, 2]),
                                               (2, [2, 2, 1])])
    def test_long_queue_served_in_arrival_order_a_window_at_a_time(
            self, window, sizes):
        b = batcher_alone(window=window)
        batches, got = [], {}

        def run(items, t_taken):
            batches.append(list(items))
            return served(items, t_taken)

        def lead():
            got["threads"], got["outs"] = _followers(
                b, self.KEY, ["f1", "f2", "f3", "f4", "f5"], run)
            return "led"

        assert b.coalesce(self.KEY, "a", run, lead=lead) == ("led", False)
        for th in got["threads"]:
            th.join(5)
        assert [len(x) for x in batches] == sizes
        assert sum(batches, []) == ["f1", "f2", "f3", "f4", "f5"]
        assert got["outs"] == {f: ({"served": f}, True)
                               for f in ("f1", "f2", "f3", "f4", "f5")}
        st = b.stats()
        assert (st["batches"], st["batched_requests"]) == (len(sizes), 5)
        assert b.metrics.recorded == ["batcher.queue_wait"] * 5

    def test_run_returning_none_is_none_for_every_member(self):
        b = batcher_alone()
        got = {}

        def lead():
            got["threads"], got["outs"] = _followers(
                b, self.KEY, ["f1", "f2"], lambda items, t: None)
            return "led"

        b.coalesce(self.KEY, "a", lambda items, t: None, lead=lead)
        for th in got["threads"]:
            th.join(5)
        assert got["outs"] == {"f1": (None, False), "f2": (None, False)}
        # a batch that ran is a batch served, whatever it answered
        assert b.stats()["occupancy"] == {2: 1}
        # ... and the packed lane's own leader reads the same
        assert b.coalesce(self.KEY, "a", lambda items, t: None) \
            == (None, False)

    def test_follower_without_an_answer_answers_itself_with_lead(self):
        b = batcher_alone()
        got = {}

        def lead():
            got["threads"], got["outs"] = _followers(
                b, self.KEY, ["f1"], lambda items, t: None,
                lead=lambda: "alone")
            return "led"

        b.coalesce(self.KEY, "a", lambda items, t: None, lead=lead)
        got["threads"][0].join(5)
        assert got["outs"] == {"f1": ("alone", False)}

    @pytest.mark.parametrize("drains", [True, False],
                             ids=["drained", "released"])
    def test_lead_raising_still_drains_or_releases_the_followers(
            self, drains):
        """Followers queued when `lead()` raises are served by the drain
        in the batcher's own `finally`; one that queues after the drain's
        last look is released (stranded, counted), never left waiting."""
        b = batcher_alone()
        got = {}

        def lead():
            if drains:
                got["threads"], got["outs"] = _followers(
                    b, self.KEY, ["f1", "f2"], served)
            raise RuntimeError("the leader's own search failed")

        if not drains:
            real = b._release

            def late_then_release(key):   # after the drain's last look
                got["threads"], got["outs"] = _followers(
                    b, self.KEY, ["f1", "f2"], served)
                real(key)
            b._release = late_then_release

        with pytest.raises(RuntimeError, match="own search failed"):
            b.coalesce(self.KEY, "a", served, lead=lead)
        for th in got["threads"]:
            th.join(5)
            assert not th.is_alive()
        st = b.stats()
        if drains:
            assert got["outs"] == {"f1": ({"served": "f1"}, True),
                                   "f2": ({"served": "f2"}, True)}
            assert (st["occupancy"], st["stranded_total"]) == ({2: 1}, 0)
        else:
            assert got["outs"] == {"f1": (None, False), "f2": (None, False)}
            assert (st["batches"], st["stranded_total"]) == (0, 2)
        assert st["run_errors_total"] == 0     # `lead` is not a batch
        assert not b._busy and not b._queues

    @pytest.mark.parametrize("answered", [True, False],
                             ids=["served", "timed-out"])
    def test_a_served_follower_books_its_wake_up(self, monkeypatch,
                                                 answered):
        monkeypatch.setattr(tracing, "AGGREGATE", tracing.SpanAggregate())
        b = batcher_alone(wait_s=5.0 if answered else 0.05)
        got = {}

        def lead():
            got["threads"], got["outs"] = _followers(
                b, self.KEY, ["f1"], served)
            if not answered:            # until the follower gave up
                deadline = time.time() + 5
                while b.stats()["wait_timeouts_total"] == 0 \
                        and time.time() < deadline:
                    time.sleep(0.005)
            return "led"

        b.coalesce(self.KEY, "a", served, lead=lead)
        got["threads"][0].join(5)
        assert not got["threads"][0].is_alive()
        rows = tracing.AGGREGATE.stats()
        assert rows["batcher.follow"]["total"] == 1
        if answered:
            assert got["outs"] == {"f1": ({"served": "f1"}, True)}
            wake = rows["batcher.wake"]
            assert wake["total"] == 1
            assert 0 <= wake["seconds_total"] \
                <= rows["batcher.follow"]["seconds_total"]
        else:
            assert got["outs"] == {"f1": (None, False)}
            assert "batcher.wake" not in rows

    def test_run_is_given_the_instant_the_batch_left_the_queue(self):
        b = batcher_alone()
        taken = []
        t0 = tracing.now_ns()
        b.coalesce(self.KEY, "a", lambda items, t: taken.append(t) or None)
        assert t0 <= taken[0] <= tracing.now_ns()

    def test_stats_keys(self):
        assert list(batcher_alone().stats()) == [
            "batches", "batched_requests", "stranded_total",
            "wait_timeouts_total", "run_errors_total", "last_error",
            "occupancy"]

    def test_the_module_knows_no_node_and_no_lane(self):
        """The batcher is below the node: it is handed its work, through
        one entry point."""
        src = pathlib.Path(batcher_mod.__file__).read_text()
        assert not re.search(
            r"\bnode\b\s*[.=)]|\.node\b|_packed_search|_search_panels"
            r"|_search_batched", src)
        assert not re.search(r"^\s*(from|import)\s+\S*(node|search)\b",
                             src, re.M)
        assert not hasattr(batcher_alone(), "node")
        assert {n for n in vars(SearchBatcher) if not n.startswith("_")} \
            == {"MAX_BATCH", "coalesce", "stats"}
        # no sentinel for a caller to compare against, no second protocol
        assert [n for n in vars(batcher_mod) if n.isupper()] == []
