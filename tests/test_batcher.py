"""Dynamic search batcher: concurrent solo requests coalesce into shared
device batches with correct per-request responses (VERDICT r3 task 2b).
"""

import threading

import pytest

from elasticsearch_tpu.node import NodeService

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
        import time
        from elasticsearch_tpu.common import tracing
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
