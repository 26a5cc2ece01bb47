"""Shard rebalancing (RELOCATING copy-then-switch) + streaming delta peer
recovery (VERDICT r4 #7/#9).

Ref: cluster/routing/allocation/allocator/BalancedShardsAllocator.java,
ShardRouting RELOCATING state machine, indices/recovery/
RecoverySourceHandler.java:149-195 (chunk streaming + checksum delta).
"""

import time

import pytest

from elasticsearch_tpu.cluster import TestCluster
from elasticsearch_tpu.cluster.state import (RELOCATING, STARTED,
                                             UNASSIGNED)


def _settle(cluster, rounds=60):
    import time
    for _ in range(rounds):
        cluster.detect_once()
        st = cluster.client().cluster.current()
        busy = any(
            c["state"] != STARTED
            for shards in st.routing.values()
            for copies in shards for c in copies)
        if not busy:
            return st
        time.sleep(0.05)
    return cluster.client().cluster.current()


class TestRebalancing:
    def test_joining_node_receives_shards(self, tmp_path):
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("docs", {"number_of_shards": 4,
                                         "number_of_replicas": 0})
            cluster.ensure_green()
            for i in range(40):
                client.index_doc("docs", str(i), {"n": i})
            client.refresh("docs")
            new_node = cluster.add_node()
            st = _settle(cluster)
            by_node: dict = {}
            for copies in st.routing["docs"]:
                for c in copies:
                    by_node[c["node"]] = by_node.get(c["node"], 0) + 1
            # 4 shards over 3 nodes: nobody holds more than 2, and the
            # NEW node actually received at least one
            assert max(by_node.values()) <= 2
            assert by_node.get(new_node.node_id, 0) >= 1
            # every doc still reachable after the moves
            out = client.search("docs", {"query": {"match_all": {}},
                                         "size": 40})
            assert out["hits"]["total"] == 40
        finally:
            cluster.close()

    def test_relocation_preserves_data_and_writes(self, tmp_path):
        cluster = TestCluster(1, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("docs", {"number_of_shards": 2,
                                         "number_of_replicas": 0})
            cluster.ensure_green()
            for i in range(30):
                client.index_doc("docs", str(i), {"n": i})
            client.refresh("docs")
            cluster.add_node()
            st = _settle(cluster)
            nodes_used = {c["node"] for copies in st.routing["docs"]
                          for c in copies}
            assert len(nodes_used) == 2      # one shard moved over
            # writes after the move land on the new owner
            client.index_doc("docs", "99", {"n": 99})
            client.refresh("docs")
            assert client.get_doc("docs", "99")["found"]
            out = client.search("docs", {"query": {"match_all": {}},
                                         "size": 50})
            assert out["hits"]["total"] == 31
        finally:
            cluster.close()

    def test_relocating_source_keeps_serving(self, tmp_path):
        from elasticsearch_tpu.cluster.state import (ClusterState,
                                                     new_index_routing,
                                                     rebalance)
        st = ClusterState.empty()
        st.nodes["a"] = {"id": "a"}
        st.nodes["b"] = {"id": "b"}
        st.data["routing"]["i"] = new_index_routing(2, 0)
        for copies in st.routing["i"]:
            copies[0]["node"] = "a"
            copies[0]["state"] = STARTED
        assert rebalance(st)
        copies0 = [c for shards in st.routing.values()
                   for copies in shards for c in copies
                   if c["state"] == RELOCATING]
        assert len(copies0) == 1
        # the relocating source still counts as a started (read-serving)
        # copy of its shard
        sid = next(sid for sid, copies in enumerate(st.routing["i"])
                   if any(c["state"] == RELOCATING for c in copies))
        assert any(c["state"] == RELOCATING
                   for c in st.started_copies("i", sid))


class TestStreamingRecovery:
    def test_recovery_is_chunked_and_delta(self, tmp_path):
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("big", {"number_of_shards": 1,
                                        "number_of_replicas": 1})
            cluster.ensure_green()
            # enough docs that the store files exceed one recovery chunk
            payload = "tok " * 200
            for i in range(800):
                client.index_doc("big", str(i), {"body": payload + str(i)})
            client.flush("big")

            # force a re-recovery of the replica through the chunk protocol
            from elasticsearch_tpu.cluster.node import ClusterNode
            ClusterNode.RECOVERY_CHUNK = 1 << 14      # 16 KiB for the test
            try:
                st = client.cluster.current()
                replica_node = next(
                    c["node"] for c in st.shard_copies("big", 0)
                    if not c["primary"])
                cluster.network.max_message_bytes = 0
                master = cluster.master_node()
                master._on_shard_failed(master.node_id, {
                    "index": "big", "shard": 0, "node": replica_node})
                cluster.ensure_green()
                # every recovery frame stayed within chunk bounds (payload
                # b64-encoded + framing; 3x is generous)
                assert cluster.network.max_message_bytes < (1 << 14) * 3
            finally:
                ClusterNode.RECOVERY_CHUNK = 1 << 19
            # the replica serves the data it recovered
            st = client.cluster.current()
            holders = [n._shards[("big", 0)] for n in cluster.nodes.values()
                       if ("big", 0) in n._shards]
            assert len(holders) == 2
            for h in holders:
                assert h.engine.get("500").found
        finally:
            cluster.close()

    def test_delta_reuse_skips_unchanged_files(self, tmp_path):
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("d", {"number_of_shards": 1,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            for i in range(300):
                client.index_doc("d", str(i), {"n": i})
            client.flush("d")
            cluster.ensure_green()
            st = client.cluster.current()
            replica_node = next(c["node"] for c in st.shard_copies("d", 0)
                                if not c["primary"])
            master = cluster.master_node()
            # first re-recovery: files arrive
            master._on_shard_failed(master.node_id, {
                "index": "d", "shard": 0, "node": replica_node})
            cluster.ensure_green()
            bytes_first = cluster.network.bytes_sent
            # second re-recovery with NO new data: the checksum delta
            # reuses every segment file — only manifest + translog move
            st = client.cluster.current()
            replica_node = next(c["node"] for c in st.shard_copies("d", 0)
                                if not c["primary"])
            before = cluster.network.bytes_sent
            master._on_shard_failed(master.node_id, {
                "index": "d", "shard": 0, "node": replica_node})
            cluster.ensure_green()
            delta_bytes = cluster.network.bytes_sent - before
            first_bytes = bytes_first
            assert delta_bytes < first_bytes / 2, \
                (delta_bytes, first_bytes)
        finally:
            cluster.close()


def _fail_replica(cluster, index: str, wipe: bool = True,
                  timeout: float = 60.0) -> str:
    """Report the replica of [index][0] failed, wait for the resulting
    re-recovery to reach a terminal stage, and return the node id — the
    canonical way these tests force a fresh peer recovery. With `wipe`
    the replica's local files go first, so the recovery STREAMS every
    byte instead of reusing it all through the checksum delta. The wait
    matters: the fail task publishes asynchronously and the pull streams
    on a background thread, so without it the caller races a recovery
    that hasn't started yet."""
    import shutil
    st = cluster.client().cluster.current()
    replica_node = next(c["node"] for c in st.shard_copies(index, 0)
                        if not c["primary"])
    target = cluster.nodes[replica_node]
    if wipe:
        with target._shards_lock:
            holder = target._shards.pop((index, 0), None)
        if holder is not None and holder.engine is not None:
            holder.drop_searcher()
            holder.engine.close()
        shutil.rmtree(target._shard_path(index, 0), ignore_errors=True)
    mark = time.monotonic()
    master = cluster.master_node()
    master._on_shard_failed(master.node_id, {
        "index": index, "shard": 0, "node": replica_node})
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with target._recoveries_lock:
            rec = target.recoveries.get((index, 0))
            fresh = (rec is not None and rec["start_s"] >= mark
                     and rec["stage"] in ("done", "failed", "cancelled"))
        if fresh:
            return replica_node
        time.sleep(0.02)
    raise AssertionError(f"re-recovery of [{index}][0] never finished")


class TestRecoveryThrottle:
    """indices.recovery.max_bytes_per_sec through the actual chunk
    stream (ISSUE 15): a token bucket on the receiving side paces every
    recovery the node runs."""

    def test_throttle_paces_the_stream_and_counts_waits(self, tmp_path):
        from elasticsearch_tpu.cluster.recovery import (parse_bytes,
                                                        snapshot)
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("t", {"number_of_shards": 1,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            payload = "tok " * 200
            for i in range(500):
                client.index_doc("t", str(i), {"body": payload + str(i)})
            client.flush("t")
            cluster.ensure_green()
            client.update_cluster_settings(
                {"indices.recovery.max_bytes_per_sec": "100kb"})
            r0 = dict(snapshot())
            t0 = time.monotonic()
            _fail_replica(cluster, "t")
            cluster.ensure_green(timeout=60.0)
            dt = time.monotonic() - t0
            r1 = dict(snapshot())
            moved = r1["bytes_total"] - r0["bytes_total"]
            assert moved > parse_bytes("100kb") / 2, moved
            assert r1["throttle_waits_total"] > r0["throttle_waits_total"]
            # compliance: measured rate stays within the limit plus the
            # burst allowance (bucket capacity = rate/2)
            assert moved / dt <= parse_bytes("100kb") * 3, (moved, dt)
            # and it actually slowed down: an unthrottled local recovery
            # of ~500 KiB completes in well under a second
            assert dt > 1.0, dt
        finally:
            cluster.close()

    def test_unlimited_rate_never_waits(self, tmp_path):
        from elasticsearch_tpu.cluster.recovery import snapshot
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("u", {"number_of_shards": 1,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            for i in range(200):
                client.index_doc("u", str(i), {"n": i})
            client.flush("u")
            cluster.ensure_green()
            client.update_cluster_settings(
                {"indices.recovery.max_bytes_per_sec": 0})
            r0 = dict(snapshot())
            _fail_replica(cluster, "u")
            cluster.ensure_green()
            r1 = dict(snapshot())
            assert r1["bytes_total"] > r0["bytes_total"]
            assert r1["throttle_waits_total"] == r0["throttle_waits_total"]
        finally:
            cluster.close()

    def test_parse_bytes(self):
        from elasticsearch_tpu.cluster.recovery import parse_bytes
        assert parse_bytes("40mb") == 40 * (1 << 20)
        assert parse_bytes("100kb") == 100 * 1024
        assert parse_bytes("1gb") == 1 << 30
        assert parse_bytes("512b") == 512.0
        assert parse_bytes(123456) == 123456.0
        assert parse_bytes(0) == 0.0          # 0 / negative = unlimited
        assert parse_bytes("-1") == 0.0
        assert parse_bytes("garbage", default=7.0) == 7.0


class TestChunkRetry:
    def test_transient_chunk_fault_is_resent_with_backoff(self, tmp_path):
        """A dropped chunk send retries the SAME bounded read instead of
        failing the whole recovery — only the final exhaustion aborts."""
        from elasticsearch_tpu.cluster.node import A_RECOVERY_CHUNK
        from elasticsearch_tpu.cluster.recovery import snapshot
        from elasticsearch_tpu.cluster.transport import \
            ConnectTransportException
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("r", {"number_of_shards": 1,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            for i in range(300):
                client.index_doc("r", str(i), {"n": i})
            client.flush("r")
            cluster.ensure_green()
            st = client.cluster.current()
            replica_node = next(c["node"]
                                for c in st.shard_copies("r", 0)
                                if not c["primary"])
            target = cluster.nodes[replica_node]
            import shutil
            with target._shards_lock:
                holder = target._shards.pop(("r", 0), None)
            if holder is not None and holder.engine is not None:
                holder.drop_searcher()
                holder.engine.close()
            shutil.rmtree(target._shard_path("r", 0), ignore_errors=True)
            real_send = target.transport.send
            fails = {"left": 2}

            def flaky(dest, action, payload, **kw):
                if action == A_RECOVERY_CHUNK and fails["left"] > 0:
                    fails["left"] -= 1
                    raise ConnectTransportException("injected chunk fault")
                return real_send(dest, action, payload, **kw)

            target.transport.send = flaky
            r0 = dict(snapshot())
            try:
                master = cluster.master_node()
                master._on_shard_failed(master.node_id, {
                    "index": "r", "shard": 0, "node": replica_node})
                # the pull streams on a background thread: wait for ITS
                # completion, not for a (possibly stale) green health
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    if dict(snapshot())["completed_total"] \
                            > r0["completed_total"]:
                        break
                    time.sleep(0.02)
                cluster.ensure_green(timeout=30.0)
            finally:
                target.transport.send = real_send
            r1 = dict(snapshot())
            assert r1["retries_total"] - r0["retries_total"] >= 2
            assert r1["completed_total"] > r0["completed_total"]
            assert fails["left"] == 0
            rows = [r for r in client.cat_recovery()
                    if r["index"] == "r" and r["stage"] == "done"]
            assert rows and rows[-1]["retries"] >= 2
        finally:
            cluster.close()


class TestRecoveryCancellation:
    def test_cancel_mid_stream_cleans_up(self, tmp_path):
        """Unassigning a copy mid-recovery (here: index deletion) aborts
        the pull between chunks, GCs the partial files and never reports
        the copy started."""
        from elasticsearch_tpu.cluster.node import (A_RECOVERY_CHUNK,
                                                    ClusterNode)
        from elasticsearch_tpu.cluster.recovery import snapshot
        cluster = TestCluster(2, str(tmp_path))
        old_chunk = ClusterNode.RECOVERY_CHUNK
        try:
            client = cluster.client()
            client.create_index("c", {"number_of_shards": 1,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            payload = "tok " * 200
            for i in range(400):
                client.index_doc("c", str(i), {"body": payload + str(i)})
            client.flush("c")
            cluster.ensure_green()
            st = client.cluster.current()
            primary_node = st.primary_of("c", 0)["node"]
            replica_node = next(c["node"]
                                for c in st.shard_copies("c", 0)
                                if not c["primary"])
            target = cluster.nodes[replica_node]
            import shutil
            with target._shards_lock:
                holder = target._shards.pop(("c", 0), None)
            if holder is not None and holder.engine is not None:
                holder.drop_searcher()
                holder.engine.close()
            shutil.rmtree(target._shard_path("c", 0), ignore_errors=True)
            # many tiny chunks, each paying injected latency: the stream
            # stays in flight long enough to cancel deterministically
            ClusterNode.RECOVERY_CHUNK = 1 << 13
            cluster.network.add_delay(primary_node, A_RECOVERY_CHUNK, 0.05)
            r0 = dict(snapshot())
            master = cluster.master_node()
            master._on_shard_failed(master.node_id, {
                "index": "c", "shard": 0, "node": replica_node})
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                rec = target.recoveries.get(("c", 0))
                if rec is not None and rec["bytes_recovered"] > 0 \
                        and rec["stage"] not in ("done", "failed"):
                    break
                time.sleep(0.01)
            else:
                raise AssertionError("recovery never got in flight")
            client.delete_index("c")
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if dict(snapshot())["cancelled_total"] \
                        > r0["cancelled_total"]:
                    break
                time.sleep(0.02)
            r1 = dict(snapshot())
            assert r1["cancelled_total"] > r0["cancelled_total"]
            assert r1["completed_total"] == r0["completed_total"]
            # partial files GC'd, nothing reported started
            import os
            assert not os.path.exists(target._shard_path("c", 0))
            assert ("c", 0) not in target._shards
        finally:
            ClusterNode.RECOVERY_CHUNK = old_chunk
            cluster.network.heal()
            cluster.close()


class TestRelocationRaces:
    """finish_relocation / cancel_relocations_for interleavings
    (ISSUE 15 satellite): a relocation target dying the same tick as the
    source's finish ack must not leave a zombie `relocating_to`."""

    def _relocating_state(self):
        from elasticsearch_tpu.cluster.state import (ClusterState,
                                                     new_index_routing)
        st = ClusterState.empty()
        st.nodes["a"] = {"id": "a"}
        st.nodes["b"] = {"id": "b"}
        st.data["routing"]["i"] = new_index_routing(1, 0)
        src = st.routing["i"][0][0]
        src.update({"node": "a", "state": RELOCATING,
                    "relocating_to": "b"})
        st.routing["i"][0].append({
            "node": "b", "primary": False, "state": "INITIALIZING",
            "relocation": True, "recover_from": "a",
            "primary_target": True})
        return st

    def test_cancel_then_finish_leaves_no_zombie(self, tmp_path):
        from elasticsearch_tpu.cluster.state import (cancel_relocations_for,
                                                     finish_relocation)
        st = self._relocating_state()
        cancel_relocations_for(st, "b")        # target node died
        assert not finish_relocation(st, "i", 0, "b")   # stale finish ack
        copies = st.routing["i"][0]
        assert len(copies) == 1
        assert copies[0]["state"] == STARTED
        assert "relocating_to" not in copies[0]

    def test_finish_sweeps_stale_pointer_when_source_reverted(self):
        """The zombie shape itself: the source was reverted to STARTED
        (concurrent cancel) but still carries the pointer when the finish
        ack lands — finish must clear it, or every later finish/cancel
        sweep double-counts the copy."""
        from elasticsearch_tpu.cluster.state import finish_relocation
        st = self._relocating_state()
        src = st.routing["i"][0][0]
        src["state"] = STARTED                 # reverted, pointer stale
        assert finish_relocation(st, "i", 0, "b")
        copies = st.routing["i"][0]
        assert all("relocating_to" not in c for c in copies)
        tgt = next(c for c in copies if c["node"] == "b")
        assert tgt["state"] == STARTED and tgt["primary"]
        assert not tgt.get("relocation")

    def test_source_failure_mid_relocation_reverts_cleanly(self, tmp_path):
        """_on_shard_failed on a RELOCATING source: the pointer pops, the
        orphaned target drops, the primary reverts to STARTED (it holds
        the only data) — and the drain then retries to completion."""
        from elasticsearch_tpu.cluster.node import (A_RECOVERY_CHUNK,
                                                    ClusterNode)
        cluster = TestCluster(2, str(tmp_path))
        old_chunk = ClusterNode.RECOVERY_CHUNK
        try:
            client = cluster.client()
            client.create_index("z", {"number_of_shards": 1,
                                      "number_of_replicas": 0})
            cluster.ensure_green()
            payload = "tok " * 200
            for i in range(300):
                client.index_doc("z", str(i), {"body": payload + str(i)})
            client.flush("z")
            st = client.cluster.current()
            src_node = st.primary_of("z", 0)["node"]
            other = next(n for n in cluster.nodes if n != src_node)
            ClusterNode.RECOVERY_CHUNK = 1 << 13
            # chunk requests flow TO the source node: delay THAT link so
            # the relocation stays observable mid-stream
            cluster.network.add_delay(src_node, A_RECOVERY_CHUNK, 0.05)
            master = cluster.master_node()
            client.update_cluster_settings(
                {"cluster.routing.allocation.exclude._id": src_node})
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                cur = master.cluster.current()
                if any(c["state"] == RELOCATING
                       for c in cur.shard_copies("z", 0)):
                    break
                time.sleep(0.01)
            else:
                raise AssertionError("relocation never started")
            # the SOURCE is reported failed while RELOCATING
            master._on_shard_failed(master.node_id, {
                "index": "z", "shard": 0, "node": src_node})

            def clean(cur):
                copies = cur.shard_copies("z", 0)
                return (all("relocating_to" not in c for c in copies)
                        and not any(c.get("relocation") for c in copies))

            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                cur = master.cluster.current()
                revert = next((c for c in cur.shard_copies("z", 0)
                               if c["node"] == src_node), None)
                if revert is not None and revert["state"] in (
                        STARTED, RELOCATING):
                    break
                time.sleep(0.01)
            cur = master.cluster.current()
            assert not any(
                c["state"] == UNASSIGNED and "relocating_to" in c
                for c in cur.shard_copies("z", 0))
            # heal the stream: the exclude filter retries and the drain
            # completes with no zombie markers anywhere
            cluster.network.heal()
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                cluster.detect_once()
                cur = master.cluster.current()
                copies = cur.shard_copies("z", 0)
                if (clean(cur) and len(copies) == 1
                        and copies[0]["node"] == other
                        and copies[0]["state"] == STARTED):
                    break
                time.sleep(0.05)
            copies = master.cluster.current().shard_copies("z", 0)
            assert copies[0]["node"] == other, copies
            assert copies[0]["state"] == STARTED
            assert clean(master.cluster.current())
            out = client.search("z", {"query": {"match_all": {}},
                                      "size": 1})
            assert out["hits"]["total"] == 300
        finally:
            ClusterNode.RECOVERY_CHUNK = old_chunk
            cluster.network.heal()
            cluster.close()


class TestCatRecoveryAndObservability:
    def test_cat_recovery_rows_and_metrics(self, tmp_path):
        from elasticsearch_tpu.cluster.recovery import snapshot
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("cr", {"number_of_shards": 1,
                                       "number_of_replicas": 1})
            cluster.ensure_green()
            for i in range(200):
                client.index_doc("cr", str(i), {"n": i})
            client.flush("cr")
            cluster.ensure_green()
            replica_node = _fail_replica(cluster, "cr")
            cluster.ensure_green()
            rows = [r for r in client.cat_recovery() if r["index"] == "cr"]
            done = [r for r in rows if r["stage"] == "done"]
            assert done, rows
            row = done[-1]
            for key in ("index", "shard", "source", "target", "stage",
                        "files_total", "files_reused", "bytes_total",
                        "bytes_recovered", "throttle_waits", "retries",
                        "start_time_ms", "elapsed_ms"):
                assert key in row, key
            assert row["target"] == replica_node
            assert row["bytes_recovered"] > 0
            assert row["elapsed_ms"] >= 0
            # the node-level metric section behind
            # es_recovery_bytes_total / es_recovery_throttle_waits_total
            sections = cluster.master_node().metric_sections()
            label, counters = sections["recovery"]
            assert label is None
            assert counters["bytes_total"] == snapshot()["bytes_total"]
            assert "throttle_waits_total" in counters
            # the recovery trace roots on the TARGET with per-chunk spans
            target = cluster.nodes[replica_node]
            tid = next(t["trace_id"] for t in target.tracer.list()
                       if t["root"] == "recovery")
            trace = target.tracer.get(tid)
            names = {s["name"] for s in trace["spans"]}
            assert "recovery_chunk" in names
            chunk = next(s for s in trace["spans"]
                         if s["name"] == "recovery_chunk")
            assert chunk["attributes"]["bytes"] > 0
        finally:
            cluster.close()


class TestAllocationIdFence:
    """Every (re)assignment stamps a fresh allocation id; started/failed
    reports only act on the era they came from (ref AllocationId). The
    chaos kill/restart roster caught the unfenced version: a restarted
    process's PRE-KILL pull completing late marked the copy's NEW (and
    actually failed) assignment STARTED — a zombie serving nothing."""

    def test_assigned_copies_carry_unique_aids(self, tmp_path):
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("z", {"number_of_shards": 2,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            st = cluster.master_node().cluster.current()
            aids = [c.get("aid")
                    for copies in st.routing["z"] for c in copies]
            assert all(a is not None for a in aids), aids
            assert len(aids) == len(set(aids)), aids
        finally:
            cluster.close()

    def test_stale_era_reports_are_ignored(self, tmp_path):
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("z", {"number_of_shards": 1,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            master = cluster.master_node()
            st = master.cluster.current()
            replica = next(c for c in st.shard_copies("z", 0)
                           if not c["primary"])
            cur_aid = replica["aid"]
            # a started AND a failed report from a previous era: neither
            # may touch the current, healthy assignment
            master._on_shard_started(master.node_id, {
                "index": "z", "shard": 0, "node": replica["node"],
                "aid": cur_aid - 1})
            master._on_shard_failed(master.node_id, {
                "index": "z", "shard": 0, "node": replica["node"],
                "aid": cur_aid - 1})
            # both handlers queue wait=False tasks: a sync no-op task
            # behind them is the drain barrier (the state thread is FIFO)
            master.cluster.submit_task("barrier", lambda cur: None)
            after = next(c for c in master.cluster.current()
                         .shard_copies("z", 0) if not c["primary"])
            assert after["state"] == STARTED
            assert after["node"] == replica["node"]
            assert after["aid"] == cur_aid
        finally:
            cluster.close()

    def test_reassignment_gets_a_new_aid(self, tmp_path):
        cluster = TestCluster(2, str(tmp_path))
        try:
            client = cluster.client()
            client.create_index("z", {"number_of_shards": 1,
                                      "number_of_replicas": 1})
            cluster.ensure_green()
            master = cluster.master_node()
            st = master.cluster.current()
            replica = next(c for c in st.shard_copies("z", 0)
                           if not c["primary"])
            old_aid = replica["aid"]
            # fail the CURRENT era (correct aid): unassign + re-allocate
            master._on_shard_failed(master.node_id, {
                "index": "z", "shard": 0, "node": replica["node"],
                "aid": old_aid})
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                cur = next(c for c in master.cluster.current()
                           .shard_copies("z", 0) if not c["primary"])
                if cur["state"] == STARTED and cur["aid"] != old_aid:
                    break
                time.sleep(0.02)
            cur = next(c for c in master.cluster.current()
                       .shard_copies("z", 0) if not c["primary"])
            assert cur["state"] == STARTED
            assert cur["aid"] > old_aid
        finally:
            cluster.close()

    def test_a_finished_pull_is_not_pulled_again_for_its_own_era(
            self, tmp_path):
        """A state published between a copy's finished pull and the
        master's booking of its report still shows the copy INITIALIZING
        under the SAME aid. Applying it must report again and pull
        nothing. The node used to close the live engine and stream again;
        for a relocation target the source is gone by then, so a STARTED
        primary was left without an engine (the 3-node, 3-shard,
        1-replica fixture of the distributed-search parity tests, once in
        a few runs on a starved machine: `[docs][0] primary not on
        [node-3]`)."""
        from elasticsearch_tpu.cluster.recovery import snapshot
        from elasticsearch_tpu.cluster.state import INITIALIZING
        cluster = TestCluster(3, str(tmp_path))
        try:
            client = cluster.client()
            # 3 primaries start one by one, each replica goes to the least
            # loaded node that may hold it: loads end 3 / 2 / 1 and the
            # rebalancer moves a started copy, under a new aid
            client.create_index("docs", {"number_of_shards": 3,
                                         "number_of_replicas": 1})
            cluster.ensure_green()
            st = _settle(cluster)
            for i in range(12):
                client.index_doc("docs", str(i), {"n": i})
            pulls = snapshot()["completed_total"]
            for sid, copies in enumerate(st.routing["docs"]):
                for c in copies:
                    if c["primary"] and c["aid"] <= 3:
                        continue        # started empty: never pulled
                    node = cluster.nodes[c["node"]]
                    holder = node._shards[("docs", sid)]
                    engine = holder.engine
                    assert engine is not None, (sid, c)
                    stale = {**c, "primary": False, "state": INITIALIZING,
                             "recover_from": "node-gone"}
                    node._init_shard(st, "docs", sid, stale)
                    assert holder.engine is engine, (sid, c)
                    assert not holder.recovering, (sid, c)
            assert snapshot()["completed_total"] == pulls
            cluster.ensure_green()
            client.refresh("docs")
            assert client.search("docs", {"size": 0})["hits"]["total"] == 12
        finally:
            cluster.close()
