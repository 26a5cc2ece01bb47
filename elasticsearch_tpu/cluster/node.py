"""ClusterNode: a full node — cluster membership, shard hosting, replicated
writes, peer recovery, and the distributed search driver.

Maps to several reference components at once (SURVEY.md §2.3/§2.5/§2.7):
  * join/election/fault-report       — discovery/zen/ZenDiscovery.java:354,500
  * reconciler (state → local shards) — indices/cluster/
                                        IndicesClusterStateService.java:150
  * replicated write                 — action/support/replication/
                                        TransportShardReplicationOperationAction.java:67,118-120
  * peer recovery (file phase)       — indices/recovery/RecoverySourceHandler.java:149-195
  * search scatter-gather            — action/search/type/TransportSearchTypeAction.java:85-177

Design notes (TPU-first deviations from the reference, on purpose):
  * Replicas apply ops with external-version semantics: the primary assigns
    the version, replicas accept any strictly-newer version and treat
    version conflicts as "already applied" — this makes the
    file-copy-then-forward recovery race idempotent without uid-locks.
  * Recovery transfers the checksummed write-once segment files produced by
    index/store.py (flush under the engine lock = the reference's brief
    phase-3 write block), so a recovered replica loads tensors straight to
    device with zero re-tokenization.
  * Dynamic mappings derive deterministically on every copy (same doc ⇒ same
    inferred mapping), so replicas don't block acks on a master mapping
    round-trip; explicit put-mapping still flows through the master.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any

from ..common import tracing
from ..index.engine import Engine, VersionConflictException
from ..mapping.mapper import MapperService
from ..parallel.routing import shard_id as route_shard
from ..search.shard_searcher import ShardSearcher
from .service import ClusterService
from .state import (INITIALIZING, RELOCATING, STARTED, UNASSIGNED,
                    ClusterState, allocate, cancel_relocations_for,
                    finish_relocation, new_index_routing, rebalance,
                    remove_node)
from .transport import (ConnectTransportException, LocalTransport,
                        RemoteTransportException, TransportService)

A_JOIN = "internal:discovery/zen/join"
A_PING = "internal:discovery/zen/fd/ping"
A_NODE_FAILED = "internal:discovery/zen/fd/node_failed"
A_SHARD_STARTED = "internal:cluster/shard/started"
A_SHARD_FAILED = "internal:cluster/shard/failed"
A_CREATE_INDEX = "indices:admin/create"
A_DELETE_INDEX = "indices:admin/delete"
A_PUT_MAPPING = "indices:admin/mapping/put"
A_PUT_ALIAS = "indices:admin/aliases/put"
A_DELETE_ALIAS = "indices:admin/aliases/delete"
A_UPDATE_SETTINGS = "indices:admin/settings/update"
A_CLOSE_INDEX = "indices:admin/close"
A_OPEN_INDEX = "indices:admin/open"
A_SHARD_DATA = "internal:gateway/local/started_shards"
A_REFRESH = "indices:admin/refresh"
A_FLUSH = "indices:admin/flush"
A_WRITE_P = "indices:data/write/op[p]"
A_WRITE_R = "indices:data/write/op[r]"
A_WRITE_R_BULK = "indices:data/write/bulk[r]"
A_GET = "indices:data/read/get"
A_QUERY = "indices:data/read/search[phase/query]"
A_QUERY_HOST = "indices:data/read/search[phase/query/host]"
A_FETCH = "indices:data/read/search[phase/fetch/id]"
A_TERM_STATS = "indices:data/read/search[phase/dfs]"
A_SCROLL_NEXT = "indices:data/read/search[phase/scroll]"
A_SCROLL_CLEAR = "indices:data/read/search[free_context]"
A_RECOVERY = "internal:index/shard/recovery/start"
A_RECOVERY_CHUNK = "internal:index/shard/recovery/chunk"
A_FS_STATS = "internal:monitor/fs"
A_RECOVERY_STATS = "indices:monitor/recovery"
A_CLUSTER_SETTINGS = "cluster:admin/settings/update"
A_NODE_STATS = "cluster:monitor/nodes/stats"
A_NODE_METRICS = "cluster:monitor/nodes/metrics"
A_SHARD_STATS = "indices:monitor/stats[shard]"


class NoMasterException(Exception):
    pass


class SearchContextMissingException(Exception):
    """Expired/unknown scroll id (ref search/SearchContextMissingException
    — a routine 404, not a server fault)."""


class UnavailableShardsException(Exception):
    pass


class _ShardHolder:
    """One locally-hosted shard copy."""

    def __init__(self):
        self.engine: Engine | None = None
        self.lock = threading.RLock()
        self.recovering = False
        self.recovery_aid = None       # allocation id of the latest pull
        self.reinit_pending = False    # a newer era waits for the old pull
        self.cancel_recovery = False   # newer state unassigned this copy
        self.pending: list[dict] = []     # ops buffered during recovery
        self.searcher: tuple | None = None   # (key, ShardSearcher, handle)

    def drop_searcher(self) -> None:
        """Release the cached searcher's engine refcount (the leak
        detector asserts the count drains at engine close)."""
        if self.searcher is not None:
            self.searcher[2].release()
            self.searcher = None


class ClusterNode:
    def __init__(self, node_id: str, data_path: str, network: LocalTransport,
                 minimum_master_nodes: int = 1,
                 attrs: dict | None = None,
                 settings: dict | None = None):
        self.node_id = node_id
        self.data_path = os.path.join(data_path, node_id)
        os.makedirs(self.data_path, exist_ok=True)
        self.minimum_master_nodes = minimum_master_nodes
        # filterable node attributes (`node.attr.*` analog) — published
        # into the cluster state at join time for the awareness/filter
        # deciders (ref DiscoveryNode attributes)
        self.attrs = dict(attrs or {})
        # node-local settings overlay (ISSUE 19): `node.devices` carves
        # this node's disjoint device subset into an owned DevicePool (so
        # host reduces dispatch under the pool's private lock, not the
        # process-wide EXEC_LOCK), `node.host` names the simulated host
        # for the transport's DCN traffic classification, and
        # `cluster.mesh.coordinator` arms jax.distributed multi-host init.
        self.settings = dict(settings or {})
        from ..parallel.mesh import (maybe_init_distributed,
                                     resolve_device_pool)
        maybe_init_distributed(self.settings)
        self.device_pool = resolve_device_pool(self.settings)
        host = self.settings.get("node.host")
        if host and hasattr(network, "set_host"):
            network.set_host(node_id, str(host))
        self.transport = TransportService(node_id, network)
        self.cluster = ClusterService(node_id, self.transport,
                                      self._apply_cluster_state)
        self._shards: dict[tuple[str, int], _ShardHolder] = {}
        self._mappers: dict[str, MapperService] = {}
        self._shards_lock = threading.RLock()
        self.closed = False
        # distributed task registry: coordinator tasks here, shard tasks on
        # the copy-holders with the coordinator as parent (the `_task` wire
        # header on shard messages; ref tasks/TaskManager + TaskId)
        from ..common.tasks import TaskManager
        self.tasks = TaskManager(node_id)
        # span tracer: shard subtrees on copy-holders continue the
        # coordinator's trace via the `_trace` wire header (partial traces
        # land in THIS node's ring under the same trace id)
        from ..common.tracing import Tracer
        self.tracer = Tracer()
        for action, handler in [
                (A_JOIN, self._on_join), (A_PING, self._on_ping),
                (A_NODE_FAILED, self._on_node_failed),
                (A_SHARD_STARTED, self._on_shard_started),
                (A_SHARD_FAILED, self._on_shard_failed),
                (A_CREATE_INDEX, self._on_create_index),
                (A_DELETE_INDEX, self._on_delete_index),
                (A_PUT_MAPPING, self._on_put_mapping),
                (A_PUT_ALIAS, self._on_put_alias),
                (A_DELETE_ALIAS, self._on_delete_alias),
                (A_UPDATE_SETTINGS, self._on_update_settings),
                (A_CLOSE_INDEX, self._on_close_index),
                (A_OPEN_INDEX, self._on_open_index),
                (A_SHARD_DATA, self._on_shard_data),
                (A_REFRESH, self._on_refresh), (A_FLUSH, self._on_flush),
                (A_WRITE_P, self._on_primary_write),
                (A_WRITE_R, self._on_replica_write),
                (A_WRITE_R_BULK, self._on_replica_bulk),
                (A_GET, self._on_get), (A_QUERY, self._on_query),
                (A_QUERY_HOST, self._on_query_host),
                (A_FETCH, self._on_fetch),
                (A_TERM_STATS, self._on_term_stats),
                (A_SCROLL_NEXT, self._on_scroll_next),
                (A_SCROLL_CLEAR, self._on_scroll_clear),
                (A_RECOVERY, self._on_recovery),
                (A_RECOVERY_CHUNK, self._on_recovery_chunk),
                (A_RECOVERY_STATS, self._on_recovery_stats),
                (A_CLUSTER_SETTINGS, self._on_cluster_settings),
                (A_FS_STATS, self._on_fs_stats),
                (A_NODE_STATS, self._on_node_stats),
                (A_NODE_METRICS, self._on_node_metrics),
                (A_SHARD_STATS, self._on_shard_stats)]:
            self.transport.register_handler(action, handler)
        # ClusterInfoService + disk watermark decider (cluster/info.py;
        # ref InternalClusterInfoService + DiskThresholdDecider) — the
        # master samples peers' fs stats during fault-detection rounds
        from .info import ClusterInfoService, DiskThresholdDecider
        self.cluster_info = ClusterInfoService()
        self.cluster_info.register_node(node_id, self.data_path)
        self.disk_decider = DiskThresholdDecider(self.cluster_info)
        # composable allocation decider chain (ISSUE 15): awareness /
        # filters / shards-limit / recovery throttling / disk, each with
        # a per-decider verdict behind /_cluster/allocation/explain
        from .deciders import DeciderChain
        self.deciders = DeciderChain.default(self.disk_decider)
        # peer-recovery rate limiting (indices.recovery.max_bytes_per_sec,
        # live from cluster settings): ONE node-wide token bucket shared
        # by every recovery this node pulls, plus per-shard progress rows
        # for GET /_cat/recovery
        from .recovery import RecoveryThrottle
        self.recovery_throttle = RecoveryThrottle(self._recovery_rate)
        self.recoveries: dict[tuple[str, int], dict] = {}
        self._recoveries_lock = threading.Lock()
        # chaos clock-skew seam: offsets WALL-clock reads only (the
        # _cat/recovery start_time_ms column). Durations and the token
        # bucket run on time.monotonic, so a skewed node must never
        # mis-throttle or report negative elapsed — the invariant the
        # ClockSkew disruption asserts.
        self.clock_skew_s = 0.0
        # per-(index, shard) round-robin cursor for read copy selection
        # (ref cluster/routing/OperationRouting.java:144-154)
        self._read_rr: dict[tuple[str, int], int] = {}
        # hedged replica reads (ISSUE 9, SURVEY §2.10.2 upgraded): per-
        # target-node latency EWMAs arm an adaptive p99 deadline; a copy
        # that blows it gets a backup request fired at another copy, the
        # first answer wins and the loser is canceled. Settings
        # (cluster.search.hedge.*) read from cluster-state settings with
        # this overlay dict as the node-local fallback.
        self._node_lat: dict[str, Any] = {}
        self.hedge_settings: dict = {}
        self.hedge_stats = {"fired": 0, "win_primary": 0,
                            "win_backup": 0, "canceled": 0, "failed": 0,
                            "moving": 0}
        # shard-level pinned scroll contexts this node hosts (data-node side
        # of the distributed scroll; ref SearchService contexts + reaper)
        self._scroll_ctx: dict[str, dict] = {}
        self._scroll_seq = 0
        self._scroll_lock = threading.Lock()
        # node-local mesh reduce (ISSUE 11): the co-hosted shard groups'
        # packed mesh stacks — one device program per host per query, the
        # transport carries pre-reduced per-shard results. Keyed by the
        # shard GROUP (index + sids), stale entries displaced on refresh.
        from ..indices.cache_service import (MeshStackCache,
                                             MeshVectorStackCache)
        self._host_mesh_stacks = MeshStackCache(max_bytes=1 << 31)
        self._host_vector_stacks = MeshVectorStackCache(max_bytes=1 << 31)
        self.host_reduce_stats = {"dispatches": 0, "declined": 0,
                                  "errors": 0, "merges": 0,
                                  # pod tier (ISSUE 19): cross-host
                                  # pre-reduced merges + their DCN hops
                                  "pod_dispatches": 0, "dcn_hops": 0}

    # ------------------------------------------------------------------
    # membership / election (ref ZenDiscovery.java:354 innerJoinCluster)
    # ------------------------------------------------------------------

    def bootstrap_as_master(self) -> None:
        """First node of a cluster: publish a state with self as master."""
        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            st.data["master_node"] = self.node_id
            st.nodes[self.node_id] = {"id": self.node_id,
                                      "name": self.node_id,
                                      "attributes": dict(self.attrs)}
            return st
        self.cluster.submit_task("bootstrap-master", task)

    def join(self, master_id: str) -> None:
        self.transport.send(master_id, A_JOIN, {"node": self.node_id,
                                                "attrs": self.attrs})
        # the publish that follows the join task delivers us the state
        deadline = time.monotonic() + 10
        while self.cluster.current().master_node is None:
            if time.monotonic() > deadline:
                raise NoMasterException(f"join to [{master_id}] not published")
            time.sleep(0.01)

    def _on_join(self, from_id: str, req: dict) -> dict:
        joining = req["node"]
        attrs = req.get("attrs") or {}

        def task(cur: ClusterState) -> ClusterState | None:
            st = cur.mutate()
            if joining in st.nodes:
                # REJOIN behind an id the table still knows: a restarted
                # process (or one back from a partition the master never
                # noticed). Its copies may be STARTED in the table while
                # the process behind the id holds nothing — reset them to
                # UNASSIGNED so allocation re-assigns with a real
                # (checksum-delta-cheap) recovery instead of serving a
                # zombie copy with no engine.
                remove_node(st, joining, decider=self.deciders)
            st.nodes[joining] = {"id": joining, "name": joining,
                                 "attributes": dict(attrs)}
            allocate(st, decider=self.deciders)
            rebalance(st, decider=self.deciders)    # a joining node receives shards (VERDICT r4 #9)
            return st
        self.cluster.submit_task(f"node-join[{joining}]", task, wait=False)
        return {"ok": True}

    def _on_ping(self, from_id: str, req: Any) -> dict:
        cur = self.cluster.current()
        # `member`: whether the PINGER is in our cluster state — the
        # MasterFaultDetection "node does not exist on master" signal. A
        # node the master removed during a partition pings a master that
        # still answers with the same master id, so without this bit the
        # healed node would never learn it was dropped and never rejoin
        # (found by the chaos harness's isolate→heal rounds).
        return {"node": self.node_id, "version": cur.version,
                "master": cur.master_node,
                "member": from_id in cur.nodes or from_id == self.node_id}

    def _on_shard_stats(self, from_id: str, req: Any) -> dict:
        """Per-shard stats for the BROADCAST template (ref action/support/
        broadcast/TransportBroadcastOperationAction — every node answers
        for the shards it holds; the coordinator aggregates)."""
        names = set(req.get("indices") or [])
        out = []
        with self._shards_lock:
            holders = list(self._shards.items())
        for (index, sid), holder in holders:
            if names and index not in names:
                continue
            if holder.engine is None:
                continue
            st = holder.engine.segment_stats()
            out.append({"index": index, "shard": sid,
                        "docs": holder.engine.doc_count(),
                        "deleted": st["deleted"],
                        "segments": st["count"],
                        "store_bytes": st["memory_in_bytes"]})
        return {"shards": out}

    def indices_stats(self, index: str = "_all") -> dict:
        """Broadcast fan-out: collect shard stats from every node holding
        copies, aggregate per index (the _stats shape over a real
        cluster)."""
        state = self.cluster.current()
        names = state.resolve_index(index)
        if not names and index not in ("_all", "*", ""):
            raise KeyError(f"no such index [{index}]")
        per_index: dict[str, dict] = {
            n: {"docs": 0, "deleted": 0, "segments": 0, "store_bytes": 0,
                "shards": 0} for n in names}
        # _shards counts SHARD COPIES consulted, like the reference's
        # broadcast responses — not nodes
        total = sum(1 for n in names
                    for copies in state.routing.get(n, [])
                    for c in copies if c["state"] == STARTED)
        successful = 0
        for node_id in sorted(state.nodes):
            try:
                if node_id == self.node_id:
                    out = self._on_shard_stats(self.node_id,
                                               {"indices": names})
                else:
                    out = self.transport.send(node_id, A_SHARD_STATS,
                                              {"indices": names})
            except (ConnectTransportException, RemoteTransportException):
                continue
            successful += len(out["shards"])
            for sh in out["shards"]:
                agg = per_index.get(sh["index"])
                if agg is None:
                    continue
                agg["docs"] += sh["docs"]
                agg["deleted"] += sh["deleted"]
                agg["segments"] += sh["segments"]
                agg["store_bytes"] += sh["store_bytes"]
                agg["shards"] += 1
        indices = {
            n: {"total": {
                "docs": {"count": a["docs"], "deleted": a["deleted"]},
                "store": {"size_in_bytes": a["store_bytes"]},
                "segments": {"count": a["segments"]},
                "shard_copies": a["shards"]}}
            for n, a in per_index.items()}
        return {"_shards": {"total": total, "successful": successful,
                            "failed": max(total - successful, 0)},
                "_all": {"total": {
                    "docs": {"count": sum(a["docs"]
                                          for a in per_index.values()),
                             "deleted": sum(a["deleted"]
                                            for a in per_index.values())},
                    "store": {"size_in_bytes": sum(
                        a["store_bytes"] for a in per_index.values())}}},
                "indices": indices}

    def _on_node_stats(self, from_id: str, req: Any) -> dict:
        """Full per-node stats for the nodes-template fan-out (ref
        action/admin/cluster/node/stats/TransportNodesStatsAction — every
        node answers for itself; the coordinator assembles the map)."""
        from ..common import monitor
        docs = 0
        shards = 0
        with self._shards_lock:         # the reconciler mutates _shards
            holders = list(self._shards.values())
        for holder in holders:
            if holder.engine is not None:
                docs += holder.engine.doc_count()
                shards += 1
        return {"name": self.node_id,
                "indices": {"docs": {"count": docs},
                            "shard_count": shards},
                "os": monitor.os_stats(),
                "process": monitor.process_stats(),
                "jvm": monitor.runtime_stats(),
                "fs": monitor.fs_stats([self.data_path])}

    def metric_sections(self) -> dict:
        """This node's scrapeable registries as OpenMetrics walk input
        (common/metrics.openmetrics_families) — the cluster analog of
        NodeService.metric_sections(), restricted to what a ClusterNode
        actually runs (shard engines, tasks, host monitor)."""
        from ..common import monitor
        docs = 0
        shards = 0
        with self._shards_lock:
            holders = list(self._shards.values())
        for holder in holders:
            if holder.engine is not None:
                docs += holder.engine.doc_count()
                shards += 1
        proc = monitor.process_stats()
        os_st = monitor.os_stats()
        load = os_st.get("load_average") or [0.0]
        from ..serving.qos import hedge_snapshot
        from .recovery import snapshot as _recovery_snapshot
        sections = {
            "node": (None, {"docs": docs, "shards": shards}),
            # node-local mesh reduce (ISSUE 11): host-reduce programs this
            # node ran (data-node side), declines down the fan-out ladder,
            # errors, and coordinator-side pre-reduced merges —
            # es_search_mesh_host_reduce_dispatches_total et al.
            "search": (None, {
                "mesh_host_reduce_dispatches_total":
                    self.host_reduce_stats["dispatches"],
                "mesh_host_reduce_declined_total":
                    self.host_reduce_stats["declined"],
                "mesh_host_reduce_errors_total":
                    self.host_reduce_stats["errors"],
                "mesh_host_reduce_merges_total":
                    self.host_reduce_stats["merges"],
                # pod reduce (ISSUE 19): coordinator-side merges whose
                # pre-reduced message crossed a host boundary (ONE DCN
                # hop per remote node), and the raw cross-host hop count
                "pod_reduce_dispatches_total":
                    self.host_reduce_stats["pod_dispatches"],
                "pod_reduce_dcn_hops_total":
                    self.host_reduce_stats["dcn_hops"]}),
            # hedged-read outcomes + per-class transport send queues
            # (ISSUE 9): es_search_hedged_total{outcome=},
            # es_transport_class_queue_depth{class=}
            "search_hedged": ("outcome",
                              {o: {"total": c}
                               for o, c in hedge_snapshot().items()}),
            # peer-recovery stream counters (ISSUE 15):
            # es_recovery_bytes_total, es_recovery_throttle_waits_total...
            # process-wide (cluster/recovery.py) — every node scrapes the
            # same truth the tests' throttle-compliance check reads
            "recovery": (None, dict(_recovery_snapshot())),
            # per-decider allocation vetoes:
            # es_allocation_decider_vetoes_total{decider=}
            "allocation_decider": ("decider",
                                   {name: {"vetoes_total": n}
                                    for name, n
                                    in self.deciders.vetoes.items()}),
            "tasks": (None, self.tasks.stats()),
            "process": (None, {
                "resident_bytes": proc.get("mem", {})
                .get("resident_in_bytes", 0),
                "threads": proc.get("threads", 0)}),
            "os": (None, {"load_1m": load[0],
                          "cpu_percent": os_st["cpu"]["percent"]}),
        }
        class_stats = getattr(self.transport.network, "class_stats", None)
        if class_stats is not None:          # TcpTransport has no classes
            sections["transport_class"] = ("class", class_stats())
        # per-transport-class latency EWMAs (ISSUE 19): the "dcn" class
        # gets its own deadline so cross-host hops never poison the ICI
        # hedge deadline — es_transport_latency_ewma_ms{class=}
        from ..serving.qos import transport_latency_snapshot
        lat = transport_latency_snapshot()
        if lat:
            sections["transport_latency"] = (
                "class", {c: {"ewma_ms": v["ewma_ms"],
                              "deadline_ms": v["deadline_ms"],
                              "observations_total": v["n"]}
                          for c, v in lat.items()})
        # fault-injection accounting (ISSUE 14): both transports count the
        # faults they actually applied — es_transport_faults_injected_total
        fault_stats = getattr(self.transport.network, "fault_stats", None)
        if fault_stats is not None:
            sections["transport"] = (None, fault_stats())
        return sections

    def _on_node_metrics(self, from_id: str, req: Any) -> dict:
        return {"sections": self.metric_sections()}

    def nodes_metric_sections(self) -> dict:
        """Fan out the metrics action to every live node; live nodes whose
        handler errors surface as failure entries (the nodes template,
        same contract as nodes_stats)."""
        state = self.cluster.current()
        out: dict = {}
        failures: list = []
        for node_id in sorted(state.nodes):
            try:
                if node_id == self.node_id:
                    out[node_id] = self.metric_sections()
                else:
                    out[node_id] = self.transport.send(
                        node_id, A_NODE_METRICS, {})["sections"]
            except ConnectTransportException:
                continue              # dead node: absent from the map
            except RemoteTransportException as e:
                failures.append({"node": node_id, "reason": str(e)})
        return {"sections_by_node": out, "failures": failures}

    def nodes_stats(self) -> dict:
        """Coordinator-side fan-out to every live node (the nodes
        template, ref TransportNodesOperationAction)."""
        state = self.cluster.current()
        out: dict = {}
        failures: list = []
        for node_id in sorted(state.nodes):
            try:
                if node_id == self.node_id:
                    out[node_id] = self._on_node_stats(self.node_id, {})
                else:
                    out[node_id] = self.transport.send(
                        node_id, A_NODE_STATS, {})
            except ConnectTransportException:
                continue              # dead node: absent from the map
            except RemoteTransportException as e:
                # LIVE node whose handler errored: report, don't hide
                # (ref TransportNodesOperationAction FailedNodeException)
                failures.append({"node": node_id, "reason": str(e)})
        return {"nodes": out, "failures": failures}

    def _on_fs_stats(self, from_id: str, req: Any) -> dict:
        """Per-node disk usage for the master's ClusterInfoService
        (ref TransportNodesStatsAction fs metric)."""
        import shutil
        try:
            du = shutil.disk_usage(self.data_path)
            return {"total": du.total, "free": du.free}
        except OSError:
            return {"total": 0, "free": 0}

    def refresh_cluster_info(self) -> None:
        """Master-side sampling round: every live node's disk usage
        (ref InternalClusterInfoService 30s cadence — here pulled during
        fault-detection rounds)."""
        from .info import DiskUsage
        state = self.cluster.current()
        for node_id in state.nodes:
            if node_id == self.node_id:
                out = self._on_fs_stats(self.node_id, {})
            else:
                try:
                    out = self.transport.send(node_id, A_FS_STATS, {})
                except (ConnectTransportException,
                        RemoteTransportException):
                    continue
            self.cluster_info.usages[node_id] = DiskUsage(
                node_id, int(out.get("total", 0)), int(out.get("free", 0)))

    # -- fault detection (ref discovery/zen/fd/, SURVEY §5.3) ----------

    def fault_detection_round(self) -> None:
        """On the master: ping everyone; below quorum STEP DOWN (the
        ZenDiscovery.java:500-596 rejoin-on-quorum-loss guard), otherwise
        drop the dead (NodesFaultDetection). On a non-master: ping the
        master; if gone, elect (MasterFaultDetection + min-id election).
        Masterless: discover a master via the seed list and rejoin, or
        bootstrap an election if a quorum of seeds agrees there is none."""
        state = self.cluster.current()
        if state.master_node == self.node_id:
            self.refresh_cluster_info()   # disk usages for the deciders
            dead = []
            for node_id in sorted(state.nodes):
                if node_id == self.node_id:
                    continue
                try:
                    self.transport.send(node_id, A_PING, {})
                except (ConnectTransportException, RemoteTransportException):
                    dead.append(node_id)
            live_count = len(state.nodes) - len(dead)
            if live_count < self.minimum_master_nodes:
                self._step_down()
                return
            for node_id in dead:
                self._remove_node(node_id)
        elif state.master_node is not None:
            try:
                resp = self.transport.send(state.master_node, A_PING, {})
                if resp.get("master") != state.master_node:
                    # our master stepped down (quorum loss): detach and go
                    # find whoever the majority elected
                    self.cluster.reset()
                    self._masterless_round()
                elif not resp.get("member", True):
                    # the master dropped us while we were partitioned
                    # away (MasterFaultDetection's node-does-not-exist
                    # contract): reset and rejoin fresh — the master's
                    # next publish replaces our stale state wholesale
                    self.rejoin(state.master_node)
            except (ConnectTransportException, RemoteTransportException):
                self._elect_after_master_loss(state)
        else:
            self._masterless_round()

    def _step_down(self) -> None:
        """Local-only demotion: no publish (we can't reach a quorum anyway).
        The next masterless round rejoins whatever master the majority
        elected — at which point the majority's state replaces ours and any
        writes acked during our minority reign are discarded (the same
        acked-write-loss window the reference documents for quorum loss)."""
        def task(cur: ClusterState) -> None:
            if cur.master_node != self.node_id:
                return None
            st = cur.mutate()
            st.data["master_node"] = None
            self.cluster.apply_local(st)
            return None     # already applied; nothing to publish
        self.cluster.submit_task("step-down[no quorum]", task, wait=False)

    def _masterless_round(self) -> None:
        """Find a live master through the seed list (the LocalTransport
        registry doubles as the unicast ping seed list) and rejoin it; if
        nobody has a master and we'd win a quorum election, take over."""
        seeds = [n for n in self.transport.network.connected_nodes()
                 if n != self.node_id]
        live = [self.node_id]
        masters: set[str] = set()
        for node_id in seeds:
            try:
                resp = self.transport.send(node_id, A_PING, {})
                live.append(node_id)
                if resp.get("master"):
                    masters.add(resp["master"])
            except (ConnectTransportException, RemoteTransportException):
                continue
        for master_id in sorted(masters):
            if master_id == self.node_id:
                continue
            try:
                self.rejoin(master_id)
                return
            except (ConnectTransportException, RemoteTransportException,
                    NoMasterException):
                continue
        if len(live) < self.minimum_master_nodes:
            return
        if min(live) == self.node_id:
            def task(cur: ClusterState) -> ClusterState:
                st = cur.mutate()
                st.data["master_node"] = self.node_id
                st.nodes[self.node_id] = {"id": self.node_id,
                                          "name": self.node_id}
                for node_id in list(st.nodes):
                    if node_id not in live:
                        remove_node(st, node_id, decider=self.deciders)
                return st
            self.cluster.submit_task("become-master[bootstrap]", task)

    def rejoin(self, master_id: str) -> None:
        """Reset local cluster state and join `master_id` fresh — the path a
        healed minority node takes back into the majority. The master's next
        publish replaces our state wholesale; our reconciler then drops any
        shards the majority no longer assigns to us."""
        self.cluster.reset()
        self.join(master_id)

    def _elect_after_master_loss(self, state: ClusterState) -> None:
        """Min-id election among reachable members, guarded by the
        minimum_master_nodes quorum (ref ZenDiscovery.java:500-535 — losing
        quorum means NO master, not a split brain)."""
        dead_master = state.master_node
        live = [self.node_id]
        for node_id in sorted(state.nodes):
            if node_id in (self.node_id, dead_master):
                continue
            try:
                self.transport.send(node_id, A_PING, {})
                live.append(node_id)
            except (ConnectTransportException, RemoteTransportException):
                pass
        if len(live) < self.minimum_master_nodes:
            return      # no quorum: stay masterless rather than split-brain
        new_master = min(live)
        if new_master != self.node_id:
            return      # the winner will notice on its own round

        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            st.data["master_node"] = self.node_id
            if dead_master is not None:
                remove_node(st, dead_master, decider=self.deciders)
            return st
        self.cluster.submit_task("become-master", task)

    def _remove_node(self, node_id: str) -> None:
        def task(cur: ClusterState) -> ClusterState | None:
            if node_id not in cur.nodes:
                return None
            st = cur.mutate()
            remove_node(st, node_id, decider=self.deciders)
            return st
        self.cluster.submit_task(f"node-left[{node_id}]", task, wait=False)

    def _on_node_failed(self, from_id: str, req: dict) -> dict:
        """A peer reports a node unreachable (the reference treats transport
        disconnects as immediate failures, MasterFaultDetection.java:183-187).
        Verify before acting — the reporter's link may be the broken one."""
        node_id = req["node"]
        try:
            self.transport.send(node_id, A_PING, {})
            return {"removed": False}
        except (ConnectTransportException, RemoteTransportException):
            self._remove_node(node_id)
            return {"removed": True}

    # ------------------------------------------------------------------
    # master metadata ops (ref cluster/metadata/MetaData*Service)
    # ------------------------------------------------------------------

    def _master_call(self, action: str, payload: dict) -> Any:
        state = self.cluster.current()
        if state.master_node is None:
            raise NoMasterException("no elected master")
        if state.master_node == self.node_id:
            return self.transport._handle(self.node_id, action, payload)
        return self.transport.send(state.master_node, action, payload)

    def create_index(self, name: str, settings: dict | None = None,
                     mappings: dict | None = None) -> None:
        self._master_call(A_CREATE_INDEX, {
            "index": name, "settings": settings or {},
            "mappings": mappings or {}})

    def delete_index(self, name: str) -> None:
        self._master_call(A_DELETE_INDEX, {"index": name})

    def put_mapping(self, index: str, type_name: str, mapping: dict) -> None:
        self._master_call(A_PUT_MAPPING, {
            "index": index, "type": type_name, "mapping": mapping})

    def _on_create_index(self, from_id: str, req: dict) -> dict:
        name, settings = req["index"], req.get("settings") or {}
        n_shards = int(settings.get("number_of_shards",
                                    settings.get("index.number_of_shards", 1)))
        n_replicas = int(settings.get(
            "number_of_replicas", settings.get("index.number_of_replicas", 1)))

        def task(cur: ClusterState) -> ClusterState:
            if name in cur.indices:
                raise ValueError(f"index [{name}] already exists")
            st = cur.mutate()
            st.indices[name] = {"settings": settings,
                                "mappings": req.get("mappings") or {},
                                "aliases": []}
            st.routing[name] = new_index_routing(n_shards, n_replicas)
            allocate(st, decider=self.deciders)
            return st
        self.cluster.submit_task(f"create-index[{name}]", task)
        return {"acknowledged": True}

    def _on_delete_index(self, from_id: str, req: dict) -> dict:
        name = req["index"]

        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            st.indices.pop(name, None)
            st.routing.pop(name, None)
            return st
        self.cluster.submit_task(f"delete-index[{name}]", task)
        return {"acknowledged": True}

    # -- cluster-level metadata services (ref cluster/metadata/
    #    MetaDataIndexAliasesService, MetaDataUpdateSettingsService,
    #    MetaDataIndexStateService) ---------------------------------------

    def put_alias(self, index: str, alias: str,
                  props: dict | None = None) -> None:
        self._master_call(A_PUT_ALIAS, {"index": index, "alias": alias,
                                        "props": props or {}})

    def delete_alias(self, index: str, alias: str) -> None:
        self._master_call(A_DELETE_ALIAS, {"index": index, "alias": alias})

    def update_index_settings(self, index: str, settings: dict) -> None:
        self._master_call(A_UPDATE_SETTINGS, {"index": index,
                                              "settings": settings})

    def close_index(self, index: str) -> None:
        self._master_call(A_CLOSE_INDEX, {"index": index})

    def open_index(self, index: str) -> None:
        self._master_call(A_OPEN_INDEX, {"index": index})

    def _on_put_alias(self, from_id: str, req: dict) -> dict:
        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            meta = st.indices.get(req["index"])
            if meta is None:
                raise KeyError(f"no such index [{req['index']}]")
            aliases = meta.get("aliases")
            if not isinstance(aliases, dict):     # legacy list form
                aliases = {a: {} for a in (aliases or [])}
            aliases[req["alias"]] = req.get("props") or {}
            meta["aliases"] = aliases
            return st
        self.cluster.submit_task(f"put-alias[{req['alias']}]", task)
        return {"acknowledged": True}

    def _on_delete_alias(self, from_id: str, req: dict) -> dict:
        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            meta = st.indices.get(req["index"])
            if meta is None:
                raise KeyError(f"no such index [{req['index']}]")
            aliases = meta.get("aliases")
            if isinstance(aliases, dict):
                aliases.pop(req["alias"], None)
            elif isinstance(aliases, list) and req["alias"] in aliases:
                aliases.remove(req["alias"])
            return st
        self.cluster.submit_task(f"delete-alias[{req['alias']}]", task)
        return {"acknowledged": True}

    def _on_update_settings(self, from_id: str, req: dict) -> dict:
        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            meta = st.indices.get(req["index"])
            if meta is None:
                raise KeyError(f"no such index [{req['index']}]")
            settings = dict(meta.get("settings") or {})
            settings.update(req.get("settings") or {})
            meta["settings"] = settings
            # a replica-count change RESIZES the routing table live
            # (ref MetaDataUpdateSettingsService.updateSettings ->
            # routing table rebuild + reallocation). Read the count from
            # the UPDATE REQUEST (either key form) — the merged map holds
            # stale creation-time values under the other key
            upd = req.get("settings") or {}
            nr = upd.get("index.number_of_replicas",
                         upd.get("number_of_replicas"))
            if nr is not None:
                nr = int(nr)
                for copies in st.routing.get(req["index"], []):
                    replicas = [c for c in copies if not c["primary"]]
                    # shed UNASSIGNED/INITIALIZING copies before STARTED
                    # ones (the reference drops ignored/unassigned first)
                    order = {UNASSIGNED: 0, INITIALIZING: 1, STARTED: 2}
                    replicas.sort(key=lambda c: order.get(c["state"], 1))
                    for surplus in replicas[: max(len(replicas) - nr, 0)]:
                        copies.remove(surplus)
                    for _ in range(nr - len(replicas)):
                        copies.append({"node": None, "primary": False,
                                       "state": UNASSIGNED})
                allocate(st, decider=self.deciders)
            return st
        self.cluster.submit_task(f"update-settings[{req['index']}]", task)
        return {"acknowledged": True}

    def _on_close_index(self, from_id: str, req: dict) -> dict:
        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            meta = st.indices.get(req["index"])
            if meta is None:
                raise KeyError(f"no such index [{req['index']}]")
            meta["state"] = "close"
            # deallocate: reconcilers drop local shards; data dirs remain
            st.routing.pop(req["index"], None)
            return st
        self.cluster.submit_task(f"close-index[{req['index']}]", task)
        return {"acknowledged": True}

    def _on_open_index(self, from_id: str, req: dict) -> dict:
        name = req["index"]
        # gateway-style primary allocation: probe which nodes still hold
        # shard data from before the close, and pin primaries there so
        # reopening recovers the documents (ref gateway/
        # GatewayAllocator primary-by-existing-copy allocation)
        holders: dict[int, str] = {}
        for node_id in sorted(self.cluster.current().nodes):
            try:
                if node_id == self.node_id:
                    out = self._on_shard_data(self.node_id, {"index": name})
                else:
                    out = self.transport.send(node_id, A_SHARD_DATA,
                                              {"index": name})
            except (ConnectTransportException, RemoteTransportException):
                continue
            for sid in out.get("shards", []):
                holders.setdefault(int(sid), node_id)

        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            meta = st.indices.get(name)
            if meta is None:
                raise KeyError(f"no such index [{name}]")
            if meta.get("state") != "close":
                return None
            meta["state"] = "open"
            settings = meta.get("settings") or {}

            def get_s(key, default):
                # prefixed key WINS: updates arrive as index.* and must
                # not be shadowed by the stale bare creation-time key
                return settings.get(f"index.{key}",
                                    settings.get(key, default))
            routing = new_index_routing(int(get_s("number_of_shards", 1)),
                                        int(get_s("number_of_replicas", 1)))
            for sid, copies in enumerate(routing):
                node_id = holders.get(sid)
                if node_id is not None and node_id in st.nodes:
                    copies[0]["node"] = node_id
                    copies[0]["state"] = INITIALIZING
            st.routing[name] = routing
            allocate(st, decider=self.deciders)
            return st
        self.cluster.submit_task(f"open-index[{name}]", task)
        return {"acknowledged": True}

    def _on_shard_data(self, from_id: str, req: dict) -> dict:
        """Which shards of `index` have data dirs on this node (the
        gateway allocator's TransportNodesListGatewayStartedShards)."""
        base = os.path.join(self.data_path, "indices", req["index"])
        out = []
        if os.path.isdir(base):
            for d in os.listdir(base):
                if d.isdigit():
                    out.append(int(d))
        return {"shards": sorted(out)}

    def _on_put_mapping(self, from_id: str, req: dict) -> dict:
        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            meta = st.indices.get(req["index"])
            if meta is None:
                raise KeyError(f"no such index [{req['index']}]")
            cur_map = meta.setdefault("mappings", {})
            merged = MapperService(mappings=cur_map)
            merged.merge(req["type"], req["mapping"])
            meta["mappings"] = merged.mappings_dict()
            return st
        self.cluster.submit_task(f"put-mapping[{req['index']}]", task)
        return {"acknowledged": True}

    # ------------------------------------------------------------------
    # reconciler (ref IndicesClusterStateService.clusterChanged :150)
    # ------------------------------------------------------------------

    def _apply_cluster_state(self, state: ClusterState) -> None:
        with self._shards_lock:
            # mappings from metadata
            for index, meta in state.indices.items():
                svc = self._mappers.get(index)
                if svc is None:
                    self._mappers[index] = MapperService(
                        mappings=meta.get("mappings") or {})
                else:
                    for tname, m in (meta.get("mappings") or {}).items():
                        svc.merge(tname, m)
            # drop shards (and whole indices) no longer assigned here
            # (ref indices/store/IndicesStore state-driven GC)
            assigned = {(i, s) for i, s, _ in
                        state.assigned_shards(self.node_id)}
            closed = {i for i, m in state.indices.items()
                      if (m or {}).get("state") == "close"}
            for key in [k for k in self._shards
                        if k not in assigned or k[0] not in state.indices]:
                holder = self._shards.pop(key)
                # an in-flight recovery pull (another thread, outside this
                # lock) observes the flag between chunks and aborts —
                # cancel_relocations_for / reassignment cancels cleanly
                # instead of streaming to a dead-end copy (ISSUE 15)
                holder.cancel_recovery = True
                if holder.engine is not None:
                    holder.drop_searcher()
                    holder.engine.close()
                # a CLOSED index keeps its shard data on disk (the engine
                # shuts down, the files stay for reopen — ref
                # MetaDataIndexStateService close semantics); only deleted
                # or relocated-away shards GC their directories
                if key[0] not in closed:
                    import shutil
                    shutil.rmtree(self._shard_path(*key),
                                  ignore_errors=True)
            # GC data dirs of indices DELETED from the metadata entirely —
            # including ones closed first (their shards left self._shards
            # at close time, so the loop above can't see them)
            idx_root = os.path.join(self.data_path, "indices")
            if os.path.isdir(idx_root):
                import shutil
                for iname in os.listdir(idx_root):
                    if iname not in state.indices:
                        shutil.rmtree(os.path.join(idx_root, iname),
                                      ignore_errors=True)
            for index in [i for i in self._mappers
                          if i not in state.indices]:
                del self._mappers[index]
            todo = [(i, s, c) for i, s, c in
                    state.assigned_shards(self.node_id)
                    if c["state"] == INITIALIZING]
        # recoveries run outside _shards_lock: they call into other nodes
        for index, sid, copy_ in todo:
            self._init_shard(state, index, sid, copy_)

    def _shard_path(self, index: str, sid: int) -> str:
        return os.path.join(self.data_path, "indices", index, str(sid))

    def _init_shard(self, state: ClusterState, index: str, sid: int,
                    copy_: dict) -> None:
        key = (index, sid)
        with self._shards_lock:
            holder = self._shards.setdefault(key, _ShardHolder())
        mappers = self._mappers[index]
        if copy_["primary"]:
            if holder.engine is None:
                holder.engine = Engine(self._shard_path(index, sid), mappers)
            # else: in-place promotion of a copy we already host
            self._report_started(index, sid, copy_.get("aid"))
            return
        # replica / relocation target: peer recovery over the seam. An
        # EXISTING local engine is stale by definition — this copy was
        # unassigned (e.g. after a failed replication hop) and must re-sync,
        # or it would come back STARTED while missing acked writes.
        aid = copy_.get("aid")
        with holder.lock:
            pulled = (aid is not None and holder.recovery_aid == aid
                      and not holder.recovering and holder.engine is not None)
        if pulled:
            # THIS era's pull is complete and its engine is the live copy:
            # a state published before the master took our report still
            # shows the copy INITIALIZING. Report again (the first report
            # may be the one that was lost) and pull nothing: a second
            # pull closes a live engine, and a relocation target's source
            # is gone once the handoff is published — that pull fails and
            # leaves a STARTED copy, a primary even, with no engine
            self._report_started(index, sid, aid)
            return
        source_node = copy_.get("recover_from")
        if source_node is None:
            primary = state.primary_of(index, sid)
            if primary is None \
                    or primary["state"] not in (STARTED, RELOCATING):
                return      # allocator shouldn't have scheduled this; wait
            source_node = primary["node"]
        with holder.lock:
            if holder.recovering:
                if holder.recovery_aid == aid:
                    return      # THIS pull is already in flight
                # an OLDER era's pull is still streaming (its started
                # report would be dropped by the master's aid fence):
                # abort it and re-enter once its thread exits — without
                # this handoff the new assignment would sit INITIALIZING
                # with no pull behind it
                holder.cancel_recovery = True
                if not holder.reinit_pending:
                    holder.reinit_pending = True
                    threading.Thread(
                        target=self._reinit_after_cancel,
                        args=(index, sid, holder),
                        name=f"recovery-reinit[{self.node_id}]"
                             f"[{index}][{sid}]",
                        daemon=True).start()
                return
            holder.recovering = True
            holder.recovery_aid = aid
            holder.cancel_recovery = False
            if holder.engine is not None:
                holder.drop_searcher()
                holder.engine.close()
                holder.engine = None
        # the stream itself runs OFF the state-apply thread (ref: the
        # dedicated recovery thread pool). Applied inline it would block
        # the master's publish for the whole transfer, serializing every
        # later state task behind one slow stream — which is exactly what
        # made mid-stream cancellation (cancel_relocations_for, index
        # deletion) unreachable. The holder is registered with
        # `recovering` set BEFORE this returns, so replica ops arriving
        # early buffer into `pending` instead of failing.
        threading.Thread(
            target=self._run_peer_recovery,
            args=(index, sid, holder, source_node, mappers,
                  copy_.get("aid")),
            name=f"recovery[{self.node_id}][{index}][{sid}]",
            daemon=True).start()

    def _reinit_after_cancel(self, index: str, sid: int, holder) -> None:
        """A newer assignment era superseded an in-flight pull: wait for
        the aborted stream's thread to exit, then re-run _init_shard
        against the CURRENT state (the era that displaced it — or an even
        newer one; _init_shard re-reads the copy either way)."""
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with holder.lock:
                if not holder.recovering:
                    holder.reinit_pending = False
                    break
            time.sleep(0.01)
        else:
            with holder.lock:
                holder.reinit_pending = False
            return
        if self.closed:
            return
        state = self.cluster.current()
        if index not in state.routing:
            return      # deleted while the old pull drained
        copy_ = next(
            (c for c in state.shard_copies(index, sid)
             if c["node"] == self.node_id and c["state"] == INITIALIZING),
            None)
        if copy_ is not None and index in self._mappers:
            self._init_shard(state, index, sid, copy_)

    def _run_peer_recovery(self, index: str, sid: int, holder,
                           source_node: str, mappers,
                           aid: int | None = None) -> None:
        path = self._shard_path(index, sid)
        from .recovery import RecoveryCancelled, record
        rec = {"index": index, "shard": sid, "source": source_node,
               "target": self.node_id, "stage": "init",
               "files_total": 0, "files_reused": 0, "bytes_total": 0,
               "bytes_recovered": 0, "throttle_waits": 0, "retries": 0,
               "start_s": time.monotonic(),
               "start_time_ms": self._wall_ms(), "elapsed_ms": 0.0}
        with self._recoveries_lock:
            self.recoveries[(index, sid)] = rec
        try:
            with self.tracer.request(
                    "recovery",
                    attrs={"index": index, "shard": sid,
                           "source": source_node}):
                ok = self._recover_files_from(source_node, index, sid,
                                              path, holder=holder, rec=rec)
        except RecoveryCancelled:
            # a newer cluster state unassigned this copy mid-stream:
            # abandon the pull, GC the partial files, report nothing
            rec["stage"] = "cancelled"
            record("cancelled_total")
            import shutil
            shutil.rmtree(path, ignore_errors=True)
            with holder.lock:
                holder.recovering = False
            rec["elapsed_ms"] = (time.monotonic() - rec["start_s"]) * 1000
            return
        except (ConnectTransportException, RemoteTransportException):
            ok = False
        rec["elapsed_ms"] = (time.monotonic() - rec["start_s"]) * 1000
        if not ok:
            rec["stage"] = "failed"
            with holder.lock:
                holder.recovering = False
            # tell the master so it unassigns/reverts THIS assignment and
            # re-allocates now — waiting for an incidental later publish
            # leaves the copy INITIALIZING (and the cluster un-green)
            # indefinitely
            self._report_failed(index, sid, aid)
            return
        rec["stage"] = "done"
        record("completed_total")
        with holder.lock:
            holder.engine = Engine(path, mappers)
            for op in holder.pending:
                self._apply_replica_op(holder, op)
            holder.pending.clear()
            holder.recovering = False
        self._report_started(index, sid, aid)

    RECOVERY_CHUNK = 1 << 19   # 512 KiB per RPC — bounded memory both sides
    RECOVERY_RETRIES = 3       # per-chunk resend attempts before giving up
    RECOVERY_RETRY_BACKOFF_S = 0.05   # doubled per attempt

    def _recovery_rate(self) -> float:
        """Live `indices.recovery.max_bytes_per_sec` (cluster settings;
        default 40mb like the reference's RecoverySettings). 0 / negative
        disables the throttle."""
        from .recovery import parse_bytes
        st = self.cluster.current().data.get("settings") or {}
        return parse_bytes(
            st.get("indices.recovery.max_bytes_per_sec", "40mb"))

    def _check_cancel(self, holder, index: str, sid: int) -> None:
        if holder is not None and holder.cancel_recovery:
            from .recovery import RecoveryCancelled
            raise RecoveryCancelled(f"[{index}][{sid}] unassigned")

    def _recovery_chunk_call(self, source: str, payload: dict,
                             rec: dict | None, holder=None) -> dict:
        """One chunk RPC with retry-with-backoff: a transient send fault
        (chaos drop, queue timeout) resends the SAME bounded read —
        chunk reads are pure, so the retry is idempotent by construction.
        The cancel flag wins over the retry loop: once this copy is
        unassigned, a failing source (often deleted along with the copy)
        must surface as a clean cancellation, not a retry storm ending
        in `failed`. The final failure propagates and aborts."""
        from .recovery import record
        for attempt in range(self.RECOVERY_RETRIES + 1):
            self._check_cancel(holder, payload["index"], payload["shard"])
            try:
                return self.transport.send(source, A_RECOVERY_CHUNK,
                                           payload)
            except (ConnectTransportException, RemoteTransportException):
                self._check_cancel(holder, payload["index"],
                                   payload["shard"])
                if attempt >= self.RECOVERY_RETRIES:
                    raise
                record("retries_total")
                if rec is not None:
                    rec["retries"] += 1
                time.sleep(self.RECOVERY_RETRY_BACKOFF_S * (2 ** attempt))
        raise AssertionError("unreachable")

    def _recover_files_from(self, source: str, index: str, sid: int,
                            path: str, holder=None,
                            rec: dict | None = None) -> bool:
        """STREAMING, delta peer recovery (ref indices/recovery/
        RecoverySourceHandler.java:149-195): fetch the source's file
        manifest, REUSE local files whose name+size+checksum already match
        (the checksum-delta phase-1 optimization), stream the rest in
        bounded chunks, verify each file's checksum on arrival. Never holds
        more than one chunk in memory per side. Each received chunk pays
        the node-wide token bucket (`indices.recovery.max_bytes_per_sec`),
        failed sends retry with backoff, and the holder's cancel flag is
        honored between chunks (RecoveryCancelled)."""
        import zlib

        from .recovery import record

        self._check_cancel(holder, index, sid)
        manifest = self.transport.send(source, A_RECOVERY,
                                       {"index": index, "shard": sid})
        os.makedirs(path, exist_ok=True)
        want = {f["name"]: f for f in manifest["files"]}
        if rec is not None:
            rec["stage"] = "index"
            rec["files_total"] = len(want)
            rec["bytes_total"] = sum(f["size"] for f in want.values())
        # drop local files not in the manifest — INCLUDING the translog
        # (a stale translog would replay old ops over recovered state)
        for root, _dirs, files in os.walk(path):
            for fn in files:
                fp = os.path.join(root, fn)
                if os.path.relpath(fp, path) not in want:
                    os.remove(fp)
        reused = 0
        for rel, meta in want.items():
            self._check_cancel(holder, index, sid)
            dst = os.path.join(path, rel)
            if os.path.exists(dst) \
                    and os.path.getsize(dst) == meta["size"] \
                    and _crc_prefix(dst, meta["size"],
                                    self.RECOVERY_CHUNK) == meta["crc"]:
                reused += 1
                if rec is not None:
                    rec["files_reused"] += 1
                continue        # identical — skip the copy entirely
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            crc = 0
            with open(dst, "wb") as f:
                off = 0
                while off < meta["size"]:
                    self._check_cancel(holder, index, sid)
                    n = min(self.RECOVERY_CHUNK, meta["size"] - off)
                    t0 = time.monotonic_ns()
                    r = self._recovery_chunk_call(source, {
                        "index": index, "shard": sid, "file": rel,
                        "offset": off, "length": n}, rec, holder=holder)
                    got = len(r["data"])
                    # the TARGET pays the token bucket for what it just
                    # pulled — N concurrent recoveries share one budget
                    slept = self.recovery_throttle.acquire(got)
                    tracing.add_span("recovery_chunk", t0,
                                     time.monotonic_ns(), file=rel,
                                     offset=off, bytes=got,
                                     throttle_s=round(slept, 4))
                    record("bytes_total", got)
                    record("chunks_total")
                    if slept > 0.0:
                        record("throttle_waits_total")
                    if rec is not None:
                        rec["bytes_recovered"] += got
                        if slept > 0.0:
                            rec["throttle_waits"] += 1
                    f.write(r["data"])
                    crc = zlib.crc32(r["data"], crc)
                    off += got
                    if not r["data"]:
                        break
            if crc != meta["crc"]:
                return False        # torn read; retry on a later state
        return True

    def _report_started(self, index: str, sid: int,
                        aid: int | None = None) -> None:
        try:
            self._master_call(A_SHARD_STARTED, {
                "index": index, "shard": sid, "node": self.node_id,
                "aid": aid})
        except (NoMasterException, ConnectTransportException,
                RemoteTransportException):
            pass        # next publish/fault round sorts it out

    def _report_failed(self, index: str, sid: int,
                       aid: int | None = None) -> None:
        try:
            self._master_call(A_SHARD_FAILED, {
                "index": index, "shard": sid, "node": self.node_id,
                "aid": aid})
        except (NoMasterException, ConnectTransportException,
                RemoteTransportException):
            pass        # next publish/fault round sorts it out

    def _on_shard_started(self, from_id: str, req: dict) -> dict:
        index, sid, node_id = req["index"], req["shard"], req["node"]
        # allocation-id fence (ref AllocationId): a report only acts on
        # the assignment era it came from. Without this, a restarted
        # process's STALE report (its pre-kill pull completing late)
        # matched the copy's NEW assignment and marked STARTED a copy
        # whose actual pull had failed — a zombie serving nothing.
        aid = req.get("aid")

        def task(cur: ClusterState) -> ClusterState | None:
            if index not in cur.routing:
                return None
            st = cur.mutate()
            changed = False
            for c in st.routing[index][sid]:
                if c["node"] == node_id and c["state"] == INITIALIZING \
                        and (aid is None or c.get("aid") == aid):
                    if c.get("relocation"):
                        changed |= finish_relocation(st, index, sid, node_id)
                    else:
                        c["state"] = STARTED
                        c.pop("fresh", None)
                        changed = True
            if changed:
                allocate(st, decider=self.deciders)    # replicas may now be able to initialize
                rebalance(st, decider=self.deciders)   # ...and the next relocation wave can start
                return st
            return None
        self.cluster.submit_task(
            f"shard-started[{index}][{sid}]", task, wait=False)
        return {"ok": True}

    def _on_shard_failed(self, from_id: str, req: dict) -> dict:
        index, sid, node_id = req["index"], req["shard"], req["node"]
        # same allocation-id fence as shard-started: a late failure
        # notice from a previous era must not unassign (or revert the
        # relocation of) the copy's CURRENT, healthy assignment. A
        # report without an aid (legacy callers, harness) matches any.
        aid = req.get("aid")

        def task(cur: ClusterState) -> ClusterState | None:
            if index not in cur.routing:
                return None
            st = cur.mutate()
            changed = False
            copies = st.routing[index][sid]
            for c in [c for c in copies if c["node"] == node_id
                      and (aid is None or c.get("aid") == aid)]:
                if c.get("relocation"):
                    copies.remove(c)     # failed target: revert the move
                    for s in copies:
                        if s.get("relocating_to") == node_id:
                            s["state"] = STARTED
                            s.pop("relocating_to", None)
                    changed = True
                elif c["state"] == RELOCATING:
                    # failing SOURCE mid-move: the target's recovery
                    # source is gone, so drop the orphaned target AND
                    # clear the pointer — unassigning while leaving
                    # `relocating_to` behind is the zombie that made
                    # finish_relocation later double-handle the shard
                    # (ISSUE 15 race fix)
                    tgt = c.pop("relocating_to", None)
                    for t in [t for t in copies
                              if t.get("relocation")
                              and (t["node"] == tgt
                                   or t.get("recover_from") == node_id)]:
                        copies.remove(t)
                    if c["primary"]:
                        c["state"] = STARTED   # same revert as cancel
                    else:
                        c["node"] = None
                        c["state"] = UNASSIGNED
                    changed = True
                elif not c["primary"]:
                    c["node"] = None
                    c["state"] = UNASSIGNED
                    changed = True
            if changed:
                allocate(st, decider=self.deciders)
                # a failure reshapes the table: re-evaluate moves so an
                # interrupted drain (exclude filter, disk evacuation)
                # retries instead of stranding the shard on a vetoed node
                rebalance(st, decider=self.deciders)
                return st
            return None
        self.cluster.submit_task(
            f"shard-failed[{index}][{sid}][{node_id}]", task, wait=False)
        return {"ok": True}

    # -- recovery source (ref RecoverySourceHandler.java:149-195) -------

    def _on_recovery(self, from_id: str, req: dict) -> dict:
        """Recovery phase 1 START: flush under the engine write lock, then
        publish the file MANIFEST (name, size, crc). Segment files are
        write-once after flush, so chunk reads need no lock; ops acked
        after the lock releases reach the target through normal forwarding
        (idempotent by version). Ref RecoverySourceHandler.java:149-195 —
        the checksum manifest is what enables the delta-reuse phase."""
        holder = self._shards.get((req["index"], req["shard"]))
        if holder is None or holder.engine is None:
            raise UnavailableShardsException(
                f"not hosting [{req['index']}][{req['shard']}]")
        eng = holder.engine
        names: list[tuple[str, int]] = []
        with eng._lock:
            # lock held only for flush + size snapshot — checksums run
            # AFTER release (post-flush files are write-once/append-only,
            # so the [0, size) prefix is stable; code review r5)
            eng.flush()
            for fn in sorted(os.listdir(eng.path)):
                fp = os.path.join(eng.path, fn)
                if os.path.isfile(fp):
                    names.append((fn, os.path.getsize(fp)))
        files = [{"name": fn, "size": size,
                  "crc": _crc_prefix(os.path.join(eng.path, fn), size,
                                     self.RECOVERY_CHUNK)}
                 for fn, size in names]
        return {"files": files}

    def _on_recovery_chunk(self, from_id: str, req: dict) -> dict:
        """One bounded chunk of a write-once recovery file."""
        holder = self._shards.get((req["index"], req["shard"]))
        if holder is None or holder.engine is None:
            raise UnavailableShardsException(
                f"not hosting [{req['index']}][{req['shard']}]")
        fp = os.path.join(holder.engine.path, req["file"])
        length = min(int(req["length"]), self.RECOVERY_CHUNK)
        with open(fp, "rb") as f:
            f.seek(int(req["offset"]))
            return {"data": f.read(length)}

    # -- recovery progress + cluster settings (ISSUE 15) ----------------

    def _wall_ms(self) -> int:
        """Wall-clock ms WITH the chaos clock skew applied — used only
        for reported timestamps, never for durations or throttling."""
        return int((time.time() + self.clock_skew_s) * 1000)

    def _on_recovery_stats(self, from_id: str, req: Any) -> dict:
        """This node's per-shard recovery rows (target side) for the
        GET /_cat/recovery fan-out (ref RecoveryState / indices:monitor/
        recovery)."""
        rows = []
        with self._recoveries_lock:
            recs = [dict(r) for r in self.recoveries.values()]
        for row in recs:
            if row["stage"] not in ("done", "failed", "cancelled"):
                row["elapsed_ms"] = \
                    (time.monotonic() - row["start_s"]) * 1000
            row.pop("start_s", None)
            rows.append(row)
        return {"recoveries": rows}

    def cat_recovery(self) -> list[dict]:
        """Every node's recovery rows, sorted — GET /_cat/recovery."""
        state = self.cluster.current()
        rows: list[dict] = []
        for node_id in sorted(state.nodes):
            try:
                if node_id == self.node_id:
                    out = self._on_recovery_stats(self.node_id, {})
                else:
                    out = self.transport.send(node_id, A_RECOVERY_STATS,
                                              {})
            except (ConnectTransportException, RemoteTransportException):
                continue
            rows.extend(out.get("recoveries", []))
        rows.sort(key=lambda r: (r["index"], r["shard"], r["target"]))
        return rows

    def update_cluster_settings(self, settings: dict) -> dict:
        """PUT /_cluster/settings: merge into the live cluster-level
        settings map and reroute — the deciders read these live, so an
        exclude filter update starts draining on this very task."""
        return self._master_call(A_CLUSTER_SETTINGS,
                                 {"settings": settings})

    def _on_cluster_settings(self, from_id: str, req: dict) -> dict:
        upd = req.get("settings") or {}

        def task(cur: ClusterState) -> ClusterState:
            st = cur.mutate()
            cs = dict(st.data.get("settings") or {})
            for k, v in upd.items():
                if v is None:
                    cs.pop(k, None)     # null resets to default
                else:
                    cs[k] = v
            st.data["settings"] = cs
            # allocation settings changed: reroute under the new rules
            allocate(st, decider=self.deciders)
            rebalance(st, decider=self.deciders)
            return st
        self.cluster.submit_task("cluster-settings", task)
        return {"acknowledged": True, "transient": dict(upd)}

    def allocation_explain(self, index: str | None = None,
                           shard: int | None = None,
                           primary: bool | None = None) -> dict:
        """POST /_cluster/allocation/explain: run EVERY decider for one
        shard copy against EVERY node and report the per-decider
        verdicts (ref ClusterAllocationExplainAction). With no body the
        first unassigned copy explains itself, like the reference."""
        state = self.cluster.current()
        target = None
        if index is None:
            for iname, shards in state.routing.items():
                for sid, copies in enumerate(shards):
                    for c in copies:
                        if c["state"] == UNASSIGNED:
                            index, shard, target = iname, sid, c
                            break
                    if target is not None:
                        break
                if target is not None:
                    break
            if target is None:
                raise ValueError(
                    "unable to find any unassigned shards to explain — "
                    "specify index and shard")
        if index not in state.routing:
            raise KeyError(f"no such index [{index}]")
        sid = int(shard or 0)
        if sid >= len(state.routing[index]):
            raise KeyError(f"no such shard [{index}][{sid}]")
        copies = state.routing[index][sid]
        if target is None:
            if primary is not None:
                target = next((c for c in copies
                               if bool(c["primary"]) == bool(primary)),
                              copies[0])
            else:
                target = next((c for c in copies
                               if c["state"] == UNASSIGNED), copies[0])
        decisions = [self.deciders.explain(state, index, sid, n)
                     for n in sorted(state.nodes)]
        overall = {d["decision"] for d in decisions}
        can = "yes" if "YES" in overall else (
            "throttle" if "THROTTLE" in overall else "no")
        return {"index": index, "shard": sid,
                "primary": bool(target["primary"]),
                "current_state": target["state"].lower(),
                "current_node": target.get("node"),
                "can_allocate": can,
                "node_allocation_decisions": decisions}

    # ------------------------------------------------------------------
    # write path (ref TransportShardReplicationOperationAction.java:67)
    # ------------------------------------------------------------------

    def index_doc(self, index: str, doc_id: str | None, source: dict,
                  type_name: str = "_doc", routing: str | None = None,
                  _local_defer: set | None = None,
                  _replica_defer: dict | None = None, **kw) -> dict:
        if doc_id is None:
            import uuid
            doc_id = uuid.uuid4().hex[:20]
        return self._write_op(index, {
            "op": "index", "id": doc_id, "source": source, "type": type_name,
            "routing": routing, **kw}, local_defer=_local_defer,
            replica_defer=_replica_defer)

    def delete_doc(self, index: str, doc_id: str,
                   routing: str | None = None,
                   _local_defer: set | None = None,
                   _replica_defer: dict | None = None, **kw) -> dict:
        return self._write_op(index, {"op": "delete", "id": doc_id,
                                      "routing": routing, **kw},
                              local_defer=_local_defer,
                              replica_defer=_replica_defer)

    def bulk(self, operations: list[tuple[str, dict, dict | None]]) -> list[dict]:
        """(action, meta, source) ops -> per-item results (ref
        TransportBulkAction split-by-shard; per-item error contract).

        Group commit for locally-held primaries: their ops defer the
        per-op translog fsync and every touched local engine syncs ONCE
        at the end of the request (the reference's per-request
        durability). Ops forwarded to remote primaries keep their per-op
        durability — the remote node acks only after its own fsync.

        Replica replication batches the same way (ISSUE 11 satellite):
        locally-held primaries append each replica op to a per-target-NODE
        batch instead of sending one framed A_WRITE_R per op, and the
        whole request's replication rides ONE A_WRITE_R_BULK send per
        (node, request) on the bulk transport class — per-op apply/buffer
        semantics on the replica and per-shard failure reporting are
        unchanged."""
        items = []
        deferred: set = set()    # local engines written with sync=False
        replica_defer: dict[str, list[dict]] = {}   # node -> replica ops
        try:
            for op_t in operations:
                # (action, meta, source) or (action, meta, source, raw_len)
                action, meta, source = op_t[0], op_t[1], op_t[2]
                index = meta.get("_index")
                type_name = meta.get("_type", "_doc")
                doc_id = meta.get("_id")
                try:
                    if action in ("index", "create"):
                        r = self.index_doc(
                            index, doc_id, source, type_name=type_name,
                            routing=meta.get("_routing")
                            or meta.get("routing"),
                            op_type="create" if action == "create"
                            else "index",
                            _local_defer=deferred,
                            _replica_defer=replica_defer)
                        items.append({action: {
                            "_index": index, "_type": type_name,
                            "_id": r["_id"], "_version": r["_version"],
                            "status": 201 if r.get("created") else 200}})
                    elif action == "delete":
                        r = self.delete_doc(
                            index, doc_id,
                            routing=meta.get("_routing")
                            or meta.get("routing"),
                            _local_defer=deferred,
                            _replica_defer=replica_defer)
                        items.append({"delete": {
                            "_index": index, "_type": type_name,
                            "_id": doc_id,
                            "_version": r["_version"],
                            "found": r.get("found", True),
                            "status": 200 if r.get("found", True) else 404}})
                    else:
                        items.append({action: {
                            "status": 400,
                            "error": f"unsupported bulk action [{action}]"}})
                except VersionConflictException as e:
                    items.append({action: {"_index": index, "_id": doc_id,
                                           "status": 409, "error": str(e)}})
                except Exception as e:  # noqa: BLE001 — per-item contract
                    items.append({action: {"_index": index, "_id": doc_id,
                                           "status": 400, "error": str(e)}})
        finally:
            # the request's whole replication: ONE framed send per target
            # node (bulk transport class), replicas ack before we return
            self._flush_replica_batches(replica_defer)
            for eng in deferred:
                try:
                    eng.translog.sync()
                except Exception:  # noqa: BLE001 — engine may have closed
                    pass
        return items

    def _flush_replica_batches(self, replica_defer: dict) -> None:
        """Send each target node its batched replica ops as one framed
        A_WRITE_R_BULK message. Failure semantics match the per-op path:
        an unreachable/erroring replica node fails its shards to the
        master (the write itself already succeeded on the primary), and
        per-op not-hosted errors come back in the response."""
        for target, ops in replica_defer.items():
            if not ops:
                continue
            failed_shards: list[tuple[str, int]] = []
            try:
                r = self.transport.send(target, A_WRITE_R_BULK,
                                        {"ops": ops})
                failed_shards = [(f["index"], f["shard"])
                                 for f in r.get("failed", [])]
            except (ConnectTransportException, RemoteTransportException):
                failed_shards = sorted({(op["index"], op["shard"])
                                        for op in ops})
            for index, sid in failed_shards:
                aid = next((c.get("aid") for c
                            in self.cluster.current().shard_copies(index, sid)
                            if c["node"] == target), None)
                try:
                    self._master_call(A_SHARD_FAILED, {
                        "index": index, "shard": sid, "node": target,
                        "aid": aid})
                except Exception:  # noqa: BLE001 — masterless interim
                    pass

    def _on_replica_bulk(self, from_id: str, req: dict) -> dict:
        """Apply a batch of replica ops in arrival order — exactly the
        per-op A_WRITE_R semantics (buffer during recovery, external-
        version apply), one framed message for the whole request."""
        applied = 0
        failed: list[dict] = []
        for op in req.get("ops", []):
            holder = self._shards.get((op["index"], op["shard"]))
            if holder is None:
                failed.append({"index": op["index"], "shard": op["shard"]})
                continue
            with holder.lock:
                if holder.recovering or holder.engine is None:
                    holder.pending.append(op)
                else:
                    self._apply_replica_op(holder, op)
            applied += 1
        return {"applied": applied, "failed": failed}

    def _write_op(self, index: str, op: dict, timeout: float = 10.0,
                  local_defer: set | None = None,
                  replica_defer: dict | None = None) -> dict:
        """Route to the primary, retrying on stale routing / primary
        failover — the reference's retry-on-cluster-state-change loop.
        local_defer: when set and the primary is LOCAL, the engine write
        skips its per-op fsync and the engine joins the set for the
        caller's single end-of-request sync (bulk group commit).
        replica_defer: when set and the primary is LOCAL, replica ops
        batch per target node instead of one framed send per op — the
        caller flushes one A_WRITE_R_BULK per node at request end."""
        deadline = time.monotonic() + timeout
        last_err: Exception | None = None
        while time.monotonic() < deadline:
            state = self.cluster.current()
            meta = state.index_meta(index)
            if meta is None:
                # auto-create may lose a race with a concurrent creator or
                # hit a masterless interim — both just mean "retry the loop"
                try:
                    self.create_index(index, {}, {})
                except NoMasterException as e:
                    last_err = e
                    time.sleep(0.02)
                except Exception as e:  # noqa: BLE001
                    if "already exists" not in str(e):
                        raise
                    last_err = e
                continue
            n_shards = len(state.routing[index])
            sid = route_shard(op["id"], n_shards, op.get("routing"))
            primary = state.primary_of(index, sid)
            if primary is None \
                    or primary["state"] not in (STARTED, RELOCATING):
                time.sleep(0.02)
                continue
            payload = {**op, "index": index, "shard": sid}
            try:
                if primary["node"] == self.node_id:
                    if local_defer is not None:
                        payload = {**payload, "sync": False}
                    res = self._on_primary_write(self.node_id, payload,
                                                 _replica_defer=replica_defer)
                    if local_defer is not None:
                        holder = self._shards.get((index, sid))
                        if holder is not None and holder.engine is not None:
                            local_defer.add(holder.engine)
                    return res
                return self.transport.send(primary["node"], A_WRITE_P, payload)
            except ConnectTransportException as e:
                last_err = e
                # transport disconnect == immediate failure report
                try:
                    self._master_call(A_NODE_FAILED,
                                      {"node": primary["node"]})
                except Exception:  # noqa: BLE001 — masterless interim
                    pass
                # the dead node may have BEEN the master: drive a detection
                # round ourselves so an election can proceed (the reference
                # couples this to transport disconnect events)
                self.fault_detection_round()
                time.sleep(0.02)
            except RemoteTransportException as e:
                if e.error_type == "VersionConflictException":
                    raise VersionConflictException(op["id"], -1, -1) from e
                if e.error_type in ("UnavailableShardsException",
                                    "NoMasterException"):
                    # stale routing: the addressee no longer holds the
                    # primary (demoted/relocated) — refresh state and retry
                    last_err = e
                    time.sleep(0.02)
                    continue
                raise
        raise UnavailableShardsException(
            f"[{index}] shard for [{op['id']}] not available: {last_err}")

    def _on_primary_write(self, from_id: str, req: dict,
                          _replica_defer: dict | None = None) -> dict:
        index, sid = req["index"], req["shard"]
        holder = self._shards.get((index, sid))
        state = self.cluster.current()
        primary = state.primary_of(index, sid)
        if holder is None or holder.engine is None or primary is None \
                or primary["node"] != self.node_id:
            raise UnavailableShardsException(
                f"[{index}][{sid}] primary not on [{self.node_id}]")
        if req["op"] == "index":
            mappers = self._mappers[index]
            mv = mappers.mapping_version()
            res = holder.engine.index(
                req["id"], req["source"], type_name=req.get("type", "_doc"),
                version=req.get("version"),
                version_type=req.get("version_type", "internal"),
                op_type=req.get("op_type", "index"),
                sync=req.get("sync"))
            if mappers.mapping_version() != mv:
                # dynamic mapping delta -> master metadata, so COORDINATORS
                # can parse queries/sorts on the new fields (ref
                # TransportIndexAction.java:194-227 MappingUpdatedAction;
                # here post-ack because replicas re-derive deterministically)
                tname = req.get("type", "_doc")
                try:
                    self._master_call(A_PUT_MAPPING, {
                        "index": index, "type": tname,
                        "mapping": mappers._mappers[tname].mapping_dict()})
                except Exception:  # noqa: BLE001 — next write retries
                    pass
        else:
            res = holder.engine.delete(
                req["id"], version=req.get("version"),
                version_type=req.get("version_type", "internal"),
                sync=req.get("sync"))
        # sync replication fan-out (ref :118-120 — replicas ack before we do)
        replica_req = {"index": index, "shard": sid, "op": req["op"],
                       "id": req["id"], "source": req.get("source"),
                       "type": req.get("type", "_doc"),
                       "version": res.version}
        for c in state.shard_copies(index, sid):
            if c["primary"] or c["node"] in (None, self.node_id) \
                    or c["state"] not in (STARTED, INITIALIZING,
                                          RELOCATING):
                continue
            if _replica_defer is not None:
                # bulk batching: this op joins its target node's batch —
                # ONE framed send per (node, request) at request end
                _replica_defer.setdefault(c["node"], []).append(replica_req)
                continue
            try:
                self.transport.send(c["node"], A_WRITE_R, replica_req)
            except (ConnectTransportException, RemoteTransportException):
                # failed replica → master unassigns it (ref replica-failure
                # notification); the write itself still succeeds
                try:
                    self._master_call(A_SHARD_FAILED, {
                        "index": index, "shard": sid, "node": c["node"],
                        "aid": c.get("aid")})
                except Exception:  # noqa: BLE001
                    pass
        return {"_index": index, "_id": res.doc_id, "_version": res.version,
                "created": res.created, "found": res.found}

    def _on_replica_write(self, from_id: str, req: dict) -> dict:
        holder = self._shards.get((req["index"], req["shard"]))
        if holder is None:
            raise UnavailableShardsException(
                f"replica [{req['index']}][{req['shard']}] not hosted")
        with holder.lock:
            if holder.recovering or holder.engine is None:
                holder.pending.append(req)
                return {"buffered": True}
            self._apply_replica_op(holder, req)
        return {"applied": True}

    def _apply_replica_op(self, holder: _ShardHolder, req: dict) -> None:
        """External-version apply: strictly-newer wins, equal/older is a
        no-op (the op already arrived via recovery file copy)."""
        try:
            if req["op"] == "index":
                holder.engine.index(req["id"], req["source"],
                                    type_name=req.get("type", "_doc"),
                                    version=req["version"],
                                    version_type="external")
            else:
                holder.engine.delete(req["id"], version=req["version"],
                                     version_type="external")
        except VersionConflictException:
            pass

    # ------------------------------------------------------------------
    # reads
    # ------------------------------------------------------------------

    def get_doc(self, index: str, doc_id: str,
                routing: str | None = None) -> dict:
        """Single-shard read with retry-on-next-copy (ref action/support/
        single/shard/TransportShardSingleOperationAction.java:123 — a
        failed copy falls through to the next one in the iteration;
        round-robin start spreads read load across copies)."""
        state = self.cluster.current()
        if index not in state.routing:
            raise KeyError(f"no such index [{index}]")
        sid = route_shard(doc_id, len(state.routing[index]), routing)
        copies = [c for c in state.routing[index][sid]
                  if c["state"] == STARTED]
        if not copies:
            raise UnavailableShardsException(f"[{index}][{sid}]")
        # prefer local, then rotate (OperationRouting.java:144-154)
        rr = self._read_rr
        start = rr.get((index, sid), 0)
        rr[(index, sid)] = start + 1
        ordered = sorted(
            copies, key=lambda c: (c["node"] != self.node_id,))
        if ordered[0]["node"] != self.node_id and len(ordered) > 1:
            ordered = ordered[start % len(ordered):] \
                + ordered[: start % len(ordered)]
        payload = {"index": index, "shard": sid, "id": doc_id}
        last_err: Exception | None = None
        for c in ordered:
            try:
                if c["node"] == self.node_id:
                    return self._on_get(self.node_id, payload)
                return self.transport.send(c["node"], A_GET, payload)
            except (ConnectTransportException, RemoteTransportException,
                    UnavailableShardsException) as e:
                last_err = e             # dead/stale copy: try the next
        raise UnavailableShardsException(
            f"[{index}][{sid}]: all copies failed") from last_err

    def _on_get(self, from_id: str, req: dict) -> dict:
        holder = self._shards.get((req["index"], req["shard"]))
        if holder is None or holder.engine is None:
            raise UnavailableShardsException(f"[{req['index']}]")
        r = holder.engine.get(req["id"])
        return {"found": r.found, "_id": req["id"],
                "_version": r.version if r.found else None,
                "_source": r.source if r.found else None}

    # -- distributed search (QUERY_THEN_FETCH over the transport seam) --
    #
    # The FULL search body crosses the seam: query, sort, aggs, highlight,
    # suggest, rescore, knn, search_after, _source. The shard side parses
    # with ITS mappers and returns wire-encoded QuerySearchResult pieces
    # (doc keys + scores + materialized sort values + agg partials +
    # suggest partials); the coordinator reduces exactly like the
    # single-node controller. A DFS term-stats round runs first so every
    # shard scores with cluster-global IDF — distributed answers match the
    # single-node engine bit-for-bit (ref TransportSearchTypeAction.java:
    # 85-177 + SearchPhaseController.java:282-399 + DfsPhase.java:57-81).

    def search_shards(self, state: ClusterState, names: list[str],
                      preference: str | None = None) -> list[tuple]:
        """One STARTED copy per shard, round-robin across copies so
        replicas add read QPS (ref OperationRouting.java:144-154);
        preference=_local / _primary / _only_local supported."""
        targets: list[tuple[str, str, int]] = []   # (node, index, shard)
        for name in names:
            for sid in range(len(state.routing[name])):
                copies = state.started_copies(name, sid)
                if not copies:
                    raise UnavailableShardsException(f"[{name}][{sid}]")
                if preference in ("_local", "_only_local"):
                    node = next((c["node"] for c in copies
                                 if c["node"] == self.node_id), None)
                    if node is None:
                        if preference == "_only_local":
                            raise UnavailableShardsException(
                                f"[{name}][{sid}] has no local copy")
                        node = copies[0]["node"]
                elif preference == "_primary":
                    node = next((c["node"] for c in copies if c["primary"]),
                                copies[0])["node"] \
                        if any(c["primary"] for c in copies) \
                        else copies[0]["node"]
                else:
                    rr = self._read_rr.get((name, sid), 0)
                    self._read_rr[(name, sid)] = rr + 1
                    node = copies[rr % len(copies)]["node"]
                targets.append((node, name, sid))
        return targets

    def _shard_call(self, node: str, action: str, payload: dict):
        # always through the network object — self-sends round-trip the
        # wire format too, so wire-unsafe payloads fail in every test
        # topology, not only when the shard happens to be remote
        return self.transport.send(node, action, payload)

    # -- hedged replica reads (ISSUE 9) -----------------------------------

    def _hedge_setting(self, key: str, default):
        st = self.cluster.current().data.get("settings") or {}
        return st.get(key, self.hedge_settings.get(key, default))

    def _observe_node_latency(self, node: str, ms: float) -> None:
        from ..serving.qos import Ewma
        lat = self._node_lat.get(node)
        if lat is None:
            lat = self._node_lat[node] = Ewma()
        lat.observe(ms)

    def _cross_host(self, node: str) -> bool:
        """True when `node` sits on a different (known) simulated host —
        the hop rides DCN, not ICI (transport `set_host` topology)."""
        host_of = getattr(self.transport.network, "host_of", None)
        if host_of is None:
            return False
        mine, theirs = host_of(self.node_id), host_of(node)
        return mine is not None and theirs is not None and mine != theirs

    def _observe_host_hop(self, node: str, ms: float) -> None:
        """Latency of one A_QUERY_HOST pre-reduced hop. Cross-host hops
        observe into the per-transport-class "dcn" EWMA — NEVER into
        `_node_lat`, whose per-node EWMAs arm the intra-host hedge
        deadline (a slow DCN link must not poison the ICI deadline).
        Co-hosted hops observe "reg"."""
        from ..serving.qos import observe_transport_latency
        if self._cross_host(node):
            self.host_reduce_stats["dcn_hops"] += 1
            from .host_reduce import note_dcn_hop
            note_dcn_hop()      # process-wide mirror for the sampler ring
            observe_transport_latency("dcn", ms)
        else:
            observe_transport_latency("reg", ms)

    def _query_with_hedge(self, state, name: str, sid: int, node: str,
                          payload: dict):
        """A_QUERY with an adaptive hedge (SURVEY §2.10.2's load-balanced
        reads, upgraded to hedging): when the chosen copy's response
        exceeds its p99-of-EWMA deadline (`cluster.search.hedge.*`), the
        SAME query fires at another STARTED copy and the first success
        wins; the loser's late answer is observed, discarded and counted
        as canceled. Error semantics are unchanged — with no success the
        primary's error raises exactly as the unhedged call would.
        Returns (result, serving_node)."""
        from ..serving.qos import record_hedge
        enabled = self._hedge_setting("cluster.search.hedge.enable", True)
        if isinstance(enabled, str):
            enabled = enabled.strip().lower() not in ("false", "0", "no",
                                                      "off")
        backups = [c["node"] for c in state.started_copies(name, sid)
                   if c["node"] != node]
        # hedge-over-moving-copy (ISSUE 15): a copy that is the source or
        # the recovery feed of an in-flight relocation is ALSO streaming
        # recovery chunks — arm the hedge even on a cold EWMA and tighten
        # the deadline by cluster.search.hedge.moving_factor so the SLO
        # holds while the move completes
        copies = state.routing.get(name, [[]] * (sid + 1))[sid] \
            if name in state.routing else []
        moving = any(
            (c["node"] == node and c["state"] == RELOCATING)
            or (c.get("relocation") and c.get("recover_from") == node)
            for c in copies)
        lat = self._node_lat.get(node)
        cold = lat is None or lat.n == 0
        if not enabled or not backups or (cold and not moving):
            # cold copy / nothing to hedge onto: the plain synchronous
            # call (and its latency seeds the EWMA for next time)
            t1 = time.perf_counter()
            r = self._shard_call(node, A_QUERY, payload)
            self._observe_node_latency(
                node, (time.perf_counter() - t1) * 1000)
            return r, node

        def _f(key, default):
            try:
                return float(self._hedge_setting(key, default))
            except (TypeError, ValueError):
                return default
        min_ms = _f("cluster.search.hedge.min_ms", 50.0)
        max_ms = _f("cluster.search.hedge.max_ms", 5000.0)
        k = _f("cluster.search.hedge.deviations", 3.0)
        base_ms = min_ms if cold else lat.deadline_ms(k)
        deadline_s = min(max(base_ms, min_ms), max_ms) / 1000.0
        if moving:
            factor = _f("cluster.search.hedge.moving_factor", 0.5)
            deadline_s *= max(min(factor, 1.0), 0.01)

        import contextvars
        cond = threading.Condition()
        results: list[tuple] = []
        winner: list[str] = []

        def call(target: str) -> None:
            t1 = time.perf_counter()
            try:
                r = self._shard_call(target, A_QUERY, payload)
                self._observe_node_latency(
                    target, (time.perf_counter() - t1) * 1000)
                out = ("ok", r, target)
            except (ConnectTransportException,
                    RemoteTransportException) as e:
                out = ("err", e, target)
            with cond:
                results.append(out)
                if out[0] == "ok" and winner and winner[0] != target:
                    # the race's loser finally answered: canceled —
                    # observed, discarded, counted
                    record_hedge("canceled")
                    self.hedge_stats["canceled"] += 1
                cond.notify_all()

        def _success():
            return next((r for r in results if r[0] == "ok"), None)

        launched = 1
        ctx = contextvars.copy_context()
        threading.Thread(target=ctx.run, args=(call, node),
                         daemon=True).start()
        with cond:
            cond.wait_for(lambda: results, timeout=deadline_s)
            lapsed = not results
        if lapsed:
            # deadline blown: fire the backup; the span sits under the
            # coordinator's query span in GET /_traces
            backup = backups[0]
            record_hedge("fired")
            self.hedge_stats["fired"] += 1
            if moving:
                record_hedge("moving")
                self.hedge_stats["moving"] += 1
            launched = 2
            with tracing.span("hedge", index=name, shard=sid,
                              primary=node, backup=backup):
                ctx2 = contextvars.copy_context()
                threading.Thread(target=ctx2.run, args=(call, backup),
                                 daemon=True).start()
                with cond:
                    cond.wait_for(lambda: _success() is not None
                                  or len(results) >= launched)
        with cond:
            got = _success()
            if got is None and len(results) < launched:
                # primary errored inside the deadline; the backup (if
                # any) may still answer — wait it out
                cond.wait_for(lambda: _success() is not None
                              or len(results) >= launched)
                got = _success()
            if got is not None:
                winner.append(got[2])
        if got is not None:
            if launched == 2:
                outcome = "win_primary" if got[2] == node else "win_backup"
                record_hedge(outcome)
                self.hedge_stats[outcome] += 1
            return got[1], got[2]
        if launched == 2:
            record_hedge("failed")
            self.hedge_stats["failed"] += 1
        raise next(r[1] for r in results if r[2] == node)

    def _dfs_stats(self, targets, query, names) -> dict | None:
        """All-reduce term statistics across shards (ref DfsPhase.java:57-81)
        so BM25 IDF is corpus-global. Returns a wire dict or None when the
        query holds no terms."""
        from ..search.query_parser import QueryParser
        terms: dict[str, set] = {}
        for name in names:
            mappers = self._mappers.get(name)
            if mappers is None:
                continue
            try:
                QueryParser(mappers).parse(query).collect_terms(terms)
            except Exception:  # noqa: BLE001 — shard-side parse will report
                return None
        if not any(terms.values()):
            return None       # term-less query: nothing to all-reduce
        terms_wire = {f: sorted(ts) for f, ts in terms.items()}
        dfs = {"doc_count": 0, "sum_dl": {}, "dfs": {}}
        for node, name, sid in targets:
            try:
                r = self._shard_call(node, A_TERM_STATS, {
                    "index": name, "shard": sid, "terms": terms_wire})
            except (ConnectTransportException, RemoteTransportException):
                continue       # the query round will account the failure
            dfs["doc_count"] += r["doc_count"]
            for f, v in r["sum_dl"].items():
                dfs["sum_dl"][f] = dfs["sum_dl"].get(f, 0.0) + v
            for f, t, df in r["dfs"]:
                key = f + "\x00" + t
                dfs["dfs"][key] = dfs["dfs"].get(key, 0) + df
        return {"doc_count": dfs["doc_count"], "sum_dl": dfs["sum_dl"],
                "dfs": [[*k.split("\x00", 1), v]
                        for k, v in dfs["dfs"].items()],
                "terms": terms_wire}

    def _on_term_stats(self, from_id: str, req: dict) -> dict:
        holder = self._shards.get((req["index"], req["shard"]))
        if holder is None or holder.engine is None:
            raise UnavailableShardsException(
                f"[{req['index']}][{req['shard']}]")
        from ..search.query_dsl import CollectionStats
        searcher = self._searcher(req["index"], req["shard"], holder)
        tbf = {f: set(ts) for f, ts in (req.get("terms") or {}).items()}
        stats = CollectionStats.from_segments(searcher.segments, tbf)
        return {"doc_count": stats.doc_count,
                "sum_dl": stats.field_sum_dl,
                "dfs": [[f, t, df]
                        for (f, t), df in stats.doc_freqs.items()]}

    def _task_header(self, task) -> dict:
        """Wire header linking a shard-level message to its coordinator
        task (crosses the JSON transport as plain strings)."""
        return {"parent": task.id, "trace": task.trace_id,
                "opaque": task.opaque_id}

    @staticmethod
    def _trace_header() -> dict | None:
        """The `_trace` wire header (next to `_task`): the active span's
        (trace id, span id), so the copy-holder's shard subtree parents
        under the coordinator's span. None when nothing is traced."""
        from ..common import tracing
        return tracing.wire_header()

    def search(self, index: str, body: dict | None = None,
               preference: str | None = None,
               scroll: str | None = None) -> dict:
        with self.tasks.scope("indices:data/read/search",
                              description=f"indices[{index}]") as task:
            return self._search(index, body, preference, scroll, task)

    def _search(self, index: str, body: dict | None,
                preference: str | None, scroll: str | None, task) -> dict:
        t0 = time.perf_counter()
        body = body or {}
        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        state = self.cluster.current()
        names = state.resolve_index(index)
        if not names:
            raise KeyError(f"no such index [{index}]")
        targets = self.search_shards(state, names, preference)
        if scroll is not None:
            return self._scroll_start(targets, body, size, scroll, t0)

        query = body.get("query") or {"match_all": {}}
        if body.get("knn") is not None and body.get("sort") is not None:
            raise ValueError("knn search cannot be combined with sort")
        if body.get("rank") is not None:
            # hybrid fusion is a single-node coordinator feature so far;
            # silently serving the knn list alone would misrepresent it
            raise ValueError(
                "rank fusion is not supported on the cluster search path")
        dfs = self._dfs_stats(targets, query, names) \
            if body.get("knn") is None else None
        agg_specs = None
        if body.get("aggs") or body.get("aggregations"):
            from ..search.aggs.aggregators import parse_aggs
            agg_specs = parse_aggs(body.get("aggs")
                                   or body.get("aggregations"))

        # phase 1: query fan-out, partial-failure accounting (a failed
        # shard reduces coverage, never aborts the search — ref
        # TransportSearchTypeAction onFirstPhaseResult failure path).
        #
        # Node-local mesh reduce (ISSUE 11): shards co-hosted on one node
        # group into ONE A_QUERY_HOST message — the data node runs all of
        # them as one shard_map program (one device fetch per host) and
        # returns pre-reduced per-shard wire results, bitwise-identical
        # to the per-shard fan-out. Declines/errors fall back to the
        # hedged per-shard path below.
        per_shard: list[tuple[int, dict]] = []
        failures: list[dict] = []
        host_served: set[int] = set()
        with tracing.span("query", shards=len(targets)):
            from .host_reduce import body_eligible
            if body_eligible(body) and self._host_reduce_enabled():
                groups: dict[tuple[str, str], list[int]] = {}
                for ti, (node, name, sid) in enumerate(targets):
                    groups.setdefault((node, name), []).append(ti)
                host_groups = [(node, name, tis)
                               for (node, name), tis in groups.items()
                               if len(tis) >= 2]

                def _call_host(node, name, tis, results):
                    sids = [targets[ti][2] for ti in tis]
                    payload = {"index": name, "shards": sids,
                               "body": body, "size": size + from_,
                               "dfs": dfs,
                               "_task": self._task_header(task),
                               "_trace": self._trace_header()}
                    try:
                        with tracing.span("mesh_host_reduce", index=name,
                                          node=node, shards=len(sids)):
                            t1 = time.perf_counter()
                            results[(node, name)] = self._shard_call(
                                node, A_QUERY_HOST, payload)
                            self._observe_host_hop(
                                node, (time.perf_counter() - t1) * 1000.0)
                    except (ConnectTransportException,
                            RemoteTransportException):
                        results[(node, name)] = None
                if host_groups:
                    # per-HOST calls fan out concurrently (the reference's
                    # async shard fan-out, one message per host): the
                    # hosts' mesh programs overlap instead of serializing
                    import contextvars
                    results: dict = {}
                    threads = []
                    for node, name, tis in host_groups[1:]:
                        ctx = contextvars.copy_context()
                        t = threading.Thread(
                            target=ctx.run, args=(_call_host, node, name,
                                                  tis, results),
                            daemon=True)
                        t.start()
                        threads.append(t)
                    _call_host(*host_groups[0][:3], results)
                    for t in threads:
                        t.join()
                    for node, name, tis in host_groups:
                        r = results.get((node, name))
                        if r is None:
                            self.host_reduce_stats["errors"] += 1
                            continue     # per-shard fallback below
                        if r.get("declined") is not None:
                            continue     # the data node counted its reason
                        self.host_reduce_stats["merges"] += 1
                        if self._cross_host(node):
                            # pod tier: a pre-reduced result crossed the
                            # host boundary — ONE DCN hop carried the
                            # whole host's shards, and the merge below
                            # is the same bitwise host merge
                            self.host_reduce_stats["pod_dispatches"] += 1
                            from .host_reduce import note_pod_dispatch
                            note_pod_dispatch()
                        for ti in tis:
                            per_shard.append((ti, r["shards"][str(
                                targets[ti][2])]))
                            host_served.add(ti)
            for ti, (node, name, sid) in enumerate(targets):
                if ti in host_served:
                    continue
                payload = {"index": name, "shard": sid, "body": body,
                           "size": size + from_, "dfs": dfs,
                           "_task": self._task_header(task),
                           "_trace": self._trace_header()}
                try:
                    r, _served = self._query_with_hedge(
                        state, name, sid, node, payload)
                    per_shard.append((ti, r))
                except (ConnectTransportException,
                        RemoteTransportException) as e:
                    failures.append({"shard": sid, "index": name,
                                     "node": node, "reason": str(e)})
        # agg/suggest partials must merge in target order regardless of
        # which lane served each shard (float merges are order-sensitive)
        per_shard.sort(key=lambda e: e[0])
        if not per_shard and targets:
            raise UnavailableShardsException(
                f"all shards failed for [{index}]: {failures}")

        reduced = self._reduce(per_shard, targets, body, names,
                               from_, size)
        hits = self._fetch_phase(reduced, targets, body, task)
        resp = self._render_response(reduced, hits, targets, failures,
                                     agg_specs, per_shard, body, t0)
        return resp

    def _parse_sort_specs(self, body: dict, names: list[str]):
        from ..search.sort import parse_sort
        mappers = [self._mappers[n] for n in names if n in self._mappers]
        return parse_sort(body.get("sort"), mappers)

    def _reduce(self, per_shard, targets, body, names, from_, size):
        """Cross-shard sort-merge on wire results
        (ref SearchPhaseController.sortDocs:147,233)."""
        from ..search import sort as sort_mod
        sort = self._parse_sort_specs(body, names)
        entries = []
        total = 0
        max_score = None
        for ti, r in per_shard:
            total += r["total"]
            if r["max_score"] is not None:
                ms = float(r["max_score"])
                if max_score is None or ms > max_score:
                    max_score = ms
            for pos, doc_id in enumerate(r["ids"]):
                score = r["scores"][pos]
                sv = r["sort"][pos] if r.get("sort") is not None else None
                if sort is None:
                    primary = -score if score is not None else float("inf")
                else:
                    primary = sort_mod.compare_key(sv, sort)
                entries.append((primary, ti, pos, doc_id, score, sv))
        entries.sort(key=lambda e: (e[0], e[1], e[2]))
        window = entries[from_: from_ + size]
        return {"window": window, "total": total, "max_score": max_score,
                "sorted": sort is not None}

    def _fetch_phase(self, reduced, targets, body, task=None) -> dict:
        """Fetch fan-out to winning shards only; highlight runs ON the data
        node inside fetch (ref FetchPhase sub-phases)."""
        by_target: dict[int, list[str]] = {}
        for _, ti, _pos, doc_id, _score, _sv in reduced["window"]:
            by_target.setdefault(ti, []).append(doc_id)
        fetched: dict[tuple[int, str], dict] = {}
        for ti, ids in by_target.items():
            node, name, sid = targets[ti]
            payload = {"index": name, "shard": sid, "ids": ids,
                       "_source": body.get("_source", True),
                       "highlight": body.get("highlight"),
                       "query": body.get("query")}
            if task is not None:
                payload["_task"] = self._task_header(task)
                payload["_trace"] = self._trace_header()
            try:
                fr = self._shard_call(node, A_FETCH, payload)
            except (ConnectTransportException, RemoteTransportException):
                continue    # hit rendered without source (copy just died)
            for doc_id, hit in zip(ids, fr["hits"]):
                fetched[(ti, doc_id)] = hit
        return fetched

    def _render_response(self, reduced, fetched, targets, failures,
                         agg_specs, per_shard, body, t0) -> dict:
        hits = []
        for _, ti, _pos, doc_id, score, sv in reduced["window"]:
            h = fetched.get((ti, doc_id), {})
            entry = {"_index": targets[ti][1],
                     "_type": h.get("_type", "_doc"),
                     "_id": doc_id, "_score": score}
            if h.get("_source") is not None:
                entry["_source"] = h["_source"]
            if reduced["sorted"]:
                entry["sort"] = sv
            if h.get("highlight"):
                entry["highlight"] = h["highlight"]
            hits.append(entry)
        resp = {"took": int((time.perf_counter() - t0) * 1000),
                "timed_out": False,
                "_shards": {"total": len(targets),
                            "successful": len(per_shard),
                            "failed": len(failures),
                            **({"failures": failures} if failures else {})},
                "hits": {"total": reduced["total"],
                         "max_score": reduced["max_score"],
                         "hits": hits}}
        if agg_specs is not None:
            from ..search.aggs.aggregators import (merge_shard_partials,
                                                   render)
            from ..search.aggs.wire import partials_from_wire
            parts = [partials_from_wire(agg_specs, r["aggs"])
                     for _, r in per_shard if r.get("aggs") is not None]
            resp["aggregations"] = render(
                agg_specs, merge_shard_partials(agg_specs, parts))
        sugg = [r["suggest"] for _, r in per_shard
                if r.get("suggest") is not None]
        if sugg:
            from ..search.suggest import merge_suggest
            resp["suggest"] = merge_suggest(body.get("suggest") or {}, sugg)
        return resp

    def msearch(self, items: list[tuple[dict, dict]]) -> dict:
        """(header, body) pairs -> {"responses": [...]}, per-item errors
        (ref TransportMultiSearchAction)."""
        responses = []
        for header, sbody in items:
            try:
                responses.append(self.search(
                    header.get("index", "_all"), sbody,
                    preference=header.get("preference")))
            except Exception as e:  # noqa: BLE001 — per-item contract
                responses.append({"error": f"{type(e).__name__}[{e}]"})
        return {"responses": responses}

    def count(self, index: str, body: dict | None = None) -> dict:
        r = self.search(index, {**(body or {}), "size": 0, "from": 0})
        return {"count": r["hits"]["total"], "_shards": r["_shards"]}

    def _searcher(self, index: str, sid: int,
                  holder: _ShardHolder) -> ShardSearcher:
        eng = holder.engine
        key = (tuple(s.seg_id for s in eng.segments),
               tuple(s.live_gen for s in eng.segments))
        if holder.searcher is None or holder.searcher[0] != key:
            holder.drop_searcher()
            # per-index search-lane settings ride the cluster state
            # (prefixed key wins, the update-settings convention) so the
            # blockwise opt-out/block width behave like the local node's
            meta = self.cluster.current().indices.get(index) or {}
            settings = meta.get("settings") or {}

            def get_s(k, default):
                return settings.get(f"index.{k}", settings.get(k, default))
            blockwise = str(get_s("search.blockwise.enable", True)) \
                .strip().lower() not in ("false", "0", "no")
            try:
                block_docs = int(get_s("search.block_docs", 0)) or None
            except (TypeError, ValueError):
                block_docs = None
            # kNN/ANN settings ride the cluster state the same way, so
            # cluster shard copies serve the same lane as a local node
            from ..index.index_service import knn_options_from
            holder.searcher = (key, ShardSearcher(
                sid, eng.segments, self._mappers[index],
                blockwise=blockwise, block_docs=block_docs,
                knn_opts=knn_options_from(get_s)),
                eng.acquire_searcher(
                    site=f"cluster[{index}][{sid}]/_searcher"))
        return holder.searcher[1]

    @contextlib.contextmanager
    def _shard_task_scope(self, action: str, req: dict):
        """Register the shard-level action under the coordinator task the
        message carries (remote copy-holders show the coordinator as
        parent — TaskId-over-the-wire semantics). When the message also
        carries a `_trace` header, the shard phase records a local span
        subtree continuing the coordinator's trace."""
        hdr = req.get("_task") or {}
        desc = f"shard [{req['index']}][{req['shard']}]"
        with self.tasks.scope(
                action, description=desc,
                parent_task_id=hdr.get("parent"),
                trace_id=hdr.get("trace"),
                opaque_id=hdr.get("opaque")) as task:
            with self.tracer.remote(req.get("_trace"), action,
                                    attrs={"description": desc,
                                           "node": self.node_id}):
                yield task

    def _on_query(self, from_id: str, req: dict) -> dict:
        holder = self._shards.get((req["index"], req["shard"]))
        if holder is None or holder.engine is None:
            raise UnavailableShardsException(
                f"[{req['index']}][{req['shard']}]")
        searcher = self._searcher(req["index"], req["shard"], holder)
        body = req.get("body") or {}
        k = int(req["size"])
        with self._shard_task_scope(
                "indices:data/read/search[phase/query]", req):
            return _shard_query_phase(searcher, self._mappers[req["index"]],
                                      body, k, req.get("dfs"),
                                      search_after=req.get("search_after"))

    _host_reduce_error_logged = 0

    def _host_reduce_enabled(self) -> bool:
        """`cluster.search.host_reduce.enable` (default true) — read live
        from cluster-state settings, like the hedge settings."""
        from .host_reduce import HOST_REDUCE_SETTING, setting_enabled
        st = self.cluster.current().data.get("settings") or {}
        return setting_enabled(st.get(HOST_REDUCE_SETTING, True))

    def _on_query_host(self, from_id: str, req: dict) -> dict:
        """Data-node side of the node-local mesh reduce: run every
        requested co-hosted shard's query phase as ONE shard_map program
        and return pre-reduced per-shard wire results. Declines (wire
        `{"declined": reason}`) send the coordinator down the per-shard
        fan-out — never an error."""
        from . import host_reduce
        if not self._host_reduce_enabled():
            return {"declined": "disabled"}
        index = req["index"]
        sids = [int(s) for s in req["shards"]]
        desc = f"shards [{index}]{sids}"
        with self.tasks.scope(
                "indices:data/read/search[phase/query/host]",
                description=desc,
                parent_task_id=(req.get("_task") or {}).get("parent"),
                trace_id=(req.get("_task") or {}).get("trace"),
                opaque_id=(req.get("_task") or {}).get("opaque")):
            with self.tracer.remote(req.get("_trace"), "mesh_host_reduce",
                                    attrs={"description": desc,
                                           "node": self.node_id}):
                try:
                    out, reason = host_reduce.try_host_reduce(
                        self, index, sids, req.get("body") or {},
                        int(req["size"]), req.get("dfs"))
                except Exception:  # noqa: BLE001 — fan-out is always correct
                    self.host_reduce_stats["errors"] += 1
                    if ClusterNode._host_reduce_error_logged < 10:
                        ClusterNode._host_reduce_error_logged += 1
                        import logging
                        logging.getLogger(__name__).warning(
                            "host mesh reduce failed; served via the "
                            "per-shard fan-out instead", exc_info=True)
                    return {"declined": "error"}
        if out is None:
            self.host_reduce_stats["declined"] += 1
            return {"declined": reason}
        self.host_reduce_stats["dispatches"] += 1
        return out

    def _on_fetch(self, from_id: str, req: dict) -> dict:
        holder = self._shards.get((req["index"], req["shard"]))
        if holder is None or holder.engine is None:
            raise UnavailableShardsException(f"[{req['index']}]")
        with self._shard_task_scope(
                "indices:data/read/search[phase/fetch/id]", req):
            return _shard_fetch_phase(holder.engine,
                                      self._mappers[req["index"]], req)

    # -- distributed scroll (ref scroll_id encoding per-shard context ids,
    #    action/search/type/TransportSearchHelper + SearchService
    #    keep-alive contexts; cursors advance per shard by the LAST
    #    GLOBALLY-EMITTED doc, the lastEmittedDocPerShard contract of
    #    SearchPhaseController.sortDocs) --------------------------------

    def _scroll_start(self, targets, body, size, keep_alive, t0) -> dict:
        if any(k in body for k in ("knn", "rescore", "search_after")):
            raise ValueError("scroll does not support "
                             "knn/rescore/search_after")
        ctxs = []
        ok_targets = []
        for node, name, sid in targets:
            try:
                r = self._shard_call(node, A_SCROLL_NEXT, {
                    "index": name, "shard": sid,
                    "init": {"body": body, "keep_alive": keep_alive}})
            except (ConnectTransportException,
                    RemoteTransportException):
                continue    # partial scroll, like the query phase
            ctxs.append(r["ctx"])
            ok_targets.append((node, name, sid))
        if not ok_targets:
            raise UnavailableShardsException(
                "scroll could not pin any shard context")
        targets = ok_targets
        with self._scroll_lock:
            self._scroll_seq += 1
            scroll_id = f"c-scroll-{self.node_id}-{self._scroll_seq}"
            ctx = {"targets": list(targets), "ctxs": ctxs,
                   "cursors": [None] * len(targets), "size": size,
                   "keep_alive": keep_alive,
                   "expiry": time.monotonic() + _keepalive_secs(keep_alive),
                   "lock": threading.Lock()}
            self._scroll_ctx[scroll_id] = ctx
        out = self._scroll_batch(ctx, t0)
        out["_scroll_id"] = scroll_id
        return out

    def scroll(self, scroll_id: str, keep_alive: str | None = None) -> dict:
        t0 = time.perf_counter()
        with self._scroll_lock:
            ctx = self._scroll_ctx.get(scroll_id)
            if ctx is None or ctx["expiry"] < time.monotonic():
                self._scroll_ctx.pop(scroll_id, None)
                ctx = None
        if ctx is None:
            raise SearchContextMissingException(
                f"No search context found for id [{scroll_id}]")
        if keep_alive:
            ctx["keep_alive"] = keep_alive
        ctx["expiry"] = time.monotonic() + _keepalive_secs(ctx["keep_alive"])
        out = self._scroll_batch(ctx, t0)
        out["_scroll_id"] = scroll_id
        return out

    def clear_scroll(self, scroll_id: str) -> bool:
        ctx = self._scroll_ctx.pop(scroll_id, None)
        if ctx is None:
            return False
        for (node, name, sid), cid in zip(ctx["targets"], ctx["ctxs"]):
            try:
                self._shard_call(node, A_SCROLL_CLEAR, {"ctx": cid})
            except (ConnectTransportException, RemoteTransportException):
                pass
        return True

    def _scroll_batch(self, ctx, t0) -> dict:
        with ctx["lock"]:
            return self._scroll_batch_locked(ctx, t0)

    def _scroll_batch_locked(self, ctx, t0) -> dict:
        from ..search import sort as sort_mod
        size = ctx["size"]
        per_shard = []
        failures = []
        for ti, ((node, name, sid), cid) in enumerate(
                zip(ctx["targets"], ctx["ctxs"])):
            try:
                r = self._shard_call(node, A_SCROLL_NEXT, {
                    "index": name, "shard": sid, "ctx": cid, "size": size,
                    "after": ctx["cursors"][ti],
                    "keep_alive": ctx["keep_alive"]})
                per_shard.append((ti, r))
            except (ConnectTransportException,
                    RemoteTransportException) as e:
                failures.append({"shard": sid, "index": name,
                                 "reason": str(e)})
        entries = []
        total = 0
        max_score = None
        specs = None
        for ti, r in per_shard:
            total += r["total"]
            if r["max_score"] is not None:
                ms = float(r["max_score"])
                max_score = ms if max_score is None else max(max_score, ms)
            if specs is None and r.get("specs") is not None:
                specs = [sort_mod.SortSpec(**sp) for sp in r["specs"]]
            for h in r["hits"]:
                entries.append((sort_mod.compare_key(h["sort"], specs),
                                ti, h))
        entries.sort(key=lambda e: (e[0], e[1]))
        window = entries[:size]
        # advance each shard's cursor to its LAST EMITTED doc
        for _, ti, h in window:
            ctx["cursors"][ti] = h["sort"]
        hits = []
        for _, ti, h in window:
            entry = {"_index": ctx["targets"][ti][1],
                     "_type": h.get("_type", "_doc"), "_id": h["_id"],
                     "_score": h.get("score")}
            if h.get("_source") is not None:
                entry["_source"] = h["_source"]
            if not h.get("implicit_sort"):
                entry["sort"] = h["sort"]
            hits.append(entry)
        return {"took": int((time.perf_counter() - t0) * 1000),
                "timed_out": False,
                "_shards": {"total": len(ctx["targets"]),
                            "successful": len(per_shard),
                            "failed": len(failures)},
                "hits": {"total": total, "max_score": max_score,
                         "hits": hits}}

    def _on_scroll_next(self, from_id: str, req: dict) -> dict:
        self._reap_scroll_ctx()
        if "init" in req:
            holder = self._shards.get((req["index"], req["shard"]))
            if holder is None or holder.engine is None:
                raise UnavailableShardsException(
                    f"[{req['index']}][{req['shard']}]")
            searcher = self._searcher(req["index"], req["shard"], holder)
            init = req["init"]
            with self._scroll_lock:
                self._scroll_seq += 1
                cid = f"ctx-{self.node_id}-{self._scroll_seq}"
                self._scroll_ctx[cid] = _make_shard_scroll_ctx(
                    searcher, self._mappers[req["index"]], init["body"],
                    _keepalive_secs(init["keep_alive"]))
            return {"ctx": cid}
        ctx = self._scroll_ctx.get(req["ctx"])
        if ctx is None:
            raise UnavailableShardsException(
                f"scroll context [{req['ctx']}] expired")
        ctx["expiry"] = time.monotonic() \
            + _keepalive_secs(req.get("keep_alive", "1m"))
        return _shard_scroll_batch(ctx, int(req["size"]), req.get("after"))

    def _on_scroll_clear(self, from_id: str, req: dict) -> dict:
        return {"found": self._scroll_ctx.pop(req["ctx"], None) is not None}

    def _reap_scroll_ctx(self) -> None:
        now = time.monotonic()
        with self._scroll_lock:
            for cid in [c for c, ctx in self._scroll_ctx.items()
                        if ctx.get("expiry", now) < now]:
                del self._scroll_ctx[cid]

    # ------------------------------------------------------------------
    # broadcast admin (ref TransportBroadcastOperationAction)
    # ------------------------------------------------------------------

    def refresh(self, index: str = "_all") -> None:
        self._broadcast(A_REFRESH, index)

    def flush(self, index: str = "_all") -> None:
        self._broadcast(A_FLUSH, index)

    def _broadcast(self, action: str, index: str) -> None:
        state = self.cluster.current()
        nodes = {c["node"] for name in state.resolve_index(index)
                 for copies in state.routing[name] for c in copies
                 if c["node"] is not None and c["state"] != UNASSIGNED}
        for node_id in sorted(nodes):
            try:
                if node_id == self.node_id:
                    self.transport._handle(self.node_id, action,
                                           {"index": index})
                else:
                    self.transport.send(node_id, action, {"index": index})
            except (ConnectTransportException, RemoteTransportException):
                continue

    def _on_refresh(self, from_id: str, req: dict) -> dict:
        names = self.cluster.current().resolve_index(req.get("index", "_all"))
        for (index, sid), holder in list(self._shards.items()):
            if index in names and holder.engine is not None:
                holder.engine.refresh()
        return {"ok": True}

    def _on_flush(self, from_id: str, req: dict) -> dict:
        names = self.cluster.current().resolve_index(req.get("index", "_all"))
        for (index, sid), holder in list(self._shards.items()):
            if index in names and holder.engine is not None:
                holder.engine.flush()
        return {"ok": True}

    # ------------------------------------------------------------------

    def health(self) -> dict:
        state = self.cluster.current()
        return {"cluster_name": state.data["cluster_name"],
                "master_node": state.master_node,
                "version": state.version, **state.health()}

    def close(self) -> None:
        """Simulates process death when called abruptly (harness.kill)."""
        self.closed = True
        self.transport.close()
        self.cluster.close()
        with self._shards_lock:
            for holder in self._shards.values():
                if holder.engine is not None:
                    holder.drop_searcher()
                    holder.engine.close()


# ---------------------------------------------------------------------------
# Data-node search phases (shared by RPC handlers; ref SearchService
# executeQueryPhase/executeFetchPhase — the shard side of the 2-phase
# protocol, returning WIRE-SAFE results)
# ---------------------------------------------------------------------------

def _crc_prefix(path: str, size: int, chunk: int) -> int:
    """crc32 over the first `size` bytes (recovery file identity — files
    are write-once/append-only after flush, so the prefix is stable)."""
    import zlib
    crc = 0
    remaining = size
    with open(path, "rb") as f:
        while remaining > 0:
            b = f.read(min(chunk, remaining))
            if not b:
                break
            crc = zlib.crc32(b, crc)
            remaining -= len(b)
    return crc


def _keepalive_secs(s: str) -> float:
    from ..node import _duration_secs     # one duration grammar everywhere
    return _duration_secs(s)


def _jsonval(v):
    """Materialized sort values / scores -> JSON-safe."""
    import numpy as np
    if isinstance(v, (list, tuple)):
        return [_jsonval(x) for x in v]
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        f = float(v)
        return None if f != f else f
    if isinstance(v, float) and v != v:
        return None
    if isinstance(v, (np.str_, np.bool_)):
        return v.item()
    return v


def _stats_from_wire(dfs: dict | None):
    if dfs is None:
        return None
    from ..search.query_dsl import CollectionStats
    return CollectionStats(
        doc_count=dfs["doc_count"],
        field_sum_dl=dict(dfs["sum_dl"]),
        doc_freqs={(f, t): df for f, t, df in dfs["dfs"]})


def _shard_query_phase(searcher: ShardSearcher, mappers: MapperService,
                       body: dict, k: int, dfs: dict | None,
                       search_after=None) -> dict:
    """Execute the FULL query phase for one shard and wire-encode the
    result (keys + scores + materialized sort values + agg/suggest
    partials). The coordinator windows [from, from+size) after the merge,
    so `k` = from + size here."""
    from ..search.aggs.aggregators import parse_aggs
    from ..search.sort import parse_sort

    stats = _stats_from_wire(dfs)
    sort = parse_sort(body.get("sort"), [mappers])
    if search_after is None:
        search_after = body.get("search_after") or None
    if search_after is not None and sort is None:
        raise ValueError("search_after requires a sort")
    agg_specs = parse_aggs(body.get("aggs") or body.get("aggregations")) \
        if (body.get("aggs") or body.get("aggregations")) else None
    rescore_spec = body.get("rescore")
    if isinstance(rescore_spec, list):
        rescore_spec = rescore_spec[0] if rescore_spec else None
    if rescore_spec is not None and sort is not None:
        raise ValueError("rescore cannot be used with a sort")
    window = int(rescore_spec.get("window_size", k)) if rescore_spec else 0
    knn = body.get("knn")

    if knn is not None:
        fnode = searcher.parse([knn["filter"]]) if knn.get("filter") else None
        raw_np = knn.get("nprobe")
        r = searcher.execute_knn(
            knn["field"], [knn["query_vector"]],
            k=int(knn.get("k", k)), metric=knn.get("metric", "cosine"),
            filter_node=fnode,
            nprobe=int(raw_np) if raw_np is not None else None,
            exact=bool(knn.get("exact", False)),
            quantization=knn.get("quantization"))
    else:
        node = searcher.parse([body.get("query") or {"match_all": {}}])
        r = searcher.execute_query_phase(
            node, size=max(k, window), from_=0, sort=sort,
            global_stats=stats, aggs=agg_specs,
            search_after=search_after,
            track_scores=bool(body.get("track_scores", False))
            if sort is not None else True)
        if rescore_spec is not None:
            r = searcher.rescore(r, rescore_spec)

    from ..search.shard_searcher import LOCAL_MASK, SEG_SHIFT
    ids, scores, svs = [], [], []
    for pos in range(r.doc_keys.shape[1]):
        key = int(r.doc_keys[0, pos])
        if key < 0:
            continue
        seg = searcher.segments[key >> SEG_SHIFT]
        # doc IDS cross the seam, not positional keys: the fetch phase may
        # race a flush/merge that reshuffles (segment, local) addresses —
        # ids stay stable (the reference's fetch uses context-pinned
        # readers; id addressing is the equivalent safety here)
        ids.append(seg.ids[key & LOCAL_MASK])
        sc = float(r.scores[0, pos])
        scores.append(None if sc != sc else sc)
        if r.sort_values is not None:
            svs.append(_jsonval(r.sort_values[0, pos]))
    mx = float(r.max_score[0])
    out: dict = {"ids": ids, "scores": scores,
                 "sort": svs if r.sort_values is not None else None,
                 "total": int(r.total_hits[0]),
                 "max_score": None if mx != mx else mx}
    if agg_specs is not None and r.aggs is not None:
        from ..search.aggs.wire import partials_to_wire
        out["aggs"] = partials_to_wire(agg_specs, r.aggs)
    if body.get("suggest"):
        from ..search.suggest import run_suggest
        out["suggest"] = run_suggest(body["suggest"], searcher.segments)
    return out


def _shard_fetch_phase(engine: Engine, mappers: MapperService,
                       req: dict) -> dict:
    """Resolve doc IDS to rendered hits; _source filtering and HIGHLIGHT
    run here, on the data node (ref FetchPhase.java sub-phases). Fetch is
    by id, not positional key, so a flush/merge racing between the query
    and fetch phases can never serve the wrong document."""
    from ..search.query_parser import QueryParser
    from ..search.shard_searcher import _filter_source

    hl_spec = None
    terms_by_field: dict[str, set] = {}
    if req.get("highlight"):
        from ..search.highlight import parse_highlight
        hl_spec = parse_highlight(req["highlight"])
        if req.get("query"):
            try:
                QueryParser(mappers).parse(req["query"]) \
                    .collect_terms(terms_by_field)
            except Exception:  # noqa: BLE001 — highlight degrades to none
                pass

    def an_for(fname):
        for dm in mappers._mappers.values():
            if fname in dm.fields:
                return dm.search_analyzer_for(fname)
        return mappers.analysis.analyzer("standard")

    src_spec = req.get("_source", True)
    hits = []
    for doc_id in req["ids"]:
        r = engine.get(doc_id, realtime=False)
        if not r.found:
            hits.append({"_id": doc_id, "_type": "_doc", "_source": None})
            continue
        raw_src = r.source
        src = None if src_spec is False \
            else _filter_source(raw_src, src_spec if src_spec is not True
                                else None)
        hit = {"_id": doc_id, "_type": r.type_name, "_source": src}
        if hl_spec is not None:
            from ..search.highlight import highlight_hit
            hl = highlight_hit(hl_spec, raw_src, terms_by_field, an_for)
            if hl:
                hit["highlight"] = hl
        hits.append(hit)
    return {"hits": hits}


def _make_shard_scroll_ctx(searcher: ShardSearcher, mappers: MapperService,
                           body: dict, keep_secs: float) -> dict:
    """Pin a point-in-time snapshot of the shard for scrolling: copy the
    segment list with frozen liveness (concurrent deletes/merges never
    change what the scroll sees — ref ScanContext reader pinning)."""
    import dataclasses as _dc

    from ..search.sort import DOC, SCORE, SortSpec, parse_sort

    segs = [_dc.replace(s, live_host=s.live_host.copy(),
                        live_count=s.live_count)
            for s in searcher.segments]
    pinned = ShardSearcher(searcher.shard_id, segs, mappers)
    user_sort = parse_sort(body.get("sort"), [mappers])
    implicit = user_sort is None
    specs = list(user_sort) if user_sort else \
        [SortSpec(field=SCORE, order="desc")]
    if not any(sp.field == DOC for sp in specs):
        specs = specs + [SortSpec(field=DOC, order="asc")]
    return {"searcher": pinned, "body": body, "specs": specs,
            "implicit": implicit,
            "expiry": time.monotonic() + keep_secs}


def _shard_scroll_batch(ctx: dict, size: int, after) -> dict:
    """One scroll batch from a pinned shard context: the next `size` docs
    after the shard's last GLOBALLY-emitted cursor, with sources inline
    (scroll fetches eagerly — one RPC per shard per batch)."""
    from ..search.shard_searcher import LOCAL_MASK, SEG_SHIFT

    searcher: ShardSearcher = ctx["searcher"]
    body = ctx["body"]
    specs = ctx["specs"]
    node = searcher.parse([body.get("query") or {"match_all": {}}])
    r = searcher.execute_query_phase(
        node, size=size, from_=0, sort=specs, search_after=after,
        track_scores=True)
    hits = []
    for pos in range(r.doc_keys.shape[1]):
        key = int(r.doc_keys[0, pos])
        if key < 0:
            continue
        seg = searcher.segments[key >> SEG_SHIFT]
        local = key & LOCAL_MASK
        sc = float(r.scores[0, pos])
        hits.append({"_id": seg.ids[local], "_type": seg.types[local],
                     "_source": seg.stored[local],
                     "score": None if sc != sc else sc,
                     "sort": _jsonval(r.sort_values[0, pos]),
                     "implicit_sort": ctx["implicit"]})
    mx = float(r.max_score[0])
    return {"hits": hits, "total": int(r.total_hits[0]),
            "max_score": None if mx != mx else mx,
            "specs": [{"field": sp.field, "order": sp.order,
                       "missing": sp.missing}
                      for sp in specs]}
