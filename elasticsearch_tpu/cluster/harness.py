"""TestCluster: N full nodes in one process, on one LocalTransport.

The analog of the reference's InternalTestCluster
(/root/reference/src/test/java/org/elasticsearch/test/InternalTestCluster.java:135
— multiple complete Node instances in one JVM, with helpers like
ensureGreen(), node kill/restart, and transport-level fault injection).
"""

from __future__ import annotations

import time

from .node import ClusterNode
from .transport import LocalTransport


class TestCluster:
    __test__ = False        # not a pytest class, despite the name

    def __init__(self, n_nodes: int, data_path: str,
                 minimum_master_nodes: int | None = None,
                 transport: str = "local", pods: int = 0):
        if minimum_master_nodes is None:
            minimum_master_nodes = n_nodes // 2 + 1
        if transport == "tcp":
            # real loopback sockets + binary frames (cluster/tcp.py) — the
            # same node code, the production wire
            from .tcp import TcpTransport
            self.network = TcpTransport()
        else:
            self.network = LocalTransport()
        self.data_path = data_path
        self.minimum_master_nodes = minimum_master_nodes
        self.pods = max(0, min(int(pods), n_nodes))
        self._pod_split = n_nodes       # fixed denominator: disjoint slices
        self.nodes: dict[str, ClusterNode] = {}
        self._seq = 0
        for _ in range(n_nodes):
            self.add_node()
        # min-id election (ref ElectMasterService sorted-node-id election)
        ids = sorted(self.nodes)
        master = self.nodes[ids[0]]
        master.bootstrap_as_master()
        for nid in ids[1:]:
            self.nodes[nid].join(ids[0])

    def _pod_settings(self, seq: int) -> dict | None:
        """Pod-mode node settings (ISSUE 19): every node OWNS a disjoint
        slice of the process's devices (`node.devices: auto:i/n` — the
        per-node-pool data plane, EXEC_LOCK-free), and nodes are spread
        over `pods` simulated hosts so inter-pod transport rides the
        "dcn" traffic class while intra-pod stays co-hosted."""
        if not self.pods:
            return None
        i = seq - 1
        n = max(self._pod_split, i + 1)
        return {"node.devices": f"auto:{i}/{n}",
                "node.host": f"pod{i * self.pods // n}"}

    def add_node(self, attrs: dict | None = None) -> ClusterNode:
        self._seq += 1
        node_id = f"node-{self._seq}"
        node = ClusterNode(node_id, self.data_path, self.network,
                           minimum_master_nodes=self.minimum_master_nodes,
                           attrs=attrs, settings=self._pod_settings(self._seq))
        self.nodes[node_id] = node
        master = self.master_node()
        if master is not None and master.node_id != node_id:
            node.join(master.node_id)
        return node

    # -- membership helpers -------------------------------------------------

    def master_node(self) -> ClusterNode | None:
        for node in self.nodes.values():
            st = node.cluster.current()
            if st.master_node == node.node_id and not node.closed:
                return node
        return None

    def client(self) -> ClusterNode:
        """Any live node works as coordinator (ref node client)."""
        for node in self.nodes.values():
            if not node.closed:
                return node
        raise RuntimeError("no live nodes")

    def node_holding_primary(self, index: str, shard: int) -> ClusterNode:
        state = self.client().cluster.current()
        primary = state.primary_of(index, shard)
        return self.nodes[primary["node"]]

    def kill_node(self, node_id: str) -> None:
        """Abrupt process death: unregister from the network WITHOUT any
        goodbye — peers discover via fault detection / failed sends."""
        node = self.nodes[node_id]
        node.closed = True
        node.transport.close()
        node.cluster.close()

    def restart_node(self, node_id: str) -> ClusterNode:
        """Bring a killed node back as a fresh process on the same data path
        and node id (ref InternalTestCluster.restartNode). The dead
        instance's engines are closed first — kill_node() simulates abrupt
        death and leaves them open, but a restart within one process must
        release the old file handles and breaker charges before the new
        instance re-opens the same directories."""
        old = self.nodes[node_id]
        if not old.closed:
            self.kill_node(node_id)
        # an in-flight recovery pull (the old applier thread) still owns
        # the shard directory the new instance will reuse: cancel it and
        # wait for a terminal stage before re-opening the same path
        with old._shards_lock:
            for holder in old._shards.values():
                holder.cancel_recovery = True
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with old._recoveries_lock:
                live = [r for r in old.recoveries.values()
                        if r["stage"] not in ("done", "failed", "cancelled")]
            if not live:
                break
            time.sleep(0.02)
        with old._shards_lock:
            for holder in old._shards.values():
                if holder.engine is not None:
                    holder.drop_searcher()
                    holder.engine.close()
                    holder.engine = None
        node = ClusterNode(node_id, self.data_path, self.network,
                           minimum_master_nodes=self.minimum_master_nodes,
                           attrs=old.attrs,
                           settings=getattr(old, "settings", None))
        self.nodes[node_id] = node
        master = self.master_node()
        if master is not None and master.node_id != node_id:
            node.join(master.node_id)
        return node

    def detect_once(self) -> None:
        """One explicit fault-detection round on every live node."""
        for node in list(self.nodes.values()):
            if not node.closed:
                node.fault_detection_round()

    def ensure_green(self, timeout: float = 15.0) -> None:
        self._ensure("green", timeout)

    def ensure_yellow_or_green(self, timeout: float = 15.0) -> None:
        self._ensure("yellow", timeout)

    def ensure_settled(self, timeout: float = 15.0) -> None:
        """Green AND still: no copy relocating. A relocation's target is
        surplus, so a cluster reads green while one streams; the handoff
        then swaps a copy under whoever compares two searches (the new
        copy has not seen the last refresh)."""
        self._ensure("green", timeout, still=True)

    def _ensure(self, at_least: str, timeout: float,
                still: bool = False) -> None:
        ok = {"green"} if at_least == "green" else {"green", "yellow"}
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            client = self.client()
            h = client.health()
            if h["status"] in ok and h["master_node"] is not None \
                    and not (still and (h["relocating_shards"]
                                        or h["initializing_shards"])):
                # every live node must have applied a state at this version
                # or later with the same master
                versions = [n.cluster.current().version
                            for n in self.nodes.values() if not n.closed]
                if min(versions) == max(versions):
                    return
            self.detect_once()
            time.sleep(0.02)
        raise TimeoutError(
            f"cluster not {at_least} within {timeout}s: "
            f"{self.client().health()}")

    def close(self) -> None:
        for node in self.nodes.values():
            if not node.closed:
                node.close()
        if hasattr(self.network, "close"):
            self.network.close()
