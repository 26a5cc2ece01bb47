"""Transport seam: named-action RPC between nodes.

The analog of the reference's TransportService + LocalTransport
(/root/reference/src/main/java/org/elasticsearch/transport/TransportService.java:60,
252,317 — registerHandler(action, handler) / sendRequest(node, action, req);
transport/local/LocalTransport.java — the in-process transport used by the
test cluster, which still serializes every message so wire bugs surface).

Every message crosses the seam as JSON bytes (bytes payloads wrapped in a
base64 tag) — the AssertingLocalTransport discipline: a payload that cannot
round-trip the wire format fails loudly in-process, exactly where a real
DCN/gRPC transport would fail. Fault injection (disconnect/drop rules) lives
here too, the MockTransportService analog
(src/test/java/org/elasticsearch/test/transport/MockTransportService.java).
"""

from __future__ import annotations

import base64
import json
import threading
from typing import Any, Callable


class TransportException(Exception):
    pass


class ConnectTransportException(TransportException):
    """Target node unreachable (dead, disconnected, or rule-dropped)."""

    def __init__(self, node_id: str, action: str = ""):
        super().__init__(f"cannot connect to node [{node_id}]"
                         + (f" for action [{action}]" if action else ""))
        self.node_id = node_id


class ActionNotFoundTransportException(TransportException):
    pass


class RemoteTransportException(TransportException):
    """Handler on the remote node raised; carries the remote error type so
    callers can branch on it (the reference serializes exceptions the same
    way)."""

    def __init__(self, node_id: str, action: str, error_type: str, message: str):
        super().__init__(f"[{node_id}][{action}] {error_type}: {message}")
        self.node_id = node_id
        self.action = action
        self.error_type = error_type
        self.error_message = message


# -- transport traffic classes (ISSUE 9) ------------------------------------
# The reference opens FIVE typed connection sets per node pair
# (NettyTransport.java:180-184: recovery=2, bulk=3, reg=6, state=1, ping=1)
# so recovery chunk streaming and bulk replication can never head-of-line-
# block query fan-out or cluster-state publishing. Here each (sender,
# target, class) tuple gets its own connection budget: a send first takes
# a class connection, waits in ITS CLASS's queue when the budget is full,
# and classes are fully isolated from each other. Same-thread nested sends
# re-enter their held connection (the in-process transport runs handlers
# in the caller's thread), and an implausibly-long wait fails OPEN with a
# counter rather than deadlocking the cluster.

TRAFFIC_CLASS_CONNECTIONS = {"recovery": 2, "bulk": 3, "reg": 6,
                             "state": 1, "ping": 1,
                             # sixth class (ISSUE 19): latency-sensitive
                             # traffic CROSSING a host boundary — the
                             # pod data plane's one pre-reduced DCN hop
                             # per host per query. Its own budget +
                             # queue keep slow DCN links from eating the
                             # intra-host "reg" connections, and the QoS
                             # EWMA tier keys off the class so DCN
                             # latency never poisons the ICI hedge
                             # deadline.
                             "dcn": 4}

#: fail-open ceiling for a class-connection wait; a timeout means the
#: class was saturated for this long — counted, never fatal
CLASS_WAIT_TIMEOUT_S = 30.0


def class_of_action(action: str) -> str:
    """Traffic class of a named transport action (mirrors the reference's
    ConnectionProfile mapping onto its five connection types)."""
    if action.startswith("internal:index/shard/recovery"):
        return "recovery"
    if action.startswith("indices:data/write"):
        return "bulk"
    if action == "internal:discovery/zen/fd/ping":
        return "ping"
    if action.startswith(("internal:cluster", "internal:discovery",
                          "internal:gateway", "cluster:",
                          "indices:admin")):
        return "state"
    return "reg"   # search/get/stats — the latency-sensitive default
                   # ("dcn" when the hop crosses hosts — LocalTransport
                   # upgrades per (sender, target) host identity)


_BYTES_TAG = "__b64__"
_ESC_TAG = "__esc__"


def _encode(obj: Any) -> Any:
    """Make a payload JSON-safe; bytes become tagged base64 strings. User
    dicts that happen to contain a tag key are escape-wrapped so document
    content can never be mistaken for wire framing."""
    if isinstance(obj, bytes):
        return {_BYTES_TAG: base64.b64encode(obj).decode("ascii")}
    if isinstance(obj, dict):
        enc = {k: _encode(v) for k, v in obj.items()}
        if _BYTES_TAG in obj or _ESC_TAG in obj:
            return {_ESC_TAG: enc}
        return enc
    if isinstance(obj, (list, tuple)):
        return [_encode(v) for v in obj]
    return obj


def _decode(obj: Any) -> Any:
    if isinstance(obj, dict):
        if set(obj) == {_BYTES_TAG}:
            return base64.b64decode(obj[_BYTES_TAG])
        if set(obj) == {_ESC_TAG}:
            return {k: _decode(v) for k, v in obj[_ESC_TAG].items()}
        return {k: _decode(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_decode(v) for v in obj]
    return obj


def roundtrip(payload: Any) -> Any:
    """Serialize + deserialize — the wire. Raises TypeError on content that
    could never cross a real transport (live objects, arrays, ...)."""
    return _decode(json.loads(json.dumps(_encode(payload))))


class LocalTransport:
    """The shared in-process 'network': a registry of node transports.

    Doubles as the discovery seed list — `connected_nodes()` is what a zen
    ping round would discover (ref discovery/zen/ping/unicast). Thread-safe;
    handlers execute synchronously in the caller's thread (the reference's
    LocalTransport hands off to a thread pool; synchronous execution keeps
    tests deterministic and still exercises the full serialize boundary).
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._nodes: dict[str, "TransportService"] = {}
        # fault-injection rules: (from_id|None, to_id) pairs that fail —
        # None matches any sender (full isolation of to_id)
        self._disconnected: set[tuple[str | None, str]] = set()
        # action-prefix-scoped drop rules (ISSUE 14): (from_id|None, to_id,
        # action_prefix) triples that fail — kills a single action class
        # (e.g. only replica bulk) without severing the link, so fault
        # detection pings keep flowing while the targeted traffic dies
        self._drop_rules: set[tuple[str | None, str, str]] = set()
        # latency-injection rules: (to_id, action_prefix) -> seconds of
        # added delivery delay (the slow-replica half of the
        # MockTransportService analog; hedged-read tests use this)
        self._delays: dict[tuple[str, str], float] = {}
        # es_transport_faults_injected_total: every fault this layer
        # actually APPLIED to a delivery (blocked, rule-dropped, delayed)
        self.faults_injected = 0
        self.messages_sent = 0
        self.bytes_sent = 0
        self.max_message_bytes = 0   # largest single frame (recovery tests
                                     # assert chunking bounds this)
        # per-(sender, target, class) connection budgets + per-class queue
        # accounting (ISSUE 9; ref NettyTransport.java:180-184)
        self._class_sems: dict[tuple[str, str, str],
                               threading.Semaphore] = {}
        self._held = threading.local()   # same-thread re-entrancy
        # simulated host identity (ISSUE 19): node_id -> host name. Two
        # nodes on DIFFERENT hosts exchange latency-sensitive traffic on
        # the "dcn" class instead of "reg" (ICI within a host, DCN
        # between — SURVEY §5.8). Unregistered nodes count as co-hosted.
        self._hosts: dict[str, str] = {}
        self._class_stats: dict[str, dict] = {
            c: {"sent_total": 0, "bytes_sent_total": 0, "queue_depth": 0,
                "max_queue_depth": 0, "queue_timeouts_total": 0,
                "connections": TRAFFIC_CLASS_CONNECTIONS[c]}
            for c in TRAFFIC_CLASS_CONNECTIONS}

    def register(self, service: "TransportService") -> None:
        with self._lock:
            self._nodes[service.node_id] = service

    def set_host(self, node_id: str, host: str) -> None:
        """Pin a node to a simulated host (the pods harness's topology
        declaration); cross-host "reg" traffic upgrades to "dcn"."""
        with self._lock:
            self._hosts[node_id] = str(host)

    def host_of(self, node_id: str) -> str | None:
        with self._lock:
            return self._hosts.get(node_id)

    def _class_for(self, from_id: str, to_id: str, action: str) -> str:
        """Traffic class of this delivery: class_of_action, with "reg"
        upgraded to "dcn" when sender and target sit on different
        (known) hosts."""
        tc = class_of_action(action)
        if tc != "reg":
            return tc
        with self._lock:
            fh = self._hosts.get(from_id)
            th = self._hosts.get(to_id)
        if fh is not None and th is not None and fh != th:
            return "dcn"
        return tc

    def unregister(self, node_id: str) -> None:
        with self._lock:
            self._nodes.pop(node_id, None)

    def connected_nodes(self) -> list[str]:
        with self._lock:
            return sorted(self._nodes)

    # -- fault injection (MockTransportService analog) --------------------

    def disconnect(self, node_id: str, from_id: str | None = None) -> None:
        """Make node_id unreachable (from from_id, or from everyone)."""
        with self._lock:
            self._disconnected.add((from_id, node_id))

    def reconnect(self, node_id: str, from_id: str | None = None) -> None:
        with self._lock:
            self._disconnected.discard((from_id, node_id))

    def partition(self, side_a: list[str], side_b: list[str]) -> None:
        """Two-way network partition between node groups
        (ref test/disruption/NetworkPartition)."""
        with self._lock:
            for a in side_a:
                for b in side_b:
                    self._disconnected.add((a, b))
                    self._disconnected.add((b, a))

    def add_rule(self, node_id: str, action_prefix: str = "",
                 from_id: str | None = None) -> None:
        """Drop every message TO node_id whose action starts with
        action_prefix ("" = every action — equivalent to disconnect), from
        from_id or from anyone. Unlike disconnect, a scoped rule leaves the
        rest of the link healthy: chaos can kill only bulk replication (or
        only the query phase) while pings keep the node in the cluster."""
        with self._lock:
            self._drop_rules.add((from_id, node_id, action_prefix))

    def clear_rule(self, node_id: str, action_prefix: str = "",
                   from_id: str | None = None) -> None:
        with self._lock:
            self._drop_rules.discard((from_id, node_id, action_prefix))

    def clear_rules(self) -> None:
        with self._lock:
            self._drop_rules.clear()

    def _rule_dropped(self, from_id: str, to_id: str, action: str) -> bool:
        # caller holds the lock
        if not self._drop_rules:
            return False
        return any(nid == to_id and (frm is None or frm == from_id)
                   and action.startswith(pfx)
                   for frm, nid, pfx in self._drop_rules)

    def fault_stats(self) -> dict:
        """Leaves for the `transport` metric section
        (es_transport_faults_injected_total) + active-rule gauges."""
        with self._lock:
            return {"faults_injected_total": self.faults_injected,
                    "disconnected_links": len(self._disconnected),
                    "drop_rules": len(self._drop_rules),
                    "delay_rules": len(self._delays)}

    def heal(self) -> None:
        with self._lock:
            self._disconnected.clear()
            self._drop_rules.clear()
            self._delays.clear()

    def add_delay(self, node_id: str, action_prefix: str,
                  seconds: float) -> None:
        """Inject delivery latency into every message TO node_id whose
        action starts with action_prefix (slow-replica fault injection —
        the hedged-read and traffic-class tests drive this)."""
        with self._lock:
            self._delays[(node_id, action_prefix)] = float(seconds)

    def clear_delay(self, node_id: str, action_prefix: str) -> None:
        with self._lock:
            self._delays.pop((node_id, action_prefix), None)

    def _delay_of(self, to_id: str, action: str) -> float:
        with self._lock:
            if not self._delays:
                return 0.0
            return max((s for (nid, pfx), s in self._delays.items()
                        if nid == to_id and action.startswith(pfx)),
                       default=0.0)

    # -- typed connection classes (ISSUE 9) --------------------------------

    def _acquire_class(self, from_id: str, to_id: str, tclass: str):
        """Take a class connection for the (sender, target) pair, queueing
        in the class's OWN send queue when the budget is full — classes
        never contend with each other. Returns a release callable, or
        None when this thread already holds a connection of the tuple
        (nested same-pair sends re-enter; the in-process transport runs
        handlers in the caller's thread)."""
        key = (from_id, to_id, tclass)
        held: dict = getattr(self._held, "keys", None) or {}
        if held.get(key):
            return None              # re-entrant: ride the held connection
        with self._lock:
            sem = self._class_sems.get(key)
            if sem is None:
                sem = self._class_sems[key] = threading.Semaphore(
                    TRAFFIC_CLASS_CONNECTIONS[tclass])
            st = self._class_stats[tclass]
            st["queue_depth"] += 1
            st["max_queue_depth"] = max(st["max_queue_depth"],
                                        st["queue_depth"])
        acquired = sem.acquire(timeout=CLASS_WAIT_TIMEOUT_S)
        with self._lock:
            st = self._class_stats[tclass]
            st["queue_depth"] -= 1
            if not acquired:
                # fail OPEN: a class saturated past the ceiling proceeds
                # (counted) rather than wedging the cluster
                st["queue_timeouts_total"] += 1
            st["sent_total"] += 1
        held[key] = True
        self._held.keys = held

        def release():
            held.pop(key, None)
            if acquired:
                sem.release()
        return release

    def class_stats(self) -> dict:
        """{class: leaves} for the `transport_class` metric section
        (es_transport_class_queue_depth{class=} et al.)."""
        with self._lock:
            return {c: dict(st) for c, st in self._class_stats.items()}

    # -- the wire ----------------------------------------------------------

    def deliver(self, from_id: str, to_id: str, action: str,
                payload: Any) -> Any:
        with self._lock:
            blocked = ((from_id, to_id) in self._disconnected
                       or (None, to_id) in self._disconnected
                       or self._rule_dropped(from_id, to_id, action))
            if blocked:
                self.faults_injected += 1
            target = self._nodes.get(to_id)
        if blocked or target is None:
            raise ConnectTransportException(to_id, action)
        release = self._acquire_class(from_id, to_id,
                                      self._class_for(from_id, to_id,
                                                      action))
        try:
            delay = self._delay_of(to_id, action)
            if delay > 0:
                with self._lock:
                    self.faults_injected += 1
                import time as _time
                _time.sleep(delay)
            return self._deliver_framed(from_id, to_id, action, payload)
        finally:
            if release is not None:
                release()

    def _deliver_framed(self, from_id: str, to_id: str, action: str,
                        payload: Any) -> Any:
        with self._lock:
            target = self._nodes.get(to_id)
        if target is None:
            raise ConnectTransportException(to_id, action)
        # per-class byte accounting: the recovery class's counter is how
        # the tests verify throttle compliance on the wire itself
        cls_st = self._class_stats[self._class_for(from_id, to_id, action)]
        wire = json.dumps(_encode(payload))
        with self._lock:
            self.messages_sent += 1
            self.bytes_sent += len(wire)
            cls_st["bytes_sent_total"] += len(wire)
            self.max_message_bytes = max(self.max_message_bytes, len(wire))
        request = _decode(json.loads(wire))
        response = target._handle(from_id, action, request)
        wire_resp = json.dumps(_encode(response))
        with self._lock:
            self.bytes_sent += len(wire_resp)
            cls_st["bytes_sent_total"] += len(wire_resp)
            self.max_message_bytes = max(self.max_message_bytes,
                                         len(wire_resp))
        return _decode(json.loads(wire_resp))


class TransportService:
    """Per-node RPC hub (ref TransportService.java:60). Actions are named
    strings (e.g. "indices:data/write/index[p]"); local sends short-circuit
    the registry but still round-trip the wire format."""

    def __init__(self, node_id: str, network: LocalTransport):
        self.node_id = node_id
        self.network = network
        self._handlers: dict[str, Callable[[str, Any], Any]] = {}
        network.register(self)

    def register_handler(self, action: str,
                         handler: Callable[[str, Any], Any]) -> None:
        """handler(from_node_id, request) -> response (JSON-safe)."""
        self._handlers[action] = handler

    def send(self, node_id: str, action: str, payload: Any = None) -> Any:
        """Synchronous request/response. Raises ConnectTransportException if
        the target is unreachable, RemoteTransportException if its handler
        raised."""
        return self.network.deliver(self.node_id, node_id, action, payload)

    def _handle(self, from_id: str, action: str, request: Any) -> Any:
        handler = self._handlers.get(action)
        if handler is None:
            raise ActionNotFoundTransportException(
                f"no handler for [{action}] on [{self.node_id}]")
        try:
            return handler(from_id, request)
        except TransportException:
            raise
        except Exception as e:  # noqa: BLE001 — serialize like a real wire
            raise RemoteTransportException(
                self.node_id, action, type(e).__name__, str(e)) from e

    def close(self) -> None:
        self.network.unregister(self.node_id)
