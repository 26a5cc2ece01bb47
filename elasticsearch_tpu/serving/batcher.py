"""Dynamic request batcher: concurrent solo `_search` requests coalesce
into ONE packed device program.

The reference gets its QPS from thread-pool concurrency (one Lucene search
per thread, search/SearchService + the SEARCH thread pool); a TPU gets it
from BATCHING — the packed kernel's cost is nearly flat in Q, so serving
32 queued requests in one program costs barely more than serving one.

Design: continuous batching with ZERO added latency when idle. The first
request for a compatibility group becomes the LEADER and executes
immediately with whatever is queued at that moment (itself). Requests
arriving while the device is busy queue up; when the leader finishes it
takes the whole accumulated queue as the next batch. Under load, batch
size self-tunes to (arrival rate x device latency) — exactly the dynamic
batching window, without a sleep on the idle path.

Two lanes share the leader/follower core (ISSUE 9):

  * the PACKED lane (`submit`) — packed-spec-eligible bodies ride the
    packed view kernel as before;
  * the COALESCED GENERAL lane (`join_batched`/`drain_batched`) — bodies
    WITHOUT a packed spec that `_search_batched` can serve (plan-shaped
    queries, aggs, knn, rescore) coalesce onto the stacked/blockwise/mesh
    Q>1 replica axis. The first request LEADS by running the ordinary
    solo path (idle-path latency stays zero and solo responses are
    byte-identical to the pre-QoS engine); requests arriving while it
    runs queue as followers, and the leader drains them as Q>1
    `_search_batched` batches — results bitwise-identical to solo
    execution (tests/test_qos.py parity matrix). Dashboard panels
    (search/aggs/panels.py) ride this lane under a key of their own:
    leader, followers and a follower whose wait ran out all run the
    panel lane's closed set of programs (Q buckets 1 | 4 | 32). A body
    with a packed spec never joins: when its packed stay returns None the
    node serves it solo through the general driver.

Followers wait under a DEADLINE-AWARE timeout (QosController.
follower_wait_s — a multiple of the EWMA device latency, never the old
silent hard-coded 30 s); timeouts and leader-exit strandings are counted
and surfaced on `/_metrics` (`es_search_batcher_wait_timeouts_total`,
`es_search_batcher_stranded_total`), and batch-execution errors are
recorded (`run_errors_total` + `last_error`), not discarded.

ref: the role of org.elasticsearch.threadpool.ThreadPool's SEARCH pool —
but the unit of concurrency is a device batch, not a thread.
"""

from __future__ import annotations

import logging
import threading

from ..common import tracing

logger = logging.getLogger("elasticsearch_tpu.serving.batcher")

#: sentinel returned by `join_batched` when the caller holds leadership —
#: it must run the solo path itself, then call `drain_batched`.
LEAD = object()


class _Entry:
    __slots__ = ("body", "spec", "event", "out", "err", "t0", "t_submit",
                 "t_taken", "abandoned")

    def __init__(self, body, spec, t0: int | None = None):
        self.body = body
        self.spec = spec         # packed lane: its spec; a panel: its row
        self.event = threading.Event()
        self.out = None          # response dict, or None -> general path
        self.err = None
        self.t0 = t0             # ns: the request's own start, for `took`
        self.t_submit = tracing.now_ns()
        self.t_taken = None      # ns: a batch took the entry off the queue
        self.abandoned = False   # follower timed out; don't spend a row


class SearchBatcher:
    """Per-node coalescer for packed-eligible solo searches."""

    MAX_BATCH = 32               # one device batch == one warm Q bucket

    _log_budget = 10             # rate-limited anomaly logging (per class)

    def __init__(self, node):
        self.node = node
        self._lock = threading.Lock()
        self._queues: dict[tuple, list[_Entry]] = {}
        self._busy: set[tuple] = set()
        self.batches = 0         # observability: device batches executed
        self.batched_requests = 0
        # batch-occupancy histogram {batch size: batches}: how full the
        # coalescing window actually runs — THE serving-efficiency gauge
        # (occupancy 1 = no coalescing happened; near MAX_BATCH = the
        # arrival rate saturates the device latency window)
        self.occupancy: dict[int, int] = {}
        # ISSUE 9 satellite: the silent failure paths are now counted —
        # stranded followers (leader exited with entries still queued),
        # follower wait timeouts (the old hard 30 s fell through with no
        # signal), and batch-execution errors (the swallowed `ex`)
        self.stranded = 0
        self.wait_timeouts = 0
        self.run_errors = 0
        self.last_error: str | None = None

    # -- shared plumbing ---------------------------------------------------

    def _window(self) -> int:
        """Coalescing window: MAX_BATCH when healthy; the QoS controller
        shrinks it under degrade pressure (smaller batches = lower
        per-batch latency) before any request sheds."""
        qos = getattr(self.node, "qos", None)
        if qos is not None:
            return qos.batch_window(self.MAX_BATCH)
        return self.MAX_BATCH

    def _wait_timeout(self) -> float:
        qos = getattr(self.node, "qos", None)
        if qos is not None:
            return qos.follower_wait_s()
        return 30.0

    @classmethod
    def _log_anomaly(cls, msg: str, *args, exc_info: bool = False) -> None:
        if cls._log_budget > 0:
            cls._log_budget -= 1
            logger.warning(msg, *args, exc_info=exc_info)

    def _wait(self, e: _Entry):
        """Follower wait with the deadline-aware timeout; a timeout falls
        to the general path, counted and logged instead of silent."""
        with tracing.span("batcher.follow"):
            served = e.event.wait(timeout=self._wait_timeout())
        self._note_wait(e)
        if not served:
            e.abandoned = True
            with self._lock:
                self.wait_timeouts += 1
            self._log_anomaly(
                "batcher follower timed out after %.1fs waiting for its "
                "leader; serving via the general path",
                self._wait_timeout())
            return None
        if e.err is not None:
            raise e.err
        return e.out

    def _take(self, batch: list[_Entry]) -> None:
        """The batch leaves the queue for the device: the end of each
        member's queue wait (leader ≈ 0; followers accrue while the
        previous batch runs) — the admission-latency half of batcher cost,
        invisible to the device timers because it happens on the host."""
        now = tracing.now_ns()
        for x in batch:
            x.t_taken = now

    def _note_wait(self, e: _Entry) -> None:
        """Each member books its own `batcher.queue_wait`, on its own
        thread, so the span lands in its own request's tree."""
        if e.t_taken is None:
            return               # never taken: timed out or stranded
        tracing.add_span("batcher.queue_wait", e.t_submit, e.t_taken)
        metrics = getattr(self.node, "metrics", None)
        if metrics is not None:
            metrics.record("batcher.queue_wait",
                           (e.t_taken - e.t_submit) / 1e6)

    def _release(self, key: tuple) -> None:
        """Leader exit: release leadership and unblock any leftover
        followers (they serve themselves on the general path) — counted,
        because a nonzero rate means the leader loop exited abnormally."""
        with self._lock:
            self._busy.discard(key)
            leftover = self._queues.pop(key, [])
            self.stranded += len(leftover)
        for x in leftover:   # no leader left: don't strand them silently
            x.out = None
            x.event.set()
        if leftover:
            self._log_anomaly(
                "batcher leader exited with %d followers still queued; "
                "they fall to the general path", len(leftover))

    # -- the packed lane ---------------------------------------------------

    def submit(self, key: tuple, name: str, body: dict, spec,
               size: int, from_: int, t0: int):
        """Execute (or join) a packed batch for this request, which began
        at `t0` (ns). Returns the response dict, or None when the request
        must take the general path (unservable batch / view refusal)."""
        key = ("packed", *key)
        e = _Entry(body, spec, t0)
        with self._lock:
            self._queues.setdefault(key, []).append(e)
            leader = key not in self._busy
            if leader:
                self._busy.add(key)
        if not leader:
            return self._wait(e)

        try:
            while True:
                with self._lock:
                    batch = self._queues.pop(key, [])
                    batch = [x for x in batch if not x.abandoned]
                    if not batch:
                        break
                    window = self._window()
                    if len(batch) > window:
                        self._queues[key] = batch[window:]
                        batch = batch[:window]
                self._run(name, batch, size, from_)
        finally:
            self._release(key)
        self._note_wait(e)
        if e.err is not None:
            raise e.err
        return e.out

    def _run(self, name, batch, size, from_):
        self._take(batch)
        try:
            outs = self.node._packed_search(
                name, [x.body for x in batch], size=size, from_=from_,
                t0=[x.t0 for x in batch], specs=[x.spec for x in batch])
        except Exception as ex:  # noqa: BLE001 — every member's error
            self._record_error(ex)
            for x in batch:
                x.err = ex
                x.event.set()
            return
        self._book(batch)
        for i, x in enumerate(batch):
            x.out = None if outs is None else outs[i]
            x.event.set()

    # -- the coalesced general lane (ISSUE 9) ------------------------------

    def join_batched(self, key: tuple, body: dict, row=None):
        """The coalesced general lane's entry point (bodies without a
        packed spec only: `NodeService._search_exec`; `row` is a dashboard
        panel's `PanelRow`, which its batch runs in the body's place).
        Returns the LEAD sentinel when the caller acquired leadership — it
        must execute its solo path for itself (the general driver, or a
        panel's Q = 1 program) and call `drain_batched(key, index)` when
        done (a try/finally at the call site). Otherwise the caller is a
        follower: blocks until the leader serves it and returns the
        response dict, or None when its wait ran out, the leader left it
        stranded or the panel lane could not serve its batch; the caller
        then serves it solo the same way. A batch that raises is re-raised
        here: it is the follower's error."""
        key = ("gen", *key)
        with self._lock:
            if key not in self._busy:
                self._busy.add(key)
                return LEAD
            e = _Entry(body, row)
            self._queues.setdefault(key, []).append(e)
        return self._wait(e)

    def drain_batched(self, key: tuple, index: str) -> None:
        """Leader epilogue: serve every follower that queued behind this
        leader's solo execution as Q>1 batches (`_search_batched`; panels'
        rows through `_search_panels`), then release leadership. Never
        raises — a failing batch is its members' error (each follower
        re-raises it)."""
        key = ("gen", *key)
        try:
            while True:
                with self._lock:
                    batch = self._queues.pop(key, [])
                    batch = [x for x in batch if not x.abandoned]
                    if not batch:
                        break
                    window = self._window()
                    if len(batch) > window:
                        self._queues[key] = batch[window:]
                        batch = batch[:window]
                self._run_batched(index, batch)
        finally:
            self._release(key)

    def _run_batched(self, index: str, batch: list[_Entry]) -> None:
        self._take(batch)
        try:
            if batch[0].spec is not None:    # a key of panels: their rows
                outs = self.node._search_panels(
                    index, [x.spec for x in batch], batch[0].t_taken)
            else:
                outs = self.node._search_batched(
                    [(index, x.body) for x in batch])
        except Exception as ex:  # noqa: BLE001 — every member's error
            self._record_error(ex)
            for x in batch:
                x.err = ex
                x.event.set()
            return
        self._book(batch)
        for i, x in enumerate(batch):
            x.out = None if outs is None else outs[i]
            x.event.set()

    # -- accounting --------------------------------------------------------

    def _book(self, batch: list[_Entry]) -> None:
        with self._lock:
            self.batches += 1
            self.batched_requests += len(batch)
            self.occupancy[len(batch)] = \
                self.occupancy.get(len(batch), 0) + 1

    def _record_error(self, ex: BaseException) -> None:
        with self._lock:
            self.run_errors += 1
            self.last_error = f"{type(ex).__name__}: {ex}"

    def stats(self) -> dict:
        with self._lock:
            return {"batches": self.batches,
                    "batched_requests": self.batched_requests,
                    "stranded_total": self.stranded,
                    "wait_timeouts_total": self.wait_timeouts,
                    "run_errors_total": self.run_errors,
                    "last_error": self.last_error,
                    "occupancy": dict(sorted(self.occupancy.items()))}
