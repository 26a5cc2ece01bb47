"""Dynamic request batcher: concurrent solo `_search` requests coalesce
into ONE device program.

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

One loop serves every lane, and it knows none of them (`coalesce`): the
caller hands it a key, its own `item` (opaque here) and `run`, the lane's
batch runner. What differs between lanes is who the leader is:

  * without `lead` the leader is a MEMBER of the first batch it runs (the
    node's packed lane: packed-spec-eligible bodies ride the packed view
    kernel, the leader's own among them);
  * with `lead` the leader holds no entry: it answers itself with
    `lead()` (the node's general driver, or a dashboard panel's Q = 1
    programs — idle-path latency stays zero and solo responses are
    byte-identical to the pre-QoS engine) while requests of its key queue
    behind it, then drains them through `run` as Q > 1 batches — results
    bitwise-identical to solo execution (tests/test_qos.py parity
    matrix). A follower that gets no answer (its wait ran out, its leader
    left it, `run` gave None) answers itself with `lead()` too.

Followers wait under a DEADLINE-AWARE timeout (QosController.
follower_wait_s — a multiple of the EWMA device latency); timeouts and
leader-exit strandings are counted and surfaced on `/_metrics`
(`es_search_batcher_wait_timeouts_total`, `..._stranded_total`), and
batch-execution errors are recorded (`run_errors_total` + `last_error`).

ref: the role of org.elasticsearch.threadpool.ThreadPool's SEARCH pool —
but the unit of concurrency is a device batch, not a thread.
"""

from __future__ import annotations

import logging
import threading

from ..common import tracing

logger = logging.getLogger("elasticsearch_tpu.serving.batcher")


class _Entry:
    __slots__ = ("item", "event", "out", "err", "t_submit", "t_taken",
                 "t_set", "abandoned")

    def __init__(self, item):
        self.item = item         # the caller's own; `run` gets it back
        self.event = threading.Event()
        self.out = None          # its slot of `run`'s answer, or None
        self.err = None
        self.t_submit = tracing.now_ns()
        self.t_taken = self.t_set = None  # ns: taken off the queue; woken
        self.abandoned = False   # follower timed out; don't spend a row


class SearchBatcher:
    """Per-node coalescer of concurrent solo searches."""

    MAX_BATCH = 32               # one device batch == one warm Q bucket

    _log_budget = 10             # rate-limited anomaly logging (per class)

    def __init__(self, qos, metrics):
        # `qos.batch_window`: MAX_BATCH when healthy, shrunk under degrade
        # pressure (smaller batches = lower per-batch latency) before any
        # request sheds; `qos.follower_wait_s`; `metrics.record`
        self.qos = qos
        self.metrics = metrics
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
        # the failure paths are counted, not silent: stranded followers
        # (leader exited with entries still queued), follower wait
        # timeouts, batch-execution errors
        self.stranded = 0
        self.wait_timeouts = 0
        self.run_errors = 0
        self.last_error: str | None = None

    def coalesce(self, key: tuple, item, run, lead=None):
        """Answer one request of the compatibility group `key`, alone or
        in a batch with the requests that queue while the group's leader
        is busy. -> (answer, shared): `shared` says the answer is a slot
        of a `run` batch (the caller books it as such; `lead()` books its
        own).

        `run(items, t_taken) -> list | None` answers a batch of queued
        items, taken off the queue at `t_taken` (ns), one answer each in
        their order. None is None for every member; an exception is every
        member's exception, re-raised in each.

        `lead is None`: `item` is queued, and the first request of an idle
        key leads: it runs batches, its own item in the first, until the
        queue is empty. An answer of None (also: the wait ran out, the
        leader left) is the caller's to serve some other way.

        `lead` given: the first request of an idle key holds no entry,
        answers itself with `lead()`, then runs what queued meanwhile and
        releases the key, whatever `lead()` did. A follower with no answer
        answers itself with `lead()` as well."""
        # a request that is queued whoever leads stamps its entry before
        # it waits for the lock: its queue wait counts from here
        e = _Entry(item) if lead is None else None
        with self._lock:
            leader = key not in self._busy
            if leader:
                self._busy.add(key)
            elif e is None:
                e = _Entry(item)
            if e is not None:
                self._queues.setdefault(key, []).append(e)
        if leader:
            try:
                own = lead() if lead is not None else None
            finally:
                self._drain(key, run)
            if e is None:
                return own, False
        served = leader or self._wait(e)
        self._note_wait(e)
        if served and e.err is not None:
            raise e.err
        out = e.out if served else None  # a wait that ran out reads nothing
        if out is None and lead is not None:
            return lead(), False
        return out, out is not None

    # -- the one loop --------------------------------------------------------

    def _drain(self, key: tuple, run) -> None:
        """The leader's loop: serve the key's queue in arrival order, a
        window at a time, until it is empty; then release the key. Never
        raises — a failing batch is its members' error."""
        try:
            while True:
                with self._lock:
                    batch = self._queues.pop(key, [])
                    batch = [x for x in batch if not x.abandoned]
                    if not batch:
                        break
                    window = self.qos.batch_window(self.MAX_BATCH)
                    if len(batch) > window:
                        self._queues[key] = batch[window:]
                        batch = batch[:window]
                self._run(run, batch)
        finally:
            self._release(key)

    def _run(self, run, batch: list[_Entry]) -> None:
        # the batch leaves the queue for the device: the end of each
        # member's queue wait (a leader's own ≈ 0; followers accrue while
        # the previous batch runs), which no device timer sees
        now = tracing.now_ns()
        for x in batch:
            x.t_taken = now
        try:
            outs = run([x.item for x in batch], now)
        except Exception as ex:  # noqa: BLE001 — every member's error
            with self._lock:
                self.run_errors += 1
                self.last_error = f"{type(ex).__name__}: {ex}"
            for x in batch:
                x.err = ex
                x.t_set = tracing.now_ns()
                x.event.set()
            return
        with self._lock:
            self.batches += 1
            self.batched_requests += len(batch)
            self.occupancy[len(batch)] = \
                self.occupancy.get(len(batch), 0) + 1
        for i, x in enumerate(batch):
            x.out = None if outs is None else outs[i]
            x.t_set = tracing.now_ns()
            x.event.set()

    def _release(self, key: tuple) -> None:
        """Leader exit: release leadership and unblock any leftover
        followers (they serve themselves some other way) — counted,
        because a nonzero rate means the leader loop exited abnormally."""
        with self._lock:
            self._busy.discard(key)
            leftover = self._queues.pop(key, [])
            self.stranded += len(leftover)
        for x in leftover:   # no leader left: don't strand them silently
            x.out = None
            x.t_set = tracing.now_ns()
            x.event.set()
        if leftover:
            self._log_anomaly(
                "batcher leader exited with %d followers still queued; "
                "they serve themselves", len(leftover))

    # -- a member's side -----------------------------------------------------

    def _wait(self, e: _Entry) -> bool:
        """Follower wait with the deadline-aware timeout -> served? A
        served follower books `batcher.wake`, from the leader's `set()` to
        its own thread running again. A timeout is counted and logged
        instead of silent."""
        timeout = self.qos.follower_wait_s()
        with tracing.span("batcher.follow"):
            served = e.event.wait(timeout=timeout)
            if served:
                tracing.add_span("batcher.wake", e.t_set, tracing.now_ns())
        if not served:
            e.abandoned = True
            with self._lock:
                self.wait_timeouts += 1
            self._log_anomaly(
                "batcher follower timed out after %.1fs waiting for its "
                "leader; it serves itself", timeout)
        return served

    def _note_wait(self, e: _Entry) -> None:
        """Each member books its own `batcher.queue_wait`, on its own
        thread, so the span lands in its own request's tree."""
        if e.t_taken is None:
            return               # never taken: timed out or stranded
        tracing.add_span("batcher.queue_wait", e.t_submit, e.t_taken)
        self.metrics.record("batcher.queue_wait",
                            (e.t_taken - e.t_submit) / 1e6)

    @classmethod
    def _log_anomaly(cls, msg: str, *args) -> None:
        if cls._log_budget > 0:
            cls._log_budget -= 1
            logger.warning(msg, *args)

    def stats(self) -> dict:
        with self._lock:
            return {"batches": self.batches,
                    "batched_requests": self.batched_requests,
                    "stranded_total": self.stranded,
                    "wait_timeouts_total": self.wait_timeouts,
                    "run_errors_total": self.run_errors,
                    "last_error": self.last_error,
                    "occupancy": dict(sorted(self.occupancy.items()))}
