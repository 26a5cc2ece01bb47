"""Per-phase search timers, histogram metrics, request profiling + slowlog.

The observability floor (SURVEY §5.1/§5.5; VERDICT r4 #10):
  * PhaseTimers — parse / device(query) / fetch / render wall-time
    accumulators, surfaced through `_nodes/stats` and `_stats`. This is
    the TPU analog of the reference's per-phase stats (SearchStats
    queryTime/fetchTime) — here the interesting split is host parse vs
    device program vs response render, because host overhead is where
    TPU serving loses its speedup.
  * MetricsRegistry — histogram-capable named timers (count/sum/min/max/
    p50/p99 from a bounded reservoir), the `profiling` section of
    `_nodes/stats`.
  * RequestProfiler — the per-request timing tree behind `"profile": true`
    on `_search` (ref search/profile/ Profilers + InternalProfiler in
    later reference versions). The TPU twist the reference never had: jit
    retraces and host↔device transfers silently dominate tail latency, so
    the profiler also diffs process-wide compile events (jax.monitoring)
    and counts bytes crossing the device boundary per request.
  * SlowLog — per-index query slowlog with live-updatable thresholds
    (ref index/search/slowlog/ShardSlowLogSearchService.java: warn/info/
    debug/trace thresholds from index settings, applied per request),
    stamped with the request's trace/opaque ids so one id correlates the
    slowlog, the task listing and the profile output.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import math
import re as _re
import threading
import time
import uuid
from collections import deque


class PhaseTimers:
    """Lock-cheap accumulators: {phase: (count, total_ms, max_ms)}."""

    PHASES = ("parse", "device", "fetch", "render", "total")

    def __init__(self):
        self._lock = threading.Lock()
        self._acc: dict[str, list] = {p: [0, 0.0, 0.0] for p in self.PHASES}

    def record(self, phase: str, ms: float) -> None:
        with self._lock:
            a = self._acc.setdefault(phase, [0, 0.0, 0.0])
            a[0] += 1
            a[1] += ms
            a[2] = max(a[2], ms)

    def stats(self) -> dict:
        with self._lock:
            return {p: {"count": a[0],
                        "time_in_millis": round(a[1], 3),
                        "max_millis": round(a[2], 3)}
                    for p, a in self._acc.items() if a[0]}


class MetricsRegistry:
    """Named wall-time histograms: count/sum/min/max plus p50/p99 computed
    from a bounded sample reservoir (the reference keeps count+sum only;
    tail percentiles are what a latency SLO actually needs)."""

    def __init__(self, reservoir: int = 512):
        self._lock = threading.Lock()
        self._reservoir = reservoir
        self._timers: dict[str, dict] = {}

    def record(self, name: str, ms: float) -> None:
        with self._lock:
            t = self._timers.get(name)
            if t is None:
                t = self._timers[name] = {
                    "count": 0, "sum": 0.0,
                    "min": float("inf"), "max": 0.0,
                    "samples": deque(maxlen=self._reservoir)}
            t["count"] += 1
            t["sum"] += ms
            t["min"] = min(t["min"], ms)
            t["max"] = max(t["max"], ms)
            t["samples"].append(ms)

    @contextlib.contextmanager
    def timer(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(name, (time.perf_counter() - t0) * 1000)

    def stats(self) -> dict:
        with self._lock:
            snap = {n: (t["count"], t["sum"], t["min"], t["max"],
                        sorted(t["samples"]))
                    for n, t in self._timers.items()}
        out = {}
        for name, (count, total, mn, mx, samples) in snap.items():
            entry = {"count": count,
                     "time_in_millis": round(total, 3),
                     "min_millis": round(mn, 3),
                     "max_millis": round(mx, 3)}
            if samples:
                entry["p50_millis"] = round(
                    samples[len(samples) // 2], 3)
                entry["p99_millis"] = round(
                    samples[min(len(samples) - 1,
                                int(len(samples) * 0.99))], 3)
            out[name] = entry
        return out


class Meter:
    """Exponentially-weighted moving-average rate meter (the codahale
    Meter the reference exposes through its stats APIs): 1m/5m/15m rates
    ticked on a fixed 5s interval, plus a lifetime mean. The clock is
    injectable so tests drive exact tick sequences with no sleeping —
    rates are then a pure function of (marks, tick times)."""

    TICK_S = 5.0
    WINDOWS = (60, 300, 900)

    def __init__(self, clock=None):
        self._clock = clock or time.monotonic
        self._lock = threading.Lock()
        self.count = 0
        self._uncounted = 0
        self._start = self._last_tick = self._clock()
        # EWMA per window; None until the first tick initializes it to the
        # first interval's instant rate (the codahale bootstrap)
        self._ewma: dict[int, float | None] = {w: None for w in self.WINDOWS}

    def _tick(self, now: float) -> None:
        # caller holds the lock
        intervals = int((now - self._last_tick) / self.TICK_S)
        if intervals <= 0:
            return
        instant = self._uncounted / self.TICK_S
        self._uncounted = 0
        self._last_tick += intervals * self.TICK_S
        for w in self.WINDOWS:
            alpha = 1.0 - math.exp(-self.TICK_S / w)
            r = self._ewma[w]
            if r is None:
                r = instant
                intervals_left = intervals - 1
            else:
                r += alpha * (instant - r)
                intervals_left = intervals - 1
            # idle intervals after the first decay toward zero
            for _ in range(intervals_left):
                r += alpha * (0.0 - r)
            self._ewma[w] = r

    def mark(self, n: int = 1) -> None:
        with self._lock:
            self._tick(self._clock())
            self.count += n
            self._uncounted += n

    def rate(self, window: int = 60) -> float:
        """Events/second over the EWMA window (0.0 before the first tick)."""
        with self._lock:
            self._tick(self._clock())
            r = self._ewma[window]
            return r if r is not None else 0.0

    def mean_rate(self) -> float:
        with self._lock:
            elapsed = self._clock() - self._start
            return self.count / elapsed if elapsed > 0 else 0.0

    def stats(self) -> dict:
        with self._lock:
            self._tick(self._clock())
            out = {"count": self.count,
                   "mean_rate": round(self.mean_rate_locked(), 4)}
            for w, label in zip(self.WINDOWS, ("1m", "5m", "15m")):
                r = self._ewma[w]
                out[f"rate_{label}"] = round(r, 4) if r is not None else 0.0
            return out

    def mean_rate_locked(self) -> float:
        elapsed = self._clock() - self._start
        return self.count / elapsed if elapsed > 0 else 0.0


# ---------------------------------------------------------------------------
# Device-level counters: jit compiles (retraces) via jax.monitoring, bytes
# crossing the host↔device boundary via the device_fetch/note_h2d seams.
# Process-wide accumulators; RequestProfiler diffs them around a request.
# ---------------------------------------------------------------------------

_DEVICE_EVENTS = {"compiles": 0, "compile_ms": 0.0,
                  "h2d_bytes": 0, "d2h_bytes": 0, "fetches": 0}
_DEVICE_LOCK = threading.Lock()
_LISTENER_INSTALLED = False


def _on_compile_duration(name, secs, **kw):  # noqa: ANN001 — jax callback
    if "/jax/core/compile/" not in name:
        return
    with _DEVICE_LOCK:
        if name.endswith("backend_compile_duration"):
            _DEVICE_EVENTS["compiles"] += 1
        _DEVICE_EVENTS["compile_ms"] += secs * 1000.0


def _install_compile_listener() -> None:
    """Register a jax.monitoring duration listener (idempotent). Compile
    events fire only on an actual retrace+compile, never on a cache-hit
    dispatch — exactly the signal the no-retrace tripwire needs."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    _LISTENER_INSTALLED = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_duration)


def device_events_snapshot() -> tuple[int, float]:
    with _DEVICE_LOCK:
        return _DEVICE_EVENTS["compiles"], _DEVICE_EVENTS["compile_ms"]


_FETCH_HIST: dict[int, int] = {}


def record_shard_fetches(n: int) -> None:
    """One shard query phase performed `n` device_fetch round-trips —
    bucket counts for the fetches-per-shard-query histogram on the
    `/_metrics` scrape (the stacked dense lane's whole point is n == 1)."""
    with _DEVICE_LOCK:
        _FETCH_HIST[int(n)] = _FETCH_HIST.get(int(n), 0) + 1


def shard_fetch_histogram() -> dict[int, int]:
    """{device_fetches_per_shard_query: occurrences} snapshot."""
    with _DEVICE_LOCK:
        return dict(_FETCH_HIST)


# peak per-query score-matrix residency (ISSUE 8): what one dense query
# phase materializes on device at most — O(Q × block) on the blockwise
# lane vs O(Q × n_pad) on the materializing executor. A gauge, not a
# counter: the scrape reads the process high-water mark.
_SCORE_MATRIX_PEAK = [0]


def record_score_matrix_bytes(n: int) -> None:
    """One dense execution is about to materialize `n` bytes of score +
    match state (the lane-accurate request-breaker charge)."""
    with _DEVICE_LOCK:
        if n > _SCORE_MATRIX_PEAK[0]:
            _SCORE_MATRIX_PEAK[0] = int(n)


def peak_score_matrix_bytes() -> int:
    with _DEVICE_LOCK:
        return _SCORE_MATRIX_PEAK[0]


_HOST_MERGES = [0]


def record_host_merge() -> None:
    """One host-side cross-shard merge ran (controller.sort_docs). The
    mesh-sharded query lane's whole point is replacing these with one
    on-device collective reduce — tests tripwire on the delta staying 0."""
    with _DEVICE_LOCK:
        _HOST_MERGES[0] += 1


def host_merge_count() -> int:
    with _DEVICE_LOCK:
        return _HOST_MERGES[0]


# bulk-ingest lane counters (ISSUE 7): how many `_bulk` requests rode the
# vectorized batch lane vs fell back to the per-doc path, how many docs each
# carried, and a docs-per-bulk pow2 histogram — es_indexing_* on the scrape
_BULK_INGEST = {"vectorized_bulks": 0, "fallback_bulks": 0,
                "vectorized_docs": 0, "fallback_docs": 0}
_BULK_DOCS_HIST: dict[int, int] = {}


def record_bulk_ingest(docs: int, vectorized: bool) -> None:
    """One `_bulk` request finished: `docs` ops, fully vectorized or not
    (a request with ANY per-doc-lane op counts as fallback — mixed
    requests are what the fallback ladder is for)."""
    with _DEVICE_LOCK:
        if vectorized:
            _BULK_INGEST["vectorized_bulks"] += 1
            _BULK_INGEST["vectorized_docs"] += docs
        else:
            _BULK_INGEST["fallback_bulks"] += 1
            _BULK_INGEST["fallback_docs"] += docs
        bucket = 1 << max(int(docs) - 1, 0).bit_length() if docs else 0
        _BULK_DOCS_HIST[bucket] = _BULK_DOCS_HIST.get(bucket, 0) + 1


def bulk_ingest_snapshot() -> dict:
    with _DEVICE_LOCK:
        return {"vectorized_bulks_total": _BULK_INGEST["vectorized_bulks"],
                "fallback_bulks_total": _BULK_INGEST["fallback_bulks"],
                "vectorized_docs_total": _BULK_INGEST["vectorized_docs"],
                "fallback_docs_total": _BULK_INGEST["fallback_docs"]}


def bulk_docs_histogram() -> dict[int, int]:
    """{pow2 docs-per-bulk bucket: request count} snapshot."""
    with _DEVICE_LOCK:
        return dict(_BULK_DOCS_HIST)


# the packed lane's dispatches by the form of the program's slot gather
# (ops/bm25_sparse.packed_gather_form): es_packed_gather_dispatches_total
# {form=}. A run on the chip that fell back to "sliced" shows here.
_PACKED_GATHER = {"blocked": 0, "sliced": 0}


def record_packed_dispatch(form: str, program: str) -> None:
    """One batch of the packed lane: by the form of its program's slot
    gather and by which program it was (`_PACKED_BATCHES`, below)."""
    with _DEVICE_LOCK:
        _PACKED_GATHER[form] += 1
        _PACKED_BATCHES[program] += 1


def packed_gather_snapshot() -> dict:
    with _DEVICE_LOCK:
        return {form: {"dispatches_total": n}
                for form, n in _PACKED_GATHER.items()}


# packed batches by the program that answered them (PackedIndexView.search):
# es_packed_batches_total{program=}. "filtered" carried columnar filters in
# some body (ops/bm25_sparse.bm25_serve_packed_filtered), "plain" in none.
_PACKED_BATCHES = {"plain": 0, "filtered": 0}


def packed_batches_snapshot() -> dict:
    with _DEVICE_LOCK:
        return {program: {"total": n}
                for program, n in _PACKED_BATCHES.items()}


# the rank streams a filtered packed batch hands its program, one a column
# (PackedIndexView._filter_streams): es_packed_filter_streams_total{state=}.
# "made" once a (view, text field, column), then "reused" by every batch that
# names the pair: a warm window reads 100 % reused.
_PACKED_FILTER_STREAMS = {"made": 0, "reused": 0}


def record_filter_stream(state: str) -> None:
    with _DEVICE_LOCK:
        _PACKED_FILTER_STREAMS[state] += 1


def packed_filter_streams_snapshot() -> dict:
    with _DEVICE_LOCK:
        return {state: {"total": n}
                for state, n in _PACKED_FILTER_STREAMS.items()}


# hits the packed lane rendered, by how (serving/executor.respond):
# es_packed_render_hits_total{form=}. "vector" took the raw render's numpy
# passes, "patched" its scalar `%.9g` (an exponent form, inf, nan), "dict"
# were built as Python dicts (`_source`, a mixed-type index, an unsafe id).
_PACKED_RENDER = {"vector": 0, "patched": 0, "dict": 0}


def record_packed_render(**hits_by_form: int) -> None:
    with _DEVICE_LOCK:
        for form, n in hits_by_form.items():
            _PACKED_RENDER[form] += n


def packed_render_snapshot() -> dict:
    with _DEVICE_LOCK:
        return {form: {"hits_total": n}
                for form, n in _PACKED_RENDER.items()}


def transfer_snapshot() -> dict:
    """Process-wide host↔device transfer counters (every device_fetch /
    note_h2d call accounts here, profiler active or not) — the scrape's
    `es_transfer_*` series."""
    with _DEVICE_LOCK:
        return {"bytes_to_device_total": _DEVICE_EVENTS["h2d_bytes"],
                "bytes_from_device_total": _DEVICE_EVENTS["d2h_bytes"],
                "device_fetches_total": _DEVICE_EVENTS["fetches"]}


def note_h2d(nbytes: int) -> None:
    """Account host→device bytes: always process-wide, and into the active
    RequestProfiler when one is installed. Hot paths call this at their
    upload points so the scrape sees every transfer, not just profiled
    requests."""
    from . import tracing
    n = int(nbytes)
    with _DEVICE_LOCK:
        _DEVICE_EVENTS["h2d_bytes"] += n
    prof = _PROFILER.get()
    if prof is not None:
        prof.note_h2d(n)
    tracing.note_h2d(n)


def _nbytes(x) -> int:
    if isinstance(x, dict):
        return sum(_nbytes(v) for v in x.values())
    if isinstance(x, (list, tuple)):
        return sum(_nbytes(v) for v in x)
    return int(getattr(x, "nbytes", 0))


def device_fetch(x):
    """jax.device_get with accounting: the fetch is one `device_fetch` span
    (common/tracing.py) carrying its bytes, counts process-wide as one
    device round-trip and its payload as device→host bytes, and in the
    active RequestProfiler and request trace when there is one. The hot
    paths call this INSTEAD of jax.device_get, so `"profile": true` sees
    every transfer without touching the kernels."""
    import jax
    from . import tracing
    fetch = tracing.span("device_fetch")
    with fetch:
        out = jax.device_get(x)
        nb = fetch.attrs["bytes"] = _nbytes(out)
    with _DEVICE_LOCK:
        _DEVICE_EVENTS["d2h_bytes"] += nb
        _DEVICE_EVENTS["fetches"] += 1
    prof = _PROFILER.get()
    if prof is not None:
        prof.note_dispatch()
        prof.note_d2h(nb)
    tracing.note_fetch(nb)
    return out


_PROFILER: contextvars.ContextVar["RequestProfiler | None"] = \
    contextvars.ContextVar("es_request_profiler", default=None)


def current_profiler() -> "RequestProfiler | None":
    return _PROFILER.get()


@contextlib.contextmanager
def use_profiler(prof: "RequestProfiler"):
    tok = _PROFILER.set(prof)
    try:
        yield prof
    finally:
        _PROFILER.reset(tok)


class RequestProfiler:
    """Per-request timing tree: coordinator phases, per-shard query
    execution with per-DSL-node score/match wall time (non-jit-visible
    timers around the jitted calls — query_dsl.Node instruments itself
    against the active profiler), plus the device section (jit cache
    hit/miss, compile time when a retrace fired, host↔device bytes)."""

    def __init__(self, trace_id: str | None = None):
        _install_compile_listener()
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.phases: dict[str, float] = {}
        self.shards: list[dict] = []
        # per-THREAD shard stack: shard phases fan out concurrently onto
        # the search pool, and each worker must attribute node timings to
        # its own shard entry, not whichever shard another thread opened
        self._local = threading.local()
        self._lock = threading.Lock()
        self.dispatches = 0
        self.d2h_bytes = 0
        self.h2d_bytes = 0
        self.paths: dict[str, int] = {}   # device path -> shard query count
        # per-request program activity (common/device_stats.py wrapper):
        # site name -> {invocations, device time} for THIS request only
        self.programs: dict[str, dict] = {}
        self._jit0 = device_events_snapshot()

    @property
    def _shard_stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    # -- coordinator phases ------------------------------------------------

    def record_phase(self, name: str, ms: float) -> None:
        with self._lock:
            self.phases[name] = self.phases.get(name, 0.0) + ms

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record_phase(name, (time.perf_counter() - t0) * 1000)

    # -- per-shard tree ----------------------------------------------------

    @contextlib.contextmanager
    def shard(self, index: str, shard_id: int):
        entry = {"index": index, "shard_id": shard_id,
                 "time_in_millis": 0.0, "query": {}}
        with self._lock:
            self.shards.append(entry)
            self._shard_stack.append(entry)
        t0 = time.perf_counter()
        try:
            yield entry
        finally:
            entry["time_in_millis"] = round(
                (time.perf_counter() - t0) * 1000, 3)
            with self._lock:
                self._shard_stack.pop()

    def record_node(self, node_type: str, op: str, ms: float) -> None:
        """One DSL-node execution (op: score|match) — aggregated per node
        type inside the current shard, or under a synthetic 'coordinator'
        shard when node execution happens outside a shard scope."""
        with self._lock:
            if self._shard_stack:
                tree = self._shard_stack[-1]["query"]
            else:
                if not self.shards or self.shards[-1].get("index") != "_coordinator":
                    self.shards.append({"index": "_coordinator",
                                        "shard_id": -1,
                                        "time_in_millis": 0.0, "query": {}})
                tree = self.shards[-1]["query"]
            b = tree.setdefault(node_type, {
                "score_count": 0, "score_time_in_millis": 0.0,
                "match_count": 0, "match_time_in_millis": 0.0})
            b[f"{op}_count"] += 1
            b[f"{op}_time_in_millis"] = round(
                b[f"{op}_time_in_millis"] + ms, 3)

    # -- device counters ---------------------------------------------------

    def note_dispatch(self, n: int = 1) -> None:
        with self._lock:
            self.dispatches += n

    def note_d2h(self, nbytes: int) -> None:
        with self._lock:
            self.d2h_bytes += int(nbytes)

    def note_h2d(self, nbytes: int) -> None:
        with self._lock:
            self.h2d_bytes += int(nbytes)

    def note_path(self, path: str) -> None:
        """One shard query phase served by `path` (sparse / stacked /
        dense / packed) — the _path_stats view scoped to THIS request."""
        with self._lock:
            self.paths[path] = self.paths.get(path, 0) + 1

    def note_program(self, name: str, ms: float) -> None:
        """One instrumented-program dispatch attributed to this request
        (device_stats.InstrumentedProgram calls in)."""
        with self._lock:
            b = self.programs.setdefault(
                name, {"invocations": 0, "device_time_in_millis": 0.0})
            b["invocations"] += 1
            b["device_time_in_millis"] = round(
                b["device_time_in_millis"] + ms, 3)

    def device_section(self) -> dict:
        compiles, compile_ms = device_events_snapshot()
        misses = compiles - self._jit0[0]
        return {"jit_cache_misses": misses,
                "jit_cache_hits": max(self.dispatches - misses, 0),
                "compile_time_in_millis": round(
                    compile_ms - self._jit0[1], 3),
                "bytes_device_to_host": self.d2h_bytes,
                "bytes_host_to_device": self.h2d_bytes,
                "query_paths": dict(self.paths),
                "programs": {k: dict(v)
                             for k, v in self.programs.items()}}

    def render(self, opaque_id: str | None = None) -> dict:
        out = {"trace_id": self.trace_id,
               "phases": {k: round(v, 3) for k, v in self.phases.items()},
               "shards": [{"id": f"[{s['index']}][{s['shard_id']}]", **s}
                          for s in self.shards],
               "device": self.device_section()}
        if opaque_id is not None:
            out["x_opaque_id"] = opaque_id
        return out


def _threshold_ms(settings, level: str,
                  kind: str = "search.slowlog.threshold.query") -> float | None:
    """index.<kind>.<level> -> ms (live: read per request, so a settings
    update applies immediately)."""
    for key in (f"index.{kind}.{level}", f"{kind}.{level}"):
        v = settings.get(key)
        if v is not None:
            from ..mapping.mapper import parse_ttl_ms
            try:
                return float(parse_ttl_ms(v))
            except Exception:  # noqa: BLE001
                return None
    return None


class SlowLog:
    """Query slowlog: threshold-gated log lines + a bounded in-memory tail
    (the reference writes log files; the tail makes it assertable and
    REST-visible). Subclasses set KIND (the settings-key prefix) and
    PAYLOAD_FIELD (what the log line carries)."""

    KIND = "search.slowlog.threshold.query"
    PAYLOAD_FIELD = "source"
    LOGGER_NAME = "elasticsearch_tpu.index.search.slowlog.query"

    def __init__(self, maxlen: int = 128):
        self.logger = logging.getLogger(self.LOGGER_NAME)
        self.tail: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def snapshot(self) -> list:
        """Race-free copy for REST rendering (the HTTP server is threaded
        and searches append concurrently)."""
        with self._lock:
            return list(self.tail)

    def maybe_log(self, settings, index: str, took_ms: float,
                  body, trace_id: str | None = None,
                  opaque_id: str | None = None) -> str | None:
        """Returns the level logged at, or None. trace_id/opaque_id stamp
        the tail entry so a slow request correlates with its task listing
        and profile output (the X-Opaque-Id contract)."""
        for level, log_fn in (("warn", self.logger.warning),
                              ("info", self.logger.info),
                              ("debug", self.logger.debug),
                              ("trace", self.logger.debug)):
            thr = _threshold_ms(settings, level, kind=self.KIND)
            if thr is not None and took_ms >= thr:
                import json
                payload = json.dumps(body)[:512] \
                    if isinstance(body, (dict, list)) else str(body)[:128]
                entry = {"level": level, "index": index,
                         "took_millis": round(took_ms, 2),
                         self.PAYLOAD_FIELD: payload}
                if trace_id is not None:
                    entry["trace_id"] = trace_id
                if opaque_id is not None:
                    entry["x_opaque_id"] = opaque_id
                with self._lock:
                    self.tail.append(entry)
                log_fn("[%s] took[%sms], %s[%s]", index,
                       entry["took_millis"], self.PAYLOAD_FIELD, payload)
                return level
        return None


class IndexingSlowLog(SlowLog):
    """Indexing slowlog (ref index/indexing/slowlog/
    ShardSlowLogIndexingService.java — index.indexing.slowlog.threshold.
    index.<level> thresholds applied per write)."""

    KIND = "indexing.slowlog.threshold.index"
    PAYLOAD_FIELD = "id"
    LOGGER_NAME = "elasticsearch_tpu.index.indexing.slowlog.index"


# ---------------------------------------------------------------------------
# OpenMetrics exposition (`GET /_metrics`): every stats registry renders as
# one scrapeable text document. The walk is generic over *sections* — a
# section is either a flat dict of leaves or a {entry: leaves} registry
# labeled by pool/breaker/timer/index/... — so a NEW registry joins the
# scrape by adding one entry to NodeService.metric_sections(), and the
# strict-parser test fails if a stats source forgets to.
# ---------------------------------------------------------------------------

# leaf keys that are MONOTONE counters in the existing stats dicts (the
# scrape renames them to the OpenMetrics `_total` convention); any curated
# leaf already ending in `_total` is a counter by construction
_COUNTER_LEAVES = frozenset({
    "count", "completed", "rejected", "tripped", "time_in_millis",
    "batches", "batched_requests", "compiles", "total_started",
    "index_total", "delete_total", "query_total", "collection_count",
    "collected",
})

_NAME_SANITIZE = _re.compile(r"[^a-zA-Z0-9_]")


def _metric_leaf(key: str) -> tuple[str, str]:
    """(leaf name, type): byte/milli renames + counter `_total` suffixing."""
    leaf = key
    if leaf.endswith("_in_bytes"):
        leaf = leaf[: -len("_in_bytes")] + "_bytes"
    if leaf.endswith("_in_millis"):
        leaf = leaf[: -len("_in_millis")] + "_millis"
    if leaf == "total_started":
        leaf = "started"          # -> *_started_total, not *_total_started_*
    if key == "total":
        # a leaf literally named "total" is a counter whose family name
        # already carries the suffix (es_search_hedged_total{outcome=})
        return "total", "counter"
    if key in _COUNTER_LEAVES or key.endswith("_total") \
            or key.endswith("time_in_millis"):
        if not leaf.endswith("_total"):
            leaf += "_total"
        return leaf, "counter"
    return leaf, "gauge"


class _Family:
    __slots__ = ("name", "mtype", "help", "samples")

    def __init__(self, name: str, mtype: str, help_text: str):
        self.name = name
        self.mtype = mtype
        self.help = help_text
        self.samples: list[tuple[dict, float]] = []


def _flatten(prefix: str, payload: dict, out: list) -> None:
    for k, v in payload.items():
        key = f"{prefix}_{k}" if prefix else str(k)
        if isinstance(v, dict):
            _flatten(key, v, out)
        elif isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        elif v == float("inf") or v != v:
            continue
        else:
            out.append((key, v))


def openmetrics_families(sections: dict, node: str,
                         families: dict | None = None) -> dict:
    """sections: {section: (label_name | None, payload)}. Labeled payloads
    are registries ({entry: {leaf: num}}); unlabeled ones flatten directly.
    Merging several nodes into one `families` dict is the cluster fan-out
    (`/_cluster/_metrics`) — same family, one sample per node."""
    fams = families if families is not None else {}

    def emit(section, labels, key, value):
        leaf, mtype = _metric_leaf(key)
        name = _NAME_SANITIZE.sub("_", f"es_{section}_{leaf}")
        fam = fams.get(name)
        if fam is None:
            fam = fams[name] = _Family(
                name, mtype, f"{section} {key} ({mtype})")
        elif fam.mtype != mtype:
            raise ValueError(
                f"metric family [{name}] registered as {fam.mtype} "
                f"and {mtype}")
        fam.samples.append((labels, float(value)))

    for section, (label_name, payload) in sections.items():
        if not isinstance(payload, dict):
            continue
        if label_name is None:
            leaves: list = []
            _flatten("", payload, leaves)
            for key, v in leaves:
                emit(section, {"node": node}, key, v)
        else:
            for entry, sub in payload.items():
                if not isinstance(sub, dict):
                    continue
                leaves = []
                _flatten("", sub, leaves)
                if isinstance(label_name, tuple):
                    # multi-label registry: entry keys are value tuples
                    # aligned with the label-name tuple
                    # (es_search_lane_decisions_total{lane=,reason=})
                    labels = {"node": node,
                              **{ln: str(lv) for ln, lv in
                                 zip(label_name, entry)}}
                else:
                    labels = {"node": node, label_name: str(entry)}
                for key, v in leaves:
                    emit(section, labels, key, v)
    return fams


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_value(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def render_families(families: dict, comments: list[str] | None = None) -> str:
    out: list[str] = []
    for name in sorted(families):
        fam = families[name]
        out.append(f"# HELP {name} {fam.help}\n")
        out.append(f"# TYPE {name} {fam.mtype}\n")
        for labels, value in fam.samples:
            lbl = ",".join(f'{k}="{_escape_label(str(v))}"'
                           for k, v in sorted(labels.items()))
            out.append(f"{name}{{{lbl}}} {_fmt_value(value)}\n")
    for c in comments or ():
        out.append(f"# {c}\n")
    out.append("# EOF\n")
    return "".join(out)


def render_openmetrics(sections: dict, node: str = "tpu-node-0") -> str:
    """One node's full exposition: `GET /_metrics`."""
    return render_families(openmetrics_families(sections, node))
