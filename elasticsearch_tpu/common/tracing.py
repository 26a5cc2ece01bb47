"""Span-based request tracing: a Dapper-style per-request span tree.

PR 4 made shard execution concurrent (`_ShardJob` fan-out) and collapsed
segment loops into single stacked dispatches, so a query's wall clock is
the max over parallel subtrees — flat per-phase timers can no longer say
where a SPECIFIC slow request's time went (queue-wait vs run, cache miss
vs stack build, jit compile vs device fetch). This module is the answer
modern serving stacks converged on (Dapper, as adopted by the OTel
ecosystem): one trace tree per request, sampled, retained in-process in a
bounded ring, exportable to standard viewers.

  * `Tracer.request(...)` roots a trace at the trace id the task layer
    already generates/echoes (common/tasks.py); `span(name, **attrs)` is
    the in-request instrumentation primitive — a context manager that is
    a near-free no-op when no trace is active, so the hot path pays one
    contextvar read when tracing is off or the request wasn't opened.
  * Propagation is contextvars-native: the coordinator's `_ShardJob`
    fan-out copies the request context onto the search pool, so shard
    subtrees parent correctly with no plumbing; `wire_header()` /
    `Tracer.remote(...)` carry (trace id, parent span id) across the
    cluster transport as the `_trace` header next to `_task`.
  * Completed traces land in a ring (`node.tracing.retention`, default
    256 traces); retention is decided at COMPLETION: `?trace=true`
    forces, a slowlog hit forces (the request proved itself interesting),
    otherwise `node.tracing.sample_rate` draws. `node.tracing.enabled:
    false` removes every span allocation.
  * Export: the stored trace renders as a nested tree
    (`GET /_traces/{id}`), Chrome trace-event JSON (`?format=chrome`,
    loadable in chrome://tracing or Perfetto) and OTLP-shaped span JSON
    (`?format=otlp`).

Spans carry monotonic-ns timestamps (duration-exact); a wall-clock anchor
captured at trace start converts to unix nanos for OTLP export.

`span()` and `add_span()` are the one timing primitive of the serving
path. The same two clock reads of a span feed three sinks:

  1. `AGGREGATE`, always on: count / seconds / self seconds / max by span
     name, `es_span_*{span=}` on `/_metrics`. Self time is the duration
     minus what child `span()` blocks on the SAME thread cover. One in
     `CPU_SAMPLE` of the spans opened with `cpu=True` (the host-compute
     spans) also reads the thread's own CPU time (`time.thread_time_ns`)
     beside its two clock reads, and books it with its own wall time:
     wall minus CPU is time the thread was ready but off the CPU (the
     interpreter lock, a core). Only so many do: on the chip's host that
     clock is a system call of about 6 µs that ticks in 10 ms (PERF.md §6,
     PR 35), fine summed over a window's spans and too dear for every one.
  2. a `jax.profiler.TraceAnnotation("es:<name>")` held open for the
     block, so a running profiler session (xprof, the benchmark's traced
     slice) shows the span on the host plane beside `XLA Ops`, on the
     profiler's clock. `add_span` (past timestamps) cannot be one.
  3. the request's span tree above, when a request trace is active.

`flight(site)` is the `program` span around one blocking device dispatch
(common/device_stats.InstrumentedProgram) and also feeds `GAPS`, the
device-gap ledger: whenever no program is in flight the device is idle as
the host sees it, and the gap is charged to the spans of the thread that
ends it (`es_device_gap_seconds_total{during=}`); the longest gaps are kept
as records (`GET /_nodes/device_gaps`). A flight that took off behind
others books `program.queue` up to the landing of the last of them: the
device's one queue as the host sees it. The profiler event of a flight
carries `t0_ns`, its start on this module's clock, so any `es:program`
event of a capture gives `offset = event.start_ns - t0_ns`, which maps
every timestamp here (a span's, a gap record's) onto that capture.
"""

from __future__ import annotations

import contextlib
import itertools
import random
import threading
import time
from collections import deque
from contextvars import ContextVar

from jax.profiler import TraceAnnotation

# (trace, current span) of the running request; copied into shard jobs by
# the fan-out's contextvars.copy_context() and into transport handlers by
# Tracer.remote()
_ACTIVE: ContextVar["tuple[Trace, Span] | None"] = \
    ContextVar("es_active_trace", default=None)


# every timestamp of this module is one read of this clock (a test seam)
_clock = time.monotonic_ns
# and every CPU time one read of this one, of the calling thread
_cpu_clock = time.thread_time_ns
CPU_SAMPLE = 4
_cpu_turn = itertools.count()


def now_ns() -> int:
    return _clock()


def current_trace() -> "Trace | None":
    active = _ACTIVE.get()
    return active[0] if active is not None else None


class Span:
    __slots__ = ("span_id", "parent_id", "name", "start_ns", "end_ns",
                 "attrs", "thread")

    def __init__(self, span_id: int, parent_id: int | None, name: str,
                 start_ns: int, attrs: dict):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ns = start_ns
        self.end_ns = start_ns
        self.attrs = attrs
        self.thread = threading.get_ident()


class Trace:
    """One in-flight request's span set (flat, parent-linked; the tree is
    built at render time). Span appends cross threads (the shard fan-out),
    so they serialize on a lock; device counters accumulate here so the
    stored trace carries its own device section."""

    __slots__ = ("trace_id", "root", "spans", "max_spans", "dropped_spans",
                 "forced", "slowlogged", "remote_parent", "opaque_id",
                 "fetches", "d2h_bytes", "h2d_bytes", "_jit0",
                 "_wall_anchor_ns", "_mono_anchor_ns", "_seq", "_lock")

    def __init__(self, trace_id: str, max_spans: int = 512):
        self.trace_id = trace_id
        self.root: Span | None = None
        self.spans: list[Span] = []
        self.max_spans = max_spans
        self.dropped_spans = 0
        self.forced = False
        self.slowlogged = False
        self.remote_parent: int | None = None
        self.opaque_id: str | None = None
        self.fetches = 0
        self.d2h_bytes = 0
        self.h2d_bytes = 0
        from .metrics import device_events_snapshot
        self._jit0 = device_events_snapshot()
        self._wall_anchor_ns = time.time_ns()
        self._mono_anchor_ns = _clock()
        self._seq = 0
        self._lock = threading.Lock()

    def new_span(self, name: str, parent_id: int | None, start_ns: int,
                 attrs: dict) -> Span | None:
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.dropped_spans += 1
                return None
            self._seq += 1
            span = Span(self._seq, parent_id, name, start_ns, attrs)
            self.spans.append(span)
            return span

    def note_fetch(self, nbytes: int) -> None:
        with self._lock:
            self.fetches += 1
            self.d2h_bytes += int(nbytes)

    def note_h2d(self, nbytes: int) -> None:
        with self._lock:
            self.h2d_bytes += int(nbytes)

    def device_section(self) -> dict:
        from .metrics import device_events_snapshot
        compiles, compile_ms = device_events_snapshot()
        return {"device_fetches": self.fetches,
                "bytes_device_to_host": self.d2h_bytes,
                "bytes_host_to_device": self.h2d_bytes,
                "jit_compiles": compiles - self._jit0[0],
                "jit_compile_time_in_millis": round(
                    compile_ms - self._jit0[1], 3)}

    def render(self) -> dict:
        """The stored (ring) form: plain JSON-safe dict, offsets in µs
        from the root start so every export derives from one snapshot."""
        root = self.root
        t0 = root.start_ns if root is not None else self._mono_anchor_ns
        spans = []
        with self._lock:
            snap = list(self.spans)
        for s in snap:
            entry = {"id": s.span_id, "parent_id": s.parent_id,
                     "name": s.name,
                     "start_us": round((s.start_ns - t0) / 1e3, 3),
                     "duration_us": round(
                         max(s.end_ns - s.start_ns, 0) / 1e3, 3),
                     "thread": s.thread}
            if s.attrs:
                entry["attributes"] = dict(s.attrs)
            spans.append(entry)
        out = {"trace_id": self.trace_id,
               "root": root.name if root is not None else "",
               "start_time_in_millis": self._wall_anchor_ns // 1_000_000,
               "start_time_unix_nanos": self._wall_anchor_ns
               + (t0 - self._mono_anchor_ns),
               "duration_in_millis": round(
                   max(root.end_ns - root.start_ns, 0) / 1e6, 3)
               if root is not None else 0.0,
               "span_count": len(spans),
               "dropped_spans": self.dropped_spans,
               "slowlog": self.slowlogged,
               "forced": self.forced,
               "device": self.device_section(),
               "spans": spans}
        if self.remote_parent is not None:
            out["remote_parent_span"] = self.remote_parent
        if self.opaque_id is not None:
            out["x_opaque_id"] = self.opaque_id
        return out


# ---------------------------------------------------------------------------
# sink 1: the always-on aggregate by span name
# ---------------------------------------------------------------------------

class SpanAggregate:
    """count / total / self / max nanoseconds by span name, and for names
    whose spans read the CPU clock the CPU and wall nanoseconds of those
    that did. Names are the static strings of the call sites, so the table
    is bounded by the code."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: dict[str, list] = {}

    def add(self, name: str, dur_ns: int, self_ns: int,
            cpu: tuple[int, int] | None = None) -> None:
        """`cpu`: (CPU ns, wall ns) of a span that read the CPU clock."""
        with self._lock:
            row = self._rows.get(name)
            if row is None:
                row = self._rows[name] = [0, 0, 0, 0, None, 0]
            row[0] += 1
            row[1] += dur_ns
            row[2] += self_ns
            if dur_ns > row[3]:
                row[3] = dur_ns
            if cpu is not None:
                row[4] = (row[4] or 0) + cpu[0]
                row[5] += cpu[1]

    def stats(self) -> dict[str, dict]:
        """The `es_span_*{span=}` payload of the `/_metrics` walk."""
        with self._lock:
            rows = {n: tuple(r) for n, r in self._rows.items()}
        out = {}
        for n, r in sorted(rows.items()):
            out[n] = {"total": r[0], "seconds_total": r[1] / 1e9,
                      "self_seconds_total": r[2] / 1e9,
                      "max_seconds": r[3] / 1e9}
            if r[4] is not None:
                out[n]["cpu_seconds_total"] = r[4] / 1e9
                out[n]["cpu_wall_seconds_total"] = r[5] / 1e9
        return out


AGGREGATE = SpanAggregate()


# ---------------------------------------------------------------------------
# the device-gap ledger: what the host did while no program was in flight
# ---------------------------------------------------------------------------

class _ThreadState:
    """One thread's open `span()` blocks, and the ones it closed since its
    request reached it or it last dispatched a program (whichever is later:
    nothing older can lie in a gap this thread ends)."""

    __slots__ = ("stack", "trail", "request_start_ns", "program_attrs")

    def __init__(self):
        self.stack: list[_SpanCtx] = []
        self.program_attrs: dict = {}   # see program_attrs()
        # (name, start_ns, end_ns); bounded: a thread that never dispatches
        # only ever loses attribution it would not have been asked for
        self.trail: deque = deque(maxlen=64)
        self.request_start_ns: int | None = None


_LOCAL = threading.local()


def _thread_state() -> _ThreadState:
    st = getattr(_LOCAL, "state", None)
    if st is None:
        st = _LOCAL.state = _ThreadState()
    return st


def charge_gap(g0: int, g1: int, state: _ThreadState) -> dict[str, int]:
    """Split the gap [g0, g1) by what `state`'s thread did in it; the
    charges sum to g1 - g0. Each instant goes to the innermost span of the
    thread that covers it (spans of one thread nest or are disjoint). What
    no span covers is `no_request` before the thread's request reached it
    (nothing was waiting for the device) and `unattributed` after."""
    ivs = [(max(s, g0), min(e, g1), n) for n, s, e in state.trail]
    ivs += [(max(c._entered_ns, g0), g1, c.name) for c in state.stack]
    ivs = sorted((iv for iv in ivs if iv[1] > iv[0]),
                 key=lambda iv: (iv[0], -iv[1]))
    own = [e - s for s, e, _ in ivs]
    covered = 0
    nest: list[int] = []
    for i, (s, e, _) in enumerate(ivs):
        while nest and ivs[nest[-1]][1] <= s:
            nest.pop()
        if nest:
            own[nest[-1]] -= e - s
        else:
            covered += e - s
        nest.append(i)
    out: dict[str, int] = {}
    for (_, _, name), ns in zip(ivs, own):
        if ns > 0:
            out[name] = out.get(name, 0) + ns
    bare = (g1 - g0) - covered
    r0 = state.request_start_ns
    before = bare if r0 is None else min(bare, max(r0 - g0, 0))
    if before > 0:
        out["no_request"] = before
    if bare > before:
        out["unattributed"] = bare - before
    return out


class _Flight:
    """One program in flight: when it took off, how many flights were in
    flight then and have not landed, and when the last of them landed."""

    __slots__ = ("takeoff_ns", "ahead", "queued", "queue_end_ns")

    def __init__(self, takeoff_ns: int, ahead: int):
        self.takeoff_ns = takeoff_ns
        self.ahead = ahead
        self.queued = ahead > 0
        self.queue_end_ns: int | None = None


class GapLedger:
    """Process-wide record of the programs in flight (dispatch to ready, as
    the blocked host thread sees it). While none is the device is idle in
    the host's view; the dispatch that ends a gap charges it to its
    thread's spans. A host view: a flight includes dispatch latency and the
    wake-up of the blocked thread, so the gap total reads at or below the
    device trace's idle time.

    The host's view of the device's one queue: a flight that takes off
    while others are in flight waits for them, until the last of them
    lands, and books that wait as `program.queue` when it lands itself
    (`program` less `program.queue` is then its own time). The longest
    gaps are kept with their charges: `RECORDS_A_SECOND` a second of this
    module's clock (by their start), for the last `RECORD_SECONDS`."""

    RECORDS_A_SECOND = 4
    RECORD_SECONDS = 600
    RECORD_CHARGES = 4          # the largest charges of a record; the rest
    # are summed under "other", so a record's charges sum to its length

    def __init__(self):
        self._lock = threading.Lock()
        self._flying: list[_Flight] = []     # in take-off order
        self._flight_start_ns = 0
        self._idle_since_ns: int | None = None   # None before any landing
        self._flight_ns = 0
        self._gap_ns: dict[str, int] = {}
        # [second, [(length, start, end, charges), ...]], oldest first
        self._records: deque = deque()

    def takeoff(self, now: int, state: _ThreadState) -> _Flight:
        gap0 = None
        with self._lock:
            flight = _Flight(now, len(self._flying))
            self._flying.append(flight)
            if flight.ahead == 0:
                self._flight_start_ns = now
                gap0 = self._idle_since_ns
        if gap0 is not None and now > gap0:
            charges = charge_gap(gap0, now, state)
            with self._lock:
                for name, ns in charges.items():
                    self._gap_ns[name] = self._gap_ns.get(name, 0) + ns
                self._keep(gap0, now, charges)
        # a later gap starts at a landing, so after now: the closed spans
        # of this thread can lie in none
        state.trail.clear()
        return flight

    def land(self, now: int, flight: _Flight) -> None:
        with self._lock:
            i = self._flying.index(flight)
            # every flight behind this one took off while it was in flight
            for behind in self._flying[i + 1:]:
                behind.ahead -= 1
                if behind.ahead == 0:
                    behind.queue_end_ns = now
            del self._flying[i]
            if not self._flying:
                # threads race from their clock read to this lock: never
                # let a landing be booked before its flight's start
                now = max(now, self._flight_start_ns)
                self._idle_since_ns = now
                self._flight_ns += now - self._flight_start_ns
        if flight.queued:
            end = now if flight.queue_end_ns is None \
                else min(flight.queue_end_ns, now)
            add_span("program.queue", flight.takeoff_ns, end)

    def _keep(self, g0: int, g1: int, charges: dict[str, int]) -> None:
        """Under the lock: the gap [g0, g1) among its second's longest.
        Gaps end in time order (the flight that ends one is in flight
        until the next can start), so a new second is the newest."""
        second = g0 // 1_000_000_000
        if not self._records or self._records[-1][0] != second:
            self._records.append([second, []])
            while self._records[0][0] <= second - self.RECORD_SECONDS:
                self._records.popleft()
        kept = self._records[-1][1]
        if len(kept) == self.RECORDS_A_SECOND:
            shortest = min(kept)            # by length, then start
            if shortest[0] >= g1 - g0:
                return
            kept.remove(shortest)
        top = sorted(charges.items(), key=lambda kv: -kv[1])
        during = dict(top[:self.RECORD_CHARGES])
        rest = sum(ns for _, ns in top[self.RECORD_CHARGES:])
        if rest:
            during["other"] = rest
        kept.append((g1 - g0, g0, g1, during))

    def gap_records(self) -> list[dict]:
        """The `GET /_nodes/device_gaps` payload: the kept gaps on this
        module's clock (`monotonic_ns`), newest last."""
        with self._lock:
            kept = [r for _, recs in self._records for r in recs]
        return [{"start_ns": g0, "end_ns": g1, "during": dict(during)}
                for _, g0, g1, during in sorted(kept, key=lambda r: r[1])]

    def gap_stats(self) -> dict[str, dict]:
        """The `es_device_gap_seconds_total{during=}` payload."""
        with self._lock:
            return {n: {"seconds_total": ns / 1e9}
                    for n, ns in sorted(self._gap_ns.items())}

    def flight_stats(self) -> dict:
        """The `es_device_flight_seconds_total` payload: the union of the
        in-flight intervals."""
        with self._lock:
            return {"seconds_total": self._flight_ns / 1e9}


GAPS = GapLedger()


def begin_request(submit_ns: int) -> None:
    """First line on the pool thread that serves a request submitted at
    `submit_ns`: records `pool.queue_wait`, and restarts this thread's
    trail at the request (the wait is the one `add_span` a gap may be
    charged to: the request WAS waiting for the device then)."""
    now = _clock()
    st = _thread_state()
    st.trail.clear()
    st.request_start_ns = submit_ns
    st.trail.append(("pool.queue_wait", submit_ns, now))
    add_span("pool.queue_wait", submit_ns, now)


# ---------------------------------------------------------------------------
# the instrumentation primitives (module-level: call sites never need a
# Tracer reference)
# ---------------------------------------------------------------------------

class _SpanCtx:
    """`with span("name", k=v) as sp:` — class-based (not
    contextlib.contextmanager) to keep the path allocation-light on seams
    that run on every request. `sp` is the request tree's Span, or None when
    no request trace is active; `attrs`, `start_ns` and `end_ns` of the
    context itself are there either way, `end_ns` once the block ended."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "_entered_ns",
                 "_cpu", "_cpu_ns", "_state", "_span", "_tok", "_ann",
                 "_child_ns")

    def __init__(self, name: str, start_ns: int | None, attrs: dict,
                 cpu: bool = False):
        self.name = name
        self.attrs = attrs
        self._cpu = cpu and next(_cpu_turn) % CPU_SAMPLE == 0
        self.start_ns = start_ns
        self.end_ns = None
        self._span = None
        self._tok = None
        self._ann = None
        self._child_ns = 0

    @property
    def duration_ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def __enter__(self) -> Span | None:
        self._ann = TraceAnnotation("es:" + self.name, **self.attrs)
        self._ann.__enter__()
        return self._open(_clock())

    def _open(self, now: int) -> Span | None:
        # a backdated span is on this thread only from now on: self time
        # and the gap ledger count it from here, so that the spans of one
        # thread always nest
        self._entered_ns = now
        if self._cpu:
            self._cpu_ns = _cpu_clock()
        if self.start_ns is None:
            self.start_ns = self._entered_ns
        self._state = _thread_state()
        self._state.stack.append(self)
        active = _ACTIVE.get()
        if active is None:
            return None
        trace, parent = active
        span = trace.new_span(
            self.name, parent.span_id if parent is not None else None,
            self.start_ns, self.attrs)
        if span is None:            # per-trace span cap: dropped, counted
            return None
        self._span = span
        self._tok = _ACTIVE.set((trace, span))
        return span

    def __exit__(self, *exc) -> bool:
        end = self.end_ns = _clock()
        cpu = _cpu_clock() - self._cpu_ns if self._cpu else None
        self._ann.__exit__(None, None, None)
        dur = end - self.start_ns
        here = end - self._entered_ns
        if cpu is not None:
            cpu = (cpu, here)
        st = self._state
        st.stack.pop()
        if st.stack:
            st.stack[-1]._child_ns += here
        st.trail.append((self.name, self._entered_ns, end))
        AGGREGATE.add(self.name, dur, max(dur - self._child_ns, 0), cpu)
        if self._span is not None:
            self._span.end_ns = end
            _ACTIVE.reset(self._tok)
        return False


def span(name: str, start_ns: int | None = None, *, cpu: bool = False,
         **attrs) -> _SpanCtx:
    """Time the block as a span: aggregate, profiler annotation and, when a
    request trace is active, a child of the current span. `start_ns`
    backdates the start (the shard-span-covers-queue-wait case). `cpu`:
    the block computes on the host; one such span in `CPU_SAMPLE` books
    the thread's CPU time in it beside its wall time
    (`es_span_cpu_seconds_total`, `es_span_cpu_wall_seconds_total`). A stats
    registry that reports the same interval (PhaseTimers, MetricsRegistry,
    RequestProfiler, ProgramRecord) is fed after the block from the
    context's own `start_ns` / `end_ns`, not from a second pair of reads."""
    return _SpanCtx(name, start_ns, attrs, cpu)


class _FlightCtx(_SpanCtx):
    __slots__ = ("_flight",)

    def __enter__(self) -> Span | None:
        # the profiler's event carries the flight's start on this module's
        # clock: the anchor of the capture (the request tree leaves it out)
        t0 = _clock()
        self._ann = TraceAnnotation("es:program", t0_ns=t0, **self.attrs)
        self._ann.__enter__()
        span_ = self._open(t0)
        self._flight = GAPS.takeoff(self.start_ns, self._state)
        return span_

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        GAPS.land(self.end_ns, self._flight)
        return False


def flight(site: str) -> _SpanCtx:
    """The `program` span: one blocking device dispatch of the program at
    `site`, dispatch to ready. Also a flight of the gap ledger."""
    return _FlightCtx("program", None,
                      {"site": site, **_thread_state().program_attrs})


@contextlib.contextmanager
def program_attrs(**attrs):
    """Attributes for the `program` spans this thread opens inside the
    block: what the caller of an instrumented program knows about the
    dispatch and the wrapper cannot."""
    st = _thread_state()
    outer = st.program_attrs
    st.program_attrs = {**outer, **attrs}
    try:
        yield
    finally:
        st.program_attrs = outer


def _tree_span(name: str, start_ns: int, end_ns: int, attrs: dict) -> None:
    active = _ACTIVE.get()
    if active is None:
        return
    trace, parent = active
    sp = trace.new_span(name,
                        parent.span_id if parent is not None else None,
                        int(start_ns), attrs)
    if sp is not None:
        sp.end_ns = int(end_ns)


def add_span(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """Record a completed span with explicit timestamps (a wait that ended
    on another thread than it began on, a phase the caller's own two reads
    already bound). It counts its own duration in the aggregate and is
    subtracted from no parent: it may belong to another request."""
    dur = max(int(end_ns) - int(start_ns), 0)
    AGGREGATE.add(name, dur, dur)
    _tree_span(name, start_ns, end_ns, attrs)


def add_event(name: str, **attrs) -> None:
    """Zero-duration marker in the request tree (cache evictions, lane
    decisions): no interval, so nothing for the aggregate."""
    if _ACTIVE.get() is not None:
        t = _clock()
        _tree_span(name, t, t, attrs)


def mark_slowlog() -> None:
    """The request crossed a slowlog threshold: force trace retention so
    the slowlog entry's trace id always resolves in `GET /_traces`."""
    trace = current_trace()
    if trace is not None:
        trace.slowlogged = True


def note_fetch(nbytes: int) -> None:
    """One device fetch of `nbytes` for the active trace's device section."""
    trace = current_trace()
    if trace is not None:
        trace.note_fetch(nbytes)


def note_h2d(nbytes: int) -> None:
    trace = current_trace()
    if trace is not None:
        trace.note_h2d(nbytes)


def wire_header() -> dict | None:
    """The `_trace` transport header: (trace id, parent span id) — None
    when nothing is being traced, so untraced requests add zero bytes."""
    active = _ACTIVE.get()
    if active is None:
        return None
    trace, span_ = active
    return {"trace_id": trace.trace_id,
            "span": span_.span_id if span_ is not None else None}


# ---------------------------------------------------------------------------
# the tracer: per-node roots, sampling, the bounded ring, exports
# ---------------------------------------------------------------------------

def _as_bool(v, default: bool) -> bool:
    if v is None:
        return default
    if isinstance(v, str):
        return v.strip().lower() not in ("false", "0", "no", "off")
    return bool(v)


class Tracer:
    """Node-level trace store. Settings (all live at node boot):

      node.tracing.enabled      default true — false removes every span
      node.tracing.sample_rate  default 1.0 — retention probability for
                                traces that neither forced nor slowlogged
      node.tracing.retention    default 256 — finished-trace ring size
      node.tracing.max_spans    default 512 — per-trace span cap; beyond
                                it spans drop (counted), the trace survives
    """

    def __init__(self, settings=None, rng=None):
        get = settings.get if settings is not None else \
            (lambda k, d=None: d)
        self.enabled = _as_bool(get("node.tracing.enabled"), True)
        try:
            self.sample_rate = float(get("node.tracing.sample_rate", 1.0))
        except (TypeError, ValueError):
            self.sample_rate = 1.0
        try:
            retention = int(get("node.tracing.retention", 256))
        except (TypeError, ValueError):
            retention = 256
        try:
            self.max_spans = int(get("node.tracing.max_spans", 512))
        except (TypeError, ValueError):
            self.max_spans = 512
        self._rng = rng or random.random
        self._ring: deque = deque(maxlen=max(retention, 1))
        self._lock = threading.Lock()
        self.active = 0
        self.traces_started = 0
        self.traces_retained = 0
        self.traces_sampled_out = 0
        self.dropped_traces = 0        # ring evictions (oldest pushed out)
        self.dropped_spans = 0
        self.spans_total = 0

    # -- roots -------------------------------------------------------------

    @contextlib.contextmanager
    def request(self, name: str, trace_id: str | None = None,
                force: bool = False, opaque_id: str | None = None,
                attrs: dict | None = None):
        """Root a trace for the request (nested roots — warmers,
        percolate-inner-search — join the surrounding trace as plain
        spans instead of starting a second one)."""
        if not self.enabled:
            yield None
            return
        if _ACTIVE.get() is not None:
            with span(name, **(attrs or {})):
                yield None
            return
        import uuid
        trace = Trace(trace_id or uuid.uuid4().hex[:16],
                      max_spans=self.max_spans)
        trace.forced = bool(force)
        trace.opaque_id = opaque_id
        trace.root = trace.new_span(name, None, _clock(),
                                    dict(attrs or {}))
        with self._lock:
            self.active += 1
            self.traces_started += 1
        tok = _ACTIVE.set((trace, trace.root))
        try:
            yield trace
        finally:
            trace.root.end_ns = _clock()
            _ACTIVE.reset(tok)
            self._finalize(trace)

    @contextlib.contextmanager
    def remote(self, header: dict | None, name: str,
               attrs: dict | None = None):
        """Continue a trace that crossed the cluster transport: the local
        subtree roots at the coordinator's (trace id, span id) from the
        `_trace` wire header and lands in THIS node's ring as a partial
        trace — `GET /_traces/{id}` on the copy-holder shows its side."""
        if not self.enabled or not header or not header.get("trace_id"):
            yield None
            return
        trace = Trace(str(header["trace_id"]), max_spans=self.max_spans)
        trace.forced = True        # explicitly propagated => keep it
        rp = header.get("span")
        trace.remote_parent = int(rp) if rp is not None else None
        trace.root = trace.new_span(name, None, _clock(),
                                    dict(attrs or {}))
        with self._lock:
            self.active += 1
            self.traces_started += 1
        tok = _ACTIVE.set((trace, trace.root))
        try:
            yield trace
        finally:
            trace.root.end_ns = _clock()
            _ACTIVE.reset(tok)
            self._finalize(trace)

    def _finalize(self, trace: Trace) -> None:
        retain = trace.forced or trace.slowlogged \
            or self.sample_rate >= 1.0 or self._rng() < self.sample_rate
        with self._lock:
            self.active -= 1
            self.spans_total += len(trace.spans)
            self.dropped_spans += trace.dropped_spans
            if not retain:
                self.traces_sampled_out += 1
                return
            if len(self._ring) == self._ring.maxlen:
                self.dropped_traces += 1
            self._ring.append(trace.render())
            self.traces_retained += 1

    # -- the REST surface --------------------------------------------------

    def list(self) -> list[dict]:
        """Newest-first summaries: the `GET /_traces` body."""
        with self._lock:
            snap = list(self._ring)
        return [{"trace_id": t["trace_id"], "root": t["root"],
                 "start_time_in_millis": t["start_time_in_millis"],
                 "duration_in_millis": t["duration_in_millis"],
                 "span_count": t["span_count"],
                 "slowlog": t["slowlog"]}
                for t in reversed(snap)]

    def get(self, trace_id: str) -> dict | None:
        with self._lock:
            snap = list(self._ring)
        for t in reversed(snap):
            if t["trace_id"] == trace_id:
                return t
        return None

    def stats(self) -> dict:
        with self._lock:
            return {"traces_started_total": self.traces_started,
                    "traces_retained_total": self.traces_retained,
                    "traces_sampled_out_total": self.traces_sampled_out,
                    "dropped_traces_total": self.dropped_traces,
                    "dropped_spans_total": self.dropped_spans,
                    "spans_total": self.spans_total,
                    "active_traces": self.active,
                    "retained_traces": len(self._ring)}


# ---------------------------------------------------------------------------
# exports: nested tree, Chrome trace-event JSON, OTLP span JSON
# ---------------------------------------------------------------------------

def span_tree(trace: dict) -> dict:
    """Stored trace -> nested tree (`GET /_traces/{id}` default body)."""
    by_id: dict[int, dict] = {}
    for s in trace["spans"]:
        by_id[s["id"]] = {**s, "children": []}
    root = None
    orphans = []
    for s in trace["spans"]:
        node = by_id[s["id"]]
        pid = s.get("parent_id")
        if pid is None:
            if root is None:
                root = node
            else:
                orphans.append(node)
        elif pid in by_id:
            by_id[pid]["children"].append(node)
        else:
            orphans.append(node)
    if root is None:
        root = {"id": 0, "name": trace.get("root", ""), "children": orphans}
    else:
        root["children"] = root.get("children", []) + orphans
    out = {k: v for k, v in trace.items() if k != "spans"}
    out["tree"] = root
    return out


def chrome_trace(trace: dict) -> dict:
    """Chrome trace-event JSON (the `?format=chrome` body): complete (X)
    events with µs timestamps, one tid lane per recording thread —
    loadable in chrome://tracing and Perfetto as-is."""
    tid_of: dict[int, int] = {}
    events: list[dict] = []
    for s in trace["spans"]:
        thread = s.get("thread", 0)
        tid = tid_of.setdefault(thread, len(tid_of) + 1)
        args = {k: v for k, v in (s.get("attributes") or {}).items()}
        args["span_id"] = s["id"]
        if s.get("parent_id") is not None:
            args["parent_span_id"] = s["parent_id"]
        events.append({"name": s["name"], "cat": "es", "ph": "X",
                       "ts": s["start_us"], "dur": s["duration_us"],
                       "pid": 1, "tid": tid, "args": args})
    for thread, tid in tid_of.items():
        events.append({"name": "thread_name", "ph": "M", "pid": 1,
                       "tid": tid,
                       "args": {"name": f"thread-{tid}"}})
    return {"displayTimeUnit": "ms",
            "otherData": {"trace_id": trace["trace_id"],
                          "root": trace["root"]},
            "traceEvents": events}


def _otlp_value(v) -> dict:
    if isinstance(v, bool):
        return {"boolValue": v}
    if isinstance(v, int):
        return {"intValue": str(v)}
    if isinstance(v, float):
        return {"doubleValue": v}
    return {"stringValue": str(v)}


def otlp_trace(trace: dict) -> dict:
    """OTLP-shaped span JSON (the `?format=otlp` body): resourceSpans →
    scopeSpans → spans with hex ids and unix-nano timestamps."""
    tid32 = (trace["trace_id"].replace("-", "") + "0" * 32)[:32]
    anchor = int(trace.get("start_time_unix_nanos",
                           trace["start_time_in_millis"] * 1_000_000))
    spans = []
    for s in trace["spans"]:
        start = anchor + int(s["start_us"] * 1000)
        parent = s.get("parent_id")
        if parent is None and trace.get("remote_parent_span") is not None:
            parent = trace["remote_parent_span"]
        entry = {"traceId": tid32,
                 "spanId": "%016x" % s["id"],
                 "name": s["name"], "kind": 1,
                 "startTimeUnixNano": str(start),
                 "endTimeUnixNano": str(
                     start + int(s["duration_us"] * 1000)),
                 "attributes": [
                     {"key": k, "value": _otlp_value(v)}
                     for k, v in (s.get("attributes") or {}).items()]}
        if parent is not None:
            entry["parentSpanId"] = "%016x" % parent
        spans.append(entry)
    return {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name",
             "value": {"stringValue": "elasticsearch-tpu"}}]},
        "scopeSpans": [{"scope": {"name": "elasticsearch_tpu.tracing"},
                        "spans": spans}]}]}
