"""Device telemetry: per-program XLA accounting, HBM high-water tracking,
and the lane-decision flight recorder (ISSUE 16).

Three concerns the serving stack had no eyes on:

**Program registry** — every compiled program dispatched from host code
(the plan-signature caches in search/blockwise and parallel/mesh_exec,
plus the module-level jitted kernels in ops/) records invocation count, cumulative dispatch wall time and
compile-event attribution. Cost analysis (flops / bytes accessed) is
computed LAZILY at scrape time by re-lowering against the captured
argument avals — `Lowered.cost_analysis()` runs no backend compile and
fires no jax.monitoring compile events (verified: the no-retrace
tripwires stay exact across scrapes) — and is None-safe on backends
that report nothing. The hot path pays one `program` span and a couple
of dict updates per dispatch: no host syncs beyond the barrier, no
retraces (tests/test_no_retrace.py pins this).

**HBM accounting** — `device.memory_stats()` polled into the stats
sampler ring with a process-lifetime high-water mark per device. CPU
backends return None; the gauges degrade to zero rather than erroring,
so the same scrape works on every platform (ROADMAP item 2c's budget
math reads the TPU numbers).

**Lane-decision flight recorder** — a contextvar-carried per-request
record of every execution-ladder decision: which lane each component
chose and every (lane, reason) decline on the way down. The same note
feeds three surfaces at once: the per-request recorder (profile output),
a zero-duration span event on the active trace, and the global
`es_search_lane_decisions_total{lane=,reason=}` counter family that
subsumes the scattered ad-hoc fallback counters (old names stay exposed
as aliases).
"""

from __future__ import annotations

import contextlib
import contextvars
import threading

import jax

from . import tracing

_LOCK = threading.Lock()

# the trace state of top-level (eager) code; any jit/vmap/grad/shard_map
# trace in progress compares unequal to it
with jax.core.eval_context():
    _TOP_LEVEL_TRACE = jax.core.get_opaque_trace_state()

# registry bound: programs enter via bounded plan caches, so this cap is a
# backstop against key churn, not a working-set limit
_MAX_PROGRAMS = 512


class ProgramRecord:
    """One compiled program's lifetime accounting. `device_ms` (exported
    as `device_time_in_millis`) is HOST wall time from dispatch until
    `block_until_ready` returns on the program's OWN outputs: dispatch
    latency, the program's wait behind whatever the device still runs,
    its transfers and the wake-up of the blocked thread included. It is
    not device time; that comes from a profiler trace only. Program cache
    keys carry the owning node's device set (`_mesh_devkey`), so records
    from different pools never alias."""

    __slots__ = ("name", "key", "invocations", "device_ms", "compile_ms",
                 "compiles", "last_invoked", "_fn", "_avals", "_cost",
                 "_cost_done")

    def __init__(self, name: str, key: str, fn):
        self.name = name
        self.key = key
        self.invocations = 0
        self.device_ms = 0.0
        self.compile_ms = 0.0
        self.compiles = 0
        self.last_invoked = 0.0
        self._fn = fn
        self._avals = None          # (args, kwargs) as ShapeDtypeStructs
        self._cost = None
        self._cost_done = False

    def cost(self) -> dict | None:
        """flops / bytes-accessed via a scrape-time re-lower against the
        captured avals. Computed once, cached; None when the backend
        reports nothing or the program can't re-lower (None-safe)."""
        with _LOCK:
            if self._cost_done:
                return self._cost
            avals = self._avals
        cost = None
        if avals is not None:
            try:
                args, kwargs = avals
                ca = self._fn.lower(*args, **kwargs).cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else None
                if isinstance(ca, dict):
                    fl = ca.get("flops")
                    by = ca.get("bytes accessed")
                    cost = {
                        "flops": float(fl) if fl is not None else None,
                        "bytes_accessed": float(by)
                        if by is not None else None}
            except Exception:  # noqa: BLE001 — cost is best-effort telemetry
                cost = None
        with _LOCK:
            self._cost = cost
            self._cost_done = True
        return cost

    def as_dict(self, with_cost: bool = True) -> dict:
        out = {"name": self.name, "key": self.key,
               "invocations": self.invocations,
               "device_time_in_millis": round(self.device_ms, 3),
               "compile_time_in_millis": round(self.compile_ms, 3),
               "compiles": self.compiles}
        if with_cost:
            c = self.cost()
            out["flops"] = c["flops"] if c else None
            out["bytes_accessed"] = c["bytes_accessed"] if c else None
        return out


_REGISTRY: dict[tuple[str, str], ProgramRecord] = {}


def _aval_of(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class InstrumentedProgram:
    """Transparent wrapper around a jitted callable: each dispatch is one
    `program` span (common/tracing.flight) whose duration is the record's
    wall-ms, plus invocation counting, first-call aval capture and compile
    attribution by diffing the process-wide compile-event counters.
    Calls made INSIDE an active trace (jit-of-jit) pass straight through
    unaccounted — they are not device dispatches."""

    __slots__ = ("jit", "record")

    def __init__(self, name: str, fn, key=""):
        self.jit = fn
        k = (name, str(key))
        with _LOCK:
            rec = _REGISTRY.get(k)
            if rec is None:
                if len(_REGISTRY) >= _MAX_PROGRAMS:
                    # evict the least-recently-invoked record (backstop)
                    oldest = min(_REGISTRY,
                                 key=lambda kk: _REGISTRY[kk].last_invoked)
                    del _REGISTRY[oldest]
                rec = _REGISTRY[k] = ProgramRecord(name, str(key), fn)
        self.record = rec

    def __call__(self, *args, **kwargs):
        if jax.core.get_opaque_trace_state() != _TOP_LEVEL_TRACE:
            return self.jit(*args, **kwargs)
        from .metrics import current_profiler, device_events_snapshot
        c0, cms0 = device_events_snapshot()
        # charge THIS program for its own work: without the barrier an
        # async backend bills the next caller's wall clock for whatever
        # this dispatch left enqueued. The `program` span's two clock reads
        # are the record's, the request profiler's and the gap ledger's.
        flight = tracing.flight(self.record.name)
        with flight:
            out = jax.block_until_ready(self.jit(*args, **kwargs))
        dt = flight.duration_ms
        c1, cms1 = device_events_snapshot()
        rec = self.record
        with _LOCK:
            rec.invocations += 1
            rec.device_ms += dt
            rec.last_invoked = flight.start_ns
            if c1 > c0:
                rec.compiles += c1 - c0
                rec.compile_ms += cms1 - cms0
            if rec._avals is None:
                rec._avals = jax.tree_util.tree_map(
                    _aval_of, (args, kwargs))
        prof = current_profiler()
        if prof is not None:
            prof.note_program(rec.name, dt)
        return out


def instrument(name: str, fn, key="") -> InstrumentedProgram:
    """Wrap a jitted callable so its dispatches enter the registry.
    Idempotent on already-wrapped callables."""
    if isinstance(fn, InstrumentedProgram):
        return fn
    from .metrics import _install_compile_listener
    _install_compile_listener()
    return InstrumentedProgram(name, fn, key=key)


def registry_snapshot(top_n: int = 50, with_cost: bool = True) -> dict:
    """The `GET /_nodes/device_stats` payload: top-N programs by
    cumulative dispatch time + whole-registry rollups. `with_cost` forces
    the lazy cost analysis (scrape-time work, never dispatch-time)."""
    with _LOCK:
        recs = list(_REGISTRY.values())
    recs.sort(key=lambda r: r.device_ms, reverse=True)
    return {
        "program_count": len(recs),
        "invocations_total": sum(r.invocations for r in recs),
        "device_time_in_millis": round(
            sum(r.device_ms for r in recs), 3),
        "compile_time_in_millis": round(
            sum(r.compile_ms for r in recs), 3),
        "compiles_total": sum(r.compiles for r in recs),
        "programs": [r.as_dict(with_cost=with_cost)
                     for r in recs[:top_n]]}


def program_metrics() -> dict[str, dict]:
    """Per-program-site rollup for the `es_xla_program_*` metric family:
    records aggregate by site name (low-cardinality labels; the full
    per-plan-key detail lives on the device_stats endpoint). Costs are
    reported only when already computed — a /_metrics scrape must never
    trigger re-lowering work."""
    with _LOCK:
        recs = list(_REGISTRY.values())
    out: dict[str, dict] = {}
    for r in recs:
        b = out.setdefault(r.name, {
            "invocations_total": 0, "device_time_in_millis": 0.0,
            "compile_time_in_millis": 0.0, "compiles": 0, "programs": 0})
        b["invocations_total"] += r.invocations
        b["device_time_in_millis"] = round(
            b["device_time_in_millis"] + r.device_ms, 3)
        b["compile_time_in_millis"] = round(
            b["compile_time_in_millis"] + r.compile_ms, 3)
        b["compiles"] += r.compiles
        b["programs"] += 1
    return out


def compile_ms_total() -> float:
    with _LOCK:
        return sum(r.compile_ms for r in _REGISTRY.values())


def reset_registry() -> None:
    """Test seam only."""
    with _LOCK:
        _REGISTRY.clear()


def reset_lane_decisions() -> None:
    """Test seam only."""
    with _LOCK:
        _LANE_DECISIONS.clear()


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

_HBM_HIGH_WATER: dict[str, int] = {}


def hbm_poll() -> dict[str, dict]:
    """Per-device memory stats keyed `platform:id`. Backends without
    memory_stats (CPU) report zeros with supported=False instead of
    erroring — the sampler ring and gauges stay shape-stable across
    platforms. Updates the process-lifetime high-water mark."""
    out: dict[str, dict] = {}
    for d in jax.devices():
        ident = f"{d.platform}:{d.id}"
        ms = d.memory_stats()
        if not ms:
            out[ident] = {"bytes_in_use": 0, "peak_bytes": 0,
                          "high_water_bytes":
                              _HBM_HIGH_WATER.get(ident, 0),
                          "limit_bytes": 0, "supported": False}
            continue
        in_use = int(ms.get("bytes_in_use", 0))
        peak = int(ms.get("peak_bytes_in_use", in_use))
        with _LOCK:
            hw = max(_HBM_HIGH_WATER.get(ident, 0), peak, in_use)
            _HBM_HIGH_WATER[ident] = hw
        out[ident] = {"bytes_in_use": in_use, "peak_bytes": peak,
                      "high_water_bytes": hw,
                      "limit_bytes": int(ms.get("bytes_limit", 0)),
                      "supported": True}
    return out


def hbm_peak_bytes() -> int:
    """Max high-water across devices (`_nodes/device_stats`' headline)."""
    polled = hbm_poll()
    return max((v["high_water_bytes"] for v in polled.values()), default=0)


# ---------------------------------------------------------------------------
# Lane-decision flight recorder
# ---------------------------------------------------------------------------

# (lane, reason) -> count; reason "chosen" marks the lane that served.
# This single labeled family subsumes the ad-hoc *_fallbacks_total
# counters (which stay exposed under their old names as aliases).
_LANE_DECISIONS: dict[tuple[str, str], int] = {}


class LaneRecorder:
    """Per-request ordered record of ladder decisions. Shared by
    reference across the `_ShardJob` context copies (contextvars.copy
    keeps the same object), so concurrent shard jobs of ONE request
    append to one record while a different request's recorder — a
    different contextvar value — stays untouched."""

    __slots__ = ("entries",)

    def __init__(self):
        self.entries: list[dict] = []

    def note(self, component: str, lane: str, reason: str) -> None:
        # list.append is atomic under the GIL; entries may interleave
        # across shard threads but never cross requests
        self.entries.append(
            {"component": component, "lane": lane, "reason": reason})

    def explain(self) -> list[dict]:
        """Group the flat decision stream per component: the lane chosen
        (if any) plus every decline that preceded it."""
        by_comp: dict[str, dict] = {}
        order: list[str] = []
        for e in self.entries:
            c = e["component"]
            if c not in by_comp:
                by_comp[c] = {"component": c, "lane": None, "declines": []}
                order.append(c)
            if e["reason"] == "chosen":
                by_comp[c]["lane"] = e["lane"]
            else:
                by_comp[c]["declines"].append(
                    {"lane": e["lane"], "reason": e["reason"]})
        return [by_comp[c] for c in order]

    def chose(self, lane: str) -> bool:
        return any(e["lane"] == lane and e["reason"] == "chosen"
                   for e in self.entries)


_LANE_RECORDER: contextvars.ContextVar["LaneRecorder | None"] = \
    contextvars.ContextVar("es_lane_recorder", default=None)


def current_lanes() -> LaneRecorder | None:
    return _LANE_RECORDER.get()


@contextlib.contextmanager
def record_lanes(rec: LaneRecorder | None = None):
    rec = rec if rec is not None else LaneRecorder()
    tok = _LANE_RECORDER.set(rec)
    try:
        yield rec
    finally:
        _LANE_RECORDER.reset(tok)


def _note(component: str, lane: str, reason: str) -> None:
    with _LOCK:
        k = (lane, reason)
        _LANE_DECISIONS[k] = _LANE_DECISIONS.get(k, 0) + 1
    rec = _LANE_RECORDER.get()
    if rec is not None:
        rec.note(component, lane, reason)
    # zero-duration marker on the active trace span (no-op untraced):
    # forced-retained traces carry the full ladder walk
    tracing.add_event("lane", component=component, lane=lane,
                      reason=reason)


def lane_chosen(component: str, lane: str) -> None:
    """The ladder settled: `component` is served by `lane`."""
    _note(component, lane, "chosen")


def lane_decline(component: str, lane: str, reason: str) -> None:
    """`lane` refused this request at `component` for `reason`; the
    ladder continues downward."""
    _note(component, lane, reason)


def lane_decisions_snapshot() -> dict[str, int]:
    """Flat `lane:reason -> count` view (`_nodes/device_stats` / tests)."""
    with _LOCK:
        return {f"{lane}:{reason}": n
                for (lane, reason), n in sorted(_LANE_DECISIONS.items())}


def lane_decision_metrics() -> dict[tuple[str, str], dict]:
    """The `es_search_lane_decisions_total{lane=,reason=}` payload:
    tuple-keyed registry for the multi-label OpenMetrics walk."""
    with _LOCK:
        return {k: {"decisions_total": n}
                for k, n in _LANE_DECISIONS.items()}
