"""Serving-QoS: admission control + load shedding in front of the device.

The reference earns its tail latency from machinery this reproduction
lacked: a search pool that REJECTS under saturation instead of queueing
unboundedly (EsRejectedExecutionException -> 429), load-balanced reads
across replica copies (OperationRouting.java:144-154) and five typed
connection classes per node pair so bulk/recovery traffic can never
starve query and cluster-state traffic (NettyTransport.java:180-184).
On a TPU the same goals map onto inference-serving staples:

  * `QosController` — per-traffic-class admission in front of the search
    pool. It tracks queue depth, breaker pressure and an EWMA of device
    latency; excess load sheds as HTTP 429 + `Retry-After` (never a 5xx,
    never an unbounded queue), and BEFORE shedding it degrades
    gracefully: the dynamic batcher shrinks its coalescing window and
    the plan cache is preferred over fresh parses.
  * `Ewma` — latency EWMA + mean absolute deviation; `deadline_ms()` is
    the adaptive p99-of-EWMA the hedged-read coordinator arms its backup
    timer with (cluster/node.py `_query_with_hedge`).
  * module-level hedge counters — the cluster coordinator records
    fired/win/cancel outcomes here so the single exposition
    (`es_search_hedged_total{outcome=}`) and the sampler ring read one
    source.

Traffic classes mirror the reference's five connection types
(recovery/bulk/reg/state/ping); the REST edge maps request classes onto
them and the transport layer gives each class its own connection budget
(cluster/transport.py)."""

from __future__ import annotations

import threading
import time

# the five reference connection classes (NettyTransport.java:180-184);
# REST admission uses search/bulk; recovery/state/ping exist for the
# transport's per-class budgets and the shed-accounting labels
TRAFFIC_CLASSES = ("search", "bulk", "recovery", "state", "ping")

# fraction of `node.search.qos.max_inflight` each class may hold; state
# and ping are control-plane traffic and are never shed (a cluster that
# sheds its own heartbeats under load partitions itself)
DEFAULT_SHARES = {"search": 0.6, "bulk": 0.3, "recovery": 0.1,
                  "state": 1.0, "ping": 1.0}

_NEVER_SHED = frozenset({"state", "ping"})


class QosShedException(Exception):
    """Admission refused: maps to HTTP 429 + Retry-After at the REST
    boundary (the EsRejectedExecutionException contract, upgraded with a
    client backoff hint)."""

    def __init__(self, tclass: str, reason: str, retry_after_s: float):
        super().__init__(
            f"qos shed [{tclass}]: {reason} (retry in {retry_after_s:.0f}s)")
        self.tclass = tclass
        self.reason = reason
        self.retry_after_s = retry_after_s


class Ewma:
    """Latency EWMA + mean-absolute-deviation (the TCP RTO estimator
    shape): `deadline_ms()` = ewma + k*dev is the adaptive percentile
    deadline hedged reads arm their backup timer with. Unlocked — every
    field write is a single atomic store and readers tolerate a torn
    pair (both fields move smoothly)."""

    __slots__ = ("alpha", "value", "dev", "n")

    def __init__(self, alpha: float = 0.2):
        self.alpha = alpha
        self.value = 0.0
        self.dev = 0.0
        self.n = 0

    def observe(self, ms: float) -> None:
        if self.n == 0:
            self.value = ms
            self.dev = ms / 2.0
        else:
            err = ms - self.value
            self.value += self.alpha * err
            self.dev += self.alpha * (abs(err) - self.dev)
        self.n += 1

    def deadline_ms(self, k: float = 3.0) -> float:
        """Adaptive p99-of-EWMA: mean + k deviations (k=3 ~ p99 for the
        latency shapes a serving tier sees)."""
        return self.value + k * self.dev


def _as_bool(v, default: bool) -> bool:
    if v is None:
        return default
    if isinstance(v, str):
        return v.strip().lower() not in ("false", "0", "no", "off")
    return bool(v)


class QosController:
    """Per-node admission control. All thresholds are live-read from
    settings so `_settings`-style overlays and tests apply without a
    restart; the clock is injectable so EWMA tests never sleep.

    Settings:
      node.search.qos.enable             default true
      node.search.qos.max_inflight       default 256 admission slots
      node.search.qos.<class>.share      per-class slot fraction
                                         (DEFAULT_SHARES)
      node.search.qos.degrade_threshold  default 0.7 — above: shrink the
                                         batch window, prefer cached plans
      node.search.qos.shed_threshold     default 0.9 — above: shed
                                         sheddable classes with 429
      node.search.qos.shed_latency_ms    default 5000 — the EWMA-p99
                                         device latency that counts as
                                         pressure 1.0
    """

    def __init__(self, settings=None, thread_pool=None, breakers=None,
                 clock=None):
        self._settings = settings
        self._thread_pool = thread_pool
        self._breakers = breakers
        self._clock = clock or time.monotonic
        self._lock = threading.Lock()
        self.latency = Ewma()
        self._latency_at = None        # clock time of the last sample
        self._inflight = {c: 0 for c in TRAFFIC_CLASSES}
        self.admitted = {c: 0 for c in TRAFFIC_CLASSES}
        self.shed = {c: 0 for c in TRAFFIC_CLASSES}
        self.degraded_total = 0
        self._degraded = False
        from ..common.metrics import Meter
        self.shed_meter = Meter(clock=clock)

    # -- live settings -----------------------------------------------------

    def _get(self, key, default):
        if self._settings is None:
            return default
        return self._settings.get(key, default)

    def enabled(self) -> bool:
        return _as_bool(self._get("node.search.qos.enable", True), True)

    def _max_inflight(self) -> int:
        try:
            return max(1, int(self._get("node.search.qos.max_inflight",
                                        256)))
        except (TypeError, ValueError):
            return 256

    def _slots(self, tclass: str) -> int:
        share = self._get(f"node.search.qos.{tclass}.share",
                          DEFAULT_SHARES.get(tclass, 0.5))
        try:
            share = float(share)
        except (TypeError, ValueError):
            share = DEFAULT_SHARES.get(tclass, 0.5)
        return max(0, int(self._max_inflight() * share))

    def _threshold(self, key: str, default: float) -> float:
        try:
            return float(self._get(f"node.search.qos.{key}", default))
        except (TypeError, ValueError):
            return default

    # -- pressure signals --------------------------------------------------

    def record_latency(self, ms: float) -> None:
        """Feed the device-latency EWMA (the coordinator calls this with
        every search's device-phase wall time)."""
        with self._lock:
            self.latency.observe(ms)
            self._latency_at = self._clock()

    def queue_frac(self) -> float:
        """Search-pool queue occupancy in [0, 1]."""
        if self._thread_pool is None:
            return 0.0
        pool = self._thread_pool.pools.get("search")
        if pool is None or not pool.queue_size:
            return 0.0
        return min(1.0, pool._q.qsize() / pool.queue_size)

    def breaker_frac(self) -> float:
        """Parent-breaker occupancy in [0, 1]."""
        if self._breakers is None:
            return 0.0
        limit = getattr(self._breakers, "total_limit", 0)
        if not limit:
            return 0.0
        with self._breakers._lock:
            used = sum(b.used for b in self._breakers.breakers.values())
        return min(1.0, max(0.0, used / limit))

    def latency_frac(self) -> float:
        """EWMA-p99 device latency relative to the shed ceiling, decayed
        with idle time. The decay breaks a shed livelock (ISSUE 12
        satellite, found driving the quantized tier's first query): one
        compile-heavy request can spike the EWMA past the ceiling, and
        because SHED requests never execute, no new sample could ever
        bring it back down — the node 429'd forever. A stale estimate is
        a weak estimate: with no fresh device latency for a while the
        signal halves per `node.search.qos.latency_halflife_s` (default
        30 s, ≤0 restores the undecayed signal), so probe traffic gets
        admitted to re-measure reality."""
        ceiling = self._threshold("shed_latency_ms", 5000.0)
        if ceiling <= 0:
            return 0.0
        frac = min(1.0, self.latency.deadline_ms() / ceiling)
        half_life = self._threshold("latency_halflife_s", 30.0)
        if half_life > 0 and self._latency_at is not None:
            idle = max(0.0, self._clock() - self._latency_at)
            frac *= 0.5 ** (idle / half_life)
        return frac

    def pressure(self) -> float:
        """The overload score in [0, 1]: the WORST of queue depth,
        breaker occupancy and EWMA device latency — any one of them
        saturating means new work will only queue, burn memory, or miss
        its deadline."""
        return max(self.queue_frac(), self.breaker_frac(),
                   self.latency_frac())

    @property
    def degraded(self) -> bool:
        """True while pressure sits in the degrade band: the batcher
        shrinks its window, plan caches are preferred. Recomputed by the
        admission path; reads are cheap."""
        return self._degraded

    # -- admission ---------------------------------------------------------

    def retry_after_s(self) -> float:
        """Client backoff hint: roughly the time for the current queue to
        drain at the EWMA latency, floored at 1s, capped at 30s."""
        if self._thread_pool is not None:
            pool = self._thread_pool.pools.get("search")
            depth = pool._q.qsize() if pool is not None else 0
        else:
            depth = 0
        est = (depth + 1) * max(self.latency.value, 1.0) / 1000.0
        return min(30.0, max(1.0, est))

    def admit(self, tclass: str) -> "_Admission":
        """Admission check for one request of `tclass`. Returns a context
        manager holding the in-flight slot; raises QosShedException when
        the request must shed. Control-plane classes (state/ping) are
        never shed."""
        if not self.enabled():
            return _Admission(self, None)
        if tclass not in self._inflight:
            tclass = "search"
        p = self.pressure()
        degrade = self._threshold("degrade_threshold", 0.7)
        shed_at = self._threshold("shed_threshold", 0.9)
        with self._lock:
            was_degraded = self._degraded
            self._degraded = p >= degrade
            if self._degraded and not was_degraded:
                self.degraded_total += 1
            if tclass not in _NEVER_SHED:
                slots = self._slots(tclass)
                if self._inflight[tclass] >= slots:
                    self.shed[tclass] += 1
                    self.shed_meter.mark()
                    raise QosShedException(
                        tclass, f"class budget exhausted "
                        f"({self._inflight[tclass]}/{slots} in flight)",
                        self.retry_after_s())
                if p >= shed_at:
                    self.shed[tclass] += 1
                    self.shed_meter.mark()
                    raise QosShedException(
                        tclass, f"node overloaded (pressure {p:.2f})",
                        self.retry_after_s())
            self._inflight[tclass] += 1
            self.admitted[tclass] += 1
        return _Admission(self, tclass)

    def _release(self, tclass: str) -> None:
        with self._lock:
            self._inflight[tclass] = max(0, self._inflight[tclass] - 1)

    # -- degrade hooks (the batcher reads these) ---------------------------

    def batch_window(self, base: int) -> int:
        """Coalescing window for the dynamic batcher: full when healthy,
        quartered under degrade pressure so per-batch latency shrinks
        before any request sheds."""
        if self._degraded:
            return max(4, base // 4)
        return base

    def follower_wait_s(self) -> float:
        """Deadline-aware max-wait for batcher followers: generous
        relative to the EWMA device latency (leader + one full batch),
        bounded so a wedged leader can never hold a follower the silent
        30 s the old hard-coded timeout did."""
        est = self.latency.deadline_ms() / 1000.0
        return min(30.0, max(1.0, 4.0 * est + 1.0))

    # -- stats -------------------------------------------------------------

    def class_stats(self) -> dict:
        """{class: leaves} for the labeled `qos` metric section
        (es_qos_shed_total{class=} et al.)."""
        with self._lock:
            return {c: {"shed_total": self.shed[c],
                        "admitted_total": self.admitted[c],
                        "inflight": self._inflight[c],
                        "slots": self._slots(c)}
                    for c in TRAFFIC_CLASSES}

    def control_plane_shed(self) -> int:
        """Sheds charged against never-shed classes — must stay 0 by
        construction; the chaos invariant checker asserts it after every
        disruption round so a regression in the admission gate is caught
        with a reproducing seed attached."""
        with self._lock:
            return sum(self.shed[c] for c in _NEVER_SHED)

    def stats(self) -> dict:
        return {"pressure": round(self.pressure(), 4),
                "queue_frac": round(self.queue_frac(), 4),
                "breaker_frac": round(self.breaker_frac(), 4),
                "latency_frac": round(self.latency_frac(), 4),
                "ewma_latency_ms": round(self.latency.value, 3),
                "ewma_deadline_ms": round(self.latency.deadline_ms(), 3),
                "degraded": 1 if self._degraded else 0,
                "degraded_total": self.degraded_total,
                "shed_rate_1m": round(self.shed_meter.rate(60), 4),
                "by_class": self.class_stats()}


class _Admission:
    """The held admission slot; releases on exit. `tclass is None` means
    QoS was disabled at admit time — nothing to release."""

    __slots__ = ("_qos", "_tclass")

    def __init__(self, qos: QosController, tclass: str | None):
        self._qos = qos
        self._tclass = tclass

    def __enter__(self) -> "_Admission":
        return self

    def __exit__(self, *exc) -> bool:
        if self._tclass is not None:
            self._qos._release(self._tclass)
        return False


# ---------------------------------------------------------------------------
# hedged-read accounting: the cluster coordinator records outcomes here so
# /_metrics and the sampler ring read one process-wide source.
# ---------------------------------------------------------------------------

HEDGE_OUTCOMES = ("fired", "win_primary", "win_backup", "canceled",
                  "failed", "moving")
# "moving": the hedge armed/fired because the chosen copy is part of an
# in-flight relocation (ISSUE 15's rebalance-under-traffic cover) — its
# node is also streaming recovery chunks, so the deadline tightens by
# cluster.search.hedge.moving_factor and fires even on a cold EWMA.

_hedge_lock = threading.Lock()
_hedge_counts = {o: 0 for o in HEDGE_OUTCOMES}
_hedge_meter = None


def record_hedge(outcome: str) -> None:
    global _hedge_meter
    with _hedge_lock:
        if _hedge_meter is None:
            from ..common.metrics import Meter
            _hedge_meter = Meter()
        _hedge_counts[outcome] = _hedge_counts.get(outcome, 0) + 1
        if outcome == "fired":
            _hedge_meter.mark()


def hedge_snapshot() -> dict:
    with _hedge_lock:
        return dict(_hedge_counts)


def hedge_rate(window: int = 60) -> float:
    with _hedge_lock:
        return _hedge_meter.rate(window) if _hedge_meter is not None else 0.0


# ---------------------------------------------------------------------------
# Per-transport-class latency EWMAs (ISSUE 19 pod tier)
# ---------------------------------------------------------------------------
# Cross-host pre-reduced merges ride the "dcn" transport class; their
# latencies observe HERE, never into the per-node `_node_lat` EWMAs that
# arm the hedge deadline — a slow DCN link must not inflate the ICI
# deadline for co-hosted copies (and vice versa). One Ewma per class,
# same alpha/deviations math as the hedge tier, surfaced by
# transport_latency_snapshot() for the metrics scrape.

_transport_lat_lock = threading.Lock()
_transport_lat: dict[str, Ewma] = {}


def observe_transport_latency(tclass: str, ms: float) -> None:
    with _transport_lat_lock:
        lat = _transport_lat.get(tclass)
        if lat is None:
            lat = _transport_lat[tclass] = Ewma()
        lat.observe(ms)


def transport_latency_snapshot() -> dict:
    """{class: {"ewma_ms", "deadline_ms", "n"}} for every observed
    transport class."""
    with _transport_lat_lock:
        return {c: {"ewma_ms": lat.value, "deadline_ms": lat.deadline_ms(),
                    "n": lat.n}
                for c, lat in _transport_lat.items()}


def reset_transport_latency() -> None:
    with _transport_lat_lock:
        _transport_lat.clear()
