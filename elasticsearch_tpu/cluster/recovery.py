"""Peer-recovery rate limiting + process-wide recovery counters.

The analog of the reference's RecoverySettings.rateLimiter
(indices/recovery/RecoverySettings.java — a SimpleRateLimiter fed by
`indices.recovery.max_bytes_per_sec`, default 40mb): every file chunk a
recovery TARGET pulls pays tokens into a per-node token bucket before
the bytes hit disk, so N concurrent recoveries share one node-wide
budget and a relocation wave cannot starve serving traffic of I/O.

Counters live module-level (the qos.record_hedge pattern): one source
of truth feeding /_metrics (`es_recovery_*`), the sampler ring, and the
tests' throttle-compliance check, readable from both the cluster
ClusterNode and the single-node NodeService without plumbing.
"""

from __future__ import annotations

import threading
import time

_UNITS = {"b": 1, "kb": 1 << 10, "mb": 1 << 20, "gb": 1 << 30,
          "tb": 1 << 40}


def parse_bytes(v, default: float = 0.0) -> float:
    """Human byte-size string -> bytes/float. `0`, negative, or unset
    mean unlimited (returned as 0.0). Accepts ints and "40mb" forms."""
    if v is None:
        return default
    if isinstance(v, (int, float)):
        return float(v) if v > 0 else 0.0
    s = str(v).strip().lower()
    if not s:
        return default
    for suffix in ("tb", "gb", "mb", "kb", "b"):
        if s.endswith(suffix):
            try:
                n = float(s[: -len(suffix)])
            except ValueError:
                return default
            n *= _UNITS[suffix]
            return n if n > 0 else 0.0
    try:
        n = float(s)
    except ValueError:
        return default
    return n if n > 0 else 0.0


class RecoveryCancelled(Exception):
    """Raised between chunks when the shard's recovery was cancelled by
    a newer cluster state (cancel_relocations_for / drop)."""


class RecoveryThrottle:
    """Token bucket over `rate_fn() -> bytes/sec` (0 = unlimited).

    The rate is re-read on every acquire so a live settings update takes
    effect mid-stream. Burst capacity is one half second of tokens —
    small enough that a chunk stream can never spike far above the
    configured rate, large enough that one RECOVERY_CHUNK never waits
    at sane rates."""

    def __init__(self, rate_fn):
        self.rate_fn = rate_fn
        self._lock = threading.Lock()
        self._tokens = 0.0
        self._last = time.monotonic()
        self.waits_total = 0
        self.throttled_time_s = 0.0

    def acquire(self, nbytes: int) -> float:
        """Block until `nbytes` of budget is available; returns seconds
        slept (0.0 when the bucket had room)."""
        rate = float(self.rate_fn() or 0.0)
        if rate <= 0 or nbytes <= 0:
            return 0.0
        burst = max(float(nbytes), rate / 2.0)
        slept = 0.0
        while True:
            with self._lock:
                now = time.monotonic()
                self._tokens = min(
                    burst, self._tokens + (now - self._last) * rate)
                self._last = now
                if self._tokens >= nbytes:
                    self._tokens -= nbytes
                    if slept > 0.0:
                        self.waits_total += 1
                        self.throttled_time_s += slept
                    return slept
                need = (nbytes - self._tokens) / rate
            wait = min(need, 0.5)
            time.sleep(wait)
            slept += wait


# -- process-wide counters (the qos.record_hedge pattern) -----------------

_LOCK = threading.Lock()
_COUNTER_KEYS = ("bytes_total", "chunks_total", "throttle_waits_total",
                 "retries_total", "cancelled_total", "completed_total")
_STATS = {k: 0 for k in _COUNTER_KEYS}


def record(key: str, n: int = 1) -> None:
    with _LOCK:
        _STATS[key] = _STATS.get(key, 0) + n


def snapshot() -> dict[str, int]:
    with _LOCK:
        return dict(_STATS)


def reset() -> None:
    """Test seam only."""
    with _LOCK:
        for k in list(_STATS):
            _STATS[k] = 0
