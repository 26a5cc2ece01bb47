"""What several readers share."""

from __future__ import annotations

import json
import os
import statistics

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def latencies_ms(ctx: dict) -> list[float]:
    """One latency per request of the window, from when it was due; a
    refusal, a failure or an answer that never came counts as the whole
    wait (it misses any limit)."""
    out = []
    for r in ctx["records"]:
        start = r["due"] if r["due"] is not None else r["sent"]
        out.append((r["done"] - start) * 1000.0)
    return out


def ok(r: dict) -> bool:
    return r["status"] == 200 and not r["item_errors"]


def quantile(values: list[float], q: float) -> float | None:
    if len(values) < 2:
        return None
    cuts = statistics.quantiles(values, n=100, method="inclusive")
    return cuts[round(q * 100) - 1]


def metric_sum(snapshot: dict, name: str, **labels) -> float:
    return sum(v for lab, v in snapshot["metrics"].get(name, [])
               if all(lab.get(k) == want for k, want in labels.items()))


def delta(ctx: dict, name: str, **labels) -> float:
    return metric_sum(ctx["after"], name, **labels) \
        - metric_sum(ctx["before"], name, **labels)


def peaks(ctx: dict) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    kind = ctx["device"].device_kind
    if kind not in table:
        raise KeyError(f"benchmark/peaks.json has no device kind {kind!r}")
    return table[kind]
