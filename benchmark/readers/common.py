"""What several readers share."""

from __future__ import annotations

import json
import os
import re
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


def roofline_share(ctx: dict, params: dict, work_of, seconds_of):
    """Least time for the work over the device time of the programs that
    did it, in per cent. The device time is that of the trace's programs
    whose names match `params["programs"]`; the work is `work_of(bodies)` of
    every request under way inside the traced slice, a request that
    straddles an edge counting by the share of its time inside; its least
    time is `seconds_of(the sum)`. None where there is no device plane, no
    such program or no work."""
    t = ctx.get("trace")
    if not t or not t["device_planes"]:
        return None
    pats = [re.compile(p) for p in params["programs"]]
    device_s = sum(s for name, (_, s) in t["modules"].items()
                   if any(p.search(name) for p in pats))
    if device_s <= 0:
        return None
    lo, hi = ctx["trace_span"]
    total = 0.0
    for r in ctx["records"]:
        if not ok(r) or r["done"] <= lo or r["sent"] >= hi:
            continue
        inside = (min(r["done"], hi) - max(r["sent"], lo)) \
            / max(r["done"] - r["sent"], 1e-9)
        total += inside * work_of(ctx["requests"][r["i"]]["bodies"])
    if not total:
        return None
    return 100.0 * seconds_of(total) / device_s
