"""Least time for the work over the device time of the programs that did
it, in per cent. The work is `work.body_bytes` of every search body under
way inside the traced slice (a request that straddles an edge counts by the
share of its time inside); the device time is that of the trace's programs
whose names match `programs` (regular expressions, data of the metric's
file). Memory-bound: the least time is bytes over the chip's peak bytes/s.
"""

import re

import work
from readers.common import ok, peaks


def read(ctx, params):
    t = ctx.get("trace")
    if not t or not t["device_planes"]:
        return None
    pats = [re.compile(p) for p in params["programs"]]
    device_s = sum(s for name, (_, s) in t["modules"].items()
                   if any(p.search(name) for p in pats))
    if device_s <= 0:
        return None
    lo, hi = ctx["trace_span"]
    n_bytes = 0.0
    for r in ctx["records"]:
        if not ok(r) or r["done"] <= lo or r["sent"] >= hi:
            continue
        inside = (min(r["done"], hi) - max(r["sent"], lo)) \
            / max(r["done"] - r["sent"], 1e-9)
        n_bytes += inside * sum(work.body_bytes(ctx["reference"], b)
                                for b in ctx["requests"][r["i"]]["bodies"])
    if not n_bytes:
        return None
    return 100.0 * work.least_seconds(peaks(ctx), n_bytes) / device_s
