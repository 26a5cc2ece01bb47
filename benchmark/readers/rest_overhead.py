"""Median of the client-side request time minus the response's own `took`
(for `_msearch`, minus the largest `took` among its items)."""

from readers.common import ok, quantile


def read(ctx, params):
    over = [(r["done"] - r["sent"]) * 1000.0 - r["took_ms"]
            for r in ctx["records"] if ok(r) and r["took_ms"] is not None]
    return quantile(over, 0.5)
