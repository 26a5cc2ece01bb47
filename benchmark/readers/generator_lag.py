"""p95 of (actual send - due) in the load generator, open loop only."""

from readers.common import quantile


def read(ctx, params):
    lags = [(r["sent"] - r["due"]) * 1000.0 for r in ctx["records"]
            if r["due"] is not None]
    return quantile(lags, params["q"])
