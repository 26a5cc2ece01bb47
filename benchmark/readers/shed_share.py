"""HTTP 429 over attempted, in per cent."""


def read(ctx, params):
    n = len(ctx["records"]) + len(ctx["never_answered"])
    if not n:
        return None
    return 100.0 * sum(r["status"] == 429 for r in ctx["records"]) / n
