"""Search bodies answered correctly per second, over the whole window."""

from readers.common import ok


def read(ctx, params):
    per = ctx["cell"].workload.get("bodies_per_request", 1)
    done = sum(per for r in ctx["records"] if ok(r))
    return done / ctx["window_s"] if done else None
