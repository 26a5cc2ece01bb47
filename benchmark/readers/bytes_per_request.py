"""A byte counter of `/_metrics` (`metric`), after the window minus before,
over the window's answered requests. Nothing where the counter did not move:
no lane answers a request without moving a byte."""

from readers.common import delta, ok


def read(ctx, params):
    answered = sum(1 for r in ctx["records"] if ok(r))
    moved = delta(ctx, params["metric"])
    if not answered or not moved:
        return None
    return moved / answered
