"""A counter of `/_metrics` (`metric`), after the window minus before."""

from readers.common import delta


def read(ctx, params):
    return delta(ctx, params["metric"])
