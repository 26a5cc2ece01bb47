"""Share of a labelled counter of `/_metrics` (`metric`) that rose under one
value of one label (`label`, `value`), over the window, in per cent: the
delta of `metric{label=value}` over the delta of `metric` under every value.
Nothing where the counter did not rise (a program without it exports no
such family)."""

from readers.common import delta


def read(ctx, params):
    total = delta(ctx, params["metric"])
    if not total:
        return None
    return 100.0 * delta(ctx, params["metric"],
                         **{params["label"]: params["value"]}) / total
