"""Share of `:chosen` lane decisions over the window that went to the
device lanes named by `prefixes`, in per cent."""


def read(ctx, params):
    before, after = ctx["before"]["lane_decisions"], \
        ctx["after"]["lane_decisions"]
    chosen = {k.split(":")[0]: v - before.get(k, 0)
              for k, v in after.items() if k.endswith(":chosen")}
    total = sum(chosen.values())
    if not total:
        return None
    return 100.0 * sum(v for lane, v in chosen.items()
                       if lane.startswith(tuple(params["prefixes"]))) / total
