"""`bytes_in_use` of the least-full device over the fullest's, after the
window, in per cent: 100 where every chip of the node holds as much as any
other, near 0 where one chip holds the index and the others nothing."""


def read(ctx, params):
    in_use = [d["bytes_in_use"] for d in ctx["after"]["hbm"].values()
              if d.get("supported")]
    if not in_use or not max(in_use):
        return None
    return 100.0 * min(in_use) / max(in_use)
