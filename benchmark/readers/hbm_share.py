"""`bytes_in_use` of the fullest device over its `limit_bytes`, after the
window, in per cent."""


def read(ctx, params):
    shares = [100.0 * d["bytes_in_use"] / d["limit_bytes"]
              for d in ctx["after"]["hbm"].values()
              if d.get("supported") and d["limit_bytes"]]
    return max(shares) if shares else None
