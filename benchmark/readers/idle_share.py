"""1 - (union of the device's operation intervals) / (traced slice), in
per cent, from the profiler's trace."""


def read(ctx, params):
    t = ctx.get("trace")
    if not t or not t["device_planes"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
