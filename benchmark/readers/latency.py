"""A percentile (`q`) of the latency of all requests of the window, each
timed from when it was due; a 429 or a failure misses: it is charged the
`miss_ms` of the metric's file."""

from readers.common import latencies_ms, ok, quantile


def read(ctx, params):
    values = [ms if ok(r) else max(ms, params["miss_ms"])
              for ms, r in zip(latencies_ms(ctx), ctx["records"])]
    values += [params["miss_ms"]] * len(ctx["never_answered"])
    return quantile(values, params["q"])
