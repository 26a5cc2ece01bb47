"""Mean milliseconds the program spent in its spans `spans` (summed) per
occurrence of its span `per`, over the window: the deltas of
`es_span_seconds_total{span=...}` over the delta of `es_span_total{span=per}`
on `/_metrics`. Nothing where `per` never occurred (a program without these
spans exports no such family)."""

from readers.common import delta


def read(ctx, params):
    n = delta(ctx, "es_span_total", span=params["per"])
    if not n:
        return None
    seconds = sum(delta(ctx, "es_span_seconds_total", span=s)
                  for s in params["spans"])
    return 1000.0 * seconds / n
