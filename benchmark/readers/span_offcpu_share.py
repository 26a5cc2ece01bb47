"""The share of the spans `spans`' wall time that their threads spent off
the CPU over the window, in per cent, from the spans that read the CPU
clock (one in `tracing.CPU_SAMPLE` of them): 100 x the sum of (delta of
`es_span_cpu_wall_seconds_total` - delta of `es_span_cpu_seconds_total`)
over the sum of the deltas of `es_span_cpu_wall_seconds_total`,
`{span=...}` on `/_metrics`. For a span that computes on the host, wall
time less the thread's own CPU time is time it was ready but not running:
waiting for the interpreter lock or for a core. Nothing where the program
exports no CPU family (the parent commit) or those spans took no time."""

from readers.common import delta


def read(ctx, params):
    if "es_span_cpu_wall_seconds_total" not in ctx["after"]["metrics"]:
        return None
    wall = sum(delta(ctx, "es_span_cpu_wall_seconds_total", span=s)
               for s in params["spans"])
    if wall <= 0:
        return None
    cpu = sum(delta(ctx, "es_span_cpu_seconds_total", span=s)
              for s in params["spans"])
    return 100.0 * (wall - cpu) / wall
