"""`roofline.py`'s share for work that is not memory-bound alone: the least
time of each request is max(bytes / the chip's peak bytes/s, flops / its
peak bf16 flops/s), from `work.request_work` (a kNN or cosine body's matrix
once a request and 2 x N x dims flops a body; a rescore's window), over the
device time of the trace's programs whose names match `programs`. The window
logic is `roofline.py`'s (`common.roofline_share`).
"""

import work
from readers.common import peaks, roofline_share


def read(ctx, params):
    return roofline_share(
        ctx, params,
        lambda bodies: work.least_seconds_mixed(
            peaks(ctx), *work.request_work(ctx["reference"], bodies)),
        lambda seconds: seconds)
