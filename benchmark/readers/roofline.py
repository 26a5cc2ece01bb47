"""Least time for the work over the device time of the programs that did
it, in per cent. The work is `work.body_bytes` of every search body under
way inside the traced slice (a request that straddles an edge counts by the
share of its time inside); the device time is that of the trace's programs
whose names match `programs` (regular expressions, data of the metric's
file). Memory-bound: the least time is bytes over the chip's peak bytes/s.
"""

import work
from readers.common import peaks, roofline_share


def read(ctx, params):
    return roofline_share(
        ctx, params,
        lambda bodies: sum(work.body_bytes(ctx["reference"], b)
                           for b in bodies),
        lambda n_bytes: work.least_seconds(peaks(ctx), n_bytes))
