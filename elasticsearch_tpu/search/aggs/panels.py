"""Dashboard panels: `size: 0` filter + one leaf aggregation, answered from a
closed, warmed set of compiled programs.

A dashboard over log events asks the same three questions with a fresh time
range every time (rally-tracks `http_logs`: `hourly_agg`, `200s-in-range`,
`term`), with the request cache off. The general lanes evaluate such a body
as eager `jnp` operations shaped by the exact batch size and by whatever the
body holds, so a window of fresh ranges compiles for ever. This lane accepts
exactly three plan shapes and runs each as ONE jitted program a segment:

  hist    range(i64 column)                     -> date_histogram(same column,
                                                   fixed interval)
  terms   match(one term) + range(i64 column)   -> terms(i64 column)
  count   range(i64 column) + term(i64 column)  -> no aggregation (hits.total)

Anything else (another clause, another aggregation or parameter, an open or
a too-wide range, an f64 column, sub-aggregations) is not this lane's:
`row_of` returns None and the caller keeps the path it had.

**The closed set.** A program is keyed by what the index fixes and by
buckets, never by what a request holds:

  programs = 3 shapes x Q buckets {1, 4, 32} x segment-row buckets (the
             power of two `n_pad` the segment tensors already have)
             [x postings-window buckets {2^10, 2^13, 2^16, ...} up to the
             segment's largest postings list, for `terms` only; the
             segment's padded postings length rides along with `n_pad`]

Range bounds, the term's value, the matched term's postings slice (start,
length) and the histogram's interval are operands. The histogram's bucket
array is `HIST_BINS` = 256 wide (7 days of hours is 169): a document's
bucket is its absolute bucket number modulo 256, which is the same for every
row of the batch, so one one-hot matmul counts all rows; a row whose range
spans at most 256 buckets meets each residue once, and the host puts the
residues back in order from the row's own lower bound. `terms` counts
per-segment ordinals of the column's distinct values (`TERM_BINS` = 32 at
most; cached on the immutable segment), not the values.

`ensure_warm` runs every member of the set that the current segments call
for once on zero operands (`warm_segment`), the first time a search meets a
segment-row bucket it has not seen; after it no time range, term, status or
batch size compiles anything. Exactness is untouched: 64-bit columns stay
64-bit on the device, totals and bucket counts are integer sums, the
per-shard `terms` truncation and the render are the aggregation framework's
own (`terms_partial_from_counts`, `merge_shard_partials`, `render`).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...common import tracing
from ...common.device_stats import instrument
from ...common.metrics import device_fetch
from ...index.segment import Segment
from ...ops.aggs import _onehot_counts
from ..query_dsl import (BoolNode, MatchNode, Node, RangeNode,
                         TermFilterNode)
from .aggregators import (AggSpec, _fixed_interval_ms,
                          terms_partial_from_counts)

Q_BUCKETS = (1, 4, 32)          # 32 == SearchBatcher.MAX_BATCH
HIST_BINS = 256                 # holds 7 days of hours (169)
TERM_BINS = 32                  # distinct values of a `terms` column
W_FLOOR, W_STEP = 1 << 10, 8    # postings-window buckets: 2^10, 2^13, ...

_I64 = np.iinfo(np.int64)


# ---------------------------------------------------------------------------
# the programs
# ---------------------------------------------------------------------------

def _range_mask(col, missing, live, lo, hi):
    """bool[Q, N]: lo <= col <= hi, present and live. A padded row carries
    lo > hi and matches nothing."""
    ok = live & ~missing
    return (col[None, :] >= lo[:, None]) & (col[None, :] <= hi[:, None]) \
        & ok[None, :]


def _pack(counts, mask):
    """i32[Q, bins + 1]: the counts, then the row's total."""
    return jnp.concatenate(
        [counts.astype(jnp.int32), mask.sum(axis=1, dtype=jnp.int32)[:, None]],
        axis=1)


@jax.jit
def panel_hist(col, missing, live, lo, hi, interval):
    with jax.named_scope("aggs.mask"):
        mask = _range_mask(col, missing, live, lo, hi)
    with jax.named_scope("aggs.bin"):
        # exact i64 floor division by a runtime operand (ops/aggs.hist_bins
        # says why never a constant); the residue is row-independent
        ids = ((col // interval) & (HIST_BINS - 1)).astype(jnp.int32)
    with jax.named_scope("aggs.count"):
        return _pack(_onehot_counts(ids, mask, HIST_BINS), mask)


@partial(jax.jit, static_argnames=("W",))
def panel_terms(col, missing, live, doc_ids, ords, lo, hi, slices, *, W: int):
    Q, N = lo.shape[0], col.shape[0]
    with jax.named_scope("aggs.mask"):
        mask = _range_mask(col, missing, live, lo, hi)
        starts, lens = slices[0], slices[1]
        offs = jnp.arange(W, dtype=jnp.int32)[None, :]
        idx = jnp.clip(starts[:, None] + offs, 0, doc_ids.shape[0] - 1)
        doc = jnp.where(offs < lens[:, None], doc_ids[idx], N)
        rows = jnp.arange(Q, dtype=jnp.int32)[:, None]
        hit = jnp.zeros((Q, N), jnp.int32).at[rows, doc].add(1, mode="drop")
        mask = mask & (hit > 0)
    with jax.named_scope("aggs.count"):
        valid = mask & (ords >= 0)[None, :]
        return _pack(_onehot_counts(ords, valid, TERM_BINS), mask)


@jax.jit
def panel_count(col, missing, live, tcol, tmissing, lo, hi, target):
    with jax.named_scope("aggs.mask"):
        mask = _range_mask(col, missing, live, lo, hi) \
            & (tcol[None, :] == target[:, None]) & ~tmissing[None, :]
    with jax.named_scope("aggs.count"):
        return mask.sum(axis=1, dtype=jnp.int32)


_PROGRAMS = {"hist": instrument("aggs:panel_hist", panel_hist),
             "terms": instrument("aggs:panel_terms", panel_terms),
             "count": instrument("aggs:panel_count", panel_count)}


# ---------------------------------------------------------------------------
# a request as this lane sees it
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PanelRow:
    kind: str                   # hist | terms | count
    field: str                  # the range's column
    lo: int                     # inclusive bounds
    hi: int
    match_field: str | None = None
    match_term: str | None = None
    term_field: str | None = None
    term_value: int | None = None
    agg: AggSpec | None = None
    interval: int = 0           # hist: bucket width in the column's units

    @property
    def shape(self) -> tuple:
        """What rows of one batch must share."""
        return (self.kind, self.field, self.match_field, self.term_field,
                self.agg.params.get("field") if self.agg else None,
                self.interval)


def _is_int(v) -> bool:
    return isinstance(v, (int, np.integer)) and not isinstance(v, bool)


def _inclusive(node: RangeNode):
    if len(node.bounds_per_query) != 1:
        return None
    lo, hi, inc_lo, inc_hi = node.bounds_per_query[0]
    if not (_is_int(lo) and _is_int(hi)):
        return None             # open, fractional or keyword bounds
    lo, hi = int(lo) + (not inc_lo), int(hi) - (not inc_hi)
    if not (_I64.min <= lo <= _I64.max and _I64.min <= hi <= _I64.max):
        return None
    return lo, hi


def row_of(node: Node, aggs: list[AggSpec]) -> PanelRow | None:
    """The parsed query and aggregations of one `size: 0` body as a row of
    this lane, or None where it is not one of the three shapes."""
    match = term = None
    if isinstance(node, RangeNode):
        rng = node
    elif isinstance(node, BoolNode) and not node.should \
            and not node.must_not and node.minimum_should_match is None \
            and len(node.must) <= 1:
        ranges = [n for n in node.filter if isinstance(n, RangeNode)]
        terms = [n for n in node.filter if isinstance(n, TermFilterNode)]
        if len(ranges) != 1 or len(terms) > 1 \
                or len(ranges) + len(terms) != len(node.filter):
            return None
        rng = ranges[0]
        term = terms[0] if terms else None
        match = node.must[0] if node.must else None
    else:
        return None
    bounds = _inclusive(rng)
    if bounds is None or len(aggs) > 1:
        return None
    agg = aggs[0] if aggs else None
    if agg is not None and (agg.subs or agg.pipelines):
        return None
    if match is not None:
        if not isinstance(match, MatchNode) or term is not None \
                or len(match.terms_per_query) != 1 \
                or len(match.terms_per_query[0]) != 1 \
                or match.minimum_should_match > 1 \
                or agg is None or agg.type != "terms" \
                or not set(agg.params) <= {"field", "size"} \
                or "field" not in agg.params:
            return None
        return PanelRow("terms", rng.field_name, *bounds,
                        match_field=match.field_name,
                        match_term=match.terms_per_query[0][0], agg=agg)
    if term is not None:
        vals = term.values_per_query
        if agg is not None or len(vals) != 1 or len(vals[0]) != 1 \
                or not _is_int(vals[0][0]):
            return None
        return PanelRow("count", rng.field_name, *bounds,
                        term_field=term.field_name,
                        term_value=int(vals[0][0]))
    if agg is None or agg.type != "date_histogram" \
            or set(agg.params) != {"field", "interval"} \
            or agg.params["field"] != rng.field_name:
        return None
    interval = _fixed_interval_ms(agg.params["interval"])
    if interval is None or interval < 1 or not float(interval).is_integer():
        return None             # calendar intervals: the host path
    interval = int(interval)
    lo, hi = bounds
    if hi // interval - lo // interval >= HIST_BINS:
        return None             # more buckets than the program's array
    return PanelRow("hist", rng.field_name, lo, hi, agg=agg,
                    interval=interval)


def servable(rows: list[PanelRow], segments: list[Segment]) -> bool:
    """Every row is one shape, and every column it reads is an i64 numeric
    column (or absent) in every segment, with few enough distinct values
    where `terms` counts them."""
    first = rows[0]
    if any(r.shape != first.shape for r in rows[1:]):
        return False
    fields = [first.field]
    if first.term_field:
        fields.append(first.term_field)
    if first.kind == "terms":
        fields.append(first.agg.params["field"])
    for seg in segments:
        for f in fields:
            nc = seg.numerics.get(f)
            if f in seg.keywords or (nc is not None and nc.dtype != "i64"):
                return False
        if first.kind == "terms" and seg.n_docs \
                and _ordinals(seg, first.agg.params["field"]) is None:
            return False
    return True


# ---------------------------------------------------------------------------
# per-segment data this lane derives, cached on the immutable segment
# ---------------------------------------------------------------------------

_DERIVE_LOCK = threading.Lock()


def _ordinals(seg: Segment, field: str):
    """(distinct values i64[V] ascending, ordinals i32[n_pad] on the device,
    -1 = missing) of an i64 column with at most TERM_BINS distinct values;
    None with more. An absent column has no values."""
    cache = seg.__dict__.setdefault("_panel_ordinals", {})
    if field not in cache:
        with _DERIVE_LOCK:
            if field not in cache:
                nc = seg.numerics.get(field)
                if nc is None:
                    values = np.empty(0, np.int64)
                    ords = np.full(seg.n_pad, -1, np.int32)
                else:
                    vals, present = jax.device_get((nc.vals, nc.missing))
                    present = ~present
                    present[seg.n_docs:] = False
                    values = np.unique(vals[present])
                    ords = np.where(present, np.searchsorted(values, vals),
                                    -1).astype(np.int32)
                cache[field] = (values, jax.device_put(ords)) \
                    if len(values) <= TERM_BINS else None
    return cache[field]


def _w_bucket(n: int) -> int:
    return max(_w_buckets(n))


def _w_buckets(max_df: int):
    """The postings-window buckets a postings list of `max_df` calls for."""
    w = W_FLOOR
    while True:
        yield w
        if w >= max_df:
            return
        w *= W_STEP


_CONST_COLS: dict[tuple, jax.Array] = {}


def _const_col(seg: Segment, fill, dtype):
    """A constant column, one per segment-row bucket: what stands in for a
    field the segment lacks (zeros, all missing) and for the warm-up's
    operands."""
    key = (seg.n_pad, fill, np.dtype(dtype).str)
    with _DERIVE_LOCK:
        if key not in _CONST_COLS:
            _CONST_COLS[key] = jax.device_put(
                np.full(seg.n_pad, fill, dtype))
        return _CONST_COLS[key]


def _column(seg: Segment, field: str):
    nc = seg.numerics.get(field)
    if nc is None:
        return _const_col(seg, 0, np.int64), _const_col(seg, True, bool)
    return nc.vals, nc.missing


# ---------------------------------------------------------------------------
# the warm-up
# ---------------------------------------------------------------------------

_WARM: set[tuple] = set()
_WARM_LOCK = threading.Lock()


def _signature(seg: Segment) -> tuple:
    """What of a segment shapes this lane's programs: its row bucket and,
    for each text field, the padded postings length and the window buckets
    its longest postings list calls for."""
    return (seg.n_pad, tuple(sorted(
        (int(fx.doc_ids.shape[0]), _w_bucket(max(fx.max_df, 1)))
        for fx in seg.text.values())))


def _members(seg: Segment):
    """The members of the closed set that one segment calls for, as
    (shape, Q bucket, text field index or None, window or None)."""
    for q in Q_BUCKETS:
        yield "hist", q, None, None
        yield "count", q, None, None
        for fx in seg.text.values():
            for w in _w_buckets(max(fx.max_df, 1)):
                yield "terms", q, fx, w


def program_set(segments: list[Segment]) -> list[tuple]:
    """The closed set for these segments, as (shape, Q bucket, segment-row
    bucket[, postings length, window]): what `ensure_warm` compiles."""
    return sorted({
        (kind, q, seg.n_pad) + ((int(fx.doc_ids.shape[0]), w) if fx else ())
        for seg in segments if seg.n_docs
        for kind, q, fx, w in _members(seg)})


def warm_segment(seg: Segment) -> None:
    """Run every member of the set that this segment calls for once, on
    zero operands (a range that selects nothing, empty postings slices)."""
    col, missing = _column(seg, "")         # no such field: zeros, missing
    ords = _const_col(seg, 0, np.int32)
    for kind, q, fx, w in _members(seg):
        lo = hi = _put(np.zeros(q, np.int64))
        if kind == "hist":
            _PROGRAMS[kind](col, missing, seg.live, lo, hi, _put(np.int64(1)))
        elif kind == "count":
            _PROGRAMS[kind](col, missing, seg.live, col, missing, lo, hi, lo)
        else:
            _PROGRAMS[kind](col, missing, seg.live, fx.doc_ids, ords, lo, hi,
                            _put(np.zeros((2, q), np.int32)), W=w)


def ensure_warm(segments: list[Segment]) -> None:
    """Warm the set for every segment shape not met before. Cheap when
    there is none: one tuple a segment. Searches that arrive while another
    warms wait here and compile nothing themselves."""
    todo = [seg for seg in segments
            if seg.n_docs and _signature(seg) not in _WARM]
    if not todo:
        return
    with _WARM_LOCK, tracing.span("aggs.warmup", segments=len(todo)):
        for seg in todo:
            sig = _signature(seg)
            if sig not in _WARM:
                warm_segment(seg)
                _WARM.add(sig)


def _put(a):
    return jax.device_put(np.asarray(a))


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def _q_bucket(n: int) -> int:
    return next(q for q in Q_BUCKETS if q >= n)


def execute(rows: list[PanelRow], shards: list[list[Segment]]):
    """One batch of at most 32 rows of one shape over every segment of
    every shard; -> (totals i64[Q, n_shards], per row the list of per-shard
    aggregation partials, or None for `count`). Exact."""
    Q, first = len(rows), rows[0]
    kind = first.kind
    Q_pad = _q_bucket(Q)
    program = _PROGRAMS[kind]
    plan = tracing.span("aggs.plan", shape=kind, rows=Q)
    with plan:
        lo = np.full(Q_pad, _I64.max, np.int64)     # padded rows: lo > hi
        hi = np.full(Q_pad, _I64.min, np.int64)
        lo[:Q] = [r.lo for r in rows]
        hi[:Q] = [r.hi for r in rows]
        host = [lo, hi]
        if kind == "hist":
            host.append(np.int64(first.interval))
        elif kind == "count":
            target = np.zeros(Q_pad, np.int64)
            target[:Q] = [r.term_value for r in rows]
            host.append(target)
        calls = []                 # (shard, segment, postings window)
        for si, segments in enumerate(shards):
            for seg in segments:
                if not seg.n_docs:
                    continue
                W = None
                if kind == "terms":
                    fx = seg.text.get(first.match_field)
                    if fx is None:
                        continue   # no posting of the field: no match
                    slices = np.zeros((2, Q_pad), np.int32)
                    for qi, r in enumerate(rows):
                        slices[0, qi], slices[1, qi], _ = \
                            fx.lookup(r.match_term)
                    if not slices[1].any():
                        continue   # none of the batch's terms is here
                    W = _w_bucket(int(slices[1].max()))
                    host.append(slices)
                calls.append((si, seg, W))
        dev = [_put(a) for a in host]
        plan.attrs["h2d_bytes"] = sum(a.nbytes for a in host)
        tracing.note_h2d(plan.attrs["h2d_bytes"])
    outs = []
    for i, (si, seg, W) in enumerate(calls):
        col, missing = _column(seg, first.field)
        if kind == "hist":
            out = program(col, missing, seg.live, dev[0], dev[1], dev[2])
        elif kind == "count":
            tcol, tmissing = _column(seg, first.term_field)
            out = program(col, missing, seg.live, tcol, tmissing,
                          dev[0], dev[1], dev[2])
        else:                      # dev[2:] are the calls' postings slices
            _, ords = _ordinals(seg, first.agg.params["field"])
            out = program(col, missing, seg.live,
                          seg.text[first.match_field].doc_ids, ords,
                          dev[0], dev[1], dev[2 + i], W=W)
        outs.append(out)

    with tracing.span("aggs.reduce", programs=len(calls)):
        host_outs = device_fetch(outs) if outs else []
        totals = np.zeros((Q, len(shards)), np.int64)
        bins = {"hist": HIST_BINS, "terms": TERM_BINS}.get(kind)
        # hist: residues add up across segments and shards before any key is
        # made; terms: ordinals are the segment's own, so values add up per
        # shard (the shard-size truncation is per shard)
        hist = np.zeros((Q, HIST_BINS), np.int64)
        by_shard: list[list[dict]] = [[{} for _ in shards] for _ in rows]
        for (si, seg, _), arr in zip(calls, host_outs):
            arr = np.asarray(arr)[:Q]
            if kind == "count":
                totals[:, si] += arr
                continue
            totals[:, si] += arr[:, bins]
            if kind == "hist":
                hist += arr[:, :bins]
                continue
            values, _ = _ordinals(seg, first.agg.params["field"])
            for qi, o in zip(*np.nonzero(arr[:, :len(values)])):
                d = by_shard[qi][si]
                key = int(values[o])
                d[key] = d.get(key, 0) + int(arr[qi, o])
        if kind == "count":
            return totals, None
        partials = []
        for qi, r in enumerate(rows):
            if kind == "hist":
                base = r.lo // r.interval       # the row's first bucket
                partials.append([{r.agg.name: {"buckets": {
                    float((base + (int(res) - base) % HIST_BINS)
                          * r.interval): {"doc_count": int(hist[qi, res])}
                    for res in np.nonzero(hist[qi])[0]}}}])
            else:
                partials.append([
                    {r.agg.name: terms_partial_from_counts(r.agg, counts)}
                    for counts in by_shard[qi]])
        return totals, partials
