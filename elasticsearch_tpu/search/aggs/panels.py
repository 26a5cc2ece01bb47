"""Dashboard panels: `size: 0` filter + one leaf aggregation, answered from a
closed, warmed set of compiled programs, one collective program a batch.

A dashboard over log events asks the same three questions with a fresh time
range every time (rally-tracks `http_logs`: `hourly_agg`, `200s-in-range`,
`term`), with the request cache off. The general lanes evaluate such a body
as eager `jnp` operations shaped by the exact batch size and by whatever the
body holds, so a window of fresh ranges compiles for ever. This lane accepts
exactly three plan shapes:

  hist    range(i64 column)                     -> date_histogram(same column,
                                                   fixed interval)
  terms   match(one term) + range(i64 column)   -> terms(i64 column)
  count   range(i64 column) + term(i64 column)  -> no aggregation (hits.total)

Anything else (another clause, another aggregation or parameter, an open or
a too-wide range, an f64 column, sub-aggregations) is not this lane's:
`row_of` returns None and the caller keeps the path it had.

**The layout.** A node owns N >= 1 chips (its `DevicePool`; no setting says
how many). Every shard has a home chip, shard number mod N (5 shards over 4
chips: 0, 1, 2, 3, 0), and `PanelView` keeps on each chip the lane's
operands for the segments whose shard lives there: the i64 columns the lane
has served with their `missing`, `live`, the `terms` column's ordinals, the
matched field's postings `doc_ids`; stacked `[G, n_pad]` a chip (G: the
segments-a-chip bucket; `n_pad`: the largest segment's row bucket; a padded
row or segment is all dead and counts nothing) and laid out as one
`[N * G, n_pad]` array with a `NamedSharding` over the chip axis. The view
is built when the segments change, not in a request's path once warm, and
is a second copy of those columns (`Segment`'s own tensors stay on the
default device for every other lane), charged to the fielddata breaker and
dropped with the index.

**One program a batch.** Each shape is ONE jitted `shard_map` program over
the chip axis: a chip runs the per-segment body over its own stacked
segments, and the integer counts are summed across chips on the chips
(`psum`, named scope `aggs.allreduce`); the host downloads one array. A node
with one chip runs the same program over an axis of one. The collective is
dispatched under the pool's dispatch lock (`mesh_exec.exec_guard`: two
collective programs interleaved on the same chips can deadlock).

**The closed set.** A program is keyed by what the index fixes and by
buckets, never by what a request holds:

  programs = 3 shapes x Q buckets {1, 4, 32} x (row bucket, segments-a-chip
             bucket) [x postings-window buckets {2^10, 2^13, 2^16, ...} up
             to the longest postings list, for `terms` only; the padded
             postings length rides along]

Range bounds, the term's value, the matched term's postings slices (start,
length a segment) and the histogram's interval are operands, built once a
batch. The histogram's bucket array is `HIST_BINS` = 256 wide (7 days of
hours is 169): a document's bucket is its absolute bucket number modulo 256,
which is the same for every row of the batch, so one one-hot matmul counts
all rows; a row whose range spans at most 256 buckets meets each residue
once, and the host puts the residues back in order from the row's own lower
bound. `terms` counts the index's ordinals of the column's distinct values
(`TERM_BINS` = 32 at most, over all segments), so counts add across a
shard's segments on the chip and come back a shard: the shard-size
truncation stays a shard's.

`ensure_warm` runs every member of the set that the view calls for once on
operands that select nothing, the first time a search meets a view of a
shape it has not seen; after it no time range, term, status, batch size or
refresh inside the warmed buckets compiles anything. Exactness is untouched:
64-bit columns stay 64-bit on the device, totals and bucket counts are
integer sums, the per-shard `terms` truncation and the render are the
aggregation framework's own (`terms_partial_from_counts`,
`merge_shard_partials`, `render`).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...common import tracing
from ...common.breaker import CircuitBreakingException
from ...common.device_stats import instrument
from ...common.metrics import device_fetch, note_h2d
from ...index.segment import Segment
from ...ops.aggs import _onehot_counts
from ...parallel.mesh import CHIP_AXIS, DevicePool
from ...parallel.mesh_exec import exec_guard
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
# the programs: a segment's body, then one shard_map program a shape
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


def _hist_body(col, missing, live, lo, hi, interval):
    with jax.named_scope("aggs.mask"):
        mask = _range_mask(col, missing, live, lo, hi)
    with jax.named_scope("aggs.bin"):
        # exact i64 floor division by a runtime operand (ops/aggs.hist_bins
        # says why never a constant); the residue is row-independent
        ids = ((col // interval) & (HIST_BINS - 1)).astype(jnp.int32)
    with jax.named_scope("aggs.count"):
        return _pack(_onehot_counts(ids, mask, HIST_BINS), mask)


def _terms_body(col, missing, live, doc_ids, ords, lo, hi, starts, lens, *,
                W: int):
    Q, N = lo.shape[0], col.shape[0]
    with jax.named_scope("aggs.mask"):
        mask = _range_mask(col, missing, live, lo, hi)
        offs = jnp.arange(W, dtype=jnp.int32)[None, :]
        idx = jnp.clip(starts[:, None] + offs, 0, doc_ids.shape[0] - 1)
        doc = jnp.where(offs < lens[:, None], doc_ids[idx], N)
        rows = jnp.arange(Q, dtype=jnp.int32)[:, None]
        hit = jnp.zeros((Q, N), jnp.int32).at[rows, doc].add(1, mode="drop")
        mask = mask & (hit > 0)
    with jax.named_scope("aggs.count"):
        valid = mask & (ords >= 0)[None, :]
        return _pack(_onehot_counts(ords, valid, TERM_BINS), mask)


def _count_body(col, missing, live, tcol, tmissing, lo, hi, target):
    with jax.named_scope("aggs.mask"):
        mask = _range_mask(col, missing, live, lo, hi) \
            & (tcol[None, :] == target[:, None]) & ~tmissing[None, :]
    with jax.named_scope("aggs.count"):
        return mask.sum(axis=1, dtype=jnp.int32)


def _allreduce(per_segment):
    """A chip's segments summed, then the chips: i32, exact."""
    mine = per_segment.sum(axis=0, dtype=jnp.int32)
    with jax.named_scope("aggs.allreduce"):
        return jax.lax.psum(mine, CHIP_AXIS)


def _build_program(kind: str, mesh, n_shards: int):
    """The jitted program of one shape over `mesh`'s chip axis. Stacked
    operands `[N * G, ...]` are split a chip (`seg`); a request's operands
    are replicated (`rep`): `bounds` i64[3, Q] is lo, hi and, by shape, the
    interval or the term's value. `terms` takes each segment's postings
    slices `[2, N * G, Q]`, a chip its own."""
    seg, rep = P(CHIP_AXIS), P()

    def over_chips(chip, in_specs):
        # check_vma off, as the other mesh programs: the bodies are shared
        # with one-chip callers (`_onehot_counts`' scan starts from a
        # constant carry); every output leaves through `_allreduce`
        return jax.shard_map(chip, mesh=mesh, in_specs=in_specs,
                             out_specs=rep, check_vma=False)

    if kind == "hist":
        def chip(col, missing, live, bounds):
            return _allreduce(jax.vmap(
                _hist_body, (0, 0, 0, None, None, None))(
                    col, missing, live, bounds[0], bounds[1], bounds[2, 0]))

        def panel_hist(col, missing, live, bounds):
            return over_chips(chip, (seg,) * 3 + (rep,))(
                col, missing, live, bounds)
        return jax.jit(panel_hist)

    if kind == "count":
        def chip(col, missing, live, tcol, tmissing, bounds):
            return _allreduce(jax.vmap(
                _count_body, (0, 0, 0, 0, 0, None, None, None))(
                    col, missing, live, tcol, tmissing,
                    bounds[0], bounds[1], bounds[2]))

        def panel_count(col, missing, live, tcol, tmissing, bounds):
            return over_chips(chip, (seg,) * 5 + (rep,))(
                col, missing, live, tcol, tmissing, bounds)
        return jax.jit(panel_count)

    def panel_terms(col, missing, live, doc_ids, ords, shard_of, bounds,
                    slices, *, W: int):
        def chip(col, missing, live, doc_ids, ords, shard_of, bounds,
                 slices):
            out = jax.vmap(partial(_terms_body, W=W),
                           (0, 0, 0, 0, 0, None, None, 0, 0))(
                col, missing, live, doc_ids, ords, bounds[0], bounds[1],
                slices[0], slices[1])
            # [G, Q, bins + 1] -> [S, Q, bins + 1]: a shard's segments live
            # on one chip, so the sum over the chips is the gather
            mine = shard_of[:, None] == jnp.arange(n_shards)[None, :]
            return _allreduce(
                jnp.where(mine[:, :, None, None], out[:, None], 0))
        return over_chips(chip, (seg,) * 6 + (rep, P(None, CHIP_AXIS)))(
            col, missing, live, doc_ids, ords, shard_of, bounds, slices)
    return jax.jit(panel_terms, static_argnames=("W",))


_PROGRAMS: dict[tuple, object] = {}
_PROGRAMS_LOCK = threading.Lock()


def _program(kind: str, view: "PanelView"):
    key = (kind, view.pool.devkey, view.n_shards if kind == "terms" else 0)
    with _PROGRAMS_LOCK:
        if key not in _PROGRAMS:
            _PROGRAMS[key] = instrument(
                f"aggs:panel_{kind}",
                _build_program(kind, view.mesh, view.n_shards), key=key[1:])
        return _PROGRAMS[key]


def _run(view: "PanelView", kind: str, args: tuple, W: int | None):
    """One dispatch of the shape's program under the pool's dispatch lock;
    -> its output, on the device."""
    kw = {} if W is None else {"W": W}
    with exec_guard(view.pool), tracing.program_attrs(
            chips=view.n_chips, segments=len(view.segments)):
        return _program(kind, view)(*args, **kw)


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


def servable(rows: list[PanelRow], view: "PanelView") -> bool:
    """Every row is one shape, and every column it reads is an i64 numeric
    column (or absent) in every segment, with few enough distinct values
    over the index where `terms` counts them; and the fielddata breaker let
    the view's copy of them in."""
    first = rows[0]
    if any(r.shape != first.shape for r in rows[1:]):
        return False
    fields = [first.field]
    if first.term_field:
        fields.append(first.term_field)
    if first.kind == "terms":
        fields.append(first.agg.params["field"])
    for seg in view.segments:
        for f in fields:
            nc = seg.numerics.get(f)
            if f in seg.keywords or (nc is not None and nc.dtype != "i64"):
                return False
    try:
        return view.operands(first) is not None
    except CircuitBreakingException:
        return False


# ---------------------------------------------------------------------------
# the view: the lane's operands, a chip
# ---------------------------------------------------------------------------

def _g_bucket(n: int) -> int:
    """The segments-a-chip bucket: 1, 2, 3, 4, 6, 8, 12, 16, 24, ..."""
    g = 1
    while g < n:
        g = g * 4 // 3 if g % 3 == 0 else max(g * 3 // 2, g + 1)
    return g


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


_DERIVE_LOCK = threading.Lock()


def _distinct(seg: Segment, field: str) -> np.ndarray:
    """The distinct values i64[V] of a segment's i64 column, ascending;
    cached on the immutable segment (TERM_BINS + 1 of them at most: more
    are not the lane's, whatever they are)."""
    cache = seg.__dict__.setdefault("_panel_distinct", {})
    if field not in cache:
        with _DERIVE_LOCK:
            if field not in cache:
                nc = seg.numerics.get(field)
                if nc is None:
                    cache[field] = np.empty(0, np.int64)
                else:
                    vals, missing = jax.device_get((nc.vals, nc.missing))
                    cache[field] = np.unique(
                        vals[:seg.n_docs][~missing[:seg.n_docs]]
                    )[:TERM_BINS + 1]
    return cache[field]


class PanelView:
    """The lane's operands for one index's segments as they stand, on the
    chips of `pool` (the module's note on the layout). An operand is placed
    the first time a row reads it (span `aggs.place`; the warm-up's pilots,
    as a rule) and, for everything the view before it (`base`) had placed,
    when a change of segments builds this one: a chip whose segments and
    buckets did not change keeps its block. `live` follows the segments'
    tombstones."""

    def __init__(self, shards: list[list[Segment]], pool: DevicePool,
                 breaker=None, base: "PanelView | None" = None):
        self.pool, self.mesh, self.breaker = pool, pool.chip_mesh(), breaker
        self.n_chips, self.n_shards = len(pool.devices), len(shards)
        self.rows: list[list[tuple[int, Segment]]] = [
            [] for _ in pool.devices]
        for si, segments in enumerate(shards):
            self.rows[pool.home_of(si)] += [
                (si, seg) for seg in segments if seg.n_docs]
        self.segments = [seg for rows in self.rows for _, seg in rows]
        self.G = _g_bucket(max(len(rows) for rows in self.rows))
        self.n_pad = max([seg.n_pad for seg in self.segments] or [8])
        self.by_chip = NamedSharding(self.mesh, P(CHIP_AXIS))
        self.by_chip_1 = NamedSharding(self.mesh, P(None, CHIP_AXIS))
        self.replicated = NamedSharding(self.mesh, P())
        self.nbytes = 0
        self._lock = threading.RLock()
        self._placed: dict[tuple, jax.Array] = {}
        self._values: dict[str, np.ndarray | None] = {}
        self._live_key = None
        # a chip's blocks are the last view's where its segments and the
        # buckets are: {operand: [the chip's block, or None]}
        self._inherit: dict[tuple, list] = {}
        if base is not None and base.pool is pool \
                and (base.G, base.n_pad) == (self.G, self.n_pad):
            same = [[id(seg) for _, seg in mine] ==
                    [id(seg) for _, seg in theirs]
                    for mine, theirs in zip(self.rows, base.rows)]
            self._inherit = {
                key: [block if same[c] else None
                      for c, block in enumerate(self._blocks(arr))]
                for key, arr in base._placed.items() if key != ("live",)}
            self._inherit.update(
                (("values", f), v) for f, v in base._values.items())
        shard_of = np.full((self.n_chips, self.G), -1, np.int32)
        for c, rows in enumerate(self.rows):
            shard_of[c, :len(rows)] = [si for si, _ in rows]
        self.shard_of = jax.device_put(shard_of.reshape(-1), self.by_chip)
        try:
            for key in (base._placed if base is not None else ()):
                if key[0] in ("column", "ordinals", "postings"):
                    getattr(self, key[0])(key[1])
        except BaseException:
            self.release()
            raise
        finally:
            self._inherit = {}      # the view before this one may go

    # -- placement -------------------------------------------------------

    def _blocks(self, arr: jax.Array) -> list:
        """A stacked operand's blocks, in the order of the chips."""
        by_device = {s.device: s.data for s in arr.addressable_shards}
        return [by_device[dev] for dev in self.pool.devices]

    def _place(self, key: tuple, width: int, dtype, fill, row_of,
               keep: list | None = None) -> jax.Array:
        """The stacked operand `key` `[N * G, width]`, placed once:
        `row_of(seg)` gives a segment's row on the host (None: all `fill`),
        padded to `width`; a chip with a block to keep (`keep`, or the view
        before this one's) uploads nothing."""
        with self._lock:
            if key in self._placed and keep is None:
                return self._placed[key]
            block_shape = (self.G, width)
            keep = keep or self._inherit.pop(key, None) \
                or [None] * self.n_chips
            place = tracing.span("aggs.place", chips=0, segments=0, bytes=0)
            with place:
                parts = []
                for c, dev in enumerate(self.pool.devices):
                    if keep[c] is not None and keep[c].shape == block_shape:
                        parts.append(keep[c])
                        continue
                    block = np.full(block_shape, fill, dtype)
                    for g, (_, seg) in enumerate(self.rows[c]):
                        row = row_of(seg)
                        if row is not None:
                            block[g, :len(row)] = row
                    parts.append(jax.device_put(block, dev))
                    place.attrs["chips"] += 1
                    place.attrs["segments"] += len(self.rows[c])
                    place.attrs["bytes"] += block.nbytes
                note_h2d(place.attrs["bytes"])
                arr = jax.make_array_from_single_device_arrays(
                    (self.n_chips * self.G, width), self.by_chip, parts)
            if key not in self._placed:
                if self.breaker is not None:
                    self.breaker.add_estimate(arr.nbytes)
                self.nbytes += arr.nbytes
            self._placed[key] = arr
            return arr

    def release(self) -> None:
        """The view leaves its index's cache: its charge goes back."""
        with self._lock:
            if self.breaker is not None:
                self.breaker.release(self.nbytes)
            self.nbytes = 0

    # -- the operands ------------------------------------------------------

    def column(self, field: str):
        """(vals i64, missing bool) `[N * G, n_pad]`; a segment without
        the field reads zeros, all missing."""
        def part(name):
            def row_of(seg):
                nc = seg.numerics.get(field)
                return None if nc is None else \
                    jax.device_get(getattr(nc, name))
            return row_of
        return (self._place(("column", field), self.n_pad, np.int64, 0,
                            part("vals")),
                self._place(("missing", field), self.n_pad, bool, True,
                            part("missing")))

    def ordinals(self, field: str):
        """(the column's distinct values over all segments i64[V]
        ascending, their ordinals i32 `[N * G, n_pad]`, -1 = missing);
        None with more than TERM_BINS values."""
        with self._lock:
            if field not in self._values:
                values = np.unique(np.concatenate(
                    [_distinct(seg, field) for seg in self.segments]
                    + [np.empty(0, np.int64)]))
                self._values[field] = values \
                    if len(values) <= TERM_BINS else None
                # an ordinal is the index's: a block of the view before
                # is this view's only where the values are the same
                old = self._inherit.get(("values", field))
                if old is None or not np.array_equal(old, values):
                    self._inherit.pop(("ordinals", field), None)
            values = self._values[field]
            if values is None:
                return None

            def row_of(seg):
                nc = seg.numerics.get(field)
                if nc is None:
                    return None
                vals, missing = jax.device_get((nc.vals, nc.missing))
                return np.where(missing, -1, np.searchsorted(values, vals))
            return values, self._place(("ordinals", field), self.n_pad,
                                       np.int32, -1, row_of)

    def postings(self, field: str) -> jax.Array:
        """The text field's postings doc ids i32 `[N * G, P_pad]`."""
        def row_of(seg):
            fx = seg.text.get(field)
            if fx is None:
                return None
            return fx.doc_ids_host[:fx.n_postings] \
                if fx.doc_ids_host is not None \
                else jax.device_get(fx.doc_ids)
        return self._place(("postings", field), self.p_pad(field), np.int32,
                           0, row_of)

    def p_pad(self, field: str) -> int:
        return max([int(seg.text[field].doc_ids.shape[0])
                    for seg in self.segments if field in seg.text] or [8])

    def max_df(self, field: str) -> int:
        return max([seg.text[field].max_df for seg in self.segments
                    if field in seg.text] or [1])

    def live(self) -> jax.Array:
        """bool `[N * G, n_pad]`: root documents not deleted; a padded row
        and a padded segment are all dead. A chip whose segments met no
        new tombstone keeps its block."""
        with self._lock:
            key = [tuple(seg.live_gen for _, seg in rows)
                   for rows in self.rows]
            if key != self._live_key:
                keep = [None] * self.n_chips if self._live_key is None else [
                    block if self._live_key[c] == key[c] else None
                    for c, block in enumerate(
                        self._blocks(self._placed[("live",)]))]
                self._place(("live",), self.n_pad, bool, False,
                            lambda seg: seg.root_live_host, keep=keep)
                self._live_key = key
            return self._placed[("live",)]

    def operands(self, row: PanelRow) -> tuple | None:
        """The stacked operands of `row`'s shape, in its program's order;
        None where `terms` meets more values than it counts."""
        col, missing = self.column(row.field)
        if row.kind == "hist":
            return col, missing, self.live()
        if row.kind == "count":
            return (col, missing, self.live()) + self.column(row.term_field)
        ords = self.ordinals(row.agg.params["field"])
        return None if ords is None else (
            col, missing, self.live(), self.postings(row.match_field),
            ords[1], self.shard_of)

    def slices(self, field: str, terms: list[str], Q_pad: int) -> np.ndarray:
        """i32[2, N * G, Q_pad]: each term's postings (start, length) in
        each segment, as the stack has the segments."""
        out = np.zeros((2, self.n_chips * self.G, Q_pad), np.int32)
        for c, rows in enumerate(self.rows):
            for g, (_, seg) in enumerate(rows):
                fx = seg.text.get(field)
                if fx is not None:
                    at = c * self.G + g
                    for qi, term in enumerate(terms):
                        out[0, at, qi], out[1, at, qi], _ = fx.lookup(term)
        return out

    # -- the closed set ---------------------------------------------------

    def text_fields(self) -> list[str]:
        return sorted({f for seg in self.segments for f in seg.text})

    def signature(self) -> tuple:
        """What of the view shapes this lane's programs."""
        return (self.pool.devkey, self.n_shards, self.G, self.n_pad,
                tuple((f, self.p_pad(f), _w_bucket(self.max_df(f)))
                      for f in self.text_fields()))

    def members(self):
        """The members of the closed set that this view calls for, as
        (shape, Q bucket, text field or None, window or None)."""
        for q in Q_BUCKETS:
            yield "hist", q, None, None
            yield "count", q, None, None
            for f in self.text_fields():
                for w in _w_buckets(self.max_df(f)):
                    yield "terms", q, f, w


def program_set(view: PanelView) -> list[tuple]:
    """The closed set for this view, as (shape, Q bucket, row bucket,
    segments-a-chip bucket[, postings length, window]): what `ensure_warm`
    compiles."""
    return sorted({
        (kind, q, view.n_pad, view.G) + ((view.p_pad(f), w) if f else ())
        for kind, q, f, w in view.members()})


# ---------------------------------------------------------------------------
# the warm-up
# ---------------------------------------------------------------------------

_WARM: set[tuple] = set()
_WARM_LOCK = threading.Lock()


def warm_view(view: PanelView) -> None:
    """Run every member of the set that this view calls for once, on
    operands that select nothing (lo > hi, empty postings slices). The
    stand-in columns are the warm-up's own and go with it."""
    rows = view.n_chips * view.G
    col, missing, ords = jax.device_put(
        (np.zeros((rows, view.n_pad), np.int64),
         np.ones((rows, view.n_pad), bool),
         np.zeros((rows, view.n_pad), np.int32)), view.by_chip)
    live = view.live()
    for kind, q, f, w in view.members():
        bounds = np.zeros((3, q), np.int64)
        bounds[0] = 1
        bounds = jax.device_put(bounds, view.replicated)
        if kind == "hist":
            args = (col, missing, live, bounds)
        elif kind == "count":
            args = (col, missing, live, col, missing, bounds)
        else:
            args = (col, missing, live, view.postings(f), ords,
                    view.shard_of, bounds, jax.device_put(
                        np.zeros((2, rows, q), np.int32), view.by_chip_1))
        _run(view, kind, args, w)


def ensure_warm(view: PanelView) -> None:
    """Warm the set for a view of a shape not met before. Cheap when it
    was: one tuple. Searches that arrive while another warms wait here and
    compile nothing themselves."""
    sig = view.signature()
    if sig in _WARM:
        return
    with _WARM_LOCK, tracing.span("aggs.warmup",
                                  segments=len(view.segments)):
        if sig not in _WARM:
            warm_view(view)
            _WARM.add(sig)


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def _q_bucket(n: int) -> int:
    return next(q for q in Q_BUCKETS if q >= n)


def execute(rows: list[PanelRow], view: PanelView):
    """One batch of at most 32 rows of one shape over every segment of
    every shard, as one program and one download; -> (totals i64[Q], per
    row the list of per-shard aggregation partials, or None for `count`).
    Exact."""
    Q, first = len(rows), rows[0]
    kind = first.kind
    Q_pad = _q_bucket(Q)
    operands = view.operands(first)
    plan = tracing.span("aggs.plan", shape=kind, rows=Q, cpu=True)
    with plan:
        bounds = np.zeros((3, Q_pad), np.int64)
        bounds[0] = _I64.max                        # padded rows: lo > hi
        bounds[1] = _I64.min
        bounds[0, :Q] = [r.lo for r in rows]
        bounds[1, :Q] = [r.hi for r in rows]
        host, how, W = [bounds], [view.replicated], None
        if kind == "hist":
            bounds[2] = first.interval
        elif kind == "count":
            bounds[2, :Q] = [r.term_value for r in rows]
        else:
            slices = view.slices(first.match_field,
                                 [r.match_term for r in rows], Q_pad)
            W = _w_bucket(int(slices[1].max()))
            host.append(slices)
            how.append(view.by_chip_1)
        dev = jax.device_put(host, how)
        plan.attrs["h2d_bytes"] = sum(a.nbytes for a in host)
        note_h2d(plan.attrs["h2d_bytes"])
    out = _run(view, kind, operands + tuple(dev), W)

    with tracing.span("aggs.reduce", programs=1):
        arr = np.asarray(device_fetch(out)).astype(np.int64)
        if kind == "count":
            return arr[:Q], None
        if kind == "hist":
            hist, totals = arr[:Q, :HIST_BINS], arr[:Q, HIST_BINS]
        else:                       # [S, Q_pad, bins + 1], a shard's own
            totals = arr[:, :Q, TERM_BINS].sum(axis=0)
            values, _ = view.ordinals(first.agg.params["field"])
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
                    {r.agg.name: terms_partial_from_counts(r.agg, {
                        int(values[o]): int(arr[si, qi, o])
                        for o in np.nonzero(arr[si, qi, :len(values)])[0]})}
                    for si in range(view.n_shards)])
        return totals, partials
