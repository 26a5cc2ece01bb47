"""Aggregation partials inside the mesh program (ISSUE 11 tentpole (b)).

Until now agg bodies declined the mesh lane: the coordinator fell back to
the per-shard fan-out, paid S device fetches and merged host-side wire
partials — exactly the flat-vs-linear reduce the device wins (ROADMAP
item 1). This module plans the SUPPORTED slice of the agg tree into device
closures that run inside the shard_map body of parallel/mesh_exec.py,
right after the query mask is computed:

    m = match & live                 # [G, Q, N] — the same mask the
                                     # per-shard collect gates on
    counts  = one-hot / affine-bucket contractions over m (exact ints)
    metrics = fused (count, sum, sum_sq, min, max) rows per segment

and `all_gather`s the per-shard partial tensors over the "shard" axis so
they ride the SAME single device fetch as the top-k reduce. Count tensors
are exact integers, so summing them on device (or host) reproduces the
per-shard dict merge bit-for-bit; f64 metric rows stay per-SEGMENT in the
gathered output and merge on host in segment order — float addition is
not associative, and the fan-out merges in exactly that order.

Supported: terms (keyword field), histogram / date_histogram (numeric,
fixed interval), range (non-date), and the metric family min / max / sum /
avg / value_count / stats / extended_stats (numeric). Sub-aggregation
TREES (ISSUE 17 tentpole (b)) flatten into composite bins on device:
a `date_histogram -> terms -> avg` tree becomes one per-doc composite
bin id (`parent_bin * child_bins + child_bin`), one exact-int bincount
per (segment, level) and one fused 5-vector stats row per (segment,
composite bin, metric leaf) — `finish` rebuilds the per-shard nested
partial dicts with the host collect's own truncation/merge code, so the
wire partials stay bit-identical to the fan-out. Trees that cannot be
reproduced bitwise decline with a stable reason (`calendar_interval`,
`float_histogram`, `subagg_bins`, `unsupported_child`) and the caller
falls down the existing ladder (mesh -> fan-out -> per-segment loop).
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

# operand placement kinds — mirrors mesh_exec's _OP_S/_OP_Q/_OP_R values
# (imported lazily there; literals here avoid a circular module import)
_OP_S = "s"
_OP_Q = "q"
_OP_R = "r"

# bin caps: past these the per-shard fan-out's own device/host ladder is
# the better place to be (and the fan-out is what we decline to)
_MAX_TERMS_BINS = 1 << 12
_MAX_HIST_BINS = 1 << 14          # aggregators._MAX_DEVICE_BINS

_METRIC_TYPES = {"min", "max", "sum", "avg", "value_count", "stats",
                 "extended_stats"}


class AggMeshPlan:
    """One planned agg tree: `devfns` run inside the shard_map body (each
    returns a [Qb, ...] tensor that the program all_gathers to [S, Qb,
    ...]), `finish(outs, q_row)` turns the fetched host arrays back into
    per-shard partial dicts — the exact wire shapes the fan-out's
    `collect_shard` produces."""

    def __init__(self, specs, devfns, finishers, sig):
        self.specs = specs
        self.devfns = devfns          # list[callable(d, m) -> tensor]
        self.finishers = finishers    # list[callable(np_out, q) -> [dict]]
        self.sig = sig                # static program-key component

    def device_fns(self):
        """The closures that actually run on device (absent-field specs
        have none — their partials are constant)."""
        return [fn for fn in self.devfns if fn is not None]

    def finish(self, outs, s_count: int, q_row: int = 0) -> list[dict]:
        """outs: fetched np arrays aligned with device_fns() -> one partial
        dict per shard (index-aligned with the stack's shard rows)."""
        per_shard: list[dict] = [{} for _ in range(s_count)]
        it = iter(outs)
        for spec, dev, fin in zip(self.specs, self.devfns, self.finishers):
            out = next(it) if dev is not None else None
            parts = fin(out, q_row)
            for si in range(s_count):
                per_shard[si][spec.name] = parts[si]
        return per_shard


def _supported_type(spec) -> bool:
    return spec.type in ({"terms", "histogram", "date_histogram", "range"}
                         | _METRIC_TYPES)


def plan_aggs(specs, pctx) -> AggMeshPlan | None:
    """Plan the agg list against a mesh _PlanCtx (parallel/mesh_exec). The
    plan emits its operands through `pctx` AFTER the query tree has been
    planned, so the device op iterator pops query ops first, agg ops
    second. None = some spec has no mesh form -> the whole query falls
    back to the fan-out."""
    if not specs:
        return None
    devfns, finishers, sigs = [], [], []
    for spec in specs:
        if not spec.subs and not _supported_type(spec):
            if spec.type == "composite":
                # composite paginates over the GLOBALLY merged bucket
                # space — a per-shard device tensor cannot carry the
                # after-key cursor, so the fan-out (whose host collect
                # factorizes key tuples per segment) is the documented
                # lane; named decline for the explain surface
                from ..common.device_stats import lane_decline
                lane_decline("coordinator.aggs", "mesh", "composite")
            return None
        try:
            if spec.subs:
                planned = _plan_subagg_tree(spec, pctx)
            elif spec.type == "terms":
                planned = _plan_terms(spec, pctx)
            elif spec.type in ("histogram", "date_histogram"):
                planned = _plan_histogram(spec, pctx)
            elif spec.type == "range":
                planned = _plan_range(spec, pctx)
            else:
                planned = _plan_metric(spec, pctx)
        except _Unsupported as e:
            if spec.subs:
                # stable decline reasons for the lane-explain surface —
                # the fan-out remains the documented fallback
                from ..common.device_stats import lane_decline
                lane_decline("coordinator.aggs", "mesh", e.reason)
            return None
        sig, dev, fin = planned
        sigs.append(sig)
        devfns.append(dev)
        finishers.append(fin)
    return AggMeshPlan(specs, devfns, finishers, tuple(sigs))


class _Unsupported(Exception):
    def __init__(self, msg: str = "", reason: str = "agg_shape"):
        super().__init__(msg)
        self.reason = reason


def _empty_terms():
    return {"buckets": {}, "other_doc_count": 0, "error_bound": 0}


def _plan_terms(spec, pctx):
    """terms on a keyword field: per-(shard, segment) ordinals remap onto a
    GLOBAL vocabulary (the host-built [S, G, Vpad] remap operand), counts
    are one one-hot contraction per segment row summed over the segment
    axis — exact integers, so the gathered [S, Q, n_bins] tensor equals
    the per-shard dict merge."""
    stack = pctx.stack
    field = spec.params.get("field")
    if not field or field in stack.mixed:
        raise _Unsupported(f"terms field [{field}]")
    if field not in stack.keywords:
        if field in stack.text or field in stack.numerics:
            # analyzed-text / numeric terms keep the host collect's
            # np.unique semantics — fan-out territory
            raise _Unsupported(f"terms over non-keyword [{field}]")
        # absent everywhere: every shard reports the empty partial
        sig = ("terms_absent",)
        return (sig, None,
                lambda out, q: [_empty_terms()
                                for _ in range(stack.s_count)])
    vocab: list[str] = sorted({v for rows in stack.shard_rows
                               for _i, seg in rows
                               for v in (seg.keywords.get(field).values
                                         if seg.keywords.get(field)
                                         else ())})
    n_bins = len(vocab)
    if n_bins == 0:
        sig = ("terms_absent",)
        return (sig, None,
                lambda out, q: [_empty_terms()
                                for _ in range(stack.s_count)])
    if n_bins > _MAX_TERMS_BINS:
        raise _Unsupported(f"terms vocab [{n_bins}]")
    bin_of = {v: i for i, v in enumerate(vocab)}
    v_pad = max(max((len(seg.keywords[field].values)
                     for rows in stack.shard_rows for _i, seg in rows
                     if field in seg.keywords), default=1), 1)
    remap = np.full((stack.s_pad, stack.g_pad, v_pad), n_bins, np.int32)
    for si, rows in enumerate(stack.shard_rows):
        for gi, (_i, seg) in enumerate(rows):
            kc = seg.keywords.get(field)
            if kc is None:
                continue
            for o, v in enumerate(kc.values):
                remap[si, gi, o] = bin_of[v]
    pctx.use_field(field, "keyword")
    pctx.emit(remap, _OP_S)
    sig = ("terms", field, n_bins, v_pad)

    def dev(d, m):
        rmp = d.pop()                            # [G, Vpad]
        ords = d.fields[field].ords              # [G, N]
        gid = jnp.where(
            ords >= 0,
            jnp.take_along_axis(rmp, jnp.maximum(ords, 0).astype(jnp.int32),
                                axis=1),
            jnp.int32(n_bins))                   # [G, N]

        def one(gid_g, m_g):                     # [N], [Qb, N]
            oh = (gid_g[:, None]
                  == jnp.arange(n_bins, dtype=jnp.int32)[None, :])
            return jax.lax.dot_general(
                m_g.astype(jnp.float32), oh.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        return jax.vmap(one)(gid, m).sum(axis=0).astype(jnp.int32)

    from ..search.aggs.aggregators import terms_partial_from_counts

    def fin(out, q):                             # out: [S, Qb, n_bins]
        parts = []
        for si in range(stack.s_count):
            row = out[si, q]
            counts = {vocab[b]: int(row[b])
                      for b in np.nonzero(row)[0]}
            parts.append(terms_partial_from_counts(spec, counts))
        return parts

    return sig, dev, fin


def _plan_histogram(spec, pctx):
    """histogram / fixed-interval date_histogram: bucket id is an affine
    transform of the column per segment (per-segment base from the cached
    column min — exactly `_device_histogram`'s keys), counts stay
    per-SEGMENT in the output so each shard rebuilds the same key->count
    dicts the per-segment device collect produced."""
    from ..search.aggs.aggregators import (_col_minmax, _fixed_interval_ms)
    stack = pctx.stack
    field = spec.params.get("field")
    if not field or field in stack.mixed:
        raise _Unsupported(f"histogram field [{field}]")
    if spec.type == "date_histogram":
        interval = _fixed_interval_ms(spec.params.get("interval", "1d"))
        if interval is None:
            raise _Unsupported("calendar interval")
    else:
        interval = float(spec.params["interval"])
    if interval <= 0:
        raise _Unsupported("non-positive interval")
    if field not in stack.numerics:
        sig = ("hist_absent",)
        return (sig, None,
                lambda out, q: [{"buckets": {}}
                                for _ in range(stack.s_count)])
    pctx.use_field(field, "numeric")
    bases = np.zeros((stack.s_pad, stack.g_pad), np.float64)
    hvalid = np.zeros((stack.s_pad, stack.g_pad), bool)
    n_bins = 1
    int_column = True
    for si, rows in enumerate(stack.shard_rows):
        for gi, (_i, seg) in enumerate(rows):
            nc = seg.numerics.get(field)
            if nc is None:
                continue
            int_column = int_column and nc.dtype == "i64"
            mn, mx = _col_minmax(seg, field, nc)
            if not (np.isfinite(mn) and np.isfinite(mx)):
                continue              # empty column: zero contribution
            base = math.floor(mn / interval) * interval
            bins = int((mx - base) // interval) + 1
            if bins > _MAX_HIST_BINS:
                # the fan-out's own device collect declines this too; keep
                # the two lanes on the same ladder rung
                raise _Unsupported(f"histogram bins [{bins}]")
            bases[si, gi] = base
            hvalid[si, gi] = True
            n_bins = max(n_bins, bins)
    # the same bucketing operands as the per-segment collect
    # (ops/aggs.hist_bins): exact i64 for integral columns, and the
    # interval a runtime operand either way
    from ..ops.aggs import hist_bins, hist_operands
    base_op, interval_op = hist_operands(int_column, bases, interval)
    pctx.emit(base_op, _OP_S)
    pctx.emit(hvalid, _OP_S)
    pctx.emit(interval_op, _OP_R)
    sig = (spec.type, field, float(interval), n_bins, base_op.dtype.str)

    def dev(d, m):
        base = d.pop()                           # [G]
        ok_g = d.pop()                           # [G]
        iv = d.pop()                             # scalar
        num = d.fields[field]
        idx = hist_bins(num.vals, base[:, None], iv)
        ok = (~num.missing) & (idx >= 0) & (idx < n_bins) \
            & ok_g[:, None]                      # [G, N]

        def one(idx_g, ok_g2, m_g):              # [N], [N], [Qb, N]
            sel = m_g & ok_g2[None, :]
            safe = jnp.where(ok_g2, idx_g, n_bins)
            oh = (safe[:, None]
                  == jnp.arange(n_bins, dtype=jnp.int32)[None, :])
            return jax.lax.dot_general(
                sel.astype(jnp.float32), oh.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # [G, Qb, n_bins] -> [Qb, G, n_bins]: per-SEGMENT counts survive
        # so host keys rebuild from each segment's own base
        return jnp.moveaxis(jax.vmap(one)(idx, ok, m), 0, 1) \
            .astype(jnp.int32)

    def fin(out, q):                             # out: [S, Qb, G, n_bins]
        parts = []
        for si in range(stack.s_count):
            buckets: dict = {}
            for gi in range(len(stack.shard_rows[si])):
                if not hvalid[si, gi]:
                    continue
                row = out[si, q, gi]
                base = bases[si, gi]
                for i in np.nonzero(row)[0]:
                    key = float(base + i * interval)
                    ent = buckets.get(key)
                    if ent is None:
                        buckets[key] = {"doc_count": int(row[i])}
                    else:
                        ent["doc_count"] += int(row[i])
            parts.append({"buckets": buckets})
        return parts

    return sig, dev, fin


def _plan_range(spec, pctx):
    """range (non-date): bounds are query-derived and uniform across
    segments, so per-shard counts sum over the segment axis on device."""
    from ..search.aggs.aggregators import _range_bounds
    stack = pctx.stack
    field = spec.params.get("field")
    if not field or field in stack.mixed:
        raise _Unsupported(f"range field [{field}]")
    bounds = _range_bounds(spec.params, is_date=False)
    if bounds is None:
        raise _Unsupported("empty ranges")
    keys, los, his = bounds
    if field not in stack.numerics:
        sig = ("range_absent",)
        return (sig, None,
                lambda out, q: [{"buckets": {}}
                                for _ in range(stack.s_count)])
    pctx.use_field(field, "numeric")
    pctx.emit(los, _OP_R)   # request-global bounds: replicated operands
    pctx.emit(his, _OP_R)
    sig = ("range", field, len(keys))

    def dev(d, m):
        lo_b, hi_b = d.pop(), d.pop()            # [R]
        num = d.fields[field]
        v = num.vals.astype(jnp.float64)         # [G, N]
        inr = (~num.missing)[:, None, :] \
            & (v[:, None, :] >= lo_b[None, :, None]) \
            & (v[:, None, :] < hi_b[None, :, None])        # [G, R, N]
        # [G, Qb, R] summed over G and N -> [Qb, R]
        return jnp.einsum("gqn,grn->qr", m.astype(jnp.int64),
                          inr.astype(jnp.int64))

    def fin(out, q):                             # out: [S, Qb, R]
        parts = []
        for si in range(stack.s_count):
            row = out[si, q]
            parts.append({"buckets": {
                key: {"doc_count": int(row[ri]), "from": lo, "to": hi}
                for ri, (key, lo, hi) in enumerate(keys)}})
        return parts

    return sig, dev, fin


def _plan_metric(spec, pctx):
    """min/max/sum/avg/value_count/stats/extended_stats on a numeric
    column: fused per-(segment, query) 5-vectors — `masked_stats`'s exact
    math over the mesh-padded column (appended zero padding is exact under
    f64 accumulation) — merged on HOST in segment order, because float
    addition is order-sensitive and the fan-out merges in that order."""
    stack = pctx.stack
    field = spec.params.get("field")
    if not field or field in stack.mixed:
        raise _Unsupported(f"metric field [{field}]")

    def empty():
        return {"count": 0, "sum": 0.0, "min": math.inf,
                "max": -math.inf, "sum_sq": 0.0}

    if field not in stack.numerics:
        sig = ("metric_absent", spec.type)
        return (sig, None,
                lambda out, q: [empty() for _ in range(stack.s_count)])
    pctx.use_field(field, "numeric")
    sig = ("metric", field)

    def dev(d, m):
        num = d.fields[field]

        def one(vals_g, miss_g, m_g):            # [N], [N], [Qb, N]
            sel = m_g & ~miss_g[None, :]
            v = vals_g.astype(jnp.float64)[None, :]
            vz = jnp.where(sel, v, 0.0)
            cnt = sel.sum(axis=1).astype(jnp.float64)
            s = vz.sum(axis=1)
            ss = (vz * vz).sum(axis=1)
            mn = jnp.where(sel, v, jnp.inf).min(axis=1)
            mx = jnp.where(sel, v, -jnp.inf).max(axis=1)
            return jnp.stack([cnt, s, ss, mn, mx], axis=1)   # [Qb, 5]

        # [G, Qb, 5] -> [Qb, G, 5]
        return jnp.moveaxis(
            jax.vmap(one)(num.vals, num.missing, m), 0, 1)

    from ..search.aggs.aggregators import merge_partial

    def fin(out, q):                             # out: [S, Qb, G, 5]
        parts = []
        for si in range(stack.s_count):
            merged = None
            for gi in range(len(stack.shard_rows[si])):
                cnt, s, ss, mn, mx = out[si, q, gi]
                part = {"count": int(cnt), "sum": float(s),
                        "sum_sq": float(ss),
                        "min": float(mn) if cnt else math.inf,
                        "max": float(mx) if cnt else -math.inf}
                merged = part if merged is None \
                    else merge_partial(spec, merged, part)
            parts.append(merged if merged is not None else empty())
        return parts

    return sig, dev, fin


# ---------------------------------------------------------------------------
# Sub-aggregation trees (ISSUE 17 tentpole (b)): composite-bin flattening
# ---------------------------------------------------------------------------

# composite (parent x child) bins past this cap keep the fan-out's host
# collect (per-bucket python masks) — the cap bounds the per-segment
# [Qb, G, bins, 5] metric tensor, not correctness
_MAX_SUBAGG_BINS = 1 << 12

_SUBAGG_PARENTS = {"terms", "histogram", "date_histogram"}

# f64 bin keys are exact only while |value| < 2^53 (search/sort_encode
# applies the same ceiling to encoded sort keys)
_MAX_EXACT_I64 = float(2 ** 53)


class _Binner:
    """One bucket level of a sub-agg tree: `nb` real bins (id == nb is the
    spill for missing/out-of-bucket docs), `dev_ids(d)` the device closure
    producing i32[G, N] per-doc bin ids, `key_of(b)` the host bucket key —
    derived the same way the fan-out's host collect derives it, so the two
    lanes can never disagree on a key."""

    def __init__(self, nb, sig, dev_ids, key_of):
        self.nb = nb
        self.sig = sig
        self.dev_ids = dev_ids
        self.key_of = key_of


class _TreeNode:
    """Planned node of a sub-agg tree. `binner is None` means the bucket
    field is absent from the whole stack — the node contributes no device
    tensors and finishes to the host collect's constant empty partial."""

    def __init__(self, spec, binner):
        self.spec = spec
        self.binner = binner
        self.metrics = []     # [(AggSpec, present: bool)]
        self.children = []    # [_TreeNode]
        self.cnb = 0          # composite bin count at this level
        self.count_off = -1
        self.metric_offs = []


def _terms_binner(spec, pctx, reason: str):
    """Global-vocab terms level — _plan_terms' remap-operand construction
    shared across every segment AND shard, so one ordinal space covers the
    whole composite bin axis."""
    stack = pctx.stack
    field = spec.params.get("field")
    if not field or field in stack.mixed:
        raise _Unsupported(f"terms field [{field}]", reason=reason)
    if field not in stack.keywords:
        if field in stack.text or field in stack.numerics:
            # analyzed-text / numeric terms keep the host collect's
            # np.unique semantics — fan-out territory
            raise _Unsupported(f"terms over non-keyword [{field}]",
                               reason=reason)
        return None     # absent everywhere -> constant empty partial
    vocab: list[str] = sorted({v for rows in stack.shard_rows
                               for _i, seg in rows
                               for v in (seg.keywords.get(field).values
                                         if seg.keywords.get(field)
                                         else ())})
    nb = len(vocab)
    if nb == 0:
        return None
    if nb > _MAX_SUBAGG_BINS:
        raise _Unsupported(f"terms vocab [{nb}]", reason="subagg_bins")
    bin_of = {v: i for i, v in enumerate(vocab)}
    v_pad = max(max((len(seg.keywords[field].values)
                     for rows in stack.shard_rows for _i, seg in rows
                     if field in seg.keywords), default=1), 1)
    remap = np.full((stack.s_pad, stack.g_pad, v_pad), nb, np.int32)
    for si, rows in enumerate(stack.shard_rows):
        for gi, (_i, seg) in enumerate(rows):
            kc = seg.keywords.get(field)
            if kc is None:
                continue
            for o, v in enumerate(kc.values):
                remap[si, gi, o] = bin_of[v]
    pctx.use_field(field, "keyword")
    pctx.emit(remap, _OP_S)

    def dev_ids(d):
        rmp = d.pop()                            # [G, Vpad]
        ords = d.fields[field].ords              # [G, N]
        return jnp.where(
            ords >= 0,
            jnp.take_along_axis(rmp, jnp.maximum(ords, 0).astype(jnp.int32),
                                axis=1),
            jnp.int32(nb))

    b = _Binner(nb, ("terms", field, nb, v_pad), dev_ids,
                lambda i: vocab[i])
    b.vocab = vocab
    return b


def _int_hist_binner(spec, pctx, reason: str):
    """Exact-integer histogram / fixed-interval date_histogram level. The
    host collect's WITH-SUBS path buckets by `(vals // step) * step`
    (aggregators._bucket_segment), which f64 affine binning cannot
    reproduce bitwise for float columns / fractional intervals — those
    decline. For i64 columns + integer steps the device bin id is exact
    i64 floor-division against a GLOBAL base, so `base + i * step` equals
    the host's floor key for every segment and shard."""
    from ..search.aggs.aggregators import _col_minmax, _fixed_interval_ms
    stack = pctx.stack
    field = spec.params.get("field")
    if not field or field in stack.mixed:
        raise _Unsupported(f"histogram field [{field}]", reason=reason)
    if spec.type == "date_histogram":
        iv = _fixed_interval_ms(spec.params.get("interval", "1d"))
        if iv is None:
            raise _Unsupported("calendar interval",
                               reason="calendar_interval")
    else:
        iv = float(spec.params["interval"])
    if iv <= 0 or not float(iv).is_integer():
        raise _Unsupported(f"non-integer interval [{iv}]",
                           reason="float_histogram")
    step = int(iv)
    if field not in stack.numerics:
        return None     # absent everywhere -> {"buckets": {}}
    mn_g, mx_g = math.inf, -math.inf
    for rows in stack.shard_rows:
        for _i, seg in rows:
            nc = seg.numerics.get(field)
            if nc is None:
                continue
            if nc.dtype != "i64":
                # float column: host buckets by np.floor(v/interval) —
                # not bitwise-reachable from affine device bins
                raise _Unsupported(f"float column [{field}]",
                                   reason="float_histogram")
            mn, mx = _col_minmax(seg, field, nc)
            if np.isfinite(mn) and np.isfinite(mx):
                mn_g = min(mn_g, mn)
                mx_g = max(mx_g, mx)
    if not np.isfinite(mn_g):
        return None     # no present values anywhere
    if max(abs(mn_g), abs(mx_g)) >= _MAX_EXACT_I64:
        raise _Unsupported("i64 precision", reason="float_histogram")
    base = (int(mn_g) // step) * step
    nb = (int(mx_g) // step) - (base // step) + 1
    if nb > _MAX_SUBAGG_BINS:
        raise _Unsupported(f"histogram bins [{nb}]", reason="subagg_bins")
    pctx.use_field(field, "numeric")
    # base rides as a replicated data operand so a refresh that only
    # shifts the column range reuses the compiled program (no-retrace)
    pctx.emit(np.array([float(base)]), _OP_R)

    def dev_ids(d):
        b = d.pop()[0].astype(jnp.int64)         # scalar base
        num = d.fields[field]
        vi = num.vals.astype(jnp.int64)          # [G, N] exact (< 2^53)
        idx = (vi - b) // step
        ok = (~num.missing) & (idx >= 0) & (idx < nb)
        return jnp.where(ok, idx, nb).astype(jnp.int32)

    return _Binner(nb, (spec.type, field, step, nb), dev_ids,
                   lambda i: float(base + i * step))


def _plan_tree_node(spec, pctx, depth: int) -> _TreeNode:
    """Recursively plan one bucket level + its subs. Operands are emitted
    in traversal order (parent binner, then each bucket child), and the
    device closure pops in the same order."""
    reason = "unsupported_child" if depth else "agg_shape"
    if spec.type == "terms":
        binner = _terms_binner(spec, pctx, reason)
    elif spec.type in ("histogram", "date_histogram"):
        binner = _int_hist_binner(spec, pctx, reason)
    else:
        raise _Unsupported(f"subs under [{spec.type}]",
                           reason="unsupported_child")
    node = _TreeNode(spec, binner)
    stack = pctx.stack
    for s in spec.subs:
        if s.type in _METRIC_TYPES:
            field = s.params.get("field")
            if not field or field in stack.mixed:
                raise _Unsupported(f"metric field [{field}]",
                                   reason="unsupported_child")
            present = field in stack.numerics
            if present and binner is not None:
                pctx.use_field(field, "numeric")
            node.metrics.append((s, present))
        elif s.type in _SUBAGG_PARENTS and depth == 0:
            node.children.append(_plan_tree_node(s, pctx, depth + 1))
        else:
            raise _Unsupported(f"sub-agg [{s.type}] at depth {depth + 1}",
                               reason="unsupported_child")
    return node


def _assign_offsets(node: _TreeNode, g_pad: int, parent_nb: int | None,
                    tot: int) -> int:
    """Lay the tree's tensors out along one packed f64 axis: per-segment
    counts [G, cnb], then per-metric [G, cnb, 5], then children."""
    if node.binner is None:
        return tot
    node.cnb = node.binner.nb if parent_nb is None \
        else parent_nb * node.binner.nb
    if node.cnb > _MAX_SUBAGG_BINS:
        raise _Unsupported(f"composite bins [{node.cnb}]",
                           reason="subagg_bins")
    node.count_off = tot
    tot += g_pad * node.cnb
    node.metric_offs = []
    for _s, present in node.metrics:
        node.metric_offs.append(tot if present else None)
        if present:
            tot += g_pad * node.cnb * 5
    for ch in node.children:
        tot = _assign_offsets(ch, g_pad, node.cnb, tot)
    return tot


def _per_g_counts(ids, m, nb):
    """ids i32[G, N] (nb = spill), m bool[G, Qb, N] -> f64[Qb, G * nb]
    exact per-segment counts (integers below 2^31 are exact in f64)."""
    def one_g(ids_g, m_g):                       # [N], [Qb, N]
        idq = jnp.where(m_g, ids_g[None, :], nb)
        return jax.vmap(
            lambda ix: jnp.bincount(ix, length=nb + 1))(idq)[:, :nb]
    c = jnp.moveaxis(jax.vmap(one_g)(ids, m), 0, 1)      # [Qb, G, nb]
    return c.reshape(c.shape[0], -1).astype(jnp.float64)


def _per_g_stats(ids, m, num, nb):
    """Fused per-(segment, bin) metric rows: (count, sum, sum_sq, min,
    max) via segment reductions over the composite bin ids ->
    f64[Qb, G * nb * 5]. Rows with count 0 are ignored at finish time
    (min/max read as +/-inf there), so the reduction identities never
    leak into the wire partial."""
    v64 = num.vals.astype(jnp.float64)
    miss = num.missing

    def one_g(ids_g, v_g, miss_g, m_g):          # [N], [N], [N], [Qb, N]
        def one_q(m_q):
            sel = m_q & ~miss_g
            idq = jnp.where(sel, ids_g, nb)
            vz = jnp.where(sel, v_g, 0.0)
            cnt = jax.ops.segment_sum(sel.astype(jnp.float64), idq,
                                      num_segments=nb + 1)
            s = jax.ops.segment_sum(vz, idq, num_segments=nb + 1)
            ss = jax.ops.segment_sum(vz * vz, idq, num_segments=nb + 1)
            mn = jax.ops.segment_min(jnp.where(sel, v_g, jnp.inf), idq,
                                     num_segments=nb + 1)
            mx = jax.ops.segment_max(jnp.where(sel, v_g, -jnp.inf), idq,
                                     num_segments=nb + 1)
            return jnp.stack([cnt, s, ss, mn, mx], axis=1)[:nb]
        return jax.vmap(one_q)(m_g)              # [Qb, nb, 5]

    st = jnp.moveaxis(jax.vmap(one_g)(ids, v64, miss, m), 0, 1)
    return st.reshape(st.shape[0], -1)           # [Qb, G*nb*5]


def _metric_part_from_row(vec) -> dict:
    cnt = int(vec[0])
    return {"count": cnt, "sum": float(vec[1]), "sum_sq": float(vec[2]),
            "min": float(vec[3]) if cnt else math.inf,
            "max": float(vec[4]) if cnt else -math.inf}


_EMPTY_METRIC = {"count": 0, "sum": 0.0, "sum_sq": 0.0,
                 "min": math.inf, "max": -math.inf}


def _empty_bucket_partial(spec) -> dict:
    if spec.type == "terms":
        return _empty_terms()
    return {"buckets": {}}


def _plan_subagg_tree(spec, pctx):
    """Plan a bucket agg WITH sub-aggregations as ONE packed device tensor
    per shard: every level's per-segment composite-bin counts and every
    metric leaf's per-segment 5-vector rows, flattened and concatenated
    along one f64 axis (counts are exact integers in f64). `fin` slices
    the gathered [S, Qb, TOT] row back apart and rebuilds the nested
    partial dicts with the host collect's own truncation and merge code
    (terms_partial_from_counts / merge_partial), reproducing the fan-out
    shard partial bit-for-bit."""
    from ..search.aggs.aggregators import (_empty_partial, merge_partial,
                                           terms_partial_from_counts)
    stack = pctx.stack
    tree = _plan_tree_node(spec, pctx, 0)
    if tree.binner is None:
        # absent parent field: the host collect's constant empty partial
        sig = ("subtree_absent", spec.type)
        return (sig, None,
                lambda out, q: [_empty_bucket_partial(spec)
                                for _ in range(stack.s_count)])
    g_pad = stack.g_pad
    _assign_offsets(tree, g_pad, None, 0)

    def tree_sig(node):
        return (node.binner.sig if node.binner is not None else None,
                tuple((s.params.get("field"), present)
                      for s, present in node.metrics),
                tuple(tree_sig(ch) for ch in node.children))

    sig = ("subtree", tree_sig(tree))

    def dev(d, m):
        outs = []

        def emit_node(node, pids, pnb):
            b = node.binner
            if b is None:
                return
            ids = b.dev_ids(d)                   # [G, N]
            if pids is None:
                cids, cnb = ids, b.nb
            else:
                ok = (pids < pnb) & (ids < b.nb)
                cids = jnp.where(ok, pids * b.nb + ids,
                                 pnb * b.nb).astype(jnp.int32)
                cnb = pnb * b.nb
            outs.append(_per_g_counts(cids, m, cnb))
            for (ms, present) in node.metrics:
                if present:
                    outs.append(_per_g_stats(
                        cids, m, d.fields[ms.params["field"]], cnb))
            for ch in node.children:
                emit_node(ch, cids, cnb)

        emit_node(tree, None, None)
        return jnp.concatenate(outs, axis=1)     # [Qb, TOT]

    def counts_of(node, row):
        return row[node.count_off:
                   node.count_off + g_pad * node.cnb] \
            .reshape(g_pad, node.cnb)

    def stats_of(node, mi, row):
        off = node.metric_offs[mi]
        return row[off: off + g_pad * node.cnb * 5] \
            .reshape(g_pad, node.cnb, 5)

    def seg_subs(node, row, gi, comp) -> dict:
        """subs dict for ONE (segment, bucket) — what _bucket_entry /
        _collect_terms_shard pass 2 collects for that segment."""
        subs: dict = {}
        for mi, (ms, present) in enumerate(node.metrics):
            subs[ms.name] = _metric_part_from_row(
                stats_of(node, mi, row)[gi, comp]) if present \
                else dict(_EMPTY_METRIC)
        for ch in node.children:
            subs[ch.spec.name] = child_partial(ch, row, gi, comp)
        return subs

    def child_partial(node, row, gi, pcomp) -> dict:
        """One bucket-child partial for (segment gi, parent composite
        bin) — exactly _collect_one's per-segment result."""
        if node.binner is None:
            return _empty_bucket_partial(node.spec)
        nb = node.binner.nb
        crow = counts_of(node, row)[gi, pcomp * nb:(pcomp + 1) * nb]
        if node.spec.type == "terms":
            counts = {node.binner.vocab[j]: int(crow[j])
                      for j in np.nonzero(crow)[0]}
            if not node.spec.subs:
                return terms_partial_from_counts(node.spec, counts)
            # _collect_terms_shard([seg]) with subs, replicated: per-
            # SEGMENT truncation, then per-key metric leaves
            p = node.spec.params
            size = int(p.get("size", 10)) or len(counts) or 1
            shard_size = int(p.get("shard_size", size * 3 + 10))
            items = sorted(counts.items(),
                           key=lambda kv: (-kv[1], str(kv[0])))
            top = items[:shard_size]
            dropped = items[shard_size:]
            buckets: dict = {}
            for key, c in top:
                j = node.binner.vocab.index(key)
                buckets[key] = {
                    "doc_count": int(c),
                    "subs": seg_subs(node, row, gi, pcomp * nb + j)}
            return {"buckets": buckets,
                    "other_doc_count": int(sum(c for _k, c in dropped)),
                    "error_bound": int(top[-1][1]) if dropped else 0}
        # histogram / date_histogram child: nonzero bins ascending ==
        # the host's np.unique(keys[sel]) order
        buckets = {}
        for j in np.nonzero(crow)[0]:
            e: dict = {"doc_count": int(crow[j])}
            if node.spec.subs:
                e["subs"] = seg_subs(node, row, gi, pcomp * nb + int(j))
            buckets[node.binner.key_of(int(j))] = e
        return {"buckets": buckets}

    def finish_shard(row, si) -> dict:
        n_rows = len(stack.shard_rows[si])
        ct = counts_of(tree, row)
        if spec.type == "terms":
            # two-pass shard semantics: top keys from the MERGED counts,
            # subs per segment merged in segment order
            merged = ct[:n_rows].sum(axis=0)
            counts = {tree.binner.vocab[b]: int(merged[b])
                      for b in np.nonzero(merged)[0]}
            p = spec.params
            size = int(p.get("size", 10)) or len(counts) or 1
            shard_size = int(p.get("shard_size", size * 3 + 10))
            items = sorted(counts.items(),
                           key=lambda kv: (-kv[1], str(kv[0])))
            top = items[:shard_size]
            dropped = items[shard_size:]
            buckets: dict = {}
            for key, c in top:
                b = tree.binner.vocab.index(key)
                sub_parts: dict = {}
                for gi in range(n_rows):
                    for s_name, part in seg_subs(tree, row, gi,
                                                 b).items():
                        prev = sub_parts.get(s_name)
                        sub_parts[s_name] = part if prev is None \
                            else merge_partial(
                                next(s for s in spec.subs
                                     if s.name == s_name), prev, part)
                buckets[key] = {
                    "doc_count": int(c),
                    "subs": {s.name: sub_parts.get(s.name,
                                                   _empty_partial(s))
                             for s in spec.subs}}
            return {"buckets": buckets,
                    "other_doc_count": int(sum(c for _k, c in dropped)),
                    "error_bound": int(top[-1][1]) if dropped else 0}
        # histogram parent: per-segment partials merged in segment order
        # (collect_shard's merge), bucket keys ascending per segment
        merged_p = None
        for gi in range(n_rows):
            srow = ct[gi]
            buckets = {}
            for b in np.nonzero(srow)[0]:
                buckets[tree.binner.key_of(int(b))] = {
                    "doc_count": int(srow[b]),
                    "subs": seg_subs(tree, row, gi, int(b))}
            part = {"buckets": buckets}
            merged_p = part if merged_p is None \
                else merge_partial(spec, merged_p, part)
        return merged_p if merged_p is not None else {"buckets": {}}

    def fin(out, q):                             # out: [S, Qb, TOT]
        return [finish_shard(out[si, q], si)
                for si in range(stack.s_count)]

    return sig, dev, fin
