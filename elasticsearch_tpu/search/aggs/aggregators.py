"""Aggregations: composable analytics tree over columnar fielddata.

The analog of the reference aggregation framework
(/root/reference/src/main/java/org/elasticsearch/search/aggregations/ —
Aggregator collect-per-doc -> InternalAggregation reduce-across-shards,
AggregationPhase.java:45,70-95). Execution model here is tensor-native
instead of per-doc collectors:

  collect  — per segment, the query's match mask (bool[n_pad], the same mask
             the scoring pass produced) gates vectorized column reductions:
             bucket assignment is one vectorized expression, counts/sums are
             np.bincount / ufunc.at over the whole column at once.
  partial  — a small, host-side, *mergeable* summary per shard, mirroring
             InternalAggregation's wire objects (sum/count/min/max pairs,
             HLL registers, t-digest centroids, bucket->count maps).
  reduce   — partials merge associatively across segments and shards
             (ref InternalAggregations.reduce via SearchPhaseController
             .merge:282-399); in the mesh data plane these merges ride
             collectives (counts psum) — host merge is the DCN fallback.
  render   — ES 2.0 response JSON shapes (buckets / value / values).

Bucket aggs: terms, histogram, date_histogram, range, date_range, filter,
filters, global, missing. Metric aggs: min, max, sum, avg, value_count,
stats, extended_stats, cardinality (HLL), percentiles (t-digest).
Sub-aggregations nest arbitrarily under bucket aggs.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field as dc_field
from datetime import datetime, timezone
from typing import Any, Callable

import numpy as np

from ...index.segment import Segment
from .hll import HyperLogLog, _hash64
from .tdigest import TDigest

BUCKET_TYPES = {"terms", "histogram", "date_histogram", "range", "date_range",
                "filter", "filters", "global", "missing",
                "significant_terms", "nested", "reverse_nested", "children",
                "geohash_grid", "geo_distance", "sampler", "composite"}
METRIC_TYPES = {"min", "max", "sum", "avg", "value_count", "stats",
                "extended_stats", "cardinality", "percentiles", "top_hits",
                "geo_bounds", "scripted_metric"}
# Pipeline aggregations (ref search/aggregations/pipeline/): computed
# HOST-SIDE at render time over the already-reduced bucket list, so every
# serving lane (loop/stacked/blockwise/mesh/host-reduce) feeds them the
# same merged partials and the outputs are identical by construction.
PIPELINE_TYPES = {"derivative", "moving_avg", "cumulative_sum",
                  "bucket_script"}
# which parents may carry which pipelines: the sequential pipelines need
# an ordered bucket axis (histogram family); bucket_script only needs
# per-bucket values, so terms qualifies too
_PIPELINE_PARENTS = {
    "derivative": ("histogram", "date_histogram"),
    "moving_avg": ("histogram", "date_histogram"),
    "cumulative_sum": ("histogram", "date_histogram"),
    "bucket_script": ("histogram", "date_histogram", "terms"),
}


def has_top_hits(specs: list["AggSpec"]) -> bool:
    """top_hits needs per-doc scores, which only the dense scoring path
    materializes — the sparse lane checks this before taking an agg tree."""
    return any(s.type == "top_hits" or has_top_hits(s.subs) for s in specs)


class AggregationParsingException(Exception):
    pass


@dataclass
class AggSpec:
    name: str
    type: str
    params: dict
    subs: list["AggSpec"] = dc_field(default_factory=list)
    # pipeline children live OUTSIDE `subs`: they never collect per doc
    # (render-time host math only), so a leaf parent stays eligible for
    # the batched device collect and the mesh planner never sees them
    pipelines: list["AggSpec"] = dc_field(default_factory=list)


def parse_aggs(spec: dict | None, *, _nested: bool = False) -> list[AggSpec]:
    """Parse the request's "aggs"/"aggregations" tree
    (ref search/aggregations/AggregatorParsers.java)."""
    if not spec:
        return []
    out = []
    for name, body in spec.items():
        subs = []
        agg_type = None
        params: dict = {}
        for key, val in body.items():
            if key in ("aggs", "aggregations"):
                subs = parse_aggs(val, _nested=True)
            elif key in BUCKET_TYPES or key in METRIC_TYPES \
                    or key in PIPELINE_TYPES:
                agg_type, params = key, (val if isinstance(val, dict) else {})
            else:
                raise AggregationParsingException(
                    f"unknown aggregation type [{key}] under [{name}]")
        if agg_type is None:
            raise AggregationParsingException(f"no type for aggregation [{name}]")
        if subs and agg_type in METRIC_TYPES:
            raise AggregationParsingException(
                f"metric aggregation [{name}] cannot have sub-aggregations")
        if subs and agg_type in PIPELINE_TYPES:
            raise AggregationParsingException(
                f"pipeline aggregation [{name}] cannot have sub-aggregations")
        pipelines = [s for s in subs if s.type in PIPELINE_TYPES]
        subs = [s for s in subs if s.type not in PIPELINE_TYPES]
        if agg_type == "composite":
            _validate_composite(name, params, subs)
        for ps in pipelines:
            _validate_pipeline(agg_type, ps)
        out.append(AggSpec(name=name, type=agg_type, params=params,
                           subs=subs, pipelines=pipelines))
    if not _nested:
        for s in out:
            if s.type in PIPELINE_TYPES:
                raise AggregationParsingException(
                    f"pipeline aggregation [{s.name}] must be a sibling "
                    f"inside a bucket aggregation's [aggs], not top-level")
    return out


def _validate_pipeline(parent_type: str, ps: "AggSpec") -> None:
    allowed = _PIPELINE_PARENTS[ps.type]
    if parent_type not in allowed:
        raise AggregationParsingException(
            f"pipeline aggregation [{ps.name}] of type [{ps.type}] requires "
            f"a parent of type {sorted(allowed)}, got [{parent_type}]")
    bp = ps.params.get("buckets_path")
    if ps.type == "bucket_script":
        if not isinstance(bp, dict) or not bp:
            raise AggregationParsingException(
                f"bucket_script [{ps.name}] needs a buckets_path map")
        if not ps.params.get("script"):
            raise AggregationParsingException(
                f"bucket_script [{ps.name}] needs a script")
    elif not isinstance(bp, str) or not bp:
        raise AggregationParsingException(
            f"pipeline aggregation [{ps.name}] needs a buckets_path string")


def _validate_composite(name: str, params: dict, subs: list) -> None:
    """composite scope for this tier: leaf-only (no sub-aggregations),
    ascending sources of terms/histogram/date_histogram — the exact slice
    the after-key disjoint-cover guarantee is proven for."""
    if subs:
        raise AggregationParsingException(
            f"composite aggregation [{name}] does not support "
            f"sub-aggregations")
    sources = params.get("sources")
    if not isinstance(sources, list) or not sources:
        raise AggregationParsingException(
            f"composite aggregation [{name}] needs a non-empty sources list")
    for src in sources:
        if not isinstance(src, dict) or len(src) != 1:
            raise AggregationParsingException(
                f"composite [{name}]: each source is one {{name: spec}}")
        sname, sbody = next(iter(src.items()))
        if not isinstance(sbody, dict) or len(sbody) != 1:
            raise AggregationParsingException(
                f"composite [{name}] source [{sname}]: one source type")
        stype, sp = next(iter(sbody.items()))
        if stype not in ("terms", "histogram", "date_histogram"):
            raise AggregationParsingException(
                f"composite [{name}] source [{sname}]: unsupported source "
                f"type [{stype}]")
        if not isinstance(sp, dict) or not sp.get("field"):
            raise AggregationParsingException(
                f"composite [{name}] source [{sname}] needs a field")
        if str(sp.get("order", "asc")) != "asc":
            raise AggregationParsingException(
                f"composite [{name}] source [{sname}]: only ascending "
                f"order is supported")
        if stype == "histogram" and "interval" not in sp:
            raise AggregationParsingException(
                f"composite [{name}] source [{sname}] needs an interval")


def _composite_sources(spec: AggSpec) -> list[tuple[str, str, dict]]:
    """-> [(source_name, source_type, source_params)], in request order
    (the composite key's lexicographic significance order)."""
    out = []
    for src in spec.params.get("sources", []):
        sname, sbody = next(iter(src.items()))
        stype, sp = next(iter(sbody.items()))
        out.append((sname, stype, sp))
    return out


# ---------------------------------------------------------------------------
# Column access
# ---------------------------------------------------------------------------

def _numeric_column(seg: Segment, field: str):
    """-> (vals [N] in the column's NATIVE dtype, valid bool[N]) or None.
    i64 stays i64: casting to float64 would collapse distinct longs > 2^53
    (snowflake ids) in terms/cardinality buckets."""
    nc = seg.numerics.get(field)
    if nc is None:
        return None
    return np.asarray(nc.vals), ~np.asarray(nc.missing)


def _text_present_mask(seg: Segment, field: str) -> np.ndarray | None:
    """bool[n_pad]: docs with at least one posting in an analyzed field."""
    fx = seg.text.get(field)
    if fx is None:
        return None
    present = np.zeros(seg.n_pad, bool)
    present[np.asarray(fx.doc_ids)[:fx.n_postings]] = True
    return present


def _keyword_column(seg: Segment, field: str):
    kc = seg.keywords.get(field)
    if kc is None:
        return None
    return np.asarray(kc.ords), kc.values


# ---------------------------------------------------------------------------
# Collect: per-segment vectorized partials
# ---------------------------------------------------------------------------

class MaskView:
    """A query-match mask that stays DEVICE-resident until a collector
    genuinely needs host numpy. The hot collectors (keyword terms, numeric
    metrics) consume `.dev` through ops/aggs kernels — one fused device
    reduction per (segment, agg), downloading a tiny partial instead of a
    bool[n_pad] mask. Everything else falls back to `.np` (downloaded once,
    cached)."""

    __slots__ = ("_dev", "_np")

    def __init__(self, m):
        if isinstance(m, np.ndarray):
            self._np = m
            self._dev = None
        else:
            self._dev = m
            self._np = None

    @property
    def dev(self):
        return self._dev

    @property
    def np(self) -> np.ndarray:
        if self._np is None:
            self._np = np.asarray(self._dev)
        return self._np


def _mv(m) -> MaskView:
    return m if isinstance(m, MaskView) else MaskView(m)


_BATCHED_LEAF_TYPES = ("terms", "histogram", "date_histogram", "range",
                       "date_range", "min", "max", "sum", "avg",
                       "value_count", "stats", "extended_stats")


def collect_shards_batched(specs: list[AggSpec], by_shard: dict,
                           extra_devs=()) -> tuple[dict | None, list]:
    """Row-batched collect for a WHOLE msearch group across ALL shards:
    by_shard[i] = (segments, device bool[Q, n_pad] masks). One device
    program per (agg, segment), then ONE device_get for everything — the
    whole analytics batch costs a single host sync, not one per program.

    `extra_devs` rides the same fetch (the count-only totals). Returns
    ({shard: per-row partials} | None if any spec needs the general path,
    extra_host_values)."""
    import jax
    eligible = all(not spec.subs and spec.type in _BATCHED_LEAF_TYPES
                   for spec in specs)
    launches: list = []          # (shard_idx, spec_idx, dev, finish)
    if eligible:
        for i, (segments, masks) in by_shard.items():
            for si, spec in enumerate(specs):
                for seg, mask in zip(segments, masks):
                    if seg.n_docs == 0:
                        continue
                    lr = _launch_one_batched(spec, seg, mask)
                    if lr is None:
                        eligible = False
                        break
                    launches.append((i, si, lr[0], lr[1]))
                if not eligible:
                    break
            if not eligible:
                break
    if not eligible:
        extra_host = jax.device_get(list(extra_devs)) if extra_devs else []
        return None, extra_host
    fetched = jax.device_get(list(extra_devs)
                             + [d for _, _, d, _ in launches])
    extra_host = fetched[:len(extra_devs)]
    host_vals = fetched[len(extra_devs):]
    out: dict[int, list] = {}
    for (i, si, _, finish), hv in zip(launches, host_vals):
        rows = finish(hv)
        per_shard = out.setdefault(i, {})
        cur = per_shard.get(si)
        per_shard[si] = rows if cur is None else \
            [merge_partial(specs[si], a, b) for a, b in zip(cur, rows)]
    result: dict[int, list] = {}
    for i, (segments, masks) in by_shard.items():
        q = int(masks[0].shape[0]) if masks else 1
        per_shard = out.get(i, {})
        rows_q = None
        out_rows = [dict() for _ in range(q)]
        for si, spec in enumerate(specs):
            per_seg_rows = per_shard.get(si) \
                or [_empty_partial(spec) for _ in range(q)]
            rows_q = len(per_seg_rows)
            for row, part in zip(out_rows, per_seg_rows):
                row[spec.name] = part
        result[i] = out_rows[:rows_q] if rows_q else out_rows
    return result, extra_host


def collect_shard_batched(specs: list[AggSpec], segments: list[Segment],
                          masks: list) -> list[dict] | None:
    """Single-shard convenience wrapper over collect_shards_batched."""
    rows_by_shard, _ = collect_shards_batched(specs, {0: (segments, masks)})
    return None if rows_by_shard is None else rows_by_shard[0]


def _launch_one_batched(spec: AggSpec, seg: Segment, mask):
    """Launch one leaf agg's device program over one segment.
    -> (device_array, finish(host_array) -> per-row partials) or None when
    the spec needs the general path. The device array is NOT synced here."""
    t = spec.type
    p = spec.params
    field = p.get("field")
    if t == "terms":
        kc = seg.keywords.get(field)
        if kc is None:
            return None
        from ...ops.aggs import masked_bincount_q
        dev = masked_bincount_q(kc.ords, mask, n_bins=len(kc.values))

        def fin_terms(counts, kc=kc):
            return [{"buckets": {kc.values[o]: {"doc_count": int(c[o])}
                                 for o in np.nonzero(c)[0]},
                     "other_doc_count": 0, "error_bound": 0}
                    for c in counts]
        return dev, fin_terms
    nc = seg.numerics.get(field) if field else None
    if nc is None:
        return None
    if t in ("min", "max", "sum", "avg", "value_count", "stats",
             "extended_stats"):
        from ...ops.aggs import masked_stats_q
        dev = masked_stats_q(nc.vals, nc.missing, mask)

        def fin_stats(st):
            return [{"count": int(r[0]), "sum": float(r[1]),
                     "sum_sq": float(r[2]),
                     "min": float(r[3]) if r[0] else math.inf,
                     "max": float(r[4]) if r[0] else -math.inf}
                    for r in st]
        return dev, fin_stats
    if t in ("histogram", "date_histogram"):
        if t == "histogram":
            interval = float(p["interval"])
        else:
            interval = _fixed_interval_ms(p.get("interval", "1d"))
            if interval is None:
                return None       # calendar intervals: host path
        if interval <= 0:
            return None
        mn, mx = _col_minmax(seg, field, nc)
        if not np.isfinite(mn) or not np.isfinite(mx):
            nrows = int(mask.shape[0])
            return (np.zeros(0),
                    lambda _hv, n=nrows: [{"buckets": {}}
                                          for _ in range(n)])
        base = math.floor(mn / interval) * interval
        n_bins = int((mx - base) // interval) + 1
        if n_bins > _MAX_DEVICE_BINS:
            return None
        from ...ops.aggs import hist_operands, masked_histogram_q
        dev = masked_histogram_q(
            nc.vals, nc.missing, mask,
            *hist_operands(nc.dtype == "i64", base, interval),
            n_bins=n_bins)

        def fin_hist(counts, base=base, interval=interval):
            return [{"buckets": {float(base + i * interval):
                                 {"doc_count": int(c[i])}
                                 for i in np.nonzero(c)[0]}}
                    for c in counts]
        return dev, fin_hist
    if t in ("range", "date_range"):
        bounds = _range_bounds(p, is_date=(t == "date_range"))
        if bounds is None:
            return None
        keys, los, his = bounds
        from ...ops.aggs import masked_ranges_q
        dev = masked_ranges_q(nc.vals, nc.missing, mask, los, his)

        def fin_ranges(counts, keys=keys):
            return [{"buckets": {key: {"doc_count": int(row[ri]),
                                       "from": lo, "to": hi}
                                 for ri, (key, lo, hi) in enumerate(keys)}}
                    for row in counts]
        return dev, fin_ranges
    return None


def _range_bounds(p: dict, is_date: bool):
    """Shared range-spec resolution for the solo and row-batched device
    collects — ONE place derives (keys, los, his) so the lanes can't
    diverge (code review r5)."""
    keys, los, his = [], [], []
    for rr in p.get("ranges", []):
        key, lo, hi = _resolve_range(rr, is_date=is_date)
        keys.append((key, lo, hi))
        los.append(-np.inf if lo is None else float(lo))
        his.append(np.inf if hi is None else float(hi))
    if not keys:
        return None
    return keys, np.asarray(los, np.float64), np.asarray(his, np.float64)


class _ShardScopedParser:
    """Wraps the query parser so filter/filters agg queries that contain
    parent/child joins resolve against the WHOLE shard's segments (the join
    spans segments; per-segment execution of an unresolved HasChildNode
    raises — code review r5)."""

    def __init__(self, qp, segments):
        self._qp = qp
        self._segments = segments
        self.mappers = qp.mappers

    def parse(self, spec):
        node = self._qp.parse(spec)
        from ..query_dsl import contains_joins
        if contains_joins(node):
            from ..joins import resolve_joins
            node = resolve_joins(node, self._segments, self.mappers, 1)
        return node


def collect_shard(specs: list[AggSpec], segments: list[Segment],
                  masks: list,
                  query_parser=None, scores: list | None = None) -> dict:
    """Collect the agg tree over one shard's segments.
    masks[i]: bool[n_pad] — (match & live) for segment i from the query
    phase; either host numpy or a device array (kept on device, MaskView).
    scores[i]: optional f32[n_pad] score row per segment (top_hits needs it).
    query_parser: compiles filter/filters sub-queries (avoids circular import).
    """
    if query_parser is not None \
            and not isinstance(query_parser, _ShardScopedParser):
        query_parser = _ShardScopedParser(query_parser, segments)
    masks = [_mv(m) for m in masks]
    if scores is None:
        scores = [None] * len(segments)
    partials = {}
    for spec in specs:
        if spec.type == "terms":
            partials[spec.name] = _collect_terms_shard(
                spec, segments, masks, query_parser, scores)
            continue
        if spec.type == "significant_terms":
            partials[spec.name] = _collect_sig_terms_shard(
                spec, segments, masks, query_parser, scores)
            continue
        if spec.type == "children":
            partials[spec.name] = _collect_children_shard(
                spec, segments, masks, query_parser, scores)
            continue
        segs_partials = [
            _collect_one(spec, seg, mask, query_parser, scores_row=sc)
            for seg, mask, sc in zip(segments, masks, scores)]
        merged = segs_partials[0] if segs_partials else _empty_partial(spec)
        for p in segs_partials[1:]:
            merged = merge_partial(spec, merged, p)
        partials[spec.name] = merged
    return partials


def _collect_children_shard(spec: AggSpec, segments: list[Segment],
                            masks: list, qp,
                            scores: list | None = None) -> dict:
    """children agg (ref search/aggregations/bucket/children/
    ParentToChildrenAggregator): parent docs in the bucket -> their child
    docs of `type`. The p/c join spans segments (children landed wherever
    their own rows did), so it is a shard-level two-pass: collect parent
    ids, then mask children per segment via the _parent ordinal column.
    Supported at the top of the agg tree (per-bucket sub-agg joins would
    need the cross-segment bucket context)."""
    ctype = str(spec.params.get("type", ""))
    if scores is None:
        scores = [None] * len(segments)
    parent_ids: set = set()
    for seg, mask in zip(segments, masks):
        m = _mv(mask).np
        for r in np.flatnonzero(m[: seg.n_docs]):
            parent_ids.add(seg.ids[r])
    merged = None
    for seg, sc in zip(segments, scores):
        kc = seg.keywords.get("_parent")
        if kc is None:
            continue
        in_set = np.array([v in parent_ids for v in kc.values] + [False])
        ords = np.asarray(kc.ords)
        cmask = in_set[np.where(ords >= 0, ords, len(kc.values))]
        cmask &= np.array(
            [t == ctype for t in seg.types]
            + [False] * (seg.n_pad - seg.n_docs), bool)
        cmask &= seg.live_host
        part = _bucket_entry(spec, seg, cmask, qp, sc)
        merged = part if merged is None else _merge_entry(spec, merged, part)
    if merged is None:
        merged = {"doc_count": 0}
    return {"buckets": {"_children": merged}}


def _merge_entry(spec: AggSpec, a: dict, b: dict) -> dict:
    out = {"doc_count": a["doc_count"] + b["doc_count"]}
    if spec.subs:
        out["subs"] = {s.name: merge_partial(s, a["subs"][s.name],
                                             b["subs"][s.name])
                       for s in spec.subs}
    return out


def _collect_sig_terms_shard(spec: AggSpec, segments: list[Segment],
                             masks: list, qp,
                             scores: list | None = None) -> dict:
    """significant_terms (ref search/aggregations/bucket/significant/
    SignificantTermsAggregator + JLHScore): per-key FOREGROUND counts over
    the query matches and BACKGROUND counts over the whole index travel in
    the partial; the score is computed at render over the merged totals."""
    if scores is None:
        scores = [None] * len(segments)
    fg: dict = {}
    fg_total = 0
    bg_total = 0
    for seg, mask in zip(segments, masks):
        for key, c in _terms_counts(spec, seg, mask).items():
            fg[key] = fg.get(key, 0) + c
        mv = _mv(mask)
        if mv.dev is not None:
            from ...ops.aggs import count_mask
            fg_total += int(np.asarray(count_mask(mv.dev)))
        else:
            fg_total += int(mv.np.sum())
        bg_total += seg.root_live_count
    size = int(spec.params.get("size", 10)) or len(fg) or 1
    shard_size = int(spec.params.get("shard_size", size * 3 + 10))
    top = sorted(fg.items(), key=lambda kv: (-kv[1], str(kv[0])))[:shard_size]
    buckets: dict = {}
    for key, c in top:
        bg = 0
        sub_parts: dict = {}
        # ONE key-mask computation per (key, segment) feeds both the
        # background count and the sub-agg collect
        for seg, mask, sc in zip(segments, masks, scores):
            m_key = _terms_key_mask(spec, seg, key)
            if m_key is None:
                continue
            bg += int((m_key[: seg.n_pad]
                       & seg.root_live_host[: len(m_key)]).sum())
            if spec.subs:
                m = m_key & _mv(mask).np
                for s in spec.subs:
                    part = _collect_one(s, seg, m, qp, scores_row=sc)
                    prev = sub_parts.get(s.name)
                    sub_parts[s.name] = part if prev is None \
                        else merge_partial(s, prev, part)
        entry: dict = {"doc_count": int(c), "bg_count": bg}
        if spec.subs:
            entry["subs"] = {s.name: sub_parts.get(s.name, _empty_partial(s))
                             for s in spec.subs}
        buckets[key] = entry
    return {"buckets": buckets, "fg_total": fg_total, "bg_total": bg_total}


def terms_partial_from_counts(spec: AggSpec, counts: dict) -> dict:
    """Shard-level terms partial from merged per-key counts: shard_size
    truncation + other_doc_count/error_bound accounting. The ONE place the
    truncation order lives — shared by the per-segment collect below and
    the mesh lane's gathered count tensors (parallel/mesh_aggs.py), so the
    two paths can never disagree on which keys a shard reports."""
    size = int(spec.params.get("size", 10)) or len(counts) or 1
    shard_size = int(spec.params.get("shard_size", size * 3 + 10))
    items = sorted(counts.items(), key=lambda kv: (-kv[1], str(kv[0])))
    top = items[:shard_size]
    dropped = items[shard_size:]
    return {"buckets": {key: {"doc_count": int(c)} for key, c in top},
            "other_doc_count": int(sum(c for _, c in dropped)),
            "error_bound": int(top[-1][1]) if dropped else 0}


def _collect_terms_shard(spec: AggSpec, segments: list[Segment],
                         masks: list[np.ndarray], qp,
                         scores: list | None = None) -> dict:
    """Two-pass terms collection with correct shard_size semantics (ref
    bucket/terms/TermsAggregator shard_size over-collection): pass 1 counts
    every key across ALL segments (vectorized, cheap), the top shard_size
    keys are chosen from the MERGED counts, and only for those keys — and
    only if there are sub-aggs — does pass 2 build per-key doc masks.
    Truncation is accounted: other_doc_count + error_bound travel in the
    partial so the coordinator's reduce can report them."""
    counts: dict = {}
    for seg, mask in zip(segments, masks):
        for key, c in _terms_counts(spec, seg, mask).items():
            counts[key] = counts.get(key, 0) + c
    if not spec.subs:
        return terms_partial_from_counts(spec, counts)
    size = int(spec.params.get("size", 10)) or len(counts) or 1
    shard_size = int(spec.params.get("shard_size", size * 3 + 10))
    items = sorted(counts.items(), key=lambda kv: (-kv[1], str(kv[0])))
    top = items[:shard_size]
    dropped = items[shard_size:]
    buckets: dict = {}
    for key, c in top:
        entry: dict = {"doc_count": int(c)}
        if spec.subs:
            if scores is None:
                scores = [None] * len(segments)
            sub_parts: dict = {}
            for seg, mask, sc in zip(segments, masks, scores):
                m = _terms_key_mask(spec, seg, key)
                if m is None:
                    continue
                m = m & _mv(mask).np
                for s in spec.subs:
                    part = _collect_one(s, seg, m, qp, scores_row=sc)
                    prev = sub_parts.get(s.name)
                    sub_parts[s.name] = part if prev is None \
                        else merge_partial(s, prev, part)
            entry["subs"] = {s.name: sub_parts.get(s.name, _empty_partial(s))
                             for s in spec.subs}
        buckets[key] = entry
    return {"buckets": buckets,
            "other_doc_count": int(sum(c for _, c in dropped)),
            "error_bound": int(top[-1][1]) if dropped else 0}


def _terms_counts(spec: AggSpec, seg: Segment, mask) -> dict:
    """Pass 1: key -> doc_count for one segment, fully vectorized. Device
    masks take the fused masked-bincount kernel (ops/aggs.py) — only the
    [V] counts vector crosses to host."""
    mask = _mv(mask)
    field = spec.params["field"]
    kc = seg.keywords.get(field)
    if kc is not None:
        if mask.dev is not None:
            from ...ops.aggs import masked_bincount
            counts = np.asarray(masked_bincount(
                kc.ords, mask.dev, n_bins=len(kc.values)))
        else:
            ords, values = _keyword_column(seg, field)
            sel = mask.np & (ords >= 0)
            counts = np.bincount(ords[sel], minlength=len(values))
        return {kc.values[o]: int(counts[o]) for o in np.nonzero(counts)[0]}
    col = _numeric_column(seg, field)
    if col is not None:
        vals, valid = col
        sel = mask.np & valid[: len(mask.np)]
        uniq, ucounts = np.unique(vals[sel], return_counts=True)
        if vals.dtype.kind == "i":
            return {int(u): int(c) for u, c in zip(uniq, ucounts)}
        return {(int(u) if float(u).is_integer() else float(u)): int(c)
                for u, c in zip(uniq, ucounts)}
    # analyzed text: token counts via the postings lists (fielddata-on-
    # analyzed-string behavior, ref index/fielddata/)
    fx = seg.text.get(field)
    if fx is None:
        return {}
    P = fx.n_postings
    doc_of = np.asarray(fx.doc_ids)[:P]
    term_of = np.repeat(np.arange(len(fx.term_lens)), fx.term_lens)
    hit = mask.np[np.minimum(doc_of, len(mask.np) - 1)]
    counts = np.bincount(term_of[hit], minlength=len(fx.term_lens))
    terms_sorted = list(fx.terms)
    return {terms_sorted[t]: int(counts[t]) for t in np.nonzero(counts)[0]}


def _terms_key_mask(spec: AggSpec, seg: Segment, key) -> np.ndarray | None:
    """Pass 2: bool[n_pad] of docs holding `key` (pre-query-mask)."""
    field = spec.params["field"]
    kw = _keyword_column(seg, field)
    if kw is not None:
        ords, _ = kw
        kc = seg.keywords[field]
        o = kc.ord_of(str(key))
        if o < 0:
            return None
        return ords == o
    col = _numeric_column(seg, field)
    if col is not None:
        vals, valid = col
        return (vals == key) & valid
    fx = seg.text.get(field)
    if fx is None:
        return None
    s, ln, tid = fx.lookup(str(key))
    if tid < 0:
        return None
    m = np.zeros(seg.n_pad, bool)
    m[np.asarray(fx.doc_ids)[s:s + ln]] = True
    return m


def _empty_partial(spec: AggSpec) -> dict:
    if spec.type == "terms":
        return {"buckets": {}, "other_doc_count": 0, "error_bound": 0}
    if spec.type == "significant_terms":
        return {"buckets": {}, "fg_total": 0, "bg_total": 0}
    if spec.type in BUCKET_TYPES:
        return {"buckets": {}}
    if spec.type == "top_hits":
        return {"total": 0, "top": []}
    if spec.type == "geo_bounds":
        return {"top": -math.inf, "bottom": math.inf,
                "left": math.inf, "right": -math.inf}
    if spec.type == "scripted_metric":
        return {"states": []}
    return _metric_collect(spec, np.zeros(0), np.zeros(0, bool))


def _collect_one(spec: AggSpec, seg: Segment, mask,
                 qp=None, scores_row=None) -> dict:
    if spec.type == "top_hits":
        return _top_hits_segment(spec, seg, _mv(mask).np, scores_row)
    if spec.type == "terms":               # as a sub-aggregation
        return _collect_terms_shard(spec, [seg], [mask], qp, [scores_row])
    if spec.type == "significant_terms":   # as a sub-aggregation
        return _collect_sig_terms_shard(spec, [seg], [mask], qp,
                                        [scores_row])
    if spec.type in METRIC_TYPES:
        return _metric_segment(spec, seg, mask)
    return _bucket_segment(spec, seg, _mv(mask), qp, scores_row)


def _top_hits_segment(spec: AggSpec, seg: Segment, mask: np.ndarray,
                      scores_row) -> dict:
    """top_hits (ref metrics/tophits/TopHitsAggregator): the top-scoring
    matched docs of the enclosing bucket, as real hit dicts so partials
    merge across segments and shards by score."""
    size = int(spec.params.get("size", 3))
    sel = np.flatnonzero(mask[: seg.n_pad])
    sel = sel[sel < seg.n_docs]
    if scores_row is not None and len(sel):
        sc = np.asarray(scores_row)[sel].astype(np.float64)
        order = np.argsort(-sc, kind="stable")[:size]
    else:
        sc = None
        order = np.arange(min(size, len(sel)))
    hits = []
    for j in order:
        d = int(sel[j])
        hits.append({"_id": seg.ids[d], "_type": seg.types[d],
                     "_score": float(sc[j]) if sc is not None else None,
                     "_source": seg.stored[d]})
    return {"total": int(mask.sum()), "top": hits}


# -- metric aggs ------------------------------------------------------------

_DEVICE_STATS_TYPES = {"min", "max", "sum", "avg", "value_count", "stats",
                       "extended_stats"}


def _metric_segment(spec: AggSpec, seg: Segment, mask) -> dict:
    mask = _mv(mask)
    field = spec.params.get("field")
    if spec.type in _DEVICE_STATS_TYPES and field and mask.dev is not None:
        nc = seg.numerics.get(field)
        if nc is not None:
            # one fused device program -> a 5-scalar partial
            from ...ops.aggs import masked_stats
            cnt, s, ss, mn, mx = np.asarray(
                masked_stats(nc.vals, nc.missing, mask.dev))
            return {"count": int(cnt), "sum": float(s), "sum_sq": float(ss),
                    "min": float(mn) if cnt else math.inf,
                    "max": float(mx) if cnt else -math.inf}
    mask = mask.np
    if spec.type == "geo_bounds" and field:
        # ref search/aggregations/metrics/geobounds/GeoBoundsAggregator
        la = _numeric_column(seg, f"{field}.lat")
        lo = _numeric_column(seg, f"{field}.lon")
        if la is None or lo is None:
            return {"top": -math.inf, "bottom": math.inf,
                    "left": math.inf, "right": -math.inf}
        sel = mask & la[1][:len(mask)] & lo[1][:len(mask)]
        if not sel.any():
            return {"top": -math.inf, "bottom": math.inf,
                    "left": math.inf, "right": -math.inf}
        lats = la[0][sel]
        lons = lo[0][sel]
        return {"top": float(lats.max()), "bottom": float(lats.min()),
                "left": float(lons.min()), "right": float(lons.max())}
    if spec.type == "scripted_metric":
        # ref search/aggregations/metrics/scripted/ScriptedMetricAggregator:
        # init/map per doc (AST-whitelisted dialect, script/engine.py),
        # combine per segment; partials carry per-segment states for the
        # final reduce_script at render time
        from ...script.engine import run_agg_script
        params = dict(spec.params.get("params") or {})
        agg: dict = {}
        if spec.params.get("init_script"):
            run_agg_script(spec.params["init_script"], {"_agg": agg},
                           params)
        map_src = spec.params.get("map_script")
        if map_src:
            from ...script.engine import doc_values_view
            for d in np.flatnonzero(mask[: seg.n_docs]):
                d = int(d)
                if not seg.live_host[d] or seg.types[d].startswith("__"):
                    continue
                # same doc['field'].value accessor view as script queries
                # and script_fields — one dialect everywhere
                run_agg_script(
                    map_src,
                    {"_agg": agg, "doc": doc_values_view(seg.stored[d]),
                     "_source": seg.stored[d]}, params)
        state = agg
        if spec.params.get("combine_script"):
            out = run_agg_script(spec.params["combine_script"],
                                 {"_agg": agg}, params)
            if out is not None:
                state = out
        return {"states": [state]}
    if spec.type == "cardinality" and field:
        kw = _keyword_column(seg, field)
        if kw is not None:
            ords, values = kw
            sel = mask & (ords >= 0)
            uniq = np.unique(ords[sel])
            hll = HyperLogLog()
            hll.add([values[o] for o in uniq])
            return {"hll": hll}
        if field in seg.text:   # distinct tokens among matched docs
            fx = seg.text[field]
            doc_of = np.asarray(fx.doc_ids)[:fx.n_postings]
            term_of = np.repeat(np.arange(len(fx.term_lens)), fx.term_lens)
            hit = mask[np.minimum(doc_of, len(mask) - 1)]
            terms_sorted = list(fx.terms)
            hll = HyperLogLog()
            hll.add([terms_sorted[t] for t in np.unique(term_of[hit])])
            return {"hll": hll}
    col = _numeric_column(seg, field) if field else None
    if col is None:
        return _metric_collect(spec, np.zeros(0), np.zeros(0, bool))
    vals, valid = col
    n = min(len(mask), len(valid))
    return _metric_collect(spec, vals[:n], valid[:n] & mask[:n])


def _metric_collect(spec: AggSpec, vals: np.ndarray, sel: np.ndarray) -> dict:
    v = vals[sel] if len(vals) else vals
    if spec.type == "cardinality":
        hll = HyperLogLog()
        hll.add_hashes(_hash64(v))
        return {"hll": hll}
    if spec.type == "percentiles":
        td = TDigest()
        td.add(v)
        return {"tdigest": td,
                "percents": spec.params.get("percents",
                                            [1, 5, 25, 50, 75, 95, 99])}
    count = int(v.size)
    vf = v.astype(np.float64, copy=False)   # stats in f64 (i64*i64 overflows)
    return {"count": count, "sum": float(vf.sum()) if count else 0.0,
            "min": float(vf.min()) if count else math.inf,
            "max": float(vf.max()) if count else -math.inf,
            "sum_sq": float((vf * vf).sum()) if count else 0.0}


# -- bucket aggs ------------------------------------------------------------

def _col_minmax(seg: Segment, field: str, nc) -> tuple[float, float]:
    """Cached (min, max) of a numeric column — one device reduction per
    immutable segment, reused by every histogram over it."""
    cache = getattr(seg, "_minmax_cache", None)
    if cache is None:
        cache = {}
        seg._minmax_cache = cache
    if field not in cache:
        from ...ops.aggs import col_minmax
        mn, mx = np.asarray(col_minmax(nc.vals, nc.missing))
        cache[field] = (float(mn), float(mx))
    return cache[field]


_FIXED_INTERVAL_MS = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000,
                      "d": 86_400_000, "w": 7 * 86_400_000}
_MAX_DEVICE_BINS = 1 << 14


def _fixed_interval_ms(interval: str) -> float | None:
    m = re.match(r"^(\d+)?\s*(ms|s|m|h|d|w|second|minute|hour|day|week)$",
                 str(interval).strip())
    if not m:
        return None
    mult = int(m.group(1) or 1)
    unit = {"second": "s", "minute": "m", "hour": "h", "day": "d",
            "week": "w"}.get(m.group(2), m.group(2))
    return float(mult * _FIXED_INTERVAL_MS[unit])


def _device_histogram(spec: AggSpec, seg: Segment, mv: "MaskView",
                      nc, interval: float) -> dict | None:
    """Leaf histogram collect fused on device (VERDICT r4 #3): bucket id =
    affine transform of the column, ONE bincount per (segment, agg); only
    the counts vector crosses to host. None -> host fallback (sub-aggs
    need per-bucket masks; huge ranges exceed the bin cap)."""
    if spec.subs or mv.dev is None or interval <= 0:
        return None
    mn, mx = _col_minmax(seg, spec.params["field"], nc)
    if not np.isfinite(mn) or not np.isfinite(mx):
        return {"buckets": {}}
    base = math.floor(mn / interval) * interval
    n_bins = int((mx - base) // interval) + 1
    if n_bins > _MAX_DEVICE_BINS:
        return None
    from ...ops.aggs import hist_operands, masked_histogram
    counts = np.asarray(masked_histogram(
        nc.vals, nc.missing, mv.dev,
        *hist_operands(nc.dtype == "i64", base, interval), n_bins=n_bins))
    out = {}
    for i in np.nonzero(counts)[0]:
        out[float(base + i * interval)] = {"doc_count": int(counts[i])}
    return {"buckets": out}


def _bucket_segment(spec: AggSpec, seg: Segment, mask,
                    qp=None, scores_row=None) -> dict:
    """Compute per-doc bucket keys, then vectorized counts + sub-collects.
    Leaf histogram/date_histogram/range over numeric columns collect ON
    DEVICE (ops/aggs.py kernels) when the query mask is device-resident."""
    t = spec.type
    p = spec.params
    n = seg.n_pad
    mv = _mv(mask)

    if t in ("histogram", "date_histogram", "range", "date_range") \
            and mv.dev is not None and not spec.subs:
        field = p.get("field")
        nc = seg.numerics.get(field) if field else None
        if nc is not None:
            if t == "histogram":
                r = _device_histogram(spec, seg, mv, nc,
                                      float(p["interval"]))
                if r is not None:
                    return r
            elif t == "date_histogram":
                iv = _fixed_interval_ms(p.get("interval", "1d"))
                if iv is not None:
                    r = _device_histogram(spec, seg, mv, nc, iv)
                    if r is not None:
                        return r
            else:   # range / date_range: all ranges in one device program
                bounds = _range_bounds(p, is_date=(t == "date_range"))
                if bounds is not None:
                    keys, los, his = bounds
                    from ...ops.aggs import masked_ranges
                    counts = np.asarray(masked_ranges(
                        nc.vals, nc.missing, mv.dev, los, his))
                    out = {}
                    for (key, lo, hi), cnt in zip(keys, counts):
                        out[key] = {"doc_count": int(cnt),
                                    "from": lo, "to": hi}
                    return {"buckets": out}

    mask = mv.np

    if t == "composite":
        return _composite_segment(spec, seg, mask)

    if t == "global":   # ignores the query: all live docs (ref bucket/global/)
        live = np.asarray(seg.live)
        return {"buckets": {"_global": _bucket_entry(
            spec, seg, live, qp, scores_row)}}

    if t == "nested":
        # switch the doc set from ROOT rows to this path's nested block
        # rows whose root is in the current bucket (ref search/aggregations/
        # bucket/nested/NestedAggregator.java — child-doc iteration becomes
        # one parent-gather over the block-join column)
        path = str(p.get("path", ""))
        kc = seg.keywords.get("_nested_path")
        child = np.zeros(n, bool)
        if kc is not None and seg.parent_of is not None:
            o = kc.ord_of(path)
            if o >= 0:
                is_child = (np.asarray(kc.ords) == o) \
                    & seg.live_host & (seg.parent_of >= 0)
                child = is_child & mask[np.maximum(seg.parent_of, 0)]
        return {"buckets": {"_nested": _bucket_entry(spec, seg, child, qp,
                                                     scores_row)}}

    if t == "reverse_nested":
        # back out of nested context to the root docs (ref bucket/nested/
        # ReverseNestedAggregator.java; path-targeted variants reduce to
        # the root here because parent_of always points at the root row)
        roots = np.zeros(n, bool)
        if seg.parent_of is not None:
            sel = np.flatnonzero(mask & (seg.parent_of >= 0))
            roots[seg.parent_of[sel]] = True
            roots &= seg.root_live_host
        return {"buckets": {"_reverse": _bucket_entry(spec, seg, roots, qp,
                                                      scores_row)}}

    if t == "filter":
        sub_mask = _filter_mask(p, seg, qp)
        m = mask & sub_mask
        return {"buckets": {"_filter": _bucket_entry(spec, seg, m, qp,
                                                     scores_row)}}

    if t == "filters":
        out = {}
        flt = p.get("filters", {})
        for fname, fspec in flt.items():
            m = mask & _filter_mask_query(fspec, seg, qp)
            out[fname] = _bucket_entry(spec, seg, m, qp, scores_row)
        return {"buckets": out}

    if t == "missing":
        field = p["field"]
        col = _numeric_column(seg, field)
        kw = _keyword_column(seg, field)
        txt = _text_present_mask(seg, field)
        if col is not None:
            miss = ~col[1]
        elif kw is not None:
            miss = kw[0] < 0
        elif txt is not None:
            miss = ~txt   # analyzed field: "has it" == any posting
        else:
            miss = np.ones(n, bool)
        m = mask & miss[:len(mask)]
        return {"buckets": {"_missing": _bucket_entry(spec, seg, m, qp,
                                                      scores_row)}}

    if t in ("histogram", "date_histogram"):
        field = p["field"]
        col = _numeric_column(seg, field)
        if col is None:
            return {"buckets": {}}
        vals, valid = col
        sel = mask & valid[:len(mask)]
        if t == "histogram":
            interval = float(p["interval"])
            if vals.dtype.kind == "i" and interval.is_integer():
                step = int(interval)   # exact int bucketing for longs
                keys = (vals // step) * step
            else:
                keys = np.floor(vals.astype(np.float64) / interval) * interval
        else:
            keys = _date_round(vals, str(p.get("interval", "1d")))
        out = {}
        for u in np.unique(keys[sel]):
            m = sel & (keys == u)
            out[float(u)] = _bucket_entry(spec, seg, m, qp, scores_row)
        return {"buckets": out}

    if t in ("range", "date_range"):
        field = p["field"]
        col = _numeric_column(seg, field)
        if col is None:
            return {"buckets": {}}
        vals, valid = col
        sel = mask & valid[:len(mask)]
        out = {}
        for r in p.get("ranges", []):
            key, lo, hi = _resolve_range(r, is_date=(t == "date_range"))
            m = sel.copy()
            if lo is not None:
                m &= vals >= float(lo)
            if hi is not None:
                m &= vals < float(hi)
            e = _bucket_entry(spec, seg, m, qp, scores_row)
            e["from"] = lo
            e["to"] = hi
            out[key] = e
        return {"buckets": out}

    if t == "geohash_grid":
        # ref search/aggregations/bucket/geogrid/GeoHashGridAggregator:
        # bucket key = the doc's geohash cell at `precision`
        field = p["field"]
        la = _numeric_column(seg, f"{field}.lat")
        lo = _numeric_column(seg, f"{field}.lon")
        if la is None or lo is None:
            return {"buckets": {}}
        from ..geo import encode_geohash
        precision = int(p.get("precision", 5))
        sel = mask & la[1][:len(mask)] & lo[1][:len(mask)]
        idx = np.flatnonzero(sel)
        keys = np.array([encode_geohash(float(la[0][d]), float(lo[0][d]),
                                        precision) for d in idx])
        out = {}
        for u in np.unique(keys) if len(idx) else []:
            m = np.zeros(n, bool)
            m[idx[keys == u]] = True
            out[str(u)] = _bucket_entry(spec, seg, m, qp, scores_row)
        return {"buckets": out}

    if t == "geo_distance":
        # ref search/aggregations/bucket/range/geodistance/
        # GeoDistanceParser: range buckets over haversine distance from an
        # origin point, in the requested unit
        from ..geo import parse_geo_point, unit_meters
        field = p["field"]
        la = _numeric_column(seg, f"{field}.lat")
        lo = _numeric_column(seg, f"{field}.lon")
        if la is None or lo is None:
            return {"buckets": {}}
        from ..geo import haversine_m
        olat, olon = parse_geo_point(p["origin"])
        unit = unit_meters(str(p.get("unit", "m")))
        dist = np.asarray(haversine_m(olat, olon, la[0], lo[0])) / unit
        sel = mask & la[1][:len(mask)] & lo[1][:len(mask)]
        out = {}
        for r in p.get("ranges", []):
            lo_v = r.get("from")
            hi_v = r.get("to")
            key = r.get("key") or (
                f"{'*' if lo_v is None else float(lo_v)}-"
                f"{'*' if hi_v is None else float(hi_v)}")
            m = sel.copy()
            if lo_v is not None:
                m &= dist >= float(lo_v)
            if hi_v is not None:
                m &= dist < float(hi_v)
            e = _bucket_entry(spec, seg, m, qp, scores_row)
            e["from"] = None if lo_v is None else float(lo_v)
            e["to"] = None if hi_v is None else float(hi_v)
            out[key] = e
        return {"buckets": out}

    if t == "sampler":
        # ref search/aggregations/bucket/sampler/SamplerAggregator: sub-aggs
        # run over only the TOP-scoring shard_size matched docs
        shard_size = int(p.get("shard_size", 100))
        sel = np.flatnonzero(mask)
        if scores_row is not None and len(sel) > shard_size:
            sc = np.asarray(scores_row)[sel].astype(np.float64)
            keep = sel[np.argsort(-sc, kind="stable")[:shard_size]]
        else:
            keep = sel[:shard_size]
        m = np.zeros(n, bool)
        m[keep] = True
        return {"buckets": {"_sample": _bucket_entry(spec, seg, m, qp,
                                                     scores_row)}}

    if t == "children":
        raise AggregationParsingException(
            "children aggregation is supported at the top of the agg tree "
            "(the parent/child join needs cross-segment bucket context)")
    raise AggregationParsingException(f"unsupported bucket agg [{t}]")


def _bucket_entry(spec: AggSpec, seg: Segment, mask: np.ndarray, qp,
                  scores_row=None) -> dict:
    entry = {"doc_count": int(mask.sum())}
    if spec.subs:
        entry["subs"] = {
            s.name: _collect_one(s, seg, mask, qp, scores_row=scores_row)
            for s in spec.subs}
    return entry


# -- composite agg ----------------------------------------------------------

def _comp_norm(v) -> int | float:
    """Normalize a numeric composite key element to a plain python value —
    ints stay exact ints (snowflake ids, epoch millis), integral floats
    collapse to int so the after-key round-trips through JSON unchanged."""
    if isinstance(v, (int, np.integer)):
        return int(v)
    f = float(v)
    return int(f) if f.is_integer() else f


def _composite_segment(spec: AggSpec, seg: Segment, mask: np.ndarray) -> dict:
    """composite collect over one segment (ref search/aggregations/bucket/
    composite/CompositeAggregator, backported to the 2.0 framework): each
    source produces a per-doc key column; docs missing ANY source value
    drop (ES composite default); the per-source columns factorize via
    np.unique and combine into one packed code, so the whole segment's
    tuple counting is a single bincount — no per-bucket python loop.
    Partial: {"buckets": {key_tuple: {"doc_count": n}}} — tuples are
    hashable, so the generic cross-segment/shard merge applies as-is."""
    n = seg.n_pad
    sel = mask[:n].copy()
    cols: list[tuple[str, np.ndarray, Any]] = []   # (kind, per-doc, vocab)
    for _sname, stype, sp in _composite_sources(spec):
        field = sp.get("field")
        if stype == "terms":
            kw = seg.keywords.get(field)
            if kw is not None:
                ords = np.asarray(kw.ords)[:n]
                sel &= ords >= 0
                cols.append(("kw", ords, kw.values))
                continue
        col = _numeric_column(seg, field)
        if col is None:
            return {"buckets": {}}
        vals, valid = col
        vals, valid = vals[:n], valid[:n]
        if stype == "terms":
            keys = vals
        elif stype == "histogram":
            interval = float(sp["interval"])
            if vals.dtype.kind == "i" and interval.is_integer():
                keys = (vals // int(interval)) * int(interval)
            else:
                keys = np.floor(vals.astype(np.float64)
                                / interval) * interval
        else:   # date_histogram
            keys = _date_round(vals, str(sp.get("interval", "1d")))
        sel &= valid[: len(sel)]
        cols.append(("num", keys, None))
    idx = np.flatnonzero(sel)
    if not len(idx):
        return {"buckets": {}}
    codes = np.zeros(len(idx), np.int64)
    uniqs: list[tuple[str, np.ndarray, Any]] = []
    for kind, arr, vocab in cols:
        u, inv = np.unique(arr[idx], return_inverse=True)
        uniqs.append((kind, u, vocab))
        codes = codes * np.int64(len(u)) + inv
    cu, ccounts = np.unique(codes, return_counts=True)
    buckets: dict = {}
    for code, cnt in zip(cu, ccounts):
        parts = []
        c = int(code)
        for kind, u, vocab in reversed(uniqs):
            c, i = divmod(c, len(u))
            v = u[i]
            parts.append(str(vocab[int(v)]) if kind == "kw"
                         else _comp_norm(v))
        buckets[tuple(reversed(parts))] = {"doc_count": int(cnt)}
    return {"buckets": buckets}


def _comp_sort_key(key: tuple) -> tuple:
    """Total order over composite key tuples: per element, strings sort
    among strings and numbers among numbers (type tag first), so mixed
    after-key inputs from JSON can never raise on comparison."""
    return tuple(("s", v) if isinstance(v, str) else ("n", float(v))
                 for v in key)


def _render_composite(spec: AggSpec, p: dict) -> dict:
    """Render after the global merge: sort the merged bucket space
    ascending, drop everything <= `after`, truncate to `size`, and emit
    `after_key` = the last returned bucket. Because the sort runs over the
    FULLY merged partials (every lane funnels through the same reduce),
    consecutive pages are a disjoint exact cover of the bucket space and
    identical on every serving lane."""
    names = [s[0] for s in _composite_sources(spec)]
    size = int(spec.params.get("size", 10))
    items = sorted(p.get("buckets", {}).items(),
                   key=lambda kv: _comp_sort_key(kv[0]))
    after = spec.params.get("after")
    if after:
        missing = [nm for nm in names if nm not in after]
        if missing:
            raise AggregationParsingException(
                f"composite [{spec.name}]: after key is missing sources "
                f"{missing}")
        ak = _comp_sort_key(tuple(after[nm] for nm in names))
        items = [kv for kv in items if _comp_sort_key(kv[0]) > ak]
    page = items[:size]
    out: dict = {"buckets": [
        {"key": dict(zip(names, k)), "doc_count": e["doc_count"]}
        for k, e in page]}
    if page:
        out["after_key"] = dict(zip(names, page[-1][0]))
    return out


def _filter_mask(params: dict, seg: Segment, qp) -> np.ndarray:
    return _filter_mask_query(params, seg, qp)


def _filter_mask_query(query_spec: dict, seg: Segment, qp) -> np.ndarray:
    """Compile + run a filter query against one segment -> bool[n_pad]."""
    if qp is None:
        raise AggregationParsingException(
            "filter aggregation requires a query parser")
    from ..query_dsl import SegmentContext, CollectionStats
    node = qp.parse(query_spec)
    terms_by_field: dict[str, set] = {}
    node.collect_terms(terms_by_field)
    stats = CollectionStats.from_segments([seg], terms_by_field)
    _, match = node.execute(SegmentContext(seg, 1, stats))
    return np.asarray(match)[0] & np.asarray(seg.live)


def _range_key(lo, hi) -> str:
    fmt = lambda x: "*" if x is None else (  # noqa: E731
        str(int(x)) if float(x).is_integer() else str(float(x)))
    return f"{fmt(lo)}-{fmt(hi)}"


def _resolve_range(r: dict, is_date: bool) -> tuple[str, float | None, float | None]:
    """Resolve a range spec's bounds (date-math for date_range) and its
    bucket key — the SINGLE place keys are derived, used by both collect and
    render so they can never disagree."""
    lo, hi = r.get("from"), r.get("to")
    if is_date:
        from ..query_parser import eval_date_math
        lo = eval_date_math(str(lo)) if isinstance(lo, str) else lo
        hi = eval_date_math(str(hi)) if isinstance(hi, str) else hi
    return r.get("key", _range_key(lo, hi)), lo, hi


# -- date rounding ----------------------------------------------------------

_FIXED_MS = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000,
             "d": 86_400_000}
_DAY = 86_400_000


def _date_round(ms: np.ndarray, interval: str) -> np.ndarray:
    """Round epoch-millis to bucket starts. Fixed units on the value array;
    calendar units (week/month/quarter/year) via exact calendar math
    (ref common/rounding/TimeZoneRounding.java, UTC only)."""
    iv = interval.strip()
    m = re.match(r"^(\d+)?\s*(ms|s|m|h|d|w|M|q|y|minute|hour|day|week|month|"
                 r"quarter|year|second)$", iv)
    if not m:
        raise AggregationParsingException(f"bad interval [{interval}]")
    n = int(m.group(1) or 1)
    unit = {"second": "s", "minute": "m", "hour": "h", "day": "d",
            "week": "w", "month": "M", "quarter": "q", "year": "y"}.get(
                m.group(2), m.group(2))
    if unit in _FIXED_MS:
        step = n * _FIXED_MS[unit]
        return np.floor_divide(ms, step) * step
    days = np.floor_divide(ms, _DAY).astype(np.int64)
    if unit == "w":
        # 1970-01-01 is a Thursday; ISO weeks start Monday
        dow = (days + 3) % 7
        start = (days - dow) * _DAY
        return start.astype(np.float64)
    d64 = days.astype("datetime64[D]")
    if unit == "M":
        mo = d64.astype("datetime64[M]")
        if n > 1:
            mo_i = mo.astype(np.int64)
            mo = (np.floor_divide(mo_i, n) * n).astype("datetime64[M]")
        return mo.astype("datetime64[ms]").astype(np.int64).astype(np.float64)
    if unit == "q":
        mo_i = d64.astype("datetime64[M]").astype(np.int64)
        q = np.floor_divide(mo_i, 3) * 3
        return q.astype("datetime64[M]").astype("datetime64[ms]") \
            .astype(np.int64).astype(np.float64)
    # year
    y = d64.astype("datetime64[Y]")
    if n > 1:
        y_i = y.astype(np.int64)
        y = (np.floor_divide(y_i, n) * n).astype("datetime64[Y]")
    return y.astype("datetime64[ms]").astype(np.int64).astype(np.float64)


# ---------------------------------------------------------------------------
# Reduce: merge partials (segments, then shards)
# ---------------------------------------------------------------------------

def merge_partial(spec: AggSpec, a: dict, b: dict) -> dict:
    if spec.type in METRIC_TYPES:
        return _merge_metric(spec, a, b)
    out = dict(a)
    if spec.type == "terms":
        out["other_doc_count"] = a.get("other_doc_count", 0) \
            + b.get("other_doc_count", 0)
        out["error_bound"] = a.get("error_bound", 0) + b.get("error_bound", 0)
    if spec.type == "significant_terms":
        out["fg_total"] = a.get("fg_total", 0) + b.get("fg_total", 0)
        out["bg_total"] = a.get("bg_total", 0) + b.get("bg_total", 0)
    buckets = dict(a.get("buckets", {}))
    for key, eb in b.get("buckets", {}).items():
        ea = buckets.get(key)
        if ea is None:
            buckets[key] = eb
        else:
            merged = {"doc_count": ea["doc_count"] + eb["doc_count"]}
            if "bg_count" in ea or "bg_count" in eb:
                merged["bg_count"] = ea.get("bg_count", 0) \
                    + eb.get("bg_count", 0)
            for extra in ("from", "to"):
                if extra in ea:
                    merged[extra] = ea[extra]
            if spec.subs:
                merged["subs"] = {
                    s.name: merge_partial(s, ea["subs"][s.name],
                                          eb["subs"][s.name])
                    for s in spec.subs}
            buckets[key] = merged
    out["buckets"] = buckets
    return out


def _merge_metric(spec: AggSpec, a: dict, b: dict) -> dict:
    if spec.type == "top_hits":
        size = int(spec.params.get("size", 3))
        merged = a.get("top", []) + b.get("top", [])
        merged.sort(key=lambda h: -(h["_score"]
                                    if h["_score"] is not None else -1e300))
        return {"total": a.get("total", 0) + b.get("total", 0),
                "top": merged[:size]}
    if spec.type == "cardinality":
        return {"hll": a["hll"].merge(b["hll"])}
    if spec.type == "percentiles":
        return {"tdigest": a["tdigest"].merge(b["tdigest"]),
                "percents": a.get("percents", b.get("percents"))}
    if spec.type == "geo_bounds":
        return {"top": max(a["top"], b["top"]),
                "bottom": min(a["bottom"], b["bottom"]),
                "left": min(a["left"], b["left"]),
                "right": max(a["right"], b["right"])}
    if spec.type == "scripted_metric":
        return {"states": a.get("states", []) + b.get("states", [])}
    return {"count": a["count"] + b["count"], "sum": a["sum"] + b["sum"],
            "min": min(a["min"], b["min"]), "max": max(a["max"], b["max"]),
            "sum_sq": a["sum_sq"] + b["sum_sq"]}


def merge_shard_partials(specs: list[AggSpec], shard_partials: list[dict]) -> dict:
    """The cross-shard aggregation reduce
    (ref SearchPhaseController.merge:282-399 InternalAggregations.reduce)."""
    out: dict = {}
    for spec in specs:
        parts = [sp[spec.name] for sp in shard_partials if spec.name in sp]
        if not parts:
            out[spec.name] = _empty_partial(spec)
            continue
        merged = parts[0]
        for p in parts[1:]:
            merged = merge_partial(spec, merged, p)
        out[spec.name] = merged
    return out


# ---------------------------------------------------------------------------
# Render: ES 2.0 response shapes
# ---------------------------------------------------------------------------

def _decimal_format(pattern: str, v) -> str:
    """Minimal Java DecimalFormat: literal prefix/suffix around a numeric
    pattern of #/0/,/. — fraction digits from the 0s/#s after the point
    (ref org.elasticsearch.search.aggregations ValueFormatter.Number)."""
    import re as _re
    m = _re.search(r"[#0][#0,.]*", pattern)
    if not m:
        return pattern
    num = m.group(0)
    prefix, suffix = pattern[:m.start()], pattern[m.end():]
    if "." in num:
        frac = num.split(".", 1)[1]
        min_frac = frac.count("0")
        max_frac = len(frac)
        s = f"{float(v):.{max_frac}f}"
        if max_frac > min_frac:
            # strip OPTIONAL (#) fraction digits only, never below min_frac
            ip, fp = s.split(".")
            fp = fp[:min_frac] + fp[min_frac:].rstrip("0")
            s = ip + ("." + fp if fp else "")
    else:
        s = str(int(round(float(v))))
    if "," in num:
        parts = s.split(".")
        parts[0] = f"{int(parts[0]):,}"
        s = ".".join(parts)
    return prefix + s + suffix


def _iso(ms: float) -> str:
    return datetime.fromtimestamp(ms / 1000.0, tz=timezone.utc) \
        .strftime("%Y-%m-%dT%H:%M:%S.") + f"{int(ms) % 1000:03d}Z"


def render(specs: list[AggSpec], partials: dict) -> dict:
    return {spec.name: _render_one(spec, partials[spec.name])
            for spec in specs}


def _render_one(spec: AggSpec, p: dict) -> dict:
    t = spec.type
    if t in METRIC_TYPES:
        return _render_metric(spec, p)

    if t == "composite":
        return _render_composite(spec, p)

    buckets = p.get("buckets", {})

    def rb(key, entry, key_field=True):
        b: dict = {}
        if key_field:
            b["key"] = key
        b["doc_count"] = entry["doc_count"]
        for extra in ("from", "to"):
            if extra in entry and entry[extra] is not None:
                b[extra] = entry[extra]
        for s in spec.subs:
            b[s.name] = _render_one(s, entry.get("subs", {}).get(
                s.name, _empty_partial(s)))
        return b

    if t == "terms":
        size = int(spec.params.get("size", 10)) or len(buckets)
        order = spec.params.get("order", {"_count": "desc"})
        if isinstance(order, list):       # ES list form: primary key first
            order = order[0] if order else {"_count": "desc"}
        if not isinstance(order, dict) or not order:
            order = {"_count": "desc"}
        okey, odir = next(iter(order.items()))
        reverse = odir == "desc"
        items = list(buckets.items())
        if okey == "_term":
            items.sort(key=lambda kv: str(kv[0]), reverse=reverse)
        else:
            # _count ties break by term ascending, like the reference's
            # InternalTerms comparator — otherwise equal-count buckets come
            # out in shard-merge order, nondeterministic across layouts
            # tie-break is term ASCENDING in both directions (ref
            # InternalOrder CompoundOrder always appends term(true))
            items.sort(key=lambda kv: str(kv[0]))
            items.sort(key=lambda kv: kv[1]["doc_count"], reverse=reverse)
        top = items[:size]
        other = sum(e["doc_count"] for _, e in items[size:]) \
            + p.get("other_doc_count", 0)
        return {"doc_count_error_upper_bound": p.get("error_bound", 0),
                "sum_other_doc_count": other,
                "buckets": _apply_pipelines(
                    spec, [rb(k, e) for k, e in top])}

    if t == "significant_terms":
        # JLH score (ref bucket/significant/heuristics/JLHScore.java):
        # (fgp - bgp) * (fgp / bgp), only for fgp > bgp
        fg_total = max(p.get("fg_total", 0), 1)
        bg_total = max(p.get("bg_total", 0), 1)
        size = int(spec.params.get("size", 10)) or len(buckets)
        scored = []
        for k, e in buckets.items():
            fgp = e["doc_count"] / fg_total
            bgp = max(e.get("bg_count", e["doc_count"]), 1) / bg_total
            if fgp <= bgp:
                continue
            score = (fgp - bgp) * (fgp / bgp)
            scored.append((score, k, e))
        scored.sort(key=lambda x: (-x[0], str(x[1])))
        out_buckets = []
        for score, k, e in scored[:size]:
            b = rb(k, e)
            b["score"] = score
            b["bg_count"] = e.get("bg_count", 0)
            out_buckets.append(b)
        return {"doc_count": p.get("fg_total", 0), "buckets": out_buckets}

    if t == "histogram":
        items = sorted(buckets.items(), key=lambda kv: kv[0])
        min_count = int(spec.params.get("min_doc_count", 1))
        fmt = spec.params.get("format")
        out = []
        for k, e in items:
            if e["doc_count"] < min_count:
                continue
            b = rb(k, e)
            if fmt:
                b["key_as_string"] = _decimal_format(fmt, k)
            out.append(b)
        return {"buckets": _apply_pipelines(spec, out)}

    if t == "date_histogram":
        items = sorted(buckets.items(), key=lambda kv: kv[0])
        min_count = int(spec.params.get("min_doc_count", 1))
        out = []
        for k, e in items:
            if e["doc_count"] < min_count:
                continue
            b = rb(int(k), e)
            b["key_as_string"] = _iso(k)
            out.append(b)
        return {"buckets": _apply_pipelines(spec, out)}

    if t in ("range", "date_range"):
        ordered = []
        for r in spec.params.get("ranges", []):
            key, _, _ = _resolve_range(r, is_date=(t == "date_range"))
            if key in buckets:
                ordered.append((key, buckets[key]))
        return {"buckets": [rb(k, e) for k, e in ordered]}

    if t == "filters":
        return {"buckets": {k: rb(k, e, key_field=False)
                            for k, e in buckets.items()}}

    if t == "geohash_grid":
        size = int(spec.params.get("size", 10_000)) or len(buckets)
        items = sorted(buckets.items(), key=lambda kv: str(kv[0]))
        items.sort(key=lambda kv: kv[1]["doc_count"], reverse=True)
        return {"buckets": [rb(k, e) for k, e in items[:size]]}

    if t == "geo_distance":
        ordered = []
        for r in spec.params.get("ranges", []):
            lo_v, hi_v = r.get("from"), r.get("to")
            key = r.get("key") or (
                f"{'*' if lo_v is None else float(lo_v)}-"
                f"{'*' if hi_v is None else float(hi_v)}")
            if key in buckets:
                ordered.append((key, buckets[key]))
        return {"buckets": [rb(k, e) for k, e in ordered]}

    # filter / global / missing / sampler: single anonymous bucket
    entry = next(iter(buckets.values()), {"doc_count": 0})
    out = {"doc_count": entry["doc_count"]}
    for s in spec.subs:
        out[s.name] = _render_one(s, entry.get("subs", {}).get(
            s.name, _empty_partial(s)))
    return out


def _render_metric(spec: AggSpec, p: dict) -> dict:
    t = spec.type
    if t == "top_hits":
        hits = p.get("top", [])
        scores = [h["_score"] for h in hits if h["_score"] is not None]
        return {"hits": {"total": p.get("total", 0),
                         "max_score": max(scores) if scores else None,
                         "hits": hits}}
    if t == "cardinality":
        return {"value": p["hll"].cardinality()}
    if t == "geo_bounds":
        if p["top"] == -math.inf:
            return {}                  # no located docs: empty bounds
        return {"bounds": {
            "top_left": {"lat": p["top"], "lon": p["left"]},
            "bottom_right": {"lat": p["bottom"], "lon": p["right"]}}}
    if t == "scripted_metric":
        states = p.get("states", [])
        reduce_src = spec.params.get("reduce_script")
        if reduce_src:
            from ...script.engine import run_agg_script
            value = run_agg_script(
                reduce_src, {"_aggs": states},
                dict(spec.params.get("params") or {}))
            return {"value": value}
        return {"value": states if len(states) != 1 else states[0]}
    if t == "percentiles":
        td = p["tdigest"]
        percents = p.get("percents") or [1, 5, 25, 50, 75, 95, 99]
        return {"values": {f"{float(pc)}": td.quantile(float(pc) / 100.0)
                           for pc in percents}}
    count, s = p["count"], p["sum"]
    if t == "value_count":
        return {"value": count}
    if t == "sum":
        return {"value": s}
    if t == "min":
        return {"value": p["min"] if count else None}
    if t == "max":
        return {"value": p["max"] if count else None}
    if t == "avg":
        return {"value": (s / count) if count else None}
    avg = s / count if count else None
    base = {"count": count, "min": p["min"] if count else None,
            "max": p["max"] if count else None, "avg": avg, "sum": s}
    if t == "stats":
        return base
    # extended_stats
    if count:
        var = max(p["sum_sq"] / count - (s / count) ** 2, 0.0)
        base.update({"sum_of_squares": p["sum_sq"], "variance": var,
                     "std_deviation": math.sqrt(var)})
    else:
        base.update({"sum_of_squares": 0.0, "variance": None,
                     "std_deviation": None})
    return base


# ---------------------------------------------------------------------------
# Pipeline aggregations (host-side, post-reduce)
# ---------------------------------------------------------------------------

def _bucket_path_value(bucket: dict, path) -> float | None:
    """Resolve a buckets_path against one RENDERED bucket (pipelines run
    after sub-agg rendering, so values read from response shapes):
    `_count` -> doc_count, `agg` -> agg.value, `agg.prop` -> that stat,
    `a>b.prop` descends nested single-bucket aggs. None = gap."""
    path = str(path).strip()
    if path == "_count":
        return float(bucket.get("doc_count", 0))
    node: Any = bucket
    parts = [s.strip() for s in path.split(">")]
    for hop in parts[:-1]:
        node = node.get(hop) if isinstance(node, dict) else None
        if node is None:
            return None
    last = parts[-1]
    if last == "_count":
        val = node.get("doc_count") if isinstance(node, dict) else None
    else:
        if "." in last:
            name, prop = last.rsplit(".", 1)
        else:
            name, prop = last, "value"
        inner = node.get(name) if isinstance(node, dict) else None
        val = inner.get(prop) if isinstance(inner, dict) else None
    if isinstance(val, bool) or not isinstance(val, (int, float)):
        return None
    return float(val)


def _apply_pipelines(spec: AggSpec, buckets: list[dict]) -> list[dict]:
    """Apply this spec's pipeline children over the final sorted bucket
    list, in declaration order (a later pipeline may read an earlier
    one's output through its buckets_path)."""
    for ps in spec.pipelines:
        _apply_one_pipeline(ps, buckets)
    return buckets


def _apply_one_pipeline(ps: AggSpec, buckets: list[dict]) -> None:
    path = ps.params.get("buckets_path")
    if ps.type == "derivative":
        # ref pipeline/derivative/DerivativePipelineAggregator: value =
        # current - previous; gap_policy "skip" carries the last non-null
        # value forward, and the first bucket never emits
        prev = None
        for b in buckets:
            v = _bucket_path_value(b, path)
            if v is not None and prev is not None:
                b[ps.name] = {"value": v - prev}
            if v is not None:
                prev = v
        return
    if ps.type == "cumulative_sum":
        # ref pipeline/cumulativesum/: running total, gaps add 0 and the
        # sum is emitted on EVERY bucket (insert_zeros semantics)
        total = 0.0
        for b in buckets:
            v = _bucket_path_value(b, path)
            total += v if v is not None else 0.0
            b[ps.name] = {"value": total}
        return
    if ps.type == "moving_avg":
        # ref pipeline/movavg/ simple model: trailing mean over the last
        # `window` non-null values INCLUDING the current bucket; gaps
        # neither emit nor perturb the window
        window = int(ps.params.get("window", 5))
        if window <= 0:
            raise AggregationParsingException(
                f"moving_avg [{ps.name}]: window must be positive")
        ring: list[float] = []
        for b in buckets:
            v = _bucket_path_value(b, path)
            if v is None:
                continue
            ring.append(v)
            if len(ring) > window:
                ring.pop(0)
            b[ps.name] = {"value": sum(ring) / len(ring)}
        return
    # bucket_script (ref pipeline/bucketscript/): resolve every named
    # path; any gap skips the bucket; the expression runs through the
    # SAME AST-whitelisted engine as script fields — both `params.x`
    # and bare `x` name forms resolve
    paths: dict = ps.params.get("buckets_path") or {}
    script = ps.params.get("script")
    base_params = {}
    if isinstance(script, dict):
        base_params = dict(script.get("params") or {})
    from ...script.engine import run_search_script
    for b in buckets:
        vals = {k: _bucket_path_value(b, pth) for k, pth in paths.items()}
        if any(v is None for v in vals.values()):
            continue
        try:
            out = run_search_script(script, {}, {**base_params, **vals},
                                    extra_names=vals)
        except AggregationParsingException:
            raise
        except Exception as e:  # noqa: BLE001 — surface as a 400, not a 500
            raise AggregationParsingException(
                f"bucket_script [{ps.name}] failed: {e}") from e
        if isinstance(out, (int, float)) and not isinstance(out, bool):
            b[ps.name] = {"value": float(out)}
