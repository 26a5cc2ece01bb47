"""Per-shard search execution: query phase + fetch phase.

The analog of the reference's per-shard search runtime
(/root/reference/src/main/java/org/elasticsearch/search/SearchService.java:285
executeQueryPhase, search/query/QueryPhase.java:91-168, search/fetch/FetchPhase.java:79):

  query phase : compile query → run over every tensor segment → per-segment
                top-k (ops/topk) → running merge → QuerySearchResult with doc
                *keys* only (no sources) — exactly the reference's 2-phase
                contract (ids first, payload later).
  fetch phase : resolve doc keys to host-side stored _source.

Doc keys are i64: (segment_index << 32) | local_doc — the tensor analog of
Lucene's (segment, docid) addressing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..common.metrics import current_profiler, device_fetch
from ..index.segment import Segment
from ..mapping.mapper import MapperService
from ..ops import topk as topk_ops
from . import sort as sort_mod
from .query_dsl import CollectionStats, Node, SegmentContext
from .query_parser import QueryParser, merge_query_batch

SEG_SHIFT = 32
LOCAL_MASK = (1 << 32) - 1
# one dense execution's peak per-(query, doc)-slot residency: f32 scores +
# bool match — the request-breaker charge unit for score matrices
SCORE_SLOT_BYTES = 5


@jax.jit
def _masked_rowmax(scores, match):
    """Per-row max over matched docs — [Q] comes back, not [Q, N]."""
    return jnp.where(match, scores, -jnp.inf).max(axis=1)


@dataclasses.dataclass
class QuerySearchResult:
    """Per-shard query-phase result (ref search/query/QuerySearchResult.java)."""
    shard_id: int
    doc_keys: np.ndarray          # i64 [Q, k]  (-1 = empty slot)
    scores: np.ndarray            # f32 [Q, k]
    sort_values: np.ndarray | None  # object [Q, k]: list of real values/None
    total_hits: np.ndarray        # i64 [Q]
    max_score: np.ndarray         # f32 [Q]
    aggs: list | None = None      # per-shard partial aggregations (search/aggs)


@dataclasses.dataclass
class FetchedHit:
    doc_key: int
    score: float
    sort_value: list | None       # materialized per-key sort values
    doc_id: str
    type_name: str
    source: dict


class ShardSearcher:
    """Executes search phases over one shard's live segment set."""

    def __init__(self, shard_id: int, segments: Sequence[Segment],
                 mappers: MapperService, stats: dict | None = None,
                 stack_cache=None, index_name: str | None = None,
                 incarnation: int = 0, stacked: bool = True,
                 blockwise: bool = True, block_docs: int | None = None,
                 request_breaker=None, knn_opts: dict | None = None):
        self.shard_id = shard_id
        self.segments = list(segments)
        self.mappers = mappers
        self.parser = QueryParser(mappers)
        # empty segments are skipped ONCE here instead of being re-checked
        # inside every query's per-segment loop (pairs keep the original
        # segment index — doc keys encode it)
        self.live_segments = [(i, s) for i, s in enumerate(self.segments)
                              if s.n_docs > 0]
        # which device program served the last query phase — tests assert the
        # sparse sort-reduce kernel is the production scoring path
        self.last_query_path: str | None = None
        # dense-lane mode of the last dense query: "stacked" | "loop"
        self.last_dense_mode: str | None = None
        # score-materialization mode of the last dense query:
        # "blockwise" (running on-device top-k, O(Q x block) peak score
        # memory) | "materialized" (full [Q, n_pad] tensors)
        self.last_block_mode: str | None = None
        self.sparse_queries = 0
        self.dense_queries = 0
        self._path_stats = stats if stats is not None else {}
        # segment-stacked dense lane (search/stacked.py): the packed stack
        # lives in the node cache service when one is attached (breaker-
        # charged, invalidated by refresh/merge/_cache/clear); direct
        # constructions memoize locally — this searcher is itself rebuilt
        # whenever the segment set changes, so the memo cannot go stale
        self.stacked_enabled = bool(stacked)
        self.stack_cache = stack_cache
        self.index_name = index_name
        self.incarnation = incarnation
        self._stack_memo = None          # False = build declined/failed
        # streaming blockwise dense execution (search/blockwise.py):
        # engages per segment/stack when its doc axis exceeds one block;
        # single-block shapes keep the materializing executor (zero
        # overhead for small corpora)
        from .blockwise import DEFAULT_BLOCK_DOCS
        from ..index.segment import next_pow2
        self.blockwise_enabled = bool(blockwise)
        self.block_docs = next_pow2(
            max(int(block_docs or DEFAULT_BLOCK_DOCS), 8), floor=8)
        # lane-accurate score-matrix accounting charges here ("request"
        # breaker): [Q, block] on the blockwise lane, [Q, n_pad] on the
        # materializing one — charged before execution, released after
        self.request_breaker = request_breaker
        # IVF-clustered ANN kNN lane (ops/ann.py): per-index settings
        # roster (index/index_service.knn_options_from); cluster indexes
        # live in the node AnnIndexCache (segment-attached) or, when no
        # cache service is wired, in this bounded local memo — the
        # searcher itself is rebuilt whenever the segment set changes
        defaults = {"ivf_enable": True, "nlist": 0, "nprobe": 0,
                    "min_docs": 4096, "precision": "bf16",
                    "quantization": "none", "pq_m": 16,
                    "rescore_window": 0}
        self.knn_opts = {**defaults, **(knn_opts or {})}
        from ..common.cache import Cache
        self._ivf_local = Cache("ann_local", max_entries=32)
        # which vector lane served the last kNN phase: "ann" | "exact"
        self.last_knn_mode: str | None = None
        # quantized scan mode of the last kNN phase: "int8" | "pq" | None
        # (f32 IVF or exact)
        self.last_quant_mode: str | None = None

    def _bump(self, key: str, n: int = 1) -> None:
        self._path_stats[key] = self._path_stats.get(key, 0) + n

    # -- lane-accurate score-matrix accounting (ISSUE 8 satellite) ---------

    def _charge_scores(self, n_bytes: int) -> int:
        """Charge the dense execution's peak score+match residency to the
        `request` breaker BEFORE the device program runs: [Q, block] bytes
        on the blockwise lane, [Q, n_pad] on the materializing one. The
        peak gauge records either way. The request breaker is the
        EVICTABLE tier (common/breaker.py): a breach counts a trip and
        FORCE-charges — accounting stays truthful for the memory that is
        about to exist — instead of failing the search; there is no
        cheaper lane below blockwise to degrade to."""
        from ..common.breaker import CircuitBreakingException
        from ..common.metrics import record_score_matrix_bytes
        record_score_matrix_bytes(n_bytes)
        if self.request_breaker is not None:
            try:
                self.request_breaker.add_estimate(n_bytes)
            except CircuitBreakingException:
                self.request_breaker.add_estimate(n_bytes, check=False)
            return n_bytes
        return 0

    def _release_scores(self, n_bytes: int) -> None:
        if n_bytes and self.request_breaker is not None:
            self.request_breaker.release(n_bytes)

    # -- statistics (DFS support, ref search/dfs/DfsPhase.java:57-81) ------

    def term_statistics(self, node: Node) -> tuple[dict, dict, int]:
        """(doc_freqs {(field,term): df}, field_sum_dl, doc_count) for this
        shard — the payload a DFS phase all-reduces across shards."""
        terms_by_field: dict[str, set[str]] = {}
        node.collect_terms(terms_by_field)
        stats = CollectionStats.from_segments(self.segments, terms_by_field)
        return stats.doc_freqs, stats.field_sum_dl, stats.doc_count

    def build_stats(self, node: Node,
                    global_stats: CollectionStats | None = None) -> CollectionStats:
        if global_stats is not None:
            return global_stats
        terms_by_field: dict[str, set[str]] = {}
        node.collect_terms(terms_by_field)
        return CollectionStats.from_segments(self.segments, terms_by_field)

    # -- query phase -------------------------------------------------------

    def parse(self, bodies: list[dict | None]) -> Node:
        nodes = [self.parser.parse(b) for b in bodies]
        return merge_query_batch(nodes)

    def execute_query_phase(self, node: Node, *, size: int = 10,
                            from_: int = 0, n_queries: int = 1,
                            sort=None,
                            global_stats: CollectionStats | None = None,
                            track_scores: bool = True,
                            aggs: list | None = None,
                            search_after=None) -> QuerySearchResult:
        """Run the batched query tree over all segments of this shard.

        sort: list[SortSpec] (search/sort.py), a legacy single-key dict, or
        None for score order. search_after: cursor values aligned with the
        sort keys.

        aggs: parsed AggSpec list (search/aggs) — collected in the same pass
        as scoring using each segment's match mask, exactly the reference's
        AggregationPhase-collectors-inside-QueryPhase model
        (ref search/query/QueryPhase.java:91-168, AggregationPhase.java:70-95).
        Aggregations apply to query row 0 of the batch (one agg tree per
        search request, like the reference).
        """
        k = max(size + from_, 1)
        Q = n_queries
        from .query_dsl import contains_joins
        if contains_joins(node):
            # parent/child joins span segments: resolve them into
            # segment-executable bitmap nodes first (search/joins.py)
            from .joins import resolve_joins
            node = resolve_joins(node, self.segments, self.mappers, Q)
        sort = sort_mod.normalize(sort)
        if search_after is not None and not isinstance(search_after, (list, tuple)):
            search_after = [search_after]
        if sort is not None:
            # sorting by _score tracks scores by definition
            track_scores = track_scores or any(
                sp.field == sort_mod.SCORE for sp in sort)

        from ..common.device_stats import lane_chosen, lane_decline
        lane_comp = f"shard[{self.shard_id}].query"
        if sort is None and search_after is None:
            # the production fast path: sort-reduce sparse kernel
            # (ops/bm25_sparse) for the plan shapes that dominate traffic.
            # Aggregations ride it too: the device match_mask (cheap —
            # presence scatters + columnar compares, no scoring) gates the
            # ops/aggs collection kernels, so agg queries no longer force
            # the dense [Q,N] scoring path (VERDICT r3 task 6).
            from .sparse_exec import execute_sparse, extract_sparse_plan
            from .aggs.aggregators import has_top_hits
            plan = extract_sparse_plan(node)
            if plan is None:
                lane_decline(lane_comp, "sparse", "plan_shape")
            elif aggs and has_top_hits(aggs):
                lane_decline(lane_comp, "sparse", "top_hits")
            if plan is not None and not (aggs and has_top_hits(aggs)):
                stats = self.build_stats(node, global_stats)
                keys, scores, total, mx = execute_sparse(
                    plan, self.segments, stats, k=k)
                agg_partials = None
                if aggs is not None:
                    from .aggs.aggregators import collect_shard
                    a_segs, a_masks = [], []
                    for _si, seg in self.live_segments:
                        ctx = SegmentContext(seg, Q, stats)
                        m = node.match_mask(ctx) & seg.live[None, :]
                        a_segs.append(seg)
                        a_masks.append(m[0])
                    agg_partials = collect_shard(aggs, a_segs, a_masks,
                                                 query_parser=self.parser)
                lane_chosen(lane_comp, "sparse")
                self.last_query_path = "sparse"
                self.sparse_queries += 1
                self._bump("sparse")
                self._bump("segment_dispatches", len(self.live_segments))
                from ..common.metrics import record_shard_fetches
                record_shard_fetches(len(self.live_segments))
                prof = current_profiler()
                if prof is not None:
                    prof.note_path("sparse")
                return QuerySearchResult(
                    shard_id=self.shard_id, doc_keys=keys, scores=scores,
                    sort_values=None, total_hits=total, max_score=mx,
                    aggs=agg_partials)

            # segment-stacked dense lane: the whole tree executes once over
            # the shard's packed segment stack and comes down in ONE
            # device_fetch (search/stacked.py). Falls through to the
            # per-segment loop when the stack is declined (breaker pressure,
            # oversized, disabled).
            if self.stacked_enabled and self.live_segments:
                out = self._try_stacked(node, k=k, Q=Q,
                                        global_stats=global_stats,
                                        track_scores=track_scores,
                                        aggs=aggs)
                if out is not None:
                    return out

        if sort is not None or search_after is not None:
            # the sparse kernel serves unsorted bodies only
            lane_decline(lane_comp, "sparse", "sorted")
        if sort is not None and self.stacked_enabled and self.live_segments:
            # sorted stacked lane (ISSUE 17): encoded cross-segment sort
            # keys ride the stacked/blockwise reduce — one program, one
            # fetch. Ineligible encodings decline with a stable reason
            # and keep the per-segment loop below.
            from . import sort_encode
            reason = sort_encode.decline_reason(
                sort, [s for _, s in self.live_segments])
            if reason is not None:
                lane_decline(lane_comp, "stacked", reason)
            else:
                out = self._try_stacked_sorted(
                    node, sort, search_after, k=k, Q=Q,
                    global_stats=global_stats,
                    track_scores=track_scores, aggs=aggs)
                if out is not None:
                    return out
        lane_chosen(lane_comp, "loop")
        self.last_query_path = "dense"
        self.last_dense_mode = "loop"
        self.last_block_mode = "materialized"
        self.dense_queries += 1
        self._bump("dense")
        prof_path = current_profiler()
        if prof_path is not None:
            prof_path.note_path("dense")
        stats = self.build_stats(node, global_stats)

        # streaming blockwise eligibility (search/blockwise.py): unsorted
        # queries over segments wider than one block run the tree inside a
        # lax.scan with a running top-k — peak score memory O(Q × block).
        # top_hits aggs need the full per-doc score row, so they keep the
        # materializing executor; single-block segments take the identity
        # fast path below (n_pad <= block never plans).
        blockwise_ok = (sort is None and self.blockwise_enabled
                        and search_after is None)
        if blockwise_ok and aggs is not None:
            from .aggs.aggregators import has_top_hits
            blockwise_ok = not has_top_hits(aggs)

        best_scores = np.full((Q, k), -np.inf, np.float32)
        best_keys = np.full((Q, k), -1, np.int64)
        # sorted path: per-row candidate lists merged by MATERIALIZED value
        # (sort.py module docstring — ordinals never cross a segment boundary)
        cands: list[list] = [[] for _ in range(Q)] if sort else []
        total = np.zeros((Q,), np.int64)
        max_score = np.full((Q,), -np.inf, np.float32)
        agg_segments: list = []
        agg_masks: list = []
        agg_scores: list = []
        n_fetches = 0

        for seg_idx, seg in self.live_segments:
            self._bump("segment_dispatches")
            kk = min(k, seg.n_pad)
            charged = 0
            fetch: dict = {}
            try:
                blk = None
                if blockwise_ok and seg.n_pad > self.block_docs:
                    # charge the BLOCKWISE estimate first; a declined plan
                    # releases it and re-charges the materializing one —
                    # accounting stays lane-accurate either way
                    charged = self._charge_scores(
                        Q * self.block_docs * SCORE_SLOT_BYTES)
                    from . import blockwise as blockwise_mod
                    blk = blockwise_mod.execute_loop_segment(
                        node, seg, n_queries=Q, stats=stats, k=k,
                        block=self.block_docs, want_mask=aggs is not None)
                    if blk is None:
                        self._release_scores(charged)
                        charged = 0
                if blk is not None:
                    self.last_block_mode = "blockwise"
                    self._bump("blockwise_dispatches")
                    if aggs is not None:
                        top_d, idx_d, total_d, mx_d, mask_d = blk
                        agg_segments.append(seg)
                        agg_masks.append(mask_d)   # row 0, liveness-gated
                        agg_scores.append(None)    # no top_hits on blocks
                    else:
                        top_d, idx_d, total_d, mx_d = blk
                    fetch = {"total": total_d, "top": top_d, "idx": idx_d}
                    if track_scores:
                        fetch["mx"] = mx_d
                else:
                    charged = charged or self._charge_scores(
                        Q * seg.n_pad * SCORE_SLOT_BYTES)
                    ctx = SegmentContext(seg, Q, stats)
                    scores, match = node.execute(ctx)
                    match = match & seg.live[None, :]
                    if aggs is not None:
                        agg_segments.append(seg)
                        agg_masks.append(match[0])   # stays device-resident
                        agg_scores.append(scores[0])  # top_hits ranks these
                    # totals/aggs reflect the full query match set —
                    # search_after narrows collection below, not the hit
                    # count (ref QueryPhase). All of this segment's device
                    # results come down in ONE fetch: one host sync per
                    # segment, not one per array.
                    fetch = {"total": topk_ops.count_matches(match)}
                    if track_scores:
                        # mask + max ON DEVICE — downloading the [Q, N]
                        # score and match matrices to host is ~0.5 GB per
                        # 64-query batch at 1M docs
                        fetch["mx"] = _masked_rowmax(scores, match)
                    if sort is None:
                        top_d, idx_d = topk_ops.topk_scores(scores, match,
                                                            k=kk)
                        fetch["top"] = top_d
                        fetch["idx"] = idx_d
                got = device_fetch(fetch)
                n_fetches += 1
                total += got["total"]
                if track_scores:
                    max_score = np.maximum(max_score, got["mx"])
                if sort is None:
                    top, idx = got["top"], got["idx"]
                    seg_keys = np.where(
                        top > -np.inf,
                        (np.int64(seg_idx) << SEG_SHIFT)
                        | idx.astype(np.int64),
                        np.int64(-1))
                    merged = np.concatenate([best_scores, top], axis=1)
                    merged_keys = np.concatenate([best_keys, seg_keys],
                                                 axis=1)
                    order = np.argsort(-merged, axis=1, kind="stable")[:, :k]
                    best_scores = np.take_along_axis(merged, order, axis=1)
                    best_keys = np.take_along_axis(merged_keys, order,
                                                   axis=1)
                else:
                    # device selection: lexicographic top-k over f64
                    # comparator keys (keyword keys = this segment's
                    # sorted ordinals)
                    keys = sort_mod.segment_keys(seg, sort, scores, Q,
                                                 seg_idx, self.shard_id)
                    if search_after is not None:
                        match = match & sort_mod.after_mask(
                            seg, sort, search_after, keys)
                    primary = jnp.where(match, keys[0], jnp.inf)
                    doc_idx = jnp.broadcast_to(
                        jnp.arange(seg.n_pad, dtype=jnp.float64)[None, :],
                        primary.shape)
                    # lexsort: LAST key is the primary; doc index breaks
                    # ties
                    order = jnp.lexsort(
                        tuple([doc_idx] + list(reversed(keys[1:]))
                              + [primary]))
                    # top-kk selection stays ON DEVICE: downloading the
                    # full [Q, n_pad] match/score matrices cost O(corpus)
                    # transfer per sorted batch (25 MB at 100k docs x 64 q)
                    # — gather at the winning positions first, then ONE
                    # small fetch
                    order = order[:, :kk].astype(jnp.int32)
                    sel_match_d = jnp.take_along_axis(match, order, axis=1)
                    sel_scores_d = jnp.take_along_axis(scores, order, axis=1)
                    order, sel_match, sel_scores = device_fetch(
                        (order, sel_match_d, sel_scores_d))
                    n_fetches += 1
                    for qi in range(Q):
                        for j in range(kk):
                            if not sel_match[qi, j]:
                                continue
                            local = int(order[qi, j])
                            dk = (seg_idx << SEG_SHIFT) | local
                            sc = float(sel_scores[qi, j])
                            vals = sort_mod.materialize(
                                seg, sort, local, sc, dk, self.shard_id)
                            cands[qi].append(
                                (sort_mod.compare_key(vals, sort),
                                 seg_idx, local, dk, sc, vals))
            finally:
                self._release_scores(charged)

        sort_vals = None
        if sort is not None:
            best_keys = np.full((Q, k), -1, np.int64)
            best_scores = np.full((Q, k), np.nan, np.float32)
            sort_vals = np.empty((Q, k), dtype=object)
            for qi in range(Q):
                cands[qi].sort(key=lambda c: (c[0], c[1], c[2]))
                for slot, c in enumerate(cands[qi][:k]):
                    best_keys[qi, slot] = c[3]
                    if track_scores:
                        best_scores[qi, slot] = c[4]
                    sort_vals[qi, slot] = c[5]
        max_score = np.where(np.isfinite(max_score), max_score, np.nan)
        best_scores = np.where(best_keys >= 0, best_scores, np.nan)
        agg_partials = None
        if aggs is not None:
            from .aggs.aggregators import collect_shard
            agg_partials = collect_shard(aggs, agg_segments, agg_masks,
                                         query_parser=self.parser,
                                         scores=agg_scores)
        from ..common.metrics import record_shard_fetches
        record_shard_fetches(n_fetches)
        return QuerySearchResult(
            shard_id=self.shard_id, doc_keys=best_keys, scores=best_scores,
            sort_values=sort_vals, total_hits=total, max_score=max_score,
            aggs=agg_partials)

    # -- segment-stacked dense lane (search/stacked.py) --------------------

    def _acquire_stack(self):
        """The shard's packed SegmentStack: through the node cache service
        when attached (breaker-charged, invalidated by refresh/merge/
        `_cache/clear`), else memoized on this searcher — which is itself
        rebuilt whenever the segment set changes. None = declined (breaker
        pressure / oversized / nothing live): callers fall back to the
        per-segment loop."""
        if self.stack_cache is not None:
            breaker = next((getattr(s, "breaker", None)
                            for _i, s in self.live_segments
                            if getattr(s, "breaker", None) is not None), None)
            return self.stack_cache.get_or_build(
                self.index_name, self.shard_id, self.incarnation,
                self.segments, breaker=breaker)
        if self._stack_memo is None:
            from .stacked import build_stack
            self._stack_memo = build_stack(self.segments) or False
        return self._stack_memo or None

    def _try_stacked(self, node: Node, *, k: int, Q: int,
                     global_stats: CollectionStats | None,
                     track_scores: bool,
                     aggs: list | None) -> QuerySearchResult | None:
        """One stacked execution attempt; None (the stack was declined)
        falls back to the loop. An execution error is the request's."""
        from ..common.device_stats import lane_decline
        stack = self._acquire_stack()
        if stack is None:
            lane_decline(f"shard[{self.shard_id}].query", "stacked",
                         "stack_declined")
            return None
        return self._execute_stacked(stack, node, k=k, Q=Q,
                                     global_stats=global_stats,
                                     track_scores=track_scores,
                                     aggs=aggs)

    def _execute_stacked(self, stack, node: Node, *, k: int, Q: int,
                         global_stats, track_scores: bool,
                         aggs: list | None) -> QuerySearchResult:
        from ..common import tracing
        from .stacked import StackedContext, execute_tree, stacked_reduce
        stats = self.build_stats(node, global_stats)
        # blockwise eligibility mirrors the loop lane: unsorted (always
        # true here), no top_hits aggs, stack wider than one block
        blockwise_ok = self.blockwise_enabled \
            and stack.n_pad > self.block_docs
        if blockwise_ok and aggs is not None:
            from .aggs.aggregators import has_top_hits
            blockwise_ok = not has_top_hits(aggs)
        self.last_block_mode = "materialized"
        blk_mask = None
        charged = 0
        try:
            with tracing.span("stacked_dispatch", shard=self.shard_id,
                              segments=len(stack.segments), k=k):
                out = None
                if blockwise_ok:
                    charged = self._charge_scores(
                        stack.g_pad * Q * self.block_docs * SCORE_SLOT_BYTES)
                    from . import blockwise as blockwise_mod
                    out = blockwise_mod.execute_stacked(
                        stack, node, n_queries=Q, stats=stats, k=k,
                        block=self.block_docs, want_mask=aggs is not None)
                    if out is None:
                        self._release_scores(charged)
                        charged = 0
                if out is not None:
                    self.last_block_mode = "blockwise"
                    self._bump("blockwise_dispatches")
                    if aggs is not None:
                        keys_d, top_d, total_d, mx_d, blk_mask = out
                    else:
                        keys_d, top_d, total_d, mx_d = out
                    live = stack.live_stack()
                else:
                    charged = charged or self._charge_scores(
                        stack.g_pad * Q * stack.n_pad * SCORE_SLOT_BYTES)
                    sctx = StackedContext(stack, Q, stats)
                    scores, match = execute_tree(node, sctx)
                    live = stack.live_stack()
                    out = stacked_reduce(scores, match, live,
                                         stack.seg_ids_dev, k=k)
                    keys_d, top_d, total_d, mx_d = out
                # per-segment totals, masked row-max and the cross-segment
                # top-k merge all happened ON DEVICE — this is the shard's
                # ONE fetch
                got = device_fetch({"keys": keys_d, "top": top_d,
                                    "total": total_d, "mx": mx_d})
        finally:
            self._release_scores(charged)
        best_keys = np.asarray(got["keys"], np.int64)
        # keep the device dtype: trees over f64 columns promote scores to
        # f64 exactly like the per-segment loop's merge does
        best_scores = np.asarray(got["top"])
        if best_keys.shape[1] < k:        # pad to the loop's [Q, k] contract
            pad = k - best_keys.shape[1]
            best_keys = np.concatenate(
                [best_keys, np.full((Q, pad), -1, np.int64)], axis=1)
            best_scores = np.concatenate(
                [best_scores,
                 np.full((Q, pad), -np.inf, best_scores.dtype)], axis=1)
        best_scores = np.where(best_keys >= 0, best_scores, np.nan)
        mx = np.asarray(got["mx"])
        max_score = np.where(np.isfinite(mx), mx, np.nan) if track_scores \
            else np.full((Q,), np.nan, mx.dtype)
        agg_partials = None
        if aggs is not None:
            from .aggs.aggregators import collect_shard
            a_segs, a_masks, a_scores = [], [], []
            for gi, seg in enumerate(stack.segments):
                a_segs.append(seg)
                if blk_mask is not None:
                    # blockwise mask rows are already liveness-gated
                    a_masks.append(blk_mask[gi, : seg.n_pad])
                    a_scores.append(None)    # no top_hits on blocks
                else:
                    a_masks.append((match[gi, 0] & live[gi])[: seg.n_pad])
                    a_scores.append(scores[gi, 0, : seg.n_pad])
            agg_partials = collect_shard(aggs, a_segs, a_masks,
                                         query_parser=self.parser,
                                         scores=a_scores)
        # the stacked lane IS the dense lane (one program instead of G):
        # dense counters keep their meaning, `stacked` marks the mode
        from ..common.device_stats import lane_chosen
        lane_chosen(f"shard[{self.shard_id}].query",
                    "stacked_blockwise" if self.last_block_mode == "blockwise"
                    else "stacked")
        self.last_query_path = "dense"
        self.last_dense_mode = "stacked"
        self.dense_queries += 1
        self._bump("dense")
        self._bump("stacked")
        self._bump("stacked_dispatches")
        from ..common.metrics import record_shard_fetches
        record_shard_fetches(1)
        prof = current_profiler()
        if prof is not None:
            prof.note_path("stacked")
        return QuerySearchResult(
            shard_id=self.shard_id, doc_keys=best_keys, scores=best_scores,
            sort_values=None, total_hits=np.asarray(got["total"], np.int64),
            max_score=max_score, aggs=agg_partials)

    # -- sorted stacked lane (ISSUE 17: search/sort_encode.py) -------------

    def _try_stacked_sorted(self, node: Node, sort, search_after, *,
                            k: int, Q: int, global_stats,
                            track_scores: bool,
                            aggs: list | None) -> QuerySearchResult | None:
        """One sorted stacked attempt; None (the stack was declined) falls
        back to the loop's materialized-value merge."""
        from ..common.device_stats import lane_decline
        stack = self._acquire_stack()
        if stack is None:
            lane_decline(f"shard[{self.shard_id}].query", "stacked",
                         "stack_declined")
            return None
        return self._execute_stacked_sorted(
            stack, node, sort, search_after, k=k, Q=Q,
            global_stats=global_stats, track_scores=track_scores,
            aggs=aggs)

    def _execute_stacked_sorted(self, stack, node: Node, sort,
                                search_after, *, k: int, Q: int,
                                global_stats, track_scores: bool,
                                aggs: list | None) -> QuerySearchResult:
        from ..common import tracing
        from . import sort_encode
        from .stacked import (StackedContext, execute_tree,
                              stacked_sorted_reduce)
        stats = self.build_stats(node, global_stats)
        cols, vocabs = sort_encode.stack_key_cols(stack, sort,
                                                  self.shard_id)
        cursor = sort_encode.encode_cursor(sort, search_after, vocabs)
        keys_dev = jnp.asarray(cols)
        cursor_dev = jnp.asarray(cursor)
        blockwise_ok = self.blockwise_enabled \
            and stack.n_pad > self.block_docs
        if blockwise_ok and aggs is not None:
            from .aggs.aggregators import has_top_hits
            blockwise_ok = not has_top_hits(aggs)
        self.last_block_mode = "materialized"
        blk_mask = None
        scores = match = live = None
        charged = 0
        try:
            with tracing.span("stacked_sorted_dispatch",
                              shard=self.shard_id,
                              segments=len(stack.segments), k=k):
                out = None
                if blockwise_ok:
                    charged = self._charge_scores(
                        stack.g_pad * Q * self.block_docs
                        * SCORE_SLOT_BYTES)
                    from . import blockwise as blockwise_mod
                    out = blockwise_mod.execute_stacked_sorted(
                        stack, node, keys_dev, cursor_dev, n_queries=Q,
                        stats=stats, k=k, block=self.block_docs,
                        want_mask=aggs is not None)
                    if out is None:
                        self._release_scores(charged)
                        charged = 0
                if out is not None:
                    self.last_block_mode = "blockwise"
                    self._bump("blockwise_dispatches")
                    if aggs is not None:
                        keys_d, top_d, total_d, mx_d, blk_mask = out
                    else:
                        keys_d, top_d, total_d, mx_d = out
                else:
                    charged = charged or self._charge_scores(
                        stack.g_pad * Q * stack.n_pad * SCORE_SLOT_BYTES)
                    sctx = StackedContext(stack, Q, stats)
                    scores, match = execute_tree(node, sctx)
                    live = stack.live_stack()
                    keys_d, top_d, total_d, mx_d = stacked_sorted_reduce(
                        scores, match, live, stack.seg_ids_dev,
                        keys_dev, cursor_dev, k=k)
                got = device_fetch({"keys": keys_d, "top": top_d,
                                    "total": total_d, "mx": mx_d})
        finally:
            self._release_scores(charged)
        best_keys = np.asarray(got["keys"], np.int64)
        fetched_scores = np.asarray(got["top"])
        if best_keys.shape[1] < k:
            pad = k - best_keys.shape[1]
            best_keys = np.concatenate(
                [best_keys, np.full((Q, pad), -1, np.int64)], axis=1)
            fetched_scores = np.concatenate(
                [fetched_scores,
                 np.full((Q, pad), -np.inf, fetched_scores.dtype)], axis=1)
        # the loop's sorted contract: scores stay NaN unless tracked
        best_scores = np.where(
            (best_keys >= 0) & track_scores, fetched_scores, np.nan)
        mx = np.asarray(got["mx"])
        max_score = np.where(np.isfinite(mx), mx, np.nan) if track_scores \
            else np.full((Q,), np.nan, mx.dtype)
        # winners' user-facing sort values materialize host-side per hit
        # — k real values per shard, never a device round-trip
        sort_vals = np.empty(best_keys.shape, dtype=object)
        for qi in range(Q):
            for slot in range(best_keys.shape[1]):
                dk = int(best_keys[qi, slot])
                if dk < 0:
                    continue
                seg = self.segments[dk >> SEG_SHIFT]
                sc = float(fetched_scores[qi, slot])
                sort_vals[qi, slot] = sort_mod.materialize(
                    seg, sort, dk & LOCAL_MASK, sc, dk, self.shard_id)
        agg_partials = None
        if aggs is not None:
            from .aggs.aggregators import collect_shard
            a_segs, a_masks, a_scores = [], [], []
            for gi, seg in enumerate(stack.segments):
                a_segs.append(seg)
                if blk_mask is not None:
                    a_masks.append(blk_mask[gi, : seg.n_pad])
                    a_scores.append(None)
                else:
                    a_masks.append((match[gi, 0] & live[gi])[: seg.n_pad])
                    a_scores.append(scores[gi, 0, : seg.n_pad])
            agg_partials = collect_shard(aggs, a_segs, a_masks,
                                         query_parser=self.parser,
                                         scores=a_scores)
        from ..common.device_stats import lane_chosen
        lane_chosen(f"shard[{self.shard_id}].query",
                    "stacked_blockwise"
                    if self.last_block_mode == "blockwise" else "stacked")
        self.last_query_path = "dense"
        self.last_dense_mode = "stacked"
        self.dense_queries += 1
        self._bump("dense")
        self._bump("stacked")
        self._bump("stacked_sorted")
        self._bump("stacked_dispatches")
        from ..common.metrics import record_shard_fetches
        record_shard_fetches(1)
        prof = current_profiler()
        if prof is not None:
            prof.note_path("stacked")
        return QuerySearchResult(
            shard_id=self.shard_id, doc_keys=best_keys,
            scores=best_scores, sort_values=sort_vals,
            total_hits=np.asarray(got["total"], np.int64),
            max_score=max_score, aggs=agg_partials)

    # -- kNN (IVF two-stage ANN / exact MXU matmul — ops/ann.py, knn.py) ---

    def _acquire_ivf(self, seg, vc, field: str, req_nprobe: int | None,
                     exact: bool):
        """(IvfData, effective nprobe) for one segment's vector column, or
        (None, 0) to use the exact kernel. The fallback ladder:
        per-request `exact`, `index.knn.ivf.enable: false`, undersized
        columns (< max(min_docs, 2*nlist)), full-coverage requests
        (nprobe >= nlist — the exact kernel is bitwise-identical AND
        cheaper), breaker-declined builds. A build that raises is the
        request's error."""
        from ..common.device_stats import lane_decline
        from ..ops import ann as ann_ops
        comp = f"shard[{self.shard_id}].knn"
        opts = self.knn_opts
        if exact or not opts["ivf_enable"]:
            lane_decline(comp, "ivf",
                         "exact_requested" if exact else "ivf_disabled")
            return None, 0
        n_docs = seg.n_docs
        nlist = int(opts["nlist"]) or ann_ops.auto_nlist(n_docs)
        if n_docs < max(int(opts["min_docs"]), 2 * nlist):
            lane_decline(comp, "ivf", "column_too_small")
            return None, 0
        nprobe = int(req_nprobe or opts["nprobe"]
                     or ann_ops.auto_nprobe(nlist))
        if nprobe >= nlist:
            lane_decline(comp, "ivf", "full_coverage")
            return None, 0
        cache = getattr(seg, "ann_cache", None)
        if cache is not None:
            ivf = cache.get_or_build(
                seg, field, nlist,
                lambda: vc.build_ivf(n_docs, nlist))
        else:
            key = (seg.seg_id, field, nlist)
            ivf = self._ivf_local.get(key)
            if ivf is None:
                ivf = vc.build_ivf(n_docs, nlist)
                if ivf is not None:
                    self._ivf_local.put(key, ivf, weight=ivf.nbytes)
        if ivf is None:
            lane_decline(comp, "ivf", "build_declined")
            self._bump("ann_fallbacks")
            return None, 0
        return ivf, min(nprobe, ivf.nlist)

    def _acquire_quant(self, seg, vc, field: str, ivf, mode: str):
        """QuantData for one segment's IVF layout, or None to stay on the
        f32 IVF scan. The quantized rungs of the fallback ladder: dims
        not divisible by pq.m, columns too small to train 256 codes,
        breaker-declined builds — each counted
        (`ann_quantized_fallbacks`) and bitwise-harmless (the f32 IVF and
        exact kernels below are unchanged)."""
        from ..common.device_stats import lane_decline
        from ..ops import ann as ann_ops
        comp = f"shard[{self.shard_id}].knn"
        m = int(self.knn_opts.get("pq_m") or ann_ops.DEFAULT_PQ_M)
        if mode == "pq" and (m < 1 or vc.dims % m
                             or ivf.n_docs < ann_ops.PQ_CODES):
            lane_decline(comp, "ann_quant", "pq_shape")
            self._bump("ann_quantized_fallbacks")
            return None
        cache = getattr(seg, "ann_cache", None)
        if cache is not None:
            quant = cache.get_or_build_quant(
                seg, field, ivf.nlist, mode, m,
                lambda: vc.build_quant(ivf, mode, m))
        else:
            key = (seg.seg_id, field, ivf.nlist, mode, m)
            quant = self._ivf_local.get(key)
            if quant is None:
                quant = vc.build_quant(ivf, mode, m)
                if quant is not None:
                    self._ivf_local.put(key, quant,
                                        weight=quant.nbytes)
        if quant is None:
            lane_decline(comp, "ann_quant", "build_declined")
            self._bump("ann_quantized_fallbacks")
        return quant

    def execute_knn(self, field: str, query_vectors, *, k: int = 10,
                    metric: str = "cosine",
                    filter_node: Node | None = None,
                    nprobe: int | None = None,
                    exact: bool = False,
                    quantization: str | None = None) -> QuerySearchResult:
        """kNN query phase over this shard's segments. Behaves like a
        query phase whose scores are vector similarities, so the controller
        reduce and fetch phase apply unchanged.

        Columns past `index.knn.ivf.min_docs` route through the IVF lane
        (centroid route + gathered blockwise cluster scan, ops/ann.py);
        when `index.knn.quantization` (or the per-request `quantization`
        override) selects int8/pq, the cluster scan runs on quantized
        codes with a full-precision rescore of the top
        `index.knn.rescore_window` survivors. Everything else — and every
        rung of the fallback ladder — runs the exact [Q, N] matmul
        (ops/knn.py). `nprobe` overrides the index default per request;
        `exact=True` pins the exact kernel."""
        from ..common import tracing
        from ..ops import ann as ann_ops
        from ..ops import knn as knn_ops

        precision = self.knn_opts["precision"]
        qmode = (quantization if quantization is not None
                 else self.knn_opts.get("quantization", "none"))
        qmode = str(qmode).strip().lower()
        if qmode not in ("int8", "pq"):
            qmode = "none"
        qv = jnp.asarray(np.asarray(query_vectors, np.float32))
        # query vectors are the host→device upload (process-wide transfer
        # counters + the active profiler, when one is installed)
        from ..common.metrics import note_h2d
        note_h2d(int(qv.size) * 4)
        Q = qv.shape[0]
        best_scores = np.full((Q, k), -np.inf, np.float32)
        best_keys = np.full((Q, k), -1, np.int64)
        total = np.zeros((Q,), np.int64)

        n_fetches = 0
        any_ann = False
        any_quant = False
        self.last_quant_mode = None
        for seg_idx, seg in self.live_segments:
            vc = seg.vectors.get(field)
            if vc is None:
                continue
            self._bump("segment_dispatches")
            live_1d = seg.live
            filtered = filter_node is not None
            if filtered:
                stats = self.build_stats(filter_node, None)
                _, match = filter_node.execute(SegmentContext(seg, Q, stats))
                live = live_1d[None, :] & match
            else:
                live = jnp.broadcast_to(live_1d[None, :], (Q, seg.n_pad))
            kk = min(k, seg.n_pad)
            ivf, nprobe_eff = self._acquire_ivf(seg, vc, field, nprobe,
                                                exact)
            quant = None
            if ivf is not None and qmode != "none":
                quant = self._acquire_quant(seg, vc, field, ivf, qmode)
            if quant is not None:
                W = ann_ops.slot_budget(ivf.sizes_desc_cum, nprobe_eff,
                                        ivf.n_docs, ivf.nlist)
                block = ann_ops.quant_scan_block_size(Q, vc.dims, qmode,
                                                      quant.m, W)
                rw = ann_ops.rescore_width(
                    min(kk, W), int(self.knn_opts.get("rescore_window")
                                    or 0), W)
                with tracing.span("quantized_scan", shard=self.shard_id,
                                  mode=qmode, nprobe=nprobe_eff,
                                  nlist=ivf.nlist, window=W, rescore=rw):
                    if qmode == "int8":
                        top, idx = ann_ops.ivf_search_int8(
                            vc.vecs, quant.codes, quant.scales,
                            ivf.centroids, ivf.starts, ivf.sizes,
                            ivf.slot_docs, ivf.norms,
                            live if filtered else live_1d, qv,
                            k=min(kk, W), metric=metric,
                            precision=precision, nprobe=nprobe_eff, W=W,
                            block=block, rw=rw, per_query_live=filtered)
                    else:
                        top, idx = ann_ops.ivf_search_pq(
                            vc.vecs, quant.codes, quant.codebooks,
                            ivf.centroids, ivf.starts, ivf.sizes,
                            ivf.slot_docs, ivf.norms,
                            live if filtered else live_1d, qv,
                            k=min(kk, W), metric=metric,
                            precision=precision, nprobe=nprobe_eff, W=W,
                            block=block, rw=rw, per_query_live=filtered)
                self._bump("ann_dispatches")
                self._bump("ann_quantized_dispatches")
                self._bump(f"ann_quantized_{qmode}")
                self.last_knn_mode = "ann"
                self.last_quant_mode = qmode
                any_ann = True
                any_quant = True
            elif ivf is not None:
                W = ann_ops.slot_budget(ivf.sizes_desc_cum, nprobe_eff,
                                        ivf.n_docs, ivf.nlist)
                block = ann_ops.scan_block_size(Q, vc.dims, W)
                with tracing.span("ann_scan", shard=self.shard_id,
                                  nprobe=nprobe_eff, nlist=ivf.nlist,
                                  window=W):
                    top, idx = ann_ops.ivf_search(
                        vc.vecs, ivf.centroids, ivf.starts, ivf.sizes,
                        ivf.slot_docs, ivf.norms,
                        live if filtered else live_1d, qv,
                        k=min(kk, W), metric=metric, precision=precision,
                        nprobe=nprobe_eff, W=W, block=block,
                        per_query_live=filtered)
                self._bump("ann_dispatches")
                self.last_knn_mode = "ann"
                any_ann = True
            else:
                sims = knn_ops._sim(qv, vc.vecs, metric,
                                    precision=precision)
                sims = jnp.where(live, sims, -jnp.inf)
                top, idx = jax.lax.top_k(sims, kk)
                self.last_knn_mode = "exact"
            live_tot = live.sum(axis=1)
            # ONE fetch per segment (every fetch is a host sync)
            top, idx, seg_tot = device_fetch((top, idx, live_tot))
            n_fetches += 1
            total += np.asarray(seg_tot)
            seg_keys = np.where(np.isfinite(top),
                                (np.int64(seg_idx) << SEG_SHIFT)
                                | idx.astype(np.int64), np.int64(-1))
            merged = np.concatenate([best_scores, top], axis=1)
            merged_keys = np.concatenate([best_keys, seg_keys], axis=1)
            order = np.argsort(-merged, axis=1, kind="stable")[:, :k]
            best_scores = np.take_along_axis(merged, order, axis=1)
            best_keys = np.take_along_axis(merged_keys, order, axis=1)

        mx = np.where(np.isfinite(best_scores[:, 0]), best_scores[:, 0], np.nan)
        best_scores = np.where(best_keys >= 0, best_scores, np.nan)
        from ..common.metrics import current_profiler, record_shard_fetches
        record_shard_fetches(n_fetches)
        prof = current_profiler()
        if prof is not None:
            prof.note_path("ann_quantized" if any_quant
                           else "ann" if any_ann else "knn")
        from ..common.device_stats import lane_chosen
        lane_chosen(f"shard[{self.shard_id}].knn",
                    "ann_quantized" if any_quant
                    else "ann" if any_ann else "exact")
        return QuerySearchResult(
            shard_id=self.shard_id, doc_keys=best_keys, scores=best_scores,
            sort_values=None, total_hits=total, max_score=mx)

    # -- rescore (ref search/rescore/RescorePhase.java) --------------------

    def rescore(self, result: QuerySearchResult, rescore_spec: dict,
                n_queries: int = 1) -> QuerySearchResult:
        """Re-score the top window with a secondary query, per shard —
        exactly the reference's QueryRescorer: secondary scores combined
        with primaries under score_mode, only within window_size."""
        spec = rescore_spec.get("query", rescore_spec)
        window = int(rescore_spec.get("window_size",
                                      result.doc_keys.shape[1]))
        rq = spec.get("rescore_query")
        if rq is None:
            return result
        q_weight = float(spec.get("query_weight", 1.0))
        r_weight = float(spec.get("rescore_query_weight", 1.0))
        mode = spec.get("score_mode", "total")
        node = self.parser.parse(rq)
        stats = self.build_stats(node, None)
        Q, K = result.doc_keys.shape

        # secondary dense scores per segment, gathered at candidate slots
        sec = np.zeros((Q, K), np.float32)
        seg_scores: dict[int, np.ndarray] = {}
        for qi in range(Q):
            for pos in range(min(window, K)):
                key = int(result.doc_keys[qi, pos])
                if key < 0:
                    continue
                seg_idx = key >> SEG_SHIFT
                local = key & LOCAL_MASK
                if seg_idx not in seg_scores:
                    ctx = SegmentContext(self.segments[seg_idx], Q, stats)
                    s, m = node.execute(ctx)
                    seg_scores[seg_idx] = np.asarray(
                        jnp.where(m, s, 0.0))
                sec[qi, pos] = seg_scores[seg_idx][qi, local]

        from ..ops.knn import combine_scores
        prim = np.nan_to_num(result.scores, nan=0.0)
        combined = np.asarray(combine_scores(
            prim, sec, mode, q_weight, r_weight))   # host-side [Q,K] math
        in_window = np.arange(K)[None, :] < window
        new_scores = np.where(in_window & (result.doc_keys >= 0),
                              combined, prim)
        # re-sort only the window (docs below the window keep their order);
        # empty slots (doc_keys < 0) sort at -inf so they can never outrank a
        # real hit with a negative combined score
        sort_key = np.where(result.doc_keys >= 0, new_scores, -np.inf)
        order = np.argsort(-np.where(in_window, sort_key, -np.inf),
                           axis=1, kind="stable")
        full_order = np.concatenate(
            [order[:, :window], np.broadcast_to(np.arange(window, K), (Q, K - window))],
            axis=1) if K > window else order
        mx = sort_key.max(axis=1)
        out_keys = np.take_along_axis(result.doc_keys, full_order, axis=1)
        out_scores = np.take_along_axis(new_scores, full_order, axis=1)
        out_scores = np.where(out_keys >= 0, out_scores, np.nan)
        return QuerySearchResult(
            shard_id=result.shard_id,
            doc_keys=out_keys,
            scores=out_scores,
            sort_values=None, total_hits=result.total_hits,
            max_score=np.where(np.isfinite(mx), mx, np.nan),
            aggs=result.aggs)

    def rescore_batch(self, result: QuerySearchResult,
                      specs: list[dict]) -> QuerySearchResult:
        """Row-batched rescore: each row has its OWN rescore spec (same
        plan shape — e.g. per-query cosine vectors); the secondary scoring
        runs as ONE device program per involved segment for the whole
        batch instead of Q separate rescores (the msearch hybrid lane)."""
        Q, K = result.doc_keys.shape
        assert len(specs) == Q
        spec0 = specs[0].get("query", specs[0])
        window = int(specs[0].get("window_size", K))
        rq_nodes = []
        for sp in specs:
            s = sp.get("query", sp)
            if s.get("rescore_query") is None:
                return result
            rq_nodes.append(self.parser.parse(s["rescore_query"]))
        node = merge_query_batch(rq_nodes)
        stats = self.build_stats(node, None)
        q_weight = float(spec0.get("query_weight", 1.0))
        r_weight = float(spec0.get("rescore_query_weight", 1.0))
        mode = spec0.get("score_mode", "total")

        sec = np.zeros((Q, K), np.float32)
        w = min(window, K)
        kw = result.doc_keys[:, :w]
        valid = kw >= 0
        seg_of = np.where(valid, kw >> SEG_SHIFT, 0)
        for seg_idx in np.unique(seg_of[valid]):
            ctx = SegmentContext(self.segments[int(seg_idx)], Q, stats)
            s, m = node.execute(ctx)
            arr = np.asarray(jnp.where(m, s, 0.0))
            qq, pp = np.nonzero(valid & (seg_of == seg_idx))
            sec[qq, pp] = arr[qq, kw[qq, pp] & LOCAL_MASK]

        from ..ops.knn import combine_scores
        prim = np.nan_to_num(result.scores, nan=0.0)
        # [Q, K] combine is trivial arithmetic — numpy inputs keep it on
        # the host, no extra device round-trip
        combined = np.asarray(combine_scores(
            prim, sec, mode, q_weight, r_weight))
        in_window = np.arange(K)[None, :] < window
        new_scores = np.where(in_window & (result.doc_keys >= 0),
                              combined, prim)
        sort_key = np.where(result.doc_keys >= 0, new_scores, -np.inf)
        order = np.argsort(-np.where(in_window, sort_key, -np.inf),
                           axis=1, kind="stable")
        full_order = np.concatenate(
            [order[:, :window],
             np.broadcast_to(np.arange(window, K), (Q, K - window))],
            axis=1) if K > window else order
        mx = sort_key.max(axis=1)
        out_keys = np.take_along_axis(result.doc_keys, full_order, axis=1)
        out_scores = np.take_along_axis(new_scores, full_order, axis=1)
        out_scores = np.where(out_keys >= 0, out_scores, np.nan)
        return QuerySearchResult(
            shard_id=result.shard_id, doc_keys=out_keys,
            scores=out_scores, sort_values=None,
            total_hits=result.total_hits,
            max_score=np.where(np.isfinite(mx), mx, np.nan),
            aggs=result.aggs)

    # -- fetch phase -------------------------------------------------------

    def execute_fetch_phase(self, doc_keys: Sequence[int],
                            scores: Sequence[float] | None = None,
                            sort_values: Sequence[list] | None = None,
                            source_filter=None) -> list[FetchedHit]:
        """Load stored fields for the reduced winners
        (ref search/fetch/FetchPhase.java:79)."""
        hits = []
        for i, key in enumerate(doc_keys):
            key = int(key)
            if key < 0:
                continue
            seg_idx = key >> SEG_SHIFT
            local = key & LOCAL_MASK
            seg = self.segments[seg_idx]
            src = seg.stored[local]
            if source_filter:
                src = _filter_source(src, source_filter)
            sv = None
            if sort_values is not None:
                sv = sort_values[i]
                if sv is not None and not isinstance(sv, list):
                    sv = list(sv) if isinstance(sv, tuple) else [sv]
            hits.append(FetchedHit(
                doc_key=key,
                score=float(scores[i]) if scores is not None else float("nan"),
                sort_value=sv,
                doc_id=seg.ids[local], type_name=seg.types[local], source=src))
        return hits


def _filter_source(src: dict, spec) -> dict:
    """_source filtering: include/exclude path lists
    (ref search/fetch/source/FetchSourceSubPhase)."""
    import fnmatch

    if spec is True or spec is None:
        return src
    if spec is False:
        return {}
    includes = spec if isinstance(spec, list) else None
    excludes = None
    if isinstance(spec, dict):
        includes = spec.get("includes", spec.get("include"))
        excludes = spec.get("excludes", spec.get("exclude"))
    if isinstance(spec, str):
        includes = [spec]

    def flatten(obj, prefix=""):
        out = {}
        for k, v in obj.items():
            path = f"{prefix}{k}"
            if isinstance(v, dict):
                out.update(flatten(v, path + "."))
            else:
                out[path] = v
        return out

    if isinstance(includes, str):
        includes = [includes]
    if isinstance(excludes, str):
        excludes = [excludes]

    def hit(path, pat):
        # a pattern names a path OR a whole subtree ("include" matches
        # "include.field1"), like the reference's XContentMapValues filter
        return fnmatch.fnmatch(path, pat) or fnmatch.fnmatch(path, pat + ".*")

    flat = flatten(src)
    keep = {}
    for path, v in flat.items():
        ok = True
        if includes:
            ok = any(hit(path, pat) for pat in includes)
        if ok and excludes:
            ok = not any(hit(path, pat) for pat in excludes)
        if ok:
            keep[path] = v
    out: dict = {}
    for path, v in keep.items():
        parts = path.split(".")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


# dispatch accounting for the per-shard rowmax kernel (common/device_stats)
from ..common.device_stats import instrument as _instrument  # noqa: E402

_masked_rowmax = _instrument("shard:masked_rowmax", _masked_rowmax)
