"""Per-index service: N shard engines + mappers + routing.

Analog of the reference's IndexService (indices/IndicesService.java creates
one per index, holding IndexShard instances; SURVEY.md §2.5). Shards here are
independent Engines on disjoint doc partitions, routed by the reference's
exact hash function (parallel/routing.py).
"""

from __future__ import annotations

import itertools
import os
import shutil
import threading
from typing import Any

# monotonic index-incarnation ids (request-cache keys include one)
_INCARNATIONS = itertools.count(1)

from ..common.settings import Settings, EMPTY as EMPTY_SETTINGS
from ..mapping.mapper import MapperService
from ..parallel.routing import shard_id as route_shard
from ..search.shard_searcher import ShardSearcher
from .engine import Engine, EngineResult, GetResult


class IndexService:
    def __init__(self, name: str, path: str, settings: Settings | None = None,
                 mappings: dict | None = None, breakers=None, caches=None):
        self.name = name
        self.path = path
        self.caches = caches               # IndicesCacheService | None
        self.settings = settings if settings is not None else EMPTY_SETTINGS
        get = lambda k, d: self.settings.get(  # noqa: E731 — "index." optional
            f"index.{k}", self.settings.get(k, d))
        ns = get("number_of_shards", 1)
        nr = get("number_of_replicas", 1)   # 0 is a VALID replica count
        self.n_shards = int(ns) if ns is not None and str(ns) != "" else 1
        self.n_replicas = int(nr) if nr is not None and str(nr) != "" else 1
        # alias name -> properties ({filter, index_routing, search_routing})
        self.aliases: dict[str, dict] = {}
        self.breakers = breakers           # CircuitBreakerService | None
        fd = breakers.breaker("fielddata") if breakers is not None else None
        # custom analyzer/filter/tokenizer chains come from INDEX settings
        # (ref AnalysisService built per-index from its Settings)
        from ..analysis.analyzers import AnalysisService
        self.mappers = MapperService(
            analysis=AnalysisService(self.settings),
            mappings=mappings or {})
        # per-field similarity registry (named configs from index settings,
        # resolved via the mapping's "similarity" property) — attached to
        # the mapper service so QueryParser sees it everywhere
        from .similarity import SimilarityService
        self.mappers.similarity = SimilarityService(self.settings)
        # the vectorized bulk-ingest lane (index/bulk_ingest.py) is on
        # unless the index opts out (`index.bulk.vectorized.enable: false`
        # — the equivalence suite uses it to pin the per-doc path)
        raw_vec = get("bulk.vectorized.enable", True)
        self._bulk_vectorized = str(raw_vec).strip().lower() \
            not in ("false", "0", "no")
        self.shards: list[Engine] = [
            Engine(os.path.join(path, str(s)), self.mappers, breaker=fd,
                   fielddata_cache=caches.fielddata
                   if caches is not None else None,
                   ann_cache=caches.ann_indexes
                   if caches is not None else None,
                   index_name=name, vectorized=self._bulk_vectorized)
            for s in range(self.n_shards)]
        self.creation_date = None
        # searcher cache: rebuilt per shard only when its segment set changes
        # (the NRT "acquire searcher" analog — ref SearcherManager); device
        # query-path counters live here so they survive across requests
        self._searcher_cache: dict[int, tuple[tuple, ShardSearcher]] = {}
        self.search_stats = {"sparse": 0, "dense": 0, "packed": 0,
                             "stacked": 0, "mesh": 0}
        # the stacked dense lane is on unless the index opts out
        # (`index.search.stacked.enable: false` — the equivalence tests and
        # the chaos oracle use it to pin the per-segment loop it replaces)
        raw_stacked = get("search.stacked.enable", True)
        self._stacked_enabled = str(raw_stacked).strip().lower() \
            not in ("false", "0", "no")
        # the mesh-sharded query lane (parallel/mesh_exec) engages for
        # multi-shard unsorted queries unless the index opts out
        # (`index.search.mesh.enable: false` — the equivalence tests use it
        # to pin the thread-pool fan-out it replaces)
        raw_mesh = get("search.mesh.enable", True)
        self._mesh_enabled = str(raw_mesh).strip().lower() \
            not in ("false", "0", "no")
        # streaming blockwise dense execution (search/blockwise.py):
        # segments/stacks wider than `index.search.block_docs` run the DSL
        # tree per pow2 doc block under a running on-device top-k — peak
        # score memory O(Q × block) instead of O(Q × n_pad). Opt out with
        # `index.search.blockwise.enable: false` (the equivalence suite and
        # the chaos oracle use it to pin the materializing executor).
        raw_blk = get("search.blockwise.enable", True)
        self._blockwise_enabled = str(raw_blk).strip().lower() \
            not in ("false", "0", "no")
        from ..search.blockwise import DEFAULT_BLOCK_DOCS
        raw_bd = get("search.block_docs", DEFAULT_BLOCK_DOCS)
        try:
            self._block_docs = int(raw_bd)
        except (TypeError, ValueError):
            self._block_docs = DEFAULT_BLOCK_DOCS
        # IVF-clustered ANN kNN lane (ops/ann.py): knn queries over
        # columns past `index.knn.ivf.min_docs` route through a trained
        # cluster index instead of the full [Q, N] matmul. Opt out with
        # `index.knn.ivf.enable: false`; nlist/nprobe default to
        # ~sqrt(N) / nlist/8 when 0. `index.knn.precision` pins the
        # matmul dtype (bf16 default, f32 for exact-parity workloads).
        self._knn_opts = knn_options_from(get)
        # op counters surfaced by _stats (ref index/shard stats holders:
        # IndexingStats w/ per-type breakdown, SearchStats w/ groups, GetStats)
        self.indexing_stats: dict = {"index_total": 0, "delete_total": 0,
                                     "types": {}}
        self.search_groups: dict[str, int] = {}
        self.query_total = 0
        self.get_total = 0
        # windowed op rates (1m/5m/15m EWMA) — `*_rate` in `_stats`,
        # `_cat/indices` and the /_metrics scrape; every op-count bump
        # below also marks its meter
        from ..common.metrics import Meter
        self.meters: dict[str, Meter] = {"search": Meter(),
                                         "indexing": Meter(),
                                         "get": Meter()}
        # shard request cache counters (ref indices/cache/request/
        # IndicesRequestCache — size-0 responses keyed by reader version)
        self.request_cache_hits = 0
        self.request_cache_misses = 0
        # unique per index INCARNATION: delete+recreate under the same name
        # must never hit the old incarnation's cache entries
        self._incarnation = next(_INCARNATIONS)
        # fused serving view over all shards' segments (serving/packed_view):
        # rebuilt only when the segment set changes; tombstone-only changes
        # are folded into its packed postings in place. A single-entry
        # common.cache Cache so its bytes/evictions surface uniformly; the
        # removal listener releases the "request" breaker charge on every exit
        from ..common.cache import Cache
        self._packed_view_cache = Cache(
            "packed_view", max_entries=1,
            weigher=lambda v: getattr(v[1], "memory_bytes", 0),
            removal_listener=self._on_packed_removed)
        if caches is not None:
            caches.register(f"packed_view[{name}]", self._packed_view_cache)
        # the panel lane's operands a chip (search/aggs/panels.PanelView):
        # a second copy of the columns it serves, charged to the fielddata
        # breaker as it grows and released when the view leaves (a change
        # of segments, close, delete: it goes with the incarnation)
        self._panel_view_cache = Cache(
            "panel_view", max_entries=1,
            removal_listener=lambda _k, v, _why: v[1].release())
        self._panel_view_lock = threading.Lock()
        if caches is not None:
            caches.register(f"panel_view[{name}]", self._panel_view_cache)

    def reader_generation(self) -> tuple:
        """Changes whenever a refresh/merge/delete changes what a searcher
        can see — the request-cache key component (the reference keys on
        the IndexReader version the same way)."""
        return tuple((e.refresh_count, e.merge_count,
                      sum(s.live_gen for s in e.segments),
                      len(e._buffer_docs))
                     for e in self.shards)

    # -- routing -----------------------------------------------------------

    def shard_for(self, doc_id: str, routing: str | None = None) -> Engine:
        return self.shards[route_shard(doc_id, self.n_shards, routing)]

    # -- document ops (ref index/shard/IndexShard.java:444-523) ------------

    def index_doc(self, doc_id: str, source: dict, type_name: str = "_doc",
                  routing: str | None = None, parent: str | None = None,
                  **kw) -> EngineResult:
        # _parent doubles as routing so parent and children co-locate
        # (ref index/mapper/internal/ParentFieldMapper routing contract)
        if parent is not None and routing is None:
            routing = parent
        res = self.shard_for(doc_id, routing).index(
            doc_id, source, type_name=type_name, routing=routing,
            parent=parent, **kw)
        self.indexing_stats["index_total"] += 1
        self.meters["indexing"].mark()
        tmap = self.indexing_stats["types"]
        tmap[type_name] = tmap.get(type_name, 0) + 1
        return res

    def get_doc(self, doc_id: str, routing: str | None = None,
                realtime: bool = True,
                parent: str | None = None) -> GetResult:
        if parent is not None and routing is None:
            routing = parent
        self.get_total += 1
        self.meters["get"].mark()
        return self.shard_for(doc_id, routing).get(doc_id, realtime=realtime)

    def delete_doc(self, doc_id: str, routing: str | None = None,
                   parent: str | None = None, **kw) -> EngineResult:
        if parent is not None and routing is None:
            routing = parent
        res = self.shard_for(doc_id, routing).delete(doc_id, **kw)
        self.indexing_stats["delete_total"] += 1
        self.meters["indexing"].mark()
        return res

    def bulk_ingest(self, ops: list) -> list:
        """Vectorized bulk lane: route a run of BulkOps to their shards and
        apply each shard's slice as ONE Engine.index_batch pass (batched
        analysis + columnar buffer + group-commit translog). Preserves
        per-shard op order (same-id ops always route to the same shard, so
        cross-shard order is immaterial). Translog fsyncs are deferred —
        the caller ends the request with sync_translogs(). Returns results
        aligned with `ops` (EngineResult or the per-item exception)."""
        for op in ops:
            if op.routing is None and op.parent is not None:
                op.routing = op.parent  # _parent doubles as routing
        if self.n_shards == 1:
            # single-shard indices skip the per-op routing hash entirely
            results = self.shards[0].index_batch(ops, sync=False)
        else:
            by_shard: dict[int, tuple[list[int], list]] = {}
            for pos, op in enumerate(ops):
                sid = route_shard(op.doc_id, self.n_shards, op.routing)
                slot = by_shard.setdefault(sid, ([], []))
                slot[0].append(pos)
                slot[1].append(op)
            results = [None] * len(ops)
            for sid, (positions, shard_ops) in by_shard.items():
                out = self.shards[sid].index_batch(shard_ops, sync=False)
                for pos, res in zip(positions, out):
                    results[pos] = res
        # op counters mirror the per-doc path: successes only, per type
        n_index = n_delete = 0
        tmap = self.indexing_stats["types"]
        for op, res in zip(ops, results):
            if not isinstance(res, EngineResult):
                continue
            if op.action == "delete":
                n_delete += 1
            else:
                n_index += 1
                tmap[op.type_name] = tmap.get(op.type_name, 0) + 1
        self.indexing_stats["index_total"] += n_index
        self.indexing_stats["delete_total"] += n_delete
        if n_index or n_delete:
            self.meters["indexing"].mark(n_index + n_delete)
        return results

    def sync_translogs(self) -> None:
        """One fsync per shard — the tail of a deferred-sync bulk request
        (ref 'request' durability: fsync per request, not per op)."""
        for e in self.shards:
            e.translog.sync()

    # -- lifecycle ---------------------------------------------------------

    def refresh(self) -> None:
        for e in self.shards:
            e.refresh()
        self._drop_stale_stacks()

    def flush(self) -> None:
        for e in self.shards:
            e.flush()
        self._drop_stale_stacks()

    def force_merge(self, max_num_segments: int = 1) -> None:
        for e in self.shards:
            e.force_merge(max_num_segments)
        self._drop_stale_stacks()

    def _drop_stale_stacks(self) -> None:
        """A refresh/merge changed some shard's segment set: free stale
        packed segment stacks NOW (their removal listener hands the device
        bytes back to the fielddata breaker) instead of waiting for the
        next query's put to displace them."""
        if self.caches is None:
            return
        valid = {(si, tuple(s.seg_id for s in e.segments if s.n_docs > 0))
                 for si, e in enumerate(self.shards)}
        self.caches.segment_stacks.drop_stale(self.name, valid)
        self.caches.mesh_stacks.drop_stale(self.name, valid)
        self.caches.mesh_vector_stacks.drop_stale(self.name, valid)

    def _on_packed_removed(self, _key, value, _reason) -> None:
        """Packed-view cache removal: hand the view's duplicate-postings
        bytes back to the `request` breaker (the view charged them at
        build time)."""
        _k, view = value
        if self.breakers is not None and view is not None:
            self.breakers.breaker("request").release(view.memory_bytes)

    def close(self) -> None:
        for cached in self._searcher_cache.values():
            cached[2].release()     # before engine close: the leak
        self._searcher_cache.clear()  # detector asserts refcounts drained
        for e in self.shards:
            e.close()
        self._packed_view_cache.clear()
        self._panel_view_cache.clear()
        if self.caches is not None:
            self.caches.segment_stacks.clear([self.name])
            self.caches.mesh_stacks.clear([self.name])
            self.caches.ann_indexes.clear([self.name])

    def delete_files(self) -> None:
        shutil.rmtree(self.path, ignore_errors=True)

    # -- search ------------------------------------------------------------

    def searchers(self) -> list[ShardSearcher]:
        out = []
        for si, e in enumerate(self.shards):
            key = tuple(s.seg_id for s in e.segments)
            cached = self._searcher_cache.get(si)
            if cached is None or cached[0] != key:
                if cached is not None:
                    # rotation releases the stale searcher's refcount —
                    # the leak detector (ISSUE 14) pins this symmetry
                    cached[2].release()
                handle = e.acquire_searcher(
                    site=f"index[{self.name}]/shard[{si}]/searchers")
                cached = (key, ShardSearcher(
                    si, e.segments, self.mappers, stats=self.search_stats,
                    stack_cache=self.caches.segment_stacks
                    if self.caches is not None else None,
                    index_name=self.name, incarnation=self._incarnation,
                    stacked=self._stacked_enabled,
                    blockwise=self._blockwise_enabled,
                    block_docs=self._block_docs,
                    request_breaker=self.breakers.breaker("request")
                    if self.breakers is not None else None,
                    knn_opts=self._knn_opts), handle)
                self._searcher_cache[si] = cached
            out.append(cached[1])
        return out

    def packed_view(self):
        """The one-device-program serving view for this index (all shards'
        segments fused). None when the index is empty, or when the "request"
        breaker refuses the view's duplicate postings (the packed view
        roughly doubles device residency for text fields — breach degrades
        to the per-segment lane, it never raises).

        NRT-friendly: when the segment set only GREW (refresh without a
        merge), the new view EXTENDS the cached one — appended segments'
        postings concatenate on device; cost is O(new postings), not
        O(index) (advisor r3 medium). Any removal (merge) rebuilds."""
        from ..serving.packed_view import PackedIndexView
        live: dict[tuple, object] = {}
        for si, e in enumerate(self.shards):
            for seg in e.segments:
                live[(si, seg.seg_id)] = seg
        if not live:
            return None
        key = tuple(sorted(live))
        cached = self._packed_view_cache.get("view")
        if cached is not None and cached[0] == key:
            return cached[1]
        req = self.breakers.breaker("request") \
            if self.breakers is not None else None
        old = cached[1] if cached is not None else None
        base = None
        entries = None
        if old is not None:
            old_keys = [(si, seg.seg_id) for si, seg in old.entries]
            if all(k in live and live[k] is seg
                   for k, (_, seg) in zip(old_keys, old.entries)) \
                    and len(old_keys) == len(set(old_keys)):
                appended = [(si, seg) for (si, sid), seg in live.items()
                            if (si, sid) not in set(old_keys)]
                appended.sort(key=lambda x: (x[0], x[1].seg_id))
                base = old
                entries = list(old.entries) + appended
        if entries is None:
            entries = [(si, seg) for si, e in enumerate(self.shards)
                       for seg in e.segments]
        if old is not None:
            # release the stale view's charge (removal listener) BEFORE
            # building — the new view needs the breaker headroom
            self._packed_view_cache.invalidate("view")
        view = PackedIndexView(entries, breaker=req, base=base)
        self._packed_view_cache.put("view", (key, view))
        return view

    def panel_view(self, pool):
        """The panel lane's view of this index's segments as they stand on
        the chips of `pool` (search/aggs/panels.py), built anew when the
        segments changed: what the last view had placed is placed again,
        a chip whose segments stayed keeps its blocks. None where the
        fielddata breaker refuses the copy: the caller keeps the path it
        had."""
        from ..common.breaker import CircuitBreakingException
        from ..search.aggs.panels import PanelView
        shards = [list(s.segments) for s in self.searchers()]
        key = (pool.devkey, tuple(tuple(id(seg) for seg in segments)
                                  for segments in shards))
        with self._panel_view_lock:
            cached = self._panel_view_cache.get("view")
            if cached is not None and cached[0] == key:
                return cached[1]
            # the stale view's charge goes back before the new one builds;
            # its blocks live on as `base` until the new view has them
            self._panel_view_cache.invalidate("view")
            try:
                view = PanelView(
                    shards, pool, base=cached[1] if cached else None,
                    breaker=self.breakers.breaker("fielddata")
                    if self.breakers is not None else None)
            except CircuitBreakingException:
                return None
            self._panel_view_cache.put("view", (key, view))
            return view

    # -- introspection -----------------------------------------------------

    def doc_count(self) -> int:
        return sum(e.doc_count() for e in self.shards)

    def stats(self) -> dict:
        seg = [e.segment_stats() for e in self.shards]
        return {
            "docs": {"count": self.doc_count(),
                     "deleted": sum(s["deleted"] for s in seg)},
            "segments": {"count": sum(s["count"] for s in seg),
                         "memory_in_bytes": sum(s["memory_in_bytes"] for s in seg)},
            "translog": {"operations": sum(e.translog.ops_since_commit
                                           for e in self.shards)},
            "shards": {"total": self.n_shards * (1 + self.n_replicas),
                       "primaries": self.n_shards},
            "packed_view_cache": self._packed_view_cache.stats(),
        }

    def mappings_dict(self) -> dict:
        return self.mappers.mappings_dict()


def knn_options_from(get) -> dict:
    """Read the kNN/ANN settings roster through an `(key, default)`
    getter (index Settings here; cluster-state dicts in cluster/node.py
    read the same keys for searcher parity)."""
    def as_bool(v, default=True):
        if v is None:
            return default
        return str(v).strip().lower() not in ("false", "0", "no")

    def as_int(v, default=0):
        try:
            return int(v)
        except (TypeError, ValueError):
            return default

    precision = str(get("knn.precision", "bf16")).strip().lower()
    if precision not in ("bf16", "f32"):
        precision = "bf16"
    # quantized ANN tier (ISSUE 12): int8 / IVF-PQ cluster scan with a
    # full-precision rescore of the top `rescore_window` survivors;
    # anything unrecognized degrades to the f32 IVF lane
    quant = str(get("knn.quantization", "none")).strip().lower()
    if quant not in ("none", "int8", "pq"):
        quant = "none"
    from ..ops.ann import DEFAULT_PQ_M
    return {
        "ivf_enable": as_bool(get("knn.ivf.enable", True)),
        "nlist": as_int(get("knn.ivf.nlist", 0)),
        "nprobe": as_int(get("knn.ivf.nprobe", 0)),
        "min_docs": as_int(get("knn.ivf.min_docs", 4096), 4096),
        "precision": precision,
        "quantization": quant,
        "pq_m": as_int(get("knn.pq.m", DEFAULT_PQ_M), DEFAULT_PQ_M),
        "rescore_window": as_int(get("knn.rescore_window", 0)),
    }
