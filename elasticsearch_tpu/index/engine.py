"""Per-shard storage engine: versioned CRUD over immutable tensor segments.

The analog of the reference InternalEngine
(/root/reference/src/main/java/org/elasticsearch/index/engine/InternalEngine.java:65):
  * in-memory write buffer (SegmentBuilder) plays IndexWriter's RAM buffer
  * refresh() freezes the buffer into a device segment — NRT searcher analog
    (InternalEngine.java:80-83 SearcherManager; default 1s in the reference)
  * LiveVersionMap for realtime get + optimistic versioning
    (InternalEngine.java:94,107; version checks :255-270)
  * every op appended to the translog before ack (InternalEngine.java:331)
  * flush() = commit: persist segment state + roll/trim translog
  * tiered-ish merge: many small segments collapse into one (index/merge/)

Single-writer discipline per shard (the reference serializes writes per uid
via uid-locks; here a shard-level lock since ops are host-side builder
mutations — device state is only produced at refresh)."""

from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Sequence

import numpy as np

from ..mapping.mapper import MapperService
from .segment import Segment, SegmentBuilder, merge_segments
from .translog import Translog


# Leak detection (ISSUE 14, the AssertingSearcher / mock-directory
# discipline): when armed (testing.chaos.detectors.arm(), wired into
# tests/conftest.py for the whole suite), Engine.close() ASSERTS that every
# acquired searcher handle was released and that every byte the engine
# charged to its breaker was handed back — naming the acquire site of each
# leak, plus the reproducing CHAOS_SEED when one is set.
LEAK_CHECK = False


def _seed_tag() -> str:
    seed = os.environ.get("CHAOS_SEED")
    return f" [CHAOS_SEED={seed}]" if seed else ""


class SearcherLeakError(AssertionError):
    """An engine closed with acquired-but-unreleased state (searcher
    handles or breaker charges). Only raised when leak checking is armed."""


class SearcherHandle:
    """A refcounted searcher acquisition (ref AssertingSearcher): the
    acquire site is recorded so a leak names the code that forgot to
    release, not just 'something leaked'."""

    __slots__ = ("engine", "site", "released")

    def __init__(self, engine: "Engine", site: str):
        self.engine = engine
        self.site = site
        self.released = False

    def release(self) -> None:
        if self.released:
            return
        self.released = True
        self.engine._open_searchers.pop(id(self), None)


class VersionConflictException(Exception):
    def __init__(self, doc_id: str, current: int, expected: int):
        super().__init__(
            f"version conflict for [{doc_id}]: current [{current}], provided [{expected}]")
        self.current = current
        self.expected = expected


class DocumentMissingException(Exception):
    pass


class EngineResult:
    """__slots__, not a dataclass: one is built per write op and the
    generated kwargs __init__ is measurable at bulk rates (ISSUE 7)."""

    __slots__ = ("doc_id", "version", "created", "found")

    def __init__(self, doc_id: str, version: int, created: bool,
                 found: bool = True):
        self.doc_id = doc_id
        self.version = version
        self.created = created
        self.found = found


@dataclass
class GetResult:
    found: bool
    doc_id: str
    version: int = -1
    source: dict | None = None
    type_name: str = "_doc"
    routing: str | None = None
    parent: str | None = None
    timestamp: int | None = None     # _timestamp metadata (epoch ms)
    ttl_expiry: int | None = None    # _ttl expiry instant (epoch ms)


def _rough_doc_bytes(source: dict) -> int:
    """Cheap buffered-source size estimate (IndexingMemoryController input;
    exactness doesn't matter — relative shard pressure does)."""
    try:
        n = 64
        for k, v in source.items():
            c = v.__class__
            n += len(k) + (len(v) if c is str
                           else 8 * len(v) if c is list else 16)
        return n
    except Exception:  # noqa: BLE001 — estimates must never raise
        return 256


def _segment_long(seg: Segment, field: str, local: int) -> int | None:
    """Host-cached read of an i64 metadata column (_timestamp/_ttl_expiry)."""
    nc = seg.numerics.get(field)
    if nc is None:
        return None
    vals = getattr(nc, "_vals_np2", None)
    if vals is None:
        vals = (np.asarray(nc.vals), np.asarray(nc.missing))
        object.__setattr__(nc, "_vals_np2", vals)
    v, miss = vals
    return None if miss[local] else int(v[local])


def _segment_parent(seg: Segment, local: int) -> str | None:
    """The doc's _parent id from the keyword column (host-cached ords)."""
    kc = seg.keywords.get("_parent")
    if kc is None:
        return None
    ords = getattr(kc, "_ords_np", None)
    if ords is None:
        ords = np.asarray(kc.ords)
        object.__setattr__(kc, "_ords_np", ords)
    o = int(ords[local])
    return kc.values[o] if o >= 0 else None


class Engine:
    """Versioned, durable per-shard engine over tensor segments."""

    MERGE_SEGMENT_COUNT = 8          # merge trigger (TieredMergePolicy-ish)
    # doc-count refresh trigger (indexing buffer analog) — a backstop; the
    # real bound is the node-wide BYTE budget (check_indexing_memory /
    # indices.memory.index_buffer_size), so this sits above a 100k-doc
    # bulk: one bulk ingest freezes into ONE segment instead of
    # paying a mid-request refresh plus a 2-segment force-merge
    MAX_BUFFER_DOCS = 131072

    def __init__(self, shard_path: str, mappers: MapperService,
                 type_name_default: str = "_doc", durability: str = "request",
                 breaker=None, fielddata_cache=None, index_name=None,
                 vectorized: bool = True, ann_cache=None):
        self.path = shard_path
        self.mappers = mappers
        # the vectorized bulk-ingest lane (index/bulk_ingest.py): batched
        # analysis in index_batch + columnar add_batch at refresh. Off
        # (`index.bulk.vectorized.enable: false`) the engine runs the
        # per-doc path end to end — the equivalence suite's control lane.
        self.vectorized = vectorized
        # HBM accounting (common/breaker.py; ref HierarchyCircuitBreaker-
        # Service): segments charge the "fielddata" breaker at build time
        self.breaker = breaker
        # node-level fielddata tier (indices/cache_service.FielddataCache):
        # when attached, built sort columns live THERE (LRU, evictable
        # under breaker pressure) instead of pinned per-segment dicts
        self.fielddata_cache = fielddata_cache
        # node-level IVF cluster-index tier (AnnIndexCache): the ANN kNN
        # lane's centroids + CSR live there, dying with their segment
        self.ann_cache = ann_cache
        self.index_name = index_name
        self._blocked_reason = None
        os.makedirs(shard_path, exist_ok=True)
        from .store import SegmentStore
        self.store = SegmentStore(shard_path)
        self.translog = Translog(os.path.join(shard_path, "translog"), durability)
        self._lock = threading.RLock()
        self.segments: list[Segment] = []
        # deletes staged until the next refresh (NRT delete visibility);
        # the set mirror answers "is this copy stale?" for O(1) get checks
        self._pending_deletes: list[tuple] = []
        self._pending_set: set[tuple[int, int]] = set()
        self._buffer = SegmentBuilder(seg_id=0)
        # id -> (source, type, routing)
        # id -> (source, type, routing, parent, ParsedDocument)
        self._buffer_docs: dict[str, tuple] = {}
        # rough host bytes buffered (IndexingMemoryController's input);
        # per-doc estimates are remembered so eviction subtracts exactly
        # what admission added (the batch lane estimates from raw JSON
        # line length, the per-doc lane from a source-dict walk)
        self._buffer_bytes = 0
        self._buffer_sizes: dict[str, int] = {}
        self._next_seg_id = 1
        # LiveVersionMap: id -> (version, deleted)
        self.versions: dict[str, tuple[int, bool]] = {}
        self._dirty = False
        # monotonic mutation generations: `mutation_gen` bumps on EVERY
        # accepted write/delete; `percolator_gen` only when the registered
        # `.percolator` roster can have changed (a `.percolator` index, or
        # any delete — deletes don't carry a type). Cache tiers key on
        # these instead of buffer lengths, which alias across
        # delete-then-reinsert of the same count (ISSUE 18 bugfix).
        self.mutation_gen = 0
        self.percolator_gen = 0
        self.refresh_count = 0
        self.flush_count = 0
        self.merge_count = 0
        # leak-detector state (ISSUE 14): open searcher handles (id ->
        # handle) and the per-site breaker ledger — net bytes this engine
        # charged, keyed by the charge site; symmetric with every
        # add_estimate/release pair below, so close() can assert it drains
        self._open_searchers: dict[int, SearcherHandle] = {}
        self._charge_sites: dict[str, int] = {}
        self._closed = False
        self._load_commit()
        self._recover()

    # -- leak-detector seams (ISSUE 14) -----------------------------------

    def acquire_searcher(self, site: str = "?") -> SearcherHandle:
        """Acquire a refcounted searcher reference. The caller MUST call
        handle.release() when the searcher goes out of use; when leak
        checking is armed, close() fails naming `site` for every handle
        still open."""
        h = SearcherHandle(self, site)
        self._open_searchers[id(h)] = h
        return h

    def _ledger(self, site: str, delta: int) -> None:
        """Track the engine's own breaker traffic per charge site; a site
        that drains to zero leaves the ledger."""
        n = self._charge_sites.get(site, 0) + delta
        if n:
            self._charge_sites[site] = n
        else:
            self._charge_sites.pop(site, None)

    def _leak_check(self) -> None:
        problems = []
        for h in self._open_searchers.values():
            problems.append(f"searcher acquired at [{h.site}] never "
                            f"released")
        for site, n in sorted(self._charge_sites.items()):
            problems.append(f"breaker charge from [{site}] has {n} bytes "
                            f"outstanding")
        # cache-entry accounting: a closed engine's segments must not pin
        # fielddata / ANN cache entries (their removal listeners hand the
        # breaker charge back — an entry that survives leaks it forever)
        for s in self.segments:
            if self.fielddata_cache is not None:
                b = self.fielddata_cache.bytes_for(s)
                if b:
                    problems.append(
                        f"fielddata cache entries for segment "
                        f"{s.seg_id} survived close: {sorted(b)}")
        if problems:
            raise SearcherLeakError(
                f"engine [{self.path}] closed with leaks: "
                + "; ".join(problems) + _seed_tag())

    # -- recovery (translog replay, ref InternalEngine recoverFromTranslog) --

    def _load_commit(self) -> None:
        """Load the last commit point (gateway recovery analog, SURVEY §5.4b):
        binary segment files load directly onto device — no re-analysis, no
        re-tokenization; recovery cost is IO + device_put, not CPU parsing.
        Raises store.CorruptIndexException if any segment file fails its
        checksum (ref index/store/Store.java recovery verification)."""
        segments, tombstones = self.store.load()
        self.segments = segments
        for s in segments:
            self._adopt(s)              # fielddata loads charge it too
        if self.breaker is not None:
            # recovery loads regardless of pressure (unbreakable add) —
            # refusing to boot would lose availability, not memory
            for s in segments:
                self.breaker.add_estimate(s.memory_bytes(), check=False)
                self._ledger(f"segment:{s.seg_id}", s.memory_bytes())
        self._next_seg_id = max((s.seg_id for s in segments), default=0) + 1
        # rebuild the LiveVersionMap: manifest order is chronological, so
        # later segments override earlier ones for re-indexed docs
        for seg in segments:
            for local, doc_id in enumerate(seg.ids):
                if seg.live_host[local] \
                        and not seg.types[local].startswith("__"):
                    self.versions[doc_id] = (seg.versions[local], False)
        for doc_id, v in tombstones.items():
            self.versions[doc_id] = (int(v), True)

    def _recover(self) -> None:
        n = 0
        for op in self.translog.snapshot():
            kind = op["op"]
            if kind == "index":
                from ..mapping.mapper import AlreadyExpiredException
                try:
                    self._apply_index(op["id"], op["source"],
                                      op.get("type", "_doc"),
                                      version=op["version"],
                                      routing=op.get("routing"),
                                      parent=op.get("parent"),
                                      timestamp=op.get("ts"),
                                      ttl=op.get("ttl"))
                except AlreadyExpiredException:
                    continue    # the doc's TTL lapsed while we were down
            elif kind == "delete":
                self._apply_delete(op["id"], version=op["version"])
            n += 1
        if n:
            self.refresh()

    # -- version resolution ------------------------------------------------

    def current_version(self, doc_id: str) -> int:
        """-1 = not found; otherwise the live version."""
        v = self.versions.get(doc_id)
        if v is None or v[1]:
            return -1
        return v[0]

    def _check_version(self, doc_id: str, version: int | None,
                       version_type: str, op_type: str) -> int:
        """Returns the new version; raises VersionConflictException
        (ref InternalEngine.java:233-339 create/index/delete w/ conflicts)."""
        return self._resolve_version(self.versions.get(doc_id), doc_id,
                                     version, version_type, op_type)

    def _resolve_version(self, raw: tuple[int, bool] | None, doc_id: str,
                         version: int | None, version_type: str,
                         op_type: str) -> int:
        """_check_version over an explicit (version, deleted) state — the
        batch lane resolves against its in-flight overlay so duplicate
        ids WITHIN one bulk request see each other's versions."""
        cur = -1 if raw is None or raw[1] else raw[0]
        if op_type == "create" and cur != -1:
            raise VersionConflictException(doc_id, cur, -1)
        if version is None or version in (-1, -3):  # MATCH_ANY / internal
            # version continues across delete tombstones, like the
            # reference's LiveVersionMap (delete v2 -> reindex v3)
            return raw[0] + 1 if raw is not None else 1
        if version_type == "external":
            if raw is not None and version <= raw[0]:
                raise VersionConflictException(doc_id, raw[0], version)
            return version
        if version_type == "external_gte":
            # >= is acceptable (ref VersionType.EXTERNAL_GTE)
            if raw is not None and version < raw[0]:
                raise VersionConflictException(doc_id, raw[0], version)
            return version
        if version_type == "force":
            return version          # ref VersionType.FORCE: always wins
        # internal: provided version must equal current
        if cur != version:
            raise VersionConflictException(doc_id, cur, version)
        return cur + 1

    # -- write ops ---------------------------------------------------------

    def index(self, doc_id: str, source: dict, type_name: str = "_doc",
              version: int | None = None, version_type: str = "internal",
              op_type: str = "index", sync: bool | None = None,
              routing: str | None = None,
              parent: str | None = None,
              timestamp=None, ttl=None) -> EngineResult:
        with self._lock:
            if self._blocked_reason is not None \
                    or len(self._buffer_docs) >= self.MAX_BUFFER_DOCS:
                # flush-or-reject happens BEFORE this write applies: a
                # breaker trip here is a clean 429 with no partial state
                # (the doc is neither buffered nor in the translog), and a
                # previously-blocked engine re-attempts the refresh in case
                # the budget was freed
                self.refresh()
            new_version = self._check_version(doc_id, version, version_type, op_type)
            created = self.current_version(doc_id) == -1
            if timestamp is None:
                # resolve NOW so translog replay reproduces the same value
                timestamp = int(time.time() * 1000)
            self._apply_index(doc_id, source, type_name, new_version, routing,
                              parent, timestamp, ttl)
            op = {"op": "index", "id": doc_id, "type": type_name,
                  "source": source, "version": new_version,
                  "routing": routing, "ts": timestamp}
            if parent is not None:
                op["parent"] = parent
            if ttl is not None:
                op["ttl"] = ttl
            self.translog.add(op, sync=sync)
            return EngineResult(doc_id=doc_id, version=new_version, created=created)

    def _apply_index(self, doc_id: str, source: dict, type_name: str,
                     version: int, routing: str | None = None,
                     parent: str | None = None,
                     timestamp=None, ttl=None) -> None:
        # parse NOW, not at refresh: a malformed doc (bad date, missing
        # parent, wrong vector dims) must 400 this request — parsing lazily
        # would poison the shared refresh instead (ref IndexShard.prepareIndex
        # parses before the engine op; code review r5)
        mapper = self.mappers.document_mapper(type_name)
        parsed = mapper.parse(source, doc_id=doc_id, routing=routing,
                              parent=parent, timestamp=timestamp, ttl=ttl)
        self._delete_everywhere(doc_id)   # pops any buffered predecessor
        self._buffer_docs[doc_id] = (source, type_name, routing, parent,
                                     parsed)
        est = _rough_doc_bytes(source)
        self._buffer_sizes[doc_id] = est
        self._buffer_bytes += est
        self.versions[doc_id] = (version, False)
        self._dirty = True
        self.mutation_gen += 1
        if type_name == ".percolator":
            self.percolator_gen += 1

    def delete(self, doc_id: str, version: int | None = None,
               version_type: str = "internal",
               sync: bool | None = None) -> EngineResult:
        with self._lock:
            cur = self.current_version(doc_id)
            found = cur != -1
            new_version = self._check_version(doc_id, version, version_type, "delete") \
                if found or version is not None else 1
            self._apply_delete(doc_id, new_version)
            self.translog.add({"op": "delete", "id": doc_id,
                               "version": new_version}, sync=sync)
            return EngineResult(doc_id=doc_id, version=new_version,
                                created=False, found=found)

    def _apply_delete(self, doc_id: str, version: int) -> None:
        self._delete_everywhere(doc_id)
        self.versions[doc_id] = (version, True)
        self._dirty = True
        self.mutation_gen += 1
        self.percolator_gen += 1

    # -- batched write path (the vectorized bulk lane, ISSUE 7) ------------

    BULK_CHUNK = 16384               # ops per batched pass (< MAX_BUFFER_DOCS)

    def index_batch(self, ops, sync: bool | None = None) -> list:
        """Apply a run of BulkOps (index/create/delete) as ONE batched pass
        per chunk: sequential version resolution against an in-flight
        overlay (duplicate ids within the request see each other), per-doc
        mapper.parse with DEFERRED text analysis, one grouped batch-analysis
        flush, then buffer mutations plus a single group-commit translog
        write (ref TransportShardBulkAction.java:133 — the reference's
        shard-level bulk pass with one fsync per request).

        Returns a list aligned with `ops`: EngineResult on success, the
        raised exception object on per-item failure (the caller maps
        VersionConflict->409 / parse errors->400 / breaker->429)."""
        from .bulk_ingest import TextBatcher
        results: list = [None] * len(ops)
        wrote = False
        with self._lock:
            for c0 in range(0, len(ops), self.BULK_CHUNK):
                chunk = ops[c0:c0 + self.BULK_CHUNK]
                if self._blocked_reason is not None \
                        or len(self._buffer_docs) + len(chunk) \
                        > self.MAX_BUFFER_DOCS:
                    try:
                        self.refresh()
                    except Exception as e:  # noqa: BLE001 — per-item 429s
                        for i in range(len(chunk)):
                            results[c0 + i] = e
                        continue
                batcher = TextBatcher()
                overlay: dict[str, tuple[int, bool]] = {}
                overlay_get = overlay.get
                versions_get = self.versions.get
                type_mappers: dict = {}
                # one wall-clock read per chunk: every doc of a batched
                # pass stamps the same _timestamp (the per-doc path's
                # per-op ms resolution collapses to chunk resolution;
                # translog replay reproduces the stored value either way)
                now_ms = int(time.time() * 1000)
                # (global_i, op, new_version, parsed|None, created/found, ts)
                staged: list[tuple] = []
                stage = staged.append
                for i, op in enumerate(chunk):
                    gi = c0 + i
                    doc_id = op.doc_id
                    raw = overlay_get(doc_id) or versions_get(doc_id)
                    try:
                        action = op.action
                        if action == "delete":
                            found = raw is not None and not raw[1]
                            nv = self._resolve_version(
                                raw, doc_id, op.version, op.version_type,
                                "delete") \
                                if found or op.version is not None else 1
                            overlay[doc_id] = (nv, True)
                            stage((gi, op, nv, None, found, None))
                            continue
                        if op.version is None:
                            # MATCH_ANY fast path (the bulk-typical shape):
                            # no per-op _resolve_version call
                            if action == "create" and raw is not None \
                                    and not raw[1]:
                                raise VersionConflictException(
                                    doc_id, raw[0], -1)
                            nv = raw[0] + 1 if raw is not None else 1
                        else:
                            nv = self._resolve_version(
                                raw, doc_id, op.version, op.version_type,
                                "create" if action == "create" else "index")
                        created = raw is None or raw[1]
                        ts = op.timestamp
                        if ts is None:
                            # resolve NOW so translog replay reproduces it
                            ts = now_ms
                        mapper = type_mappers.get(op.type_name)
                        if mapper is None:
                            mapper = type_mappers[op.type_name] = \
                                self.mappers.document_mapper(op.type_name)
                        # positional call: 7 kwarg bindings cost ~0.5µs/doc
                        parsed = mapper.parse(op.source, doc_id, op.routing,
                                              op.parent, ts, op.ttl, batcher)
                        overlay[doc_id] = (nv, False)
                        stage((gi, op, nv, parsed, created, ts))
                    except Exception as e:  # noqa: BLE001 — per-item
                        results[gi] = e
                failed = batcher.flush()
                records: list[dict] = []
                for gi, op, nv, parsed, flag, ts in staged:
                    if parsed is not None and id(parsed) in failed:
                        results[gi] = failed[id(parsed)]
                        continue
                    doc_id = op.doc_id
                    if op.action == "delete":
                        self._apply_delete(doc_id, nv)
                        records.append({"op": "delete", "id": doc_id,
                                        "version": nv})
                        results[gi] = EngineResult(
                            doc_id=doc_id, version=nv, created=False,
                            found=flag)
                        continue
                    # _apply_index minus the (already done) parse
                    self._delete_everywhere(doc_id)
                    self._buffer_docs[doc_id] = (op.source, op.type_name,
                                                 op.routing, op.parent,
                                                 parsed)
                    # REST-lane ops carry the raw JSON line length — a
                    # better estimate than the dict walk, and free
                    est = op.raw_len or _rough_doc_bytes(op.source)
                    self._buffer_sizes[doc_id] = est
                    self._buffer_bytes += est
                    self.versions[doc_id] = (nv, False)
                    self._dirty = True
                    self.mutation_gen += 1
                    if op.type_name == ".percolator":
                        self.percolator_gen += 1
                    rec = {"op": "index", "id": doc_id,
                           "type": op.type_name, "source": op.source,
                           "version": nv, "routing": op.routing, "ts": ts}
                    if op.parent is not None:
                        rec["parent"] = op.parent
                    if op.ttl is not None:
                        rec["ttl"] = op.ttl
                    records.append(rec)
                    results[gi] = EngineResult(doc_id=doc_id, version=nv,
                                               created=flag)
                if records:
                    self.translog.add_batch(records, sync=False)
                    wrote = True
                self._analysis_batched = getattr(
                    self, "_analysis_batched", 0) + batcher.batched_values
                self._analysis_fallback = getattr(
                    self, "_analysis_fallback", 0) + batcher.fallback_values
            if wrote:
                if sync is None:
                    sync = self.translog.durability == "request"
                if sync:
                    self.translog.sync()
        return results

    def _delete_everywhere(self, doc_id: str) -> None:
        """Remove from the write buffer now; segment tombstones are
        DEFERRED to the next refresh — deletes are invisible to search
        until a new searcher, exactly the NRT contract (realtime GET sees
        them immediately through the version map; ref InternalEngine
        delete + refresh visibility)."""
        popped = self._buffer_docs.pop(doc_id, None)
        if popped is not None:
            est = self._buffer_sizes.pop(doc_id, None)
            self._buffer_bytes -= est if est is not None \
                else _rough_doc_bytes(popped[0])
        for seg in self.segments:
            local = seg.id_to_local.get(doc_id)
            if local is not None and seg.live_host[local]:
                self._pending_deletes.append((seg, local))
                self._pending_set.add((seg.seg_id, local))

    # -- read ops ----------------------------------------------------------

    def get(self, doc_id: str, realtime: bool = True) -> GetResult:
        """Realtime get: buffer first (translog-analog read,
        ref index/get/ShardGetService.java:66-99), then segments."""
        with self._lock:
            v = self.versions.get(doc_id)
            if v is None or v[1]:
                return GetResult(found=False, doc_id=doc_id)
            version = v[0]
            if realtime and doc_id in self._buffer_docs:
                src, tname, routing, parent, parsed = \
                    self._buffer_docs[doc_id]
                ts = parsed.longs.get("_timestamp")
                ex = parsed.longs.get("_ttl_expiry")
                return GetResult(found=True, doc_id=doc_id, version=version,
                                 source=src, type_name=tname,
                                 routing=routing, parent=parent,
                                 timestamp=ts[0] if ts else None,
                                 ttl_expiry=ex[0] if ex else None)
            for seg in self.segments:
                local = seg.id_to_local.get(doc_id)
                if local is not None and seg.live_host[local] \
                        and (seg.seg_id, local) not in self._pending_set:
                    # a pending-delete copy is stale: returning it would
                    # pair the OLD source with the NEW version (review r5)
                    return GetResult(found=True, doc_id=doc_id, version=version,
                                     source=seg.stored[local],
                                     type_name=seg.types[local],
                                     routing=seg.routings[local]
                                     if seg.routings else None,
                                     parent=_segment_parent(seg, local),
                                     timestamp=_segment_long(
                                         seg, "_timestamp", local),
                                     ttl_expiry=_segment_long(
                                         seg, "_ttl_expiry", local))
            # non-realtime get sees only refreshed (searchable) state — an
            # unrefreshed buffer doc is a miss (ref ShardGetService contract)
            return GetResult(found=False, doc_id=doc_id)

    # -- refresh / flush / merge ------------------------------------------

    def refresh(self) -> None:
        """Freeze the write buffer into a new device segment — the NRT
        'new searcher' event (ref InternalEngine refresh, default 1s).
        Charges the segment's device bytes against the breaker; a breach
        keeps the buffer, marks the engine write-blocked, and raises
        CircuitBreakingException (HTTP 429) — never an OOM."""
        with self._lock:
            if self._pending_deletes:
                for seg, local in self._pending_deletes:
                    seg.delete_local(local)
                self._pending_deletes.clear()
                self._pending_set.clear()
                self._maybe_merge()
            self._drop_dead_segments()
            if not self._buffer_docs:
                return
            builder = SegmentBuilder(seg_id=self._next_seg_id)
            if self.vectorized:
                # columnar lane: contiguous runs of non-nested docs append
                # through add_batch (one lexsort per field at build instead
                # of per-token dict work); nested blocks keep the per-doc
                # path so block-join row order is untouched. Runs preserve
                # buffer order, so local ids match the per-doc loop.
                run: list[tuple] = []
                for doc_id, (_src, tname, _routing, _parent, parsed) \
                        in self._buffer_docs.items():
                    v = self.versions[doc_id][0]
                    if parsed.nested:
                        if run:
                            builder.add_batch(run)
                            run = []
                        builder.add(parsed, tname, version=v)
                    else:
                        run.append((parsed, tname, v))
                if run:
                    builder.add_batch(run)
            else:
                for doc_id, (_src, tname, _routing, _parent, parsed) \
                        in self._buffer_docs.items():
                    builder.add(parsed, tname,
                                version=self.versions[doc_id][0])
            site = f"segment:{self._next_seg_id}"
            if self.breaker is not None:
                # charge BEFORE build() uploads device arrays: a tripped
                # breaker prevents the allocation itself, not just the
                # accounting (advisor r4). Estimate mirrors memory_bytes().
                est = builder.estimate_bytes()
                try:
                    self.breaker.add_estimate(est)
                except Exception as e:
                    self._blocked_reason = e
                    raise
                self._ledger(site, est)
            try:
                seg = builder.build()
            except BaseException:
                # device upload failed — undo the charge or the breaker
                # ratchets up on every retried refresh
                if self.breaker is not None:
                    self.breaker.release(est)
                    self._ledger(site, -est)
                raise
            if self.breaker is not None:
                # true up any estimate drift without re-tripping
                drift = seg.memory_bytes() - est
                if drift > 0:
                    self.breaker.add_estimate(drift, check=False)
                elif drift < 0:
                    self.breaker.release(-drift)
                self._ledger(site, drift)
            self._blocked_reason = None
            self._next_seg_id += 1
            self._adopt(seg)
            self.segments.append(seg)
            self._buffer_docs.clear()
            self._buffer_sizes.clear()
            self._buffer_bytes = 0
            self.refresh_count += 1
            self._maybe_merge()

    def _drop_dead_segments(self) -> None:
        """Dead-empty segments (zero live docs — fully tombstoned, or an
        empty load) leave the segment set at refresh: searchers stop
        paying per-query empty checks for them, their device bytes go
        back to the breaker, and loaded fielddata dies with them."""
        dead = [s for s in self.segments if s.live_count == 0]
        if not dead:
            return
        self.segments = [s for s in self.segments if s.live_count > 0]
        if self.breaker is not None:
            self.breaker.release(sum(s.memory_bytes() for s in dead))
            for s in dead:
                self._ledger(f"segment:{s.seg_id}", -s.memory_bytes())
        self._drop_fielddata(dead)

    def _maybe_merge(self) -> None:
        """Size-tiered merge selection (ref index/merge/policy/
        LogMergePolicy: segments in the same log_{factor}(size) tier merge
        when the tier fills) — small merges stay small; the corpus is never
        re-merged all-to-one on every trigger."""
        factor = self.MERGE_SEGMENT_COUNT
        tiers: dict[int, list[Segment]] = {}
        for seg in self.segments:
            t = int(math.log(max(seg.live_count, 1), factor))
            tiers.setdefault(t, []).append(seg)
        for t in sorted(tiers):
            if len(tiers[t]) >= factor:
                self._merge_subset(tiers[t])
                return   # one merge per trigger keeps refresh latency flat

    def _merge_subset(self, subset: list[Segment]) -> None:
        chosen = set(id(s) for s in subset)
        merged = merge_segments(subset, self._next_seg_id)
        self._charge_merge(merged, subset)
        self._next_seg_id += 1
        out: list[Segment] = []
        placed = False
        for s in self.segments:
            if id(s) in chosen:
                if not placed and merged.n_docs:
                    self._adopt(merged)
                    out.append(merged)
                    placed = True
            else:
                out.append(s)
        self.segments = out
        self.merge_count += 1

    def force_merge(self, max_num_segments: int = 1) -> None:
        """Merge segments (ref index/merge/ TieredMergePolicy + optimize API)."""
        with self._lock:
            self.refresh()     # staged docs AND deferred deletes first
            if len(self.segments) <= max_num_segments:
                # may still want to purge deletes
                if not any(s.live_count < s.n_docs for s in self.segments):
                    return
            merged = merge_segments(self.segments, self._next_seg_id)
            self._charge_merge(merged, self.segments)
            self._next_seg_id += 1
            self._adopt(merged)
            self.segments = [merged] if merged.n_docs else []
            self.merge_count += 1

    def _adopt(self, seg: Segment) -> None:
        """Stamp a segment with this shard's accounting hooks: the breaker
        its device bytes/fielddata charge, the node fielddata cache its
        sort columns live in, and the index name cache entries carry (so
        `_cache/clear?index=` can target them)."""
        seg.breaker = self.breaker
        seg.fielddata_cache = self.fielddata_cache
        seg.ann_cache = self.ann_cache
        seg.index_name = self.index_name

    def _drop_fielddata(self, sources: list[Segment]) -> None:
        """Loaded fielddata dies with its source segments: cache-managed
        columns invalidate through the cache (its removal listener hands
        bytes back to the breaker); legacy per-segment dicts release
        directly."""
        for s in sources:
            if getattr(s, "fielddata_cache", None) is not None:
                s.fielddata_cache.drop_segment(s)
            elif self.breaker is not None:
                self.breaker.release(sum(s.fielddata_bytes().values()))
            if getattr(s, "ann_cache", None) is not None:
                s.ann_cache.drop_segment(s)

    def _charge_merge(self, merged: Segment, sources: list[Segment]) -> None:
        """Swap breaker accounting from the source segments to the merged
        one (the merged set is usually smaller: tombstones purged). An
        all-tombstoned merge result is DROPPED by the callers, so it must
        not be charged — that leaked phantom bytes for the node lifetime."""
        if self.breaker is not None:
            if merged.n_docs:
                self.breaker.add_estimate(merged.memory_bytes(), check=False)
                self._ledger(f"segment:{merged.seg_id}",
                             merged.memory_bytes())
            self.breaker.release(sum(s.memory_bytes() for s in sources))
            for s in sources:
                self._ledger(f"segment:{s.seg_id}", -s.memory_bytes())
        self._drop_fielddata(sources)

    def flush(self) -> None:
        """Commit: write NEW segment files + the checksummed commit point,
        roll + trim translog (ref InternalEngine.flush -> Lucene commit +
        translog roll). Already-persisted segments are untouched — flush cost
        is O(new docs + deletes), independent of corpus size."""
        with self._lock:
            self.refresh()
            gen = self.translog.roll()
            tombstones = {k: v[0] for k, v in self.versions.items() if v[1]}
            self.store.commit(self.segments, tombstones)
            self.translog.trim(gen)
            self.flush_count += 1

    @staticmethod
    def open_committed(shard_path: str, mappers: MapperService, **kw) -> "Engine":
        """Recover an engine: committed state + translog replay on top.
        (The plain constructor performs the same recovery; kept as the
        explicit-recovery entry point.)"""
        eng = Engine(shard_path, mappers,
                     durability=kw.get("durability", "request"))
        eng.refresh()
        return eng

    # -- stats / introspection --------------------------------------------

    def doc_count(self) -> int:
        with self._lock:
            # root docs only — nested block rows are an implementation
            # detail of the block join, not user documents
            return sum(s.root_live_count for s in self.segments) \
                + len(self._buffer_docs)

    def segment_stats(self) -> dict:
        return {"count": len(self.segments),
                "docs": sum(s.live_count for s in self.segments),
                "deleted": sum(s.n_docs - s.live_count for s in self.segments),
                "memory_in_bytes": sum(s.memory_bytes() for s in self.segments),
                "buffered_docs": len(self._buffer_docs)}

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return          # idempotent: a second close must not
            self._closed = True  # double-release the breaker charges
        if self.breaker is not None:
            self.breaker.release(sum(s.memory_bytes()
                                     for s in self.segments))
            for s in self.segments:
                self._ledger(f"segment:{s.seg_id}", -s.memory_bytes())
        self._drop_fielddata(self.segments)
        self.translog.close()
        if LEAK_CHECK:
            self._leak_check()
