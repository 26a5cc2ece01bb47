"""NodeService: the single-process coordinator over local indices.

Plays the role of the reference's Node + action layer for the local case
(/root/reference/src/main/java/org/elasticsearch/node/Node.java + action/ —
SURVEY.md §2.7): create/delete index (master ops), document CRUD + bulk
(replicated-write template collapses to the local primary), and the search
scatter-gather driver (TransportSearchTypeAction QUERY_THEN_FETCH:
§3.2 call stack — query phase on all shards, controller reduce, fetch from
winners only, aggregation tree reduce).
"""

from __future__ import annotations

import fnmatch
import functools
import logging
import os
import re
import threading
import time
from typing import Any, NamedTuple

from .common import tracing
from .common.settings import Settings
from .index.engine import (DocumentMissingException, EngineResult,
                           VersionConflictException)
from .index.index_service import IndexService
from .search import controller
from .search.aggs import parse_aggs, merge_shard_partials, render as render_aggs
from .search.query_dsl import QueryParsingException
from .search.shard_searcher import ShardSearcher
from .serving.executor import PACKED_BODY_KEYS


class IndexMissingException(Exception):
    def __init__(self, index: str):
        # the reference's message format: "[name] missing"
        # (ref IndexMissingException.java)
        super().__init__(f"[{index}] missing")
        self.index = index


class IndexAlreadyExistsException(Exception):
    def __init__(self, index: str):
        super().__init__(f"index [{index}] already exists")
        self.index = index


class InvalidIndexNameException(Exception):
    pass


class IndexClosedException(Exception):
    """Operations on a closed index are blocked (ref ClusterBlockException
    for INDEX_CLOSED_BLOCK; HTTP 403)."""

    def __init__(self, index: str):
        super().__init__(f"blocked by: [FORBIDDEN/4/index closed] [{index}]")
        self.index = index


# invalid characters, not an allowlist: unicode index names are legal
# (ref MetaDataCreateIndexService.validateIndexName)
_INDEX_BAD_CHARS = set(' "*\\<>|,/?#')


class _ValidIndex:
    @staticmethod
    def match(name: str):
        if not name or name != name.lower():
            return None
        if name.startswith(("_", "-", "+")):
            return None
        if any(c in _INDEX_BAD_CHARS for c in name):
            return None
        if name in (".", ".."):
            return None
        return True


_VALID_INDEX = _ValidIndex()

logger = logging.getLogger("elasticsearch_tpu.node")


def alias_dict(x) -> dict:
    """Normalize persisted alias forms (legacy name lists or prop dicts)
    into {name: props}; a bare "routing" fans out to both routings
    (ref AliasAction/AliasMetaData semantics)."""
    if isinstance(x, dict):
        out = {k: dict(v or {}) for k, v in x.items()}
    else:
        out = {a: {} for a in (x or [])}
    for props in out.values():
        if "routing" in props:
            props.setdefault("index_routing", props["routing"])
            props.setdefault("search_routing", props["routing"])
        for k in ("routing", "index_routing", "search_routing"):
            if k in props:
                props[k] = str(props[k])   # routing values are strings
    return out


class NodeService:
    """One node holding every shard locally (multi-node arrives with the
    cluster layer; the API surface is already the distributed one)."""

    def __init__(self, data_path: str, settings: Settings | None = None,
                 cluster_name: str = "elasticsearch-tpu"):
        self.data_path = data_path
        self.settings = settings or Settings()
        self.cluster_name = cluster_name
        # CPython GC tuning — the JVM-flags analog (the reference ships
        # curated GC defaults in bin/elasticsearch.in.sh). A node keeps
        # millions of long-lived container objects alive (segment postings,
        # caches, buffered docs); CPython's default (700, 10, 10) gc
        # thresholds re-walk all of them every few bulk requests — measured
        # ~40% of a 100k-doc ingest spent in gen2 sweeps. Raising the
        # thresholds keeps cycle collection alive but amortized.
        # node.gc.threshold0 <= 0 opts out entirely.
        import gc
        _gt0 = int(self.settings.get("node.gc.threshold0", 50_000))
        if _gt0 > 0:
            gc.set_threshold(
                _gt0, int(self.settings.get("node.gc.threshold1", 25)),
                int(self.settings.get("node.gc.threshold2", 25)))
        from .common.breaker import CircuitBreakerService
        self.breakers = CircuitBreakerService(self.settings)
        # device ownership (ISSUE 19): `node.devices` carves this node's
        # disjoint device subset out of jax.devices() (DevicePool with a
        # private dispatch lock → EXEC_LOCK off the per-node hot path);
        # `cluster.mesh.coordinator` arms jax.distributed multi-host
        # init. Both default off → the legacy shared pool.
        from .parallel.mesh import maybe_init_distributed, resolve_device_pool
        maybe_init_distributed(self.settings)
        self.device_pool = resolve_device_pool(self.settings)
        # node-level cache subsystem (indices/cache_service.py): request
        # responses, parsed query plans, fielddata columns — byte-accounted
        # LRU tiers behind one core (ref IndicesRequestCache +
        # LRUQueryCache + IndicesFieldDataCache)
        from .indices import IndicesCacheService
        self.caches = IndicesCacheService(self.settings, self.breakers)
        self.indices: dict[str, IndexService] = {}
        self.closed: dict[str, dict] = {}     # closed index -> metadata
        self.templates: dict[str, dict] = {}
        # scroll contexts: id -> (index expr, body, cursor, expiry)
        # (ref SearchService keep-alive reaper, SearchService.java:132,166);
        # locked: the REST server is threaded
        import threading
        self._scrolls: dict[str, dict] = {}
        self._scroll_seq = 0
        self._scroll_lock = threading.Lock()
        os.makedirs(data_path, exist_ok=True)
        from .snapshots import SnapshotsService
        self.snapshots = SnapshotsService(self)
        from .common.metrics import (IndexingSlowLog, Meter, MetricsRegistry,
                                     PhaseTimers, SlowLog)
        self.phase_timers = PhaseTimers()
        self.metrics = MetricsRegistry()
        self.slowlog = SlowLog()
        self.indexing_slowlog = IndexingSlowLog()
        # node-wide windowed op rates (1m/5m/15m EWMA) — `_nodes/stats`
        # `rates` section + the /_metrics scrape; per-index meters live on
        # each IndexService
        self.meters: dict[str, Meter] = {"search": Meter(),
                                         "indexing": Meter(),
                                         "get": Meter()}
        # task registry: every coordinator + shard-level action in flight
        # (ref tasks/TaskManager; GET /_tasks)
        from .common.tasks import TaskManager
        self.tasks = TaskManager("tpu-node-0")
        # span tracer (common/tracing.py): per-request span trees rooted
        # at the task trace id, retained in a bounded ring under
        # node.tracing.* settings — GET /_traces
        from .common.tracing import Tracer
        self.tracer = Tracer(self.settings)
        # named bounded executors (ref ThreadPool.java:116); the HTTP layer
        # routes each request class through its pool, overflow -> 429
        from .common.threadpool import ThreadPool
        self.thread_pool = ThreadPool(self.settings)
        # serving-QoS admission control (serving/qos.py, ISSUE 9): per-
        # traffic-class load shedding in front of the pools, driven by
        # queue depth + breaker pressure + an EWMA of request latency —
        # the same signal the batcher's deadline-aware window and the
        # hedged-read coordinator key off
        from .serving.qos import QosController
        self.qos = QosController(self.settings,
                                 thread_pool=self.thread_pool,
                                 breakers=self.breakers)
        # NodeEnvironment dir lock (ref env/NodeEnvironment.java:118 —
        # an flock on the node dir so two nodes can't share data paths)
        self._node_lock = open(os.path.join(data_path, "node.lock"), "w")
        try:
            import fcntl
            fcntl.flock(self._node_lock, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            self._node_lock.close()
            raise RuntimeError(
                f"failed to obtain node lock on [{data_path}]: is another "
                f"node using the same data path?") from None
        # lifecycle state machine (ref common/component/Lifecycle.java)
        from .common.lifecycle import Lifecycle
        self.lifecycle = Lifecycle()
        # plugins (ref plugins/PluginsService.java:91)
        from .common.plugins import PluginsService
        self.plugins = PluginsService(os.path.join(data_path, "plugins"))
        # file-script hot reload via the resource watcher (ref watcher/
        # ResourceWatcherService + config/scripts file scripts); the
        # scripts-dir watcher attaches after search_templates exists below
        from .common.watcher import ResourceWatcherService
        self.watcher = ResourceWatcherService()
        from .serving.batcher import SearchBatcher
        self._batcher = SearchBatcher(self.qos, self.metrics)
        tpl_path = os.path.join(data_path, "_templates.json")
        if os.path.exists(tpl_path):
            import json
            with open(tpl_path) as f:
                self.templates.update(json.load(f))
        # stored SEARCH templates (mustache-lite bodies, search/templates.py)
        self.search_templates: dict[str, Any] = {}
        st_path = os.path.join(data_path, "_search_templates.json")
        if os.path.exists(st_path):
            import json
            with open(st_path) as f:
                self.search_templates.update(json.load(f))
        self._recover_indices()
        for svc in self.indices.values():
            svc.mappers.search_templates = self.search_templates
        from .common.watcher import FileWatcher
        scripts_dir = os.path.join(data_path, "scripts")
        os.makedirs(scripts_dir, exist_ok=True)
        self.watcher.add(FileWatcher(scripts_dir, _ScriptDirListener(self)))
        self.watcher.start()     # interval thread: hot reload after boot
        self.plugins.on_node_start(self)
        import threading as _th
        self._maint_stop = _th.Event()
        _th.Thread(target=self._maintenance_loop, daemon=True,
                   name="es[index_maintenance]").start()
        # stats-history sampler (common/monitor.StatsSampler): a bounded
        # ring of node-gauge snapshots on a cadence (ref monitor/ services;
        # `node.sampler.interval` seconds, <=0 disables the thread — tests
        # drive sample() manually either way)
        from .common.monitor import StatsSampler
        try:
            interval = float(self.settings.get("node.sampler.interval", 10))
        except (TypeError, ValueError):
            interval = 10.0
        self.sampler = StatsSampler(self._sampler_snapshot,
                                    interval_s=interval)
        self.sampler.start()
        # self-monitoring collector (ISSUE 17 tentpole (c)): opt-in
        # sampler->`.monitoring-es-*` pipeline through the bulk lane,
        # served back by GET /_monitoring/overview via the sorted +
        # sub-agg device lanes (common/monitoring.py)
        from .common.monitoring import MonitoringCollector
        self.monitoring = MonitoringCollector.from_settings(self)
        if self.monitoring is not None:
            self.monitoring.start()
        # watcher alerting tier (ISSUE 20): registry recovered from the
        # `.watches` index; document watches ride the monitoring
        # collector's percolate batch, aggregation watches the scheduler
        # (watcher/service.py). `self.watcher` is the file-resource
        # watcher above — hence `watcher_service`.
        from .watcher.service import WatcherService
        self.watcher_service = WatcherService.from_settings(self)
        self.lifecycle.move_to_started()

    # -- index management (master ops, ref MetaDataCreateIndexService) ----

    def _recover_indices(self) -> None:
        """Reopen on-disk indices (gateway recovery, SURVEY.md §5.4(b));
        closed indices register metadata-only (no engines)."""
        import json
        for name in sorted(os.listdir(self.data_path)):
            meta_path = os.path.join(self.data_path, name, "_meta.json")
            if not os.path.exists(meta_path):
                continue
            with open(meta_path) as f:
                meta = json.load(f)
            if meta.get("state") == "close":
                self.closed[name] = meta
                continue
            self.indices[name] = IndexService(
                name, os.path.join(self.data_path, name),
                Settings(meta.get("settings", {})), meta.get("mappings", {}),
                breakers=self.breakers, caches=self.caches)
            self.indices[name].aliases = alias_dict(meta.get("aliases", []))

    def _persist_index_meta(self, svc: IndexService) -> None:
        import json
        meta = {"settings": dict(svc.settings),
                "mappings": svc.mappings_dict(),
                "aliases": dict(sorted(svc.aliases.items()))}
        path = os.path.join(svc.path, "_meta.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, path)

    def create_index(self, name: str, settings: dict | None = None,
                     mappings: dict | None = None,
                     aliases: dict | None = None) -> IndexService:
        if name in self.indices or name in self.closed:
            raise IndexAlreadyExistsException(name)
        if not _VALID_INDEX.match(name) or name != name.lower():
            raise InvalidIndexNameException(f"invalid index name [{name}]")
        merged_settings = dict(settings or {})
        merged_mappings = dict(mappings or {})
        merged_aliases = alias_dict(aliases or {})
        # index templates (ref MetaDataIndexTemplateService): apply by pattern
        for tname, tpl in sorted(self.templates.items(),
                                 key=lambda kv: kv[1].get("order", 0)):
            if fnmatch.fnmatch(name, tpl.get("template", "*")):
                for k, v in (tpl.get("settings") or {}).items():
                    merged_settings.setdefault(k, v)
                for t, m in (tpl.get("mappings") or {}).items():
                    merged_mappings.setdefault(t, m)
                for a, props in alias_dict(tpl.get("aliases")
                                           or {}).items():
                    merged_aliases.setdefault(a, props)
        svc = IndexService(name, os.path.join(self.data_path, name),
                           Settings(merged_settings), merged_mappings,
                           breakers=self.breakers, caches=self.caches)
        errs = getattr(svc.mappers.analysis, "build_errors", None)
        if errs:
            # strict at CREATE time (the user can fix the request); node
            # RECOVERY of existing indices stays lenient (code review r5)
            svc.close()
            import shutil
            shutil.rmtree(svc.path, ignore_errors=True)
            raise ValueError("analysis configuration: " + "; ".join(errs))
        svc.aliases = merged_aliases
        svc.mappers.search_templates = self.search_templates
        self.indices[name] = svc
        self._persist_index_meta(svc)
        return svc

    def delete_index(self, name: str) -> None:
        import shutil
        deleted_closed = False
        for n in list(self.closed):
            if n == name or fnmatch.fnmatch(n, name) \
                    or name in ("_all", "*", ""):
                self.closed.pop(n)
                shutil.rmtree(os.path.join(self.data_path, n),
                              ignore_errors=True)
                deleted_closed = True
        if deleted_closed and name not in self.indices \
                and "*" not in name and name not in ("_all", ""):
            return     # the exact name was a closed index: done
        for n in self._resolve(name):
            svc = self.indices.pop(n)
            svc.close()
            svc.delete_files()

    def close_index(self, expr: str) -> list[str]:
        """Close indices: engines shut down, device memory released, data
        retained; reads/writes are blocked until reopened
        (ref MetaDataIndexStateService.closeIndex)."""
        names = self._resolve(expr)
        for n in names:
            svc = self.indices.pop(n)
            meta = {"settings": dict(svc.settings),
                    "mappings": svc.mappings_dict(),
                    "aliases": dict(sorted(svc.aliases.items())),
                    "state": "close"}
            svc.flush()
            svc.close()
            self.closed[n] = meta
            self._persist_meta_dict(n, meta)
        return names

    def open_index(self, expr: str) -> list[str]:
        """Reopen closed indices (ref MetaDataIndexStateService.openIndex)."""
        names = [n for n in self.closed
                 if n == expr or fnmatch.fnmatch(n, expr)
                 or expr in ("_all", "*", "")]
        if not names and "*" not in expr and expr not in self.indices:
            raise IndexMissingException(expr)
        for n in names:
            meta = self.closed.pop(n)
            meta = {**meta, "state": "open"}
            svc = IndexService(n, os.path.join(self.data_path, n),
                               Settings(meta.get("settings", {})),
                               meta.get("mappings", {}),
                               breakers=self.breakers, caches=self.caches)
            svc.aliases = alias_dict(meta.get("aliases", []))
            svc.mappers.search_templates = self.search_templates
            self.indices[n] = svc
            self._persist_meta_dict(n, meta)
        return names

    def _persist_meta_dict(self, name: str, meta: dict) -> None:
        import json
        path = os.path.join(self.data_path, name, "_meta.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, path)

    def _resolve(self, expr: str) -> list[str]:
        """Index expression: name, alias, comma list, wildcards, _all.
        Wildcards expand to OPEN indices only (expand_wildcards=open, the
        reference default); naming a closed index directly is a 403."""
        if expr in ("_all", "*", ""):
            return list(self.indices)
        out: list[str] = []
        for part in expr.split(","):
            if part in self.indices:
                out.append(part)
                continue
            if part in self.closed:
                raise IndexClosedException(part)
            matched = [n for n, svc in self.indices.items()
                       if part in svc.aliases or fnmatch.fnmatch(n, part)]
            if not matched and "*" not in part:
                raise IndexMissingException(part)
            out.extend(m for m in matched if m not in out)
        return out

    def index_service(self, name: str) -> IndexService:
        svcs = self._resolve(name)
        if not svcs:
            raise IndexMissingException(name)
        return self.indices[svcs[0]]

    # -- document ops ------------------------------------------------------

    def index_doc(self, index: str, doc_id: str | None, source: dict,
                  type_name: str = "_doc", auto_create: bool = True,
                  **kw) -> tuple[str, EngineResult]:
        """ref TransportIndexAction.java:63 — auto-creates the index like
        the reference's create-index-on-first-doc behavior."""
        if index not in self.indices:
            if index in self.closed:
                raise IndexClosedException(index)
            if not auto_create:
                raise IndexMissingException(index)
            if not _VALID_INDEX.match(index):
                raise InvalidIndexNameException(index)
            self.create_index(index)
        if doc_id is None:
            import uuid
            doc_id = uuid.uuid4().hex[:20]
        svc = self.indices[index]
        t0 = time.perf_counter()
        res = svc.index_doc(doc_id, source, type_name=type_name, **kw)
        self.meters["indexing"].mark()
        self.indexing_slowlog.maybe_log(
            svc.settings, index, (time.perf_counter() - t0) * 1000, doc_id)
        return index, res

    def get_doc(self, index: str, doc_id: str, **kw):
        self.meters["get"].mark()
        return self.index_service(index).get_doc(doc_id, **kw)

    def delete_doc(self, index: str, doc_id: str, **kw):
        self.meters["indexing"].mark()
        return self.index_service(index).delete_doc(doc_id, **kw)

    def update_doc(self, index: str, doc_id: str, body: dict,
                   type_name: str = "_doc",
                   version: int | None = None,
                   routing: str | None = None,
                   parent: str | None = None,
                   timestamp=None, ttl=None,
                   sync: bool | None = None) -> tuple[EngineResult, bool]:
        """Scripted/partial update: get -> transform -> reindex
        (ref action/update/UpdateHelper.java:61). Returns (result, noop).
        Auto-creates the index like the reference's update-with-upsert.
        routing/parent route the get AND carry into the re-index so child
        documents keep their _parent (code review r5)."""
        if index not in self.indices:
            if not _VALID_INDEX.match(index):
                raise InvalidIndexNameException(index)
            self.create_index(index)
        svc = self.index_service(index)
        if routing is None and parent is None \
                and svc.mappers.parent_type_of(type_name):
            from .mapping.mapper import RoutingMissingException
            raise RoutingMissingException(
                f"routing is required for [{index}]/[{type_name}]/"
                f"[{doc_id}]")
        cur = svc.get_doc(doc_id, routing=routing, parent=parent)
        if not cur.found:
            if version is not None:
                # update-with-version on a missing doc is a CONFLICT
                # (ref UpdateRequest validation / VersionConflictEngine-
                # Exception on upsert-with-version)
                raise VersionConflictException(doc_id, -1, version)
            if "upsert" in body:
                upsert = dict(body["upsert"])
                # inline metadata in the upsert doc (legacy ES form the
                # YAML suites use: {"foo": "bar", "_parent": 5})
                meta_parent = upsert.pop("_parent", None)
                meta_routing = upsert.pop("_routing", None)
                res = svc.index_doc(
                    doc_id, upsert, type_name=type_name,
                    routing=routing if routing is not None
                    else (str(meta_routing)
                          if meta_routing is not None else None),
                    parent=parent if parent is not None
                    else (str(meta_parent)
                          if meta_parent is not None else None),
                    timestamp=timestamp, ttl=ttl, sync=sync)
                return res, False
            if body.get("doc_as_upsert") and "doc" in body:
                res = svc.index_doc(doc_id, body["doc"], type_name=type_name,
                                    routing=routing, parent=parent,
                                    timestamp=timestamp, ttl=ttl, sync=sync)
                return res, False
            raise DocumentMissingException(f"[{type_name}][{doc_id}]: document missing")
        if version is not None and cur.version != version:
            raise VersionConflictException(doc_id, cur.version, version)
        src = dict(cur.source)
        if "script" in body:
            from .script.engine import run_update_script
            src, op = run_update_script(body["script"], src,
                                        params=body.get("params")
                                        or (body["script"].get("params")
                                            if isinstance(body["script"], dict)
                                            else None))
            # honor ctx.op like the reference's UpdateHelper: delete deletes,
            # anything other than index (none/create) is a noop
            # (ref UpdateHelper.java:246-249 else-branch -> Operation.NONE)
            if op == "delete":
                res = svc.delete_doc(doc_id, sync=sync)
                return res, False
            if op != "index":
                return EngineResult(doc_id=doc_id, version=cur.version,
                                    created=False), True
        elif "doc" in body:
            merged = _deep_merge(src, body["doc"])
            # metadata-only updates (new ttl/timestamp) are NOT noops
            if body.get("detect_noop", True) and merged == src \
                    and ttl is None and timestamp is None:
                return EngineResult(doc_id=doc_id, version=cur.version,
                                    created=False), True
            src = merged
        if parent is None and svc.mappers.parent_type_of(cur.type_name):
            # child docs route by parent id, so the stored routing IS the
            # parent (ref UpdateHelper preserves _parent across the reindex)
            parent = routing if routing is not None else cur.routing
        res = svc.index_doc(doc_id, src, type_name=cur.type_name,
                            version=cur.version,
                            routing=routing if routing is not None
                            else cur.routing,
                            parent=parent,
                            timestamp=timestamp, ttl=ttl, sync=sync)
        return res, False

    def bulk(self, operations: list[tuple[str, dict, dict | None]]) -> list[dict]:
        """ops: (action, meta, source). ref TransportBulkAction splits by
        shard; TransportShardBulkAction applies a shard's slice as ONE pass.

        Contiguous runs of index/create/delete ops ride the VECTORIZED
        batch lane (index/bulk_ingest.py): per index, one
        IndexService.bulk_ingest call — batched analysis, columnar segment
        append, group-commit translog. Updates, unknown actions, disabled
        indices (`index.bulk.vectorized.enable: false`) and any setup
        failure fall back to the per-doc path with identical per-item
        semantics. ALL actions (updates included) share the deferred-sync
        contract: translog fsyncs collapse to ONE sync per touched index
        at the end — the reference's per-request durability."""
        from .common.breaker import CircuitBreakingException
        from .common.metrics import record_bulk_ingest
        from .index.bulk_ingest import BulkOp
        from .index.engine import EngineResult

        items: list = [None] * len(operations)
        touched: set[str] = set()
        fallback_ops = 0

        def error_item(pos, action, index, doc_id, e) -> None:
            if isinstance(e, VersionConflictException):
                st = 409
            elif isinstance(e, CircuitBreakingException):
                st = 429
            else:
                st = 400
            items[pos] = {action: {"_index": index, "_id": doc_id,
                                   "status": st, "error": str(e)}}

        def per_op(pos, action, meta, source) -> None:
            nonlocal fallback_ops
            fallback_ops += 1
            index = meta.get("_index")
            type_name = meta.get("_type", "_doc")
            doc_id = meta.get("_id")
            try:
                if action in ("index", "create"):
                    _, res = self.index_doc(
                        index, doc_id, source, type_name=type_name,
                        op_type="create" if action == "create" else "index",
                        routing=meta.get("_routing") or meta.get("routing"),
                        parent=meta.get("_parent") or meta.get("parent"),
                        sync=False)
                    touched.add(index)
                    items[pos] = {action: {
                        "_index": index, "_type": type_name, "_id": res.doc_id,
                        "_version": res.version,
                        "status": 201 if res.created else 200}}
                elif action == "delete":
                    res = self.delete_doc(index, doc_id, sync=False)
                    touched.add(index)
                    items[pos] = {"delete": {
                        "_index": index, "_type": type_name, "_id": doc_id,
                        "_version": res.version, "found": res.found,
                        "status": 200 if res.found else 404}}
                elif action == "update":
                    # updates join the deferred-sync + group-commit
                    # contract like index/delete (they used to fsync per
                    # op AND miss the end-of-request sync entirely)
                    res, noop = self.update_doc(index, doc_id, source,
                                                type_name=type_name,
                                                sync=False)
                    touched.add(index)
                    items[pos] = {"update": {
                        "_index": index, "_type": type_name, "_id": doc_id,
                        "_version": res.version, "status": 200}}
                else:
                    items[pos] = {action: {
                        "status": 400,
                        "error": f"unknown action [{action}]"}}
            except Exception as e:  # noqa: BLE001 — per-item error contract
                error_item(pos, action, index, doc_id, e)

        run: list[tuple[int, str, dict, dict | None, int]] = []

        def flush_run() -> None:
            nonlocal fallback_ops
            if not run:
                return
            groups: dict = {}
            for entry in run:
                groups.setdefault(entry[2].get("_index"), []).append(entry)
            for index, entries in groups.items():
                svc = None
                try:
                    if index not in self.indices:
                        if index in self.closed:
                            raise IndexClosedException(index)
                        if not _VALID_INDEX.match(index):
                            raise InvalidIndexNameException(index)
                        self.create_index(index)
                    svc = self.indices[index]
                except Exception:  # noqa: BLE001 — per-op path reports it
                    svc = None
                if svc is None or not svc._bulk_vectorized:
                    for pos, action, meta, source, _rl in entries:
                        per_op(pos, action, meta, source)
                    continue
                batch = []
                batch_append = batch.append
                for pos, action, meta, source, raw_len in entries:
                    m_get = meta.get
                    doc_id = m_get("_id")
                    if doc_id is None:
                        if action == "delete":   # delete without id: let
                            per_op(pos, action, meta, source)  # it 400
                            continue
                        import uuid
                        doc_id = uuid.uuid4().hex[:20]
                    elif doc_id.__class__ is not str:
                        doc_id = str(doc_id)
                    routing = m_get("_routing")
                    if routing is None:
                        routing = m_get("routing")
                    parent = m_get("_parent")
                    if parent is None:
                        parent = m_get("parent")
                    # positional BulkOp: kwarg binding costs real time at
                    # 100k ops/request
                    batch_append((pos, action, meta, BulkOp(
                        action, doc_id, source,
                        m_get("_type") or "_doc",
                        routing, parent, raw_len=raw_len)))
                if not batch:
                    continue
                ops = [b[3] for b in batch]
                try:
                    results = svc.bulk_ingest(ops)
                except Exception as e:  # noqa: BLE001 — must not 500 the
                    # request: unapplied ops report the failure per item
                    results = [e] * len(ops)
                touched.add(index)
                self.meters["indexing"].mark(len(ops))
                for (pos, action, meta, op), res in zip(batch, results):
                    if not isinstance(res, EngineResult):
                        error_item(pos, action, index, op.doc_id, res)
                    elif action == "delete":
                        items[pos] = {"delete": {
                            "_index": index, "_type": op.type_name,
                            "_id": meta.get("_id"), "_version": res.version,
                            "found": res.found,
                            "status": 200 if res.found else 404}}
                    else:
                        items[pos] = {action: {
                            "_index": index, "_type": op.type_name,
                            "_id": res.doc_id, "_version": res.version,
                            "status": 201 if res.created else 200}}
            run.clear()

        for pos, op_t in enumerate(operations):
            # ops are (action, meta, source) or (action, meta, source,
            # raw_len) — _parse_bulk adds the raw source line's byte
            # length so the engine's buffer estimate skips a dict walk
            action = op_t[0]
            if action in ("index", "create", "delete"):
                run.append((pos, action, op_t[1], op_t[2],
                            op_t[3] if len(op_t) > 3 else 0))
            else:
                flush_run()          # order matters: an update may read a
                per_op(pos, action, op_t[1], op_t[2])  # doc this bulk indexed
        flush_run()
        for name in touched:
            svc = self.indices.get(name)
            if svc is not None:
                svc.sync_translogs()
        # shared indexing-buffer budget across shards (the reference's
        # IndexingMemoryController runs on a schedule; per-bulk keeps the
        # invariant without a thread)
        self.check_indexing_memory()
        if operations:
            record_bulk_ingest(len(operations),
                               vectorized=fallback_ops == 0)
        return items

    # -- search (the QUERY_THEN_FETCH driver, SURVEY §3.2) -----------------

    def _trace_ids(self) -> tuple[str | None, str | None]:
        """(trace_id, opaque_id) of the current request, from the active
        task (REST path) or profiler (direct calls) — stamps slowlog
        entries so one id correlates slowlog + tasks + profile."""
        from .common.metrics import current_profiler
        from .common.tasks import current_task
        t = current_task()
        if t is not None:
            return t.trace_id, t.opaque_id
        p = current_profiler()
        if p is not None:
            return p.trace_id, None
        return None, None

    def _record_phase(self, phase: str, ms: float,
                      compiles0: int | None = None) -> None:
        self.phase_timers.record(phase, ms)
        self.metrics.record(f"search.{phase}", ms)
        if phase == "total":
            # feed the QoS latency EWMA: every served search, every lane —
            # except a request during which XLA compiled (`compiles0` is
            # the process-wide compile count at its start): a cold compile
            # is set-up time, not the serving latency admission sheds on
            from .common.metrics import device_events_snapshot
            if device_events_snapshot()[0] == compiles0:
                self.qos.record_latency(ms)

    def _parse_cached(self, name: str, query):
        """Parse a query through the node-level query-plan cache
        (indices/cache_service): repeated query templates skip host-side
        re-parse, and a stable tree keeps the jit compile-cache keys
        stable too. Parsed trees are execution-stateless (every
        per-segment computation flows through SegmentContext), so sharing
        one tree across requests is safe; bodies the cache refuses (date
        math, templates, ...) parse fresh."""
        svc = self.indices[name]
        from .search.query_parser import QueryParser
        key = self.caches.plan_key(name, svc._incarnation,
                                   svc.mappers.mapping_version(), query)
        node = self.caches.get_plan(key)
        if node is None:
            node = QueryParser(svc.mappers).parse(query)
            self.caches.put_plan(key, node)
        return node

    def search(self, index: str, body: dict | None = None,
               size: int | None = None, from_: int | None = None,
               scroll: str | None = None, scan: bool = False,
               request_cache: bool | None = None) -> dict:
        """Entry point: installs a RequestProfiler when the body carries
        `"profile": true` (ref search/profile — the per-request timing
        tree), then runs the QUERY_THEN_FETCH driver."""
        body = body or {}
        if not body.get("profile") or scroll is not None:
            return self._search_exec(index, body, size=size, from_=from_,
                                     scroll=scroll, scan=scan,
                                     request_cache=request_cache)
        from .common.metrics import (RequestProfiler, current_profiler,
                                     use_profiler)
        from .common.tasks import current_task
        task = current_task()
        if current_profiler() is not None:   # nested (warmer/percolate)
            return self._search_exec(index, body, size=size, from_=from_,
                                     request_cache=request_cache)
        prof = RequestProfiler(
            trace_id=task.trace_id if task is not None else None)
        from .common.device_stats import record_lanes
        with use_profiler(prof), record_lanes() as lanes:
            resp = self._search_exec(index, body, size=size, from_=from_,
                                     request_cache=False)
        resp["profile"] = prof.render(
            opaque_id=task.opaque_id if task is not None else None)
        # the lane-decision flight record: which execution lane served each
        # component and every (lane, reason) decline on the ladder walk
        resp["profile"]["lanes"] = lanes.explain()
        return resp

    def _search_exec(self, index: str, body: dict | None = None,
                     size: int | None = None, from_: int | None = None,
                     scroll: str | None = None, scan: bool = False,
                     request_cache: bool | None = None) -> dict:
        from .common.metrics import device_events_snapshot
        compiles0 = device_events_snapshot()[0]
        # the request's clock starts with its plan: `took`, the `total`
        # phase and the general lane's `parse` all count from this read
        planning = tracing.span("search.plan", cpu=True)
        with planning:
            plan = self._search_plan(index, body or {}, size, from_,
                                     request_cache, scroll is not None)
        tns0 = planning.start_ns
        body, size, from_ = plan.body, plan.size, plan.from_
        if scroll is not None:
            return self._scroll_start(index, body, size, scroll, scan=scan)
        if plan.cached is not None:
            return plan.cached
        names, sort, alias_flt = plan.names, plan.sort, plan.alias_flt
        cache_key = plan.cache_key

        # the packed fast path: one device program over every shard/segment
        # of the index (serving/packed_view) — the production serving lane.
        # Concurrent solo requests COALESCE through the batcher: under load
        # the device serves whole queues of independent requests as one
        # program (serving/batcher.py), which is where TPU QPS comes from.
        from .common.device_stats import lane_chosen, lane_decline
        if len(names) == 1:
            if plan.spec is None:
                lane_decline("serve", "packed", "plan_shape")
            else:
                spec = plan.spec
                key = ("packed", names[0], size, from_, *spec[1:4])
                stay = tracing.span("packed_batch", index=names[0])
                with stay:
                    # queue wait + the shared device program of the
                    # coalesced batch (serving/batcher.py): the span
                    # covers this request's whole stay in the lane. A
                    # program that raises is this request's error — a
                    # device failure is never served by a slower lane.
                    out, _ = self._batcher.coalesce(
                        key, (body, spec, tns0),
                        lambda items, _t: self._packed_search(
                            names[0], [b for b, _, _ in items], size=size,
                            from_=from_, t0=[t for _, _, t in items],
                            specs=[sp for _, sp, _ in items]))
                if out is None:
                    lane_decline("serve", "packed", "batcher_declined")
                else:
                    lane_chosen("serve", "packed")
                    # batcher lane: only TOTAL is honest here — the
                    # request's wall time includes queue wait and
                    # shared-batch work, not this request's device time.
                    # From the plan span's start to the stay's end: the
                    # spans' own clock reads, no second pair
                    self._served_total(names[0], body,
                                       (stay.end_ns - tns0) / 1e6, compiles0)
                    return out

        # a dashboard panel (`plan.panel`, search/aggs/panels.py): `size: 0`,
        # a range and one of the lane's three shapes. Alone, behind a
        # leader or after a follower's wait ran out it runs the lane's
        # closed set of programs; None only where the index's segments are
        # not the lane's, and the body then keeps the path below.
        if plan.panel is not None:
            out = self._serve_panel(names[0], body, plan.panel, tns0,
                                    compiles0)
            if out is not None:
                if cache_key is not None:
                    self.caches.request_cache.put(cache_key, names, out)
                return out

        # coalesced general lane (serving/batcher.py): bodies the packed
        # kernel can't serve but the batched executor can (plan-shaped
        # queries, aggs, knn, rescore). The leader, and a follower left
        # without an answer, run the ordinary solo path; followers are
        # served as ONE Q>1 batched program, bitwise-identical to solo
        # execution (tests/test_qos.py parity matrix). Cacheable bodies
        # skip the lane so the request cache keeps filling. A body WITH a
        # packed spec never comes here: when its stay in the packed
        # batcher ended on None (time-out, strand, view refusal) it is
        # served solo below, so a stalled packed window cannot reach this
        # lane's programs.
        if (len(names) == 1 and plan.spec is None and cache_key is None
                and not body.get("profile") and self.qos.enabled()):
            from .common.metrics import current_profiler as _cur_prof
            bkey = self._msearch_batch_key(names[0], body) \
                if _cur_prof() is None else None
            if bkey is not None:
                out, shared = self._batcher.coalesce(
                    ("gen", *bkey), body,
                    lambda items, _t: self._search_batched(
                        [(names[0], b) for b in items]),
                    lead=lambda: self._search_general(
                        index, names, body, size, from_, sort, alias_flt,
                        cache_key, tns0, compiles0))
                if shared:
                    self._served_shared(names[0], body, tns0, compiles0)
                return out
        return self._search_general(index, names, body, size, from_, sort,
                                    alias_flt, cache_key, tns0, compiles0)

    def _served_shared(self, name: str, body: dict, tns0: int,
                       compiles0: int, lane: str = "batched") -> None:
        """A follower was served from a shared batch: only TOTAL is honest
        (its wall time includes queue wait and shared work)."""
        from .common.device_stats import lane_chosen
        lane_chosen("serve", lane)
        self._served_total(name, body, (tracing.now_ns() - tns0) / 1e6,
                           compiles0)

    def _served_total(self, name: str, body: dict, took_ms: float,
                      compiles0: int) -> None:
        """A fast lane served the request in `took_ms`: the `total` phase
        and the index's slowlog."""
        self._record_phase("total", took_ms, compiles0)
        tid, oid = self._trace_ids()
        if self.slowlog.maybe_log(self.indices[name].settings, name,
                                  took_ms, body, trace_id=tid,
                                  opaque_id=oid) is not None:
            tracing.mark_slowlog()

    def _search_plan(self, index: str, body: dict, size: int | None,
                     from_: int | None, request_cache: bool | None,
                     scrolling: bool) -> "_SearchPlan":
        """Everything `_search_exec` decides before a lane runs: the
        rendered body and page, the indices, the request cache's answer or
        key, alias filters, the sort, the packed lane's spec and, for a
        body without one, its row of the panel lane."""
        if "template" in body and "query" not in body:
            # body-level search template (ref RestSearchTemplateAction when
            # the template arrives inside a plain _search body)
            from .search.templates import render_template
            rendered = render_template(body["template"],
                                       self.search_templates)
            if isinstance(rendered, str):
                import json as _json
                rendered = _json.loads(rendered)
            body = {**{k: v for k, v in body.items() if k != "template"},
                    **rendered}
        size = int(body.get("size", 10) if size is None else size)
        from_ = int(body.get("from", 0) if from_ is None else from_)
        if scrolling:       # the scroll driver plans its own searches
            return _SearchPlan(body, size, from_)
        names = self._resolve(index)
        if not names:
            raise IndexMissingException(index)
        for n in names:   # stats-group tallies (body "stats": [tags])
            for tag in body.get("stats") or []:
                svc = self.indices[n]
                svc.search_groups[tag] = svc.search_groups.get(tag, 0) + 1

        # shard request cache (indices/cache_service.IndicesRequestCache):
        # size-0 bodies are cacheable by default, keyed on body + reader
        # generation; any refresh/delete/merge rotates the generation =
        # auto-invalidation. `index.requests.cache.enable: false` opts an
        # index out; an explicit `?request_cache=true` overrides it per
        # request (the reference's per-request override contract).
        cacheable = (request_cache is not False and size == 0
                     and from_ == 0
                     and (request_cache or "script_fields" not in body))
        if cacheable and request_cache is None:
            cacheable = all(_req_cache_enabled(self.indices[n].settings)
                            for n in names)
        cache_key = None
        if cacheable:
            import json as _json
            try:
                body_json = _json.dumps(body, sort_keys=True, default=str)
                # wall-clock-relative date math must never cache (the
                # reference refuses now-based requests the same way)
                if "now" in body_json:
                    cache_key = None
                else:
                    gens = tuple(
                        (n, self.indices[n]._incarnation,
                         self.indices[n].reader_generation())
                        for n in names)
                    # the raw index EXPRESSION is part of the key: a
                    # filtered alias and its index must not share entries
                    cache_key = (str(index), body_json, gens)
            except TypeError:
                cache_key = None
            if cache_key is not None:
                hit = self.caches.request_cache.get(cache_key)
                if hit is not None:
                    for n in names:
                        self.indices[n].request_cache_hits += 1
                    return _SearchPlan(body, size, from_, cached=hit)
                for n in names:
                    self.indices[n].request_cache_misses += 1

        alias_flt = self._alias_filters_by_index(index, names)
        if len(names) == 1 and alias_flt:
            # single index: wrapping the body keeps the packed lane eligible
            body = {**body, "query": self._wrap_alias_query(
                body.get("query"), alias_flt[names[0]])}
            alias_flt = {}
        from .search.sort import parse_sort
        sort = parse_sort(body.get("sort"),
                          [self.indices[n].mappers for n in names])
        spec = panel = None
        if len(names) == 1:
            from .search.query_parser import QueryParser
            from .serving.executor import packed_spec_of
            spec = packed_spec_of(
                QueryParser(self.indices[names[0]].mappers), body)
            if spec is None and size == 0 and from_ == 0:
                panel = self._panel_row(names[0], body)
        return _SearchPlan(body, size, from_, names=names, sort=sort,
                           alias_flt=alias_flt, cache_key=cache_key,
                           spec=spec, panel=panel)

    def _search_general(self, index, names, body, size, from_, sort,
                        alias_flt, cache_key, tns0, compiles0):
        """The general QUERY_THEN_FETCH driver (mesh -> concurrent fan-out
        -> per-segment ladder) — everything below the fast serving lanes.
        Split from _search_exec so a coalescing LEADER can execute it for
        itself and drain its followers in a finally."""
        # SearchStats query_total for the general path (the packed/batcher
        # lanes and _search_batched count their own serves)
        self.meters["search"].mark()
        for n in names:
            self.indices[n].query_total += 1
            self.indices[n].meters["search"].mark()

        searchers: list[ShardSearcher] = []
        index_of: list[str] = []
        for n in names:
            for s in self.indices[n].searchers():
                searchers.append(s)
                index_of.append(n)

        agg_specs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        query = body.get("query", {"match_all": {}})
        if _contains_mlt(query):
            query = self._expand_mlt(query, names)
        knn = body.get("knn")
        from .search.query_parser import parse_rank
        rank_spec = parse_rank(body.get("rank"))
        rescore_spec = body.get("rescore")
        if isinstance(rescore_spec, list):
            rescore_spec = rescore_spec[0] if rescore_spec else None
        # rescore window must be collected in the query phase
        window = int(rescore_spec.get("window_size", size)) \
            if rescore_spec else 0

        search_after = body.get("search_after")
        if isinstance(search_after, list) and not search_after:
            search_after = None
        if search_after is not None and sort is None:
            raise QueryParsingException("search_after requires a sort")
        if rescore_spec is not None and sort is not None:
            # the reference's RescorePhase rejects rescore+sort outright
            raise QueryParsingException("rescore cannot be used with a sort")
        if rank_spec is not None:
            # hybrid fusion (ISSUE 10): both retrievers must exist, and the
            # fused list has no sort/rescore interpretation
            if knn is None:
                raise QueryParsingException(
                    "rank requires a knn section to fuse with the query")
            if sort is not None:
                raise QueryParsingException("rank cannot be used with a sort")
            if rescore_spec is not None:
                raise QueryParsingException(
                    "rank cannot be combined with rescore")
        rank_window = 0
        knn_nprobe = None
        knn_exact = False
        knn_quant = None
        if knn is not None:
            if agg_specs:
                # the knn phase computes no agg partials; silently returning
                # empty aggregations would be a lie (advisor r1 finding)
                raise QueryParsingException(
                    "aggregations are not supported with knn search")
            raw_np = knn.get("nprobe")
            knn_nprobe = int(raw_np) if raw_np is not None else None
            knn_exact = bool(knn.get("exact", False))
            # per-request quantization override (ISSUE 12): pin the int8/
            # pq scan or force the f32 IVF lane regardless of the index
            # default — one index can be measured all three ways
            knn_quant = knn.get("quantization")
            if knn_quant is not None and str(knn_quant).strip().lower() \
                    not in ("none", "int8", "pq"):
                raise QueryParsingException(
                    f"knn quantization must be one of [none, int8, pq], "
                    f"got [{knn_quant}]")
            qv_single = knn.get("query_vector")
            if qv_single is None:
                qvs = knn.get("query_vectors")
                if not qvs:
                    raise QueryParsingException(
                        "knn requires query_vector (or query_vectors with "
                        "exactly one entry)")
                if len(qvs) != 1:
                    raise QueryParsingException(
                        "knn search takes one query_vector per request; use "
                        "ShardSearcher.execute_knn for batched vectors")
                qv_single = qvs[0]
            if "field" not in knn:
                raise QueryParsingException("knn requires a field")
            if rank_spec is not None:
                # fusion ranks over a per-retriever window, then returns
                # the caller's size — knn.k defaults to the window
                rank_window = rank_spec.window_size or max(size + from_, 10)
                knn_k = int(knn.get("k", rank_window))
            else:
                # k is the user's neighbor count contract: the response
                # carries at most min(k, size) hits (never silently raised
                # — the reduce below shrinks size instead; k defaults to
                # covering pagination)
                knn_k = int(knn.get("k", size + from_))
                size = min(size, max(knn_k - from_, 0))

        # index-global term statistics, shared by every shard: BOTH serving
        # lanes score with the same IDF, so packed vs fallback answers are
        # identical (VERDICT r3 weak #4; ref search/dfs/DfsPhase semantics,
        # here the default because stats are one host reduce away)
        global_stats = None
        nodes_by_index: dict[str, Any] = {}
        if knn is None or rank_spec is not None:
            from .search.query_dsl import CollectionStats
            terms_by_field: dict[str, set] = {}
            for n in names:
                from .search.query_parser import merge_query_batch
                q_n = self._wrap_alias_query(query, alias_flt[n]) \
                    if n in alias_flt else query
                parsed = self._parse_cached(n, q_n)
                parsed.collect_terms(terms_by_field)
                nodes_by_index[n] = merge_query_batch([parsed])
            all_segs = [seg for s in searchers for seg in s.segments]
            global_stats = CollectionStats.from_segments(
                all_segs, terms_by_field)

        tns_parsed = tracing.now_ns()
        tracing.add_span("parse", tns0, tns_parsed)
        self._record_phase("parse", (tns_parsed - tns0) / 1e6)
        from .common.metrics import current_profiler
        prof = current_profiler()
        if prof is not None:
            prof.record_phase("parse", (tns_parsed - tns0) / 1e6)
        def _run_shard(i: int, s: ShardSearcher,
                       submit_ns: int | None = None):
            # shard-level action registered under the coordinator task
            # (ref TransportSearchTypeAction per-shard phase actions).
            # The trace's shard span covers submit→done; queue_wait
            # (submit→start) and run (start→done) split it so a saturated
            # search pool is visibly queue time, not shard work.
            start_ns = tracing.now_ns()
            with self.tasks.scope(
                    "indices:data/read/search[phase/query]",
                    description=f"shard [{index_of[i]}][{s.shard_id}]"), \
                 _maybe_shard_profile(prof, index_of[i], s.shard_id), \
                 tracing.span("shard",
                              start_ns=submit_ns if submit_ns is not None
                              else start_ns,
                              index=index_of[i], shard=s.shard_id):
                if submit_ns is not None:
                    tracing.add_span("queue_wait", submit_ns, start_ns)
                with tracing.span("run", start_ns=start_ns):
                    if knn is not None:
                        fnode = s.parse([knn["filter"]]) \
                            if knn.get("filter") else None
                        r = s.execute_knn(knn["field"], [qv_single],
                                          k=knn_k,
                                          metric=knn.get("metric",
                                                         "cosine"),
                                          filter_node=fnode,
                                          nprobe=knn_nprobe,
                                          exact=knn_exact,
                                          quantization=knn_quant)
                        if rank_spec is not None:
                            # hybrid fusion: the text retriever runs in
                            # the SAME shard pass; fuse_hybrid merges the
                            # two global lists after the fan-out
                            r_text = s.execute_query_phase(
                                nodes_by_index[index_of[i]],
                                size=rank_window, from_=0,
                                global_stats=global_stats,
                                track_scores=True)
                            r = (r_text, r)
                    else:
                        r = s.execute_query_phase(
                            nodes_by_index[index_of[i]],
                            size=max(size, window),
                            from_=from_, sort=sort,
                            global_stats=global_stats,
                            aggs=agg_specs if agg_specs else None,
                            search_after=search_after,
                            track_scores=bool(body.get("track_scores",
                                                       False))
                            if sort is not None else True)
                    if rescore_spec is not None:
                        r = s.rescore(r, rescore_spec)
            return r

        shard_failures = 0
        shard_failure_details: list[dict] = []
        mesh_reduced = None
        mesh_aggs_merged = None
        with tracing.span("query"):
            # mesh-sharded query lane (parallel/mesh_exec): when this node
            # owns every shard and the device mesh can seat them, the
            # whole multi-shard query phase — per-shard stacked execution,
            # agg partial collect AND the cross-shard merge — runs as ONE
            # shard_map program with ONE device fetch and zero host-side
            # per-shard merges. kNN bodies ride their own mesh program
            # (parallel/mesh_knn: exact matmul or IVF under the sharded
            # axis). Sorted bodies ride the encoded-key sorted program
            # (ISSUE 17, mesh_exec.execute_sorted) — ineligible encodings
            # decline with a stable reason. Rescore/rank bodies,
            # cross-host shards and unsupported plan/agg shapes fall
            # through to the fan-out.
            if (len(names) == 1 and len(searchers) > 1 and knn is None
                    and rescore_spec is None):
                mesh_out = self._try_mesh(
                    names[0], searchers, nodes_by_index[names[0]],
                    global_stats, size=size, from_=from_,
                    agg_specs=agg_specs or None, sort=sort,
                    search_after=search_after,
                    track_scores=bool(body.get("track_scores", False))
                    if sort is not None else True)
                if mesh_out is not None:
                    mesh_rows, mesh_aggs_merged = mesh_out
                    mesh_reduced = mesh_rows[0] if mesh_rows else None
            elif (len(names) == 1 and len(searchers) > 1
                  and knn is not None and rank_spec is None
                  and rescore_spec is None):
                mesh_reduced = self._try_mesh_knn(
                    names[0], searchers, knn, k=knn_k, qv=[qv_single],
                    nprobe=knn_nprobe, exact=knn_exact,
                    quantization=knn_quant, size=size, from_=from_)
            if mesh_reduced is not None:
                results = []
            elif len(searchers) == 1:
                # sequential fast path: no job/context machinery, errors
                # raise straight through exactly as before
                results = [_run_shard(0, searchers[0])]
            else:
                # concurrent fan-out onto the bounded `search` pool. Each
                # job runs in a COPY of the coordinator's context so
                # tasks.scope parenting, the active profiler AND the active
                # trace span propagate; claim-once semantics let the
                # coordinator steal any job the pool hasn't started
                # (deadlock-free even when coordinators themselves occupy
                # the search pool), and pool-queue overflow simply leaves
                # the remainder to run inline.
                import contextvars
                from .common.threadpool import EsRejectedExecutionException
                jobs = []
                for i, s in enumerate(searchers):
                    ctx = contextvars.copy_context()
                    jobs.append(_ShardJob(
                        functools.partial(ctx.run, _run_shard, i, s,
                                          tracing.now_ns())))
                try:
                    for job in jobs[1:]:
                        self.thread_pool.execute("search", job.run)
                except EsRejectedExecutionException:
                    pass
                jobs[0].run()
                results = []
                first_error = None
                for i, job in enumerate(jobs):
                    job.join()
                    if job.error is not None:
                        # shard-failure accounting (ref per-shard onFailure
                        # in TransportSearchTypeAction): the response
                        # carries the failure; only an all-shards failure
                        # raises
                        shard_failures += 1
                        first_error = first_error or job.error
                        shard_failure_details.append({
                            "index": index_of[i],
                            "shard": searchers[i].shard_id,
                            "reason": f"{type(job.error).__name__}: "
                                      f"{job.error}"})
                        er = _empty_shard_result(
                            searchers[i].shard_id, sort=sort)
                        results.append((er, er) if rank_spec is not None
                                       else er)
                    else:
                        results.append(job.result)
                if shard_failures == len(searchers) \
                        and first_error is not None:
                    raise first_error

        tns_fetch0 = tracing.now_ns()
        self._record_phase("device", (tns_fetch0 - tns_parsed) / 1e6)
        if prof is not None:
            prof.record_phase("query", (tns_fetch0 - tns_parsed) / 1e6)
        # the mesh lane already reduced ON DEVICE — sort_docs (the host
        # cross-shard merge) runs only for the fan-out path; rank bodies
        # fuse the two retrievers' GLOBAL lists on device instead
        if mesh_reduced is not None:
            reduced = mesh_reduced
        elif rank_spec is not None:
            reduced = controller.fuse_hybrid(
                [t for t, _ in results], [v for _, v in results],
                rank_spec, from_=from_, size=size)
        else:
            reduced = controller.sort_docs(results, from_=from_, size=size,
                                           sort=sort)
        src_filter = body.get("_source")
        fields_spec = body.get("fields")
        if isinstance(fields_spec, str):
            fields_spec = [fields_spec]
        hits = controller.fetch_and_merge(
            reduced, searchers,
            source_filter=(lambda s: _source_filter(s, src_filter))
            if src_filter is not None else None,
            fields_spec=fields_spec)
        for slot, h in enumerate(hits):
            h["_index"] = index_of[reduced.shard_order[slot]]

        hl_spec = None
        if body.get("highlight") and knn is None:
            from .search.highlight import highlight_hit, parse_highlight
            hl_spec = parse_highlight(body["highlight"])
        t_hl0 = time.perf_counter()
        if hl_spec is not None:
            from .search.shard_searcher import LOCAL_MASK, SEG_SHIFT
            for slot, h in enumerate(hits):
                si = reduced.shard_order[slot]
                key = reduced.doc_keys[slot]
                seg = searchers[si].segments[key >> SEG_SHIFT]
                raw_src = seg.stored[key & LOCAL_MASK]
                mappers = self.indices[index_of[si]].mappers

                def an_for(fname, _m=mappers):
                    for dm in _m._mappers.values():
                        if fname in dm.fields:
                            return dm.search_analyzer_for(fname)
                    return _m.analysis.analyzer("standard")

                hl = highlight_hit(hl_spec, raw_src, terms_by_field, an_for)
                if hl:
                    h["highlight"] = hl
        if hl_spec is not None and prof is not None:
            prof.record_phase("highlight",
                              (time.perf_counter() - t_hl0) * 1000)

        if body.get("script_fields"):
            # per-hit computed fields (ref search/fetch/script/
            # ScriptFieldsFetchSubPhase + lang-expression doc[...] access)
            from .script.engine import run_search_script
            from .search.shard_searcher import LOCAL_MASK, SEG_SHIFT
            for slot, h in enumerate(hits):
                si = reduced.shard_order[slot]
                key = reduced.doc_keys[slot]
                seg = searchers[si].segments[key >> SEG_SHIFT]
                raw_src = seg.stored[key & LOCAL_MASK]
                flds = h.setdefault("fields", {})
                for fname, fspec in body["script_fields"].items():
                    val = run_search_script(
                        fspec, raw_src, params=(fspec or {}).get("params")
                        if isinstance(fspec, dict) else None)
                    flds[fname] = [val]

        shards_section: dict[str, Any] = {
            "total": len(searchers),
            "successful": len(searchers) - shard_failures,
            "failed": shard_failures}
        if shard_failure_details:
            shards_section["failures"] = shard_failure_details
        resp: dict[str, Any] = {
            "took": 0,              # set once the response is assembled
            "timed_out": False,
            "_shards": shards_section,
            "hits": {"total": reduced.total_hits,
                     "max_score": None if reduced.max_score != reduced.max_score
                     else reduced.max_score,
                     "hits": hits},
        }
        if agg_specs:
            aggs_span = tracing.span("aggregations")
            with aggs_span:
                if mesh_aggs_merged is not None:
                    # the mesh program already collected + merged the
                    # partials on device (parallel/mesh_aggs.py)
                    merged = mesh_aggs_merged
                else:
                    merged = merge_shard_partials(
                        agg_specs, [r.aggs for r in results if r.aggs])
                resp["aggregations"] = render_aggs(agg_specs, merged)
            if prof is not None:
                prof.record_phase("aggregations", aggs_span.duration_ms)
        if body.get("suggest"):
            resp["suggest"] = self.suggest(index, body["suggest"])
        tns_done = tracing.now_ns()
        tracing.add_span("fetch", tns_fetch0, tns_done)
        fetch_ms = (tns_done - tns_fetch0) / 1e6
        took_ms = (tns_done - tns0) / 1e6
        self._record_phase("fetch", fetch_ms)
        self._record_phase("total", took_ms, compiles0)
        if prof is not None:
            # response-assembly remainder: everything after the device
            # phase that isn't already booked (reduce/fetch/highlight/aggs)
            post = sum(v for k, v in prof.phases.items()
                       if k not in ("parse", "query"))
            prof.record_phase("serialize", max(fetch_ms - post, 0.0))
        resp["took"] = int(took_ms)
        tid, oid = self._trace_ids()
        slow = None
        for n in names:     # every searched index's thresholds apply
            slow = self.slowlog.maybe_log(self.indices[n].settings, n,
                                          took_ms, body,
                                          trace_id=tid, opaque_id=oid) \
                or slow
        if slow is not None:
            # a slowlogged request always keeps its trace — the slowlog
            # entry's trace_id must resolve in GET /_traces
            tracing.mark_slowlog()
        if cache_key is not None:
            # byte-accounted LRU insert charging the `request` breaker; a
            # refused insert (budget/breaker pressure) just means this
            # response goes out uncached — never a 5xx
            self.caches.request_cache.put(cache_key, names, resp)
        return resp

    def _alias_filters_by_index(self, expr: str,
                                names: list[str]) -> dict[str, list]:
        """Per-index alias filters: each index searched THROUGH a filtered
        alias gets that alias's filter applied to ITS shards only; multiple
        filtered aliases targeting one index OR together (ref
        cluster/metadata/AliasMetaData + filtering-alias resolution in
        TransportSearchTypeAction — filters are per-index, should-combined)."""
        by_index: dict[str, list] = {}
        unfiltered: set[str] = set()   # reached concretely or via a
        for part in str(expr).split(","):   # filter-less alias → no filter
            for n in names:
                if part == n or ("*" in part and fnmatch.fnmatch(n, part)):
                    unfiltered.add(n)
                    continue
                props = self.indices[n].aliases.get(part)
                if props is None:
                    continue
                if props.get("filter"):
                    by_index.setdefault(n, []).append(props["filter"])
                else:
                    unfiltered.add(n)
        for n in unfiltered:
            by_index.pop(n, None)
        return by_index

    @staticmethod
    def _wrap_alias_query(query, filters: list):
        flt = filters[0] if len(filters) == 1 \
            else {"bool": {"should": filters}}
        return {"bool": {"must": [query or {"match_all": {}}],
                         "filter": [flt]}}

    def _expand_mlt(self, q, names: list[str]):
        """Rewrite more_like_this specs into term-disjunction queries
        (ref index/query/MoreLikeThisQueryParser + common/lucene/search/
        MoreLikeThisQuery: select the like-text's top tf*idf terms, query
        them as a should-of-terms). Runs BEFORE parsing because term
        selection needs corpus statistics the parser doesn't hold."""
        if isinstance(q, list):
            return [self._expand_mlt(x, names) for x in q]
        if not isinstance(q, dict):
            return q
        if not any(_is_mlt_entry(k, v) for k, v in q.items()):
            return {k: self._expand_mlt(v, names) for k, v in q.items()}

        spec = q.get("more_like_this")
        if spec is None:
            spec = q.get("mlt")
        fields = spec.get("fields") or ["_all"]
        min_tf = int(spec.get("min_term_freq", 2))
        min_df = int(spec.get("min_doc_freq", 5))
        max_terms = int(spec.get("max_query_terms", 25))
        texts: list[str] = []
        if spec.get("like_text"):
            texts.append(str(spec["like_text"]))
        likes = spec.get("like", [])
        likes = likes if isinstance(likes, list) else [likes]
        doc_refs = [d for d in likes if isinstance(d, dict)] \
            + list(spec.get("docs") or []) \
            + [{"_id": i} for i in (spec.get("ids") or [])]
        texts += [t for t in likes if isinstance(t, str)]
        exclude_ids: list[str] = []

        def _texts_from(source: dict):
            for f in fields:
                v = source.get(f) if f != "_all" else None
                if isinstance(v, str):
                    texts.append(v)
                elif f == "_all":
                    texts.extend(x for x in source.values()
                                 if isinstance(x, str))

        for ref in doc_refs:
            if "doc" in ref and isinstance(ref["doc"], dict):
                _texts_from(ref["doc"])      # artificial document form
                continue
            if "_id" not in ref:
                continue
            try:
                got = self.get_doc(ref.get("_index", names[0]),
                                   str(ref["_id"]))
            except IndexMissingException:
                continue
            if got.found and got.source:
                exclude_ids.append(str(ref["_id"]))
                _texts_from(got.source)

        # ignore_like: terms appearing in these docs are STRUCK from the
        # selected term set (ref MoreLikeThisQueryParser "ignore_like" /
        # unlike handling)
        ignore_texts: list[str] = []
        ignores = spec.get("ignore_like") or spec.get("unlike") or []
        ignores = ignores if isinstance(ignores, list) else [ignores]
        for ref in ignores:
            if isinstance(ref, str):
                ignore_texts.append(ref)
                continue
            if isinstance(ref, dict) and "doc" in ref:
                ignore_texts.extend(x for x in ref["doc"].values()
                                    if isinstance(x, str))
                continue
            if not isinstance(ref, dict) or "_id" not in ref:
                continue
            try:
                got = self.get_doc(ref.get("_index", names[0]),
                                   str(ref["_id"]))
            except IndexMissingException:
                continue
            if got.found and got.source:
                ignore_texts.extend(x for x in got.source.values()
                                    if isinstance(x, str))

        segments = [seg for n in names
                    for e in self.indices[n].shards for seg in e.segments]
        all_fields = {f for seg in segments for f in seg.text} \
            if fields == ["_all"] else set(fields)
        should = []
        from .search.query_dsl import MatchNoneNode  # noqa: F401 (shape doc)
        for field in sorted(all_fields):
            mappers = self.indices[names[0]].mappers
            an = None
            for dm in mappers._mappers.values():
                if field in dm.fields:
                    an = dm.search_analyzer_for(field)
                    break
            if an is None:
                an = mappers.analysis.analyzer("standard")
            tf: dict[str, int] = {}
            for t in texts:
                for tok in an(t):
                    tf[tok] = tf.get(tok, 0) + 1
            for t in ignore_texts:
                for tok in an(t):
                    tf.pop(tok, None)
            import math as _m
            n_docs = max(sum(s.n_docs for s in segments), 1)
            scored = []
            for term, f in tf.items():
                if f < min_tf:
                    continue
                df = sum(s.doc_freq(field, term) for s in segments)
                if df < min_df:
                    continue
                scored.append((f * _m.log(1 + n_docs / (df + 1)), term))
            scored.sort(reverse=True)
            terms = [t for _, t in scored[:max_terms]]
            if terms:
                # the reference's default minimum_should_match for MLT
                # is 30% of the selected terms
                msm = max(1, round(0.3 * len(terms)))
                should.append({"match": {field: {
                    "query": " ".join(terms),
                    "minimum_should_match": msm}}})
        if not should:
            return {"match_none": {}}
        out: dict = {"bool": {"should": should, "minimum_should_match": 1}}
        if exclude_ids and not spec.get("include", False):
            # the reference excludes the input docs themselves
            # (ref MoreLikeThisQueryParser include=false default)
            out["bool"]["must_not"] = [{"ids": {"values": exclude_ids}}]
        return out

    def _percolate_filter(self, name: str, flt, out: dict) -> dict:
        """Body filter/query restricts WHICH registered .percolator docs
        participate, evaluated against their own indexed fields
        (ref PercolatorService percolate-with-filter)."""
        if flt is None or not out["matches"]:
            return out
        res = self.search(name, {
            "query": {"bool": {"filter": [flt]}},
            "size": 10_000, "_source": False})
        allowed = {h["_id"] for h in res["hits"]["hits"]}
        out["matches"] = [m for m in out["matches"] if m["_id"] in allowed]
        out["total"] = len(out["matches"])
        return out

    def percolate(self, index: str, body: dict,
                  type_name: str = "_doc",
                  doc_id: str | None = None) -> dict:
        """Match a doc against the index's registered queries
        (ref percolator/PercolatorService.java:108-132) — through the
        dense doc×query matrix executor (search/percolate_exec.py),
        which itself ladders down to the per-doc loop."""
        from .search.percolate_exec import percolate_batch
        names = self._resolve(index)
        if not names:
            raise IndexMissingException(index)
        doc = (body or {}).get("doc")
        if doc is None and doc_id is not None:
            got = self.get_doc(names[0], doc_id)
            if not got.found:
                raise DocumentMissingException(
                    f"[{type_name}][{doc_id}]: document missing")
            doc = got.source
        if doc is None:
            raise QueryParsingException("percolate requires a doc")
        flt = (body or {}).get("filter") or (body or {}).get("query")
        total = 0
        matches: list = []
        from .common.device_stats import current_lanes, record_lanes
        # reuse an active recorder (chaos parity sweeps wrap their own)
        with record_lanes(current_lanes()) as lanes:
            for n in names:
                out = percolate_batch(self.indices[n], n,
                                      [(doc, type_name)],
                                      caches=self.caches,
                                      devices=self.device_pool.devices
                                      if self.device_pool else None)[0]
                out = self._percolate_filter(n, flt, out)
                total += out["total"]
                matches.extend(out["matches"])
        resp = {"took": 0, "_shards": {"total": len(names),
                                       "successful": len(names),
                                       "failed": 0},
                "total": total, "matches": matches}
        if (body or {}).get("profile"):
            # the percolate ladder's explain surface: which rung carried
            # the request (mesh / dense / loop) and why others declined
            resp["profile"] = {"lanes": lanes.explain()}
        return resp

    def mpercolate(self, index: str, bodies: list[dict],
                   type_name: str = "_doc") -> dict:
        """Batched percolation: every doc becomes one row of the SAME
        dense doc×query matrix program — the whole batch costs one device
        dispatch per index, not one per doc (ISSUE 18 `_mpercolate`)."""
        from .search.percolate_exec import percolate_batch
        names = self._resolve(index)
        if not names:
            raise IndexMissingException(index)
        docs: list[tuple[dict, str]] = []
        for b in bodies:
            doc = (b or {}).get("doc")
            if doc is None:
                raise QueryParsingException("percolate requires a doc")
            docs.append((doc, (b or {}).get("type", type_name)))
        shards = {"total": len(names), "successful": len(names),
                  "failed": 0}
        merged = [{"took": 0, "_shards": dict(shards),
                   "total": 0, "matches": []} for _ in docs]
        for n in names:
            outs = percolate_batch(self.indices[n], n, docs,
                                   caches=self.caches,
                                   devices=self.device_pool.devices
                                   if self.device_pool else None)
            for i, out in enumerate(outs):
                flt = (bodies[i] or {}).get("filter") \
                    or (bodies[i] or {}).get("query")
                out = self._percolate_filter(n, flt, out)
                merged[i]["total"] += out["total"]
                merged[i]["matches"].extend(out["matches"])
        return {"responses": merged}

    def refresh_doc_shard(self, index: str, doc_id: str,
                          routing: str | None = None) -> None:
        """Per-op ?refresh=true refreshes only the WRITTEN shard (ref
        TransportShardReplicationOperationAction per-shard refresh) — other
        shards' pending deletes stay invisible until their own refresh."""
        for name in self._resolve(index):   # aliases resolve like writes do
            svc = self.indices.get(name)
            if svc is not None:
                svc.shard_for(doc_id, routing).refresh()

    def termvectors(self, index: str, doc_id: str, type_name: str = "_doc",
                    fields: list[str] | None = None, realtime: bool = True,
                    term_statistics: bool = False,
                    field_statistics: bool = True,
                    positions: bool = True, offsets: bool = True,
                    routing: str | None = None,
                    parent: str | None = None) -> dict:
        """Per-document term vectors (ref action/termvectors/
        TransportTermVectorsAction + TermVectorsResponse): term/position/
        offset lists re-derived from the stored source through the SAME
        analysis chain that indexed it (tensor segments don't keep per-doc
        postings slices addressable by doc, so re-analysis — which is
        exact, same analyzer, same source — replaces Lucene's stored term
        vectors)."""
        import time as _time
        t0 = _time.perf_counter()
        names = self._resolve(index)
        if not names:
            raise IndexMissingException(index)
        name = names[0]
        svc = self.indices[name]
        res = svc.get_doc(doc_id, routing=routing, parent=parent,
                          realtime=realtime)
        out = {"_index": name, "_type": res.type_name if res.found
               else type_name, "_id": doc_id, "found": res.found,
               "took": 0}
        if not res.found:
            return out
        out["_version"] = res.version
        mapper = svc.mappers.document_mapper(res.type_name, create=False) \
            or svc.mappers.document_mapper(type_name)
        segments = [seg for e in svc.shards for seg in e.segments]

        def flat(prefix, obj, into):
            for k, v in obj.items():
                path = f"{prefix}{k}"
                if isinstance(v, dict):
                    flat(path + ".", v, into)
                else:
                    into[path] = v

        flat_src: dict[str, Any] = {}
        flat("", res.source or {}, flat_src)
        tv: dict[str, dict] = {}
        for field, value in flat_src.items():
            ft = mapper.fields.get(field)
            if ft is None or ft.type != "text":
                continue
            if fields is not None and field not in fields:
                continue
            analyzer = mapper._analyzer_for(ft)
            texts = value if isinstance(value, list) else [value]
            terms: dict[str, dict] = {}
            pos = 0
            for text in texts:
                for m in re.finditer(r"\w+(?:[.']\w+)*", str(text)):
                    toks = analyzer(m.group(0))
                    if not toks:
                        continue     # filtered out (stopword etc.)
                    t = toks[0]
                    entry = terms.setdefault(t, {"term_freq": 0,
                                                 "tokens": []})
                    entry["term_freq"] += 1
                    tok: dict = {}
                    if positions:
                        tok["position"] = pos
                    if offsets:
                        tok["start_offset"] = m.start()
                        tok["end_offset"] = m.end()
                    if tok:
                        entry["tokens"].append(tok)
                    pos += 1
            if not terms:
                continue
            if term_statistics:
                for t, entry in terms.items():
                    df = ttf = 0
                    for seg in segments:
                        fx = seg.text.get(field)
                        if fx is None:
                            continue
                        s, ln, tid = fx.lookup(t)
                        if tid >= 0:
                            df += ln
                            import numpy as _np
                            ttf += int(_np.asarray(fx.tf)[s:s + ln].sum())
                    entry["doc_freq"] = df
                    entry["ttf"] = ttf
            fstat = None
            if field_statistics:
                import numpy as _np
                sum_df = doc_count = 0
                sum_ttf = 0.0
                for seg in segments:
                    fx = seg.text.get(field)
                    if fx is None:
                        continue
                    sum_df += int(fx.term_lens.sum())
                    sum_ttf += fx.sum_dl        # Σ tokens == Σ tf
                    if fx.doc_ids_host is not None:
                        # docs CONTAINING the field (ref FieldStats
                        # docCount), not all docs in the segment
                        uniq = _np.unique(fx.doc_ids_host)
                        doc_count += int(
                            seg.live_host[uniq].sum())
                    else:
                        doc_count += seg.root_live_count
                fstat = {"sum_doc_freq": sum_df,
                         "doc_count": doc_count,
                         "sum_ttf": int(sum_ttf)}
            entry_out: dict = {}
            if fstat is not None:
                entry_out["field_statistics"] = fstat
            entry_out["terms"] = terms
            tv[field] = entry_out
        out["term_vectors"] = tv
        out["took"] = int((_time.perf_counter() - t0) * 1000)
        return out

    def suggest(self, index: str, body: dict) -> dict:
        """Run suggesters over the index's term dictionaries
        (ref search/suggest/SuggestPhase.java:43)."""
        from .search.suggest import run_suggest
        names = self._resolve(index)
        if not names:
            raise IndexMissingException(index)
        segments = [seg for n in names
                    for e in self.indices[n].shards for seg in e.segments]
        return run_suggest(body, segments,
                           mappers=self.indices[names[0]].mappers)

    def _packed_search(self, name: str, bodies: list[dict], *, size: int,
                       from_: int, t0: list[int], raw: bool = False,
                       specs: list | None = None) -> list | None:
        """Serve a batch of same-shaped requests through the packed view:
        ONE device program across all shards/segments, one upload, one
        download (serving/). `t0[i]` (ns) is when the request of body i
        began: each response's `took` is its own, whoever led the batch.
        Returns per-body responses (dicts, or the JSON's `bytes` when `raw`
        and `_source: false`), or None to fall back."""
        from .serving.executor import packed_spec_of
        from .serving.executor import respond as respond_batch
        svc = self.indices[name]
        view = svc.packed_view()
        if view is None:
            return None
        if specs is None:
            from .search.query_parser import QueryParser
            parser = QueryParser(svc.mappers)
            specs = [packed_spec_of(parser, body) for body in bodies]
        if any(s is None for s in specs):
            return None
        field, k1, b = specs[0][1], specs[0][2], specs[0][3]
        if any(s[1] != field or s[2] != k1 or s[3] != b for s in specs[1:]):
            return None
        if not view.servable(field):
            return None     # request breaker refused the packed postings
        queries = [s[0] for s in specs]
        k = max(size + from_, 1)
        from .serving.packed_view import FilterColumnRefused
        try:
            scores, docs, hits = view.search(field, queries, k=k, k1=k1, b=b)
        except FilterColumnRefused:
            return None    # breaker refused a filter column: general path
        respond = tracing.span("packed.respond", cpu=True)
        with respond:
            # `took` ends where the rendering starts (the span's own read).
            # serving/executor.respond renders the batch: the `_source:
            # false` bodies of a raw request as `bytes`, all their hits in
            # one vector pass; else dicts. Nothing above this span may gain
            # or lose a line: the packed programs' compile-cache keys hold
            # the line of `view.search(` above and of `self._packed_search(`
            # in `msearch` below (PERF.md §6, PR 29), which is why this
            # block is as long as the loop it replaced.
            tooks = [(respond.start_ns - t) // 1_000_000 for t in t0]
            out, said = respond_batch(
                view, name, bodies, scores, docs, hits, raw=raw,
                n_shards=svc.n_shards, tooks=tooks, from_=from_, size=size,
                source_filter=_source_filter)
            # form=raw|dict, hits=, patched= (rows that took the scalar
            # `%.9g`): on the span of `GET /_traces`; the counter
            # es_packed_render_hits_total{form=} has the same by form
            respond.attrs.update(said)
        # count AFTER successful response assembly — a failure above is the
        # request's error and must not be booked as a packed serve
        svc.search_stats["packed"] = \
            svc.search_stats.get("packed", 0) + len(bodies)
        svc.query_total += len(bodies)
        svc.meters["search"].mark(len(bodies))
        self.meters["search"].mark(len(bodies))
        return out

    # -- mesh-sharded query lane (parallel/mesh_exec, ISSUE 6) -------------

    def _try_mesh(self, name: str, searchers, node_tree, global_stats, *,
                  size: int, from_: int, n_queries: int = 1,
                  agg_specs=None, sort=None, search_after=None,
                  track_scores: bool = True):
        """One mesh-lane attempt for a multi-shard query batch: returns
        (per-row ReducedDocs list, merged agg partial | None) from the
        on-device collective reduce (single searches take row 0), or
        None to fall back to the PR-4 concurrent fan-out (opt-out
        settings, joins, unsupported plan/agg shapes, too few devices,
        breaker-declined/oversized mesh stacks). An execution error is the
        request's error, never a decline.

        With `agg_specs`, the agg tree rides the SAME program
        (parallel/mesh_aggs.py) — the merged partial equals the fan-out's
        per-shard collect + host merge bit-for-bit. With `sort`, the
        encoded-key sorted program (ISSUE 17) replaces the host merge;
        winners' user-facing sort values materialize host-side per hit."""
        from .common.device_stats import lane_chosen, lane_decline
        svc = self.indices[name]
        if not svc._mesh_enabled \
                or not _mesh_enabled_setting(self.settings):
            lane_decline("query", "mesh", "opt_out")
            return None
        from .search.query_dsl import contains_joins
        if contains_joins(node_tree):
            lane_decline("query", "mesh", "joins")
            return None
        from .parallel import mesh_exec
        if not mesh_exec.plan_types_supported(node_tree):
            lane_decline("query", "mesh", "plan_unsupported")
            return None
        if mesh_exec.mesh_for(len(searchers),
                              pool=self.device_pool) is None:
            # cross-host topology / fewer devices than shards
            lane_decline("query", "mesh", "no_mesh")
            return None
        k = max(size + from_, 1)
        stack = self.caches.mesh_stacks.get_or_build(
            name, svc._incarnation,
            [list(s.segments) for s in searchers],
            breaker=self.breakers.breaker("fielddata"),
            pool=self.device_pool)
        if stack is None:
            lane_decline("query", "mesh", "stack_declined")
            return None
        with tracing.span("mesh_reduce", index=name,
                          shards=len(searchers), k=k):
            if sort is not None:
                out = mesh_exec.execute_sorted(
                    stack, node_tree, global_stats, sort,
                    search_after, k=k, Q=n_queries,
                    agg_specs=agg_specs)
            else:
                out = mesh_exec.execute(
                    stack, node_tree, global_stats, k=k, Q=n_queries,
                    block_docs=svc._block_docs
                    if svc._blockwise_enabled else None,
                    agg_specs=agg_specs)
        if out is None:
            # plan/agg shape has no collective form (field shapes),
            # or the sort encoding declined (reason already recorded)
            lane_decline("query", "mesh",
                         "agg_shape" if agg_specs else "plan_shape")
            if agg_specs:
                svc.search_stats["mesh_agg_fallbacks"] = \
                    svc.search_stats.get("mesh_agg_fallbacks", 0) + 1
            return None
        keys, shard_of, scores, totals, mxs, agg_per_shard = out
        lane_chosen("query", "mesh")
        svc.search_stats["mesh"] = svc.search_stats.get("mesh", 0) + 1
        svc.search_stats["mesh_dispatches"] = \
            svc.search_stats.get("mesh_dispatches", 0) + 1
        if sort is not None:
            svc.search_stats["mesh_sorted_dispatches"] = \
                svc.search_stats.get("mesh_sorted_dispatches", 0) + 1
        if agg_specs:
            svc.search_stats["mesh_agg_dispatches"] = \
                svc.search_stats.get("mesh_agg_dispatches", 0) + 1
        if mesh_exec.last_block_mode == "blockwise":
            svc.search_stats["blockwise_dispatches"] = \
                svc.search_stats.get("blockwise_dispatches", 0) + 1
        from .common.metrics import current_profiler, record_shard_fetches
        record_shard_fetches(1)     # ONE fetch served every shard
        prof = current_profiler()
        if prof is not None:
            prof.note_path("mesh")
        if sort is not None:
            rows = _mesh_rows_sorted(
                keys, shard_of, scores, totals, mxs, searchers,
                n_queries=n_queries, size=size, from_=from_, sort=sort,
                track_scores=track_scores)
        else:
            rows = _mesh_rows(keys, shard_of, scores, totals, mxs,
                              n_queries=n_queries, size=size, from_=from_)
        agg_merged = None
        if agg_per_shard is not None:
            from .search.aggs.aggregators import merge_shard_partials
            agg_merged = merge_shard_partials(agg_specs, agg_per_shard)
        return rows, agg_merged

    # -- mesh kNN lane (parallel/mesh_knn, ISSUE 11) -----------------------

    def _try_mesh_knn(self, name: str, searchers, knn: dict, *, k: int,
                      qv, nprobe, exact: bool, size: int, from_: int,
                      quantization: str | None = None):
        """One mesh attempt for a multi-shard kNN body: all co-hosted
        shards' vector columns execute as ONE shard_map program — exact
        matmul or the IVF centroid-route + cluster scan under the sharded
        axis — with the cross-shard top-k reduce on device. Returns
        ReducedDocs or None to fall back to the per-shard fan-out (mixed
        IVF/exact segment lanes, non-uniform nlist, filter plans without a
        mesh form, opt-outs). An execution error is the request's error."""
        from .common.device_stats import lane_chosen, lane_decline
        svc = self.indices[name]
        if not svc._mesh_enabled \
                or not _mesh_enabled_setting(self.settings):
            lane_decline("knn", "mesh_knn", "opt_out")
            return None
        from .parallel import mesh_exec, mesh_knn
        if mesh_exec.mesh_for(len(searchers),
                              pool=self.device_pool) is None:
            lane_decline("knn", "mesh_knn", "no_mesh")
            return None
        vstack = self.caches.mesh_vector_stacks.get_or_build(
            name, svc._incarnation, knn["field"],
            [list(s.segments) for s in searchers],
            breaker=self.breakers.breaker("fielddata"),
            pool=self.device_pool)
        if vstack is None:
            lane_decline("knn", "mesh_knn", "vstack_declined")
            return None
        fnode = None
        if knn.get("filter"):
            fnode = searchers[0].parse([knn["filter"]])
        stack = None
        if fnode is not None:
            stack = self.caches.mesh_stacks.get_or_build(
                name, svc._incarnation,
                [list(s.segments) for s in searchers],
                breaker=self.breakers.breaker("fielddata"),
                pool=self.device_pool)
            if stack is None:
                lane_decline("knn", "mesh_knn", "stack_declined")
                return None
        with tracing.span("mesh_reduce", index=name,
                          shards=len(searchers), k=k, knn=True):
            out = mesh_knn.execute(
                vstack, qv, k=k,
                metric=knn.get("metric", "cosine"),
                knn_opts=searchers[0].knn_opts,
                nprobe=nprobe, exact=exact,
                quantization=quantization,
                acquire_ivf=lambda si, seg, vc:
                    searchers[si]._acquire_ivf(
                        seg, vc, knn["field"], nprobe, exact),
                acquire_quant=lambda si, seg, vc, ivf, mode:
                    searchers[si]._acquire_quant(
                        seg, vc, knn["field"], ivf, mode),
                filter_node=fnode, filter_stack=stack)
        if out is None:
            # mesh_knn.execute noted the specific (lane, reason) itself
            svc.search_stats["mesh_ann_fallbacks"] = \
                svc.search_stats.get("mesh_ann_fallbacks", 0) + 1
            return None
        keys, shard_of, scores, totals, mxs, used_ivf, used_quant = out
        lane_chosen("knn", "mesh_knn")
        svc.search_stats["mesh"] = svc.search_stats.get("mesh", 0) + 1
        svc.search_stats["mesh_dispatches"] = \
            svc.search_stats.get("mesh_dispatches", 0) + 1
        svc.search_stats["mesh_ann_dispatches"] = \
            svc.search_stats.get("mesh_ann_dispatches", 0) + 1
        if used_ivf:
            svc.search_stats["ann_dispatches"] = \
                svc.search_stats.get("ann_dispatches", 0) + 1
        if used_quant:
            svc.search_stats["ann_quantized_dispatches"] = \
                svc.search_stats.get("ann_quantized_dispatches", 0) + 1
            svc.search_stats[f"ann_quantized_{used_quant}"] = \
                svc.search_stats.get(f"ann_quantized_{used_quant}", 0) + 1
        from .common.metrics import current_profiler, record_shard_fetches
        record_shard_fetches(1)
        prof = current_profiler()
        if prof is not None:
            prof.note_path("mesh")
        return _mesh_rows(keys, shard_of, scores, totals, mxs,
                          n_queries=1, size=size, from_=from_)[0]

    def count(self, index: str, body: dict | None = None) -> dict:
        out = self.search(index, {**(body or {}), "size": 0})
        return {"count": out["hits"]["total"], "_shards": out["_shards"]}

    # -- msearch: batched multi-search (ref action/search/MultiSearchRequest;
    # rest/action/search/RestMultiSearchAction). The TPU twist: requests
    # whose query trees share a plan shape merge into ONE batched device
    # program (merge_query_batch) — the batching that the ≥10x QPS target
    # comes from (SURVEY.md §7: the unit of device work is a batch of
    # queries, not one query at a time). ----------------------------------

    # single source of truth for which body keys the fast lanes understand
    # (serving/executor.PACKED_BODY_KEYS) — the plan-shape batched lane and
    # the packed lane must never diverge in eligibility
    _BATCHABLE_KEYS = PACKED_BODY_KEYS

    def msearch(self, requests: list[tuple[dict, dict]],
                raw: bool = False) -> dict | bytes:
        """Batched multi-search. With `raw=True` returns the response body
        as pre-serialized bytes when possible (the packed path builds hit
        JSON vectorized — see serving/executor.py)."""
        import json
        from .common.device_stats import lane_chosen, lane_decline
        from .serving.executor import packed_spec_of
        responses: list = [None] * len(requests)
        metas: list[tuple[str, dict]] = []
        packed_groups: dict[Any, list[int]] = {}
        packed_specs: dict[int, Any] = {}
        parsers: dict[str, Any] = {}
        leftovers: list[int] = []
        planning = tracing.span("search.plan", cpu=True)
        with planning:
            for i, (header, body) in enumerate(requests):
                index = (header or {}).get("index") or "_all"
                body = body or {}
                metas.append((index, body))
                key = None
                try:
                    names = self._resolve(index)
                    if len(names) == 1:
                        name = names[0]
                        if name not in parsers:
                            from .search.query_parser import QueryParser
                            parsers[name] = QueryParser(
                                self.indices[name].mappers)
                        spec = packed_spec_of(parsers[name], body)
                        if spec is not None:
                            packed_specs[i] = spec
                            key = (name, int(body.get("size", 10)),
                                   int(body.get("from", 0)),
                                   repr(body.get("_source", True)))
                except Exception:  # noqa: BLE001 — solo path reports it
                    key = None
                if key is not None:
                    packed_groups.setdefault(key, []).append(i)
                else:
                    leftovers.append(i)
        t0 = planning.start_ns      # one `took` clock for every item

        for key, idxs in packed_groups.items():
            name, size, from_, _src = key
            try:
                outs = self._packed_search(
                    name, [metas[i][1] for i in idxs], size=size,
                    from_=from_, t0=[t0] * len(idxs), raw=raw,
                    specs=[packed_specs[i] for i in idxs])
            except Exception as e:  # noqa: BLE001 — per-item error contract
                # a program that raised is its members' error, never a
                # reason to serve them from a slower lane
                for i in idxs:
                    responses[i] = _msearch_error(e)
                continue
            if outs is None:
                lane_decline("msearch", "packed", "view_declined")
                leftovers.extend(idxs)
            else:
                lane_chosen("msearch", "packed")
                for i, out in zip(idxs, outs):
                    responses[i] = out

        # general path for whatever the packed lane couldn't serve:
        # plan-shape device batching, then solo
        groups: dict[Any, list[int]] = {}
        for i in leftovers:
            key = self._msearch_batch_key(*metas[i])
            groups.setdefault(key if key is not None else ("solo", i),
                              []).append(i)
        for key, idxs in groups.items():
            if (isinstance(key, tuple) and key and key[0] == "solo") \
                    or len(idxs) == 1:
                for i in idxs:
                    responses[i] = self._msearch_one(*metas[i])
                continue
            try:
                outs = self._search_batched([metas[i] for i in idxs])
            except Exception as e:  # noqa: BLE001 — per-item error contract
                outs = [_msearch_error(e)] * len(idxs)
            for i, out in zip(idxs, outs):
                responses[i] = out

        if raw:
            # the raw lane serializes here, so the HTTP layer's own
            # `rest.serialize` finds bytes and has nothing left to do
            # (the packed lane's items are bytes already; a dict came from
            # another lane or is an item's error)
            with tracing.span("rest.serialize", cpu=True):
                return b'{"responses":[' + b",".join(
                    r if isinstance(r, bytes) else json.dumps(r).encode()
                    for r in responses) + b']}'
        return {"responses": responses}

    def _msearch_one(self, index: str, body: dict) -> dict:
        try:
            return self.search(index, body)
        except Exception as e:  # noqa: BLE001 — per-item error contract
            return _msearch_error(e)

    def _msearch_batch_key(self, index: str, body: dict):
        """Group key for device batching, or None if the request needs the
        general path (sort/knn/... or an unparseable query). Requests with
        IDENTICAL agg trees batch together: the query phase runs once with
        Q rows and agg collect runs per row against device masks — the
        analytics-workload analog of the packed lane (BASELINE config #3)."""
        aggs = body.get("aggs") or body.get("aggregations")
        if any(k not in self._BATCHABLE_KEYS
               and k not in ("aggs", "aggregations", "knn", "rescore")
               for k in body):
            return None
        try:
            import json as _json
            knn = body.get("knn")
            if knn is not None:
                # batched exact kNN: one MXU matmul per shard serves the
                # whole group (per-query vectors vary; shape must not)
                if aggs is not None or body.get("rescore") is not None \
                        or knn.get("filter") is not None:
                    return None
                qv = knn.get("query_vector")
                if qv is None:
                    return None
                raw_np = knn.get("nprobe")
                return (index, int(body.get("size", 10)),
                        int(body.get("from", 0)), "knn", knn.get("field"),
                        int(knn.get("k", 10)),
                        knn.get("metric", "cosine"), len(qv),
                        int(raw_np) if raw_np is not None else None,
                        bool(knn.get("exact", False)),
                        str(knn.get("quantization") or ""))
            agg_key = None
            if aggs is not None:
                from .search.aggs.aggregators import has_top_hits, parse_aggs
                if has_top_hits(parse_aggs(aggs)):
                    return None     # top_hits needs per-row scores
                agg_key = _json.dumps(aggs, sort_keys=True)
            names = self._resolve(index)
            if not names:
                return None
            node = self._parse_cached(
                names[0], body.get("query") or {"match_all": {}})
            rescore_key = None
            rescore = body.get("rescore")
            if rescore is not None:
                # batched hybrid rescore: same plan + knobs, per-row vectors
                if isinstance(rescore, list):
                    if len(rescore) != 1:
                        return None
                    rescore = rescore[0]
                rs = rescore.get("query", rescore)
                rq = rs.get("rescore_query")
                if rq is None or body.get("sort") is not None:
                    return None
                rescore_key = (self._parse_cached(names[0], rq).plan_key(),
                               int(rescore.get("window_size", 0)),
                               rs.get("score_mode", "total"),
                               float(rs.get("query_weight", 1.0)),
                               float(rs.get("rescore_query_weight", 1.0)))
            return (index, int(body.get("size", 10)),
                    int(body.get("from", 0)), node.plan_key(), agg_key,
                    rescore_key)
        except Exception:  # noqa: BLE001
            return None

    # -- dashboard panels (search/aggs/panels.py) ---------------------------

    _PANEL_KEYS = frozenset({"size", "from", "query", "aggs", "aggregations"})

    def _panel_row(self, name: str, body: dict):
        """-> the body (its page already known to be `size: 0`, `from: 0`)
        as a row of the panel lane, or None where it is not one of the
        lane's three shapes. Read from the body and the mapping alone."""
        from .search.aggs import panels
        if not self._PANEL_KEYS.issuperset(body):
            return None
        try:
            aggs = parse_aggs(body.get("aggs") or body.get("aggregations"))
            node = self._parse_cached(
                name, body.get("query") or {"match_all": {}})
        except Exception:  # noqa: BLE001 — the general path reports it
            return None
        return panels.row_of(node, aggs)

    def _serve_panel(self, name: str, body: dict, row, tns0: int,
                     compiles0: int) -> dict | None:
        """One dashboard panel. It always runs the lane's programs; under
        QoS it also coalesces (serving/batcher.py): the first LEADS with
        the Q = 1 programs, panels of its shape that arrive meanwhile are
        drained as Q > 1 batches, and a follower whose wait ran out or
        whose leader left runs alone like a leader. None where the lane
        cannot serve the index's segments (`_search_panels`)."""
        from .common.metrics import current_profiler
        if self.qos.enabled() and current_profiler() is None:
            out, shared = self._batcher.coalesce(
                ("gen", "panels", name, *row.shape), row,
                lambda rows, t_taken: self._search_panels(name, rows,
                                                          t_taken),
                lead=lambda: self._panel_solo(name, body, row, tns0,
                                              compiles0))
            if shared:
                self._served_shared(name, body, tns0, compiles0,
                                    self._panel_lane())
            return out
        return self._panel_solo(name, body, row, tns0, compiles0)

    def _panel_pool(self):
        """The chips the panel lane runs on: the node's own pool, or every
        device of the process where none was carved out."""
        from .parallel.mesh import shared_pool
        return self.device_pool or shared_pool()

    def _panel_lane(self) -> str:
        """The lane's name in `lane_decisions`, by its form: the collective
        over the chips the node owns, or the same program on its one chip.
        Read from the pool alone; no setting chooses."""
        return "panels_mesh" if len(self._panel_pool().devices) > 1 \
            else "panels"

    def _panel_solo(self, name: str, body: dict, row, tns0: int,
                    compiles0: int) -> dict | None:
        """One panel alone (a leader, a follower whose wait ran out, or no
        coalescing at all): the lane's Q = 1 programs."""
        from .common.device_stats import lane_chosen
        outs = self._search_panels(name, [row], tns0)
        if outs is None:
            return None
        lane_chosen("serve", self._panel_lane())
        self._served_total(name, body, (tracing.now_ns() - tns0) / 1e6,
                           compiles0)
        return outs[0]

    def _search_panels(self, name: str, rows: list,
                       t0_ns: int) -> list[dict] | None:
        """Answer rows of one shape (`_panel_row`) from the panel lane's
        programs over the index's segments as they stand, in batches of
        `SearchBatcher.MAX_BATCH` at most: exact totals and bucket counts,
        merged and rendered by the aggregation framework. None where the
        segments are not the lane's (`panels.servable`: a column that is
        not i64, more distinct values than `terms` counts): the caller
        keeps the path it had. A program that raises is the members'
        error."""
        from .search.aggs import panels
        svc = self.indices[name]
        view = svc.panel_view(self._panel_pool())
        if view is None or not panels.servable(rows, view):
            return None
        panels.ensure_warm(view)
        outs: list[dict] = []
        step = self._batcher.MAX_BATCH
        for lo in range(0, len(rows), step):
            chunk = rows[lo:lo + step]
            totals, partials = panels.execute(chunk, view)
            render = tracing.span("aggs.render", rows=len(chunk), cpu=True)
            with render:
                took = (render.start_ns - t0_ns) // 1_000_000
                for qi, row in enumerate(chunk):
                    out = {"took": took, "timed_out": False,
                           "_shards": {"total": view.n_shards,
                                       "successful": view.n_shards,
                                       "failed": 0},
                           "hits": {"total": int(totals[qi]),
                                    "max_score": None, "hits": []}}
                    if row.agg is not None:
                        out["aggregations"] = render_aggs(
                            [row.agg], merge_shard_partials(
                                [row.agg], partials[qi]))
                    outs.append(out)
        # counted after the answers are whole, as the other fast lanes do
        self.meters["search"].mark(len(rows))
        svc.query_total += len(rows)
        svc.search_stats["panels"] = \
            svc.search_stats.get("panels", 0) + len(rows)
        svc.meters["search"].mark(len(rows))
        return outs

    def _search_batched(self, metas: list[tuple[str, dict]]) -> list[dict]:
        """Execute same-shaped requests (one `_msearch_batch_key`: an
        `_msearch` group, or the followers a coalescing leader drains) as
        one batch. An `_msearch` group of dashboard panels (`_panel_row`:
        `size: 0`, a range with a `date_histogram`, a `terms` under a
        one-term `match`, or a bare filtered count) runs the panel lane's
        compiled programs, as solo panels do (`_serve_panel`, which hands
        its followers' rows straight to `_search_panels`). Every other
        group runs one batched query phase per shard as eager operations
        shaped by the exact Q (knn, rescore, scored pages, other
        aggregation trees); per-row reduce + fetch mirrors the
        single-search flow."""
        t0 = time.perf_counter()
        index, first_body = metas[0]
        names = self._resolve(index)
        size = int(first_body.get("size", 10))
        from_ = int(first_body.get("from", 0))
        if len(names) == 1 and size == 0 and from_ == 0:
            t0_ns = tracing.now_ns()
            rows = [self._panel_row(names[0], b) for _, b in metas]
            if None not in rows:
                outs = self._search_panels(names[0], rows, t0_ns)
                if outs is not None:
                    return outs
        searchers: list[ShardSearcher] = []
        index_of: list[str] = []
        for n in names:
            for s in self.indices[n].searchers():
                searchers.append(s)
                index_of.append(n)
        knn = first_body.get("knn")
        if knn is not None:
            # batched exact kNN: one matmul per shard for the whole group
            qvs = [b["knn"]["query_vector"] for _, b in metas]
            knn_k = int(knn.get("k", 10))
            raw_np = knn.get("nprobe")
            results = [
                s.execute_knn(knn["field"], qvs, k=max(knn_k, size + from_),
                              metric=knn.get("metric", "cosine"),
                              nprobe=int(raw_np) if raw_np is not None
                              else None,
                              exact=bool(knn.get("exact", False)),
                              quantization=knn.get("quantization"))
                for s in searchers]
            size = min(size, max(knn_k - from_, 0))
            return self._batched_reduce(metas, searchers, index_of, results,
                                        size, from_, None, t0)

        queries = [b.get("query") or {"match_all": {}} for _, b in metas]
        rescore_spec0 = first_body.get("rescore")
        if isinstance(rescore_spec0, list):
            rescore_spec0 = rescore_spec0[0] if rescore_spec0 else None
        window = int(rescore_spec0.get("window_size", size)) \
            if rescore_spec0 else 0
        # parse once per index (shards share a MapperService), not per shard;
        # index-global stats keep this lane score-consistent with the packed
        # lane (same IDF everywhere)
        from .search.query_dsl import CollectionStats
        nodes_by_index = {}
        terms_by_field: dict[str, set] = {}
        for n in names:
            from .search.query_parser import merge_query_batch
            nodes_by_index[n] = merge_query_batch(
                [self._parse_cached(n, q) for q in queries])
            nodes_by_index[n].collect_terms(terms_by_field)
        global_stats = CollectionStats.from_segments(
            [seg for s in searchers for seg in s.segments], terms_by_field)

        # mesh-batched lane (ISSUE 8 satellite, ROADMAP item 1 follow-up):
        # a Q>1 plan-shaped batch over a single multi-shard index rides the
        # mesh's "replica" axis — the whole batch's query phase AND the
        # cross-shard merge run as ONE collective program with ONE device
        # fetch. Aggs/knn/rescore/count-only groups keep the fan-out below
        # (same ladder as the single-search coordinator).
        if (len(names) == 1 and len(searchers) > 1
                and rescore_spec0 is None and size + from_ > 0
                and not (first_body.get("aggs")
                         or first_body.get("aggregations"))):
            mesh_out = self._try_mesh(
                names[0], searchers, nodes_by_index[names[0]],
                global_stats, size=size, from_=from_,
                n_queries=len(queries))
            mesh_rows = mesh_out[0] if mesh_out is not None else None
            if mesh_rows is not None:
                outs = self._batched_reduce(metas, searchers, index_of,
                                            None, size, from_, None, t0,
                                            reduced_rows=mesh_rows)
                self.meters["search"].mark(len(metas))
                for n in names:
                    svc = self.indices[n]
                    svc.query_total += len(metas)
                    svc.search_stats["batched"] = \
                        svc.search_stats.get("batched", 0) + len(metas)
                    svc.meters["search"].mark(len(metas))
                return outs

        aggs_body = first_body.get("aggs") or first_body.get("aggregations")
        count_only = size + from_ == 0 and rescore_spec0 is None
        seg_masks: list | None = None
        if count_only or aggs_body is not None:
            # ONE match-mask program per segment serves totals (count-only
            # fast path) AND agg collect — never computed twice
            from .search.query_dsl import SegmentContext
            Q = len(queries)
            seg_masks = []
            for i, s in enumerate(searchers):
                for seg in s.segments:
                    if seg.n_docs == 0:
                        continue
                    ctx = SegmentContext(seg, Q, global_stats)
                    m = nodes_by_index[index_of[i]].match_mask(ctx) \
                        & seg.live[None, :]
                    seg_masks.append((i, seg, m))
        total_devs: list = []
        if count_only:
            # agg/count-only batch: SKIP scoring entirely. The dense [Q, N]
            # scoring pass cost the r5 agg bench ~99% of its time at 1M
            # docs. The per-segment totals stay ON DEVICE here and ride the
            # agg collect's single device_get below (one host sync for the
            # whole batch).
            total_devs = [(i, m.sum(axis=1)) for i, _seg, m in seg_masks]
            results = None
        else:
            results = [
                s.execute_query_phase(nodes_by_index[index_of[i]],
                                      size=max(size, window),
                                      from_=from_, n_queries=len(queries),
                                      global_stats=global_stats)
                for i, s in enumerate(searchers)]
        if rescore_spec0 is not None:
            specs = []
            for _, b in metas:
                rs = b.get("rescore")
                specs.append(rs[0] if isinstance(rs, list) else rs)
            results = [s.rescore_batch(r, specs)
                       for s, r in zip(searchers, results)]

        # identical agg trees across the batch (guaranteed by the group
        # key): the shared match-mask programs above gate per-row device
        # collect — the config #3 analytics fast lane
        agg_rendered: list[dict] | None = None
        totals_host: list = []
        if aggs_body is not None:
            from .search.aggs.aggregators import (collect_shard,
                                                  collect_shards_batched,
                                                  merge_shard_partials,
                                                  parse_aggs)
            from .search.aggs.aggregators import render as render_aggs
            agg_specs = parse_aggs(aggs_body)
            Q = len(queries)
            by_shard: dict[int, tuple[list, list]] = {}
            for i, seg, m in seg_masks:
                segs, ms = by_shard.setdefault(i, ([], []))
                segs.append(seg)
                ms.append(m)
            # leaf agg trees: ONE device program per (agg, segment) covers
            # every row, ONE device_get covers the whole batch (+ count-only
            # totals riding along)
            rows_by_shard, totals_host = collect_shards_batched(
                agg_specs, by_shard,
                extra_devs=[d for _, d in total_devs])
            agg_rendered = []
            if rows_by_shard is not None:
                for qi in range(Q):
                    partials = [rows[qi]
                                for rows in rows_by_shard.values()]
                    agg_rendered.append(render_aggs(
                        agg_specs,
                        merge_shard_partials(agg_specs, partials)))
            else:
                # general per-row path (sub-aggs, non-columnar fields, ...)
                for qi in range(Q):
                    partials = [collect_shard(
                        agg_specs, segs, [m[qi] for m in ms],
                        query_parser=searchers[i].parser)
                        for i, (segs, ms) in by_shard.items()]
                    agg_rendered.append(render_aggs(
                        agg_specs, merge_shard_partials(agg_specs,
                                                        partials)))
        elif total_devs:
            import jax
            totals_host = jax.device_get([d for _, d in total_devs])

        if results is None:
            # materialize the count-only QuerySearchResults from the fused
            # fetch's totals
            from .search.shard_searcher import QuerySearchResult
            import numpy as _np
            Q = len(queries)
            totals = {i: _np.zeros((Q,), _np.int64)
                      for i in range(len(searchers))}
            for (i, _d), hv in zip(total_devs, totals_host):
                totals[i] += _np.asarray(hv)
            results = [QuerySearchResult(
                shard_id=s.shard_id,
                doc_keys=_np.full((Q, 0), -1, _np.int64),
                scores=_np.full((Q, 0), _np.nan, _np.float32),
                sort_values=None, total_hits=totals[i],
                max_score=_np.full((Q,), _np.nan, _np.float32))
                for i, s in enumerate(searchers)]

        outs = self._batched_reduce(metas, searchers, index_of, results,
                                    size, from_, agg_rendered, t0)
        # count AFTER successful assembly — a raise above degrades the
        # batch to the solo path, which books its own query_total (the
        # packed lane documents the same convention)
        self.meters["search"].mark(len(metas))
        for n in names:
            svc = self.indices[n]
            svc.query_total += len(metas)
            svc.search_stats["batched"] = \
                svc.search_stats.get("batched", 0) + len(metas)
            svc.meters["search"].mark(len(metas))
        return outs

    def _batched_reduce(self, metas, searchers, index_of, results,
                        size, from_, agg_rendered, t0,
                        reduced_rows=None) -> list[dict]:
        took = int((time.perf_counter() - t0) * 1000)
        outs = []
        for qi, (_, body) in enumerate(metas):
            # the mesh-batched lane hands per-row ReducedDocs straight from
            # the device reduce — sort_docs (the host merge) is skipped
            reduced = reduced_rows[qi] if reduced_rows is not None \
                else controller.sort_docs(results, from_=from_, size=size,
                                          query_row=qi)
            src_filter = body.get("_source")
            fields_spec = body.get("fields")
            if isinstance(fields_spec, str):
                fields_spec = [fields_spec]
            hits = controller.fetch_and_merge(
                reduced, searchers,
                source_filter=(lambda s: _source_filter(s, src_filter))
                if src_filter is not None else None,
                fields_spec=fields_spec)
            for slot, h in enumerate(hits):
                h["_index"] = index_of[reduced.shard_order[slot]]
            out = {
                "took": took,
                "timed_out": False,
                "_shards": {"total": len(searchers),
                            "successful": len(searchers), "failed": 0},
                "hits": {"total": reduced.total_hits,
                         "max_score": None
                         if reduced.max_score != reduced.max_score
                         else reduced.max_score,
                         "hits": hits},
            }
            if agg_rendered is not None:
                out["aggregations"] = agg_rendered[qi]
            outs.append(out)
        return outs

    # -- scroll (cursored reads, ref §3.5 scroll/scan call stack) ----------

    def _scroll_start(self, index: str, body: dict, size: int,
                      keep_alive: str, scan: bool = False) -> dict:
        """Open a scroll context: PIN a point-in-time snapshot of every
        shard's segment set (frozen liveness), then advance with
        search_after cursors over the pinned searchers — O(depth) total,
        and concurrent writes/deletes/merges never change what the scroll
        sees (ref search/scan/ScanContext.java:55 pinning the reader,
        SearchService.java:316-330 context keep-alive)."""
        import threading

        names = self._resolve(index)
        if not names:
            raise IndexMissingException(index)
        alias_flt = self._alias_filters_by_index(index, names)
        if any(k in body for k in ("knn", "rescore", "search_after",
                                   "rank")):
            raise QueryParsingException(
                "scroll does not support knn/rescore/search_after/rank")
        from .search.sort import DOC, SCORE, SortSpec, parse_sort
        user_sort = parse_sort(body.get("sort"),
                               [self.indices[n].mappers for n in names])
        implicit = user_sort is None
        if scan:
            # scan: doc order, no scoring (ref search_type=scan +
            # search/scan/ScanContext) — first response carries only total
            user_sort = None
            implicit = True
            specs = [SortSpec(field=DOC, order="asc")]
        else:
            specs = list(user_sort) if user_sort else \
                [SortSpec(field=SCORE, order="desc")]
        if not any(sp.field == DOC for sp in specs):
            # _doc tiebreak makes the cursor a total order: batches never
            # repeat or skip docs with equal primary keys
            specs = specs + [SortSpec(field=DOC, order="asc")]

        # pin: share device arrays, freeze the liveness bitmap
        import dataclasses as _dc
        searchers: list[ShardSearcher] = []
        index_of: list[str] = []
        for n in names:
            svc = self.indices[n]
            for e in svc.shards:
                segs = [_dc.replace(seg, live_host=seg.live_host.copy(),
                                    live_count=seg.live_count)
                        for seg in e.segments]
                # shard ids unique ACROSS indices: the _doc cursor key
                # embeds them, and a collision would skip docs mid-scroll
                searchers.append(ShardSearcher(len(searchers), segs,
                                               svc.mappers))
                index_of.append(n)

        query = body.get("query", {"match_all": {}})
        from .search.query_dsl import CollectionStats
        from .search.query_parser import QueryParser, merge_query_batch
        nodes_by_index: dict[str, Any] = {}
        terms_by_field: dict[str, set] = {}
        for n in names:
            q_n = self._wrap_alias_query(query, alias_flt[n]) \
                if n in alias_flt else query
            parsed = QueryParser(self.indices[n].mappers).parse(q_n)
            parsed.collect_terms(terms_by_field)
            nodes_by_index[n] = merge_query_batch([parsed])
        stats = CollectionStats.from_segments(
            [seg for s in searchers for seg in s.segments], terms_by_field)

        with self._scroll_lock:
            self._reap_scrolls()
            self._scroll_seq += 1
            sid = f"scroll-{self._scroll_seq}"
            ctx = {"searchers": searchers, "index_of": index_of,
                   "nodes": nodes_by_index, "specs": specs, "stats": stats,
                   "cursor": None, "implicit_sort": implicit,
                   "source": body.get("_source"),
                   "fields": body.get("fields"),
                   "aggs": body.get("aggs") or body.get("aggregations"),
                   "expiry": time.monotonic() + _duration_secs(keep_alive),
                   "keep_alive": keep_alive, "lock": threading.Lock()}
            self._scrolls[sid] = ctx
        if scan:
            # the scan contract: the initial response has totals only;
            # docs start flowing on the first scroll call
            ctx["size"] = size
            out = self._scroll_batch(ctx, 0)
            ctx["size"] = size
        else:
            out = self._scroll_batch(ctx, size)
        out["_scroll_id"] = sid
        return out

    def scroll(self, scroll_id: str, keep_alive: str | None = None) -> dict:
        with self._scroll_lock:
            self._reap_scrolls()
            ctx = self._scrolls.get(scroll_id)
            if ctx is None:
                raise IndexMissingException(
                    f"scroll [{scroll_id}] expired or unknown")
            if keep_alive:
                ctx["keep_alive"] = keep_alive
            ctx["expiry"] = time.monotonic() \
                + _duration_secs(ctx["keep_alive"])
        out = self._scroll_batch(ctx, ctx.get("size", 10))
        out["_scroll_id"] = scroll_id
        return out

    def _scroll_batch(self, ctx: dict, size: int | None = None) -> dict:
        t0 = time.perf_counter()
        # per-context lock: two concurrent scrolls on the same id must not
        # read the same cursor and return duplicate batches
        with ctx["lock"]:
            if size is None:
                size = ctx.get("size", 10)
            ctx["size"] = size
            searchers = ctx["searchers"]
            agg_specs = None
            if ctx["cursor"] is None and ctx["aggs"]:
                agg_specs = parse_aggs(ctx["aggs"])
            results = [
                s.execute_query_phase(
                    ctx["nodes"][ctx["index_of"][i]], size=size,
                    sort=ctx["specs"], search_after=ctx["cursor"],
                    global_stats=ctx["stats"],
                    track_scores=False,   # the _score spec re-enables it
                    aggs=agg_specs)
                for i, s in enumerate(searchers)]
            reduced = controller.sort_docs(results, from_=0, size=size,
                                           sort=ctx["specs"])
            src_filter = ctx["source"]
            fields_spec = ctx.get("fields")
            if isinstance(fields_spec, str):
                fields_spec = [fields_spec]
            hits = controller.fetch_and_merge(
                reduced, searchers,
                source_filter=(lambda s: _source_filter(s, src_filter))
                if src_filter is not None else None,
                fields_spec=fields_spec)
            for slot, h in enumerate(hits):
                h["_index"] = ctx["index_of"][reduced.shard_order[slot]]
            if hits:
                ctx["cursor"] = hits[-1]["sort"]
            if ctx["implicit_sort"]:
                # default scroll is score-ordered; the synthetic sort keys
                # are cursor plumbing, not part of the user's response shape
                for h in hits:
                    h.pop("sort", None)
            resp: dict[str, Any] = {
                "took": int((time.perf_counter() - t0) * 1000),
                "timed_out": False,
                "_shards": {"total": len(searchers),
                            "successful": len(searchers), "failed": 0},
                "hits": {"total": reduced.total_hits,
                         "max_score": None
                         if reduced.max_score != reduced.max_score
                         else reduced.max_score,
                         "hits": hits},
            }
            if agg_specs:
                merged = merge_shard_partials(
                    agg_specs, [r.aggs for r in results if r.aggs])
                resp["aggregations"] = render_aggs(agg_specs, merged)
            return resp

    def clear_scroll(self, scroll_ids: list[str]) -> int:
        with self._scroll_lock:
            return sum(1 for sid in scroll_ids
                       if self._scrolls.pop(sid, None) is not None)

    def _reap_scrolls(self) -> None:
        # caller holds _scroll_lock
        now = time.monotonic()
        for sid in [s for s, c in self._scrolls.items() if c["expiry"] < now]:
            del self._scrolls[sid]

    # -- admin -------------------------------------------------------------

    def refresh(self, index: str = "_all") -> None:
        for n in self._resolve(index):
            self.indices[n].refresh()
            self._run_warmers(n)

    def _run_warmers(self, name: str) -> None:
        """Execute registered warmer searches against the FRESH searcher
        (ref indices/warmer/IndicesWarmer + IndexWarmersMetaData: warmers
        run on every new reader so caches/packed views are hot before the
        first real query). Best-effort: a broken warmer logs, never fails
        the refresh."""
        svc = self.indices.get(name)
        warmers = getattr(svc, "warmers", None)
        if not warmers:
            return
        for wname, spec in list(warmers.items()):
            body = dict(spec.get("source") or {})
            body.setdefault("size", 0)
            try:
                self.search(name, body, request_cache=False)
                svc.warmer_runs = getattr(svc, "warmer_runs", 0) + 1
            except Exception as e:  # noqa: BLE001
                logger.warning("warmer [%s] on [%s] failed: %s",
                               wname, name, e)

    def flush(self, index: str = "_all") -> None:
        for n in self._resolve(index):
            self.indices[n].flush()
            self._persist_index_meta(self.indices[n])

    def force_merge(self, index: str = "_all",
                    max_num_segments: int = 1) -> None:
        """ref the _optimize API (action/admin/indices/optimize)."""
        for n in self._resolve(index):
            self.indices[n].force_merge(max_num_segments)

    def put_mapping(self, index: str, type_name: str, mapping: dict) -> None:
        for n in self._resolve(index):
            self.indices[n].mappers.merge(type_name, mapping)
            self._persist_index_meta(self.indices[n])

    def put_template(self, name: str, body: dict) -> None:
        self.templates[name] = body
        self._persist_templates()

    def _persist_templates(self) -> None:
        import json
        path = os.path.join(self.data_path, "_templates.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.templates, f)
        os.replace(tmp, path)

    def _persist_search_templates(self) -> None:
        import json
        path = os.path.join(self.data_path, "_search_templates.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.search_templates, f)
        os.replace(tmp, path)

    def delete_by_query(self, index: str, body: dict) -> int:
        """Delete every doc matching the query (ref the 1.x _query API,
        action/deletebyquery/) — scroll the match set, bulk-delete by id."""
        query_body = {"query": body.get("query", body or {"match_all": {}}),
                      "size": 1000, "_source": False}
        out = self.search(index, query_body, scroll="1m")
        sid = out.get("_scroll_id")
        deleted = 0
        try:
            while True:
                hits = out["hits"]["hits"]
                if not hits:
                    break
                for h in hits:
                    try:
                        self.delete_doc(h["_index"], h["_id"], sync=False)
                        deleted += 1
                    except Exception:  # noqa: BLE001 — already gone
                        pass
                out = self.scroll(sid)
        finally:
            if sid:
                self.clear_scroll([sid])
        for n in self._resolve(index):
            self.indices[n].sync_translogs()
        return deleted

    # -- index maintenance scheduler: LIVE dynamic settings ----------------

    def run_index_maintenance(self) -> dict:
        """One pass of the per-index schedulers that the reference runs as
        background services, each reading its threshold from LIVE settings
        so `_settings` updates apply to a running index immediately:
          * index.refresh_interval  — periodic NRT refresh
            (ref index/shard/IndexShard refresh scheduler; default here is
            manual-refresh to keep NRT tests deterministic)
          * index.translog.flush_threshold_ops — flush when the translog
            accumulates that many ops (ref index/translog/
            TranslogService.java:105-115)
        Returns {"refreshed": n, "flushed": n}."""
        now = time.monotonic()
        refreshed = flushed = 0
        for name, svc in list(self.indices.items()):
            s = svc.settings
            ri = s.get("index.refresh_interval", s.get("refresh_interval"))
            if ri not in (None, "", "-1", -1):
                from .mapping.mapper import parse_ttl_ms
                try:
                    interval = parse_ttl_ms(ri) / 1000.0
                except Exception:  # noqa: BLE001
                    interval = None
                last = getattr(svc, "_last_sched_refresh", 0.0)
                if interval is not None and now - last >= interval:
                    svc._last_sched_refresh = now
                    try:
                        svc.refresh()
                        self._run_warmers(name)
                        refreshed += 1
                    except Exception:  # noqa: BLE001 — keep the scheduler
                        pass
            fto = s.get("index.translog.flush_threshold_ops",
                        s.get("translog.flush_threshold_ops"))
            if fto not in (None, ""):
                try:
                    fto = int(fto)
                except ValueError:
                    continue
                for e in svc.shards:
                    if e.translog.ops_since_commit >= fto > 0:
                        try:
                            e.flush()
                            flushed += 1
                        except Exception:  # noqa: BLE001
                            pass
        return {"refreshed": refreshed, "flushed": flushed}

    def _maintenance_loop(self) -> None:
        while not self._maint_stop.wait(0.25):
            try:
                self.run_index_maintenance()
            except Exception:  # noqa: BLE001 — scheduler must survive
                pass

    # -- TTL purger (ref indices/ttl/IndicesTTLService.java:66) -----------

    def purge_expired_docs(self, now_ms: int | None = None) -> int:
        """Sweep every shard for docs whose _ttl expiry lies in the past
        and delete them (the reference's 60s PurgerThread does exactly
        this with a bulk request)."""
        import numpy as _np
        now = int(time.time() * 1000) if now_ms is None else int(now_ms)
        deleted = 0
        for name, svc in list(self.indices.items()):
            expired: list[tuple[str, Any]] = []
            for e in svc.shards:
                with e._lock:
                    segments = list(e.segments)
                for seg in segments:
                    nc = seg.numerics.get("_ttl_expiry")
                    if nc is None:
                        continue
                    vals = _np.asarray(nc.vals)
                    miss = _np.asarray(nc.missing)
                    hits = _np.flatnonzero(~miss[:seg.n_docs]
                                           & (vals[:seg.n_docs] < now))
                    for local in hits:
                        local = int(local)
                        if not seg.live_host[local] \
                                or seg.types[local].startswith("__"):
                            continue
                        expired.append((seg.ids[local],
                                        seg.routings[local]))
            for doc_id, routing in expired:
                try:
                    svc.delete_doc(doc_id, routing=routing)
                    deleted += 1
                except Exception:  # noqa: BLE001 — already re-deleted/raced
                    pass
            if expired:
                svc.refresh()
        return deleted

    def start_ttl_purger(self, interval_s: float = 60.0) -> None:
        """Background purger thread (off by default; tests drive
        purge_expired_docs directly)."""
        import threading as _th
        if getattr(self, "_ttl_thread", None) is not None:
            return
        self._ttl_stop = _th.Event()

        def loop():
            while not self._ttl_stop.wait(interval_s):
                try:
                    self.purge_expired_docs()
                except Exception:  # noqa: BLE001 — keep the purger alive
                    pass
        self._ttl_thread = _th.Thread(target=loop, daemon=True,
                                      name="es[ttl_purger]")
        self._ttl_thread.start()

    # -- IndexingMemoryController (ref indices/memory/
    #    IndexingMemoryController.java:60) ---------------------------------

    def check_indexing_memory(self) -> int:
        """One shared indexing-buffer byte budget across ALL shards
        (`indices.memory.index_buffer_size`); over budget, the largest
        buffers refresh until back under. Returns refreshes triggered."""
        raw = self.settings.get("indices.memory.index_buffer_size",
                                "128mb")
        try:
            budget = _parse_bytes(str(raw))
        except ValueError:
            budget = 128 << 20
        engines = [e for svc in self.indices.values() for e in svc.shards]
        total = sum(e._buffer_bytes for e in engines)
        refreshed = 0
        while total > budget:
            biggest = max(engines, key=lambda e: e._buffer_bytes)
            if biggest._buffer_bytes <= 0:
                break
            total -= biggest._buffer_bytes
            biggest.refresh()
            refreshed += 1
        return refreshed

    def cluster_health(self, level: str = "cluster") -> dict:
        shards = sum(s.n_shards for s in self.indices.values())
        unassigned = sum(s.n_shards * s.n_replicas
                         for s in self.indices.values())
        per_index = {}
        if level in ("indices", "shards"):
            for n, s in self.indices.items():
                ih = {"status": "yellow" if s.n_replicas else "green",
                      "number_of_shards": s.n_shards,
                      "number_of_replicas": s.n_replicas,
                      "active_primary_shards": s.n_shards,
                      "active_shards": s.n_shards,
                      "relocating_shards": 0, "initializing_shards": 0,
                      "unassigned_shards": s.n_shards * s.n_replicas}
                if level == "shards":
                    ih["shards"] = {
                        str(i): {"status": ih["status"],
                                 "primary_active": True,
                                 "active_shards": 1,
                                 "relocating_shards": 0,
                                 "initializing_shards": 0,
                                 "unassigned_shards": s.n_replicas}
                        for i in range(s.n_shards)}
                per_index[n] = ih
        return {** ({"indices": per_index}
                    if level in ("indices", "shards") else {}),
            "cluster_name": self.cluster_name,
            "status": "yellow" if unassigned else "green",
            "timed_out": False,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "active_primary_shards": shards,
            "active_shards": shards,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": sum(
                s.n_shards * s.n_replicas for s in self.indices.values()),
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number": 100.0,
        }

    def stats(self) -> dict:
        return {"indices": {n: s.stats() for n, s in self.indices.items()},
                "breakers": self.breakers.stats(),
                "caches": self.caches.stats(),
                "search_batcher": self._batcher.stats()}

    # -- telemetry (the /_metrics exposition + stats-history sampler) ------

    def device_stats_payload(self, top_n: int = 50) -> dict:
        """`GET /_nodes/device_stats` (ISSUE 16): the per-program XLA
        registry (compile ms, invocations, cumulative dispatch time, lazy
        flops/bytes-accessed cost — None-safe on CPU), per-device HBM
        stats with the process high-water mark, and the global
        lane-decision counters. Cost analysis is forced HERE (a scrape-
        time re-lower), never on the dispatch path."""
        from .common import device_stats
        return {
            "programs": device_stats.registry_snapshot(
                top_n=top_n, with_cost=True),
            "hbm": device_stats.hbm_poll(),
            "lane_decisions": device_stats.lane_decisions_snapshot(),
        }

    def metric_sections(self) -> dict:
        """Every stats registry of this node as OpenMetrics walk input:
        {section: (label_name | None, payload)}. A NEW stats source joins
        the `/_metrics` scrape (and the strict-parser tripwire test) by
        adding one entry here — labeled registries (pools, breakers,
        timers, indices) pick up new entries automatically."""
        from .common import device_stats, monitor
        from .common.metrics import (device_events_snapshot,
                                     packed_batches_snapshot,
                                     packed_filter_streams_snapshot,
                                     packed_gather_snapshot,
                                     packed_render_snapshot,
                                     transfer_snapshot)
        batcher = self._batcher.stats()
        occupancy = batcher.pop("occupancy", {})
        per_index = {}
        for n, svc in self.indices.items():
            seg = [e.segment_stats() for e in svc.shards]
            rc = self.caches.request_cache.index_stats(n)
            per_index[n] = {
                "docs": svc.doc_count(),
                "store_size_in_bytes": sum(s["memory_in_bytes"]
                                           for s in seg),
                "segments": sum(s["count"] for s in seg),
                "search_total": svc.query_total,
                "indexing_total": svc.indexing_stats["index_total"],
                "delete_total": svc.indexing_stats["delete_total"],
                "request_cache_hits_total": svc.request_cache_hits,
                "request_cache_misses_total": svc.request_cache_misses,
                "request_cache_memory_in_bytes": rc["bytes"],
                "request_cache_evictions_total": rc["evictions"],
                "search_rate_1m": svc.meters["search"].rate(60),
                "indexing_rate_1m": svc.meters["indexing"].rate(60),
            }
        compiles, compile_ms = device_events_snapshot()
        os_st = monitor.os_stats()
        proc = monitor.process_stats()
        load = os_st.get("load_average") or [0.0]
        # device execution-path counters summed across indices: how many
        # per-segment programs ran vs how many segment-stacked ones (the
        # stacked dense lane replaces G dispatches + G fetches with 1 + 1)
        from .common.metrics import shard_fetch_histogram
        path_totals: dict[str, int] = {}
        for svc in self.indices.values():
            for pk, pv in svc.search_stats.items():
                path_totals[pk] = path_totals.get(pk, 0) + pv
        from .common.metrics import (bulk_docs_histogram,
                                     bulk_ingest_snapshot, host_merge_count,
                                     peak_score_matrix_bytes)
        from .script.jax_compile import script_compiles_snapshot
        from .search.percolate_exec import percolate_stats_snapshot
        from .serving.qos import hedge_snapshot
        _perc_raw = percolate_stats_snapshot()
        _perc_stats = {
            "dispatches": {ln: _perc_raw[ln]
                           for ln in ("dense", "loop", "mesh")},
            "docs": _perc_raw["docs"],
            "matrix_cells": _perc_raw["matrix_cells"],
            "residual_queries": _perc_raw["residual_queries"],
        }
        qos_stats = self.qos.stats()
        qos_by_class = qos_stats.pop("by_class")
        search_exec = {
            "segment_dispatches_total":
                path_totals.get("segment_dispatches", 0),
            "stacked_dispatches_total":
                path_totals.get("stacked_dispatches", 0),
            "stacked_queries_total": path_totals.get("stacked", 0),
            # streaming blockwise dense lane (ISSUE 8): executions that ran
            # the tree per doc block under a running on-device top-k, plus
            # the process-peak score-matrix residency a dense query phase
            # materialized (O(Q×block) blockwise vs O(Q×n_pad) full)
            "blockwise_dispatches_total":
                path_totals.get("blockwise_dispatches", 0),
            "peak_score_matrix_bytes": peak_score_matrix_bytes(),
            # mesh-sharded lane (ISSUE 6): whole-index collective programs
            # vs per-shard stacked/segment dispatches, plus how many
            # host-side cross-shard merges still ran (fan-out path)
            "mesh_dispatches_total": path_totals.get("mesh_dispatches", 0),
            "mesh_queries_total": path_totals.get("mesh", 0),
            # aggs + IVF kNN through the mesh program (ISSUE 11): how
            # much of each workload rides the collective lane vs falls
            # down the ladder to the fan-out
            "mesh_agg_dispatches_total":
                path_totals.get("mesh_agg_dispatches", 0),
            "mesh_agg_fallbacks_total":
                path_totals.get("mesh_agg_fallbacks", 0),
            # sorted queries through the dense lanes (ISSUE 17): encoded
            # sort keys ranked on device by the per-shard stacked program
            # vs the whole-index mesh collective — bodies that decline
            # the encoding still ride the loop and show up in the lane
            # decision family below, not here
            "stacked_sorted_queries_total":
                path_totals.get("stacked_sorted", 0),
            "mesh_sorted_dispatches_total":
                path_totals.get("mesh_sorted_dispatches", 0),
            "mesh_ann_dispatches_total":
                path_totals.get("mesh_ann_dispatches", 0),
            "mesh_ann_fallbacks_total":
                path_totals.get("mesh_ann_fallbacks", 0),
            "host_merges_total": host_merge_count(),
            # IVF-clustered ANN lane (ISSUE 10): segment executions that
            # routed through the centroid->cluster-scan kernel vs declined
            # builds that fell back to the exact matmul
            "ann_dispatches_total": path_totals.get("ann_dispatches", 0),
            "ann_fallbacks_total": path_totals.get("ann_fallbacks", 0),
            # quantized ANN tier (ISSUE 12): scans served on int8/PQ codes
            # (the per-mode split rides the labeled search_ann_quantized
            # section below) vs declines back to the f32 IVF scan
            "ann_quantized_fallbacks_total":
                path_totals.get("ann_quantized_fallbacks", 0),
            "sparse_queries_total": path_totals.get("sparse", 0),
            "dense_queries_total": path_totals.get("dense", 0),
            "packed_queries_total": path_totals.get("packed", 0),
        }
        return {
            "threadpool": ("pool", self.thread_pool.stats()),
            "breaker": ("breaker", self.breakers.stats()),
            "search_phase": ("phase", self.phase_timers.stats()),
            "timer": ("timer", self.metrics.stats()),
            "search_batcher": (None, batcher),
            "batch_occupancy": ("size",
                                {str(k): {"count": v}
                                 for k, v in occupancy.items()}),
            "index": ("index", per_index),
            # the cache subsystem: one sample set per tier (request /
            # query_plan / fielddata / registered extras), uniform leaves
            "cache": ("cache", self.caches.stats()),
            # stacked-vs-segment dispatch counters (ISSUE 4) plus a
            # fetches-per-shard-query histogram: bucket n = a shard query
            # phase that needed n device round-trips (stacked lane: 1)
            "search": (None, search_exec),
            # quantized-scan adoption split by mode (ISSUE 12):
            # es_search_ann_quantized_dispatches_total{mode="int8"|"pq"}
            "search_ann_quantized": ("mode", {
                "int8": {"dispatches_total":
                         path_totals.get("ann_quantized_int8", 0)},
                "pq": {"dispatches_total":
                       path_totals.get("ann_quantized_pq", 0)}}),
            "search_fetches": ("fetches_per_query",
                               {str(n): {"count": c}
                                for n, c in sorted(
                                    shard_fetch_histogram().items())}),
            # reverse-search lane adoption (ISSUE 18):
            # es_search_percolate_dispatches_total{lane=} — how many
            # percolate dispatches the dense doc×query matrix carried vs
            # the per-doc loop vs the mesh rung
            "search_percolate": ("lane", {
                lane: {"dispatches_total": n}
                for lane, n in _perc_stats["dispatches"].items()}),
            "percolate": (None, {
                "docs_total": _perc_stats["docs"],
                "matrix_cells_total": _perc_stats["matrix_cells"],
                "residual_queries_total": _perc_stats["residual_queries"]}),
            # expression->XLA script compiler (ISSUE 18):
            # es_script_compiles_total{target=} counts TRUE builds only —
            # cached template re-use with different params must not bump it
            "script": ("target", {
                t: {"compiles_total": n}
                for t, n in script_compiles_snapshot().items()} or {
                "function_score": {"compiles_total": 0}}),
            # bulk-ingest lane (ISSUE 7): vectorized vs per-doc-fallback
            # request/doc counters + ingest docs/s, and a docs-per-bulk
            # pow2 histogram (how much batching clients actually send)
            "indexing": (None, {**bulk_ingest_snapshot(),
                                "ingest_docs_per_sec":
                                    self.meters["indexing"].rate(60)}),
            "bulk_docs": ("docs_per_bulk",
                          {str(n): {"count": c}
                           for n, c in sorted(
                               bulk_docs_histogram().items())}),
            # serving-QoS (ISSUE 9): per-class admission/shed counters +
            # the pressure/EWMA gauges, and hedged-read outcomes
            # (es_qos_shed_total{class=}, es_search_hedged_total{outcome=})
            "qos": ("class", qos_by_class),
            "qos_node": (None, qos_stats),
            "search_hedged": ("outcome",
                              {o: {"total": c}
                               for o, c in hedge_snapshot().items()}),
            # watcher alerting tier (ISSUE 20): evaluation/fire/throttle
            # counters + per-watch last-fire gauges
            # (es_watcher_watch_*{watch=}); zeros when watcher.enable is
            # false so the scrape shape stays stable
            "watcher": (None, self.watcher_service.metric_totals()
                        if getattr(self, "watcher_service", None) else
                        {"evaluations_total": 0, "fires_total": 0,
                         "throttled_total": 0, "errors_total": 0,
                         "percolate_rides_total": 0,
                         "alerts_indexed_total": 0,
                         "retention_deletes_total": 0, "watches": 0}),
            "watcher_watch": ("watch",
                              self.watcher_service.metric_per_watch()
                              if getattr(self, "watcher_service", None)
                              else {}),
            "jit": (None, {"compiles": compiles,
                           "compile_time_in_millis": round(compile_ms, 3)}),
            # per-program-site XLA accounting (ISSUE 16): invocations,
            # cumulative dispatch time, attributed compiles per site —
            # es_xla_program_*{program=}; full per-plan-key detail + cost
            # analysis live on GET /_nodes/device_stats
            "xla_program": ("program", device_stats.program_metrics()),
            # per-device HBM gauges (zeros + supported=False on CPU) —
            # the high-water mark is the 100M-vectors budget number
            "device_hbm": ("device", {
                ident: {k2: v2 for k2, v2 in st.items()
                        if k2 != "supported"}
                for ident, st in device_stats.hbm_poll().items()}),
            # the lane-decision counter family (ISSUE 16):
            # es_search_lane_decisions_total{lane=,reason=} — one label
            # pair per ladder decision; the old *_fallbacks/_errors
            # counters above stay as aliases
            "search_lane": (("lane", "reason"),
                            device_stats.lane_decision_metrics()),
            "transfer": (None, transfer_snapshot()),
            # es_packed_gather_dispatches_total{form=}: packed dispatches
            # by how the program copied its slots (blocked | sliced)
            "packed_gather": ("form", packed_gather_snapshot()),
            # es_packed_render_hits_total{form=}: hits the packed lane
            # rendered, by how (vector | patched | dict)
            "packed_render": ("form", packed_render_snapshot()),
            # es_packed_batches_total{program=}: packed batches by the
            # program that answered them (plain | filtered)
            "packed_batches": ("program", packed_batches_snapshot()),
            # es_packed_filter_streams_total{state=}: the rank streams that
            # filtered packed batches used, by whether made for it or reused
            "packed_filter_streams": ("state",
                                      packed_filter_streams_snapshot()),
            "tasks": (None, self.tasks.stats()),
            # span tracer: started/retained/sampled-out trace counters,
            # ring-eviction + span-cap drop counters, live gauges
            "tracing": (None, self.tracer.stats()),
            # the always-on span aggregate (common/tracing.py): count,
            # seconds, self seconds and max by span name, process-wide —
            # es_span_*{span=}; and the device-gap ledger: host-seen idle
            # time of the device by what the dispatching thread did in it —
            # es_device_gap_seconds_total{during=} beside the in-flight
            # union es_device_flight_seconds_total
            "span": ("span", tracing.AGGREGATE.stats()),
            "device_gap": ("during", tracing.GAPS.gap_stats()),
            "device_flight": (None, tracing.GAPS.flight_stats()),
            "rate": ("op", {n: m.stats() for n, m in self.meters.items()}),
            "process": (None, {
                "resident_bytes": proc.get("mem", {})
                .get("resident_in_bytes", 0),
                "threads": proc.get("threads", 0),
                "open_file_descriptors":
                    proc.get("open_file_descriptors", 0)}),
            "os": (None, {"load_1m": load[0],
                          "cpu_percent": os_st["cpu"]["percent"],
                          "mem_used_bytes": os_st.get("mem", {})
                          .get("used_in_bytes", 0)}),
        }

    def _sampler_snapshot(self) -> dict:
        """Flat gauge snapshot for the stats-history ring: the signals an
        incident inspection reaches for first (queue pressure, rejection,
        device-memory headroom, rates, batch coalescing, host health)."""
        from .common import device_stats, monitor
        from .common.metrics import bulk_ingest_snapshot, device_events_snapshot
        _bulk_snap = bulk_ingest_snapshot()
        _hbm = device_stats.hbm_poll()
        _hbm_in_use = sum(v["bytes_in_use"] for v in _hbm.values())
        _hbm_peak = max((v["high_water_bytes"] for v in _hbm.values()),
                        default=0)
        pool = self.thread_pool.stats().get("search", {})
        br = self.breakers.stats()
        batcher = self._batcher.stats()
        os_st = monitor.os_stats()
        load = os_st.get("load_average") or [0.0]
        out = {
            "heap_used_bytes": monitor._rss(),
            "threads": monitor.process_stats().get("threads", 0),
            "load_1m": load[0],
            "cpu_percent": os_st["cpu"]["percent"],
            "search_rate_1m": self.meters["search"].rate(60),
            "indexing_rate_1m": self.meters["indexing"].rate(60),
            "get_rate_1m": self.meters["get"].rate(60),
            # ingest docs/s + batch-lane adoption (vectorized vs fallback
            # docs) ride the 1-hour history ring: an ingest-rate incident
            # inspection sees both the rate and WHICH lane carried it
            "ingest_docs_per_sec": self.meters["indexing"].rate(60),
            "bulk_vectorized_docs_total":
                _bulk_snap["vectorized_docs_total"],
            "bulk_fallback_docs_total": _bulk_snap["fallback_docs_total"],
            "pool_search_queue": pool.get("queue", 0),
            "pool_search_active": pool.get("active", 0),
            "pool_search_rejected_total": pool.get("rejected", 0),
            "batcher_batches_total": batcher["batches"],
            "batcher_batched_requests_total": batcher["batched_requests"],
            "docs": sum(s.doc_count() for s in self.indices.values()),
            "tasks_running": self.tasks.stats()["running"],
            "jit_compiles_total": device_events_snapshot()[0],
            # per-device HBM residency (ISSUE 16): bytes_in_use tracks the
            # live working set, hbm_peak the process high-water — the
            # ring answers "what did device memory look like at 14:05"
            "hbm_bytes_in_use": _hbm_in_use,
            "hbm_peak_bytes": _hbm_peak,
            "request_cache_memory_bytes":
                self.caches.request_cache.cache.memory_bytes,
            "request_cache_hits_total": self.caches.request_cache.cache.hits,
            "fielddata_cache_memory_bytes":
                self.caches.fielddata.cache.memory_bytes,
            "segment_stack_cache_memory_bytes":
                self.caches.segment_stacks.cache.memory_bytes,
            "mesh_stack_cache_memory_bytes":
                self.caches.mesh_stacks.cache.memory_bytes,
            # mesh vector stacks (ISSUE 11) + mesh agg/ANN lane adoption:
            # an incident inspection sees whether agg/kNN traffic rides
            # the collective lane or fell down the ladder
            "mesh_vector_stack_cache_memory_bytes":
                self.caches.mesh_vector_stacks.cache.memory_bytes,
            # vector-serving memory + lane adoption (ISSUE 10): IVF
            # centroid/CSR residency and how much kNN traffic the ANN
            # lane carried
            "ann_index_cache_memory_bytes":
                self.caches.ann_indexes.cache.memory_bytes,
            # quantized tier residency split (ISSUE 12): codes at their
            # true 1/4-1/32 bytes, codebooks separately — the incident
            # view of what the quantized stack actually costs
            "ann_quant_cache_memory_bytes":
                self.caches.ann_indexes.quant.memory_bytes,
            "ann_quant_code_bytes":
                max(self.caches.ann_indexes.quant_code_bytes, 0),
            "ann_quant_codebook_bytes":
                max(self.caches.ann_indexes.quant_book_bytes, 0),
            # registered-query corpus residency (ISSUE 18): what the
            # reverse-search registry costs in host bytes right now
            "percolator_registry_cache_memory_bytes":
                self.caches.percolator_registry.cache.memory_bytes,
        }
        mesh_totals = {"mesh_agg_dispatches": 0, "mesh_ann_dispatches": 0}
        for svc in self.indices.values():
            for mk in mesh_totals:
                mesh_totals[mk] += svc.search_stats.get(mk, 0)
        out["mesh_agg_dispatches_total"] = mesh_totals["mesh_agg_dispatches"]
        out["mesh_ann_dispatches_total"] = mesh_totals["mesh_ann_dispatches"]
        from .common.metrics import peak_score_matrix_bytes
        out["peak_score_matrix_bytes"] = peak_score_matrix_bytes()
        # serving-QoS gauges (ISSUE 9): queue depth, shed/hedge rates —
        # the signals a tail-latency incident inspection reaches for
        from .serving.qos import hedge_rate, hedge_snapshot
        qos = self.qos.stats()
        out["qos_pressure"] = qos["pressure"]
        out["qos_queue_depth"] = pool.get("queue", 0)
        out["qos_shed_rate_1m"] = qos["shed_rate_1m"]
        out["qos_shed_total"] = sum(c["shed_total"]
                                    for c in qos["by_class"].values())
        out["qos_degraded"] = qos["degraded"]
        out["hedge_rate_1m"] = hedge_rate(60)
        out["hedged_fired_total"] = hedge_snapshot()["fired"]
        # peer-recovery stream counters (ISSUE 15): bytes moved and
        # throttle back-pressure ride the history ring so a rebalance
        # wave's cost is visible next to the latency gauges it protects
        from .cluster.recovery import snapshot as recovery_snapshot
        rec = recovery_snapshot()
        out["recovery_bytes_total"] = rec["bytes_total"]
        out["recovery_throttle_waits_total"] = rec["throttle_waits_total"]
        bst = batcher
        out["batcher_stranded_total"] = bst["stranded_total"]
        out["batcher_wait_timeouts_total"] = bst["wait_timeouts_total"]
        # pod-plane health (ISSUE 20 satellite of ISSUE 19): exec-lock
        # contention, per-class transport latency EWMAs (dcn always
        # present — a pod watch must see 0.0, not a missing field) and
        # the process-wide pod reduce dispatch totals join the ring so
        # watches over `.monitoring-es-*` can alert on pod health
        from .parallel.mesh_exec import exec_lock_stats
        els = exec_lock_stats()
        out["exec_lock_waits"] = (els.get("shared_waits", 0)
                                  + els.get("pool_waits", 0))
        out["exec_lock_shared_waits"] = els.get("shared_waits", 0)
        out["exec_lock_pool_waits"] = els.get("pool_waits", 0)
        from .serving.qos import transport_latency_snapshot
        tlat = transport_latency_snapshot()
        for cls in sorted(set(tlat) | {"dcn"}):
            out[f"transport_latency_ewma_ms_{cls}"] = \
                tlat.get(cls, {}).get("ewma_ms", 0.0)
        from .cluster.host_reduce import pod_reduce_snapshot
        out.update(pod_reduce_snapshot())
        tr = self.tracer.stats()
        out["tracing_active_traces"] = tr["active_traces"]
        out["tracing_dropped_total"] = tr["dropped_traces_total"]
        for name, b in br.items():
            out[f"breaker_{name}_used_bytes"] = b["estimated_size_in_bytes"]
        return out

    def close(self) -> None:
        if not self.lifecycle.move_to_closed():
            return                      # idempotent double-close
        self.watcher.stop()
        if getattr(self, "watcher_service", None) is not None:
            self.watcher_service.close()  # joins the scheduler thread
        if getattr(self, "monitoring", None) is not None:
            self.monitoring.close()     # joins the collector thread
        if getattr(self, "sampler", None) is not None:
            self.sampler.stop()
        if getattr(self, "_maint_stop", None) is not None:
            self._maint_stop.set()
        if getattr(self, "_ttl_stop", None) is not None:
            self._ttl_stop.set()
        for svc in self.indices.values():
            svc.close()
        self.caches.close()     # releases request-breaker charges
        self.thread_pool.shutdown()
        try:
            import fcntl
            fcntl.flock(self._node_lock, fcntl.LOCK_UN)
            self._node_lock.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------

class _SearchPlan(NamedTuple):
    """What `_search_plan` decided for one `_search` (node.py)."""
    body: dict
    size: int
    from_: int
    cached: dict | None = None      # the request cache's answer
    names: list | None = None       # None: a scroll plans for itself
    sort: Any = None
    alias_flt: dict | None = None
    cache_key: tuple | None = None
    spec: tuple | None = None       # the packed lane's, or None
    panel: Any = None               # panels.PanelRow of a dashboard panel


class _ShardJob:
    """Claim-once shard execution for the concurrent query fan-out: a
    search-pool worker runs the job if it picks it up first, otherwise the
    coordinator steals it and runs it inline (join() claims before
    waiting). Because the coordinator can always execute every job itself,
    the fan-out stays deadlock-free even when the coordinators themselves
    occupy the same bounded pool."""

    __slots__ = ("fn", "done", "result", "error", "_claim")

    def __init__(self, fn):
        self.fn = fn
        self.done = threading.Event()
        self.result = None
        self.error = None
        self._claim = threading.Lock()

    def run(self) -> None:
        if not self._claim.acquire(blocking=False):
            return                          # someone else owns it
        try:
            self.result = self.fn()
        except BaseException as e:  # noqa: BLE001 — surfaced via .error
            self.error = e
        finally:
            self.done.set()

    def join(self) -> None:
        self.run()                          # steal if still queued
        self.done.wait()


def _empty_shard_result(shard_id: int, sort=None):
    """Placeholder result for a failed shard: keeps the reduce's
    result-per-searcher alignment while contributing zero hits."""
    import numpy as np

    from .search.shard_searcher import QuerySearchResult
    sv = None
    if sort is not None:
        sv = np.empty((1, 1), dtype=object)
        sv[0, 0] = None
    return QuerySearchResult(
        shard_id=shard_id,
        doc_keys=np.full((1, 1), -1, np.int64),
        scores=np.full((1, 1), np.nan, np.float32),
        sort_values=sv,
        total_hits=np.zeros((1,), np.int64),
        max_score=np.full((1,), np.nan, np.float32))


def _maybe_shard_profile(prof, index: str, shard_id: int):
    """prof.shard(...) when profiling, else a no-op context."""
    import contextlib
    if prof is None:
        return contextlib.nullcontext()
    return prof.shard(index, shard_id)


def _is_mlt_entry(k, v) -> bool:
    """True only for MLT QUERY nodes — a field literally named 'mlt' in a
    match/term leaf must not be hijacked (code review r4)."""
    return k in ("more_like_this", "mlt") and isinstance(v, dict) \
        and ({"like_text", "like", "docs", "ids", "fields"} & v.keys())


def _msearch_error(e: Exception) -> dict:
    """One failed `_msearch` item, in the reference's Name[detail]
    rendering (ref MultiSearchResponse.Item failure messages)."""
    from .rest.http_server import _status_of
    return {"error": f"{type(e).__name__}[{e}]", "status": _status_of(e)}


def _contains_mlt(q) -> bool:
    if isinstance(q, dict):
        return any(_is_mlt_entry(k, v) or _contains_mlt(v)
                   for k, v in q.items())
    if isinstance(q, list):
        return any(_contains_mlt(x) for x in q)
    return False


def _mesh_rows(keys, shard_of, scores, totals, mxs, *, n_queries: int,
               size: int, from_: int):
    """Per-row ReducedDocs from a mesh program's fetched outputs. Totals/
    max arrive PER SHARD ([S, Q]): int totals sum exactly, max over finite
    per-shard row-maxes equals the fan-out's global max bit-for-bit."""
    import math as _math

    import numpy as np

    from .search.controller import ReducedDocs
    window = slice(from_, from_ + size)
    rows = []
    for qi in range(n_queries):
        row_k, row_sh, row_s = keys[qi], shard_of[qi], scores[qi]
        valid = row_k >= 0
        vk, vsh, vs = row_k[valid], row_sh[valid], row_s[valid]
        mx_col = mxs[:, qi]
        mx_fin = mx_col[np.isfinite(mx_col)]
        mxv = float(mx_fin.max()) if mx_fin.size else float("nan")
        rows.append(ReducedDocs(
            shard_order=[int(x) for x in vsh[window]],
            doc_keys=[int(x) for x in vk[window]],
            scores=[float(x) for x in vs[window]],
            sort_values=None,
            total_hits=int(totals[:, qi].sum()),
            max_score=mxv if _math.isfinite(mxv) else float("nan")))
    return rows


def _mesh_rows_sorted(keys, shard_of, scores, totals, mxs, searchers, *,
                      n_queries: int, size: int, from_: int, sort,
                      track_scores: bool):
    """Per-row ReducedDocs for a SORTED mesh program (ISSUE 17): hit
    order arrived in encoded-key order from the device; only the winners'
    user-facing sort values materialize here — k real values per query,
    never a device round-trip. Scores follow the sorted-loop contract
    (NaN unless track_scores)."""
    import math as _math

    import numpy as np

    from .search import sort as sort_mod
    from .search.controller import ReducedDocs
    from .search.shard_searcher import LOCAL_MASK, SEG_SHIFT
    window = slice(from_, from_ + size)
    rows = []
    for qi in range(n_queries):
        valid = keys[qi] >= 0
        vk = keys[qi][valid][window]
        vsh = shard_of[qi][valid][window]
        vs = scores[qi][valid][window]
        svs, out_scores = [], []
        for dk, sh, sc in zip(vk, vsh, vs):
            seg = searchers[int(sh)].segments[int(dk) >> SEG_SHIFT]
            sc = float(sc) if track_scores else float("nan")
            out_scores.append(sc)
            svs.append(sort_mod.materialize(
                seg, sort, int(dk) & LOCAL_MASK, sc, int(dk), int(sh)))
        mx_col = mxs[:, qi]
        mx_fin = mx_col[np.isfinite(mx_col)]
        mxv = float(mx_fin.max()) if mx_fin.size and track_scores \
            else float("nan")
        rows.append(ReducedDocs(
            shard_order=[int(x) for x in vsh],
            doc_keys=[int(x) for x in vk],
            scores=out_scores,
            sort_values=svs,
            total_hits=int(totals[:, qi].sum()),
            max_score=mxv if _math.isfinite(mxv) else float("nan")))
    return rows


def _mesh_enabled_setting(settings) -> bool:
    """`node.search.mesh.enable` (default true) — the node-level opt-out
    of the mesh-sharded query lane (read live, so tests and `_settings`
    overlays apply without a restart)."""
    v = settings.get("node.search.mesh.enable", True)
    if isinstance(v, str):
        return v.strip().lower() not in ("false", "0", "no", "off")
    return bool(v)


def _req_cache_enabled(settings) -> bool:
    """`index.requests.cache.enable` (default true) — the per-index
    request-cache opt-out (ref IndicesRequestCache INDEX_CACHE_REQUEST_
    ENABLED setting)."""
    v = settings.get("index.requests.cache.enable",
                     settings.get("requests.cache.enable", True))
    if isinstance(v, str):
        return v.strip().lower() not in ("false", "0", "no", "off")
    return bool(v)


def _duration_secs(s: str) -> float:
    m = re.match(r"^(\d+(?:\.\d+)?)(ms|s|m|h|d)?$", str(s).strip())
    if not m:
        return 60.0
    n = float(m.group(1))
    return n * {"ms": 0.001, "s": 1, "m": 60, "h": 3600,
                "d": 86400, None: 1}[m.group(2)]


def _deep_merge(base: dict, patch: dict) -> dict:
    out = dict(base)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


class _ScriptDirListener:
    """FileWatcher listener: *.mustache / *.json files in <data>/scripts
    become stored search templates named by file stem (the reference's
    config/scripts file scripts, hot-reloaded by the resource watcher)."""

    def __init__(self, node: "NodeService"):
        self.node = node

    def _load(self, path: str) -> None:
        stem, ext = os.path.splitext(os.path.basename(path))
        if ext not in (".mustache", ".json"):
            return
        try:
            with open(path) as f:
                content = f.read()
        except OSError:
            return
        self.node.search_templates[stem] = content
        for svc in self.node.indices.values():
            svc.mappers.search_templates = self.node.search_templates

    def on_file_created(self, path: str) -> None:
        self._load(path)

    def on_file_changed(self, path: str) -> None:
        self._load(path)

    def on_file_deleted(self, path: str) -> None:
        stem, ext = os.path.splitext(os.path.basename(path))
        if ext in (".mustache", ".json"):
            self.node.search_templates.pop(stem, None)


def _parse_bytes(v: str) -> int:
    """"128mb" / "1gb" / "512kb" / plain ints -> bytes (ByteSizeValue)."""
    s = str(v).strip().lower()
    for suffix, mult in (("pb", 1 << 50), ("tb", 1 << 40), ("gb", 1 << 30),
                         ("mb", 1 << 20), ("kb", 1 << 10), ("b", 1)):
        if s.endswith(suffix):
            return int(float(s[: -len(suffix)]) * mult)
    return int(float(s))


def _source_filter(src: dict, spec) -> dict | None:
    """None = omit the _source key entirely (the `_source: false` contract —
    the reference drops the field, it does not send an empty object)."""
    if spec is False:
        return None
    if spec is True or spec is None:
        return src
    # path-aware include/exclude over FLATTENED source paths, so
    # "include.field1" style dotted patterns reach nested objects
    # (ref search/fetch/source/FetchSourceSubPhase)
    from .search.shard_searcher import _filter_source
    return _filter_source(src, spec)
