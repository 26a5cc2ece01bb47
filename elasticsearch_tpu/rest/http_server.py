"""REST API over HTTP: the reference's port-9200 surface.

Analog of /root/reference/src/main/java/org/elasticsearch/rest/ (RestController
path-trie dispatch, rest/action/* 1:1 handlers) + http/netty/. The wire
contract targets the machine-readable specs in
/root/reference/rest-api-spec/api/*.json (ES 2.0 response shapes) so existing
clients can point at this server unchanged.

Implementation: stdlib ThreadingHTTPServer — the control plane is IO-bound
host code; the data plane stays on device. (A C++ server lands with the
native runtime milestone; the handler table below is transport-agnostic.)
"""

from __future__ import annotations

import contextlib
import json
import fnmatch
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable
from urllib.parse import parse_qs, urlparse

from ..common import tracing
from ..index.engine import VersionConflictException, DocumentMissingException
from ..node import (IndexAlreadyExistsException, IndexClosedException,
                    IndexMissingException, InvalidIndexNameException,
                    NodeService)
from ..search.aggs import AggregationParsingException
from ..search.query_dsl import QueryParsingException
from ..serving.qos import QosShedException


class RestError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status


def _status_of(e: Exception) -> int:
    from ..common.breaker import CircuitBreakingException
    from ..common.threadpool import EsRejectedExecutionException
    if isinstance(e, RestError):
        return e.status
    if isinstance(e, (CircuitBreakingException, EsRejectedExecutionException,
                      QosShedException)):
        return 429     # TOO_MANY_REQUESTS, ref EsRejectedExecutionException
    from ..snapshots import (RepositoryException, SnapshotException,
                             SnapshotMissingException)
    if isinstance(e, SnapshotMissingException):
        return 404
    if isinstance(e, (RepositoryException, SnapshotException)):
        return 400
    if isinstance(e, IndexClosedException):
        return 403     # ClusterBlockException / INDEX_CLOSED_BLOCK
    if isinstance(e, IndexMissingException):
        return 404
    if isinstance(e, DocumentMissingException):
        return 404
    if isinstance(e, IndexAlreadyExistsException):
        return 400
    if isinstance(e, VersionConflictException):
        return 409
    from ..script.engine import ScriptException
    from ..mapping.mapper import (AlreadyExpiredException,
                                  MapperParsingException,
                                  MergeMappingException,
                                  RoutingMissingException)
    if isinstance(e, (InvalidIndexNameException, QueryParsingException,
                      AggregationParsingException, ScriptException,
                      MapperParsingException, MergeMappingException,
                      RoutingMissingException, AlreadyExpiredException,
                      json.JSONDecodeError, KeyError, ValueError)):
        return 400
    return 500


class RestController:
    """Method+path-pattern dispatch (ref rest/RestController.java:44,119,163
    path trie; regex table is equivalent at this route count)."""

    def __init__(self, node: NodeService, registrar: Callable | None = None):
        self.node = node
        self.routes: list[tuple[str, re.Pattern, Callable]] = []
        (registrar or _register_routes)(self, node)

    def register(self, method: str, pattern: str, handler: Callable) -> None:
        # {name} -> named group; e.g. /{index}/_search
        rx = re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern)
        # specificity: literal segments outrank parameters (the path-trie
        # rule — /_mget must beat /{index})
        segs = [s for s in pattern.split("/") if s]
        literal = sum(1 for s in segs if "{" not in s)
        self.routes.append((method, re.compile(f"^{rx}/?$"), handler,
                            (literal, -len(segs))))

    def dispatch(self, method: str, path: str, params: dict,
                 body: bytes,
                 headers: dict | None = None) -> tuple[int, dict | str]:
        from urllib.parse import unquote
        # percent-decode per segment (ref RestUtils.decodeComponent) —
        # unicode index names / ids arrive encoded
        path = "/".join(unquote(seg) for seg in path.split("/"))
        best = None
        for m, rx, handler, spec in self.routes:
            if m != method:
                continue
            match = rx.match(path)
            if match and (best is None or spec > best[2]):
                best = (handler, match, spec)
        if best is None:
            raise RestError(400, f"no handler for [{method} {path}]")
        handler, match, _ = best
        tasks = getattr(self.node, "tasks", None)
        if tasks is None:
            return handler(match.groupdict(), params, body)
        # every REST request is a registered task carrying the caller's
        # X-Opaque-Id plus a generated trace id; child scopes (per-shard
        # phases, transport handlers) inherit both via the task context.
        # The span tracer roots at the SAME trace id, so slowlog, task
        # listing, profile and GET /_traces correlate on one id;
        # `?trace=true` forces retention past the sampler.
        opaque = (headers or {}).get("x-opaque-id")
        with tasks.scope(_action_of(method, path),
                         description=f"{method} {path}",
                         opaque_id=opaque) as task:
            tracer = getattr(self.node, "tracer", None)
            if tracer is None or not tracer.enabled \
                    or path.startswith("/_traces"):
                # reading traces must never perturb the trace store
                return handler(match.groupdict(), params, body)
            with tracer.request(f"{method} {path}",
                                trace_id=task.trace_id,
                                force=_pbool(params, "trace", False),
                                opaque_id=opaque,
                                attrs={"method": method, "path": path,
                                       "action": task.action}):
                return handler(match.groupdict(), params, body)


def _action_of(method: str, path: str) -> str:
    """Reference-style action name for the task registry (each
    TransportAction declares one; here the route class implies it)."""
    seg = [s for s in path.split("/") if s]
    if any(s in ("_search", "_msearch", "_count", "_suggest", "_percolate",
                 "_mpercolate", "_mlt", "_explain", "_validate")
           for s in seg):
        return "indices:data/read/search"
    if "_bulk" in seg:
        return "indices:data/write/bulk"
    if "_mget" in seg:
        return "indices:data/read/mget"
    if "_tasks" in seg or ("_cat" in seg and "tasks" in seg):
        return "cluster:monitor/tasks/lists"
    if any(s in ("_nodes", "_cluster", "_cat", "_stats") for s in seg):
        return "cluster:monitor"
    if len(seg) >= 3 and not any(s.startswith("_") for s in seg[:2]):
        return "indices:data/read/get" if method in ("GET", "HEAD") \
            else "indices:data/write/index"
    return f"rest:{method.lower()}" + ("/" + seg[0] if seg else "/")


def _pbool(p: dict, name: str, default: bool) -> bool:
    """Boolean URL param: accepts true/false, 1/0, yes/no (ES client
    convention — the YAML suites use all three spellings)."""
    v = p.get(name, [None])[0]
    if v is None:
        return default
    return str(v).lower() not in ("false", "0", "no", "off")


def _meta_field_of(res, f: str):
    """_timestamp / _ttl rendering for `fields` (ref internal field
    mappers: _timestamp returns the index instant, _ttl the REMAINING
    time-to-live)."""
    import time as _time
    if f == "_timestamp":
        return res.timestamp
    if f == "_ttl" and res.ttl_expiry is not None:
        return res.ttl_expiry - int(_time.time() * 1000)
    return None


def _json_body(body: bytes) -> dict:
    if not body:
        return {}
    return json.loads(body)


def _register_routes(c: RestController, node: NodeService) -> None:
    def _resolve_lenient(expr, p):
        return _resolve_lenient_impl(node, expr, p)

    def _expand_indices(expr, p):
        return _expand_indices_impl(node, expr, p)

    # -- cluster / node level ---------------------------------------------
    def root(g, p, b):
        return 200, {"status": 200, "name": "tpu-node-0",
                     "cluster_name": node.cluster_name,
                     "version": {"number": "2.0.0-tpu",
                                 "lucene_version": "tensor-native"},
                     "tagline": "You Know, for Search"}
    c.register("GET", "/", root)
    c.register("HEAD", "/", lambda g, p, b: (200, {}))

    c.register("GET", "/_cluster/health",
               lambda g, p, b: (200, node.cluster_health(
                   p.get("level", ["cluster"])[0])))
    c.register("GET", "/_cluster/health/{index}",
               lambda g, p, b: (200, node.cluster_health(
                   p.get("level", ["cluster"])[0])))

    def put_template(g, p, b):
        if _pbool(p, "create", False) and g["name"] in node.templates:
            raise RestError(400, f"IndexTemplateAlreadyExistsException: "
                                 f"index_template [{g['name']}] already "
                                 f"exists")
        node.put_template(g["name"], _json_body(b))
        return 200, {"acknowledged": True}
    c.register("PUT", "/_template/{name}", put_template)

    # -- snapshots (ref rest/action/admin/cluster/snapshots/) --------------
    c.register("PUT", "/_snapshot/{repo}",
               lambda g, p, b: (200, node.snapshots.put_repository(
                   g["repo"], _json_body(b))))
    c.register("POST", "/_snapshot/{repo}",
               lambda g, p, b: (200, node.snapshots.put_repository(
                   g["repo"], _json_body(b))))
    def get_repo(g, p, b):
        name = g.get("repo")
        if name in (None, "_all", "*"):
            return 200, dict(node.snapshots.repos)
        return 200, node.snapshots.get_repository(name)
    c.register("GET", "/_snapshot", get_repo)
    c.register("GET", "/_snapshot/{repo}", get_repo)
    c.register("POST", "/_snapshot/{repo}/_verify",
               lambda g, p, b: (
                   200, {"nodes": {"tpu-node-0": {"name": "tpu-node-0"}}})
               if g["repo"] in node.snapshots.repos
               else (404, {"error": f"RepositoryMissingException: "
                                    f"[{g['repo']}] missing", "status": 404}))
    c.register("PUT", "/_snapshot/{repo}/{snap}",
               lambda g, p, b: (200, node.snapshots.create_snapshot(
                   g["repo"], g["snap"], _json_body(b))))
    c.register("GET", "/_snapshot/{repo}/{snap}",
               lambda g, p, b: (200, node.snapshots.get_snapshots(
                   g["repo"], g["snap"])))
    c.register("DELETE", "/_snapshot/{repo}/{snap}",
               lambda g, p, b: (200, node.snapshots.delete_snapshot(
                   g["repo"], g["snap"])))
    c.register("POST", "/_snapshot/{repo}/{snap}/_restore",
               lambda g, p, b: (200, node.snapshots.restore_snapshot(
                   g["repo"], g["snap"], _json_body(b))))

    # -- search (must register before the generic doc routes) -------------
    def search(g, p, b):
        with tracing.span("rest.parse_body", cpu=True):
            body = _search_body(p, b)
        scroll = p.get("scroll", [None])[0]
        scan = p.get("search_type", [None])[0] == "scan"
        rc = p.get("request_cache", [None])[0]
        return 200, node.search(g.get("index", "_all"), body, scroll=scroll,
                                scan=scan,
                                request_cache=None if rc is None
                                else rc == "true")

    def _search_body(p, b) -> dict:
        """The `_search` body with the URL's overrides folded in."""
        body = _json_body(b)
        if "q" in p:   # URI search (ref RestSearchAction query_string support)
            body.setdefault("query", {"query_string": {"query": p["q"][0]}})
        if "size" in p:
            body["size"] = int(p["size"][0])
        if "from" in p:
            body["from"] = int(p["from"][0])
        if "sort" in p and "sort" not in body:
            # URI sort: "field", "field:desc", comma lists (RestSearchAction)
            clauses = []
            for part in p["sort"][0].split(","):
                if ":" in part:
                    f, o = part.rsplit(":", 1)
                    clauses.append({f: {"order": o}})
                else:
                    clauses.append(part)
            body["sort"] = clauses
        # URL _source/_source_include/_source_exclude override the body spec
        # (ref RestSearchAction.parseSearchSource fetchSource handling)
        s = p.get("_source", [None])[0]
        if s is not None:
            body["_source"] = False if s == "false" else \
                (True if s == "true" else s.split(","))
        inc = p.get("_source_include", p.get("_source_includes", [None]))[0]
        exc = p.get("_source_exclude", p.get("_source_excludes", [None]))[0]
        if inc or exc:
            # combine with any ?_source= list into ONE fetch-source context
            cur = body.get("_source")
            inc_l = inc.split(",") if inc else \
                (cur if isinstance(cur, list)
                 else [cur] if isinstance(cur, str) else None)
            body["_source"] = {"include": inc_l,
                               "exclude": exc.split(",") if exc else None}
        return body

    def scroll_next(g, p, b):
        body = _json_body(b) if b and b.strip().startswith(b"{") else {}
        sid = g.get("scroll_id") or body.get("scroll_id") \
            or p.get("scroll_id", [None])[0] \
            or (b.decode().strip() if b else None)
        if not sid:
            raise RestError(400, "scroll_id is required")
        keep = body.get("scroll") or p.get("scroll", [None])[0]
        return 200, node.scroll(sid, keep)
    c.register("GET", "/_search/scroll", scroll_next)
    c.register("POST", "/_search/scroll", scroll_next)
    c.register("GET", "/_search/scroll/{scroll_id}", scroll_next)
    c.register("POST", "/_search/scroll/{scroll_id}", scroll_next)

    def clear_scroll(g, p, b):
        body = _json_body(b)
        sids = g.get("scroll_id") or body.get("scroll_id") \
            or p.get("scroll_id", [None])[0] or []
        if isinstance(sids, str):
            sids = sids.split(",")
        if sids == ["_all"]:
            sids = list(node._scrolls)
            n = node.clear_scroll(sids)
        else:
            n = node.clear_scroll(sids)
            if n == 0 and sids:
                return 404, {"succeeded": True, "num_freed": 0}
        return 200, {"succeeded": True, "num_freed": n}
    c.register("DELETE", "/_search/scroll", clear_scroll)
    c.register("DELETE", "/_search/scroll/{scroll_id}", clear_scroll)
    c.register("GET", "/{index}/_search", search)
    c.register("POST", "/{index}/_search", search)
    c.register("GET", "/_search", search)
    c.register("POST", "/_search", search)
    c.register("GET", "/{index}/{type}/_search",
               lambda g, p, b: search(g, p, b))
    c.register("POST", "/{index}/{type}/_search",
               lambda g, p, b: search(g, p, b))

    def count(g, p, b):
        return 200, node.count(g.get("index", "_all"), _json_body(b))
    c.register("GET", "/{index}/_count", count)
    c.register("POST", "/{index}/_count", count)
    c.register("GET", "/_count", count)

    def msearch(g, p, b):
        # NDJSON: alternating header / body lines
        # (ref rest/action/search/RestMultiSearchAction)
        with tracing.span("rest.parse_body", cpu=True):
            lines = [json.loads(ln) for ln in b.decode("utf-8").split("\n")
                     if ln.strip()]
            if len(lines) % 2:
                raise RestError(400,
                                "msearch body must be header/body pairs")
            requests = []
            for i in range(0, len(lines), 2):
                header = dict(lines[i])
                if g.get("index") and "index" not in header:
                    header["index"] = g["index"]
                requests.append((header, lines[i + 1]))
        # raw=True: the packed serving lane pre-serializes hit JSON with
        # vectorized string ops; bytes pass straight through to the socket
        return 200, node.msearch(requests, raw=True)
    def mlt_api(g, p, b):
        spec: dict = {"ids": [g["id"]]}
        if "mlt_fields" in p:
            spec["fields"] = p["mlt_fields"][0].split(",")
        for prm, key in (("min_term_freq", "min_term_freq"),
                         ("min_doc_freq", "min_doc_freq"),
                         ("max_query_terms", "max_query_terms")):
            if prm in p:
                spec[key] = int(p[prm][0])
        body = _json_body(b)
        body["query"] = {"more_like_this": spec}
        return 200, node.search(g["index"], body)
    c.register("GET", "/{index}/{type}/{id}/_mlt", mlt_api)
    c.register("POST", "/{index}/{type}/{id}/_mlt", mlt_api)

    def percolate_api(g, p, b, count_only=False):
        body = _json_body(b)
        doc_index, doc_type = g["index"], g.get("type", "_doc")
        # percolate_index/percolate_type: fetch the doc from one index,
        # match against ANOTHER's registered queries (ref
        # RestPercolateAction existing-doc routing)
        perc_index = p.get("percolate_index", [doc_index])[0]
        perc_type = p.get("percolate_type", [doc_type])[0]
        if g.get("id") is not None and "doc" not in (body or {}):
            got = node.get_doc(node._resolve(doc_index)[0], str(g["id"]))
            if not got.found:
                raise DocumentMissingException(
                    f"[{doc_type}][{g['id']}]: document missing")
            want_ver = p.get("version", [None])[0]
            if want_ver is not None and int(want_ver) != got.version:
                raise VersionConflictException(str(g["id"]), got.version,
                                               int(want_ver))
            body = {**(body or {}), "doc": got.source}
        out = node.percolate(perc_index, body, type_name=perc_type,
                             doc_id=None)
        if count_only:
            out = {k: v for k, v in out.items() if k != "matches"}
        return 200, out
    for m in ("GET", "POST"):
        c.register(m, "/{index}/{type}/_percolate", percolate_api)
        c.register(m, "/{index}/{type}/{id}/_percolate", percolate_api)
        c.register(m, "/{index}/{type}/_percolate/count",
                   lambda g, p, b: percolate_api(g, p, b, count_only=True))
        c.register(m, "/{index}/{type}/{id}/_percolate/count",
                   lambda g, p, b: percolate_api(g, p, b, count_only=True))

    def mpercolate_api(g, p, b):
        lines = [ln for ln in b.decode("utf-8").split("\n") if ln.strip()]
        items = []   # (index, type, body, doc_id, parse_error)
        i = 0
        while i < len(lines):
            start = i
            try:
                head = json.loads(lines[i])
                i += 1
                body = json.loads(lines[i]) if i < len(lines) else {}
                i += 1
                (_kind, meta), = head.items()
                items.append((meta.get("index", g.get("index", "_all")),
                              meta.get("type", "_doc"), body,
                              meta.get("id"), None))
            except Exception as e:  # noqa: BLE001 — per-item contract
                i = start + 2   # skip the malformed header+body pair
                items.append((None, None, None, None,
                              f"{type(e).__name__}[{e}]"))
        responses: list = [None] * len(items)
        # inline-doc items sharing an (index, type) batch into ONE dense
        # doc×query matrix dispatch (node.mpercolate, ISSUE 18); items
        # with an existing-doc id or a parse error run per item below
        groups: dict = {}
        for idx, (ix, tp, body, did, err) in enumerate(items):
            if err is None and did is None \
                    and isinstance(body, dict) and "doc" in body:
                groups.setdefault((ix, tp), []).append(idx)
        for (ix, tp), idxs in groups.items():
            try:
                outs = node.mpercolate(
                    ix, [items[j][2] for j in idxs],
                    type_name=tp)["responses"]
                for j, out in zip(idxs, outs):
                    responses[j] = out
            except Exception as e:  # noqa: BLE001 — per-item contract
                for j in idxs:
                    responses[j] = {"error": f"{type(e).__name__}[{e}]"}
        for idx, (ix, tp, body, did, err) in enumerate(items):
            if responses[idx] is not None:
                continue
            if err is not None:
                responses[idx] = {"error": err}
                continue
            try:
                responses[idx] = node.percolate(ix, body, type_name=tp,
                                                doc_id=did)
            except Exception as e:  # noqa: BLE001 — per-item contract
                responses[idx] = {"error": f"{type(e).__name__}[{e}]"}
        return 200, {"responses": responses}
    c.register("GET", "/_mpercolate", mpercolate_api)
    c.register("POST", "/_mpercolate", mpercolate_api)
    c.register("GET", "/{index}/_mpercolate", mpercolate_api)
    c.register("POST", "/{index}/_mpercolate", mpercolate_api)
    c.register("GET", "/{index}/{type}/_mpercolate", mpercolate_api)
    c.register("POST", "/{index}/{type}/_mpercolate", mpercolate_api)

    # -- search templates (ref RestSearchTemplateAction + script store) ----
    def put_search_template(g, p, b):
        body = _json_body(b)
        tpl = body.get("template", body)
        compact = tpl if isinstance(tpl, str) \
            else json.dumps(tpl, separators=(",", ":"))
        if re.search(r"\{\{\s*\}\}", compact):
            # empty mustache variable — the reference's compile-time reject
            raise RestError(
                400, "ElasticsearchIllegalArgumentException[Unable to parse "
                     "template: empty mustache variable]")
        created = g["id"] not in node.search_templates
        node.search_templates[g["id"]] = body.get("template", body)
        node._persist_search_templates()
        # templates live in the .scripts system index in the reference
        return (201 if created else 200), {
            "_index": ".scripts", "_type": "mustache", "_id": g["id"],
            "_version": 1, "created": created, "acknowledged": True}
    c.register("PUT", "/_search/template/{id}", put_search_template)
    c.register("POST", "/_search/template/{id}", put_search_template)

    def get_search_template(g, p, b):
        tpl = node.search_templates.get(g["id"])
        if tpl is None:
            return 404, {"_id": g["id"], "found": False}
        # the reference stores templates as COMPACT script strings
        rendered = tpl if isinstance(tpl, str) \
            else json.dumps(tpl, separators=(",", ":"))
        return 200, {"_index": ".scripts", "_type": "mustache",
                     "_id": g["id"], "found": True, "lang": "mustache",
                     "template": rendered}
    c.register("GET", "/_search/template/{id}", get_search_template)

    def delete_search_template(g, p, b):
        if node.search_templates.pop(g["id"], None) is None:
            return 404, {"_index": ".scripts", "_type": "mustache",
                         "_id": g["id"], "found": False}
        node._persist_search_templates()
        return 200, {"_index": ".scripts", "_type": "mustache",
                     "_id": g["id"], "_version": 2, "found": True,
                     "acknowledged": True}
    c.register("DELETE", "/_search/template/{id}", delete_search_template)

    def search_template(g, p, b):
        from ..search.templates import render_template
        body = render_template(_json_body(b), node.search_templates)
        return 200, node.search(g.get("index", "_all"), body)
    c.register("GET", "/_search/template", search_template)
    c.register("POST", "/_search/template", search_template)
    c.register("GET", "/{index}/_search/template", search_template)
    c.register("POST", "/{index}/_search/template", search_template)
    c.register("GET", "/{index}/{type}/_search/template", search_template)
    c.register("POST", "/{index}/{type}/_search/template", search_template)

    def suggest_api(g, p, b):
        out = node.suggest(g.get("index", "_all"), _json_body(b))
        return 200, {"_shards": {"total": 1, "successful": 1, "failed": 0},
                     **out}
    c.register("GET", "/_suggest", suggest_api)
    c.register("POST", "/_suggest", suggest_api)
    c.register("GET", "/{index}/_suggest", suggest_api)
    c.register("POST", "/{index}/_suggest", suggest_api)

    c.register("GET", "/_msearch", msearch)
    c.register("POST", "/_msearch", msearch)
    c.register("GET", "/{index}/_msearch", msearch)
    c.register("POST", "/{index}/_msearch", msearch)

    # -- bulk --------------------------------------------------------------
    def bulk(g, p, b):
        import time
        t0 = time.perf_counter()
        default_index = g.get("index")
        ops = _parse_bulk(b, default_index)
        items = node.bulk(ops)
        errors = any(next(iter(i.values())).get("status", 200) >= 300
                     for i in items)
        if p.get("refresh", ["false"])[0] != "false":
            node.refresh(default_index or "_all")
        # pre-serialized compact bytes: a 100k-doc ingest emits ~10MB of
        # item acks — compact separators + the handler's bytes fast lane
        # keep response encoding out of the ingest budget
        return 200, json.dumps(
            {"took": int((time.perf_counter() - t0) * 1000),
             "errors": errors, "items": items},
            separators=(",", ":")).encode()
    c.register("POST", "/_bulk", bulk)
    c.register("PUT", "/_bulk", bulk)
    c.register("POST", "/{index}/_bulk", bulk)
    c.register("POST", "/{index}/{type}/_bulk", bulk)

    # -- admin per index ---------------------------------------------------
    def create_index(g, p, b):
        body = _json_body(b)
        svc = node.create_index(g["index"], settings=body.get("settings"),
                                mappings=body.get("mappings"),
                                aliases=body.get("aliases"))
        if body.get("warmers"):
            svc.warmers = {w: {"types": spec.get("types", []),
                               "source": spec.get("source", {})}
                           for w, spec in body["warmers"].items()}
        return 200, {"acknowledged": True}
    c.register("PUT", "/{index}", create_index)
    c.register("POST", "/{index}", create_index)

    def delete_index(g, p, b):
        node.delete_index(g["index"])
        return 200, {"acknowledged": True}
    c.register("DELETE", "/{index}", delete_index)

    def index_exists(g, p, b):
        try:
            node._resolve(g["index"])
            return 200, {}
        except IndexClosedException:
            return 200, {}     # closed indices exist
        except IndexMissingException:
            return 404, {}
    c.register("HEAD", "/{index}", index_exists)

    def refresh(g, p, b):
        node.refresh(g.get("index", "_all"))
        return 200, {"_shards": {"failed": 0}}
    c.register("POST", "/{index}/_refresh", refresh)
    c.register("POST", "/_refresh", refresh)

    def flush(g, p, b):
        node.flush(g.get("index", "_all"))
        return 200, {"_shards": {"failed": 0}}
    c.register("POST", "/{index}/_flush", flush)
    c.register("POST", "/_flush", flush)

    def optimize(g, p, b):
        node.force_merge(g.get("index", "_all"),
                         int(p.get("max_num_segments", [1])[0]))
        return 200, {"_shards": {"failed": 0}}
    c.register("POST", "/{index}/_optimize", optimize)
    c.register("POST", "/_optimize", optimize)
    c.register("POST", "/{index}/_forcemerge", optimize)

    def get_mapping(g, p, b):
        tpat = g.get("type")
        out = {}
        found_type = False
        opens, closeds = _expand_indices(g.get("index", "_all"), p)
        for n in opens:
            md = node.indices[n].mappings_dict()
            if tpat and tpat not in ("_all", "*"):
                md = {t: m for t, m in md.items()
                      if any(fnmatch.fnmatch(t, pat)
                             for pat in tpat.split(","))}
            if md:
                found_type = True
            out[n] = {"mappings": md}
        for n in closeds:
            if n not in out:
                out[n] = {"mappings": node.closed[n].get("mappings") or {}}
                found_type = True
        if tpat and tpat not in ("_all", "*") and not found_type:
            return 200, {}     # no matching type: empty body, HTTP 200
        return 200, out
    c.register("GET", "/{index}/_mapping", get_mapping)
    c.register("GET", "/_mapping", get_mapping)
    c.register("GET", "/{index}/_mapping/{type}", get_mapping)
    c.register("GET", "/_mapping/{type}", get_mapping)
    c.register("GET", "/{index}/{type}/_mapping", get_mapping)

    def head_type(g, p, b):
        try:
            for n in node._resolve(g["index"]):
                if g["type"] in node.indices[n].mappers.types():
                    return 200, {}
        except IndexMissingException:
            pass
        return 404, {}
    c.register("HEAD", "/{index}/{type}", head_type)

    def field_mapping(g, p, b):
        """GET field mappings (ref indices.get_field_mapping spec +
        TransportGetFieldMappingsAction: full-path patterns key by full
        path, leaf-relative patterns key by leaf name; empty result = {};
        unknown explicit type = TypeMissingException 404)."""
        fields = g.get("field", "*").split(",")
        tpat = g.get("type")
        include_defaults = _pbool(p, "include_defaults", False)
        out = {}
        matched_type = False
        for n in node._resolve(g.get("index", "_all")):
            svc = node.indices[n]
            tmap = {}
            for t in svc.mappers.types():
                if tpat and tpat not in ("_all", "*") \
                        and not any(fnmatch.fnmatch(t, pp)
                                    for pp in tpat.split(",")):
                    continue
                matched_type = True
                dm = svc.mappers.document_mapper(t, create=False)
                fmap = {}
                for f in fields:
                    # full-name matches win; ONLY if a pattern matches no
                    # full name does it fall back to leaf (index-name)
                    # matching, keyed by the leaf-relative name
                    hits = [(path, path) for path in dm.fields
                            if fnmatch.fnmatch(path, f)]
                    if not hits:
                        hits = [(path.split(".")[-1], path)
                                for path in dm.fields
                                if fnmatch.fnmatch(path.split(".")[-1], f)]
                    for key, path in hits:
                        ft = dm.fields[path]
                        d = ft.to_dict()
                        if include_defaults and d.get("type") == "string" \
                                and "analyzer" not in d \
                                and d.get("index") != "not_analyzed":
                            d = {**d, "analyzer": "default"}
                        fmap[key] = {"full_name": path,
                                     "mapping": {path.split(".")[-1]: d}}
                if fmap:
                    tmap[t] = fmap
            if tmap:
                out[n] = {"mappings": tmap}
        if tpat and tpat not in ("_all", "*") and not matched_type:
            return 404, {"error": f"TypeMissingException: "
                                  f"type[[{tpat}]] missing", "status": 404}
        return 200, out
    c.register("GET", "/_mapping/field/{field}", field_mapping)
    c.register("GET", "/{index}/_mapping/field/{field}", field_mapping)
    c.register("GET", "/{index}/_mapping/{type}/field/{field}",
               field_mapping)
    c.register("GET", "/_mapping/{type}/field/{field}", field_mapping)

    def put_mapping(g, p, b):
        body = _json_body(b)
        tname = g.get("type", "_doc")
        mapping = body.get(tname, body)
        for n in node._resolve(g.get("index", "_all")):
            node.put_mapping(n, tname, mapping)
        return 200, {"acknowledged": True}
    c.register("PUT", "/{index}/_mapping/{type}", put_mapping)
    c.register("PUT", "/{index}/{type}/_mapping", put_mapping)
    c.register("PUT", "/{index}/_mapping", put_mapping)
    c.register("POST", "/{index}/_mapping/{type}", put_mapping)
    c.register("POST", "/{index}/{type}/_mapping", put_mapping)
    c.register("PUT", "/_mapping/{type}", put_mapping)   # blank index = _all
    c.register("POST", "/_mapping/{type}", put_mapping)

    def analyze(g, p, b):
        body = _json_body(b)
        text = body.get("text") or (p.get("text", [""])[0])
        svc = node.index_service(g["index"]) if g.get("index") else None
        from ..analysis.analyzers import AnalysisService, Analyzer
        an = (svc.mappers.analysis if svc else AnalysisService())
        tokenizer = body.get("tokenizer", p.get("tokenizer", [None])[0])
        filters = body.get("filters", body.get("token_filters"))
        if filters is None:
            filters = p.get("filters", [None])[0]
            filters = filters.split(",") if filters else []
        elif isinstance(filters, str):
            filters = filters.split(",")
        field = body.get("field", p.get("field", [None])[0])
        if tokenizer:
            analyzer_obj = an.custom(tokenizer, filters)
        elif field and svc is not None \
                and "analyzer" not in body and "analyzer" not in p:
            # field form: analyze with THAT field's analyzer — keyword /
            # not_analyzed fields preserve the raw token
            ft = svc.mappers.field_type(field)
            if ft is not None and ft.type == "keyword":
                analyzer_obj = an.analyzer("keyword")
            elif ft is not None:
                analyzer_obj = an.analyzer(ft.analyzer)
            else:
                analyzer_obj = an.analyzer("standard")
        else:
            name = body.get("analyzer", p.get("analyzer", ["standard"])[0])
            analyzer_obj = an.analyzer(name)
        tokens = analyzer_obj.analyze(
            text if isinstance(text, str) else " ".join(text))
        return 200, {"tokens": [
            {"token": t, "start_offset": 0, "end_offset": 0,
             "type": "<ALPHANUM>", "position": i}
            for i, t in enumerate(tokens)]}
    c.register("GET", "/_analyze", analyze)
    c.register("POST", "/_analyze", analyze)
    c.register("GET", "/{index}/_analyze", analyze)
    c.register("POST", "/{index}/_analyze", analyze)

    # -- documents ---------------------------------------------------------
    def put_doc(g, p, b):
        kw = {}
        if "version" in p:
            kw["version"] = int(p["version"][0])
            kw["version_type"] = p.get("version_type", ["internal"])[0]
        if p.get("op_type", [None])[0] == "create":
            kw["op_type"] = "create"
        if "version" in p:
            kw["version"] = int(p["version"][0])
        if "version_type" in p:
            kw["version_type"] = p["version_type"][0]
        routing = p.get("routing", [None])[0]
        parent = p.get("parent", [None])[0]
        _, res = node.index_doc(g["index"], g.get("id"), _json_body(b),
                                type_name=g.get("type", "_doc"),
                                routing=routing, parent=parent,
                                timestamp=p.get("timestamp", [None])[0],
                                ttl=p.get("ttl", [None])[0], **kw)
        if _pbool(p, "refresh", False):
            node.refresh_doc_shard(g["index"], res.doc_id,
                                   routing or parent)
        status = 201 if res.created else 200
        out = {"_index": g["index"], "_type": g.get("type", "_doc"),
               "_id": res.doc_id, "_version": res.version,
               "created": res.created,
               "_shards": _write_shards(node, g["index"])}
        # percolate-on-ingest (ref RestIndexAction ?percolate=): the just-
        # written doc runs against the registered queries of the SAME index
        # (or the query given in the param) through the dense matrix lane;
        # matches ride back on the index response
        if p.get("percolate", [None])[0] is not None:
            praw = p["percolate"][0]
            pbody: dict = {"doc": _json_body(b)}
            if praw not in ("", "*", "true", "1"):
                try:
                    pbody.update(json.loads(praw))
                except (ValueError, TypeError):
                    pass
            perc = node.percolate(g["index"], pbody,
                                  type_name=g.get("type", "_doc"))
            out["matches"] = perc["matches"]
        return status, out
    c.register("PUT", "/{index}/{type}/{id}", put_doc)
    c.register("POST", "/{index}/{type}/{id}", put_doc)
    c.register("POST", "/{index}/{type}", put_doc)

    def create_doc(g, p, b):
        p = {**p, "op_type": ["create"]}
        return put_doc(g, p, b)
    c.register("PUT", "/{index}/{type}/{id}/_create", create_doc)
    c.register("POST", "/{index}/{type}/{id}/_create", create_doc)

    def _resolve_get(g, p):
        """Shared GET semantics: realtime, version check, source filtering
        (ref index/get/ShardGetService + RestGetAction params)."""
        realtime = _pbool(p, "realtime", True)
        if _pbool(p, "refresh", False):
            node.refresh(g["index"])
        routing = p.get("routing", [None])[0]
        parent = p.get("parent", [None])[0]
        tname = g.get("type")
        if routing is None and parent is None and tname:
            svc = node.indices.get(g["index"])
            if svc is not None and svc.mappers.parent_type_of(tname):
                from ..mapping.mapper import RoutingMissingException
                raise RoutingMissingException(
                    f"routing is required for [{g['index']}]/[{tname}]/"
                    f"[{g['id']}]")
        res = node.get_doc(g["index"], g["id"],
                           routing=routing, parent=parent,
                           realtime=realtime)
        if res.found and "version" in p \
                and p.get("version_type", ["internal"])[0] != "force" \
                and int(p["version"][0]) != res.version:
            # force never conflicts on reads (ref VersionType.FORCE)
            raise VersionConflictException(
                g["id"], res.version, int(p["version"][0]))
        return res

    def _source_of(res, p):
        src = res.source
        spec = p.get("_source", [None])[0]
        if spec is not None:
            if spec in ("false", "no"):
                return None
            if spec not in ("true", "yes"):
                src = _source_filter_paths(src, spec.split(","), None)
        inc = p.get("_source_include", p.get("_source_includes", [None]))[0]
        exc = p.get("_source_exclude", p.get("_source_excludes", [None]))[0]
        if inc or exc:
            src = _source_filter_paths(src, inc.split(",") if inc else None,
                                       exc.split(",") if exc else None)
        return src

    def get_doc(g, p, b):
        res = _resolve_get(g, p)
        out = {"_index": g["index"], "_type": res.type_name, "_id": g["id"],
               "found": res.found}
        if res.found:
            out["_version"] = res.version
            src = _source_of(res, p)
            # fields param suppresses _source unless explicitly requested
            # (ref RestGetAction: fields and source are separate fetches)
            fld_list = p["fields"][0].split(",") if "fields" in p else None
            if src is not None and (fld_list is None
                                    or "_source" in fld_list
                                    or "_source" in p):
                out["_source"] = src
            if fld_list is not None:
                fields = {}
                for f in fld_list:
                    if f == "_source":
                        continue
                    if f == "_routing":
                        if res.routing is not None:
                            fields["_routing"] = res.routing
                        continue
                    if f == "_parent":
                        if res.parent is not None:
                            fields["_parent"] = res.parent
                        continue
                    if f in ("_timestamp", "_ttl"):
                        v = _meta_field_of(res, f)
                        if v is not None:
                            fields[f] = v
                        continue
                    v = res.source.get(f) if res.source else None
                    if v is not None:
                        fields[f] = v if isinstance(v, list) else [v]
                if fields:
                    out["fields"] = fields
        return (200 if res.found else 404), out
    c.register("GET", "/{index}/{type}/{id}", get_doc)

    def get_source(g, p, b):
        res = _resolve_get(g, p)
        if not res.found:
            return 404, {"error": "not found", "status": 404}
        src = _source_of(res, p)
        return 200, src if src is not None else {}
    c.register("GET", "/{index}/{type}/{id}/_source", get_source)

    def head_doc(g, p, b):
        try:
            res = _resolve_get(g, p)
        except IndexMissingException:
            return 404, {}
        return (200 if res.found else 404), {}
    c.register("HEAD", "/{index}/{type}/{id}", head_doc)
    c.register("HEAD", "/{index}/{type}/{id}/_source", head_doc)

    def delete_doc(g, p, b):
        kw = {}
        if "version" in p:
            kw["version"] = int(p["version"][0])
        if "version_type" in p:
            kw["version_type"] = p["version_type"][0]
        routing = p.get("routing", [None])[0]
        parent = p.get("parent", [None])[0]
        res = node.delete_doc(g["index"], g["id"],
                              routing=routing, parent=parent, **kw)
        if _pbool(p, "refresh", False):
            node.refresh_doc_shard(g["index"], g["id"],
                                   routing or parent)
        return (200 if res.found else 404), {
            "found": res.found, "_index": g["index"],
            "_type": g.get("type", "_doc"), "_id": g["id"],
            "_version": res.version,
            "_shards": _write_shards(node, g["index"])}
    c.register("DELETE", "/{index}/{type}/{id}", delete_doc)

    def update_doc(g, p, b):
        vt = p.get("version_type", ["internal"])[0]
        if vt not in ("internal", "force"):
            raise RestError(
                400, "ActionRequestValidationException: version type "
                     f"[{vt}] is not supported by the update API")
        kw = {}
        if "version" in p:
            kw["version"] = int(p["version"][0])
        res, noop = node.update_doc(g["index"], g["id"], _json_body(b),
                                    type_name=g.get("type", "_doc"),
                                    routing=p.get("routing", [None])[0],
                                    parent=p.get("parent", [None])[0],
                                    timestamp=p.get("timestamp", [None])[0],
                                    ttl=p.get("ttl", [None])[0], **kw)
        if _pbool(p, "refresh", False):
            node.refresh_doc_shard(g["index"], g["id"],
                                   p.get("routing", [None])[0]
                                   or p.get("parent", [None])[0])
        out = {"_index": g["index"], "_type": g.get("type", "_doc"),
               "_id": g["id"], "_version": res.version,
               "_shards": _write_shards(node, g["index"])}
        if "fields" in p:
            got = node.get_doc(g["index"], g["id"],
                               routing=p.get("routing", [None])[0],
                               parent=p.get("parent", [None])[0])
            if got.found:
                fields = {}
                src_included = False
                for f in p["fields"][0].split(","):
                    if f == "_source":
                        src_included = True
                        continue
                    v = (got.source or {}).get(f)
                    if v is not None:
                        fields[f] = v if isinstance(v, list) else [v]
                entry: dict = {"found": True, "_version": got.version}
                if src_included:
                    entry["_source"] = got.source
                if fields:
                    entry["fields"] = fields
                out["get"] = entry
        return 200, out
    c.register("POST", "/{index}/{type}/{id}/_update", update_doc)

    def mget(g, p, b):
        body = _json_body(b)
        items = body.get("docs")
        if items is None and "ids" in body:
            items = [{"_id": i} for i in body["ids"]]
        if not items:
            raise RestError(400, "ActionRequestValidationException: "
                                 "Validation Failed: 1: no documents "
                                 "to get;")
        realtime = _pbool(p, "realtime", True)
        if _pbool(p, "refresh", False):
            # refresh every index the request touches, incl. per-doc _index
            touched = {d.get("_index", g.get("index")) for d in items
                       if isinstance(d, dict)} | {g.get("index")}
            for idx in touched:
                if idx:
                    try:
                        node.refresh(idx)
                    except IndexMissingException:
                        pass
        url_fields = p.get("fields", [None])[0]
        if url_fields is not None:
            url_fields = url_fields.split(",")
        # URL-level _source / _source_include / _source_exclude apply to
        # every doc that doesn't carry its own spec (ref RestMultiGetAction
        # defaultFetchSource)
        url_spec = None
        s = p.get("_source", [None])[0]
        if s is not None:
            url_spec = False if s == "false" else \
                (True if s == "true" else s.split(","))
        inc = p.get("_source_include", p.get("_source_includes", [None]))[0]
        exc = p.get("_source_exclude", p.get("_source_excludes", [None]))[0]
        if inc or exc:
            url_spec = {"include": inc.split(",") if inc else None,
                        "exclude": exc.split(",") if exc else None}
        default_type = g.get("type")
        docs = []
        for d in items:
            if not isinstance(d, dict):
                d = {"_id": d}
            idx = d.get("_index", g.get("index"))
            if "_id" not in d:
                raise RestError(400, "ActionRequestValidationException: "
                                     "id is missing")
            if idx is None:
                raise RestError(400, "ActionRequestValidationException: "
                                     "index is missing")
            doc_id = str(d["_id"])
            want_type = d.get("_type", default_type)
            routing = d.get("_routing") or d.get("routing")
            parent = d.get("_parent") or d.get("parent")
            try:
                res = node.get_doc(
                    idx, doc_id,
                    routing=str(routing) if routing is not None else None,
                    parent=str(parent) if parent is not None else None,
                    realtime=realtime)
            except IndexMissingException as e:
                docs.append({"_index": idx,
                             "_type": want_type or "_doc",
                             "_id": doc_id,
                             "error": str(e), "found": False})
                continue
            # type filter: a requested type must MATCH the stored type
            # (ref TransportGetAction type resolution; "_all" matches any)
            found = res.found
            if found and want_type not in (None, "_all") \
                    and res.type_name != want_type:
                found = False
            entry = {"_index": idx,
                     "_type": res.type_name if found
                     else (want_type or "_doc"),
                     "_id": doc_id, "found": found}
            if found:
                entry["_version"] = res.version
                flds = d.get("fields", d.get("_fields", url_fields))
                if flds:
                    if isinstance(flds, str):
                        flds = [flds]
                    fields = {}
                    src_included = False
                    for f in flds:
                        if f == "_source":
                            src_included = True
                        elif f == "_routing":
                            if res.routing is not None:
                                fields["_routing"] = res.routing
                        elif f == "_parent":
                            if getattr(res, "parent", None) is not None:
                                fields["_parent"] = res.parent
                        else:
                            v = (res.source or {}).get(f)
                            if v is not None:
                                fields[f] = v if isinstance(v, list) else [v]
                    if fields:
                        entry["fields"] = fields
                    if src_included:
                        entry["_source"] = res.source
                else:
                    src = res.source
                    spec = d["_source"] if "_source" in d else url_spec
                    if spec is not None:
                        if spec is False:
                            src = None
                        elif spec is not True:
                            if isinstance(spec, str):
                                spec = [spec]
                            inc = spec if isinstance(spec, list) else \
                                spec.get("include", spec.get("includes"))
                            exc = None if isinstance(spec, list) else \
                                spec.get("exclude", spec.get("excludes"))
                            src = _source_filter_paths(src, inc, exc)
                    if src is not None:
                        entry["_source"] = src
            docs.append(entry)
        return 200, {"docs": docs}
    c.register("GET", "/_mget", mget)
    c.register("POST", "/_mget", mget)
    c.register("GET", "/{index}/_mget", mget)
    c.register("POST", "/{index}/_mget", mget)
    c.register("GET", "/{index}/{type}/_mget", mget)
    c.register("POST", "/{index}/{type}/_mget", mget)

    # -- termvectors / mtermvectors (ref action/termvectors/) -------------
    def termvectors(g, p, b):
        body = _json_body(b) if b else {}
        flds = p.get("fields", [None])[0]
        if flds is not None:
            flds = flds.split(",")
        elif body.get("fields"):
            flds = list(body["fields"])
        return 200, node.termvectors(
            g["index"], str(g.get("id", body.get("_id", ""))),
            type_name=g.get("type", "_doc"), fields=flds,
            realtime=_pbool(p, "realtime", True),
            term_statistics=_pbool(p, "term_statistics", False)
            or bool(body.get("term_statistics")),
            field_statistics=_pbool(p, "field_statistics", True),
            positions=_pbool(p, "positions", True),
            offsets=_pbool(p, "offsets", True),
            routing=p.get("routing", [None])[0],
            parent=p.get("parent", [None])[0])
    for pat in ("/{index}/{type}/{id}/_termvectors",
                "/{index}/{type}/{id}/_termvector",
                "/{index}/{type}/_termvectors",
                "/{index}/{type}/_termvector"):
        c.register("GET", pat, termvectors)
        c.register("POST", pat, termvectors)

    def mtermvectors(g, p, b):
        body = _json_body(b) if b else {}
        items = body.get("docs")
        if items is None and "ids" in body:
            items = [{"_id": i} for i in body["ids"]]
        if items is None and "ids" in p:
            items = [{"_id": i} for i in p["ids"][0].split(",")]
        if not items:
            raise RestError(400, "ActionRequestValidationException: "
                                 "Validation Failed: 1: no documents "
                                 "requested;")
        tstats = _pbool(p, "term_statistics", False) \
            or bool(body.get("term_statistics"))
        docs = []
        for d in items:
            idx = d.get("_index", g.get("index"))
            if idx is None:
                docs.append({"error": "index is missing"})
                continue
            try:
                docs.append(node.termvectors(
                    idx, str(d["_id"]),
                    type_name=d.get("_type", g.get("type", "_doc")),
                    fields=d.get("fields"),
                    realtime=_pbool(p, "realtime", True),
                    term_statistics=tstats or bool(d.get("term_statistics")),
                    routing=d.get("_routing") or d.get("routing"),
                    parent=d.get("_parent") or d.get("parent")))
            except Exception as e:  # noqa: BLE001 — per-item contract
                docs.append({"_index": idx, "_id": str(d.get("_id")),
                             "error": f"{type(e).__name__}[{e}]"})
        return 200, {"docs": docs}
    for pat in ("/_mtermvectors", "/{index}/_mtermvectors",
                "/{index}/{type}/_mtermvectors"):
        c.register("GET", pat, mtermvectors)
        c.register("POST", pat, mtermvectors)

    # -- search_shards (ref TransportSearchShardsAction) -------------------
    def search_shards(g, p, b):
        names = node._resolve(g.get("index", "_all"))
        shards = []
        nodes = {"node0": {"name": "tpu-node-0",
                           "transport_address": "local[1]"}}
        for n in names:
            for sid, _e in enumerate(node.indices[n].shards):
                shards.append([{"index": n, "shard": sid, "primary": True,
                                "state": "STARTED", "node": "node0"}])
        return 200, {"nodes": nodes, "shards": shards}
    c.register("GET", "/_search_shards", search_shards)
    c.register("POST", "/_search_shards", search_shards)
    c.register("GET", "/{index}/_search_shards", search_shards)
    c.register("POST", "/{index}/_search_shards", search_shards)

    # -- cache clear (ref indices/cache/clear/TransportClearIndicesCache-
    #    Action): real invalidation against the node cache subsystem.
    #    ?query= / ?request= / ?fielddata= select tiers (aliases the
    #    reference accepted — filter/filter_cache/query_cache/request_cache
    #    — map onto the same three); no flag at all clears everything. ----
    def clear_cache(g, p, b):
        names = node._resolve(g.get("index", "_all"))

        def flag(*keys):
            for k in keys:
                v = p.get(k, [None])[0]
                if v is not None:
                    # bare `?request` (no value) means true, like the ref
                    return str(v).strip().lower() not in ("false", "0", "no")
            return None
        q = flag("query", "query_cache", "filter", "filter_cache")
        r = flag("request", "request_cache")
        f = flag("fielddata", "field_data")
        if q is None and r is None and f is None:
            q = r = f = True
        cleared = node.caches.clear(
            query=bool(q), request=bool(r), fielddata=bool(f),
            indices=None if g.get("index") in (None, "", "_all", "*")
            else names)
        return 200, {"_shards": {
            "total": sum(len(node.indices[n].shards) for n in names),
            "successful": sum(len(node.indices[n].shards) for n in names),
            "failed": 0}, "cleared": cleared}
    for pat in ("/_cache/clear", "/{index}/_cache/clear"):
        c.register("POST", pat, clear_cache)
        c.register("GET", pat, clear_cache)

    # -- recovery status API (ref action/admin/indices/recovery) ----------
    def recovery_api(g, p, b):
        names = node._resolve(g.get("index", "_all"))
        out = {}
        for n in names:
            svc = node.indices[n]
            shards = []
            for sid, e in enumerate(svc.shards):
                nbytes = sum(s.memory_bytes() for s in e.segments)
                ep = {"id": "node0", "name": "tpu-node-0",
                      "host": "localhost", "transport_address":
                      "127.0.0.1:9300", "ip": "127.0.0.1"}
                shards.append({
                    "id": sid, "type": "GATEWAY", "stage": "DONE",
                    "primary": True,
                    "start_time_in_millis": 0, "total_time_in_millis": 0,
                    "source": dict(ep), "target": dict(ep),
                    "index": {
                        "size": {"total_in_bytes": nbytes,
                                 "reused_in_bytes": 0,
                                 "recovered_in_bytes": nbytes,
                                 "percent": "100.0%"},
                        "files": {"total": len(e.segments), "reused": 0,
                                  "recovered": len(e.segments),
                                  "percent": "100.0%"},
                        "total_time_in_millis": 0,
                        "source_throttle_time_in_millis": 0,
                        "target_throttle_time_in_millis": 0},
                    "translog": {"recovered": 0, "total": -1,
                                 "total_on_start": 0, "percent": "-1.0%",
                                 "total_time_in_millis": 0},
                    "start": {"check_index_time_in_millis": 0,
                              "total_time_in_millis": 0},
                })
            out[n] = {"shards": shards}
        return 200, out
    c.register("GET", "/_recovery", recovery_api)
    c.register("GET", "/{index}/_recovery", recovery_api)

    _register_indices_routes(c, node)


def _resolve_lenient_impl(node, expr, p) -> list[str]:
    """IndicesOptions handling at the REST seam: ignore_unavailable skips
    missing concrete names; whitespace in comma lists is trimmed
    (ref action/support/IndicesOptions)."""
    iu = _pbool(p, "ignore_unavailable", False)
    out: list[str] = []
    expr = str(expr or "_all")
    for part in expr.split(","):
        part = part.strip()
        try:
            out.extend(n for n in node._resolve(part) if n not in out)
        except IndexMissingException:
            if not iu:
                raise
        except IndexClosedException:
            if not iu:    # ignore_unavailable also skips closed indices
                raise
    if not out and not _pbool(p, "allow_no_indices", True) \
            and ("*" in expr or expr == "_all"):
        raise IndexMissingException(expr)
    return out


def _expand_indices_impl(node, expr, p) -> tuple[list[str], list[str]]:
    """-> (open_names, closed_names) honoring expand_wildcards
    (open/closed/all/none; ref IndicesOptions.fromRequest)."""
    ew = (p.get("expand_wildcards", ["open"])[0] or "open").split(",")
    if "all" in ew:
        ew = ["open", "closed"]
    expr = str(expr or "_all")
    parts = [x.strip() for x in expr.split(",")]
    if "none" in ew:
        return ([x for x in parts if x in node.indices],
                [x for x in parts if x in node.closed])
    opens = []
    closeds = []
    for part in parts:
        if part in node.closed:
            # expand_wildcards governs WILDCARD expansion only; a closed
            # index named concretely always resolves (IndicesOptions)
            if part not in closeds:
                closeds.append(part)
            continue
        if "open" in ew:
            try:
                opens.extend(n for n in _resolve_lenient_impl(node, part, p)
                             if n not in opens)
            except IndexClosedException:      # closed reached via alias
                pass
        elif part in node.indices:
            opens.append(part)
    if "closed" in ew:
        closeds.extend(
            n for n in node.closed
            if n not in closeds and any(fnmatch.fnmatch(n, x)
                                        or x in ("_all", "*")
                                        for x in parts))
    return opens, closeds


def _flat_settings(svc) -> dict:
    """Flat 'index.'-prefixed settings map with the implicit defaults the
    reference always reports (ref RestGetSettingsAction string rendering)."""
    out = {"index.number_of_shards": str(svc.n_shards),
           "index.number_of_replicas": str(svc.n_replicas),
           "index.version.created": "2000000"}
    for k, v in dict(svc.settings).items():
        key = k if k.startswith("index.") else f"index.{k}"
        out[key] = str(v)
    return out


def _nest_flat(flat: dict) -> dict:
    out: dict = {}
    for k, v in flat.items():
        parts = k.split(".")
        node = out
        for p in parts[:-1]:
            nxt = node.get(p)
            if not isinstance(nxt, dict):
                nxt = {}
                node[p] = nxt
            node = nxt
        node[parts[-1]] = v
    return out


def _render_settings(svc, flat: bool = False) -> dict:
    f = _flat_settings(svc)
    return f if flat else _nest_flat(f)


def _write_shards(node: NodeService, index: str) -> dict:
    try:
        svc = node.indices[node._resolve(index)[0]]
        total = 1 + svc.n_replicas
    except Exception:  # noqa: BLE001
        total = 1
    return {"total": total, "successful": 1, "failed": 0}


def _source_filter_paths(src: dict, includes, excludes) -> dict:
    from ..search.shard_searcher import _filter_source
    if isinstance(includes, str):
        includes = [includes]
    if isinstance(excludes, str):
        excludes = [excludes]
    spec: dict = {}
    if includes:
        spec["includes"] = [p if "*" in p else p + "*" for p in includes] \
            + list(includes)
    if excludes:
        spec["excludes"] = list(excludes)
    return _filter_source(src, spec)


def _register_indices_routes(c: RestController, node: NodeService) -> None:
    """Admin/index APIs beyond the core CRUD set (alias CRUD, templates,
    settings, validate, segments, stats, cluster info) — the breadth the
    rest-api-spec YAML suites exercise (ref rest/action/admin/)."""

    def _resolve_lenient(expr, p):
        return _resolve_lenient_impl(node, expr, p)

    def _expand_indices(expr, p):
        return _expand_indices_impl(node, expr, p)

    # -- GET method variants the specs allow -------------------------------
    def refresh(g, p, b):
        node.refresh(g.get("index", "_all"))
        return 200, {"_shards": {"failed": 0}}
    c.register("GET", "/{index}/_refresh", refresh)
    c.register("GET", "/_refresh", refresh)

    def flush(g, p, b):
        node.flush(g.get("index", "_all"))
        return 200, {"_shards": {"failed": 0}}
    c.register("GET", "/{index}/_flush", flush)
    c.register("GET", "/_flush", flush)

    def optimize(g, p, b):
        node.force_merge(g.get("index", "_all"),
                         int(p.get("max_num_segments", ["1"])[0]))
        return 200, {"_shards": {"failed": 0}}
    c.register("GET", "/{index}/_optimize", optimize)
    c.register("GET", "/_optimize", optimize)

    # -- open / close (ref rest/action/admin/indices/open+close) ----------
    def close_index(g, p, b):
        node.close_index(g["index"])
        return 200, {"acknowledged": True}
    c.register("POST", "/{index}/_close", close_index)

    def open_index(g, p, b):
        node.open_index(g["index"])
        return 200, {"acknowledged": True}
    c.register("POST", "/{index}/_open", open_index)

    # -- aliases (ref cluster/metadata/MetaDataIndicesAliasesService) ------
    def _alias_map(index_expr: str | None, name: str | None):
        """-> {index: [matching aliases]} honoring wildcards in `name`."""
        names = node._resolve(index_expr or "_all")
        out: dict[str, list[str]] = {}
        for n in names:
            aliases = sorted(node.indices[n].aliases)
            if name and name not in ("_all", "*"):
                pats = name.split(",")
                aliases = [a for a in aliases
                           if any(fnmatch.fnmatch(a, pat) for pat in pats)]
            out[n] = aliases
        return out

    def put_alias(g, p, b):
        from ..node import alias_dict
        props = alias_dict({g["name"]: _json_body(b)})[g["name"]]
        for n in node._resolve(g["index"]):
            node.indices[n].aliases[g["name"]] = props
            node._persist_index_meta(node.indices[n])
        return 200, {"acknowledged": True}
    for pat in ("/{index}/_alias/{name}", "/{index}/_aliases/{name}",
                "/_alias/{name}", "/_aliases/{name}"):
        c.register("PUT", pat, put_alias)
        c.register("POST", pat, put_alias)

    def delete_alias(g, p, b):
        removed = False
        for n in node._resolve(g["index"]):
            svc = node.indices[n]
            match = [a for a in svc.aliases
                     if any(fnmatch.fnmatch(a, pat)
                            for pat in g["name"].split(","))] \
                if g["name"] not in ("_all", "*") else list(svc.aliases)
            for a in match:
                svc.aliases.pop(a, None)
                removed = True
            if match:
                node._persist_index_meta(svc)
        if not removed:
            return 404, {"error": f"aliases [{g['name']}] missing",
                         "status": 404}
        return 200, {"acknowledged": True}
    c.register("DELETE", "/{index}/_alias/{name}", delete_alias)
    c.register("DELETE", "/{index}/_aliases/{name}", delete_alias)

    def get_alias(g, p, b):
        amap = _alias_map(g.get("index"), g.get("name"))
        if g.get("name") and not any(amap.values()):
            if g.get("index"):
                # missing alias scoped to an existing index: empty body
                # (ref get_alias REST contract)
                return 200, {}
            return 404, {"error": f"alias [{g['name']}] missing",
                         "status": 404}
        def render_props(n, a):
            props = node.indices[n].aliases.get(a, {})
            return {k: v for k, v in props.items()
                    if k in ("filter", "index_routing", "search_routing")}
        return 200, {n: {"aliases": {a: render_props(n, a) for a in al}}
                     for n, al in amap.items()
                     if al or not g.get("name")}
    for pat in ("/_alias", "/_alias/{name}", "/{index}/_alias",
                "/{index}/_alias/{name}"):
        c.register("GET", pat, get_alias)

    def get_aliases_old(g, p, b):
        # the legacy `_aliases` GET contract: matching indices always
        # appear, each with its (possibly empty) aliases map, HTTP 200 —
        # no 404 for a missing alias (ref RestGetAliasesAction vs
        # RestGetIndicesAliasesAction)
        amap = _alias_map(g.get("index"), g.get("name"))
        def render_props(n, a):
            props = node.indices[n].aliases.get(a, {})
            return {k: v for k, v in props.items()
                    if k in ("filter", "index_routing", "search_routing")}
        return 200, {n: {"aliases": {a: render_props(n, a) for a in al}}
                     for n, al in amap.items()}
    for pat in ("/_aliases", "/_aliases/{name}", "/{index}/_aliases",
                "/{index}/_aliases/{name}"):
        c.register("GET", pat, get_aliases_old)

    def head_alias(g, p, b):
        amap = _alias_map(g.get("index"), g.get("name"))
        return (200 if any(amap.values()) else 404), {}
    c.register("HEAD", "/_alias/{name}", head_alias)
    c.register("HEAD", "/{index}/_alias/{name}", head_alias)

    def update_aliases(g, p, b):
        from ..node import alias_dict
        body = _json_body(b)
        for action in body.get("actions", []):
            (kind, spec), = action.items()
            indices = spec.get("indices") or [spec["index"]]
            aliases = spec.get("aliases") or [spec["alias"]]
            props = alias_dict({"x": {
                k: v for k, v in spec.items()
                if k in ("filter", "routing", "index_routing",
                         "search_routing")}})["x"]
            for expr in indices:
                for n in node._resolve(expr):
                    svc = node.indices[n]
                    for a in aliases:
                        if kind == "add":
                            svc.aliases[a] = props
                        else:
                            svc.aliases.pop(a, None)
                    node._persist_index_meta(svc)
        return 200, {"acknowledged": True}
    c.register("POST", "/_aliases", update_aliases)

    # -- templates ---------------------------------------------------------
    def _tpl_render(tpl: dict, flat: bool) -> dict:
        # settings render in the normalized index.* string form, nested by
        # default / flat with flat_settings (ref MetaDataIndexTemplateService
        # -> RestGetIndexTemplateAction settings serialization)
        out = dict(tpl)
        f = {}
        for k, v in (tpl.get("settings") or {}).items():
            key = k if k.startswith("index.") else f"index.{k}"
            f[key] = str(v)
        out["settings"] = f if flat else _nest_flat(f)
        if tpl.get("aliases"):
            from ..node import alias_dict
            out["aliases"] = alias_dict(tpl["aliases"])
        return out

    def get_template(g, p, b):
        name = g.get("name")
        flat = p.get("flat_settings", ["false"])[0] == "true"
        if name is None:
            return 200, {t: _tpl_render(v, flat)
                         for t, v in node.templates.items()}
        out = {t: _tpl_render(v, flat) for t, v in node.templates.items()
               if any(fnmatch.fnmatch(t, pat) for pat in name.split(","))}
        if not out and "*" not in name:
            return 404, {"error": f"template [{name}] missing",
                         "status": 404}
        return 200, out
    c.register("GET", "/_template", get_template)
    c.register("GET", "/_template/{name}", get_template)

    def delete_template(g, p, b):
        match = [t for t in node.templates
                 if fnmatch.fnmatch(t, g["name"])]
        if not match:
            if "*" in g["name"]:    # wildcard deletes are no-match tolerant
                return 200, {"acknowledged": True}
            return 404, {"error": f"template [{g['name']}] missing",
                         "status": 404}
        for t in match:
            del node.templates[t]
        node._persist_templates()
        return 200, {"acknowledged": True}
    c.register("DELETE", "/_template/{name}", delete_template)

    c.register("HEAD", "/_template/{name}",
               lambda g, p, b: ((200 if any(
                   fnmatch.fnmatch(t, g["name"]) for t in node.templates)
                   else 404), {}))

    # -- indices.get / settings -------------------------------------------
    _GET_FEATURES = {"_settings": "settings", "_mappings": "mappings",
                     "_mapping": "mappings", "_warmers": "warmers",
                     "_warmer": "warmers", "_aliases": "aliases",
                     "_alias": "aliases"}

    def get_index(g, p, b):
        flat = p.get("flat_settings", ["false"])[0] == "true"
        feats = None
        if g.get("feature"):
            feats = []
            for f in g["feature"].split(","):
                if f not in _GET_FEATURES:
                    raise RestError(
                        400, f"no handler for [GET /{g['index']}/{f}]")
                feats.append(_GET_FEATURES[f])
        out = {}
        opens, closeds = _expand_indices(g["index"], p)
        for n in opens:
            svc = node.indices[n]
            sections = {"aliases": {a: svc.aliases[a]
                                    for a in sorted(svc.aliases)},
                        "mappings": svc.mappings_dict(),
                        "settings": _render_settings(svc, flat),
                        "warmers": getattr(svc, "warmers", {})}
            out[n] = sections if feats is None \
                else {k: v for k, v in sections.items() if k in feats}
        for n in closeds:
            if n in out:
                continue
            meta = node.closed[n]
            f = {f"index.{k}" if not k.startswith("index.") else k: str(v)
                 for k, v in (meta.get("settings") or {}).items()}
            f.setdefault("index.number_of_shards", "1")
            f.setdefault("index.number_of_replicas", "0")
            sections = {"aliases": meta.get("aliases") or {},
                        "mappings": meta.get("mappings") or {},
                        "settings": f if flat else _nest_flat(f),
                        "warmers": {}}
            out[n] = sections if feats is None \
                else {k: v for k, v in sections.items() if k in feats}
        return 200, out
    c.register("GET", "/{index}", get_index)
    c.register("GET", "/{index}/{feature}", get_index)

    def get_settings(g, p, b):
        flat = p.get("flat_settings", ["false"])[0] == "true"
        sel = g.get("setting") or p.get("name", [None])[0]
        if sel in ("_all", "*"):
            sel = None
        out = {}
        opens, closeds = _expand_indices(g.get("index", "_all"), p)
        flats = [(n, _flat_settings(node.indices[n])) for n in opens]
        for n in closeds:
            if any(n == m for m, _ in flats):
                continue
            meta = node.closed[n]
            f = {k if k.startswith("index.") else f"index.{k}": str(v)
                 for k, v in (meta.get("settings") or {}).items()}
            f.setdefault("index.number_of_shards", "1")
            f.setdefault("index.number_of_replicas", "0")
            flats.append((n, f))
        for n, f in flats:
            if sel:
                pats = sel.split(",")
                f = {k: v for k, v in f.items()
                     if any(fnmatch.fnmatch(k, pat)
                            or fnmatch.fnmatch(k[6:], pat)
                            for pat in pats)}
            out[n] = {"settings": f if flat else _nest_flat(f)}
        return 200, out
    c.register("GET", "/_settings", get_settings)
    c.register("GET", "/_settings/{setting}", get_settings)
    c.register("GET", "/{index}/_settings", get_settings)
    c.register("GET", "/{index}/_settings/{setting}", get_settings)

    # runtime-updatable index settings (ref cluster/settings/
    # DynamicSettings.java:30 + IndexDynamicSettings): everything else is
    # STATIC and rejected on an open index, like the reference
    _DYNAMIC_INDEX_SETTINGS = (
        "number_of_replicas", "refresh_interval", "max_result_window",
        "translog.", "slowlog.", "search.slowlog.", "indexing.slowlog.",
        "blocks.", "routing.", "merge.", "gc_deletes", "warmer.",
        "mapping.", "auto_expand_replicas", "mapper.",
    )

    def _is_dynamic_setting(key: str) -> bool:
        k = key[6:] if key.startswith("index.") else key
        return any(k == d or (d.endswith(".") and k.startswith(d))
                   for d in _DYNAMIC_INDEX_SETTINGS)

    def _flatten_settings(obj, prefix="") -> dict:
        out = {}
        for k, v in obj.items():
            if isinstance(v, dict):
                out.update(_flatten_settings(v, f"{prefix}{k}."))
            else:
                out[f"{prefix}{k}"] = v
        return out

    def put_settings(g, p, b):
        body = _json_body(b)
        flat = body.get("settings", body)
        flat = flat.get("index", flat) if isinstance(
            flat.get("index", None), dict) else flat
        flat = _flatten_settings(flat)   # nested {"translog": {...}} form
        for k in flat:
            if not _is_dynamic_setting(k):
                raise RestError(
                    400, f"IllegalArgumentException: can't update non "
                         f"dynamic settings [[{k}]] for open indices")
        for n in _resolve_lenient(g.get("index", "_all"), p):
            svc = node.indices[n]
            data = dict(svc.settings)
            for k, v in flat.items():
                data[k] = v
            from ..common.settings import Settings
            svc.settings = Settings(data)
            nr = svc.settings.get("number_of_replicas",
                                  svc.settings.get(
                                      "index.number_of_replicas"))
            if nr is not None:
                svc.n_replicas = int(nr)
            dur = svc.settings.get("index.translog.durability",
                                   svc.settings.get("translog.durability"))
            if dur is not None:
                for e in svc.shards:     # applied LIVE to running engines
                    e.translog.durability = str(dur).lower()
            node._persist_index_meta(svc)
        return 200, {"acknowledged": True}
    c.register("PUT", "/_settings", put_settings)
    c.register("PUT", "/{index}/_settings", put_settings)

    # -- validate / explain / delete-by-query ------------------------------
    def _lucene_str(q) -> str:
        """Rough Lucene toString rendering of a parsed query (enough for
        the validate_query explain contract; ref Query.toString())."""
        (kind, spec), = q.items() if isinstance(q, dict) and q else \
            (("match_all", {}),)
        if kind == "match_all":
            return "ConstantScore(*:*)"
        if kind in ("term", "match"):
            (f, v), = spec.items()
            if isinstance(v, dict):
                v = v.get("value", v.get("query"))
            return f"{f}:{v}"
        if kind == "query_string":
            return str(spec.get("query", ""))
        return json.dumps(q, separators=(",", ":"))

    def validate_query(g, p, b):
        body = _json_body(b)
        query = body.get("query", {"match_all": {}})
        names = node._resolve(g.get("index", "_all"))
        valid = True
        err = None
        try:
            from ..search.query_parser import QueryParser
            mappers = node.indices[names[0]].mappers if names else None
            QueryParser(mappers).parse(query)
        except Exception as e:  # noqa: BLE001 — that's the point
            valid = False
            err = str(e)
        out = {"valid": valid,
               "_shards": {"total": 1, "successful": 1, "failed": 0}}
        if p.get("explain", ["false"])[0] == "true":
            expl = {"index": names[0] if names else "_all", "valid": valid}
            if err:
                expl["error"] = err
            else:
                expl["explanation"] = _lucene_str(query)
            out["explanations"] = [expl]
        return 200, out
    for pat in ("/_validate/query", "/{index}/_validate/query",
                "/{index}/{type}/_validate/query"):
        c.register("GET", pat, validate_query)
        c.register("POST", pat, validate_query)

    def explain_doc(g, p, b):
        body = _json_body(b)
        query = body.get("query", {"match_all": {}})
        concrete = node._resolve(g["index"])[0]   # alias -> concrete name
        out = node.search(g["index"], {
            "query": {"bool": {"must": [query],
                               "filter": [{"ids": {"values": [g["id"]]}}]}},
            "size": 1, "track_scores": True})
        hits = out["hits"]["hits"]
        matched = bool(hits)
        resp = {"_index": concrete, "_type": g.get("type", "_doc"),
                "_id": g["id"], "matched": matched}
        if matched:
            score = hits[0]["_score"] or 0.0
            resp["explanation"] = {"value": score,
                                   "description": "sum of:", "details": []}
        # URL _source params attach the fetched doc as a `get` section
        # (ref RestExplainAction fetchSource handling)
        s = p.get("_source", [None])[0]
        inc = p.get("_source_include", p.get("_source_includes", [None]))[0]
        exc = p.get("_source_exclude", p.get("_source_excludes", [None]))[0]
        if s is not None or inc or exc:
            got = node.get_doc(concrete, str(g["id"]))
            if got.found:
                gsec: dict = {"found": True}
                if s != "false":
                    src = got.source
                    if s not in (None, "true"):
                        src = _source_filter_paths(src, s.split(","), None)
                    if inc or exc:
                        src = _source_filter_paths(
                            src, inc.split(",") if inc else None,
                            exc.split(",") if exc else None)
                    gsec["_source"] = src
                resp["get"] = gsec
        return 200, resp
    c.register("GET", "/{index}/{type}/{id}/_explain", explain_doc)
    c.register("POST", "/{index}/{type}/{id}/_explain", explain_doc)

    def delete_by_query(g, p, b):
        body = _json_body(b)
        if not body and "q" not in p:
            raise RestError(400, "delete_by_query requires a query")
        deleted = node.delete_by_query(g["index"], body)
        return 200, {"_indices": {g["index"]: {"_shards": {
            "total": 1, "successful": 1, "failed": 0}}},
            "deleted": deleted}
    c.register("DELETE", "/{index}/_query", delete_by_query)
    c.register("DELETE", "/{index}/{type}/_query", delete_by_query)

    # -- segments / cluster info ------------------------------------------
    def segments_api(g, p, b):
        out = {}
        names = _resolve_lenient(g.get("index", "_all"), p)
        total = sum(node.indices[n].n_shards for n in names)
        for n in names:
            svc = node.indices[n]
            shards = {}
            for si, e in enumerate(svc.shards):
                shards[str(si)] = [{
                    "routing": {"state": "STARTED", "primary": True},
                    "num_committed_segments": len(e.segments),
                    "num_search_segments": len(e.segments),
                    "segments": {
                        # Lucene generation names start at _0; seg ids at 1
                        f"_{seg.seg_id - 1}": {
                            "generation": seg.seg_id,
                            "num_docs": seg.live_count,
                            "deleted_docs": seg.n_docs - seg.live_count,
                            "memory_in_bytes": seg.memory_bytes(),
                            "search": True, "committed": True,
                        } for seg in e.segments}}]
            out[n] = {"shards": shards}
        return 200, {"_shards": {"total": total, "successful": total,
                                 "failed": 0}, "indices": out}
    c.register("GET", "/_segments", segments_api)
    c.register("GET", "/{index}/_segments", segments_api)

    c.register("GET", "/_cluster/pending_tasks",
               lambda g, p, b: (200, {"tasks": []}))
    def get_cluster_settings(g, p, b):
        cs = getattr(node, "_cluster_settings",
                     {"persistent": {}, "transient": {}})
        return 200, {"persistent": dict(cs["persistent"]),
                     "transient": dict(cs["transient"])}

    def put_cluster_settings(g, p, b):
        # per-component logger levels apply LIVE (ref
        # common/logging + RestClusterUpdateSettingsAction: the
        # `logger.<component>: <level>` dynamic settings)
        import logging as _logging
        body = _json_body(b)
        cs = getattr(node, "_cluster_settings", None)
        if cs is None:
            cs = node._cluster_settings = {"persistent": {},
                                           "transient": {}}
        def logger_for(k: str):
            name = k[len("logger."):]
            return _logging.getLogger(
                "elasticsearch_tpu" if name in ("_root", "")
                else f"elasticsearch_tpu.{name}")
        for scope in ("persistent", "transient"):
            for k, v in _flatten_settings(body.get(scope) or {}).items():
                if v is None:
                    cs[scope].pop(k, None)
                    if k.startswith("logger."):
                        # null RESTORES the default (inherit from parent)
                        logger_for(k).setLevel(_logging.NOTSET)
                    continue
                cs[scope][k] = v
                if k.startswith("logger."):
                    name = str(v).upper()
                    # ES supports TRACE below DEBUG; register it once
                    if name == "TRACE":
                        _logging.addLevelName(5, "TRACE")
                        lvl = 5
                    else:
                        lvl = getattr(_logging, name, None)
                    if isinstance(lvl, int):
                        logger_for(k).setLevel(lvl)
                    else:
                        raise RestError(
                            400, f"IllegalArgumentException: unknown "
                                 f"logger level [{v}] for [{k}]")
        return 200, {"acknowledged": True,
                     "persistent": dict(cs["persistent"]),
                     "transient": dict(cs["transient"])}
    c.register("GET", "/_cluster/settings", get_cluster_settings)
    c.register("PUT", "/_cluster/settings", put_cluster_settings)

    _BLOCK_IDS = {"read_only": ("5", "index read-only (api)"),
                  "read": ("7", "index read (api)"),
                  "write": ("8", "index write (api)"),
                  "metadata": ("9", "index metadata (api)")}

    def cluster_state(g, p, b):
        metrics = set((g.get("metric") or "_all").split(","))
        idx_expr = g.get("index")
        if idx_expr:
            opens, closeds = _expand_indices(idx_expr, p)
        else:
            opens, closeds = list(node.indices), list(node.closed)
        out: dict = {"cluster_name": node.cluster_name,
                     "master_node": "tpu-node-0"}
        if metrics & {"_all", "metadata"}:
            meta = {"indices": {}, "templates": dict(node.templates)}
            for n in opens:
                svc = node.indices[n]
                meta["indices"][n] = {
                    "state": "open",
                    "aliases": sorted(svc.aliases),
                    "mappings": svc.mappings_dict(),
                    "settings": _render_settings(svc)}
            for n in closeds:
                cm = node.closed[n]
                meta["indices"][n] = {
                    "state": "close",
                    "aliases": sorted(cm.get("aliases") or {}),
                    "mappings": cm.get("mappings") or {},
                    "settings": _nest_flat(
                        {k if k.startswith("index.") else f"index.{k}":
                         str(v)
                         for k, v in (cm.get("settings") or {}).items()})}
            out["metadata"] = meta
        if metrics & {"_all", "nodes"}:
            out["nodes"] = {"tpu-node-0": {"name": "tpu-node-0"}}
        if metrics & {"_all", "routing_table"}:
            out["routing_table"] = {"indices": {
                n: {"shards": {}} for n in opens}}
        if metrics & {"_all", "routing_nodes", "routing_table"}:
            out["routing_nodes"] = {"unassigned": [], "nodes": {
                "tpu-node-0": []}}
        if metrics & {"_all", "blocks"}:
            blocks: dict = {}
            bi: dict = {}
            for n in opens:
                ib = {}
                for key, (bid, desc) in _BLOCK_IDS.items():
                    v = node.indices[n].settings.get(f"index.blocks.{key}")
                    if str(v).lower() == "true":
                        ib[bid] = {"description": desc, "retryable": False,
                                   "levels": ["write", "metadata_write"]}
                if ib:
                    bi[n] = ib
            for n in closeds:
                bi[n] = {"4": {"description": "index closed",
                               "retryable": False,
                               "levels": ["read", "write"]}}
            if bi:
                blocks["indices"] = bi
            out["blocks"] = blocks
        return 200, out
    c.register("GET", "/_cluster/state", cluster_state)
    c.register("GET", "/_cluster/state/{metric}", cluster_state)
    c.register("GET", "/_cluster/state/{metric}/{index}", cluster_state)

    def cluster_reroute(g, p, b):
        # ref cluster/routing/allocation/command/* + RestClusterRerouteAction
        # (single-node build: commands are explained, never applied; the
        # real relocation machinery lives in cluster/state.py rebalance)
        body = _json_body(b) if b else {}
        explanations = []
        for cmd in (body.get("commands") or []):
            (kind, params), = cmd.items()
            params = {"allow_primary": False, **(params or {})}
            explanations.append({
                "command": kind,
                "parameters": params,
                "decisions": [{
                    "decider": f"{kind}_allocation_command",
                    "decision": "NO",
                    "explanation": f"[{kind}] cannot apply: no matching "
                                   f"started shard copy on this node"}]})
        metric = set((p.get("metric", [""])[0] or "").split(",")) - {""}
        state: dict = {"version": 1, "master_node": "tpu-node-0"}
        # metadata is EXCLUDED from the default reroute response
        # (ref RestClusterRerouteAction.DEFAULT_METRICS)
        if not metric or "nodes" in metric or "_all" in metric:
            if not metric or "_all" in metric:
                state["nodes"] = {"tpu-node-0": {"name": "tpu-node-0"}}
            elif "nodes" in metric:
                state["nodes"] = {"tpu-node-0": {"name": "tpu-node-0"}}
        if "metadata" in metric or "_all" in metric:
            state["metadata"] = {"indices": {
                n: {"state": "open"} for n in node.indices}}
        if not metric or "routing_table" in metric or "_all" in metric:
            state["routing_table"] = {"indices": {
                n: {"shards": {}} for n in node.indices}}
        out = {"acknowledged": True, "state": state}
        if _pbool(p, "explain", False):
            out["explanations"] = explanations
        return 200, out
    c.register("POST", "/_cluster/reroute", cluster_reroute)

    # -- _cat (RestTable contract: v/h/help, aligned columns) --------------
    from . import cat as _cat

    def cat_count(g, p, b):
        names = node._resolve(g.get("index", "_all"))
        total = sum(node.indices[n].doc_count() for n in names)
        return 200, _cat.render(p, [
            ("epoch", "seconds since 1970-01-01 00:00:00"),
            ("timestamp", "time in HH:MM:SS"),
            ("count", "the document count")],
            [{**_cat.now_cols(), "count": total}])
    c.register("GET", "/_cat/count", cat_count)
    c.register("GET", "/_cat/count/{index}", cat_count)

    def cat_health(g, p, b):
        h = node.cluster_health()
        return 200, _cat.render(p, [
            ("epoch", "seconds since 1970-01-01 00:00:00"),
            ("timestamp", "time in HH:MM:SS"),
            ("cluster", "cluster name"), ("status", "health status"),
            ("node.total", "total number of nodes"),
            ("node.data", "number of nodes that can store data"),
            ("shards", "total number of shards"),
            ("pri", "number of primary shards"),
            ("relo", "number of relocating nodes"),
            ("init", "number of initializing nodes"),
            ("unassign", "number of unassigned shards"),
            ("pending_tasks", "number of pending tasks")],
            [{**_cat.now_cols(), "cluster": h["cluster_name"],
              "status": h["status"], "node.total": h["number_of_nodes"],
              "node.data": h["number_of_data_nodes"],
              "shards": h["active_shards"],
              "pri": h["active_primary_shards"],
              "relo": h["relocating_shards"],
              "init": h["initializing_shards"],
              "unassign": h["unassigned_shards"],
              "pending_tasks": h["number_of_pending_tasks"]}])
    c.register("GET", "/_cat/health", cat_health)

    def cat_indices(g, p, b):
        rows = []
        for n in sorted(node._resolve(g.get("index", "_all"))):
            svc = node.indices[n]
            size = sum(e.segment_stats()["memory_in_bytes"]
                       for e in svc.shards)
            deleted = sum(e.segment_stats()["deleted"] for e in svc.shards)
            rc = node.caches.request_cache.index_stats(n)
            rc_ops = svc.request_cache_hits + svc.request_cache_misses
            rows.append({
                "health": "green" if svc.n_replicas == 0 else "yellow",
                "status": "open", "index": n, "pri": svc.n_shards,
                "rep": svc.n_replicas, "docs.count": svc.doc_count(),
                "docs.deleted": deleted,
                "store.size": _cat.human_bytes(size),
                "pri.store.size": _cat.human_bytes(size),
                "search.rate": f"{svc.meters['search'].rate(60):.2f}",
                "indexing.rate":
                    f"{svc.meters['indexing'].rate(60):.2f}",
                "request_cache.memory": _cat.human_bytes(rc["bytes"]),
                "request_cache.hit_ratio":
                    f"{svc.request_cache_hits / rc_ops:.2f}"
                    if rc_ops else ""})
        for n in sorted(node.closed):
            rows.append({"health": "green", "status": "close", "index": n,
                         "pri": "", "rep": "", "docs.count": "",
                         "docs.deleted": "", "store.size": "",
                         "pri.store.size": "", "search.rate": "",
                         "indexing.rate": "", "request_cache.memory": "",
                         "request_cache.hit_ratio": ""})
        return 200, _cat.render(p, [
            ("health", "current health status"), ("status", "open/close"),
            ("index", "index name"), ("pri", "number of primary shards"),
            ("rep", "number of replica shards"),
            ("docs.count", "available docs"),
            ("docs.deleted", "deleted docs"),
            ("store.size", "store size of primaries & replicas"),
            ("pri.store.size", "store size of primaries"),
            ("search.rate", "1m EWMA searches per second"),
            ("indexing.rate", "1m EWMA indexing ops per second"),
            ("request_cache.memory", "request cache bytes for this index"),
            ("request_cache.hit_ratio",
             "request cache hits / lookups")], rows,
            aliases={"sr": "search.rate", "ir": "indexing.rate",
                     "rcm": "request_cache.memory",
                     "rchr": "request_cache.hit_ratio"})
    c.register("GET", "/_cat/indices", cat_indices)
    c.register("GET", "/_cat/indices/{index}", cat_indices)

    def cat_aliases(g, p, b):
        rows = []
        for n, svc in sorted(node.indices.items()):
            for a in sorted(svc.aliases):
                if g.get("name") and not any(
                        fnmatch.fnmatch(a, pat)
                        for pat in g["name"].split(",")):
                    continue
                props = svc.aliases[a]
                rows.append({"alias": a, "index": n,
                             "filter": "*" if props.get("filter") else "-",
                             "routing.index":
                                 props.get("index_routing", "-") or "-",
                             "routing.search":
                                 props.get("search_routing", "-") or "-"})
        return 200, _cat.render(p, [
            ("alias", "alias name"), ("index", "index the alias points to"),
            ("filter", "filter"), ("routing.index", "index routing"),
            ("routing.search", "search routing")], rows)
    c.register("GET", "/_cat/aliases", cat_aliases)
    c.register("GET", "/_cat/aliases/{name}", cat_aliases)

    def cat_shards(g, p, b):
        rows = []
        for n in sorted(node._resolve(g.get("index", "_all"))):
            svc = node.indices[n]
            for si, e in enumerate(svc.shards):
                size = e.segment_stats()["memory_in_bytes"]
                rows.append({"index": n, "shard": si, "prirep": "p",
                             "state": "STARTED", "docs": e.doc_count(),
                             "store": _cat.human_bytes(size),
                             "ip": "127.0.0.1", "node": "tpu-node-0"})
                shadow = str(svc.settings.get(
                    "shadow_replicas",
                    svc.settings.get("index.shadow_replicas",
                                     False))).lower() == "true"
                for _ in range(svc.n_replicas):
                    rows.append({"index": n, "shard": si,
                                 "prirep": "s" if shadow else "r",
                                 "state": "UNASSIGNED", "docs": "",
                                 "store": "", "ip": "", "node": ""})
        return 200, _cat.render(p, [
            ("index", "index name"), ("shard", "shard id"),
            ("prirep", "primary or replica"), ("state", "shard state"),
            ("docs", "number of docs"), ("store", "store size"),
            ("ip", "node ip"), ("node", "node name")], rows)
    c.register("GET", "/_cat/shards", cat_shards)
    c.register("GET", "/_cat/shards/{index}", cat_shards)

    # every pool name the reference's table shows (ThreadPool.Names); pools
    # this build doesn't run report zeros with their reference pool type
    _TP_ALL = ["bulk", "flush", "generic", "get", "index", "management",
               "optimize", "percolate", "refresh", "search", "snapshot",
               "suggest", "warmer"]
    _TP_TYPE = {"bulk": "fixed", "index": "fixed", "search": "fixed",
                "get": "fixed", "percolate": "fixed", "suggest": "fixed",
                "generic": "cached", "management": "scaling",
                "flush": "scaling", "optimize": "scaling",
                "refresh": "scaling", "snapshot": "scaling",
                "warmer": "scaling"}
    # short-form column aliases (ref RestThreadPoolAction's per-pool alias
    # scheme): <pool prefix> + a/q/r/s/l/c/t for active/queue/rejected/
    # size/largest/completed/type, e.g. h=sq,sr,sl selects the search
    # pool's live queue depth, rejections and high-water queue mark
    _TP_PFX = {"bulk": "b", "flush": "f", "generic": "ge", "get": "g",
               "index": "i", "management": "ma", "optimize": "o",
               "percolate": "p", "refresh": "r", "search": "s",
               "snapshot": "sn", "suggest": "su", "warmer": "w"}
    _TP_ALIAS = {"h": "host", "i": "ip", "po": "port", "p": "pid"}
    for _pool, _pfx in _TP_PFX.items():
        for _short, _col in (("a", "active"), ("q", "queue"),
                             ("r", "rejected"), ("s", "size"),
                             ("l", "largest"), ("c", "completed"),
                             ("t", "type"), ("qs", "queueSize")):
            _TP_ALIAS[f"{_pfx}{_short}"] = f"{_pool}.{_col}"

    def cat_thread_pool(g, p, b):
        # ref rest/action/cat/RestThreadPoolAction.java:108-150 — one row
        # per node; default columns host/ip + bulk/index/search gauges
        st = node.thread_pool.stats()
        full = p.get("full_id", ["false"])[0] == "true"
        row = {"id": "tpu-node-0" if full else "tpu0",
               "pid": os.getpid(), "host": "localhost",
               "ip": "127.0.0.1", "port": 9300}
        cols = [("id", "unique node id"), ("pid", "process id"),
                ("host", "host name"), ("ip", "ip address"),
                ("port", "bound transport port")]
        for name in _TP_ALL:
            s = st.get(name)
            typ = _TP_TYPE[name]
            row[f"{name}.type"] = typ
            row[f"{name}.active"] = s["active"] if s else 0
            row[f"{name}.size"] = s["threads"] if s else 0
            row[f"{name}.queue"] = s["queue"] if s else 0
            row[f"{name}.queueSize"] = (s["queue_size"] if s
                                        and s["queue_size"] > 0 else "")
            row[f"{name}.rejected"] = s["rejected"] if s else 0
            row[f"{name}.largest"] = s["largest"] if s else 0
            row[f"{name}.completed"] = s["completed"] if s else 0
            row[f"{name}.min"] = s["threads"] if s and typ == "fixed" else ""
            row[f"{name}.max"] = s["threads"] if s and typ == "fixed" else ""
            row[f"{name}.keepAlive"] = "" if typ == "fixed" else "5m"
            for col in ("type", "active", "size", "queue", "queueSize",
                        "rejected", "largest", "completed", "min", "max",
                        "keepAlive"):
                cols.append((f"{name}.{col}", f"{name} pool {col}"))
        defaults = ["host", "ip"] + [f"{n}.{c}"
                                     for n in ("bulk", "index", "search")
                                     for c in ("active", "queue", "rejected")]
        return 200, _cat.render(p, cols, [row], defaults=defaults,
                                aliases=_TP_ALIAS)
    c.register("GET", "/_cat/thread_pool", cat_thread_pool)

    def cat_plugins(g, p, b):
        # ref rest/action/cat/RestPluginsAction
        infos = node.plugins.infos() if getattr(node, "plugins", None) \
            else []
        rows = [{"name": "tpu-node-0", "component": i["name"],
                 "version": i["version"], "type": "j",
                 "description": i["description"]} for i in infos]
        return 200, _cat.render(p, [
            ("name", "node name"), ("component", "plugin name"),
            ("version", "plugin version"), ("type", "plugin type"),
            ("description", "plugin description")], rows)
    c.register("GET", "/_cat/plugins", cat_plugins)

    def cat_segments(g, p, b):
        rows = []
        for n in sorted(node._resolve(g.get("index", "_all"))):
            svc = node.indices[n]
            for si, e in enumerate(svc.shards):
                for seg in e.segments:
                    rows.append({
                        "index": n, "shard": si, "prirep": "p",
                        "ip": "127.0.0.1", "segment": f"_{seg.seg_id}",
                        "generation": seg.seg_id,
                        "docs.count": seg.live_count,
                        "docs.deleted": seg.n_docs - seg.live_count,
                        "size": _cat.human_bytes(seg.memory_bytes()),
                        "size.memory": seg.memory_bytes(),
                        "committed": str(
                            seg.seg_id in e.store.persisted).lower(),
                        "searchable": "true", "version": "2.0.0",
                        "compound": "false"})
        return 200, _cat.render(p, [
            ("index", "index name"), ("shard", "shard id"),
            ("prirep", "primary or replica"), ("ip", "node ip"),
            ("segment", "segment name"), ("generation", "generation"),
            ("docs.count", "number of docs in segment"),
            ("docs.deleted", "number of deleted docs in segment"),
            ("size", "segment size in bytes"),
            ("size.memory", "segment memory in bytes"),
            ("committed", "is segment committed"),
            ("searchable", "is segment searched"),
            ("version", "version"), ("compound", "is segment compound")],
            rows)
    c.register("GET", "/_cat/segments", cat_segments)
    c.register("GET", "/_cat/segments/{index}", cat_segments)

    def cat_nodes(g, p, b):
        import resource
        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        heap = rss_kb * 1024
        row = {"host": "localhost", "ip": "127.0.0.1",
               "heap.percent": 42, "ram.percent": 50, "load": "1.00",
               "node.role": "d", "master": "*", "name": "tpu-node-0",
               "heap.current": _cat.human_bytes(heap),
               "heap.max": _cat.human_bytes(4 << 30),
               "file_desc.current": 256, "file_desc.percent": 1,
               "file_desc.max": 65536}
        return 200, _cat.render(p, [
            ("host", "host name"), ("ip", "ip address"),
            ("heap.percent", "used heap ratio"),
            ("ram.percent", "used machine memory ratio"),
            ("load", "most recent load avg"),
            ("node.role", "d:data node, c:client node"),
            ("master", "*:current master, m:master eligible"),
            ("name", "node name"),
            ("heap.current", "used heap"), ("heap.max", "max heap"),
            ("file_desc.current", "used file descriptors"),
            ("file_desc.percent", "used file descriptor ratio"),
            ("file_desc.max", "max file descriptors")],
            [row],
            defaults=["host", "ip", "heap.percent", "ram.percent", "load",
                      "node.role", "master", "name"])
    c.register("GET", "/_cat/nodes", cat_nodes)

    def cat_tasks(g, p, b):
        infos = node.tasks.task_infos(
            actions=p.get("actions", [None])[0], detailed=True)
        rows = [{"action": i["action"], "task_id": tid,
                 "parent_task_id": i.get("parent_task_id", "-"),
                 "type": i["type"], "start_time": i["start_time_in_millis"],
                 "running_time": f"{i['running_time_in_nanos'] // 1000}micros",
                 "node": i["node"],
                 "description": i.get("description", "")}
                for tid, i in sorted(infos.items())]
        return 200, _cat.render(p, [
            ("action", "task action"), ("task_id", "task id"),
            ("parent_task_id", "parent task id"), ("type", "task type"),
            ("start_time", "start time in millis"),
            ("running_time", "running time"), ("node", "node name"),
            ("description", "task description")], rows,
            defaults=["action", "task_id", "parent_task_id", "type",
                      "start_time", "running_time", "node"])
    c.register("GET", "/_cat/tasks", cat_tasks)

    def cat_master(g, p, b):
        return 200, _cat.render(p, [
            ("id", "node id"), ("host", "host name"),
            ("ip", "ip address"), ("node", "node name")],
            [{"id": "tpu0", "host": "localhost", "ip": "127.0.0.1",
              "node": "tpu-node-0"}])
    c.register("GET", "/_cat/master", cat_master)

    def cat_pending_tasks(g, p, b):
        return 200, _cat.render(p, [
            ("insertOrder", "task insertion order"),
            ("timeInQueue", "how long task has been in queue"),
            ("priority", "task priority"),
            ("source", "task source")], [])
    c.register("GET", "/_cat/pending_tasks", cat_pending_tasks)

    def cat_allocation(g, p, b):
        nid = g.get("node_id")
        if nid and nid not in ("tpu-node-0", "tpu0", "_master", "*",
                               "_all", "_local"):
            return 200, _cat.render(p, [("shards", "")], [])
        total = sum(e.segment_stats()["memory_in_bytes"]
                    for svc in node.indices.values() for e in svc.shards)
        n_shards = sum(svc.n_shards for svc in node.indices.values())
        unit = p.get("bytes", [None])[0]
        scale = {"b": 1, "k": 1 << 10, "m": 1 << 20,
                 "g": 1 << 30, "t": 1 << 40}.get(unit)
        size = (lambda n: int(n // scale)) if scale             else _cat.human_bytes
        return 200, _cat.render(p, [
            ("shards", "number of shards on node"),
            ("disk.used", "disk used (total, not just ES)"),
            ("disk.avail", "disk available"),
            ("disk.total", "total capacity"),
            ("disk.percent", "percent disk used"),
            ("host", "host name"), ("ip", "ip address"),
            ("node", "node name")],
            [{"shards": n_shards, "disk.used": size(total),
              "disk.avail": size(100 << 30),
              "disk.total": size(100 << 30),
              "disk.percent": 1, "host": "localhost", "ip": "127.0.0.1",
              "node": "tpu-node-0"}])
    c.register("GET", "/_cat/allocation", cat_allocation)
    c.register("GET", "/_cat/allocation/{node_id}", cat_allocation)

    def cat_fielddata(g, p, b):
        # loaded per-field fielddata bytes across every segment (ref
        # rest/action/cat/RestFielddataAction.java — one column per field)
        per_field: dict[str, int] = {}
        for svc in node.indices.values():
            for e in svc.shards:
                for seg in e.segments:
                    for f, nb in seg.fielddata_bytes().items():
                        per_field[f] = per_field.get(f, 0) + nb
        fsel = g.get("fields") or ",".join(p.get("fields", []))
        if fsel:
            want = fsel.split(",")
            per_field = {f: nb for f, nb in per_field.items() if f in want}
        cols = [("id", "node id"), ("host", "host name"),
                ("ip", "ip address"), ("node", "node name"),
                ("total", "total field data usage")]
        row = {"id": "tpu0", "host": "localhost", "ip": "127.0.0.1",
               "node": "tpu-node-0",
               "total": _cat.human_bytes(sum(per_field.values()))}
        if p.get("help", ["false"])[0] in ("false", None):
            for f in sorted(per_field):
                cols.append((f, f"field data usage of [{f}]"))
                row[f] = _cat.human_bytes(per_field[f])
        return 200, _cat.render(p, cols, [row])
    c.register("GET", "/_cat/fielddata", cat_fielddata)
    c.register("GET", "/_cat/fielddata/{fields}", cat_fielddata)

    def cat_recovery(g, p, b):
        rows = []
        for n in sorted(node._resolve(g.get("index", "_all"))):
            svc = node.indices[n]
            for si in range(svc.n_shards):
                rows.append({"index": n, "shard": si, "time": 0,
                             "type": "gateway", "stage": "done",
                             "source_host": "localhost",
                             "target_host": "localhost",
                             "repository": "n/a", "snapshot": "n/a",
                             "files": 0, "files_percent": "100.0%",
                             "bytes": 0, "bytes_percent": "100.0%",
                             "total_files": 0, "total_bytes": 0,
                             "translog": 0, "translog_percent": "100.0%",
                             "total_translog": 0})
        return 200, _cat.render(p, [
            ("index", "index name"), ("shard", "shard id"),
            ("time", "recovery time"), ("type", "recovery type"),
            ("stage", "recovery stage"),
            ("source_host", "source host"), ("target_host", "target host"),
            ("repository", "repository"), ("snapshot", "snapshot"),
            ("files", "number of files"),
            ("files_percent", "percent of files recovered"),
            ("bytes", "number of bytes"),
            ("bytes_percent", "percent of bytes recovered"),
            ("total_files", "total number of files"),
            ("total_bytes", "total number of bytes"),
            ("translog", "translog operations recovered"),
            ("translog_percent", "percent of translog recovered"),
            ("total_translog", "total translog operations")], rows)
    c.register("GET", "/_cat/recovery", cat_recovery)
    c.register("GET", "/_cat/recovery/{index}", cat_recovery)


    # -- indices.stats (reference response shape: CommonStats sections,
    #    metric/level/fields/groups/types filtering; ref
    #    action/admin/indices/stats/CommonStats.java + RestIndicesStatsAction)
    _STATS_METRICS = {
        "docs", "store", "indexing", "get", "search", "merge", "refresh",
        "flush", "warmer", "filter_cache", "id_cache", "fielddata",
        "percolate", "completion", "segments", "translog", "suggest",
        "recovery", "query_cache", "request_cache",
    }

    def _csv_param(p, name):
        v = p.get(name)
        if not v:
            return None
        return [x.strip(" '\"[]") for x in ",".join(v).split(",")
                if x.strip(" '\"[]")]

    def index_stats_v2(g, p, b):
        names = node._resolve(g.get("index", "_all"))
        metric = g.get("metric") or ",".join(p.get("metric", [])) or "_all"
        want = set(x.strip() for x in metric.split(","))
        if "_all" in want:
            want = set(_STATS_METRICS)
        level = p.get("level", ["indices"])[0]
        fields_sel = _csv_param(p, "fields")
        fd_sel = fields_sel or _csv_param(p, "fielddata_fields")
        comp_sel = fields_sel or _csv_param(p, "completion_fields")
        groups_sel = _csv_param(p, "groups")
        types_sel = _csv_param(p, "types")

        def shard_stats(svc):
            seg = [e.segment_stats() for e in svc.shards]
            fd_fields: dict[str, int] = {}
            comp_fields: dict[str, int] = {}
            for e in svc.shards:
                for s in e.segments:
                    for f, nb in s.fielddata_bytes().items():
                        fd_fields[f] = fd_fields.get(f, 0) + nb
                    for f, kc in s.keywords.items():
                        ft_types = [dm.fields.get(f)
                                    for dm in svc.mappers._mappers.values()]
                        if any(ft is not None and ft.type == "completion"
                               for ft in ft_types):
                            comp_fields[f] = comp_fields.get(f, 0) \
                                + int(kc.ords.size) * 4 \
                                + sum(len(v) for v in kc.values)
            out = {}
            if "docs" in want:
                out["docs"] = {"count": svc.doc_count(),
                               "deleted": sum(s["deleted"] for s in seg)}
            if "store" in want:
                out["store"] = {"size_in_bytes": sum(
                    s["memory_in_bytes"] for s in seg),
                    "throttle_time_in_millis": 0}
            if "indexing" in want:
                ix = {"index_total": svc.indexing_stats["index_total"],
                      "index_time_in_millis": 0, "index_current": 0,
                      "index_rate_1m": svc.meters["indexing"].rate(60),
                      "index_rate_5m": svc.meters["indexing"].rate(300),
                      "index_rate_15m": svc.meters["indexing"].rate(900),
                      "delete_total": svc.indexing_stats["delete_total"],
                      "noop_update_total": 0, "is_throttled": False,
                      "throttle_time_in_millis": 0}
                if types_sel:
                    ix["types"] = {
                        t: {"index_total": c, "index_time_in_millis": 0,
                            "index_current": 0, "delete_total": 0}
                        for t, c in svc.indexing_stats["types"].items()
                        if any(fnmatch.fnmatch(t, x) for x in types_sel)}
                out["indexing"] = ix
            if "get" in want:
                out["get"] = {"total": svc.get_total, "exists_total": 0,
                              "missing_total": 0, "current": 0,
                              "time_in_millis": 0}
            if "search" in want:
                se = {"open_contexts": 0,
                      "query_total": svc.query_total,
                      "query_time_in_millis": 0, "query_current": 0,
                      "query_rate_1m": svc.meters["search"].rate(60),
                      "query_rate_5m": svc.meters["search"].rate(300),
                      "query_rate_15m": svc.meters["search"].rate(900),
                      "fetch_total": svc.query_total,
                      "fetch_time_in_millis": 0, "fetch_current": 0}
                if groups_sel:
                    se["groups"] = {
                        t: {"query_total": c, "query_time_in_millis": 0,
                            "query_current": 0, "fetch_total": c,
                            "fetch_time_in_millis": 0, "fetch_current": 0}
                        for t, c in svc.search_groups.items()
                        if any(fnmatch.fnmatch(t, x) for x in groups_sel)}
                # device-lane split: packed one-program serves + plan-shape
                # batched serves vs general per-segment path — the
                # "how much of the load rides one device program" gauge
                se["lanes"] = dict(svc.search_stats)
                out["search"] = se
            if "merge" in want:
                out["merges"] = {
                    "current": 0, "current_docs": 0, "current_size_in_bytes": 0,
                    "total": sum(e.merge_count for e in svc.shards),
                    "total_time_in_millis": 0, "total_docs": 0,
                    "total_size_in_bytes": 0}
            if "refresh" in want:
                out["refresh"] = {"total": sum(e.refresh_count
                                               for e in svc.shards),
                                  "total_time_in_millis": 0}
            if "flush" in want:
                out["flush"] = {"total": sum(
                    getattr(e, "flush_count", 0) for e in svc.shards),
                    "total_time_in_millis": 0}
            if "warmer" in want:
                out["warmer"] = {"current": 0, "total": 0,
                                 "total_time_in_millis": 0}
            rc = node.caches.request_cache.index_stats(svc.name)
            if "filter_cache" in want:
                # the query-plan cache is this engine's filter/query-cache
                # analog (compiled executables, not doc-id bitsets); its
                # per-index share keyed by the plan key's index component
                plan_bytes = plan_entries = 0
                for k, _v, w in node.caches.query_plan.entries_snapshot():
                    if k[0] == svc.name:
                        plan_bytes += w
                        plan_entries += 1
                out["filter_cache"] = {"memory_size_in_bytes": plan_bytes,
                                       "entries": plan_entries,
                                       "evictions":
                                           node.caches.query_plan.evictions}
            if "query_cache" in want:
                # wire-format parity: ES 2.0 clients read the request
                # cache's numbers under this section name too
                out["query_cache"] = {
                    "memory_size_in_bytes": rc["bytes"],
                    "hit_count": svc.request_cache_hits,
                    "miss_count": svc.request_cache_misses,
                    "evictions": rc["evictions"]}
            if "request_cache" in want:
                out["request_cache"] = {
                    "memory_size_in_bytes": rc["bytes"],
                    "entries": rc["count"],
                    "hit_count": svc.request_cache_hits,
                    "miss_count": svc.request_cache_misses,
                    "evictions": rc["evictions"]}
            if "id_cache" in want:
                # parent/child id maps ride the fielddata tier here: the
                # live bytes of _parent/_uid columns, usually 0
                out["id_cache"] = {"memory_size_in_bytes": sum(
                    nb for f, nb in fd_fields.items()
                    if f.startswith(("_parent", "_uid")))}
            if "fielddata" in want:
                fd = {"memory_size_in_bytes": sum(fd_fields.values()),
                      "evictions":
                          node.caches.fielddata.evictions_of(svc.name)}
                if fd_sel:
                    fd["fields"] = {
                        f: {"memory_size_in_bytes": nb}
                        for f, nb in fd_fields.items()
                        if any(fnmatch.fnmatch(f, x) for x in fd_sel)}
                out["fielddata"] = fd
            if "percolate" in want:
                out["percolate"] = {"total": 0, "time_in_millis": 0,
                                    "current": 0,
                                    "memory_size_in_bytes": -1,
                                    "memory_size": "-1b", "queries": 0}
            if "completion" in want:
                co = {"size_in_bytes": sum(comp_fields.values())}
                if comp_sel:
                    co["fields"] = {
                        f: {"size_in_bytes": nb}
                        for f, nb in comp_fields.items()
                        if any(fnmatch.fnmatch(f, x) for x in comp_sel)}
                out["completion"] = co
            if "segments" in want:
                out["segments"] = {
                    "count": sum(s["count"] for s in seg),
                    "memory_in_bytes": sum(s["memory_in_bytes"]
                                           for s in seg)}
            if "translog" in want:
                out["translog"] = {"operations": sum(
                    len(list(e.translog.snapshot())) for e in svc.shards),
                    "size_in_bytes": 0}
            if "suggest" in want:
                out["suggest"] = {"total": 0, "time_in_millis": 0,
                                  "current": 0}
            if "recovery" in want:
                out["recovery"] = {"current_as_source": 0,
                                   "current_as_target": 0,
                                   "throttle_time_in_millis": 0}
            return out

        def acc(dst, src):
            for k, v in src.items():
                d = dst.setdefault(k, {})
                for k2, v2 in v.items():
                    if isinstance(v2, dict):
                        d2 = d.setdefault(k2, {})
                        for k3, v3 in v2.items():
                            if isinstance(v3, (int, float)) \
                                    and not isinstance(v3, bool):
                                d3 = d2.setdefault(k3, 0)
                                d2[k3] = d3 + v3
                            else:
                                d2[k3] = v3
                    elif isinstance(v2, (int, float)) \
                            and not isinstance(v2, bool):
                        d[k2] = d.get(k2, 0) + v2
                    else:
                        d[k2] = v2

        indices = {}
        prim_all: dict = {}
        total_shards = 0
        total_copies = 0
        for n in names:
            svc = node.indices[n]
            prim = shard_stats(svc)
            acc(prim_all, prim)
            entry = {"primaries": prim, "total": prim}
            if level == "shards":
                entry["shards"] = {
                    str(i): [dict(prim, routing={
                        "state": "STARTED", "primary": True,
                        "node": "tpu-node-0"})]
                    for i in range(svc.n_shards)}
            indices[n] = entry
            total_shards += svc.n_shards
            total_copies += svc.n_shards * (1 + svc.n_replicas)
        out = {"_shards": {"total": total_copies,
                           "successful": total_shards, "failed": 0},
               "_all": {"primaries": prim_all, "total": prim_all}}
        if level != "cluster":
            out["indices"] = indices
        if not g.get("index") and "search" in want:
            # node-wide device timers + breaker hierarchy: the TPU
            # observability surface (ref AllCircuitBreakerStats)
            out["breakers"] = node.breakers.stats()
            out["search_phases"] = node.phase_timers.stats()
        return 200, out
    c.register("GET", "/_stats", index_stats_v2)
    c.register("GET", "/{index}/_stats", index_stats_v2)
    c.register("GET", "/_stats/{metric}", index_stats_v2)
    c.register("GET", "/{index}/_stats/{metric}", index_stats_v2)

    # -- nodes info / stats (ref rest/action/admin/cluster/node/) ----------
    def nodes_info(g, p, b):
        return 200, {"cluster_name": node.cluster_name, "nodes": {
            "tpu-node-0": {"name": "tpu-node-0", "version": "2.0.0-tpu",
                           "host": "localhost", "ip": "127.0.0.1",
                           "transport_address": "local[1]",
                           "http_address": "127.0.0.1:9200",
                           "build": "tensor-native",
                           "os": {}, "jvm": {},
                           "transport": {"profiles": {}},
                           "http": {},
                           "plugins": getattr(node, "plugins", None)
                           and node.plugins.infos() or []}}}
    c.register("GET", "/_nodes", nodes_info)
    c.register("GET", "/_nodes/{metric}", nodes_info)

    def nodes_stats(g, p, b):
        # per-phase device/host timers are the TPU hot_threads analog:
        # they say WHERE a slow search spent its time (parse vs device
        # program vs fetch/render; ref monitor/jvm/HotThreads.java:36 +
        # SearchStats — VERDICT r4 #10 observability floor). os/process/
        # fs/jvm sections come from common/monitor.py (ref monitor/*Service)
        from ..common import monitor
        return 200, {"cluster_name": node.cluster_name, "nodes": {
            "tpu-node-0": {"name": "tpu-node-0",
                           "indices": {"docs": {"count": sum(
                               s.doc_count()
                               for s in node.indices.values())}},
                           "os": monitor.os_stats(),
                           "process": monitor.process_stats(),
                           "jvm": monitor.runtime_stats(),
                           "fs": monitor.fs_stats([node.data_path]),
                           "breakers": node.breakers.stats(),
                           "thread_pool": node.thread_pool.stats(),
                           "search_phases": node.phase_timers.stats(),
                           "profiling": node.metrics.stats(),
                           "tasks": node.tasks.stats(),
                           "slowlog_tail": node.slowlog.snapshot(),
                           "search_batcher": node._batcher.stats(),
                           "caches": node.caches.stats(),
                           "rates": {name: m.stats()
                                     for name, m in node.meters.items()}}}}
    c.register("GET", "/_nodes/stats", nodes_stats)
    c.register("GET", "/_nodes/stats/{metric}", nodes_stats)

    # -- span tracing (common/tracing.py): the retained-trace ring ---------
    def list_traces(g, p, b):
        # newest-first summaries; GET /_traces/{id} has the full tree
        return 200, {"traces": node.tracer.list()}
    c.register("GET", "/_traces", list_traces)

    def get_trace(g, p, b):
        from ..common.tracing import chrome_trace, otlp_trace, span_tree
        t = node.tracer.get(g["trace_id"])
        if t is None:
            return 404, {"error": f"ResourceNotFoundException: trace "
                                  f"[{g['trace_id']}] not found "
                                  f"(expired from the ring or never "
                                  f"retained)", "status": 404}
        fmt = p.get("format", [None])[0]
        if fmt == "chrome":
            # Chrome trace-event JSON: load in chrome://tracing / Perfetto
            return 200, chrome_trace(t)
        if fmt == "otlp":
            return 200, otlp_trace(t)
        return 200, span_tree(t)
    c.register("GET", "/_traces/{trace_id}", get_trace)

    def nodes_slowlog(g, p, b):
        # the slowlog tails as a first-class endpoint: each entry carries
        # its trace_id, so a slow line links straight to GET /_traces/{id}
        import fnmatch as _fn
        want = p.get("index", [None])[0]

        def _filter(entries):
            if not want:
                return entries
            pats = [x for x in str(want).split(",") if x]
            return [e for e in entries
                    if any(_fn.fnmatch(e.get("index", ""), pat)
                           for pat in pats)]
        return 200, {"cluster_name": node.cluster_name, "nodes": {
            "tpu-node-0": {
                "search": _filter(node.slowlog.snapshot()),
                "indexing": _filter(node.indexing_slowlog.snapshot())}}}
    c.register("GET", "/_nodes/slowlog", nodes_slowlog)

    def nodes_device_stats(g, p, b):
        # device telemetry (ISSUE 16): the per-compiled-program registry
        # (top-N by cumulative dispatch time, with scrape-time XLA cost
        # analysis — None fields on backends that report nothing), per-
        # device HBM stats with the process high-water mark, and the
        # global lane-decision counters
        try:
            top_n = int(p.get("top_n", [50])[0])
        except (TypeError, ValueError):
            top_n = 50
        return 200, {"cluster_name": node.cluster_name, "nodes": {
            "tpu-node-0": node.device_stats_payload(top_n=top_n)}}
    c.register("GET", "/_nodes/device_stats", nodes_device_stats)

    def nodes_device_gaps(g, p, b):
        # the device-gap ledger's longest gaps (common/tracing.GapLedger:
        # 4 a second for 600 s), each with what the dispatching thread did
        # in it, on the program's clock; an `es:program` event of a
        # profiler capture carries `t0_ns` on that clock, and
        # `start_ns - t0_ns` maps a gap onto the capture
        return 200, {"cluster_name": node.cluster_name, "nodes": {
            "tpu-node-0": {"clock": "monotonic_ns",
                           "gaps": tracing.GAPS.gap_records()}}}
    c.register("GET", "/_nodes/device_gaps", nodes_device_gaps)

    def nodes_stats_history(g, p, b):
        # the StatsSampler ring (common/monitor.py): timestamped gauge
        # samples + min/max/avg rollups, so a spike BETWEEN two stats
        # calls is still inspectable without an external TSDB
        sel = _csv_param(p, "metric")
        return 200, {"cluster_name": node.cluster_name, "nodes": {
            "tpu-node-0": node.sampler.history(sel)}}
    c.register("GET", "/_nodes/stats/history", nodes_stats_history)

    def monitoring_overview(g, p, b):
        # self-monitoring overview (ISSUE 17): a REAL sorted + 2-level
        # sub-agg search over the `.monitoring-es-*` indices the
        # collector fills — the node observing itself through the
        # sorted/sub-agg device lanes this tier builds
        mon = getattr(node, "monitoring", None)
        if mon is None:
            return 404, {"error": "ResourceNotFoundException: monitoring "
                                  "is not enabled on this node (set "
                                  "node.monitoring.enable)", "status": 404}
        try:
            size = int(p.get("size", [10])[0])
        except (TypeError, ValueError):
            size = 10
        interval = p.get("interval", ["1m"])[0] or "1m"
        return 200, mon.overview(size=size, interval=interval)
    c.register("GET", "/_monitoring/overview", monitoring_overview)

    def metrics_exposition(g, p, b):
        # OpenMetrics text over every stats registry (common/metrics.py
        # render walk; `# TYPE`/`# HELP`, `_total`/`_bytes` conventions,
        # node/pool/breaker/index labels) — the standard scrape surface
        from ..common.metrics import render_openmetrics
        return 200, render_openmetrics(node.metric_sections(),
                                       node="tpu-node-0")
    c.register("GET", "/_metrics", metrics_exposition)

    # -- watcher alerting tier (ISSUE 20): watch CRUD + stats + alerts -----
    def _watcher_service():
        ws = getattr(node, "watcher_service", None)
        if ws is None:
            raise RestError(400, "watcher is not enabled on this node "
                                 "(set watcher.enable)")
        return ws

    def put_watch(g, p, b):
        from ..watcher.watch import WatchParsingException
        ws = _watcher_service()
        try:
            out = ws.put_watch(g["watch_id"], _json_body(b))
        except WatchParsingException as e:
            return 400, {"error": f"WatchParsingException: {e}",
                         "status": 400}
        status = 201 if out["created"] else 200
        return status, out
    c.register("PUT", "/_watcher/watch/{watch_id}", put_watch)

    def get_watch(g, p, b):
        from ..watcher.service import WatchMissingException
        ws = _watcher_service()
        try:
            return 200, ws.get_watch(g["watch_id"])
        except WatchMissingException:
            return 404, {"found": False, "_id": g["watch_id"],
                         "status": 404}
    c.register("GET", "/_watcher/watch/{watch_id}", get_watch)

    def delete_watch(g, p, b):
        from ..watcher.service import WatchMissingException
        ws = _watcher_service()
        try:
            return 200, ws.delete_watch(g["watch_id"])
        except WatchMissingException:
            return 404, {"found": False, "_id": g["watch_id"],
                         "status": 404}
    c.register("DELETE", "/_watcher/watch/{watch_id}", delete_watch)

    def execute_watch(g, p, b):
        # manual evaluation outside the schedule (ref _execute): runs
        # the input search + condition now, fires/throttles for real
        from ..watcher.service import WatchMissingException
        ws = _watcher_service()
        try:
            return 200, ws.execute_watch(g["watch_id"])
        except WatchMissingException:
            return 404, {"found": False, "_id": g["watch_id"],
                         "status": 404}
    c.register("POST", "/_watcher/watch/{watch_id}/_execute", execute_watch)

    def ack_watch(g, p, b):
        # acked watches stay quiet until the condition goes false once
        from ..watcher.service import WatchMissingException
        ws = _watcher_service()
        try:
            return 200, ws.ack_watch(g["watch_id"])
        except WatchMissingException:
            return 404, {"found": False, "_id": g["watch_id"],
                         "status": 404}
    c.register("PUT", "/_watcher/watch/{watch_id}/_ack", ack_watch)

    def watcher_stats(g, p, b):
        return 200, _watcher_service().watcher_stats()
    c.register("GET", "/_watcher/stats", watcher_stats)

    def list_alerts(g, p, b):
        # the audit trail: newest firings across the rolling
        # `.alerts-es-*` indices, optionally filtered per watch
        ws = _watcher_service()
        try:
            size = int(p.get("size", [50])[0])
        except (TypeError, ValueError):
            size = 50
        return 200, ws.alerts(size=size,
                              watch_id=p.get("watch_id", [None])[0])
    c.register("GET", "/_alerts", list_alerts)

    # -- task management (ref tasks/TaskManager + ListTasksAction:
    #    GET /_tasks, GET /_tasks/{id}, GET /_cat/tasks) -------------------
    def list_tasks_api(g, p, b):
        out = node.tasks.list_tasks(
            actions=p.get("actions", [None])[0],
            detailed=_pbool(p, "detailed", False))
        if _pbool(p, "recent", False):
            # recently-completed ring: short-lived shard tasks stay
            # assertable after the request finishes (test seam)
            out["recent"] = node.tasks.recent_infos(
                actions=p.get("actions", [None])[0])
        return 200, out
    c.register("GET", "/_tasks", list_tasks_api)

    def get_task_api(g, p, b):
        t = node.tasks.get(g["task_id"])
        if t is None:
            return 404, {"error": f"ResourceNotFoundException: task "
                                  f"[{g['task_id']}] isn't running",
                         "status": 404}
        return 200, {"completed": False, "task": t.info(detailed=True)}
    c.register("GET", "/_tasks/{task_id}", get_task_api)

    def _duration_ms(v: str, default: float) -> float:
        s = str(v).strip().lower()
        for suffix, mult in (("micros", 0.001), ("ms", 1.0), ("s", 1000.0),
                             ("m", 60_000.0), ("h", 3_600_000.0)):
            if s.endswith(suffix):
                try:
                    return float(s[: -len(suffix)]) * mult
                except ValueError:
                    return default
        try:
            return float(s)
        except ValueError:
            return default

    def nodes_hot_threads(g, p, b):
        from ..common import monitor
        return 200, monitor.hot_threads(
            threads=int(p.get("threads", ["3"])[0]),
            snapshots=int(p.get("snapshots", ["10"])[0]),
            interval_ms=_duration_ms(p.get("interval", ["50ms"])[0], 50.0))
    c.register("GET", "/_nodes/hot_threads", nodes_hot_threads)
    c.register("GET", "/_nodes/{node_id}/hot_threads", nodes_hot_threads)
    c.register("GET", "/_cluster/nodes/hotthreads", nodes_hot_threads)

    def cluster_stats(g, p, b):
        # ref action/admin/cluster/stats/ClusterStatsNodes+Indices
        from ..common import monitor
        seg_count = mem = docs = deleted = 0
        shards = 0
        for svc in node.indices.values():
            shards += svc.n_shards
            docs += svc.doc_count()
            for e in svc.shards:
                st = e.segment_stats()
                seg_count += st["count"]
                mem += st["memory_in_bytes"]
                deleted += st["deleted"]
        return 200, {
            "timestamp": int(time.time() * 1000),
            "cluster_name": node.cluster_name,
            "status": node.cluster_health()["status"],
            "indices": {
                "count": len(node.indices),
                "shards": {"total": shards, "primaries": shards},
                "docs": {"count": docs, "deleted": deleted},
                "store": {"size_in_bytes": mem},
                "segments": {"count": seg_count,
                             "memory_in_bytes": mem},
            },
            "nodes": {
                "count": {"total": 1, "master_data": 1},
                "versions": ["2.0.0-tpu"],
                "os": monitor.os_stats(),
                "process": monitor.process_stats(),
                "jvm": monitor.runtime_stats(),
                "fs": monitor.fs_stats([node.data_path]),
            },
        }
    c.register("GET", "/_cluster/stats", cluster_stats)

    # -- warmers (registry parity; packed-view warmup is the real warmer) --
    def put_warmer(g, p, b):
        body = _json_body(b)
        for n in node._resolve(g.get("index", "_all")):
            svc = node.indices[n]
            if not hasattr(svc, "warmers"):
                svc.warmers = {}
            svc.warmers[g["name"]] = {
                "types": [g["type"]] if g.get("type") else [],
                "source": body}
        return 200, {"acknowledged": True}
    c.register("PUT", "/{index}/_warmer/{name}", put_warmer)
    c.register("PUT", "/{index}/{type}/_warmer/{name}", put_warmer)
    c.register("PUT", "/_warmer/{name}", put_warmer)

    def get_warmer(g, p, b):
        name = g.get("name")
        out = {}
        for n in node._resolve(g.get("index", "_all")):
            svc = node.indices[n]
            wm = getattr(svc, "warmers", {})
            if name:
                pats = ["*" if x == "_all" else x for x in name.split(",")]
                wm = {w: s for w, s in wm.items()
                      if any(fnmatch.fnmatch(w, pat) for pat in pats)}
                if wm:
                    out[n] = {"warmers": wm}
            else:
                # unfiltered listing shows every index, empty map included
                out[n] = {"warmers": wm}
        return 200, out
    for pat in ("/_warmer", "/_warmer/{name}", "/{index}/_warmer",
                "/{index}/_warmer/{name}"):
        c.register("GET", pat, get_warmer)

    def delete_warmer(g, p, b):
        name = g.get("name")
        if not name:
            raise RestError(400, "ActionRequestValidationException: "
                                 "warmer name is missing")
        removed = False
        for n in node._resolve(g["index"]):
            svc = node.indices[n]
            wm = getattr(svc, "warmers", {})
            match = list(wm) if name in ("_all", "*") else \
                [w for w in wm if any(fnmatch.fnmatch(w, pat)
                                      for pat in name.split(","))]
            for w in match:
                del wm[w]
                removed = True
        if not removed:
            return 404, {"error": f"IndexWarmerMissingException: "
                                  f"index_warmer [{name}] missing",
                         "status": 404}
        return 200, {"acknowledged": True}
    c.register("DELETE", "/{index}/_warmer/{name}", delete_warmer)
    c.register("DELETE", "/{index}/_warmer", delete_warmer)


def _parse_bulk(body: bytes, default_index: str | None) -> list:
    """NDJSON bulk format (ref rest/action/bulk/RestBulkAction).

    All lines parse as ONE json array (a single C-level loads instead of
    one per line — measurable at 100k-doc ingests); the python walk only
    pairs action lines with their sources. Ops carry the raw source
    line's byte length as a 4th element so the engine's buffered-bytes
    estimate skips re-walking each source dict (node.bulk accepts both
    3- and 4-tuples)."""
    lines = [ln for ln in body.split(b"\n") if ln and not ln.isspace()]
    if not lines:
        return []
    docs = json.loads(b"[" + b",".join(lines) + b"]")
    ops = []
    i = 0
    n = len(docs)
    while i < n:
        action_line = docs[i]
        (action, meta), = action_line.items()
        if default_index and "_index" not in meta:
            meta["_index"] = default_index
        i += 1
        source = None
        raw_len = 0
        if action != "delete" and i < n:
            source = docs[i]
            raw_len = len(lines[i])
            i += 1
        ops.append((action, meta, source, raw_len))
    return ops




# ---------------------------------------------------------------------------

# which QoS traffic class admits each pool-routed request class (the
# reference's five connection types, NettyTransport.java:180-184 — REST
# traffic is read (search-class) or write (bulk-class); state/ping are
# transport-internal and never shed). Pool None (management) skips
# admission entirely: control-plane reads must work DURING an overload.
_TRAFFIC_CLASS_OF = {"search": "search", "get": "search",
                     "bulk": "bulk", "index": "bulk"}


def _pool_of(method: str, path: str) -> str | None:
    """Which named thread pool serves this request class (ref
    ThreadPool.Names mapping in each TransportAction's executor()); None =
    run inline on the connection thread (management/admin)."""
    seg = [s for s in path.split("/") if s]
    _SEARCH = {"_search", "_msearch", "_count", "_suggest", "_percolate",
               "_mpercolate", "_count_percolate", "_explain", "_validate",
               "_mlt", "_knn", "_termvectors", "_termvector",
               "_mtermvectors", "_search_shards"}
    if any(s in _SEARCH for s in seg):
        return "search"
    if "_bulk" in seg:
        return "bulk"
    if "_mget" in seg:
        return "get"
    if (len(seg) == 3 and not any(s.startswith("_") for s in seg[:2])):
        if method in ("GET", "HEAD"):
            return "get"
        if method in ("PUT", "POST", "DELETE"):
            return "index"
    if len(seg) == 4 and seg[3] == "_update":
        return "index"
    return None


class HttpServer:
    """Threaded HTTP front-end (ref http/HttpServer.java + netty transport)."""

    def __init__(self, node: NodeService, host: str = "127.0.0.1",
                 port: int = 9200, registrar: Callable | None = None):
        self.controller = RestController(node, registrar=registrar)
        if getattr(node, "plugins", None) is not None:
            # plugins may contribute REST endpoints (ref PluginsService +
            # RestModule handler registration)
            node.plugins.register_routes(self.controller, node)
        controller = self.controller

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):   # silence per-request logs
                pass

            def _handle(self, method: str):
                # the root of the request's spans (common/tracing.py): first
                # byte of the body read to the last byte written. The HTTP
                # thread's spans feed the aggregate and the profiler only;
                # the request's span TREE roots in `dispatch`, on the pool
                with tracing.span("rest.request", method=method):
                    self._serve(method)

            def _serve(self, method: str):
                parsed = urlparse(self.path)
                params = parse_qs(parsed.query)
                bad_body = None
                with tracing.span("rest.read_body"):
                    length = int(self.headers.get("Content-Length") or 0)
                    body = self.rfile.read(length) if length else b""
                    # XContent seam (common/xcontent.py; ref
                    # XContentFactory): YAML/CBOR request bodies normalize
                    # to JSON at the edge so every handler stays
                    # single-format
                    ctype_in = self.headers.get("Content-Type") or ""
                    if body and ("yaml" in ctype_in or "cbor" in ctype_in
                                 or "smile" in ctype_in):
                        from ..common import xcontent
                        try:
                            body = json.dumps(
                                xcontent.decode(body, ctype_in)).encode()
                        except Exception as e:  # noqa: BLE001 — yaml/cbor
                            # parsers raise their own types; ALL malformed
                            # bodies must 406, never drop the connection
                            bad_body = e
                if bad_body is not None:
                    self._reply(406, json.dumps(
                        {"error": f"{type(bad_body).__name__}: {bad_body}",
                         "status": 406}).encode(),
                        "application/json; charset=UTF-8", method)
                    return
                req_headers = {k.lower(): v for k, v in self.headers.items()}
                extra_headers: dict = {}
                try:
                    # admission control (serving/qos.py, ISSUE 9): the QoS
                    # controller sheds excess load per traffic class as
                    # 429 + Retry-After BEFORE the pool, then each request
                    # class runs on its named bounded pool; queue overflow
                    # -> 429 before any engine/device work (ref
                    # ThreadPool.java:116 + EsRejectedExecutionException)
                    pool = _pool_of(method, parsed.path)
                    tp = getattr(node, "thread_pool", None)
                    qos = getattr(node, "qos", None)
                    tclass = _TRAFFIC_CLASS_OF.get(pool)
                    admission = contextlib.nullcontext()
                    if qos is not None and tclass is not None:
                        admit = tracing.span("qos.admit")
                        with admit:
                            try:
                                admission = qos.admit(tclass)
                            except QosShedException:
                                admit.attrs["shed"] = True
                                raise
                    with admission:
                        if pool is None or tp is None:
                            status, payload = controller.dispatch(
                                method, parsed.path, params, body,
                                req_headers)
                        else:
                            submitted = tracing.now_ns()

                            def on_pool(*args):
                                # first line on the pool thread: the end
                                # of `pool.queue_wait`
                                tracing.begin_request(submitted)
                                return controller.dispatch(*args)
                            status, payload = tp.submit(
                                pool, on_pool,
                                method, parsed.path, params, body,
                                req_headers).result()
                except Exception as e:  # noqa: BLE001 — REST error contract
                    status = _status_of(e)
                    payload = {"error": f"{type(e).__name__}: {e}",
                               "status": status}
                    if status == 429:
                        # backpressure contract: every shed/rejection
                        # carries a client backoff hint (never a 5xx)
                        retry = getattr(e, "retry_after_s", None)
                        if retry is None and getattr(node, "qos", None) \
                                is not None:
                            retry = node.qos.retry_after_s()
                        import math as _math
                        extra_headers["Retry-After"] = \
                            str(int(_math.ceil(retry or 1.0)))
                fmt = params.get("format", [None])[0]
                with tracing.span("rest.serialize", cpu=True):
                    if isinstance(payload, bytes):
                        data = payload       # pre-serialized JSON fast lane
                        ctype = "application/json; charset=UTF-8"
                    elif isinstance(payload, str):
                        data = payload.encode("utf-8")
                        ctype = "text/plain; charset=UTF-8"
                    elif fmt in ("yaml", "cbor"):
                        from ..common import xcontent
                        try:
                            data, ctype = xcontent.encode(payload, fmt)
                        except Exception:  # noqa: BLE001 — unencodable
                            # value: JSON fallback
                            data = json.dumps(payload).encode()
                            ctype = "application/json; charset=UTF-8"
                    else:
                        data = json.dumps(payload).encode("utf-8")
                        ctype = "application/json; charset=UTF-8"
                self._reply(status, data, ctype, method,
                            opaque_id=req_headers.get("x-opaque-id"),
                            extra=extra_headers)

            def _reply(self, status, data, ctype, method, opaque_id=None,
                       extra=None):
                if method == "HEAD":
                    data = b""
                with tracing.span("rest.write", status=status):
                    self.send_response(status)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(data)))
                    if opaque_id:
                        # the reference echoes X-Opaque-Id on every response
                        self.send_header("X-Opaque-Id", opaque_id)
                    for k, v in (extra or {}).items():
                        self.send_header(k, v)
                    self.end_headers()
                    self.wfile.write(data)

            def do_GET(self):
                self._handle("GET")

            def do_POST(self):
                self._handle("POST")

            def do_PUT(self):
                self._handle("PUT")

            def do_DELETE(self):
                self._handle("DELETE")

            def do_HEAD(self):
                self._handle("HEAD")

        class Server(ThreadingHTTPServer):
            # stdlib default backlog is 5: a burst of concurrent clients
            # (the dynamic batcher's whole point) gets connection resets
            request_queue_size = 128
            daemon_threads = True

        self.server = Server((host, port), Handler)
        self.port = self.server.server_port
        self._thread: threading.Thread | None = None

    def start(self) -> "HttpServer":
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()
