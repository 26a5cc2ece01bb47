"""REST gateway fronting a multi-node cluster.

In the reference EVERY node serves HTTP and coordinates distributed
execution (http/HttpServer.java feeding the action layer). This registrar
plugs a ClusterNode coordinator into the same threaded HttpServer /
RestController plumbing the single-node product uses, so REST requests hit
a real cluster: metadata ops become master tasks, document ops route to
primaries with replication, search runs the full 2-phase scatter-gather
(cluster/node.py).

    node = cluster.client()
    HttpServer(node, port=9200, registrar=register_cluster_routes).start()
"""

from __future__ import annotations

import json
import time

from ..cluster.node import ClusterNode
from .http_server import RestError, _json_body, _parse_bulk


def register_cluster_routes(c, node: ClusterNode) -> None:
    # -- banner / health ---------------------------------------------------
    def banner(g, p, b):
        return 200, {"status": 200, "name": node.node_id,
                     "cluster_name": "elasticsearch-tpu",
                     "version": {"number": "2.0.0-tpu",
                                 "lucene_version": "tensor-native"},
                     "tagline": "You Know, for Search"}
    c.register("GET", "/", banner)
    c.register("HEAD", "/", banner)

    def health(g, p, b):
        h = node.health()
        want = p.get("wait_for_status", [None])[0]
        deadline = time.monotonic() + 30.0
        rank = {"red": 0, "yellow": 1, "green": 2}
        while want and rank[h["status"]] < rank.get(want, 0) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
            h = node.health()
        return 200, {"cluster_name": h["cluster_name"],
                     "status": h["status"], "timed_out": False,
                     "number_of_nodes": h["number_of_nodes"],
                     "number_of_data_nodes": h["number_of_data_nodes"],
                     "active_primary_shards": h["active_primary_shards"],
                     "active_shards": h["active_shards"],
                     "relocating_shards": h.get("relocating_shards", 0),
                     "initializing_shards": h["initializing_shards"],
                     "unassigned_shards": h["unassigned_shards"]}
    c.register("GET", "/_cluster/health", health)
    c.register("GET", "/_cluster/health/{index}", health)

    def cluster_state(g, p, b):
        st = node.cluster.current()
        return 200, {"cluster_name": st.data.get("cluster_name"),
                     "master_node": st.master_node, "version": st.version,
                     "nodes": st.nodes,
                     "metadata": {"indices": st.indices},
                     "routing_table": {"indices": {
                         i: {"shards": {str(s): copies
                                        for s, copies in enumerate(shards)}}
                         for i, shards in st.routing.items()}}}
    c.register("GET", "/_cluster/state", cluster_state)

    def nodes_stats(g, p, b):
        # the nodes template over the REAL transport: every live node
        # answers for itself; handler errors on live nodes surface as
        # per-node failures (ref TransportNodesStatsAction +
        # FailedNodeException)
        res = node.nodes_stats()
        out = {"cluster_name": "elasticsearch-tpu", "nodes": res["nodes"]}
        if res["failures"]:
            out["failures"] = res["failures"]
        return 200, out
    c.register("GET", "/_nodes/stats", nodes_stats)
    c.register("GET", "/_nodes/stats/{metric}", nodes_stats)

    def metrics_local(g, p, b):
        # the coordinator's OWN exposition (same contract as the
        # single-node /_metrics)
        from ..common.metrics import render_openmetrics
        return 200, render_openmetrics(node.metric_sections(),
                                       node=node.node_id)
    c.register("GET", "/_metrics", metrics_local)

    def cluster_metrics(g, p, b):
        # cluster-wide exposition: per-node sections fan out over the
        # transport and merge into ONE valid document (same family, one
        # sample per node via the `node` label); live nodes whose handler
        # errored surface as comment entries, never a dropped scrape
        from ..common.metrics import openmetrics_families, render_families
        res = node.nodes_metric_sections()
        fams: dict = {}
        for node_id, sections in sorted(res["sections_by_node"].items()):
            openmetrics_families(sections, node_id, fams)
        comments = [
            f"node-failure node={f['node']} reason="
            + str(f["reason"])[:200].replace("\n", " ")
            for f in res["failures"]]
        return 200, render_families(fams, comments=comments)
    c.register("GET", "/_cluster/_metrics", cluster_metrics)

    def list_tasks(g, p, b):
        # tasks running on THIS coordinator (shard tasks live on the
        # copy-holders' own managers, parent-linked over the transport)
        detailed = p.get("detailed", ["false"])[0] not in ("false", None)
        out = node.tasks.list_tasks(actions=p.get("actions", [None])[0],
                                    detailed=detailed)
        if p.get("recent", ["false"])[0] not in ("false", None):
            out["recent"] = node.tasks.recent_infos(
                actions=p.get("actions", [None])[0])
        return 200, out
    c.register("GET", "/_tasks", list_tasks)

    def nodes_info(g, p, b):
        # node INFO shape (addresses/version — what client sniffers read;
        # ref RestNodesInfoAction), distinct from the stats body
        st = node.cluster.current()
        infos = {}
        for node_id in sorted(st.nodes):
            addr = None
            net = getattr(node.transport, "network", None)
            if net is not None and hasattr(net, "address_of"):
                addr = net.address_of(node_id)
            infos[node_id] = {
                "name": node_id, "version": "2.0.0-tpu",
                "build": "tensor-native",
                "transport_address": f"{addr[0]}:{addr[1]}" if addr
                else f"local[{node_id}]",
                "http_address": None, "host": "localhost",
                "ip": "127.0.0.1", "os": {}, "jvm": {},
                "transport": {"profiles": {}}, "http": {}, "plugins": []}
        return 200, {"cluster_name": "elasticsearch-tpu", "nodes": infos}
    c.register("GET", "/_nodes", nodes_info)

    def indices_stats(g, p, b):
        # the broadcast template over the transport: shard stats from
        # every holder, coordinator-aggregated (ref
        # TransportIndicesStatsAction over TransportBroadcastOperation)
        return 200, node.indices_stats(g.get("index", "_all"))
    c.register("GET", "/_stats", indices_stats)
    c.register("GET", "/{index}/_stats", indices_stats)

    # -- index admin (master template) ------------------------------------
    def create_index(g, p, b):
        body = _json_body(b)
        node.create_index(g["index"], settings=body.get("settings") or {},
                          mappings=body.get("mappings") or {})
        return 200, {"acknowledged": True}
    c.register("PUT", "/{index}", create_index)
    c.register("POST", "/{index}", create_index)

    def delete_index(g, p, b):
        node.delete_index(g["index"])
        return 200, {"acknowledged": True}
    c.register("DELETE", "/{index}", delete_index)

    def index_exists(g, p, b):
        st = node.cluster.current()
        return (200 if g["index"] in st.indices else 404), ""
    c.register("HEAD", "/{index}", index_exists)

    def put_mapping(g, p, b):
        node.put_mapping(g["index"], g.get("type", "_doc"), _json_body(b))
        return 200, {"acknowledged": True}
    c.register("PUT", "/{index}/_mapping/{type}", put_mapping)
    c.register("PUT", "/{index}/_mapping", put_mapping)
    c.register("POST", "/{index}/_mapping/{type}", put_mapping)

    def get_mapping(g, p, b):
        st = node.cluster.current()
        names = st.resolve_index(g.get("index", "_all"))
        out = {}
        for n in names:
            meta = st.index_meta(n) or {}
            out[n] = {"mappings": meta.get("mappings") or {}}
        return 200, out
    c.register("GET", "/{index}/_mapping", get_mapping)
    c.register("GET", "/_mapping", get_mapping)

    # -- documents (replicated writes / routed reads) ----------------------
    def _maybe_refresh(g, p):
        if p.get("refresh", ["false"])[0] != "false":
            node.refresh(g.get("index", "_all"))

    def put_doc(g, p, b):
        kw = {}
        if p.get("op_type", [None])[0] == "create":
            kw["op_type"] = "create"
        if "version" in p:
            kw["version"] = int(p["version"][0])
            kw["version_type"] = p.get("version_type", ["internal"])[0]
        r = node.index_doc(g["index"], g.get("id"), _json_body(b),
                           type_name=g.get("type", "_doc"),
                           routing=p.get("routing", [None])[0], **kw)
        _maybe_refresh(g, p)
        return (201 if r.get("created") else 200), {
            "_index": g["index"], "_type": g.get("type", "_doc"),
            "_id": r["_id"], "_version": r["_version"],
            "created": r.get("created", False)}
    c.register("PUT", "/{index}/{type}/{id}", put_doc)
    c.register("POST", "/{index}/{type}/{id}", put_doc)
    c.register("POST", "/{index}/{type}", put_doc)

    def get_doc(g, p, b):
        r = node.get_doc(g["index"], g["id"],
                         routing=p.get("routing", [None])[0])
        if not r["found"]:
            return 404, {"_index": g["index"], "_type": g.get("type"),
                         "_id": g["id"], "found": False}
        return 200, {"_index": g["index"], "_type": g.get("type", "_doc"),
                     "_id": g["id"], "_version": r["_version"],
                     "found": True, "_source": r["_source"]}
    c.register("GET", "/{index}/{type}/{id}", get_doc)
    c.register("HEAD", "/{index}/{type}/{id}", get_doc)

    def delete_doc(g, p, b):
        r = node.delete_doc(g["index"], g["id"],
                            routing=p.get("routing", [None])[0])
        _maybe_refresh(g, p)
        found = r.get("found", True)
        return (200 if found else 404), {
            "found": found, "_index": g["index"],
            "_type": g.get("type", "_doc"), "_id": g["id"],
            "_version": r["_version"]}
    c.register("DELETE", "/{index}/{type}/{id}", delete_doc)

    def bulk(g, p, b):
        ops = _parse_bulk(b, g.get("index"))
        items = node.bulk(ops)
        _maybe_refresh(g, p)
        errors = any(next(iter(i.values())).get("status", 200) >= 300
                     for i in items)
        return 200, {"took": 0, "errors": errors, "items": items}
    c.register("POST", "/_bulk", bulk)
    c.register("PUT", "/_bulk", bulk)
    c.register("POST", "/{index}/_bulk", bulk)
    c.register("POST", "/{index}/{type}/_bulk", bulk)

    # -- search (2-phase scatter-gather) -----------------------------------
    def search(g, p, b):
        body = _json_body(b) if b else {}
        if "size" in p:
            body["size"] = int(p["size"][0])
        if "from" in p:
            body["from"] = int(p["from"][0])
        if "q" in p:
            body["query"] = {"query_string": {"query": p["q"][0]}}
        scroll = p.get("scroll", [None])[0]
        out = node.search(g.get("index", "_all"), body,
                          preference=p.get("preference", [None])[0],
                          scroll=scroll)
        return 200, out
    c.register("GET", "/{index}/_search", search)
    c.register("POST", "/{index}/_search", search)
    c.register("GET", "/_search", search)
    c.register("POST", "/_search", search)

    def scroll_next(g, p, b):
        body = {}
        sid = p.get("scroll_id", [None])[0]
        if b and b.strip().startswith(b"{"):
            body = _json_body(b)
            sid = body.get("scroll_id") or sid
        elif b and sid is None:
            sid = b.decode("utf-8").strip()   # bare-id body (pre-2.0 form)
        if not sid:
            raise RestError(400, "scroll_id is missing")
        keep = body.get("scroll") or p.get("scroll", [None])[0]
        from ..cluster.node import SearchContextMissingException
        try:
            return 200, node.scroll(sid, keep_alive=keep)
        except SearchContextMissingException as e:
            raise RestError(404, f"SearchContextMissingException: {e}")
    c.register("GET", "/_search/scroll", scroll_next)
    c.register("POST", "/_search/scroll", scroll_next)

    def clear_scroll(g, p, b):
        body = _json_body(b) if b else {}
        sids = body.get("scroll_id") or []
        if isinstance(sids, str):
            sids = [sids]
        found = any([node.clear_scroll(s) for s in sids])  # clear ALL ids
        return 200, {"succeeded": True, "found": found}
    c.register("DELETE", "/_search/scroll", clear_scroll)

    def msearch(g, p, b):
        lines = [json.loads(ln) for ln in b.decode("utf-8").split("\n")
                 if ln.strip()]
        items = []
        for i in range(0, len(lines) - 1, 2):
            header = lines[i] or {}
            if "index" not in header and g.get("index"):
                header["index"] = g["index"]
            items.append((header, lines[i + 1]))
        return 200, node.msearch(items)
    c.register("POST", "/_msearch", msearch)
    c.register("GET", "/_msearch", msearch)
    c.register("POST", "/{index}/_msearch", msearch)

    def count(g, p, b):
        body = _json_body(b) if b else {}
        if "q" in p:
            body["query"] = {"query_string": {"query": p["q"][0]}}
        return 200, node.count(g.get("index", "_all"), body)
    c.register("GET", "/{index}/_count", count)
    c.register("POST", "/{index}/_count", count)
    c.register("GET", "/_count", count)

    # -- broadcast admin ---------------------------------------------------
    def refresh(g, p, b):
        node.refresh(g.get("index", "_all"))
        return 200, {"_shards": {"failed": 0}}
    c.register("POST", "/{index}/_refresh", refresh)
    c.register("GET", "/{index}/_refresh", refresh)
    c.register("POST", "/_refresh", refresh)

    def flush(g, p, b):
        node.flush(g.get("index", "_all"))
        return 200, {"_shards": {"failed": 0}}
    c.register("POST", "/{index}/_flush", flush)
    c.register("POST", "/_flush", flush)

    # -- _cat --------------------------------------------------------------
    def cat_shards(g, p, b):
        st = node.cluster.current()
        rows = []
        for index, shards in sorted(st.routing.items()):
            for sid, copies in enumerate(shards):
                for cp in copies:
                    rows.append(" ".join([
                        index, str(sid),
                        "p" if cp["primary"] else "r",
                        cp["state"], str(cp.get("node") or "-")]))
        return 200, "\n".join(rows) + ("\n" if rows else "")
    c.register("GET", "/_cat/shards", cat_shards)

    def cat_nodes(g, p, b):
        st = node.cluster.current()
        rows = [" ".join([nid,
                          "*" if nid == st.master_node else "-"])
                for nid in sorted(st.nodes)]
        return 200, "\n".join(rows) + "\n"
    c.register("GET", "/_cat/nodes", cat_nodes)

    def cat_recovery(g, p, b):
        # index shard source target stage files_total files_reused
        # bytes_total bytes_recovered throttle_waits retries elapsed_ms
        rows = []
        for r in node.cat_recovery():
            if g.get("index") and r["index"] != g["index"]:
                continue
            rows.append(" ".join([
                r["index"], str(r["shard"]), str(r["source"]),
                str(r["target"]), r["stage"], str(r["files_total"]),
                str(r["files_reused"]), str(r["bytes_total"]),
                str(r["bytes_recovered"]), str(r["throttle_waits"]),
                str(r["retries"]), f"{r['elapsed_ms']:.1f}"]))
        return 200, "\n".join(rows) + ("\n" if rows else "")
    c.register("GET", "/_cat/recovery", cat_recovery)
    c.register("GET", "/_cat/recovery/{index}", cat_recovery)

    # -- allocation / settings (ISSUE 15) ----------------------------------
    def allocation_explain(g, p, b):
        body = _json_body(b) if b else {}
        try:
            out = node.allocation_explain(
                index=body.get("index"),
                shard=body.get("shard"),
                primary=body.get("primary"))
        except ValueError as e:
            raise RestError(400, str(e))
        except KeyError as e:
            raise RestError(404, str(e))
        return 200, out
    c.register("POST", "/_cluster/allocation/explain", allocation_explain)
    c.register("GET", "/_cluster/allocation/explain", allocation_explain)

    def put_cluster_settings(g, p, b):
        body = _json_body(b) if b else {}
        # accept both the flat form and the transient/persistent wrappers
        upd: dict = {}
        for section in ("persistent", "transient"):
            sec = body.get(section)
            if isinstance(sec, dict):
                upd.update(sec)
        if not upd:
            upd = {k: v for k, v in body.items()
                   if k not in ("persistent", "transient")}
        if not upd:
            raise RestError(400, "no settings to update")
        return 200, node.update_cluster_settings(upd)
    c.register("PUT", "/_cluster/settings", put_cluster_settings)

    def get_cluster_settings(g, p, b):
        st = node.cluster.current()
        return 200, {"persistent": {},
                     "transient": dict(st.data.get("settings") or {})}
    c.register("GET", "/_cluster/settings", get_cluster_settings)
