"""Route-registry tripwire: the controller's route count stays at or above
its current floor, no (method, pattern) is registered twice, and every
endpoint the README's Observability section documents resolves to a real
handler — docs and the route table can't silently drift apart."""

import os
import re

import pytest

from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.rest.http_server import RestController

README = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "README.md")


@pytest.fixture(scope="module")
def controller(tmp_path_factory):
    node = NodeService(str(tmp_path_factory.mktemp("routes")))
    c = RestController(node)
    yield c
    node.close()


def _resolves(controller, path: str) -> bool:
    return any(rx.match(path) for _m, rx, _h, _s in controller.routes)


def test_route_count_floor_and_uniqueness(controller):
    # floor, not exact: new PRs add routes; LOSING routes is the bug.
    # (252 registered at ISSUE-5 time: tracing added /_traces,
    # /_traces/{trace_id} and /_nodes/slowlog)
    # re-anchored at ISSUE 17: /_monitoring/overview joined the table
    # re-anchored at ISSUE 18: 254 registered — the percolate/mpercolate
    # routes pre-existed (now served by the dense doc×query executor),
    # so the reverse-search PR adds handlers, not patterns
    # re-anchored at ISSUE 20: 261 registered — watcher CRUD/_execute/
    # _ack, /_watcher/stats and /_alerts joined the table
    assert len(controller.routes) >= 261, len(controller.routes)
    seen = set()
    for method, rx, _h, _s in controller.routes:
        key = (method, rx.pattern)
        assert key not in seen, f"duplicate route {key}"
        seen.add(key)


def test_new_observability_routes_resolve(controller):
    for path in ("/_metrics", "/_nodes/device_gaps",
                 "/_nodes/stats/history", "/_nodes/stats",
                 "/_cat/thread_pool", "/_cat/indices",
                 "/_cache/clear", "/someindex/_cache/clear",
                 "/_cat/fielddata",
                 "/_traces", "/_traces/abcdef0123456789",
                 "/_nodes/slowlog", "/_monitoring/overview"):
        assert _resolves(controller, path), path


def test_reverse_search_routes_resolve(controller):
    # ISSUE 18: the reverse-search surface — single-doc, existing-doc,
    # count variants and the multi-percolate batch endpoint
    for path in ("/idx/_doc/_percolate", "/idx/_doc/42/_percolate",
                 "/idx/_doc/_percolate/count",
                 "/idx/_doc/42/_percolate/count",
                 "/_mpercolate", "/idx/_mpercolate",
                 "/idx/_doc/_mpercolate"):
        assert _resolves(controller, path), path


def test_readme_observability_endpoints_resolve(controller):
    with open(README) as f:
        text = f.read()
    section = text.split("## Observability", 1)[1].split("\n## ", 1)[0]
    paths = set()
    for m in re.finditer(r"localhost:9200(/[^\s'\"]*)", section):
        p = m.group(1).split("?", 1)[0].rstrip("'\"")
        if p != "/":
            paths.add(p)
    assert len(paths) >= 6, f"README section lost its examples: {paths}"
    for p in sorted(paths):
        assert _resolves(controller, p), \
            f"README documents [{p}] but no route matches it"
