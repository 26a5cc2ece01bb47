"""The benchmark's own tests: its files on the import path, and
`BENCHMARK.json` as it would read with the entries a later PR adds."""

import copy
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    B = json.load(f)


def entries_through(last: str) -> list:
    """The metrics of `BENCHMARK.json` up to the per-layer entry `last`: what
    stood when `last` was the newest, so that a test of those entries is
    left standing by later entries and by a later cell that joins them."""
    names = [m["name"] for m in B["per_layer"]]
    return B["end_to_end"] + B["per_layer"][:names.index(last) + 1]


def with_entries(bench: dict, added: dict) -> dict:
    """`bench` and the entries of one more cell: its configuration, its
    entry under `workloads`, its own per-layer metrics, and its name in the
    `workloads` list of each metric it `joins`. Files and entries only."""
    out = copy.deepcopy(bench)
    for kind in ("configs", "workloads", "per_layer"):
        out[kind] += copy.deepcopy(added[kind])
    names = [w["name"] for w in added["workloads"]]
    for m in out["end_to_end"] + out["per_layer"]:
        if m["name"] in added["joins"]:
            m["workloads"] = m["workloads"] + names
    return out


with open(os.path.join(FIXTURES, "dashboard-entries.json")) as f:
    B_PLUS = with_entries(B, json.load(f))


@pytest.fixture(autouse=True, scope="module")
def _own_span_table():
    """Each file's spans in a table of its own: a file that serves requests
    in this process leaves nothing in the span table that a later file on
    the same worker reads whole (`/_metrics`' `es_span_*`)."""
    try:
        from elasticsearch_tpu.common import tracing
    except ImportError:     # a copy of the benchmark alone serves nothing
        yield
        return
    kept, tracing.AGGREGATE = tracing.AGGREGATE, tracing.SpanAggregate()
    yield
    tracing.AGGREGATE = kept


@pytest.fixture(scope="session")
def bench_plus_file(tmp_path_factory):
    """`B_PLUS` as a file that `harness.run(bench_file=...)` reads."""
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(B_PLUS))
    return str(path)
