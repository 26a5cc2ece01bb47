"""What every existing cell reads stays bit for bit what it was before the
harness learned dense vectors and `dis_max`: for each configuration under
`benchmark/configs/`, chunk 0 and its `_bulk` payload; for each cell's file
under `benchmark/workloads/`, the requests of a 40 s window, the least bytes
of its first 32 bodies and the reference's answer (mask and score, every
bit) to its first 8. The digests were computed before the widening and are
pinned here. The reference runs at 12,000 documents: the code path is the
cell's, the size one a test run can hold."""

import hashlib
import json
import os

import numpy as np
import pytest

import corpus
import traffic
import work
from conftest import BENCH
from reference import Reference

SEED = 2 ** 31 + 5
DOCS = 12_000

CONFIG_OF = {
    "wiki.rerank-top1000": "wiki-bm25-5s",
    "wiki.match-top10": "wiki-bm25-5s",
    "wiki.filtered-top1000": "wiki-filtered-5s",
    "httplogs.dash-panels": "httplogs-dash-5s",
    "httplogs.dashboard-mesh": "httplogs-5s-mesh",
    "httplogs.dashboard": "httplogs-5s",
}

CHUNK_DIGESTS = {
    "httplogs-5s":
        "1bcee7a7c18a9afe0ee6514046bdfe7edb9903cd17b1226a48d459451484975b",
    "httplogs-5s-mesh":
        "1bcee7a7c18a9afe0ee6514046bdfe7edb9903cd17b1226a48d459451484975b",
    "httplogs-dash-5s":
        "1bcee7a7c18a9afe0ee6514046bdfe7edb9903cd17b1226a48d459451484975b",
    "wiki-bm25-5s":
        "439d11ceba980d7358a35d8983753495689861a8442fe475a0757e60e6752a65",
    "wiki-filtered-5s":
        "d8a460de3dd5b17a89b0c68fae83c252baa297138fa6409dcbb847335a74d73a",
}
TRAFFIC_DIGESTS = {
    "httplogs.dash-panels":
        "c93115f5ecc7ccdee5d07df75a2635b2eb7b821afd26e71f8dbc6549a1a5f88a",
    "httplogs.dashboard":
        "964d93944287780621bc932b7e96104240882de6fcd9b52f2df4e49c889c20e2",
    "httplogs.dashboard-mesh":
        "9ac918cd3c7bcbfcfe4ea97e191d7ab6748bb3ccd43e4aa74bbbf06632266ba9",
    "wiki.filtered-top1000":
        "6fc9c995bbdcd5c8948e731e7292ad902fe9ec8415e267904467351c91949a40",
    "wiki.match-top10":
        "6a2fd79d53438c597d06c2f65f0b65c7fb94ae0041e6e0e9e9e934b70c0186c9",
    "wiki.rerank-top1000":
        "ad22aa70c109824a6fa26991164e2a0306b137105964af0a52b40f3a36cd1e37",
}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _array_digest(h, a) -> None:
    a = np.ascontiguousarray(a)
    h.update(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())


def chunk_digest(cfg: dict) -> str:
    h = hashlib.sha256()
    for name, col in corpus.chunk(cfg, SEED, 0).items():
        h.update(name.encode())
        for a in (col if isinstance(col, tuple) else (col,)):
            _array_digest(h, a)
    h.update(corpus.payload(cfg, SEED, 0))
    return h.hexdigest()


def traffic_digest(workload: dict, cfg: dict) -> str:
    h = hashlib.sha256()
    ref = Reference({**cfg, "documents": DOCS}, SEED)
    requests = traffic.build(workload, cfg, SEED, 40.0)
    for r in requests:
        h.update(json.dumps(r, sort_keys=True).encode())
    bodies = [b for r in requests for b in r["bodies"]]
    for b in bodies[:32]:
        h.update(repr(work.body_bytes(ref, b)).encode())
    for b in bodies[:8]:
        ans = ref.answer(b)
        _array_digest(h, ans["mask"])
        _array_digest(h, ans["score"])
        h.update(repr((ans["total"], ans.get("aggs"))).encode())
    return h.hexdigest()


def test_every_configuration_and_cell_file_is_pinned():
    assert set(CHUNK_DIGESTS) == {
        f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))}
    assert set(TRAFFIC_DIGESTS) == set(CONFIG_OF) == {
        f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads"))}


@pytest.mark.parametrize("name", sorted(CHUNK_DIGESTS))
def test_corpus_chunk_and_payload_are_the_parents(name):
    assert chunk_digest(_load("configs", f"{name}.json")) == \
        CHUNK_DIGESTS[name]


@pytest.mark.parametrize("cell", sorted(TRAFFIC_DIGESTS))
def test_requests_bytes_and_answers_are_the_parents(cell):
    cfg = _load("configs", f"{CONFIG_OF[cell]}.json")
    assert traffic_digest(_load("workloads", f"{cell}.json"), cfg) == \
        TRAFFIC_DIGESTS[cell]
