"""chip_smoke.py — the quickest proof that the REST search path still starts
and answers correctly on the chip.

One process: it starts `NodeService` + `HttpServer` on a thread, loads
BASELINE.json `configs[1]` ("bool+filter BM25, 1M docs / 5 shards") through
`_bulk`, and drives `_msearch` / `_search` / aggregations over HTTP from
client code in the same process. Every answer is compared with a plain
numpy reference computed here from the same generated documents, and
`GET /_nodes/device_stats` must show the index resident in device memory
and the expected lane serving each step. Nothing is caught: the first thing
that is not as stated raises, and the process exits non-zero.

    python chip_smoke.py              # one TPU chip, 5 shards
    python chip_smoke.py --chips 4    # one node owning four chips, 4 shards

It passes on a TPU only: `JAX_PLATFORMS=cpu python chip_smoke.py` exits
non-zero before indexing anything. tests/test_chip_smoke.py calls `run`
at 2,000 documents with the expected platform `cpu`.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.metadata
import json
import os
import shutil
import sys
import time

import numpy as np

DOCS = 1_000_000          # BASELINE.json configs[1]
SHARDS = 5
BULK_DOCS = 4_000         # documents per `_bulk` request
VOCAB = 30_000            # the corpus shape: Zipf 1.3 over 30k terms,
MEAN_TOKENS = 20          # mean 20 tokens per document
N_STATUS = 20
Q_BATCH = 256             # bodies per `_msearch`
TOP_K = 1000
TERMS_PER_QUERY = 4
K1, B = 1.2, 0.75         # index/similarity defaults
TS_BASE = 1_700_000_000_000
TS_SPAN = 30 * 86_400_000
HOUR_MS = 3_600_000
REL_TOL = 1e-4

INDEX = "smoke"
DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".chip_smoke_data")

# reasons a lane may decline on this path; anything else in
# `lane_decisions` (an `error`, an unknown reason) fails the smoke
DOCUMENTED_DECLINES = {
    "packed:plan_shape",      # serving/executor.packed_spec_of said no
    "sparse:plan_shape",      # search/sparse_exec has no plan for the tree
    "mesh:no_mesh",           # fewer devices than (pow2-padded) shards
}


class SmokeFailure(Exception):
    """Something was not as the smoke states it must be."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# the corpus, made from the seed
# ---------------------------------------------------------------------------

class Corpus:
    """`docs` documents: `body` tokens (ragged: `lens` + flat `ranks`),
    `status`, `bytes`, `ts`. `bytes` and `ts` are built so that a 64-bit
    column narrowed on the device (to i32, f32 or anything short of 41
    exact bits) gives a wrong count: `bytes` is H·2^32 + L with half the
    L values in 0..7, so bounds of the form H·2^32 + 3 separate documents
    an f32 or a wrapped i32 cannot; a tenth of `ts` sits one millisecond
    either side of an hour boundary."""

    def __init__(self, docs: int, seed: int):
        rng = np.random.default_rng(seed)
        self.n = docs
        self.lens = np.maximum(rng.poisson(MEAN_TOKENS, docs), 3)
        self.ranks = (np.minimum(rng.zipf(1.3, size=int(self.lens.sum())),
                                 VOCAB) - 1).astype(np.int64)
        self.offsets = np.concatenate([[0], np.cumsum(self.lens)])
        self.status = rng.integers(0, N_STATUS, docs)
        hi = rng.integers(0, 256, docs).astype(np.int64)
        lo = np.where(rng.random(docs) < 0.5,
                      rng.integers(0, 8, docs),
                      rng.integers(0, 1 << 32, docs)).astype(np.int64)
        self.bytes = (hi << 32) + lo
        ts = TS_BASE + rng.integers(0, TS_SPAN, docs)
        edge = rng.random(docs) < 0.1
        hour = (ts // HOUR_MS) * HOUR_MS
        self.ts = np.where(edge, hour - rng.integers(0, 2, docs), ts)
        self.words = np.array([f"term{i:05d}" for i in range(VOCAB)])

    def source(self, i: int) -> dict:
        toks = self.ranks[self.offsets[i]:self.offsets[i + 1]]
        return {"body": " ".join(self.words[toks]),
                "status": f"s{self.status[i]:02d}",
                "bytes": int(self.bytes[i]), "ts": int(self.ts[i])}

    def bulk_payload(self, start: int, stop: int) -> bytes:
        lines = []
        for i in range(start, stop):
            lines.append('{"index":{"_id":"%d"}}' % i)
            lines.append(json.dumps(self.source(i), separators=(",", ":")))
        return ("\n".join(lines) + "\n").encode()


class Reference:
    """Plain numpy BM25 over the corpus with index-global statistics —
    what both the packed and the general lane score with."""

    def __init__(self, corpus: Corpus):
        self.c = corpus
        doc_of = np.repeat(np.arange(corpus.n, dtype=np.int64), corpus.lens)
        pairs, tf = np.unique(corpus.ranks * corpus.n + doc_of,
                              return_counts=True)      # sorted by (term, doc)
        self.post_doc = pairs % corpus.n
        self.post_tf = tf.astype(np.float64)
        self.term_start = np.searchsorted(pairs // corpus.n,
                                          np.arange(VOCAB + 1))
        self.avgdl = float(corpus.lens.sum()) / corpus.n
        self.n_postings = len(pairs)

    def match(self, terms) -> tuple[np.ndarray, np.ndarray]:
        """(doc ids ascending, BM25 score) of the documents holding any of
        `terms` — a `match` query with the default `or` operator."""
        docs, contribs = [], []
        n = self.c.n
        for t in terms:
            s, e = self.term_start[t], self.term_start[t + 1]
            d = self.post_doc[s:e]
            tf = self.post_tf[s:e]
            df = e - s
            idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
            norm = K1 * (1.0 - B + B * self.c.lens[d] / self.avgdl)
            docs.append(d)
            contribs.append(idf * (K1 + 1.0) * tf / (tf + norm))
        if not docs:
            return np.empty(0, np.int64), np.empty(0)
        uniq, inv = np.unique(np.concatenate(docs), return_inverse=True)
        return uniq, np.bincount(inv, weights=np.concatenate(contribs))


def check_hits(label: str, resp: dict, ref_docs: np.ndarray,
               ref_scores: np.ndarray, size: int) -> None:
    """Total exact; scores to REL_TOL; ids equal above the last tie."""
    check("error" not in resp, f"{label}: item failed: {resp.get('error')}")
    hits = resp["hits"]
    check(hits["total"] == len(ref_docs),
          f"{label}: total {hits['total']} != reference {len(ref_docs)}")
    got = hits["hits"]
    want_n = min(size, len(ref_docs))
    check(len(got) == want_n, f"{label}: {len(got)} hits, expected {want_n}")
    if not want_n:
        return
    ref_of = dict(zip(ref_docs.tolist(), ref_scores.tolist()))
    got_scores = np.array([h["_score"] for h in got])
    got_ids = [int(h["_id"]) for h in got]
    check(np.all(np.isfinite(got_scores)), f"{label}: non-finite score")
    check(np.all(np.diff(got_scores) <= 0), f"{label}: hits not score-sorted")
    check(len(set(got_ids)) == len(got_ids), f"{label}: duplicate hit")
    for i, s in zip(got_ids, got_scores):
        check(i in ref_of, f"{label}: hit {i} does not match the query")
        check(abs(s - ref_of[i]) <= REL_TOL * ref_of[i],
              f"{label}: doc {i} score {s} != reference {ref_of[i]}")
    # every reference document scoring clearly above the last returned one
    # must have been returned
    floor = got_scores[-1] * (1.0 + REL_TOL)
    must = set(ref_docs[ref_scores > floor].tolist())
    check(must <= set(got_ids),
          f"{label}: {len(must - set(got_ids))} better documents missing")
    if hits.get("max_score") is not None:
        check(abs(hits["max_score"] - ref_scores.max())
              <= REL_TOL * ref_scores.max(), f"{label}: max_score differs")


# ---------------------------------------------------------------------------
# HTTP client
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=900)

    def send(self, method: str, path: str, body=None) -> bytes:
        """-> response body. Anything but 200 (a 429 included) fails."""
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        if isinstance(body, str):
            body = body.encode()
        self.conn.request(method, path, body=body)
        r = self.conn.getresponse()
        data = r.read()
        check(r.status == 200,
              f"{method} {path}: HTTP {r.status}: {data[:2000]!r}")
        return data

    def call(self, method: str, path: str, body=None):
        return json.loads(self.send(method, path, body))


def msearch_payload(bodies: list[dict]) -> str:
    lines = []
    for b in bodies:
        lines.append(json.dumps({"index": INDEX}))
        lines.append(json.dumps(b))
    return "\n".join(lines) + "\n"


def device_stats(client: Client) -> dict:
    return client.call("GET", "/_nodes/device_stats")["nodes"]["tpu-node-0"]


def metric(client: Client, name: str) -> float:
    total = 0.0
    for line in client.send("GET", "/_metrics").decode().splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def lanes_since(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def chosen(delta: dict) -> list[str]:
    return sorted(k.split(":")[0] for k in delta if k.endswith(":chosen"))


# ---------------------------------------------------------------------------
# the smoke
# ---------------------------------------------------------------------------

def run(docs: int, shards: int, platform: str, *, seed: int = 0,
        n_devices: int = 1, reduced: list | None = None) -> dict:
    """Index `docs` documents into `shards` shards on a fresh node and
    drive steps (a)–(e); returns the summary. Raises on the first
    mismatch. `platform` is what `jax.devices()[0].platform` must be and
    `n_devices` how many devices the node must own (more than one: the
    mesh lane must serve steps (d) and (e))."""
    import jax
    import elasticsearch_tpu  # noqa: F401 — sets x64 and the compile cache

    dev = jax.devices()[0]
    check(dev.platform == platform,
          f"JAX runs on [{dev.platform}], this smoke needs [{platform}]")
    check(len(jax.devices()) == n_devices,
          f"JAX reports {len(jax.devices())} devices, this run is for "
          f"{n_devices} (see --chips)")
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "numpy")}
    if platform == "tpu":
        versions["libtpu"] = importlib.metadata.version("libtpu")
    print(f"device_kind={dev.device_kind} devices={n_devices} "
          f"versions={versions} compile_cache="
          f"{jax.config.jax_compilation_cache_dir}", flush=True)

    from elasticsearch_tpu.node import NodeService
    from elasticsearch_tpu.rest import HttpServer

    t_start = time.perf_counter()
    corpus = Corpus(docs, seed)
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    node = NodeService(DATA_DIR)
    server = HttpServer(node, port=0).start()
    try:
        smoke = Smoke(Client(server.port), corpus, shards, platform,
                      n_devices, seed)
        smoke.load()
        for step in (smoke.step_a, smoke.step_b, smoke.step_c,
                     smoke.step_d, smoke.step_e):
            step()
        summary = smoke.verify_device()
    finally:
        server.stop()
        node.close()
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    summary.update({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "n_devices": n_devices, "docs": docs, "shards": shards,
        "seed": seed, "versions": versions, "reduced": reduced or [],
        "wall_s": round(time.perf_counter() - t_start, 1)})
    return summary


class Smoke:
    """The load and the five steps, in the order `run` calls them."""

    def __init__(self, client: Client, corpus: Corpus, shards: int,
                 platform: str, n_devices: int, seed: int):
        self.client = client
        self.corpus = corpus
        self.shards = shards
        self.platform = platform
        self.n_devices = n_devices
        self.rng = np.random.default_rng(seed + 1)
        self.ref: Reference | None = None
        self.lanes: dict[str, list[str]] = {}     # step -> lanes chosen
        self.request_ms: dict[str, float] = {}    # step -> HTTP wall time
        self.summary: dict = {}

    # -- helpers --------------------------------------------------------------

    def terms(self, n: int = TERMS_PER_QUERY) -> list[int]:
        """Mid-frequency term ranks, distinct within a query."""
        return self.rng.choice(np.arange(64, 8192), size=n,
                               replace=False).tolist()

    def text(self, ts) -> str:
        return " ".join(self.corpus.words[ts])

    def request(self, name: str, method: str, path: str, body):
        """One timed request of step `name`, with the lane decisions it
        caused booked to the step."""
        before = device_stats(self.client)["lane_decisions"]
        t = time.perf_counter()
        out = self.client.call(method, path, body)
        ms = (time.perf_counter() - t) * 1000
        delta = lanes_since(
            before, device_stats(self.client)["lane_decisions"])
        self.request_ms[name] = round(self.request_ms.get(name, 0) + ms, 1)
        self.lanes[name] = sorted(set(self.lanes.get(name, []))
                                  | set(chosen(delta)))
        print(f"step {name}: {method} {path} {ms:.1f} ms lanes={delta}",
              flush=True)
        return out

    # -- load -----------------------------------------------------------------

    def load(self) -> None:
        client, corpus, docs = self.client, self.corpus, self.corpus.n
        self.lanes_at_start = device_stats(client)["lane_decisions"]
        client.call("PUT", f"/{INDEX}", {
            "settings": {"number_of_shards": self.shards,
                         "number_of_replicas": 0},
            "mappings": {"_doc": {"properties": {
                "body": {"type": "string"},
                "status": {"type": "string", "index": "not_analyzed"},
                "bytes": {"type": "long"},
                "ts": {"type": "date"}}}}})
        t0 = time.perf_counter()
        acked = 0
        for start in range(0, docs, BULK_DOCS):
            out = client.call(
                "POST", f"/{INDEX}/_bulk",
                corpus.bulk_payload(start, min(start + BULK_DOCS, docs)))
            check(not out["errors"], f"_bulk at {start}: item errors")
            acked += len(out["items"])
        client.call("POST", f"/{INDEX}/_refresh")
        index_s = time.perf_counter() - t0
        print(f"indexed {acked} docs in {index_s:.1f}s", flush=True)
        check(client.call("GET", f"/{INDEX}/_count")["count"]
              == acked == docs,
              "_count differs from the documents acknowledged")
        for i in self.rng.integers(0, docs, 5).tolist():
            got = client.call("GET", f"/{INDEX}/_doc/{i}")
            check(got["found"] and got["_source"] == corpus.source(i),
                  f"GET _doc/{i} does not return its source")
        self.ref = Reference(corpus)
        self.summary.update({
            "index_s": round(index_s, 1),
            "segments": int(metric(client, "es_index_segments")),
            "postings": self.ref.n_postings})

    # -- (a) 256 match bodies, top-1000, no _source, twice ------------------------

    def step_a(self) -> None:
        qterms = [self.terms() for _ in range(Q_BATCH)]
        payload = msearch_payload([
            {"query": {"match": {"body": self.text(ts)}}, "size": TOP_K,
             "_source": False} for ts in qterms])

        def one_pass(label):
            out = self.request(label, "POST", "/_msearch",
                               payload)["responses"]
            check(len(out) == Q_BATCH, f"{label}: {len(out)} responses")
            for qi, (resp, ts) in enumerate(zip(out, qterms)):
                check_hits(f"{label}[{qi}]", resp, *self.ref.match(ts),
                           TOP_K)

        one_pass("a1")
        self.summary["compile_ms_cold_first_msearch"] = round(
            metric(self.client, "es_jit_compile_time_millis_total"), 1)
        compiles0 = metric(self.client, "es_jit_compiles_total")
        prog0 = device_stats(self.client)["programs"]["compiles_total"]
        one_pass("a2")
        check(metric(self.client, "es_jit_compiles_total") == compiles0
              and device_stats(self.client)["programs"]["compiles_total"]
              == prog0, "the second pass of (a) compiled")

    # -- (b) 256 bool{must: match, filter: range on bytes}, bounds > 2^32 -------

    def step_b(self) -> None:
        # every bound is the value of a document that matches the body's
        # text (of one with a low half in 0..7 where there is one: others
        # then sit one unit to either side). A body in four closes both
        # ends, the others open one or both: `lt` read as `lte` on the TPU
        # when the chip compared emulated float64 (PR 33), and the total was
        # then too high; since PR 34 the host resolves each end to an ordinal
        specs = []
        for qi in range(Q_BATCH):
            ts = self.terms()
            v = self.corpus.bytes[self.ref.match(ts)[0]]
            near = v[(v & 0xFFFFFFFF) < 8]
            lo, hi = sorted(self.rng.choice(
                near if len(near) else v if len(v) else self.corpus.bytes,
                2).tolist())
            specs.append((ts, ("gte", "gt")[qi % 2], lo,
                          ("lte", "lt")[qi // 2 % 2], hi))
        out = self.request("b", "POST", "/_msearch", msearch_payload([
            {"query": {"bool": {
                "must": [{"match": {"body": self.text(ts)}}],
                "filter": [{"range": {"bytes": {lo_op: lo, hi_op: hi}}}]}},
             "size": TOP_K, "_source": False}
            for ts, lo_op, lo, hi_op, hi in specs]))["responses"]
        check(len(out) == Q_BATCH, f"b: {len(out)} responses")
        on_open_end = 0
        for qi, (resp, (ts, lo_op, lo, hi_op, hi)) in enumerate(
                zip(out, specs)):
            d, s = self.ref.match(ts)
            v = self.corpus.bytes[d]
            keep = ((v > lo) if lo_op == "gt" else (v >= lo)) \
                & ((v < hi) if hi_op == "lt" else (v <= hi))
            on_open_end += int(((v == lo) & (lo_op == "gt")).sum()
                               + ((v == hi) & (hi_op == "lt")).sum())
            check_hits(f"b[{qi}]", resp, d[keep], s[keep], TOP_K)
        check(on_open_end > 0, "b: no candidate sat on an open end")

    # -- (c) eight solo _search, size 10, with _source (fetch phase) -------------

    def step_c(self) -> None:
        for qi in range(8):
            ts = self.terms()
            resp = self.request("c", "POST", f"/{INDEX}/_search", {
                "query": {"match": {"body": self.text(ts)}}, "size": 10})
            check_hits(f"c[{qi}]", resp, *self.ref.match(ts), 10)
            for h in resp["hits"]["hits"]:
                check(h["_source"] == self.corpus.source(int(h["_id"])),
                      f"c[{qi}]: _source of {h['_id']} differs")

    # -- (d) dis_max over two match clauses: packed declines, a dense lane -------

    def step_d(self) -> None:
        # steps (d) and (e) are the ones a mesh serves: what they add to
        # each device and to the host-merge count is checked at the end
        self.hbm_before_d = device_stats(self.client)["hbm"]
        self.merges_before_d = metric(self.client,
                                      "es_search_host_merges_total")
        t1, t2 = self.terms(2), self.terms(2)
        resp = self.request("d", "POST", f"/{INDEX}/_search", {
            "query": {"dis_max": {"queries": [
                {"match": {"body": self.text(t1)}},
                {"match": {"body": self.text(t2)}}]}},
            "size": 10, "_source": False})
        d1, s1 = self.ref.match(t1)
        d2, s2 = self.ref.match(t2)
        best = np.zeros(self.corpus.n)
        best[d1] = s1
        best[d2] = np.maximum(best[d2], s2)
        d = np.union1d(d1, d2)
        check_hits("d", resp, d, best[d], 10)

    # -- (e) size 0: terms on status + hourly date_histogram on ts --------------

    def step_e(self) -> None:
        ts = [int(self.rng.integers(8, 32))]     # a frequent term: wide mask
        resp = self.request("e", "POST", f"/{INDEX}/_search", {
            "size": 0, "query": {"match": {"body": self.text(ts)}},
            "aggs": {
                "by_status": {"terms": {"field": "status",
                                        "size": N_STATUS}},
                "per_hour": {"date_histogram": {"field": "ts",
                                                "interval": "hour"}}}})
        d, _ = self.ref.match(ts)
        check(resp["hits"]["total"] == len(d), "e: total differs")
        want = np.bincount(self.corpus.status[d], minlength=N_STATUS)
        got = {b["key"]: b["doc_count"]
               for b in resp["aggregations"]["by_status"]["buckets"]}
        check(got == {f"s{i:02d}": int(c) for i, c in enumerate(want) if c},
              "e: terms(status) bucket counts differ")
        hours, counts = np.unique(self.corpus.ts[d] // HOUR_MS,
                                  return_counts=True)
        want = {int(h) * HOUR_MS: int(c) for h, c in zip(hours, counts)}
        got = {b["key"]: b["doc_count"]
               for b in resp["aggregations"]["per_hour"]["buckets"]
               if b["doc_count"]}
        wrong = {k for k in set(got) | set(want) if got.get(k) != want.get(k)}
        check(not wrong, f"e: date_histogram(ts): {len(wrong)} of "
                         f"{len(want)} hourly buckets differ, "
                         f"e.g. {sorted(wrong)[:3]}")

    # -- the device really did it ---------------------------------------------------

    def verify_device(self) -> dict:
        client, platform = self.client, self.platform
        mesh = self.n_devices > 1
        host_merges = metric(client, "es_search_host_merges_total") \
            - self.merges_before_d
        stats = device_stats(client)
        hbm = stats["hbm"]
        check(hbm and all(k.startswith(f"{platform}:") for k in hbm),
              f"hbm keys {sorted(hbm)} are not {platform}:<id>")
        in_use = {k: v["bytes_in_use"] for k, v in hbm.items()}
        if platform == "tpu":
            check(all(v["supported"] for v in hbm.values()),
                  "a device reports no memory stats")
            check(sum(in_use.values()) >= 12 * self.ref.n_postings,
                  f"bytes_in_use {in_use} < 12 B x {self.ref.n_postings} "
                  "postings: the index is not resident on the device")
        for name in ("a1", "a2", "b", "c"):
            check(self.lanes[name] == ["packed"],
                  f"step {name} was served by {self.lanes[name]}, not packed")
        dense = {"mesh"} if mesh else {"stacked", "stacked_blockwise", "loop"}
        for name in ("d", "e") if mesh else ("d",):
            # the fan-out's cross-shard reduce (`host_merge`) is no query lane
            query_lanes = set(self.lanes[name]) - {"host_merge"}
            check(query_lanes and query_lanes <= dense,
                  f"step {name} was served by {self.lanes[name]}, "
                  f"expected {sorted(dense)}")
        undocumented = {
            k: v for k, v in lanes_since(self.lanes_at_start,
                                         stats["lane_decisions"]).items()
            if not k.endswith(":chosen") and k not in DOCUMENTED_DECLINES}
        check(not undocumented, f"undocumented lane declines: {undocumented}")
        added = {k: v - self.hbm_before_d[k]["bytes_in_use"]
                 for k, v in in_use.items()}
        if mesh:
            check(host_merges == 0,
                  f"{host_merges} host merges during mesh-served steps")
        if mesh and platform == "tpu":
            check(len(added) == self.n_devices and min(added.values()) > 0
                  and max(added.values()) <= 2 * min(added.values()),
                  "steps (d)+(e) did not add index bytes to every device "
                  f"in roughly equal parts: {added}")
        self.summary.update({
            "compile_ms_total": round(
                metric(client, "es_jit_compile_time_millis_total"), 1),
            "compiles_total": int(metric(client, "es_jit_compiles_total")),
            "lanes": self.lanes, "request_ms": self.request_ms,
            "hbm_bytes_in_use": in_use,
            "hbm_bytes_added_by_d_e": added,
            "hbm_peak_bytes": {k: v["peak_bytes"] for k, v in hbm.items()},
            "hbm_limit_bytes": {k: v["limit_bytes"] for k, v in hbm.items()},
            "breaker_total_limit_bytes": client.call(
                "GET", "/_nodes/stats")["nodes"]["tpu-node-0"]["breakers"]
            ["parent"]["limit_size_in_bytes"]})
        return self.summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    shards, reduced = SHARDS, []
    if args.chips == 4:
        # the general mesh lane (`match`, dis_max, its aggregations: steps
        # (d) and (e)) pads the shard count to a power of two, so 5 shards
        # would need 8 devices (parallel/mesh_exec.py `mesh_for`). The limit
        # is that lane's alone since PR 31: the panel lane's axis is the
        # chips, and it runs 5 shards on 4 (search/aggs/panels.py; the
        # benchmark's cell httplogs.dashboard-mesh)
        shards = 4
        reduced.append("shards 5 -> 4: the general mesh lane pads the shard "
                       "axis to a power of two and the host has 4 chips")
    summary = run(DOCS, shards, "tpu", seed=args.seed, n_devices=args.chips,
                  reduced=reduced)
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": {
        "platform": summary["platform"], "kind": summary["device_kind"],
        "count": summary["n_devices"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
