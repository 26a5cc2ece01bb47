"""Headline benchmark: BM25 top-1000 QPS measured THROUGH THE PRODUCT —
documents indexed via HTTP `_bulk` (full analysis + engine + segments),
queries served via HTTP `_msearch` batches hitting the sort-reduce sparse
kernel (the same scoring path every `_search` request takes).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

vs_baseline: the identical engine+HTTP pipeline run in a subprocess pinned to
the XLA-CPU backend — the documented proxy rung of the baseline ladder
(BASELINE.md: XLA-CPU proxy -> stock ES same corpus -> 10M-doc Wiki).
>1.0 = faster than CPU. Set BENCH_CPU=0 to skip the CPU leg.

Workload shape: BASELINE.json config #1/#2 (match-query BM25 over an
analyzed English-like corpus; default 100k docs, override with BENCH_DOCS),
k=1000 like the north-star metric; solo `_search` p50/p99 (size=10) is
reported alongside.

Secondary leg: `python bench.py --kernel` runs the round-1 pure-kernel
synthetic harness (1M docs, no engine) for kernel-regression tracking.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

# make the CPU backend available alongside the accelerator for --kernel
_plat = os.environ.get("JAX_PLATFORMS", "")
if _plat and "cpu" not in _plat.split(","):
    os.environ["JAX_PLATFORMS"] = _plat + ",cpu"

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Wall-clock budget: the harness kills bench.py at ~870s (round 5 hit
# rc=124 and lost the whole headline line). Legs check the budget between
# measurement passes and DEGRADE — the headline JSON always prints from
# whatever completed.
BENCH_T0 = time.monotonic()
BENCH_TIME_BUDGET = float(os.environ.get("BENCH_TIME_BUDGET", "600"))


def _remaining() -> float:
    return BENCH_TIME_BUDGET - (time.monotonic() - BENCH_T0)


def _over_budget(margin: float = 0.0) -> bool:
    return _remaining() <= margin


# The headline line survives EVERYTHING (BENCH_r05 recorded "parsed": null
# at rc=124): legs update _FINAL_LINE as results land, and a SIGTERM/SIGINT
# (the harness timeout's first strike) prints whatever is measured so far
# instead of dying silently. _emit prints at most once.
# tail-latency headline keys (ISSUE 9) default to null at import time so
# a forced timeout/bailout still emits them (the subprocess guard test
# pins this)
_FINAL_LINE: dict = {"value": None, "unit": "qps",
                     "conc_p99_ms": None, "shed_429s": None,
                     "hedged_wins": None,
                     # ANN vector-serving headline keys (ISSUE 10):
                     # seeded null at import so a forced timeout still
                     # emits them (the subprocess guard contract)
                     "knn_nprobe": None, "knn_recall_at_10": None,
                     "ann_dispatches": None,
                     # cluster-wide collectives data plane (ISSUE 11):
                     # seeded null at import so a forced timeout still
                     # emits them (the subprocess guard contract)
                     "cluster_host_reduce_qps": None,
                     "mesh_agg_dispatches": None,
                     # quantized ANN tier (ISSUE 12): seeded null at
                     # import so a forced timeout still emits them
                     "knn_int8_qps": None, "knn_pq_qps": None,
                     "pq_recall_at_10": None,
                     "vector_stack_bytes_f32": None,
                     "vector_stack_bytes_quantized": None,
                     # chaos harness (ISSUE 14): seeded null at import so
                     # a forced timeout still emits them
                     "chaos_rounds": None, "chaos_parity_checks": None,
                     "chaos_invariant_violations": None,
                     # rebalance-under-load (ISSUE 15): seeded null at
                     # import so a forced timeout still emits them
                     "rebalance_p99_ms": None, "rebalance_move_s": None,
                     "recovery_throttle_bytes_per_sec": None,
                     "decider_vetoes": None,
                     # device telemetry flight recorder (ISSUE 16): seeded
                     # null at import so a forced timeout still emits them
                     "xla_compile_ms_total": None, "hbm_peak_bytes": None,
                     "lane_decision_counts": None, "flight": None,
                     # log-analytics observability tier (ISSUE 17):
                     # seeded null at import so a forced timeout still
                     # emits them
                     "sorted_mesh_qps": None, "sorted_fanout_qps": None,
                     "subagg_mesh_qps": None,
                     "monitoring_overview_p50_ms": None,
                     # reverse search + script compiler (ISSUE 18):
                     # seeded null at import so a forced timeout still
                     # emits them
                     "percolate_qps": None, "percolate_matrix_qps": None,
                     "percolate_vs_loop": None,
                     "script_score_qps": None, "script_vs_decline": None,
                     # pod-scale serving (ISSUE 19): seeded null at
                     # import so a forced timeout still emits them
                     "pod_qps": None, "single_pool_qps": None,
                     "pod_vs_single": None, "dcn_hops_per_query": None,
                     "exec_lock_waits": None,
                     # watcher alerting tier (ISSUE 20): seeded null at
                     # import so a forced timeout still emits them
                     "watcher_evals_per_sec": None,
                     "watcher_fire_p50_ms": None,
                     "watcher_percolate_rides": None,
                     "composite_page_qps": None}
_LINE_PRINTED = False


def _emit(line: dict) -> None:
    global _LINE_PRINTED
    if not _LINE_PRINTED:
        _LINE_PRINTED = True
        print(json.dumps(line), flush=True)


# legs that raised or overran their slice: named in the line, and the
# process exits non-zero (a dropped leg is not a successful run)
_FAILED_LEGS: list[str] = []


class _BudgetExceeded(Exception):
    """Raised INTO a running leg by the SIGALRM handler while budget
    remains: the per-leg try/except degrades that leg and the run
    continues. Past the budget, SIGALRM emits the line and exits instead
    — the r05 failure mode (rc=124, "parsed": null) can't recur as long
    as the interpreter is executing Python bytecode at all."""


_ALARM_MARGIN = float(os.environ.get("BENCH_ALARM_MARGIN", "45"))


def _install_bailout() -> None:
    """Arm the always-emit guards. MUST run before the first leg (module
    import time): round 5 hung during a leg with no handler armed and the
    harness's rc=124 erased the whole headline line. The line a bail-out
    prints is evidence of how far the run got, not a result: the exit
    code is non-zero."""
    import signal

    def bail(signum, frame):  # noqa: ANN001 — signal handler signature
        _FINAL_LINE.setdefault("error", f"terminated by signal {signum} "
                               f"({_remaining():.0f}s of budget left)")
        _emit(_FINAL_LINE)
        os._exit(1)

    def alarm(signum, frame):  # noqa: ANN001 — signal handler signature
        if _remaining() <= 5.0:
            # the whole budget is gone: print whatever landed and stop
            _FINAL_LINE.setdefault(
                "error", "wall-clock budget exhausted (SIGALRM)")
            _emit(_FINAL_LINE)
            os._exit(1)
        # a LEG overran its slice while budget remains: re-arm the hard
        # stop at the budget edge and interrupt the leg so it degrades
        signal.alarm(max(int(_remaining()), 1))
        raise _BudgetExceeded(
            f"leg alarm fired with {_remaining():.0f}s of budget left")

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, bail)
        except (ValueError, OSError):      # non-main thread / restricted env
            pass
    try:
        signal.signal(signal.SIGALRM, alarm)
        signal.alarm(max(int(BENCH_TIME_BUDGET + _ALARM_MARGIN), 1))
    except (ValueError, OSError, AttributeError):
        pass


_FLIGHT_PREV: dict = {"invocations": 0, "device_ms": 0.0,
                      "compile_ms": 0.0, "compiles": 0, "lanes": {}}


def _flight_snapshot(leg: str) -> None:
    """Flight recorder (ISSUE 16): after every leg, fold that leg's
    device-stats DELTAS (program dispatches, compile time, lane
    decisions, HBM high-water) into _FINAL_LINE["flight"]. The sidecar
    updates incrementally, so a SIGALRM/rc=124 mid-leg still emits every
    leg that finished before the kill — the same always-emit contract as
    the headline keys."""
    try:
        from elasticsearch_tpu.common import device_stats
        snap = device_stats.registry_snapshot(top_n=0, with_cost=False)
        lanes = device_stats.lane_decisions_snapshot()
        prev = _FLIGHT_PREV
        entry = {
            "invocations": snap["invocations_total"] - prev["invocations"],
            "device_ms": round(
                snap["device_time_in_millis"] - prev["device_ms"], 3),
            "compile_ms": round(
                snap["compile_time_in_millis"] - prev["compile_ms"], 3),
            "compiles": snap["compiles_total"] - prev["compiles"],
            "lane_decisions": {k: n - prev["lanes"].get(k, 0)
                               for k, n in lanes.items()
                               if n - prev["lanes"].get(k, 0)},
            "hbm_peak_bytes": device_stats.hbm_peak_bytes()}
        _FLIGHT_PREV.update(
            {"invocations": snap["invocations_total"],
             "device_ms": snap["device_time_in_millis"],
             "compile_ms": snap["compile_time_in_millis"],
             "compiles": snap["compiles_total"], "lanes": lanes})
        flight = _FINAL_LINE.get("flight") or {}
        flight[leg] = entry
        flight["program_count"] = snap["program_count"]
        _FINAL_LINE["flight"] = flight
        _FINAL_LINE["xla_compile_ms_total"] = round(
            device_stats.compile_ms_total(), 3)
        _FINAL_LINE["hbm_peak_bytes"] = device_stats.hbm_peak_bytes()
        _FINAL_LINE["lane_decision_counts"] = lanes
    except Exception as e:  # noqa: BLE001 — telemetry never fails the run
        print(f"flight snapshot ({leg}) failed: {e}", file=sys.stderr)


def _arm_leg_alarm(reserve: float) -> None:
    """Per-leg wall-clock enforcement by elapsed-time subtraction: the
    leg about to run may consume at most what is LEFT of the budget minus
    `reserve` (held back for later legs + the final print). A leg that
    hangs gets a _BudgetExceeded raised into it and degrades instead of
    erasing the run."""
    try:
        import signal
        signal.alarm(max(int(_remaining() - reserve), 1))
    except (ValueError, OSError, AttributeError):
        pass


def _arm_hard_alarm() -> None:
    """Measurement done: keep only the budget-edge emit guard armed."""
    try:
        import signal
        signal.alarm(max(int(_remaining() + _ALARM_MARGIN), 5))
    except (ValueError, OSError, AttributeError):
        pass


# armed at import — before the first leg, in every mode (main process,
# the BENCH_LEG=cpu subprocess, --kernel)
_install_bailout()

if os.environ.get("BENCH_SELFTEST_HANG"):
    # test seam: simulate the r05 hang (a leg stuck before any result
    # lands). The guards above must still print the one-line JSON.
    _FINAL_LINE.setdefault("metric", "selftest_hang")
    time.sleep(3600)


N_DOCS = int(os.environ.get("BENCH_DOCS", str(100_000)))
VOCAB = 30_000
AVG_DL = 20
Q_BATCH = 256             # queries per _msearch request (device batch)
N_BATCHES = 4             # distinct msearch payloads
REPS = 3
K = 1000                  # top-1000 (headline metric)
T = 4                     # terms per query
LATENCY_N = 50            # solo _search latency probes

# config #3: terms + date_histogram analytics over a log-event corpus
AGG_DOCS = int(os.environ.get("BENCH_AGG_DOCS", str(4_000_000)))
AGG_Q = 128               # agg requests per msearch batch
AGG_BATCHES = 4
# configs #4/#5: stored-vector cosine + BM25->dense hybrid rescore
VEC_DOCS = int(os.environ.get("BENCH_VEC_DOCS", str(100_000)))
VEC_DIMS = 768
VEC_Q = 128
VEC_BATCHES = 4
# IVF-clustered ANN (ISSUE 10): clusters + probes for the vector legs —
# nprobe/nlist = 1/16 of the corpus scanned per query
VEC_NLIST = int(os.environ.get("BENCH_VEC_NLIST", "256"))
VEC_NPROBE = int(os.environ.get("BENCH_VEC_NPROBE", "16"))
# recall-sensitive leg: pin f32 matmuls (`index.knn.precision`) — the
# recall@10 bar is measured against an f32 numpy oracle, and bf16's
# ~1e-3 relative error alone costs ~0.03 recall on near-tie neighbor
# sets (see README Vector search); on CPU runners f32 is also native
VEC_PRECISION = os.environ.get("BENCH_VEC_PRECISION", "f32")
# quantized ANN tier (ISSUE 12): PQ subquantizers (768/48 = 16-dim
# subspaces, 48 B/vec = 1/64 of f32) and the full-precision rescore
# window the int8/pq scans rank through before answering
VEC_PQ_M = int(os.environ.get("BENCH_VEC_PQ_M", "48"))
VEC_RESCORE = int(os.environ.get("BENCH_VEC_RESCORE", "64"))


def make_corpus(n_docs: int, seed: int = 7):
    """Zipf-distributed synthetic English-like corpus, built as strings so
    every doc passes the real analysis chain."""
    rng = np.random.default_rng(seed)
    words = np.array([f"term{i:05d}" for i in range(VOCAB)])
    lens = np.maximum(rng.poisson(AVG_DL, n_docs), 3)
    ranks = np.minimum(rng.zipf(1.3, size=int(lens.sum())), VOCAB) - 1
    docs = []
    pos = 0
    for L in lens:
        docs.append(" ".join(words[ranks[pos:pos + L]]))
        pos += L
    return docs


def make_queries(n: int, seed: int = 42) -> list[str]:
    rng = np.random.default_rng(seed)
    tids = rng.integers(64, 8192, size=(n, T))
    return [" ".join(f"term{t:05d}" for t in row) for row in tids]


def http(port: int, method: str, path: str, body: bytes | str = b"",
         timeout: float = 600.0) -> dict:
    import urllib.request
    if isinstance(body, str):
        body = body.encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=body or None, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def run_agg_leg(tag: str) -> dict:
    """BASELINE config #3: terms + date_histogram aggregations over an
    AGG_DOCS log-event index, through HTTP — the device-side masked
    bincount / affine-histogram collect path (ops/aggs.py)."""
    import shutil
    import tempfile
    from elasticsearch_tpu.node import NodeService
    from elasticsearch_tpu.rest import HttpServer

    workdir = tempfile.mkdtemp(prefix=f"bench-agg-{tag}-")
    node = NodeService(os.path.join(workdir, "node"))
    server = HttpServer(node, port=0).start()
    port = server.port
    try:
        rng = np.random.default_rng(11)
        tags = [f"svc{i:02d}" for i in range(20)]
        t0 = time.perf_counter()
        http(port, "PUT", "/logs", json.dumps(
            {"settings": {"number_of_shards": 1},
             "mappings": {"_doc": {"properties": {
                 "tag": {"type": "string", "index": "not_analyzed"},
                 "ts": {"type": "date"},
                 "value": {"type": "long"}}}}}))
        base_ms = 1_700_000_000_000
        batch = 10_000
        tag_ids = rng.integers(0, len(tags), AGG_DOCS)
        ts = base_ms + rng.integers(0, 30 * 86_400_000, AGG_DOCS)
        vals = rng.integers(0, 10_000, AGG_DOCS)
        for i in range(0, AGG_DOCS, batch):
            lines = []
            for j in range(i, min(i + batch, AGG_DOCS)):
                lines.append('{"index":{"_id":"%d"}}' % j)
                lines.append('{"tag":"%s","ts":%d,"value":%d}'
                             % (tags[tag_ids[j]], ts[j], vals[j]))
            http(port, "POST", "/logs/_bulk", "\n".join(lines) + "\n")
        http(port, "POST", "/logs/_refresh")
        http(port, "POST", "/logs/_optimize")
        index_secs = time.perf_counter() - t0

        payloads = []
        for bi in range(AGG_BATCHES):
            lines = []
            for qi in range(AGG_Q):
                tag = tags[(bi * AGG_Q + qi) % len(tags)]
                lines.append('{"index":"logs"}')
                lines.append(json.dumps({
                    "size": 0,
                    "query": {"term": {"tag": tag}},
                    "aggs": {
                        "per_day": {"date_histogram": {"field": "ts",
                                                       "interval": "1d"}},
                        "by_tag": {"terms": {"field": "tag"}},
                        "val_stats": {"stats": {"field": "value"}}}}))
            payloads.append("\n".join(lines) + "\n")
        http(port, "POST", "/_msearch", payloads[0])     # warm compile
        t1 = time.perf_counter()
        n = 0
        for _ in range(REPS):
            for pl in payloads:
                out = http(port, "POST", "/_msearch", pl)
                n += len(out["responses"])
            if _over_budget():
                break          # a slow leg degrades the number, not erases it
        res = {"agg_qps": n / (time.perf_counter() - t1),
               "agg_index_secs": index_secs,
               "agg_docs_per_sec": AGG_DOCS / index_secs}

        # request-cache serving leg (ISSUE 3): the dashboard workload —
        # one heavy size=0 aggregation repeated verbatim. The first call
        # fills the shared request cache; repeats are O(1) lookups. The
        # uncached probes rotate a range filter so every body is novel —
        # the latency gap IS the cache win, measured through HTTP.
        solo = json.dumps({
            "size": 0, "query": {"term": {"tag": tags[0]}},
            "aggs": {"per_day": {"date_histogram": {"field": "ts",
                                                    "interval": "1d"}},
                     "val_stats": {"stats": {"field": "value"}}}})
        http(port, "POST", "/logs/_search", solo)        # fill (miss)
        cached_lat = []
        for _ in range(25):
            t2 = time.perf_counter()
            http(port, "POST", "/logs/_search", solo)
            cached_lat.append((time.perf_counter() - t2) * 1000)
        uncached_lat = []
        for i in range(10):
            body = json.dumps({
                "size": 0, "query": {"bool": {
                    "must": [{"term": {"tag": tags[0]}}],
                    "filter": [{"range": {"value": {"gte": i}}}]}},
                "aggs": {"per_day": {"date_histogram": {
                    "field": "ts", "interval": "1d"}},
                    "val_stats": {"stats": {"field": "value"}}}})
            t2 = time.perf_counter()
            http(port, "POST", "/logs/_search", body)
            uncached_lat.append((time.perf_counter() - t2) * 1000)
        cached_lat.sort()
        uncached_lat.sort()
        st = http(port, "GET", "/logs/_stats")
        rc = st["indices"]["logs"]["total"].get("request_cache", {})
        lookups = rc.get("hit_count", 0) + rc.get("miss_count", 0)
        res.update({
            "request_cache_hit_ratio":
                rc.get("hit_count", 0) / lookups if lookups else None,
            "request_cache_mem_bytes": rc.get("memory_size_in_bytes"),
            "agg_cached_p50_ms": cached_lat[len(cached_lat) // 2],
            "agg_uncached_p50_ms": uncached_lat[len(uncached_lat) // 2]})
        return res
    finally:
        server.stop()
        node.close()
        shutil.rmtree(workdir, ignore_errors=True)


def run_multiseg_leg(tag: str) -> dict:
    """ISSUE 4: the live-index (never force-merged, ~8 segments/shard)
    dense workload. Two identical indices — one on the segment-stacked
    dense lane, one pinned to the per-segment loop
    (`index.search.stacked.enable: false`) — serve the same dense
    unsorted query mix; the p50 gap is the stacked win, and the
    device-fetch counter delta is the fetches-per-query proof."""
    import shutil
    import tempfile
    from elasticsearch_tpu.node import NodeService
    from elasticsearch_tpu.rest import HttpServer
    from elasticsearch_tpu.common.metrics import transfer_snapshot

    n_docs = int(os.environ.get("BENCH_MS_DOCS", "40000"))
    n_segments = int(os.environ.get("BENCH_MS_SEGMENTS", "8"))
    reps = int(os.environ.get("BENCH_MS_REPS", "60"))
    workdir = tempfile.mkdtemp(prefix=f"bench-ms-{tag}-")
    node = NodeService(os.path.join(workdir, "node"))
    server = HttpServer(node, port=0).start()
    port = server.port
    try:
        rng = np.random.default_rng(23)
        words = [f"w{i:03d}" for i in range(300)]
        mapping = {"mappings": {"_doc": {"properties": {
            "body": {"type": "string"},
            "n": {"type": "long"}}}}}
        for name, extra in (("live", {}),
                            ("live_loop",
                             {"index.search.stacked.enable": False})):
            http(port, "PUT", f"/{name}", json.dumps(
                {**mapping,
                 "settings": {"number_of_shards": 1, **extra}}))
        word_ids = rng.integers(0, len(words), (n_docs, 6))
        # two size tiers (half big, half small segments) — the realistic
        # live-index shape, and no single tier fills the engine's
        # 8-segment merge trigger, so all ~8 segments survive refresh
        big = n_segments // 2
        small_sz = max(n_docs // 100, 8)
        big_sz = (n_docs - small_sz * (n_segments - big)) // big
        sizes = [big_sz] * big + [small_sz] * (n_segments - big)
        for name in ("live", "live_loop"):
            j = 0
            for sz in sizes:
                if _over_budget(margin=45.0):
                    # indexing alone ate the slice: degrade to absent
                    # keys — the headline line still prints (r05 fix)
                    return {}
                lines = []
                for _ in range(sz):
                    lines.append('{"index":{"_id":"%d"}}' % j)
                    lines.append(json.dumps({
                        "body": " ".join(words[w] for w in word_ids[j]),
                        "n": int(j)}))
                    j += 1
                http(port, "POST", f"/{name}/_bulk",
                     "\n".join(lines) + "\n")
                # refresh per batch -> one segment per round, NO force
                # merge: this leg measures the live-index shape
                http(port, "POST", f"/{name}/_refresh")

        def body_of(i: int) -> str:
            # should-scoring keeps the query off the sparse/packed lanes:
            # this is the dense tree the stacked lane serves
            a, b = words[i % len(words)], words[(i * 7 + 3) % len(words)]
            return json.dumps({"size": 10, "query": {"bool": {
                "should": [{"match": {"body": a}}, {"match": {"body": b}}],
                "filter": [{"range": {"n": {"gte": (i * 13) % 1000}}}]}}})

        out: dict = {}
        seg_counts = {
            name: http(port, "GET", f"/{name}/_stats")["indices"][name]
            ["total"]["segments"]["count"]
            for name in ("live", "live_loop")}
        for name, key in (("live", "stacked"), ("live_loop", "per_segment")):
            http(port, "POST", f"/{name}/_search", body_of(0))   # warm
            f0 = transfer_snapshot()["device_fetches_total"]
            lat = []
            served = 0
            for i in range(reps):
                t0 = time.perf_counter()
                http(port, "POST", f"/{name}/_search", body_of(i))
                lat.append((time.perf_counter() - t0) * 1000)
                served += 1
                if _over_budget():
                    break
            f1 = transfer_snapshot()["device_fetches_total"]
            lat.sort()
            out[f"{key}_p50_ms"] = lat[len(lat) // 2]
            out[f"{key}_fetches_per_query"] = (f1 - f0) / max(served, 1)
        out["multiseg_segments"] = seg_counts.get("live", n_segments)
        if out.get("per_segment_p50_ms"):
            out["multiseg_speedup"] = (out["per_segment_p50_ms"]
                                       / out["stacked_p50_ms"])

        # mesh lane (ISSUE 6): the ≥4-shard config — one shard_map program
        # with an on-device cross-shard reduce vs the thread-pool fan-out
        # over per-shard stacked programs. Skipped when the host lacks the
        # devices to seat the shards (the production fallback, measured
        # honestly as absent keys rather than a fake number).
        import jax as _jax
        n_mesh_shards = int(os.environ.get("BENCH_MS_SHARDS", "4"))
        if len(_jax.devices()) >= n_mesh_shards \
                and not _over_budget(margin=60.0):
            for name, extra in (("live_mesh", {}),
                                ("live_fanout",
                                 {"index.search.mesh.enable": False})):
                http(port, "PUT", f"/{name}", json.dumps(
                    {**mapping, "settings": {
                        "number_of_shards": n_mesh_shards, **extra}}))
            for name in ("live_mesh", "live_fanout"):
                j = 0
                for sz in sizes:
                    if _over_budget(margin=45.0):
                        return out       # keep the 1-shard numbers
                    lines = []
                    for _ in range(sz):
                        lines.append('{"index":{"_id":"%d"}}' % j)
                        lines.append(json.dumps({
                            "body": " ".join(words[w] for w in word_ids[j]),
                            "n": int(j)}))
                        j += 1
                    http(port, "POST", f"/{name}/_bulk",
                         "\n".join(lines) + "\n")
                    http(port, "POST", f"/{name}/_refresh")
            for name, key in (("live_mesh", "mesh"),
                              ("live_fanout", "fanout")):
                http(port, "POST", f"/{name}/_search", body_of(0))   # warm
                f0 = transfer_snapshot()["device_fetches_total"]
                lat = []
                served = 0
                for i in range(reps):
                    t0 = time.perf_counter()
                    http(port, "POST", f"/{name}/_search", body_of(i))
                    lat.append((time.perf_counter() - t0) * 1000)
                    served += 1
                    if _over_budget():
                        break
                f1 = transfer_snapshot()["device_fetches_total"]
                lat.sort()
                out[f"{key}_p50_ms"] = lat[len(lat) // 2]
                out[f"{key}_fetches_per_query"] = \
                    (f1 - f0) / max(served, 1)
            out["mesh_shards"] = n_mesh_shards
            if out.get("fanout_p50_ms") and out.get("mesh_p50_ms"):
                out["mesh_speedup"] = (out["fanout_p50_ms"]
                                       / out["mesh_p50_ms"])

            # aggs through the mesh program (ISSUE 11): terms/histogram/
            # stats partials collect INSIDE the collective and ride the
            # same single fetch — count the dispatches that actually
            # took the lane
            agg_body = json.dumps({
                "size": 0, "query": {"match": {"body": words[0]}},
                "aggs": {"h": {"histogram": {"field": "n",
                                             "interval": 64}},
                         "s": {"stats": {"field": "n"}}}})
            agg_reps = min(reps, 30)
            http(port, "POST", "/live_mesh/_search?request_cache=false",
                 agg_body)                                   # warm
            t0 = time.perf_counter()
            agg_served = 0
            for _ in range(agg_reps):
                http(port, "POST",
                     "/live_mesh/_search?request_cache=false", agg_body)
                agg_served += 1
                if _over_budget(margin=30.0):
                    break
            if agg_served:
                out["mesh_agg_qps"] = agg_served / max(
                    time.perf_counter() - t0, 1e-9)
            out["mesh_agg_dispatches"] = node.indices["live_mesh"] \
                .search_stats.get("mesh_agg_dispatches", 0)

            # sorted + 2-level sub-agg tree through the dense lanes
            # (ISSUE 17): the log-analytics shape — newest-first sort
            # and a histogram -> metrics tree — through the mesh
            # program vs the thread-pool fan-out over per-shard sorted
            # stacked programs, on the same corpus
            sorted_body = json.dumps({
                "size": 10, "query": {"match": {"body": words[0]}},
                "sort": [{"n": "desc"}]})
            subagg_body = json.dumps({
                "size": 0, "query": {"match": {"body": words[0]}},
                "aggs": {"h": {
                    "histogram": {"field": "n", "interval": 64},
                    "aggs": {"mx": {"max": {"field": "n"}},
                             "c": {"value_count": {"field": "n"}}}}}})
            s_reps = min(reps, 40)

            def observability_qps(name: str, body: str):
                http(port, "POST",
                     f"/{name}/_search?request_cache=false", body)  # warm
                t0 = time.perf_counter()
                served = 0
                for _ in range(s_reps):
                    http(port, "POST",
                         f"/{name}/_search?request_cache=false", body)
                    served += 1
                    if _over_budget(margin=30.0):
                        break
                return served / max(time.perf_counter() - t0, 1e-9)

            if not _over_budget(margin=45.0):
                out["sorted_mesh_qps"] = observability_qps(
                    "live_mesh", sorted_body)
                out["sorted_fanout_qps"] = observability_qps(
                    "live_fanout", sorted_body)
                out["subagg_mesh_qps"] = observability_qps(
                    "live_mesh", subagg_body)
                out["subagg_fanout_qps"] = observability_qps(
                    "live_fanout", subagg_body)
                out["mesh_sorted_dispatches"] = node.indices["live_mesh"] \
                    .search_stats.get("mesh_sorted_dispatches", 0)
                if out.get("sorted_fanout_qps"):
                    out["sorted_mesh_speedup"] = (out["sorted_mesh_qps"]
                                                  / out["sorted_fanout_qps"])
                if out.get("subagg_fanout_qps"):
                    out["subagg_mesh_speedup"] = (out["subagg_mesh_qps"]
                                                  / out["subagg_fanout_qps"])

            # the self-monitoring overview end to end (ISSUE 17
            # tentpole (c)): sampler snapshots drain into
            # .monitoring-es-* via the bulk lane, and GET
            # /_monitoring/overview answers with the sorted + 2-level
            # sub-agg body through the device lanes
            if not _over_budget(margin=40.0):
                from elasticsearch_tpu.common.monitoring import \
                    MonitoringCollector
                node.monitoring = MonitoringCollector(node, interval_s=0)
                for _ in range(24):
                    node.sampler.sample()
                node.monitoring.collect_once()
                http(port, "GET", "/_monitoring/overview")       # warm
                lat = []
                for _ in range(min(reps, 20)):
                    t0 = time.perf_counter()
                    http(port, "GET", "/_monitoring/overview")
                    lat.append((time.perf_counter() - t0) * 1000)
                    if _over_budget(margin=30.0):
                        break
                lat.sort()
                out["monitoring_overview_p50_ms"] = lat[len(lat) // 2]
        return out
    finally:
        server.stop()
        node.close()
        shutil.rmtree(workdir, ignore_errors=True)


def run_cluster_leg(tag: str) -> dict:
    """Cluster-wide collectives data plane (ISSUE 11): a 2-node cluster
    co-hosting a 4-shard index serves the same match-query workload
    through the node-local mesh reduce (ONE A_QUERY_HOST + one device
    program per host per query) vs the per-shard transport fan-out —
    `cluster_host_reduce_qps` vs `cluster_fanout_qps` on the same corpus
    is the flat-vs-linear reduce the device wins (ROADMAP item 1)."""
    import shutil
    import tempfile
    from elasticsearch_tpu.cluster import TestCluster

    n_docs = int(os.environ.get("BENCH_CLUSTER_DOCS", "100000"))
    n_shards = int(os.environ.get("BENCH_CLUSTER_SHARDS", "8"))
    reps = int(os.environ.get("BENCH_CLUSTER_REPS", "150"))
    n_q = 64
    tmp = tempfile.mkdtemp(prefix=f"bench-cluster-{tag}-")
    out: dict = {}
    cluster = TestCluster(2, tmp)
    try:
        client = cluster.client()
        # 2 nodes x (n_shards/2) co-hosted shards each — the ISSUE 11
        # acceptance config: each host reduces its 4 co-hosted shards in
        # ONE device program per query
        client.create_index("cdocs", {"number_of_shards": n_shards,
                                      "number_of_replicas": 0})
        cluster.ensure_green()
        docs = make_corpus(n_docs, seed=11)
        ops = []
        for i, body in enumerate(docs):
            ops.append(("index", {"_index": "cdocs", "_id": str(i)},
                        {"body": body}))
            if len(ops) >= 4000:
                client.bulk(ops)
                ops = []
            if _over_budget(margin=60.0):
                return {}        # indexing ate the slice: absent keys
        if ops:
            client.bulk(ops)
        client.refresh("cdocs")
        queries = make_queries(n_q, seed=13)

        def set_setting(val):
            master = cluster.master_node()

            def task(cur):
                st = cur.mutate()
                st.data.setdefault("settings", {})[
                    "cluster.search.host_reduce.enable"] = val
                return st
            master.cluster.submit_task("bench-host-reduce", task)

        def body_of(i: int) -> dict:
            # dense bool-should shape: the workload the collective reduce
            # serves (match-only bodies ride the per-shard sparse kernel
            # on the fan-out, a different lane entirely)
            terms = queries[i % n_q].split()
            return {"size": 10, "query": {"bool": {
                "should": [{"match": {"body": terms[0]}},
                           {"match": {"body": terms[1]}}]}}}

        def measure():
            for i in range(n_q):         # warm every pow2 shape bucket
                client.search("cdocs", json.loads(json.dumps(body_of(i))))
                if _over_budget(margin=45.0):
                    return None
            t0 = time.perf_counter()
            served = 0
            for i in range(reps):
                client.search("cdocs", json.loads(json.dumps(body_of(i))))
                served += 1
                if _over_budget(margin=30.0):
                    break
            return served / max(time.perf_counter() - t0, 1e-9)

        set_setting(True)
        d0 = sum(n.host_reduce_stats["dispatches"]
                 for n in cluster.nodes.values())
        out["cluster_host_reduce_qps"] = measure()
        out["cluster_host_reduce_dispatches"] = sum(
            n.host_reduce_stats["dispatches"]
            for n in cluster.nodes.values()) - d0
        set_setting(False)
        out["cluster_fanout_qps"] = measure()
        out["cluster_shards"] = n_shards
        if out.get("cluster_fanout_qps") and out.get(
                "cluster_host_reduce_qps"):
            out["cluster_host_speedup"] = (out["cluster_host_reduce_qps"]
                                           / out["cluster_fanout_qps"])
        return {k: v for k, v in out.items() if v is not None}
    finally:
        cluster.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _pod_leg_measure(tag: str) -> dict:
    """Pod-scale serving (ISSUE 19): 2 simulated pools — each node OWNS
    half the devices and is its own host — vs the single shared-pool
    cluster on the SAME corpus and workload, both driven by TWO
    concurrent coordinators (the regime where per-pool dispatch locks
    beat the process-wide EXEC_LOCK). `pod_vs_single` is the acceptance
    ratio; `exec_lock_waits` must stay 0 on the per-node path;
    `dcn_hops_per_query` counts the pre-reduced cross-host hops."""
    import shutil
    import tempfile
    import threading
    from elasticsearch_tpu.cluster import TestCluster
    from elasticsearch_tpu.parallel.mesh_exec import (exec_lock_stats,
                                                      reset_exec_lock_stats)

    n_docs = int(os.environ.get("BENCH_POD_DOCS", "40000"))
    n_shards = int(os.environ.get("BENCH_POD_SHARDS", "8"))
    reps = int(os.environ.get("BENCH_POD_REPS", "120"))
    n_q = 32
    docs = make_corpus(n_docs, seed=17)
    queries = make_queries(n_q, seed=19)

    def body_of(i: int) -> dict:
        terms = queries[i % n_q].split()
        return {"size": 10, "query": {"bool": {
            "should": [{"match": {"body": terms[0]}},
                       {"match": {"body": terms[1]}}]}}}

    def build(pods: int):
        tmp = tempfile.mkdtemp(prefix=f"bench-pod-{tag}-{pods}-")
        cluster = TestCluster(2, tmp, pods=pods)
        client = cluster.client()
        client.create_index("pdocs", {"number_of_shards": n_shards,
                                      "number_of_replicas": 0})
        cluster.ensure_green()
        ops = []
        for i, body in enumerate(docs):
            ops.append(("index", {"_index": "pdocs", "_id": str(i)},
                        {"body": body}))
            if len(ops) >= 4000:
                client.bulk(ops)
                ops = []
            if _over_budget(margin=60.0):
                break
        if ops:
            client.bulk(ops)
        client.refresh("pdocs")
        return cluster, tmp

    def measure(cluster):
        # one coordinator thread per node, dispatching simultaneously
        nodes = [cluster.nodes[nid] for nid in sorted(cluster.nodes)]
        for i in range(n_q):             # warm every pow2 shape bucket
            nodes[0].search("pdocs", json.loads(json.dumps(body_of(i))))
            if _over_budget(margin=45.0):
                return None, 0
        served = [0] * len(nodes)

        def go(ci: int, node) -> None:
            for i in range(reps):
                node.search("pdocs",
                            json.loads(json.dumps(body_of(i + ci))))
                served[ci] += 1
                if _over_budget(margin=30.0):
                    break
        threads = [threading.Thread(target=go, args=(ci, n), daemon=True)
                   for ci, n in enumerate(nodes)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = max(time.perf_counter() - t0, 1e-9)
        total = sum(served)
        return (total / dt if total else None), total

    out: dict = {}
    cluster, tmp = build(2)
    try:
        reset_exec_lock_stats()
        # count the PRE-REDUCED query hops (one A_QUERY_HOST per remote
        # node), not every cross-host send — fetches/pings ride the dcn
        # transport class too but are not the reduce's hop budget
        d0 = sum(n.host_reduce_stats["dcn_hops"]
                 for n in cluster.nodes.values())
        out["pod_qps"], total = measure(cluster)
        if total:
            hops = sum(n.host_reduce_stats["dcn_hops"]
                       for n in cluster.nodes.values()) - d0
            out["dcn_hops_per_query"] = round(hops / total, 3)
        st = exec_lock_stats()
        out["exec_lock_waits"] = st["shared_waits"] \
            + st["shared_acquisitions"]
        out["pod_reduce_dispatches"] = sum(
            n.host_reduce_stats["pod_dispatches"]
            for n in cluster.nodes.values())
    finally:
        cluster.close()
        shutil.rmtree(tmp, ignore_errors=True)
    if _over_budget(margin=60.0):
        return {k: v for k, v in out.items() if v is not None}
    cluster, tmp = build(0)
    try:
        out["single_pool_qps"], _ = measure(cluster)
    finally:
        cluster.close()
        shutil.rmtree(tmp, ignore_errors=True)
    if out.get("pod_qps") and out.get("single_pool_qps"):
        out["pod_vs_single"] = out["pod_qps"] / out["single_pool_qps"]
    return {k: v for k, v in out.items() if v is not None}


def run_pod_leg(tag: str) -> dict:
    """Two owned pools need >= 4 devices. With fewer the leg reports
    itself skipped: numbers from virtual CPU devices never enter a line
    that names another platform."""
    import jax
    n = len(jax.devices())
    if n >= 4:
        return _pod_leg_measure(tag)
    return {"pod_skipped": f"needs >= 4 devices, found {n} "
                           f"({jax.devices()[0].platform})"}


def run_vector_leg(tag: str) -> dict:
    """BASELINE configs #4/#5: function_score cosine over stored 768-d
    vectors (exact kNN through the product) and BM25->dense hybrid rescore,
    with recall@10 against a numpy brute-force oracle."""
    import shutil
    import tempfile
    from elasticsearch_tpu.node import NodeService
    from elasticsearch_tpu.rest import HttpServer

    workdir = tempfile.mkdtemp(prefix=f"bench-vec-{tag}-")
    # the latency-EWMA shed signal is off for THIS leg only: the quantized
    # tier's first query per mode pays a one-off train+compile measured in
    # tens of seconds, which would spike the EWMA past the 5s ceiling and
    # 429 the whole remaining leg (one sequential client — queue/breaker
    # admission stays on; the QoS contract has its own leg)
    from elasticsearch_tpu.common.settings import Settings
    node = NodeService(os.path.join(workdir, "node"),
                       settings=Settings(
                           {"node.search.qos.shed_latency_ms": 0}))
    server = HttpServer(node, port=0).start()
    port = server.port
    try:
        # clustered corpus: text and vectors CORRELATE (each doc belongs to
        # a topic; its text contains the topic token, its vector sits near
        # the topic centroid). The BM25 gate then retrieves the right
        # cluster and hybrid recall@10 vs the GLOBAL kNN oracle measures
        # the pipeline honestly — with random text/vectors it would only
        # measure the (meaningless) overlap of two unrelated top-k sets.
        # Within each topic, docs cluster around PROTOTYPES (~16 near-
        # duplicates each) so a query's true top-10 sits at a real margin
        # above the rest — the regime ANN retrieval serves. The previous
        # corpus's ranks 2-10 were pure-noise ties (margins far below any
        # quantizer's error), which made recall@10 measure tie-ranking
        # luck instead of neighbor retrieval (ISSUE 12).
        rng = np.random.default_rng(23)
        n_topics = 64
        group = 16                         # docs per prototype
        centers = rng.normal(0, 1, (n_topics, VEC_DIMS)).astype(np.float32)
        centers /= np.linalg.norm(centers, axis=1, keepdims=True)
        sigma = 0.35 / np.sqrt(VEC_DIMS)   # noise NORM ~0.35 vs unit center
        sigma_dup = 0.12 / np.sqrt(VEC_DIMS)   # near-duplicate radius
        n_protos = max(VEC_DOCS // group, 1)
        proto_topic = rng.integers(0, n_topics, n_protos)
        protos = centers[proto_topic] \
            + sigma * rng.normal(0, 1, (n_protos, VEC_DIMS)).astype(
                np.float32)
        proto_of = np.repeat(np.arange(n_protos), group)[:VEC_DOCS]
        if len(proto_of) < VEC_DOCS:
            proto_of = np.resize(proto_of, VEC_DOCS)
        topic_of = proto_topic[proto_of]
        vecs = protos[proto_of] \
            + sigma_dup * rng.normal(0, 1, (VEC_DOCS, VEC_DIMS)).astype(
                np.float32)
        vecs = vecs.astype(np.float32)
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        base_docs = make_corpus(VEC_DOCS, seed=29)
        docs = [f"topic{topic_of[j]:03d} " + base_docs[j]
                for j in range(VEC_DOCS)]
        t0 = time.perf_counter()
        http(port, "PUT", "/vec", json.dumps(
            {"settings": {"number_of_shards": 1,
                          "index.knn.ivf.nlist": VEC_NLIST,
                          "index.knn.ivf.nprobe": VEC_NPROBE,
                          "index.knn.precision": VEC_PRECISION,
                          "index.knn.pq.m": VEC_PQ_M,
                          "index.knn.rescore_window": VEC_RESCORE},
             "mappings": {"_doc": {"properties": {
                 "body": {"type": "string"},
                 "emb": {"type": "dense_vector",
                         "dims": VEC_DIMS}}}}}))
        batch = 500
        for i in range(0, VEC_DOCS, batch):
            lines = []
            for j in range(i, min(i + batch, VEC_DOCS)):
                lines.append('{"index":{"_id":"%d"}}' % j)
                emb = ",".join("%.3f" % x for x in vecs[j])
                lines.append('{"body":%s,"emb":[%s]}'
                             % (json.dumps(docs[j]), emb))
            http(port, "POST", "/vec/_bulk", "\n".join(lines) + "\n")
        http(port, "POST", "/vec/_refresh")
        http(port, "POST", "/vec/_optimize")
        index_secs = time.perf_counter() - t0

        nq = VEC_Q * VEC_BATCHES
        q_proto = rng.integers(0, n_protos, nq)
        q_topic = proto_topic[q_proto]
        qv = protos[q_proto] \
            + sigma_dup * rng.normal(0, 1, (nq, VEC_DIMS)).astype(
                np.float32)
        qv = qv.astype(np.float32)
        qv /= np.linalg.norm(qv, axis=1, keepdims=True)
        # brute-force oracle top-10 by cosine (global — the honest bar)
        oracle = np.argsort(-(qv @ vecs.T), axis=1)[:, :10]
        queries = [f"topic{q_topic[i]:03d}" for i in range(nq)]

        def measure(body_of, oracle_of=None):
            payloads = []
            for bi in range(VEC_BATCHES):
                lines = []
                for qi in range(VEC_Q):
                    gi = bi * VEC_Q + qi
                    lines.append('{"index":"vec"}')
                    lines.append(json.dumps(body_of(gi)))
                payloads.append("\n".join(lines) + "\n")
            first = http(port, "POST", "/_msearch", payloads[0])  # warm
            recall = None
            if oracle_of is not None:
                hits_total = 0
                match_total = 0
                for bi, pl in enumerate(payloads):
                    out = first if bi == 0 \
                        else http(port, "POST", "/_msearch", pl)
                    for qi, resp in enumerate(out["responses"]):
                        gi = bi * VEC_Q + qi
                        want = oracle_of(gi)
                        got = {int(h["_id"])
                               for h in resp["hits"]["hits"][:len(want)]}
                        match_total += len(got & want)
                        hits_total += len(want)
                recall = match_total / max(hits_total, 1)
            t1 = time.perf_counter()
            n = 0
            for _ in range(REPS):
                for pl in payloads:
                    out = http(port, "POST", "/_msearch", pl)
                    n += len(out["responses"])
                if _over_budget():
                    break
            return n / (time.perf_counter() - t1), recall

        # config #4 (ISSUE 10): kNN through the product — the IVF lane
        # (centroid route + gathered cluster scan) is the index default;
        # the exact [Q, N] matmul runs as the control at the same corpus
        knn_qps, knn_recall = measure(
            lambda gi: {"knn": {"field": "emb",
                                "query_vector": [round(float(x), 3)
                                                 for x in qv[gi]],
                                "k": 10},
                        "size": 10, "_source": False},
            oracle_of=lambda gi: set(oracle[gi]))
        ann_dispatches = node.indices["vec"].search_stats.get(
            "ann_dispatches", 0)
        knn_exact_qps = None
        if not _over_budget(margin=30.0):
            knn_exact_qps, _ = measure(
                lambda gi: {"knn": {"field": "emb",
                                    "query_vector": [round(float(x), 3)
                                                     for x in qv[gi]],
                                    "k": 10, "exact": True},
                            "size": 10, "_source": False})

        # quantized tier (ISSUE 12): int8 + PQ scans on the SAME corpus
        # via the per-request override — no reindex, same nprobe, same
        # oracle. The TRAIN phase (the first query builds codes /
        # codebooks, sample-capped at ops/ann.TRAIN_SAMPLE_CAP) and the
        # SCAN phase are budget-checked separately so a slow build is
        # skipped-and-reported instead of eating the remaining legs
        # (the r05 rc=124 lesson).
        quant_res: dict = {}
        qcache = node.caches.ann_indexes
        for mode in ("int8", "pq"):
            if _over_budget(margin=45.0):
                print(f"quantized [{mode}] skipped: "
                      f"{_remaining():.0f}s of budget left",
                      file=sys.stderr)
                break
            b0 = qcache.quant_code_bytes + qcache.quant_book_bytes

            def qbody(gi, _mode=mode):
                return {"knn": {"field": "emb",
                                "query_vector": [round(float(x), 3)
                                                 for x in qv[gi]],
                                "k": 10, "quantization": _mode},
                        "size": 10, "_source": False}
            http(port, "POST", "/vec/_search", json.dumps(qbody(0)))
            quant_res[f"vector_stack_bytes_{mode}"] = \
                qcache.quant_code_bytes + qcache.quant_book_bytes - b0
            if _over_budget(margin=45.0):
                print(f"quantized [{mode}] trained but scan skipped: "
                      f"{_remaining():.0f}s of budget left",
                      file=sys.stderr)
                break
            qps, rec = measure(qbody,
                               oracle_of=lambda gi: set(oracle[gi]))
            quant_res[f"knn_{mode}_qps"] = qps
            quant_res[f"{mode}_recall"] = rec
        # the f32 column bytes the quantized tier replaces in the scan —
        # measured from the live segments, not assumed
        searcher = next(iter(node.indices["vec"].searchers()), None)
        if searcher is not None:
            quant_res["vector_stack_bytes_f32"] = sum(
                int(seg.vectors["emb"].vecs.size) * 4
                for _i, seg in searcher.live_segments
                if "emb" in seg.vectors)

        # config #5: hybrid — BM25 top-1000 then dense rescore to top-10
        hybrid_qps, hybrid_recall = measure(
            lambda gi: {"query": {"match": {"body": queries[gi]}},
                        "size": 10,
                        "rescore": {"window_size": K, "query": {
                            "rescore_query": {"function_score": {
                                "query": {"match_all": {}},
                                "cosine": {"field": "emb",
                                           "query_vectors": [
                                               [round(float(x), 3)
                                                for x in qv[gi]]]},
                                "boost_mode": "replace"}},
                            "query_weight": 0.0,
                            "rescore_query_weight": 1.0,
                            "score_mode": "total"}},
                        "_source": False},
            oracle_of=lambda gi: set(oracle[gi]))
        # first-class hybrid fusion (the body's "rank" section): BM25
        # and the IVF vector list fuse via RRF at the coordinator
        hybrid_rrf_qps = hybrid_rrf_recall = None
        if not _over_budget(margin=30.0):
            hybrid_rrf_qps, hybrid_rrf_recall = measure(
                lambda gi: {"query": {"match": {"body": queries[gi]}},
                            "knn": {"field": "emb",
                                    "query_vector": [round(float(x), 3)
                                                     for x in qv[gi]],
                                    "k": 100},
                            "rank": {"rrf": {"window_size": 100}},
                            "size": 10, "_source": False},
                oracle_of=lambda gi: set(oracle[gi]))
        return {"knn_qps": knn_qps, "knn_recall": knn_recall,
                "knn_exact_qps": knn_exact_qps,
                "knn_nprobe": VEC_NPROBE,
                "ann_dispatches": ann_dispatches,
                "hybrid_qps": hybrid_qps, "hybrid_recall": hybrid_recall,
                "hybrid_rrf_qps": hybrid_rrf_qps,
                "hybrid_rrf_recall": hybrid_rrf_recall,
                "vec_index_secs": index_secs,
                "vec_docs_per_sec": VEC_DOCS / index_secs,
                **quant_res}
    finally:
        server.stop()
        node.close()
        shutil.rmtree(workdir, ignore_errors=True)


def run_scale_leg(tag: str) -> dict:
    """ISSUE 8 scale leg (opt-in: BENCH_SCALE=1): the BASELINE 10M-doc
    tier's shapes at bench scale — config #3 aggs at BENCH_SCALE_AGG_DOCS
    (default 4M) and config #4 vectors at BENCH_SCALE_VEC_DOCS (default
    1M) — under the per-leg wall-clock budget. The streaming blockwise
    dense lane keeps peak device score memory O(Q × block); the leg
    reports peak RSS and the process-peak score-matrix gauge so the bound
    is visible in the one-line JSON (the materializing path either trips
    the request breaker or blows the budget at these sizes)."""
    global AGG_DOCS, VEC_DOCS
    import resource
    from elasticsearch_tpu.common.metrics import peak_score_matrix_bytes
    out: dict = {}
    save_agg, save_vec = AGG_DOCS, VEC_DOCS
    AGG_DOCS = int(os.environ.get("BENCH_SCALE_AGG_DOCS", str(4_000_000)))
    VEC_DOCS = int(os.environ.get("BENCH_SCALE_VEC_DOCS", str(1_000_000)))
    try:
        try:
            r = run_agg_leg(tag + "-scale")
            out.update({"scale_agg_qps": r["agg_qps"],
                        "scale_agg_docs": AGG_DOCS,
                        "scale_agg_index_secs": r["agg_index_secs"]})
        except Exception as e:  # noqa: BLE001 — legs are best-effort
            print(f"BENCH_SCALE agg leg failed: {e}", file=sys.stderr)
        if not _over_budget(margin=90.0):
            _arm_leg_alarm(reserve=60.0)
            try:
                r = run_vector_leg(tag + "-scale")
                out.update({"scale_knn_qps": r["knn_qps"],
                            "scale_knn_recall": r["knn_recall"],
                            "scale_knn_exact_qps": r.get("knn_exact_qps"),
                            "scale_ann_dispatches": r.get("ann_dispatches"),
                            "scale_vec_docs": VEC_DOCS,
                            "scale_vec_index_secs": r["vec_index_secs"],
                            # quantized tier at the scale corpus
                            # (ISSUE 12): the 10M-config crossover proof
                            "scale_knn_int8_qps": r.get("knn_int8_qps"),
                            "scale_knn_pq_qps": r.get("knn_pq_qps"),
                            "scale_pq_recall": r.get("pq_recall"),
                            "scale_vector_stack_bytes_f32":
                                r.get("vector_stack_bytes_f32"),
                            "scale_vector_stack_bytes_pq":
                                r.get("vector_stack_bytes_pq")})
            except Exception as e:  # noqa: BLE001
                print(f"BENCH_SCALE vec leg failed: {e}", file=sys.stderr)
        out["scale_peak_rss_bytes"] = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024
        out["scale_peak_score_matrix_bytes"] = peak_score_matrix_bytes()
    finally:
        AGG_DOCS, VEC_DOCS = save_agg, save_vec
    return out


def run_engine_leg(tag: str) -> dict:
    """Full product pipeline: index via _bulk, serve via _msearch/_search."""
    import shutil
    import tempfile
    from elasticsearch_tpu.node import NodeService
    from elasticsearch_tpu.rest import HttpServer

    workdir = tempfile.mkdtemp(prefix=f"bench-{tag}-")
    node = NodeService(os.path.join(workdir, "node"))
    server = HttpServer(node, port=0).start()
    port = server.port
    try:
        docs = make_corpus(N_DOCS)
        t0 = time.perf_counter()          # after corpus gen: index cost only
        http(port, "PUT", "/bench", json.dumps(
            {"settings": {"number_of_shards": 1},
             "mappings": {"_doc": {"properties": {
                 "body": {"type": "string"},
                 "price": {"type": "long"}}}}}))
        # 4000 docs/bulk (~600KB) sits inside the reference's recommended
        # 5-15MB window and halves the per-request HTTP/ack overhead the
        # 2000-doc batches paid
        batch = 4000
        for i in range(0, len(docs), batch):
            lines = []
            for j, d in enumerate(docs[i:i + batch]):
                # corpus terms are plain ASCII — interpolation is exact
                # JSON and keeps client-side encoding out of index_secs
                # (the agg leg builds its lines the same way)
                lines.append('{"index":{"_id":"%d"}}' % (i + j))
                lines.append('{"body":"%s","price":%d}' % (d, (i + j) % 1000))
            http(port, "POST", "/bench/_bulk", "\n".join(lines) + "\n")
        http(port, "POST", "/bench/_refresh")
        http(port, "POST", "/bench/_optimize")
        index_secs = time.perf_counter() - t0

        queries = make_queries(Q_BATCH * N_BATCHES)

        def msearch_payloads(body_of):
            out = []
            for bi in range(N_BATCHES):
                lines = []
                for q in queries[bi * Q_BATCH:(bi + 1) * Q_BATCH]:
                    lines.append(json.dumps({"index": "bench"}))
                    lines.append(json.dumps(body_of(q)))
                out.append("\n".join(lines) + "\n")
            return out

        def measure_msearch(payloads):
            http(port, "POST", "/_msearch", payloads[0])   # warm compile
            t1 = time.perf_counter()
            n = 0
            for _ in range(REPS):
                for pl in payloads:
                    out = http(port, "POST", "/_msearch", pl)
                    n += len(out["responses"])
                if _over_budget():
                    break
            return n / (time.perf_counter() - t1)

        # config #1: match query, top-K
        qps = measure_msearch(msearch_payloads(
            lambda q: {"query": {"match": {"body": q}}, "size": K,
                       "_source": False}))
        # config #2: bool{match + range filter}, top-K — the packed
        # kernel's filter slots serve this
        lo = 100
        qps_filter = measure_msearch(msearch_payloads(
            lambda q: {"query": {"bool": {
                "must": [{"match": {"body": q}}],
                "filter": [{"range": {"price": {"gte": lo,
                                                "lte": lo + 500}}}]}},
                "size": K, "_source": False}))

        # solo _search latency, size=10 (BASELINE config #1 shape)
        lat = []
        solo = json.dumps({"query": {"match": {"body": queries[0]}},
                           "size": 10, "_source": False})
        http(port, "POST", "/bench/_search", solo)
        for q in queries[:LATENCY_N]:
            body = json.dumps({"query": {"match": {"body": q}},
                               "size": 10, "_source": False})
            t1 = time.perf_counter()
            http(port, "POST", "/bench/_search", body)
            lat.append((time.perf_counter() - t1) * 1000)
        lat.sort()

        def serving_counters():
            # batcher + admission counters ride the payload so the bench
            # trajectory captures serving EFFICIENCY (how much coalescing
            # and rejection happened), not just latency
            bst = node._batcher.stats()
            return {"batches": bst["batches"],
                    "batched_requests": bst["batched_requests"],
                    "search_rejected":
                        node.thread_pool.stats()["search"]["rejected"]}

        # concurrent solo clients (NOT pre-batched msearch): the dynamic
        # batcher coalesces these into shared device programs. Skipped
        # cleanly when the wall-clock budget is spent.
        if _over_budget(margin=30.0):
            return {"qps": qps, "qps_filter": qps_filter,
                    "p50_ms": lat[len(lat) // 2],
                    "p99_ms": lat[min(len(lat) - 1, int(len(lat) * 0.99))],
                    "conc_qps": None, "conc_p50_ms": None,
                    "conc_p99_ms": None, "shed_429s": None,
                    "hedged_wins": None,
                    "conc_clients": 0, "index_secs": index_secs,
                    "docs_per_sec": N_DOCS / index_secs,
                    **serving_counters()}
        import threading
        import urllib.error
        # BENCH_CONC_CLIENTS (ISSUE 9) is the canonical fan-in override;
        # BENCH_CONC stays honored for older harness configs
        CONC = int(os.environ.get("BENCH_CONC_CLIENTS",
                                  os.environ.get("BENCH_CONC", "32")))
        PER = 8
        conc_lat: list[float] = []
        shed_429s = [0]
        conc_lock = threading.Lock()

        def client(ci: int):
            for qi in range(PER):
                q = queries[(ci * PER + qi) % len(queries)]
                body = json.dumps({"query": {"match": {"body": q}},
                                   "size": 10, "_source": False})
                t2 = time.perf_counter()
                try:
                    http(port, "POST", "/bench/_search", body)
                except urllib.error.HTTPError as e:
                    # load shedding IS the contract under overload: a 429
                    # is counted, anything else still fails the leg
                    if e.code != 429:
                        raise
                    with conc_lock:
                        shed_429s[0] += 1
                    continue
                dt = (time.perf_counter() - t2) * 1000
                with conc_lock:
                    conc_lat.append(dt)

        # unmeasured warm round: the batcher compiles one program per
        # coalesced Q-shape bucket; steady-state is what we measure
        warm_threads = [threading.Thread(target=client, args=(ci,))
                        for ci in range(CONC)]
        for t in warm_threads:
            t.start()
        for t in warm_threads:
            t.join()
        conc_lat.clear()
        shed_429s[0] = 0
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(CONC)]
        t1 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        conc_dt = time.perf_counter() - t1
        conc_lat.sort()
        from elasticsearch_tpu.serving.qos import hedge_snapshot
        return {"qps": qps,
                "qps_filter": qps_filter,
                "p50_ms": lat[len(lat) // 2],
                "p99_ms": lat[min(len(lat) - 1, int(len(lat) * 0.99))],
                "conc_qps": len(conc_lat) / conc_dt,
                "conc_p50_ms": conc_lat[len(conc_lat) // 2]
                if conc_lat else None,
                "conc_p99_ms": conc_lat[min(len(conc_lat) - 1,
                                            int(len(conc_lat) * 0.99))]
                if conc_lat else None,
                "shed_429s": shed_429s[0],
                "hedged_wins": hedge_snapshot()["win_backup"],
                "conc_clients": CONC,
                "index_secs": index_secs,
                "docs_per_sec": N_DOCS / index_secs,
                **serving_counters()}
    finally:
        server.stop()
        node.close()
        shutil.rmtree(workdir, ignore_errors=True)


def run_chaos_leg(tag: str) -> dict:
    """Chaos harness leg (ISSUE 14): one seeded round of the cross-lane
    parity oracle + leak detectors in the cheap single-node mode
    (cluster_nodes=0 — the multi-node disruption rounds live in tier-1's
    chaos smoke; the bench leg proves the oracle runs clean on THIS
    build and reports the counts). BENCH_CHAOS_SEED / BENCH_CHAOS_ROUNDS
    override; a mismatch degrades to a non-zero count in the line, never
    a failed run."""
    import shutil
    import tempfile
    from elasticsearch_tpu.testing.chaos import ChaosOptions, ChaosRunner
    seed = int(os.environ.get("BENCH_CHAOS_SEED", "1234"))
    rounds = int(os.environ.get("BENCH_CHAOS_ROUNDS", "1"))
    workdir = tempfile.mkdtemp(prefix=f"bench-chaos-{tag}-")
    try:
        report = ChaosRunner(workdir, ChaosOptions(
            seed=seed, rounds=rounds, cluster_nodes=0,
            raise_on_failure=False)).run()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return {"chaos_seed": report.seed,
            "chaos_rounds": report.rounds,
            "chaos_parity_checks": report.parity_checks,
            "chaos_mismatches": len(report.mismatches),
            "chaos_invariant_violations":
                len(report.invariant_violations)}


def run_percolate_leg(tag: str) -> dict:
    """Reverse search (ISSUE 18): register BENCH_PERCOLATE_QUERIES dense-
    eligible queries (match / term / range / bool — the four channel
    families of the doc×query grid), then percolate doc batches through
    the ONE-program dense executor vs the per-doc loop rung measured on a
    small doc subsample and extrapolated. Also times a compilable
    script_score riding the fused device lane vs the SAME expression
    forced onto the host evaluator (an `if true else` wrapper declines the
    compiler but evaluates identically) — the compiled-vs-decline ratio."""
    import shutil
    import tempfile
    from elasticsearch_tpu.common.metrics import transfer_snapshot
    from elasticsearch_tpu.node import NodeService
    from elasticsearch_tpu.search import percolator as perc_mod
    from elasticsearch_tpu.search.percolate_exec import percolate_batch

    nq = int(os.environ.get("BENCH_PERCOLATE_QUERIES", "50000"))
    batch_docs = int(os.environ.get("BENCH_PERCOLATE_BATCH", "64"))
    reps = int(os.environ.get("BENCH_PERCOLATE_REPS", "6"))
    loop_docs = int(os.environ.get("BENCH_PERCOLATE_LOOP_DOCS", "2"))
    s_docs = int(os.environ.get("BENCH_SCRIPT_DOCS", "5000"))
    s_reps = int(os.environ.get("BENCH_SCRIPT_REPS", "30"))
    workdir = tempfile.mkdtemp(prefix=f"bench-perc-{tag}-")
    node = NodeService(os.path.join(workdir, "node"))
    out: dict = {}
    try:
        node.create_index("perc", settings={"number_of_shards": 1},
                          mappings={"_doc": {"properties": {
                              "body": {"type": "string"},
                              "tag": {"type": "string",
                                      "index": "not_analyzed"},
                              "n": {"type": "long"}}}})
        tags = [f"t{i}" for i in range(16)]

        def qbody(i: int) -> dict:
            w = f"term{64 + (i * 131) % 8000:05d}"
            kind = i % 4
            if kind == 0:
                return {"match": {"body": w}}
            if kind == 1:
                return {"term": {"tag": tags[i % len(tags)]}}
            if kind == 2:
                lo = (i * 37) % 5000
                return {"range": {"n": {"gte": lo, "lt": lo + 200}}}
            return {"bool": {"must": [{"match": {"body": w}}],
                             "must_not": [{"term": {
                                 "tag": tags[(i + 7) % len(tags)]}}]}}

        registered = 0
        for i in range(0, nq, 4000):
            ops = [("index", {"_index": "perc", "_id": f"pq-{j}",
                              "_type": ".percolator"},
                    {"query": qbody(j)})
                   for j in range(i, min(i + 4000, nq))]
            node.bulk(ops)
            registered += len(ops)
            if _over_budget(margin=120.0):
                break              # partial registry: ratio still holds
        node.refresh("perc")
        svc = node.indices["perc"]
        rng = np.random.default_rng(29)
        docs = [{"body": " ".join(
                     f"term{t:05d}" for t in rng.integers(64, 8192, size=6)),
                 "tag": tags[int(rng.integers(len(tags)))],
                 "n": int(rng.integers(0, 5200))}
                for _ in range(batch_docs)]
        pairs = [(d, "_doc") for d in docs]
        percolate_batch(svc, "perc", pairs, caches=node.caches)   # warm
        f0 = transfer_snapshot()["device_fetches_total"]
        t0 = time.perf_counter()
        dense_n = batches = 0
        for _ in range(reps):
            percolate_batch(svc, "perc", pairs, caches=node.caches)
            dense_n += len(pairs)
            batches += 1
            if _over_budget(margin=90.0):
                break
        dense_s = time.perf_counter() - t0
        fetches = transfer_snapshot()["device_fetches_total"] - f0
        out.update({
            "percolate_queries": registered,
            "percolate_qps": dense_n / max(dense_s, 1e-9),
            "percolate_matrix_qps":
                dense_n * registered / max(dense_s, 1e-9),
            "percolate_fetches_per_batch": fetches / max(batches, 1)})
        # loop rung on a doc SUBSAMPLE, extrapolated — per-doc it re-plans
        # and re-dispatches the whole registry, which is the point
        registry = perc_mod.parsed_registry(svc)
        t0 = time.perf_counter()
        loop_n = 0
        for doc in docs[:loop_docs]:
            _, seg, root = perc_mod.build_doc_segment(svc, doc)
            perc_mod.loop_match(registry, seg, root)
            loop_n += 1
            if _over_budget(margin=60.0):
                break
        loop_s = time.perf_counter() - t0
        if loop_n:
            loop_qps = loop_n / max(loop_s, 1e-9)
            out["percolate_loop_qps"] = loop_qps
            out["percolate_vs_loop"] = \
                out["percolate_qps"] / max(loop_qps, 1e-9)

        # -- script_score: compiled device lane vs forced host decline
        node.create_index("sdocs", settings={"number_of_shards": 1},
                          mappings={"_doc": {"properties": {
                              "body": {"type": "string"},
                              "n": {"type": "long"},
                              "price": {"type": "double"}}}})
        bodies = make_corpus(s_docs, seed=31)
        for i in range(0, s_docs, 4000):
            node.bulk([("index", {"_index": "sdocs", "_id": str(j)},
                        {"body": bodies[j], "n": j,
                         "price": float((j * 7) % 1000) / 10.0})
                       for j in range(i, min(i + 4000, s_docs))])
        node.refresh("sdocs")
        expr = ("doc['n'].value * 2.0"
                " + Math.min(doc['price'].value, params.c)")

        def sbody(src: str, i: int) -> dict:
            return {"size": 10, "query": {"function_score": {
                "query": {"match": {"body": f"term{64 + i % 512:05d}"}},
                "script_score": {"script": src, "params": {"c": 50.0}},
                "boost_mode": "replace"}}}

        def measure_script(src: str, max_reps: int) -> float | None:
            node.search("sdocs", sbody(src, 0))        # warm compile
            t0 = time.perf_counter()
            n = 0
            for i in range(max_reps):
                node.search("sdocs", sbody(src, i + 1))
                n += 1
                if _over_budget(margin=45.0):
                    break
            return n / max(time.perf_counter() - t0, 1e-9) if n else None

        comp = measure_script(expr, s_reps)
        # the wrapper declines compilation (IfExp is outside the grammar)
        # but the host evaluator computes the identical expression
        host = measure_script(f"({expr}) if true else 0.0",
                              max(s_reps // 6, 2))
        if comp:
            out["script_score_qps"] = comp
        if comp and host:
            out["script_host_qps"] = host
            out["script_vs_decline"] = comp / host
        return out
    finally:
        node.close()
        shutil.rmtree(workdir, ignore_errors=True)


def run_watcher_leg(tag: str) -> dict:
    """Watcher alerting tier (ISSUE 20): register BENCH_WATCHER_WATCHES
    watches (mixed percolate/agg conditions), drive the monitoring
    collector so document watches ride its dense percolate batch, tick
    the scheduler over the agg watches, and page a composite agg through
    `after`-key cursors — evals/sec, per-fire latency, ride count, and
    composite pages/sec."""
    import shutil
    import tempfile
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import NodeService

    n_watches = int(os.environ.get("BENCH_WATCHER_WATCHES", "1000"))
    n_agg = max(1, int(os.environ.get("BENCH_WATCHER_AGG", "50")))
    rounds = int(os.environ.get("BENCH_WATCHER_ROUNDS", "3"))
    fire_reps = int(os.environ.get("BENCH_WATCHER_FIRE_REPS", "20"))
    comp_docs = int(os.environ.get("BENCH_COMPOSITE_DOCS", "20000"))
    comp_secs = float(os.environ.get("BENCH_COMPOSITE_SECS", "5"))
    workdir = tempfile.mkdtemp(prefix=f"bench-watch-{tag}-")
    node = NodeService(os.path.join(workdir, "node"), Settings({
        "node.monitoring.enable": True,
        "node.monitoring.interval": 0,      # manual collector ticks
        "node.sampler.interval": 0,
        "watcher.interval": 0,              # manual scheduler ticks
        "watcher.throttle_period": "0s"}))
    out: dict = {}
    try:
        ws = node.watcher_service
        agg_body = {"size": 0, "aggs": {"over_time": {
            "date_histogram": {"field": "@timestamp", "interval": "1s"},
            "aggs": {"rate": {"derivative": {"buckets_path": "_count"}}},
        }}}
        stride = max(1, n_watches // n_agg)
        for i in range(n_watches):
            if i % stride != 0 or i // stride >= n_agg:
                # document watch: one more column of the dense matrix
                ws.put_watch(f"doc-{i}", {"input": {"percolate": {
                    "query": {"term": {"kind": "node_stats"}}
                    if i % 2 else
                    {"range": {"heap_used_bytes": {"gte": i % 97}}}}}})
            else:
                ws.put_watch(f"agg-{i}", {
                    "trigger": {"schedule": {"interval": "1s"}},
                    "input": {"search": {"request": {
                        "index": ".monitoring-es-*", "body": agg_body}}},
                    "condition": {"compare": {
                        "ctx.payload.hits.total": {"gte": 0}}}})
            if _over_budget(margin=120.0):
                break              # partial registry: rates still hold
        out["watcher_watches"] = len(ws.watches)

        # collector ticks: every bulk percolates ALL document watches in
        # one dense matrix program (the dogfood ride)
        e0 = ws.stats["evaluations_total"]
        t0 = time.perf_counter()
        for _ in range(3):
            for _ in range(4):
                node.sampler.sample()
                time.sleep(0.002)
            node.monitoring.collect_once()
            if _over_budget(margin=90.0):
                break
        # scheduler rounds over the agg watches (now_ms advances past
        # every 1s trigger so each round evaluates the full agg set)
        base_ms = int(time.time() * 1000)
        for r in range(rounds):
            ws.run_due(now_ms=base_ms + (r + 1) * 2000)
            if _over_budget(margin=90.0):
                break
        eval_s = time.perf_counter() - t0
        evals = ws.stats["evaluations_total"] - e0
        out["watcher_evals_per_sec"] = evals / max(eval_s, 1e-9)
        out["watcher_percolate_rides"] = ws.stats["percolate_rides_total"]
        out["watcher_fires"] = ws.stats["fires_total"]

        # per-fire latency: one always-firing watch, throttle 0 — each
        # execute runs search + condition + alert bulk + registry persist
        ws.put_watch("fire-probe", {
            "input": {"search": {"request": {
                "index": ".monitoring-es-*",
                "body": {"size": 0, "query": {"match_all": {}}}}}},
            "condition": {"always": {}}, "throttle_period": "0s"})
        lat = []
        for _ in range(fire_reps):
            t0 = time.perf_counter()
            res = ws.execute_watch("fire-probe")
            lat.append((time.perf_counter() - t0) * 1000.0)
            if not res.get("fired"):
                break
            if _over_budget(margin=60.0):
                break
        if lat:
            lat.sort()
            out["watcher_fire_p50_ms"] = lat[len(lat) // 2]

        # composite after-key pagination: full disjoint cover of a
        # keyword×histogram bucket space, pages/sec
        node.create_index("comp", settings={"number_of_shards": 1},
                          mappings={"_doc": {"properties": {
                              "tag": {"type": "string",
                                      "index": "not_analyzed"},
                              "n": {"type": "long"}}}})
        for i in range(0, comp_docs, 4000):
            node.bulk([("index", {"_index": "comp", "_id": str(j)},
                        {"tag": f"t{j % 40:02d}", "n": j % 500})
                       for j in range(i, min(i + 4000, comp_docs))])
        node.refresh("comp")

        def comp_body(after):
            b = {"size": 0, "aggs": {"pages": {"composite": {
                "size": 50,
                "sources": [{"tag": {"terms": {"field": "tag"}}},
                            {"bin": {"histogram": {"field": "n",
                                                   "interval": 100}}}]},
            }}}
            if after is not None:
                b["aggs"]["pages"]["composite"]["after"] = after
            return b

        pages = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < comp_secs:
            after = None
            while True:
                resp = node.search("comp", comp_body(after))
                comp = resp["aggregations"]["pages"]
                pages += 1
                after = comp.get("after_key")
                if after is None or not comp["buckets"]:
                    break
            if _over_budget(margin=60.0):
                break
        comp_s = time.perf_counter() - t0
        out["composite_page_qps"] = pages / max(comp_s, 1e-9)
        out["composite_pages"] = pages
        return out
    finally:
        node.close()
        shutil.rmtree(workdir, ignore_errors=True)


def run_rebalance_leg(tag: str) -> dict:
    """Multi-tenant elasticity (ISSUE 15): drain one node of a live
    3-node cluster via an `exclude._id` filter update WHILE 32 client
    threads keep querying it — the relocations stream through the
    `indices.recovery.max_bytes_per_sec` token bucket and hedged reads
    cover the moving copies. Reports the under-move p50/p99 (the SLO
    pair: p99 must hold <= 5x p50), the drain wall time, the measured
    recovery byte rate vs the configured throttle, and the decider veto
    count the drain produced."""
    import shutil
    import tempfile
    import threading
    from elasticsearch_tpu.cluster import TestCluster
    from elasticsearch_tpu.cluster.recovery import parse_bytes
    from elasticsearch_tpu.cluster.recovery import snapshot as rec_snapshot
    from elasticsearch_tpu.cluster.state import (INITIALIZING, RELOCATING,
                                                 UNASSIGNED)

    n_docs = int(os.environ.get("BENCH_REBAL_DOCS", "12000"))
    n_shards = int(os.environ.get("BENCH_REBAL_SHARDS", "4"))
    rate = os.environ.get("BENCH_REBAL_RATE", "4mb")
    conc = int(os.environ.get("BENCH_CONC_CLIENTS",
                              os.environ.get("BENCH_CONC", "32")))
    tmp = tempfile.mkdtemp(prefix=f"bench-rebal-{tag}-")
    cluster = TestCluster(3, tmp)
    try:
        client = cluster.client()
        client.create_index("rdocs", {"number_of_shards": n_shards,
                                      "number_of_replicas": 1})
        cluster.ensure_green()
        ops = []
        for i, body in enumerate(make_corpus(n_docs, seed=17)):
            ops.append(("index", {"_index": "rdocs", "_id": str(i)},
                        {"body": body}))
            if len(ops) >= 4000:
                client.bulk(ops)
                ops = []
            if _over_budget(margin=60.0):
                return {}        # indexing ate the slice: absent keys
        if ops:
            client.bulk(ops)
        client.refresh("rdocs")
        client.update_cluster_settings(
            {"indices.recovery.max_bytes_per_sec": rate})
        queries = make_queries(32, seed=19)

        def body_of(i: int) -> dict:
            return {"size": 10, "query": {
                "match": {"body": queries[i % len(queries)]}}}

        for i in range(16):        # warm the shape buckets
            client.search("rdocs", body_of(i))
        lats: list[float] = []
        errors = [0]
        lock = threading.Lock()
        stop = threading.Event()

        def qos_client(ci: int) -> None:
            qi = 0
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    client.search("rdocs", body_of(ci * 7 + qi))
                except Exception:  # noqa: BLE001 — shed/transient under move
                    with lock:
                        errors[0] += 1
                    continue
                dt = (time.perf_counter() - t0) * 1000
                with lock:
                    lats.append(dt)
                qi += 1

        threads = [threading.Thread(target=qos_client, args=(ci,))
                   for ci in range(conc)]
        for t in threads:
            t.start()
        time.sleep(0.5)            # steady-state before the move starts
        victim = sorted(cluster.nodes)[-1]
        r0 = dict(rec_snapshot())
        v0 = sum(n.deciders.veto_total() for n in cluster.nodes.values())
        with lock:
            lats.clear()           # measure latency UNDER the move only
        t_move = time.perf_counter()
        client.update_cluster_settings(
            {"cluster.routing.allocation.exclude._id": victim})
        deadline = time.monotonic() + max(min(_remaining() - 60.0, 120.0),
                                          5.0)
        moved = False
        while time.monotonic() < deadline:
            st = cluster.master_node().cluster.current()
            copies = [c for cs in st.routing.get("rdocs", []) for c in cs]
            busy = any(c["state"] in (RELOCATING, INITIALIZING)
                       or c.get("relocation") for c in copies)
            holds = any(c["node"] == victim and c["state"] != UNASSIGNED
                        for c in copies)
            if not busy and not holds:
                moved = True
                break
            time.sleep(0.05)
        move_s = time.perf_counter() - t_move
        stop.set()
        for t in threads:
            t.join()
        r1 = dict(rec_snapshot())
        lats.sort()
        rec_bytes = r1["bytes_total"] - r0["bytes_total"]
        out = {
            "rebalance_moved": moved,
            "rebalance_move_s": move_s,
            "rebalance_p50_ms": lats[len(lats) // 2] if lats else None,
            "rebalance_p99_ms": lats[min(len(lats) - 1,
                                         int(len(lats) * 0.99))]
            if lats else None,
            "rebalance_queries": len(lats),
            "rebalance_errors": errors[0],
            "rebalance_recovered_bytes": rec_bytes,
            "recovery_throttle_bytes_per_sec":
                rec_bytes / max(move_s, 1e-9),
            "recovery_throttle_limit_bytes_per_sec": parse_bytes(rate),
            "recovery_throttle_waits":
                r1["throttle_waits_total"] - r0["throttle_waits_total"],
            "decider_vetoes":
                sum(n.deciders.veto_total()
                    for n in cluster.nodes.values()) - v0,
            "hedged_moving": sum(n.hedge_stats.get("moving", 0)
                                 for n in cluster.nodes.values())}
        return {k: v for k, v in out.items() if v is not None}
    finally:
        cluster.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _run_all_legs(tag: str) -> dict:
    _arm_leg_alarm(reserve=120.0)
    res = run_engine_leg(tag)
    _flight_snapshot("engine")
    if tag == "main":
        # results land in the emergency line the moment they exist, so a
        # kill during a LATER leg still reports the measured headline
        _FINAL_LINE.update({k: res[k] for k in
                            ("qps", "qps_filter", "p50_ms", "p99_ms",
                             "conc_qps", "conc_p50_ms", "conc_p99_ms",
                             "shed_429s", "hedged_wins",
                             "batches", "batched_requests",
                             "search_rejected") if k in res})
        _FINAL_LINE["value"] = res.get("qps")
    # optional legs run only while the budget allows AND degrade to
    # absent keys on failure — the headline line always prints. The
    # vector leg runs FIRST among them (ISSUE 12): the quantized-tier
    # crossover is the acceptance measurement, so a squeezed budget
    # degrades analytics keys, not the vector ones.
    legs = [("BENCH_VEC", "1", run_vector_leg),
            ("BENCH_AGG", "1", run_agg_leg),
            ("BENCH_MULTISEG", "1", run_multiseg_leg),
            # cluster host-reduce leg (ISSUE 11): skipped on the CPU
            # baseline subprocess — both lanes run the same device code,
            # so the ratio is measured once, in the main process
            ("BENCH_CLUSTER", "1" if tag == "main" else "0",
             run_cluster_leg),
            # pod-scale serving (ISSUE 19): a concurrency ratio between
            # two clusters in the same process — measured once, in the
            # main process
            ("BENCH_POD", "1" if tag == "main" else "0", run_pod_leg),
            # chaos parity oracle (ISSUE 14): correctness counts, not a
            # perf ratio — measured once, in the main process
            ("BENCH_CHAOS", "1" if tag == "main" else "0",
             run_chaos_leg),
            # rebalance-under-load SLO (ISSUE 15): wall-clock + SLO
            # ratio, not a device-perf ratio — measured once, in the
            # main process
            # reverse-search dense-vs-loop + compiled-vs-host script
            # ratios (ISSUE 18): both lanes run in the same process, so
            # the ratio is measured once, in the main process
            ("BENCH_PERCOLATE", "1" if tag == "main" else "0",
             run_percolate_leg),
            # watcher alerting tier (ISSUE 20): scheduler/ride/pagination
            # rates over a single self-monitoring node — measured once,
            # in the main process
            ("BENCH_WATCHER", "1" if tag == "main" else "0",
             run_watcher_leg),
            ("BENCH_REBAL", "1" if tag == "main" else "0",
             run_rebalance_leg),
            # 4M-doc aggs + 1M-doc vectors: opt-in —
            # the scale tier only fits a long budget
            ("BENCH_SCALE", "0", run_scale_leg)]
    for li, (flag, default, leg) in enumerate(legs):
        if os.environ.get(flag, default) == "0":
            continue
        if _over_budget(margin=90.0):
            print(f"{flag} leg skipped: {_remaining():.0f}s of "
                  f"BENCH_TIME_BUDGET left", file=sys.stderr)
            continue
        # tightened per-leg slices (BENCH_r05 rc=124 hardening): each leg
        # may consume only what's left MINUS a hold-back for every leg
        # still queued (45s each) plus the final-print headroom — a slow
        # leg gets _BudgetExceeded raised into it and is skipped-and-
        # reported, it can no longer starve the legs behind it
        later = sum(1 for f, d, _fn in legs[li + 1:]
                    if os.environ.get(f, d) != "0")
        _arm_leg_alarm(reserve=45.0 * later + 45.0)
        try:
            res.update(leg(tag))
        except _BudgetExceeded as e:
            print(f"{flag} leg over its slice, skipped: {e}",
                  file=sys.stderr)
            _FAILED_LEGS.append(f"{flag}: over its slice")
        except Exception as e:  # noqa: BLE001 — the other legs still run;
            # the line names this one and the exit code is non-zero
            print(f"{flag} leg failed: {e}", file=sys.stderr)
            _FAILED_LEGS.append(f"{flag}: {type(e).__name__}: {e}")
        finally:
            _flight_snapshot(flag.removeprefix("BENCH_").lower())
    _arm_hard_alarm()
    return res


def main_engine():
    import subprocess
    _FINAL_LINE["metric"] = \
        f"http_msearch_bm25_top{K}_qps_{N_DOCS // 1000}k_docs"
    res: dict = {}
    err = None
    try:
        res = _run_all_legs("main")
    except Exception as e:  # noqa: BLE001 — a failed leg degrades the
        err = f"{type(e).__name__}: {e}"    # number, never erases the line
    ratios: dict = {}
    plat = "unknown"
    try:
        import jax
        plat = jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        pass
    ratio_keys = ["qps", "qps_filter", "conc_qps", "agg_qps", "knn_qps",
                  "hybrid_qps", "scale_agg_qps", "scale_knn_qps"]
    # on a CPU run there is nothing to compare with: vs_baseline is null
    if plat != "cpu" and os.environ.get("BENCH_CPU", "1") != "0" \
            and not _over_budget(60.0) and res:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_LEG"] = "cpu"
        # the CPU leg gets what's LEFT of the budget (minus headroom to
        # print): a timeout here degrades vs_baseline to null, it no
        # longer erases the headline line (BENCH_r05 rc=124)
        env["BENCH_TIME_BUDGET"] = str(max(30.0, _remaining() - 30.0))
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True,
                timeout=max(30.0, _remaining() - 15.0))
            for ln in out.stdout.splitlines():
                if ln.startswith("{"):
                    cpu = json.loads(ln)
                    for k in ratio_keys:
                        if res.get(k) and cpu.get(k):
                            ratios[k] = res[k] / cpu[k]
                    break
            if not ratios:
                print(f"cpu leg produced no result (rc={out.returncode}): "
                      f"{out.stderr[-500:]}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — baseline leg is best-effort
            print(f"cpu leg failed: {e}", file=sys.stderr)
    rnd = lambda x: round(x, 3) if x is not None else None  # noqa: E731
    r2 = lambda x: round(x, 2) \
        if isinstance(x, (int, float)) else None  # noqa: E731
    line = {
        "metric": f"http_msearch_bm25_top{K}_qps_{N_DOCS // 1000}k_docs",
        "value": r2(res.get("qps")), "unit": "qps",
        "vs_baseline": rnd(ratios.get("qps")),
        "qps_filter": r2(res.get("qps_filter")),
        "vs_baseline_filter": rnd(ratios.get("qps_filter")),
        "conc_qps": r2(res.get("conc_qps")),
        "vs_baseline_concurrent": rnd(ratios.get("conc_qps")),
        "conc_p50_ms": r2(res.get("conc_p50_ms")),
        # tail latency as a headline (ISSUE 9): the p99 under concurrent
        # fan-in plus the QoS counters that explain it
        "conc_p99_ms": r2(res.get("conc_p99_ms")),
        "shed_429s": res.get("shed_429s"),
        "hedged_wins": res.get("hedged_wins"),
        "conc_clients": res.get("conc_clients", 0),
        "p50_ms": r2(res.get("p50_ms")),
        "p99_ms": r2(res.get("p99_ms")),
        "index_secs": r2(res.get("index_secs")),
        # ingest throughput headline (ISSUE 7): ≥20k docs/s through the
        # vectorized bulk lane is the write-path acceptance bar
        "docs_per_sec": r2(res.get("docs_per_sec")),
        "batches": res.get("batches"),
        "batched_requests": res.get("batched_requests"),
        "search_rejected": res.get("search_rejected"),
        "budget_secs_left": round(_remaining(), 1),
        "platform": plat,
        # device telemetry flight recorder (ISSUE 16): the per-leg
        # sidecar + rollups already landed in _FINAL_LINE after each leg
        "xla_compile_ms_total": _FINAL_LINE.get("xla_compile_ms_total"),
        "hbm_peak_bytes": _FINAL_LINE.get("hbm_peak_bytes"),
        "lane_decision_counts": _FINAL_LINE.get("lane_decision_counts"),
        "flight": _FINAL_LINE.get("flight")}
    if err is not None:
        line["error"] = err
    if _FAILED_LEGS:
        line["failed_legs"] = list(_FAILED_LEGS)
    if "pod_skipped" in res:
        line["pod_skipped"] = res["pod_skipped"]
    if "agg_qps" in res:
        line.update({
            "agg_qps": round(res["agg_qps"], 2),
            "vs_baseline_agg": rnd(ratios.get("agg_qps")),
            "agg_docs": AGG_DOCS,
            "agg_index_secs": round(res["agg_index_secs"], 1),
            "agg_docs_per_sec": r2(res.get("agg_docs_per_sec")),
            # request-cache leg: hit ratio + resident bytes + the
            # cached-vs-uncached p50 gap (the cache's latency win)
            "request_cache_hit_ratio": rnd(
                res.get("request_cache_hit_ratio")),
            "request_cache_mem_bytes": res.get("request_cache_mem_bytes"),
            "agg_cached_p50_ms": r2(res.get("agg_cached_p50_ms")),
            "agg_uncached_p50_ms": r2(res.get("agg_uncached_p50_ms"))})
    if "stacked_p50_ms" in res:
        # multiseg leg (ISSUE 4) — the keys were computed but never made
        # it into the emitted line before ISSUE 5
        line.update({
            "stacked_p50_ms": r2(res.get("stacked_p50_ms")),
            "per_segment_p50_ms": r2(res.get("per_segment_p50_ms")),
            "multiseg_speedup": rnd(res.get("multiseg_speedup")),
            "stacked_fetches_per_query":
                r2(res.get("stacked_fetches_per_query")),
            "per_segment_fetches_per_query":
                r2(res.get("per_segment_fetches_per_query")),
            "multiseg_segments": res.get("multiseg_segments")})
        if "mesh_p50_ms" in res:
            # mesh lane (ISSUE 6): one collective program vs the
            # thread-pool fan-out on the multi-shard config
            line.update({
                "mesh_p50_ms": r2(res.get("mesh_p50_ms")),
                "fanout_p50_ms": r2(res.get("fanout_p50_ms")),
                "mesh_speedup": rnd(res.get("mesh_speedup")),
                "mesh_fetches_per_query":
                    r2(res.get("mesh_fetches_per_query")),
                "fanout_fetches_per_query":
                    r2(res.get("fanout_fetches_per_query")),
                "mesh_shards": res.get("mesh_shards"),
                # aggs through the mesh program (ISSUE 11)
                "mesh_agg_qps": r2(res.get("mesh_agg_qps")),
                "mesh_agg_dispatches": res.get("mesh_agg_dispatches")})
    if "cluster_host_reduce_qps" in res:
        # cluster-wide collectives data plane (ISSUE 11): one device
        # program per HOST vs one transport round-trip per shard
        line.update({
            "cluster_host_reduce_qps": r2(res.get("cluster_host_reduce_qps")),
            "cluster_fanout_qps": r2(res.get("cluster_fanout_qps")),
            "cluster_host_speedup": rnd(res.get("cluster_host_speedup")),
            "cluster_shards": res.get("cluster_shards"),
            "cluster_host_reduce_dispatches":
                res.get("cluster_host_reduce_dispatches")})
    if "pod_qps" in res:
        # pod-scale serving (ISSUE 19): concurrent per-pool collectives
        # vs the shared-pool EXEC_LOCK serialization, with the DCN hop
        # count and the shared-lock contention evidence
        line.update({
            "pod_qps": r2(res.get("pod_qps")),
            "single_pool_qps": r2(res.get("single_pool_qps")),
            "pod_vs_single": rnd(res.get("pod_vs_single")),
            "dcn_hops_per_query": rnd(res.get("dcn_hops_per_query")),
            "exec_lock_waits": res.get("exec_lock_waits"),
            "pod_reduce_dispatches": res.get("pod_reduce_dispatches")})
    if "chaos_rounds" in res:
        # chaos harness (ISSUE 14): zero mismatches / zero violations is
        # the acceptance signal; the seed makes any non-zero reproducible
        line.update({
            "chaos_seed": res.get("chaos_seed"),
            "chaos_rounds": res.get("chaos_rounds"),
            "chaos_parity_checks": res.get("chaos_parity_checks"),
            "chaos_mismatches": res.get("chaos_mismatches"),
            "chaos_invariant_violations":
                res.get("chaos_invariant_violations")})
    if "percolate_qps" in res:
        # reverse search + script compiler (ISSUE 18): the dense-vs-loop
        # percolate ratio at the registered-query count, the matrix cell
        # rate, and the compiled-vs-host script_score ratio
        line.update({
            "percolate_queries": res.get("percolate_queries"),
            "percolate_qps": r2(res.get("percolate_qps")),
            "percolate_matrix_qps": r2(res.get("percolate_matrix_qps")),
            "percolate_loop_qps": rnd(res.get("percolate_loop_qps")),
            "percolate_vs_loop": rnd(res.get("percolate_vs_loop")),
            "percolate_fetches_per_batch":
                r2(res.get("percolate_fetches_per_batch")),
            "script_score_qps": r2(res.get("script_score_qps")),
            "script_host_qps": r2(res.get("script_host_qps")),
            "script_vs_decline": rnd(res.get("script_vs_decline"))})
    if "watcher_evals_per_sec" in res:
        # watcher alerting tier (ISSUE 20): evaluation throughput,
        # per-fire latency (search + condition + alert bulk + persist),
        # the collector percolate-ride count, and composite pages/sec
        line.update({
            "watcher_watches": res.get("watcher_watches"),
            "watcher_evals_per_sec": r2(res.get("watcher_evals_per_sec")),
            "watcher_fire_p50_ms": r2(res.get("watcher_fire_p50_ms")),
            "watcher_percolate_rides": res.get("watcher_percolate_rides"),
            "watcher_fires": res.get("watcher_fires"),
            "composite_page_qps": r2(res.get("composite_page_qps")),
            "composite_pages": res.get("composite_pages")})
    if "rebalance_move_s" in res:
        # rebalance-under-load (ISSUE 15): the SLO pair under a live
        # shard move + the throttle-compliance evidence
        line.update({
            "rebalance_moved": res.get("rebalance_moved"),
            "rebalance_move_s": r2(res.get("rebalance_move_s")),
            "rebalance_p50_ms": r2(res.get("rebalance_p50_ms")),
            "rebalance_p99_ms": r2(res.get("rebalance_p99_ms")),
            "rebalance_queries": res.get("rebalance_queries"),
            "rebalance_errors": res.get("rebalance_errors"),
            "rebalance_recovered_bytes": res.get(
                "rebalance_recovered_bytes"),
            "recovery_throttle_bytes_per_sec": r2(res.get(
                "recovery_throttle_bytes_per_sec")),
            "recovery_throttle_limit_bytes_per_sec": res.get(
                "recovery_throttle_limit_bytes_per_sec"),
            "recovery_throttle_waits": res.get("recovery_throttle_waits"),
            "decider_vetoes": res.get("decider_vetoes"),
            "hedged_moving": res.get("hedged_moving")})
    if "scale_peak_rss_bytes" in res:
        # BENCH_SCALE leg (ISSUE 8): the 10M-doc-tier shapes, served by
        # the blockwise lane; peak RSS + peak score-matrix residency show
        # the O(Q × block) bound holding at 4M-doc aggs / 1M-doc vectors
        line.update({
            "scale_agg_qps": r2(res.get("scale_agg_qps")),
            "vs_baseline_scale_agg": rnd(ratios.get("scale_agg_qps")),
            "scale_agg_docs": res.get("scale_agg_docs"),
            "scale_agg_index_secs": r2(res.get("scale_agg_index_secs")),
            "scale_knn_qps": r2(res.get("scale_knn_qps")),
            "vs_baseline_scale_knn": rnd(ratios.get("scale_knn_qps")),
            "scale_knn_recall_at_10": rnd(res.get("scale_knn_recall")),
            "scale_knn_int8_qps": r2(res.get("scale_knn_int8_qps")),
            "scale_knn_pq_qps": r2(res.get("scale_knn_pq_qps")),
            "scale_pq_recall_at_10": rnd(res.get("scale_pq_recall")),
            "scale_vector_stack_bytes_f32":
                res.get("scale_vector_stack_bytes_f32"),
            "scale_vector_stack_bytes_pq":
                res.get("scale_vector_stack_bytes_pq"),
            "scale_vec_docs": res.get("scale_vec_docs"),
            "scale_vec_index_secs": r2(res.get("scale_vec_index_secs")),
            "scale_peak_rss_bytes": res.get("scale_peak_rss_bytes"),
            "scale_peak_score_matrix_bytes":
                res.get("scale_peak_score_matrix_bytes")})
    if "knn_qps" in res:
        exact = res.get("knn_exact_qps")
        line.update({
            "knn_qps": round(res["knn_qps"], 2),
            "vs_baseline_knn": rnd(ratios.get("knn_qps")),
            "knn_recall_at_10": round(res["knn_recall"], 4),
            # ANN lane (ISSUE 10): probes, adoption and the in-corpus
            # IVF-vs-exact speedup (the acceptance ratio)
            "knn_nprobe": res.get("knn_nprobe"),
            "ann_dispatches": res.get("ann_dispatches"),
            "knn_exact_qps": r2(exact),
            "ivf_speedup": rnd(res["knn_qps"] / exact) if exact else None,
            "hybrid_qps": round(res["hybrid_qps"], 2),
            "vs_baseline_hybrid": rnd(ratios.get("hybrid_qps")),
            "hybrid_recall_at_10": round(res["hybrid_recall"], 4),
            "hybrid_rrf_qps": r2(res.get("hybrid_rrf_qps")),
            "hybrid_rrf_recall_at_10": rnd(res.get("hybrid_rrf_recall")),
            "vec_docs": VEC_DOCS, "vec_dims": VEC_DIMS,
            "vec_index_secs": r2(res.get("vec_index_secs")),
            "vec_docs_per_sec": r2(res.get("vec_docs_per_sec"))})
        # quantized ANN tier (ISSUE 12): int8/PQ scan QPS vs the f32 IVF
        # lane on the same corpus + the measured byte reduction of the
        # quantized vector stack (codes + codebooks vs the f32 column)
        ivf = res.get("knn_qps")
        i8 = res.get("knn_int8_qps")
        pq = res.get("knn_pq_qps")
        qbytes = [res.get("vector_stack_bytes_int8"),
                  res.get("vector_stack_bytes_pq")]
        qbytes = [b for b in qbytes if b]
        line.update({
            "knn_int8_qps": r2(i8),
            "int8_recall_at_10": rnd(res.get("int8_recall")),
            "int8_vs_ivf": rnd(i8 / ivf) if i8 and ivf else None,
            "knn_pq_qps": r2(pq),
            "pq_recall_at_10": rnd(res.get("pq_recall")),
            "pq_vs_ivf": rnd(pq / ivf) if pq and ivf else None,
            "knn_pq_m": VEC_PQ_M, "knn_rescore_window": VEC_RESCORE,
            "vector_stack_bytes_f32": res.get("vector_stack_bytes_f32"),
            "vector_stack_bytes_int8":
                res.get("vector_stack_bytes_int8"),
            "vector_stack_bytes_pq": res.get("vector_stack_bytes_pq"),
            "vector_stack_bytes_quantized":
                min(qbytes) if qbytes else None})
    _FINAL_LINE.update(line)
    _emit(line)
    if err is not None or _FAILED_LEGS:
        sys.exit(1)


# ---------------------------------------------------------------------------
# --kernel: round-1 synthetic kernel harness (kernel regression tracking)
# ---------------------------------------------------------------------------

KN_DOCS = 1 << 20
KVOCAB = 1 << 17
KAVG_DL = 64
KQ = 64
KNB = 8


def build_chained(Wt: int):
    import jax
    import jax.numpy as jnp
    from elasticsearch_tpu.ops.bm25_sparse import bm25_topk_sparse
    kern = partial(bm25_topk_sparse, Wt=Wt, k=K, n_docs=KN_DOCS)

    @jax.jit
    def chained(doc_ids, tf, dl, qs, ql, w):
        def body(carry, batch):
            s, ln, ww = batch
            top, docs, hits = kern(doc_ids, tf, dl, s, ln, ww,
                                   jnp.float32(1.2), jnp.float32(0.75),
                                   jnp.float32(KAVG_DL))
            return carry + top[:, 0].sum() + docs[:, 0].sum() + hits.sum(), None
        acc, _ = jax.lax.scan(body, jnp.float32(0.0), (qs, ql, w))
        return acc
    return chained


def run_on(device, postings, batches, Wt):
    import jax
    args = [jax.device_put(a, device) for a in postings + batches]
    chained = build_chained(Wt)
    float(chained(*args))                      # compile + first exec
    t0 = time.perf_counter()
    for _ in range(REPS):
        float(chained(*args))                  # host fetch = true sync
    dt = (time.perf_counter() - t0) / REPS
    return KNB * KQ / dt


def main_kernel():
    import jax
    from __graft_entry__ import _synthetic_segment
    doc_ids, tf, doc_len, term_starts, term_lens = _synthetic_segment(
        KN_DOCS, KVOCAB, KAVG_DL, seed=7)
    dl = doc_len[doc_ids].astype(np.float32)

    rng = np.random.default_rng(42)
    tids = rng.integers(64, 8192, size=(KNB, KQ, T))
    qs = term_starts[tids].astype(np.int32)
    ql = term_lens[tids].astype(np.int32)
    w = np.abs(rng.normal(2.0, 0.5, (KNB, KQ, T))).astype(np.float32)
    Wt = 1 << int(np.ceil(np.log2(max(8, ql.max()))))

    pad = lambda a, fill: np.concatenate(   # noqa: E731
        [a, np.full(Wt, fill, a.dtype)])
    postings = [pad(doc_ids, KN_DOCS), pad(tf, 0), pad(dl, 1)]
    batches = [qs, ql, w]

    main_dev = jax.devices()[0]
    qps = run_on(main_dev, postings, batches, Wt)
    vs = None               # a CPU run has nothing to compare with
    if main_dev.platform != "cpu":
        try:
            cpu = jax.devices("cpu")[0]
            vs = round(qps / run_on(cpu, postings, batches, Wt), 3)
        except Exception as e:  # noqa: BLE001
            print(f"cpu baseline unavailable: {e}", file=sys.stderr)
    print(json.dumps({"metric": "kernel_bm25_top1000_qps_1M_docs",
                      "value": round(qps, 2), "unit": "qps",
                      "platform": main_dev.platform,
                      "vs_baseline": vs}))


if __name__ == "__main__":
    if "--kernel" in sys.argv:
        main_kernel()
    elif os.environ.get("BENCH_LEG") == "cpu":
        res = _run_all_legs("cpu")
        out = {"metric": "cpu_leg", "unit": "qps"}
        for k, v in res.items():
            if isinstance(v, (int, float)):
                out[k] = round(v, 3)
        print(json.dumps(out))
    else:
        main_engine()
