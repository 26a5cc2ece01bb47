"""Hand-worked cases: the roofline's byte counts, the reference's BM25, the
open-loop schedule, and the generator's latency-from-due and lag."""

import json
import math
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import compare
import traffic
import work
from conftest import BENCH
from reference import Reference, bf16

TINY = {
    "name": "tiny", "index": "tiny", "documents": 300,
    "similarity": {"k1": 1.2, "b": 0.75},
    "fields": {
        "body": {"kind": "text", "vocab": 50, "zipf": 1.0,
                 "length": {"dist": "uniform", "min": 3, "max": 9}},
        "ts": {"kind": "date", "base_millis": 1_000_000_000_000,
               "span_days": 4},
        "status": {"kind": "choice", "values": [200, 404],
                   "weights": [3, 1]}},
}


@pytest.fixture(scope="module")
def ref():
    return Reference(TINY, 7)


def test_match_bytes_are_postings_plus_output(ref):
    body = {"query": {"match": {"body": "t000003 t000010"}}, "size": 10}
    df = ref.df("body", [3, 10])
    assert work.body_bytes(ref, body) == 12 * int(df.sum()) + 8 * 10
    # df is the number of documents that hold the term
    lens, ranks = np.concatenate([c["body"][0] for c in ref.chunks]), \
        np.concatenate([c["body"][1] for c in ref.chunks])
    doc_of = np.repeat(np.arange(ref.n), lens)
    assert df[0] == len(set(doc_of[ranks == 3]))


def test_dashboard_bytes_are_docs_in_range_times_columns(ref):
    lo = TINY["fields"]["ts"]["base_millis"] + 86_400_000
    rng = {"range": {"ts": {"gte": lo, "lt": lo + 86_400_000}}}
    inside = int(((ref.cols["ts"] >= lo)
                  & (ref.cols["ts"] < lo + 86_400_000)).sum())
    hist = {"size": 0, "query": rng, "aggs": {
        "h": {"date_histogram": {"field": "ts", "interval": "hour"}}}}
    assert work.body_bytes(ref, hist) == inside * 8
    count = {"size": 0, "query": {"bool": {"filter": [
        rng, {"term": {"status": 200}}]}}}
    assert work.body_bytes(ref, count) == inside * (8 + 4)
    assert work.least_seconds({"hbm_bytes_per_s": 819e9}, 819e9) == 1.0


def test_reference_bm25_by_hand(ref):
    mask, score = ref.evaluate({"match": {"body": "t000004"}})
    post_doc, post_tf, start, avgdl = ref.postings("body")
    d = int(np.flatnonzero(mask)[0])
    s, e = start[4], start[5]
    tf = float(post_tf[s:e][post_doc[s:e] == d][0])
    df = e - s
    idf = math.log(1 + (300 - df + 0.5) / (df + 0.5))
    norm = 1.2 * (0.25 + 0.75 * ref.lens["body"][d] / avgdl)
    assert score[d] == pytest.approx(idf * 2.2 * tf / (tf + norm), rel=1e-12)
    assert int(mask.sum()) == df


def test_low_precision_control_is_caught(ref):
    low = Reference(TINY, 7, precision="low")
    assert bf16(1.00390625) in (1.0, 1.0078125)
    tally = compare.Tally()
    for t in range(3, 12):
        body = {"query": {"match": {"body": f"t{t:06d} t{t + 9:06d}"}},
                "size": 10, "_source": False}
        compare.compare_answer(tally, "c", body, low.respond(body), ref, 1e-5)
    assert 1e-4 < tally.n["score_rel_err_max"] < 0.05
    same = compare.Tally()
    compare.compare_answer(same, "s", body, ref.respond(body), ref, 1e-5)
    assert same.n["score_rel_err_max"] == 0 and not same.notes


def test_narrowed_columns_are_caught(ref):
    low = Reference(TINY, 7, precision="low")
    lo = TINY["fields"]["ts"]["base_millis"] + 3_600_000 * 5
    body = {"size": 0, "query": {"range": {"ts": {"gte": lo,
                                                  "lt": lo + 86_400_000}}},
            "aggs": {"h": {"date_histogram": {"field": "ts",
                                              "interval": "hour"}}}}
    tally = compare.Tally()
    compare.compare_answer(tally, "c", body, low.respond(body), ref, 1e-5)
    assert tally.n["buckets_wrong"] == 1


WORKLOAD = {"loop": "open", "rate_per_s": 50.0, "endpoint": "_search",
            "shape_seed": 3, "warmup": {"replay_s": 1.0, "rounds": 1},
            "sample": {"requests": 5},
            "mix": [{"weight": 1, "body": {"query": {"match": {"body": {
                "$text": {"field": "body", "min": 2, "max": 3,
                          "skip_top": 2}}}}, "size": 10}}]}


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.build(WORKLOAD, TINY, 1, 4.0)
    b = traffic.build(WORKLOAD, TINY, 2, 4.0)
    assert len(a) == len(b) == 200
    assert sorted(r["payload"] for r in a) == sorted(r["payload"] for r in b)
    assert [r["payload"] for r in a] != [r["payload"] for r in b]
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r["due"] for r in rs]), 9))
    assert gaps(a) == gaps(b)
    assert 0 < a[0]["due"] and a[-1]["due"] < 4.0
    assert traffic.build(WORKLOAD, TINY, 1, 4.0) == a
    assert traffic.sample(WORKLOAD, 200, 2 ** 31 + 5) == \
        traffic.sample(WORKLOAD, 200, 2 ** 31 + 5)
    for r in a:
        terms = r["bodies"][0]["query"]["match"]["body"].split()
        assert 2 <= len(terms) <= 3 and len(set(terms)) == len(terms)
        assert all(int(t[1:]) >= 2 for t in terms)


CLOSED = {**WORKLOAD, "loop": "closed", "clients": 2, "endpoint": "_msearch",
          "bodies_per_request": 3, "requests_per_10s": 40, "order_block": 4}


@pytest.mark.parametrize("sent", [4, 8, 20, 40])
def test_a_closed_loop_sends_the_same_requests_whatever_the_seed(sent):
    """A closed loop gets through a part of what is built. With
    `order_block` the seed changes the order inside runs of that many
    requests, so any whole number of runs is the same set for every seed."""
    a = traffic.build(CLOSED, TINY, 1, 10.0)
    b = traffic.build(CLOSED, TINY, 2 ** 31 + 9, 10.0)
    assert len(a) == len(b) == 44 and "due" not in a[0]
    assert [r["payload"] for r in a] != [r["payload"] for r in b]
    assert sorted(r["payload"] for r in a[:sent]) == \
        sorted(r["payload"] for r in b[:sent])
    assert all(len(r["bodies"]) == 3 and r["path"] == "/_msearch"
               for r in a)
    free = traffic.build({**CLOSED, "order_block": 44}, TINY, 1, 10.0)
    assert sorted(r["payload"] for r in free) == \
        sorted(r["payload"] for r in a)


def test_nothing_rides_in_the_window_but_the_mix():
    """`warmup.pilots` are the warm-up's alone: the window's requests are
    the same with and without them."""
    pilot = {"query": {"match": {"body": "t000002 t000003"}}, "size": 10}
    piloted = {**WORKLOAD, "warmup": {**WORKLOAD["warmup"],
                                      "pilots": [pilot], "copies": 4}}
    assert traffic.build(piloted, TINY, 5, 2.0) == \
        traffic.build(WORKLOAD, TINY, 5, 2.0)
    sent, = traffic.pilot_requests(piloted, TINY)
    assert sent["bodies"] == [pilot] and sent["path"] == "/tiny/_search"
    assert json.loads(sent["payload"]) == pilot
    assert traffic.pilot_requests(WORKLOAD, TINY) == []


class _Slow(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        body = json.loads(b'{"took": 7, "hits": {"total": 0, "hits": []}}')
        time.sleep(0.3 if self.path.endswith("slow") else 0.0)
        data = json.dumps(body).encode()
        self.send_response(429 if self.path.endswith("shed") else 200)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


def _generate(tmp_path, plan, requests):
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    with open(tmp_path / "requests.jsonl", "w") as f:
        for r in requests:
            f.write(json.dumps(r) + "\n")
    plan = {"port": server.server_address[1], "grace_s": 10,
            "requests": str(tmp_path / "requests.jsonl"),
            "out": str(tmp_path), "clients": 1, "connections": 1, **plan}
    with open(tmp_path / "plan.json", "w") as f:
        json.dump(plan, f)
    gen = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py"),
         str(tmp_path / "plan.json")], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)
    assert gen.stdout.readline().strip() == "ready"
    gen.stdin.write("go\n")
    gen.stdin.flush()
    assert gen.wait(timeout=60) == 0
    server.shutdown()
    with open(tmp_path / "records.jsonl") as f:
        lines = [json.loads(x) for x in f]
    return lines[0], lines[1:]


def test_open_loop_times_from_due_and_reports_lag(tmp_path):
    reqs = [{"path": "/slow", "payload": "{}", "due": 0.05, "keep": True},
            {"path": "/x", "payload": "{}", "due": 0.10, "keep": False},
            {"path": "/shed", "payload": "{}", "due": 0.60, "keep": False}]
    header, recs = _generate(tmp_path, {"loop": "open", "seconds": 1.0}, reqs)
    assert header["never_answered"] == [] and len(recs) == 3
    slow, behind, shed = recs
    # one connection: the request behind the stall left late, and its
    # latency counts from when it was due
    assert behind["sent"] - behind["due"] > 0.2
    assert behind["done"] - behind["due"] > 0.2
    assert slow["sent"] - slow["due"] < 0.05
    assert shed["status"] == 429 and slow["took_ms"] == 7
    assert os.path.exists(tmp_path / "kept" / "0.json")
    assert not os.path.exists(tmp_path / "kept" / "1.json")
    from readers import generator_lag, latency, shed_share
    ctx = {"records": recs, "never_answered": []}
    assert generator_lag.read(ctx, {"q": 0.95}) > 150
    assert shed_share.read(ctx, {}) == pytest.approx(100 / 3)
    # the refused request misses: it is charged the miss time
    assert latency.read(ctx, {"q": 0.95, "miss_ms": 10000}) > 5000


def test_closed_loop_finishes_what_is_under_way(tmp_path):
    reqs = [{"path": "/slow", "payload": "{}", "due": None, "keep": False}
            for _ in range(50)]
    header, recs = _generate(tmp_path, {"loop": "closed", "seconds": 0.7,
                                        "clients": 2}, reqs)
    assert 4 <= len(recs) <= 8 and all(r["status"] == 200 for r in recs)
    assert max(r["done"] for r in recs) > 0.7
    assert all(r["sent"] < 0.7 for r in recs)


class _RefusesTwice(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    seen: list = []

    def log_message(self, *a):
        pass

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        self.seen.append(self.path)
        self.send_response(429 if len(self.seen) <= 2 else 200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"{}")


def test_a_refused_pilot_is_sent_until_it_is_let_in():
    import harness
    _RefusesTwice.seen = []
    server = ThreadingHTTPServer(("127.0.0.1", 0), _RefusesTwice)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    refusals: list = []
    harness._fire(server.server_address[1],
                  {"path": "/tiny/_search", "payload": "{}"}, refusals,
                  pause_s=0.01)
    assert len(_RefusesTwice.seen) == 3 and len(refusals) == 2
    # one that is never let in is the set-up's failure, not a silent skip
    with pytest.raises(RuntimeError, match="HTTP 429"):
        _RefusesTwice.seen = []
        harness._fire(server.server_address[1],
                      {"path": "/tiny/_search", "payload": "{}"}, [],
                      patience_s=-1.0)
    server.shutdown()


@pytest.mark.parametrize("rounds, want", [
    ([(0, 5), (0, 0)], (0, 0)),          # refused, then clean: two rounds
    ([(3, 0), (0, 2), (0, 0)], (0, 0)),  # compiles, refusals, clean
    ([(0, 7)] * 4, (0, 7)),              # never clean: `rounds` at most
])
def test_the_replay_goes_on_until_a_round_is_refused_nothing(rounds, want):
    import harness
    serving = harness.Serving.__new__(harness.Serving)
    serving.cell = type("C", (), {"workload": {
        **WORKLOAD, "warmup": {"replay_s": 0.5, "rounds": 4}}, "cfg": TINY})()
    serving.seed, left = 5, list(rounds)
    calls = []

    def window(requests, keep, seconds, trace):
        compiled, refused = left.pop(0)
        calls.append(len(requests))
        metric = {"metrics": {"es_jit_compiles_total": [({}, 0.0)]}}
        after = {"metrics": {"es_jit_compiles_total": [({}, compiled)]}}
        records = [{"status": 429}] * refused + [{"status": 200}] * 3
        return {"before": metric, "after": after, "records": records}

    serving.window = window
    assert serving.replay() == want
    assert len(calls) == len(rounds) and all(calls)
