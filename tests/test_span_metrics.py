"""One span per layer boundary of the packed serving path (ISSUE 24):
the always-on aggregate on `/_metrics`, the device-gap ledger, the join to
the profiler's clock, and the named scopes inside the packed program.
"""

import glob
import json
import os
import re
import time
import urllib.request

import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.rest import HttpServer

N_SOLO = 5
MATCH = {"query": {"match": {"body": "quick fox"}}, "size": 3}

# every boundary of ISSUE 24's table; `batcher.follow` needs a follower and
# is covered in tests/test_batcher.py
SPANS = ("rest.request", "rest.read_body", "qos.admit", "pool.queue_wait",
         "rest.parse_body", "search.plan", "packed_batch",
         "batcher.queue_wait", "packed.build_slots", "program", "packed.d2h",
         "packed.respond", "rest.serialize", "rest.write")


@pytest.fixture(scope="module")
def http(tmp_path_factory):
    node = NodeService(str(tmp_path_factory.mktemp("spans")))
    srv = HttpServer(node, port=0).start()
    base = f"http://127.0.0.1:{srv.port}"

    def req(method, path, body=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        data = body.encode() if body is not None else None
        r = urllib.request.Request(base + path, data=data, method=method)
        raw = urllib.request.urlopen(r).read()
        try:
            return json.loads(raw)
        except ValueError:
            return raw.decode()

    req("PUT", "/sp", {"settings": {"number_of_shards": 2},
                       "mappings": {"_doc": {"properties": {
                           "body": {"type": "string"}}}}})
    req("POST", "/_bulk?refresh=true", "".join(
        json.dumps({"index": {"_index": "sp", "_type": "_doc",
                              "_id": str(i)}}) + "\n"
        + json.dumps({"body": f"quick brown fox {i} jumps"}) + "\n"
        for i in range(120)))
    req("POST", "/sp/_search", MATCH)            # warm the solo shape
    yield node, req
    srv.stop()
    node.close()


def _scrape(req) -> dict:
    """`/_metrics` -> {family: {label value or "": number}} for the span,
    gap, flight and transfer families."""
    out: dict[str, dict] = {}
    for line in req("GET", "/_metrics").splitlines():
        m = re.match(r'^(es_(?:span|device_gap|device_flight|transfer)\w*)'
                     r'\{(.*)\} (\S+)$', line)
        if not m:
            continue
        labels = dict(p.split("=", 1) for p in m.group(2).split(","))
        key = (labels.get("span") or labels.get("during")
               or '""').strip('"')
        out.setdefault(m.group(1), {})[key] = float(m.group(3))
    return out


def _msearch_body(n: int) -> str:
    return "".join(json.dumps({"index": "sp"}) + "\n" + json.dumps(MATCH)
                   + "\n" for _ in range(n))


# -- (a) the aggregate on /_metrics ----------------------------------------

def test_metrics_carry_every_span_of_the_packed_path(http, monkeypatch):
    node, req = http
    monkeypatch.setattr(tracing, "CPU_SAMPLE", 1)   # every host span reads
    before = _scrape(req)
    for _ in range(N_SOLO):
        assert req("POST", "/sp/_search", MATCH)["hits"]["total"] == 120
    ms = req("POST", "/_msearch", _msearch_body(4))
    assert [r["hits"]["total"] for r in ms["responses"]] == [120] * 4

    def delta(after, family, span):
        return after[family].get(span, 0.0) \
            - before.get(family, {}).get(span, 0.0)

    # an HTTP thread closes `rest.write` and `rest.request` after the client
    # has its answer: wait for the last one rather than race it
    requests = N_SOLO + 1
    deadline = time.monotonic() + 5.0
    while True:
        after = _scrape(req)
        if delta(after, "es_span_total", "rest.write") >= requests + 1 \
                or time.monotonic() > deadline:
            break
        time.sleep(0.01)

    for span in SPANS:
        assert span in after["es_span_total"], span
        for family in ("es_span_seconds_total", "es_span_self_seconds_total",
                       "es_span_max_seconds"):
            assert span in after[family], (family, span)
        assert after["es_span_self_seconds_total"][span] \
            <= after["es_span_seconds_total"][span] + 1e-9, span
        assert after["es_span_max_seconds"][span] \
            <= after["es_span_seconds_total"][span] + 1e-9, span

    # the search requests alone open these; each solo request leads its
    # own batch, the _msearch runs its four bodies as one program
    want = {"qos.admit": requests, "pool.queue_wait": requests,
            "rest.parse_body": requests, "search.plan": requests,
            "packed_batch": N_SOLO, "batcher.queue_wait": N_SOLO,
            "packed.build_slots": requests, "program": requests,
            "packed.d2h": requests, "packed.respond": requests}
    got = {span: delta(after, "es_span_total", span) for span in want}
    assert got == want
    # the scrapes are requests too (the first one's `rest.request` closes
    # inside the delta); the raw _msearch serializes once more in the node
    assert delta(after, "es_span_total", "rest.request") == requests + 1
    assert delta(after, "es_span_total", "rest.serialize") == requests + 2

    # a request's children lie inside it, whichever thread ran them (the
    # lane's own spans lie in `packed_batch`, or for the _msearch beside it)
    inside = ("rest.read_body", "qos.admit", "pool.queue_wait",
              "rest.parse_body", "search.plan", "packed_batch",
              "rest.serialize", "rest.write")
    children = sum(delta(after, "es_span_seconds_total", s) for s in inside)
    assert 0 < children \
        <= delta(after, "es_span_seconds_total", "rest.request")
    # the host-compute spans book their thread's CPU time beside their
    # wall time; a wait, timed or booked from two timestamps (`add_span`),
    # books none
    host = {"rest.parse_body", "search.plan", "packed.build_slots",
            "packed.respond", "rest.serialize"}
    assert set(after["es_span_cpu_seconds_total"]) == host
    assert set(after["es_span_cpu_wall_seconds_total"]) == host
    assert delta(after, "es_span_cpu_seconds_total", "rest.parse_body") > 0
    assert delta(after, "es_span_cpu_wall_seconds_total", "search.plan") \
        == pytest.approx(delta(after, "es_span_seconds_total", "search.plan"))
    # a program in flight is no gap: both views are there and positive
    assert after["es_device_flight_seconds_total"][""] > \
        before["es_device_flight_seconds_total"][""]
    assert sum(after["es_device_gap_seconds_total"].values()) > 0


def test_transfer_counters_reach_the_packed_lane(http):
    node, req = http
    before = _scrape(req)
    out = req("POST", "/sp/_search?trace=true", MATCH)
    assert out["hits"]["total"] == 120
    after = _scrape(req)
    up = after["es_transfer_bytes_to_device_total"][""] \
        - before["es_transfer_bytes_to_device_total"][""]
    down = after["es_transfer_bytes_from_device_total"][""] \
        - before["es_transfer_bytes_from_device_total"][""]
    fetches = after["es_transfer_device_fetches_total"][""] \
        - before["es_transfer_device_fetches_total"][""]
    # one i32[1, 3S+1] slot table up (S >= 32) and one i32[1, 2k+1] answer
    # down (k padded to 8): still ONE download
    assert up >= 4 * (3 * 32 + 1)
    assert down == 4 * (2 * 8 + 1)
    assert fetches == 1
    # the spans' attributes are the same numbers
    trace = next(t for t in req("GET", "/_traces")["traces"]
                 if "_search" in t["root"])          # newest first
    spans = {s["name"]: s for s in
             req("GET", f"/_traces/{trace['trace_id']}?format=chrome")
             ["traceEvents"] if s.get("ph") == "X"}
    prep = spans["packed.build_slots"]["args"]
    assert prep["h2d_bytes"] == up
    # the table is the one host array the program's dispatch uploads; the
    # BM25 scalars were made by the fixture's warming search and stay
    assert (prep["operands"], prep["consts"]) == (1, "reused")
    assert spans["packed.d2h"]["args"]["d2h_bytes"] == down
    # the leader's tree: packed_batch is the parent of its whole stay
    stay = spans["packed_batch"]["args"]["span_id"]
    for name in ("batcher.queue_wait", "packed.build_slots", "program",
                 "packed.d2h", "packed.respond"):
        assert spans[name]["args"]["parent_span_id"] == stay, name


# -- (b) the gap ledger under an injected clock -----------------------------

class _Clock:
    def __init__(self):
        self.t = 1_000

    def __call__(self):
        return self.t


@pytest.fixture()
def ledger(monkeypatch):
    """A fresh aggregate and ledger on a clock the test sets by hand."""
    clock = _Clock()
    monkeypatch.setattr(tracing, "_clock", clock)
    monkeypatch.setattr(tracing, "AGGREGATE", tracing.SpanAggregate())
    monkeypatch.setattr(tracing, "GAPS", tracing.GapLedger())
    monkeypatch.setattr(tracing._LOCAL, "state", tracing._ThreadState(),
                        raising=False)
    return clock


def _gaps() -> dict:
    return {k: round(v["seconds_total"] * 1e9)
            for k, v in tracing.GAPS.gap_stats().items()}


def test_overlapping_flights_make_no_gap(ledger):
    one, other = tracing._ThreadState(), tracing._ThreadState()
    gaps = tracing.GAPS
    first = gaps.takeoff(1_000, one)
    second = gaps.takeoff(1_400, other)     # overlaps the first
    gaps.land(1_700, first)                 # the second is still in flight
    gaps.land(2_000, second)
    assert _gaps() == {}
    assert gaps.flight_stats()["seconds_total"] * 1e9 \
        == pytest.approx(1_000)     # the union, not the sum (1300)
    gaps.takeoff(2_600, other)      # only now was the device idle
    assert _gaps() == {"no_request": 600}


def test_the_charges_of_a_gap_sum_to_the_gap(ledger):
    with tracing.flight("ops:a"):
        ledger.t = 2_000            # lands at 2000: the gap starts
    ledger.t = 2_100
    tracing.begin_request(2_050)    # queued 2050..2100: the request waits
    ledger.t = 2_150
    with tracing.span("search.plan"):           # 2150..2400
        ledger.t = 2_200
        with tracing.span("cache.get"):         # 2200..2300, innermost
            ledger.t = 2_300
        ledger.t = 2_400
    ledger.t = 2_500
    with tracing.span("packed_batch"):          # open at the dispatch
        ledger.t = 2_600
        with tracing.span("packed.build_slots"):
            ledger.t = 2_900
        ledger.t = 3_000
        with tracing.flight("ops:a"):           # the gap ends at 3000
            ledger.t = 3_500
    assert _gaps() == {
        "no_request": 50,           # 2000..2050: nothing waited for the chip
        "pool.queue_wait": 50,
        "search.plan": 150,         # 250 less its child's 100
        "cache.get": 100,
        "packed_batch": 200,        # 2500..2600 and 2900..3000
        "packed.build_slots": 300,
        "unattributed": 150,        # 2100..2150 and 2400..2500
    }
    assert sum(_gaps().values()) == 1_000
    # self time in the aggregate: the same subtraction, per thread
    rows = tracing.AGGREGATE.stats()
    assert rows["search.plan"]["self_seconds_total"] * 1e9 \
        == pytest.approx(150)
    assert rows["packed_batch"]["self_seconds_total"] * 1e9 \
        == pytest.approx(1_000 - 300 - 500)
    assert rows["pool.queue_wait"]["total"] == 1


def test_an_empty_trail_charges_no_request(ledger):
    with tracing.flight("ops:a"):
        ledger.t = 2_000
    ledger.t = 5_000
    with tracing.flight("ops:a"):   # no span, no request on this thread
        ledger.t = 5_100
    assert _gaps() == {"no_request": 3_000}
    # each dispatch cleared the trail: what the thread closed before it
    # can lie in no later gap (the flight itself closed after it)
    assert list(tracing._thread_state().trail) == [("program", 5_000, 5_100)]


def test_a_span_that_began_before_the_gap_is_clipped_to_it(ledger):
    state = tracing._ThreadState()
    state.trail.append(("packed.respond", 500, 1_200))
    state.request_start_ns = 100
    assert tracing.charge_gap(1_000, 2_000, state) == {
        "packed.respond": 200, "unattributed": 800}


def test_a_flight_behind_others_books_its_queue(ledger):
    one, two, three = (tracing._ThreadState() for _ in range(3))
    gaps = tracing.GAPS
    a = gaps.takeoff(1_000, one)
    b = gaps.takeoff(1_200, two)        # behind a
    c = gaps.takeoff(1_300, three)      # behind a and b
    gaps.land(1_700, a)                 # b's queue ends; c still waits for b
    gaps.land(2_100, b)                 # c's queue ends
    gaps.land(2_500, c)
    d = gaps.takeoff(3_000, one)        # alone: the device was idle
    gaps.land(3_200, d)
    row = tracing.AGGREGATE.stats()["program.queue"]
    assert row["total"] == 2
    assert row["seconds_total"] * 1e9 == pytest.approx(500 + 800)
    assert "cpu_seconds_total" not in row


def test_a_queue_ends_at_the_flights_own_landing_at_the_latest(ledger):
    # the host's view: threads race from their landing to the ledger's
    # lock, so the one behind may be booked first; its wait is then all of
    # its flight, never more
    one, two = tracing._ThreadState(), tracing._ThreadState()
    gaps = tracing.GAPS
    a = gaps.takeoff(1_000, one)
    b = gaps.takeoff(1_100, two)
    gaps.land(1_600, b)
    gaps.land(1_650, a)
    row = tracing.AGGREGATE.stats()["program.queue"]
    assert (row["total"], round(row["seconds_total"] * 1e9)) == (1, 500)


def test_disjoint_flights_book_no_queue(ledger):
    for t in (1_000, 2_000, 3_000):
        with tracing.flight("ops:a"):
            ledger.t = t + 500
        ledger.t = t + 1_000
    rows = tracing.AGGREGATE.stats()
    assert rows["program"]["total"] == 3
    assert "program.queue" not in rows


S = 1_000_000_000


def _leave_gaps(*gaps) -> None:
    """Flights that leave exactly these (start, length) gaps, in order."""
    st = tracing._ThreadState()
    flight = tracing.GAPS.takeoff(gaps[0][0] - 100, st)
    for start, length in gaps:
        tracing.GAPS.land(start, flight)
        flight = tracing.GAPS.takeoff(start + length, st)
    tracing.GAPS.land(gaps[-1][0] + gaps[-1][1] + 100, flight)


def test_the_longest_gaps_of_each_second_are_kept(ledger):
    _leave_gaps(*[(5 * S + i * 10_000, length) for i, length
            in enumerate([300, 100, 600, 200, 500, 400])], (6 * S, 50))
    got = tracing.GAPS.gap_records()
    assert [r["end_ns"] - r["start_ns"] for r in got] \
        == [300, 600, 500, 400, 50]         # by start, newest last
    for r in got:
        assert r["during"] == {"no_request": r["end_ns"] - r["start_ns"]}
    # 600 s on, the first second's records are gone (the idle stretch
    # from the last landing, in second 6, is a gap too)
    _leave_gaps(((5 + 600) * S, 70))
    assert [r["start_ns"] // S for r in tracing.GAPS.gap_records()] \
        == [6, 6, 605]


def test_a_gap_record_keeps_four_charges_and_the_rest_as_other(ledger):
    ledger.t = 990
    with tracing.flight("ops:a"):
        ledger.t = 1_000
    st = tracing._thread_state()
    st.request_start_ns = 1_000
    for i, name in enumerate(["a", "b", "c", "d", "e", "f"]):
        st.trail.append((name, 1_000 + 100 * i, 1_000 + 100 * i + 10 * (i + 1)))
    ledger.t = 1_700
    with tracing.flight("ops:a"):
        ledger.t = 1_800
    rec, = tracing.GAPS.gap_records()
    assert (rec["start_ns"], rec["end_ns"]) == (1_000, 1_700)
    assert rec["during"] == {"unattributed": 490, "f": 60, "e": 50, "d": 40,
                             "other": 30 + 20 + 10}
    assert sum(rec["during"].values()) == 700


def test_cpu_time_is_below_wall_off_the_cpu_and_near_it_on(monkeypatch):
    monkeypatch.setattr(tracing, "AGGREGATE", tracing.SpanAggregate())
    monkeypatch.setattr(tracing, "CPU_SAMPLE", 1)
    with tracing.span("sleeps", cpu=True):
        time.sleep(0.05)
    with tracing.span("spins", cpu=True):
        t = time.perf_counter()
        while time.perf_counter() - t < 0.05:
            pass
    with tracing.span("waits"):         # not a host-compute span
        time.sleep(0.01)
    rows = tracing.AGGREGATE.stats()
    assert "cpu_seconds_total" not in rows["waits"]
    sleeps, spins = rows["sleeps"], rows["spins"]
    assert sleeps["cpu_wall_seconds_total"] == sleeps["seconds_total"]
    assert sleeps["seconds_total"] >= 0.05
    assert sleeps["cpu_seconds_total"] < 0.2 * sleeps["seconds_total"]
    # near the wall: the core may be shared with the suite's other workers
    assert 0.5 * spins["seconds_total"] < spins["cpu_seconds_total"] \
        <= spins["seconds_total"] * 1.01


def test_one_host_span_in_cpu_sample_reads_the_cpu_clock(monkeypatch):
    import itertools
    monkeypatch.setattr(tracing, "AGGREGATE", tracing.SpanAggregate())
    monkeypatch.setattr(tracing, "_cpu_turn", itertools.count())
    for _ in range(2 * tracing.CPU_SAMPLE):
        with tracing.span("host", cpu=True):
            time.sleep(0.002)
    row = tracing.AGGREGATE.stats()["host"]
    assert row["total"] == 2 * tracing.CPU_SAMPLE
    # two of the spans read the clock: their wall, not all the spans'
    share = row["cpu_wall_seconds_total"] / row["seconds_total"]
    assert 0.5 / tracing.CPU_SAMPLE < share < 1.6 / tracing.CPU_SAMPLE
    assert row["cpu_seconds_total"] < row["cpu_wall_seconds_total"]


def test_the_dispatch_lock_wait_is_a_span_only_when_the_lock_was_held(
        monkeypatch):
    import threading
    import types
    from elasticsearch_tpu.parallel.mesh_exec import exec_guard
    monkeypatch.setattr(tracing, "AGGREGATE", tracing.SpanAggregate())
    pool = types.SimpleNamespace(lock=threading.Lock())
    with exec_guard(pool):
        pass
    assert "exec.lock_wait" not in tracing.AGGREGATE.stats()
    pool.lock.acquire()
    holder = threading.Timer(0.05, pool.lock.release)
    holder.start()
    with exec_guard(pool):
        pass
    holder.join(5)
    row = tracing.AGGREGATE.stats()["exec.lock_wait"]
    assert row["total"] == 1
    assert 0.03 <= row["seconds_total"] < 5


# -- (c) the join: spans on the profiler's clock ------------------------------

def test_profiler_trace_holds_the_spans_as_host_events(http, tmp_path):
    import jax
    from jax.profiler import ProfileData
    node, req = http
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        req("POST", "/sp/_search", MATCH)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("es:"):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    (r0, r1), = events["es:rest.request"]
    (b0, b1), = events["es:packed.build_slots"]
    assert r0 <= b0 < b1 <= r1
    (p0, p1), = events["es:program"]
    assert b1 <= p0 < p1 <= r1


def test_the_gap_ledger_maps_onto_the_profilers_clock(tmp_path, monkeypatch):
    """Every `es:program` event carries `t0_ns`, its start on the program's
    clock: one offset maps a gap record onto the capture, where it lies
    between the programs."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    monkeypatch.setattr(tracing, "GAPS", tracing.GapLedger())
    double = jax.jit(lambda v: v * 2 + 1)
    x = jnp.ones(64)
    double(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(8):
            with tracing.flight("ops:join"):
                jax.block_until_ready(double(x))
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    programs = [(e.start_ns, e.start_ns + e.duration_ns,
                 e.start_ns - dict(e.stats)["t0_ns"])
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events
                if e.name == "es:program"]
    assert len(programs) == 8
    offsets = [off for _, _, off in programs]
    spread = max(offsets) - min(offsets)
    assert spread <= 200_000
    offset = min(offsets)
    gaps = tracing.GAPS.gap_records()
    assert 4 <= len(gaps) <= 7          # 7 gaps, at most 4 kept a second
    for g in gaps:
        g0, g1 = g["start_ns"] + offset, g["end_ns"] + offset
        assert g1 - g0 >= 4_000_000
        for p0, p1, _ in programs:   # no overlap beyond the anchor's error
            assert min(g1, p1) - max(g0, p0) <= spread + 20_000


def test_device_gaps_endpoint_lists_the_kept_gaps(http):
    node, req = http
    req("POST", "/sp/_search", MATCH)
    time.sleep(0.01)
    req("POST", "/sp/_search", MATCH)
    body = req("GET", "/_nodes/device_gaps")["nodes"]["tpu-node-0"]
    assert body["clock"] == "monotonic_ns"
    starts = [g["start_ns"] for g in body["gaps"]]
    assert starts and starts == sorted(starts)       # newest last
    for g in body["gaps"]:
        assert sum(g["during"].values()) == g["end_ns"] - g["start_ns"]


# -- (d) the named scopes inside the packed program ---------------------------

def test_packed_program_names_every_scope():
    import jax.numpy as jnp
    from elasticsearch_tpu.ops.bm25_sparse import (
        bm25_serve_packed, bm25_serve_packed_filtered)
    Q, S, CHUNK, P, N = 2, 4, 8, 64, 32
    common = (jnp.zeros((Q, 3 * S + 1), jnp.int32),
              jnp.zeros(P, jnp.int32), jnp.ones(P, jnp.float32),
              jnp.ones(P, jnp.float32),
              jnp.float32(1.2), jnp.float32(0.75), jnp.float32(1.0),
              jnp.float32(0.0))
    scopes = {"packed.gather", "packed.score", "packed.sort",
              "packed.combine_runs", "packed.keep", "packed.topk",
              "packed.pack_out"}

    def named(lowered) -> set:
        text = lowered.as_text(debug_info=True)
        return {s for s in scopes | {"packed.filters"}
                if re.search(r'"[^"\n]*/' + re.escape(s) + r'/', text)}

    plain = bm25_serve_packed.jit.lower(*common, S=S, CHUNK=CHUNK, R=4, k=8)
    assert named(plain) == scopes
    FR, FT, TV = 2, 2, 4
    filtered = bm25_serve_packed_filtered.jit.lower(
        *common, jnp.zeros((1, N), jnp.int32),
        jnp.full((Q, FR), -1, jnp.int32), jnp.zeros((Q, FR), jnp.int32),
        jnp.zeros((Q, FR), jnp.int32), jnp.zeros((Q, FR), jnp.int32),
        jnp.full((Q, FT), -1, jnp.int32), jnp.zeros((Q, FT, TV), jnp.int32),
        jnp.zeros((Q, FT), jnp.int32),
        S=S, CHUNK=CHUNK, R=4, k=8, FR=FR, FT=FT, TV=TV)
    assert named(filtered) == scopes | {"packed.filters"}
