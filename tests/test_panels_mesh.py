"""The panel lane across chips (search/aggs/panels.py): S shards over the N
chips a node owns, one collective program a batch.

(a) each of the three programs against a plain numpy oracle on seeded random
columns (uneven segments a shard, a shard with none, missing values,
tombstones after placement), equal integer for integer at chip axes of 1, 2,
4 and 8 with 5 shards: the shares add up to the whole at every axis; (b) a
shard's home is its number mod the chips owned and a chip holds its own
segments' operands only; (c) the set of programs is closed and enumerable;
(d) leaders of the three shapes dispatching at once finish."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.common.device_stats import lane_decisions_snapshot
from elasticsearch_tpu.common.metrics import device_events_snapshot
from elasticsearch_tpu.index.segment import (NumericColumn, Segment,
                                             TextFieldIndex, next_pow2)
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.parallel.mesh import DevicePool
from elasticsearch_tpu.parallel.mesh_exec import exec_lock_stats
from elasticsearch_tpu.search.aggs import panels
from elasticsearch_tpu.search.aggs.aggregators import parse_aggs

HOUR = 3_600_000
BASE = 893_964_617_000
WORDS = ["get", "images", "english", "french", "index", "html", "gif"]
STATUS = np.array([200, 200, 200, 304, 304, 404, 500, 206, 302])
AXES = [1, 2, 4, 8]
# documents a segment, a shard: uneven, shard 3 holds nothing, one segment
# is empty; with 4 chips chip 0 holds shards 0 and 4
LAYOUT = [[40, 7, 300, 1], [60], [33, 0, 90], [], [5, 150, 12]]


def pool_of(n: int) -> DevicePool:
    return DevicePool(jax.devices()[:n], name=f"test{n}")


# -- seeded random segments -----------------------------------------------------

def make_segment(seg_id: int, n: int, rng) -> tuple[Segment, dict]:
    """A segment of `n` documents of random columns and the same columns
    on the host, for the oracle. A twentieth of the timestamps and of the
    statuses are missing."""
    n_pad = next_pow2(n)
    ts = BASE + rng.integers(0, 140 * HOUR, n)
    ts = np.where(rng.random(n) < 0.1, (ts // HOUR) * HOUR, ts)   # on an edge
    st = STATUS[rng.integers(0, len(STATUS), n)]
    ts_missing, st_missing = rng.random(n) < 0.05, rng.random(n) < 0.05
    words = rng.random((n, len(WORDS))) < 0.4

    def padded(a, fill):
        out = np.full(n_pad, fill, a.dtype)
        out[:n] = a
        return out
    numerics = {
        "@timestamp": NumericColumn(
            jnp.asarray(padded(np.where(ts_missing, 0, ts), 0)),
            jnp.asarray(padded(ts_missing, True)), "i64"),
        "status": NumericColumn(
            jnp.asarray(padded(np.where(st_missing, 0, st), 0)),
            jnp.asarray(padded(st_missing, True)), "i64")}
    lists = [np.flatnonzero(words[:, w]).astype(np.int32)
             for w in range(len(WORDS))]
    lens = np.array([len(x) for x in lists], np.int32)
    doc_ids = np.concatenate(lists) if n else np.empty(0, np.int32)
    p_pad = next_pow2(len(doc_ids))
    text = {"request": TextFieldIndex(
        terms={w: i for i, w in enumerate(WORDS)},
        term_starts=(np.cumsum(lens) - lens).astype(np.int32),
        term_lens=lens, doc_ids=jnp.asarray(padded_to(doc_ids, p_pad)),
        tf=None, doc_len=None, dl=None, sum_dl=0.0,
        n_postings=len(doc_ids), max_df=int(lens.max(initial=0)),
        doc_ids_host=doc_ids)}
    live = np.zeros(n_pad, bool)
    live[:n] = True
    seg = Segment(seg_id=seg_id, n_docs=n, n_pad=n_pad, text=text,
                  keywords={}, numerics=numerics, vectors={}, stored=[],
                  ids=[], types=[], id_to_local={}, live_host=live)
    return seg, {"ts": ts, "st": st, "ts_missing": ts_missing,
                 "st_missing": st_missing, "words": words, "seg": seg}


def padded_to(a, size):
    out = np.zeros(size, a.dtype)
    out[:len(a)] = a
    return out


def _same_buffer(a, b) -> bool:
    """Two views of one block on one chip, not a copy."""
    return a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()


def make_shards(layout, seed):
    rng = np.random.default_rng(seed)
    shards, host = [], []
    for si, sizes in enumerate(layout):
        made = [make_segment(si * 100 + i, n, rng)
                for i, n in enumerate(sizes)]
        shards.append([seg for seg, _ in made])
        host.append([h for _, h in made])
    return shards, host


@pytest.fixture(scope="module")
def corpus():
    return make_shards(LAYOUT, seed=31)


# -- rows and the oracle ------------------------------------------------------

AGGS = {"hist": {"per_hour": {"date_histogram": {"field": "@timestamp",
                                                 "interval": "hour"}}},
        "terms": {"by_status": {"terms": {"field": "status", "size": 2}}}}


def rows_of(kind, n, seed):
    """`n` rows of one shape with fresh ranges, words and statuses.
    `terms` asks for 2 buckets, so a shard reports 3 x 2 + 10 = 16 of its
    keys at most: the truncation is a shard's, whatever the chips."""
    rng = np.random.default_rng(seed)
    agg = parse_aggs(AGGS[kind])[0] if kind in AGGS else None
    out = []
    for _ in range(n):
        lo = BASE + int(rng.integers(-12 * HOUR, 100 * HOUR))
        hi = lo + int(rng.integers(HOUR // 2, 7 * 24 * HOUR)) - 1
        if kind == "hist":
            out.append(panels.PanelRow("hist", "@timestamp", lo, hi, agg=agg,
                                       interval=HOUR))
        elif kind == "terms":
            out.append(panels.PanelRow(
                "terms", "@timestamp", lo, hi, match_field="request",
                match_term=WORDS[int(rng.integers(0, len(WORDS)))], agg=agg))
        else:
            out.append(panels.PanelRow(
                "count", "@timestamp", lo, hi, term_field="status",
                term_value=int(STATUS[int(rng.integers(0, len(STATUS)))])))
    return out


def oracle(row, host):
    """(total, per shard {key: count}) by numpy over the host's columns."""
    total, per_shard = 0, []
    for segments in host:
        counts = {}
        for h in segments:
            n = len(h["ts"])
            live = h["seg"].live_host[:n]
            mask = live & ~h["ts_missing"] & (h["ts"] >= row.lo) \
                & (h["ts"] <= row.hi)
            if row.kind == "count":
                mask &= ~h["st_missing"] & (h["st"] == row.term_value)
            if row.kind == "terms":
                mask &= h["words"][:, WORDS.index(row.match_term)]
                keys = h["st"][mask & ~h["st_missing"]]
            else:
                keys = (h["ts"][mask] // HOUR) * HOUR
            total += int(mask.sum())
            for k, c in zip(*np.unique(keys, return_counts=True)):
                counts[int(k)] = counts.get(int(k), 0) + int(c)
        per_shard.append(counts)
    return total, per_shard


def check(rows, view, host):
    totals, partials = panels.execute(rows, view)
    assert totals.shape == (len(rows),)
    for qi, row in enumerate(rows):
        total, per_shard = oracle(row, host)
        assert int(totals[qi]) == total
        if row.kind == "count":
            assert partials is None
        elif row.kind == "hist":
            merged = {}
            for counts in per_shard:
                for k, c in counts.items():
                    merged[k] = merged.get(k, 0) + c
            got, = partials[qi]
            assert {int(k): b["doc_count"] for k, b in
                    got[row.agg.name]["buckets"].items()} == merged
        else:           # a shard's own partial, truncated as a shard's
            assert len(partials[qi]) == len(host)
            for got, counts in zip(partials[qi], per_shard):
                assert got[row.agg.name] == \
                    panels.terms_partial_from_counts(row.agg, counts)
    return json.dumps([totals.tolist(), partials], sort_keys=True)


# -- (a) exact at every chip axis ------------------------------------------------

@pytest.mark.parametrize("kind", ["hist", "terms", "count"])
@pytest.mark.parametrize("chips", AXES)
def test_programs_equal_the_oracle_at_every_axis(corpus, chips, kind):
    shards, host = corpus
    view = panels.PanelView(shards, pool_of(chips))
    assert panels.servable(rows_of(kind, 1, 0), view)
    for q in (1, 3, 32):
        check(rows_of(kind, q, seed=q + len(kind)), view, host)


@pytest.mark.parametrize("kind", ["hist", "terms", "count"])
def test_the_answer_is_the_same_bytes_at_every_axis(corpus, kind):
    shards, host = corpus
    rows = rows_of(kind, 7, seed=77)
    answers = {chips: check(rows, panels.PanelView(shards, pool_of(chips)),
                            host) for chips in AXES}
    assert len(set(answers.values())) == 1


@pytest.mark.parametrize("chips", AXES)
def test_tombstones_after_placement_are_followed(chips):
    shards, host = make_shards(LAYOUT, seed=chips)
    view = panels.PanelView(shards, pool_of(chips))
    rows = {k: rows_of(k, 4, seed=9) for k in ("hist", "terms", "count")}
    for kind in rows:
        check(rows[kind], view, host)
    live0 = view.live()
    uploads0 = device_events_snapshot()
    # every third document of shard 0's third segment and of shard 4's
    # second dies; the chips of the other shards keep their blocks
    for seg in (shards[0][2], shards[4][1]):
        for i in range(0, seg.n_docs, 3):
            seg.delete_local(i)
    for kind in rows:
        check(rows[kind], view, host)
    assert device_events_snapshot()[0] == uploads0[0]       # no compile
    blocks0, blocks1 = view._blocks(live0), view._blocks(view.live())
    touched = {view.pool.home_of(0), view.pool.home_of(4)}
    for c in range(chips):
        assert _same_buffer(blocks0[c], blocks1[c]) == (c not in touched)


def test_a_segment_past_the_one_hot_block_counts_in_blocks():
    """A 131,072-row segment runs `_onehot_counts`' blocked scan inside
    the chip's body (two blocks of 65,536), as the benchmark's do."""
    shards, host = make_shards([[70_000], [900], [], [10], [2_000]], seed=5)
    view = panels.PanelView(shards, pool_of(4))
    assert view.n_pad == 131_072 and view.G == 2
    for kind in ("hist", "terms", "count"):
        check(rows_of(kind, 2, seed=3), view, host)


# -- (b) homes and placement ------------------------------------------------------

def test_five_shards_over_four_chips(corpus):
    shards, host = corpus
    pool = pool_of(4)
    assert [pool.home_of(s) for s in range(5)] == [0, 1, 2, 3, 0]
    # the general mesh lane pads the shard axis to 8 and declines here;
    # the panel lane's axis is the chips
    assert pool.mesh_for(5) is None
    assert pool.chip_mesh().shape == {"chip": 4}
    view = panels.PanelView(shards, pool)
    # chip 0: shards 0 and 4, the empty segment left out; G is the bucket
    assert [[si for si, _ in rows] for rows in view.rows] == \
        [[0, 0, 0, 0, 4, 4, 4], [1], [2, 2], []]
    assert view.G == 8 and view.n_pad == 512
    col, missing = view.column("@timestamp")
    assert col.shape == (4 * 8, 512) and col.dtype == jnp.int64
    for c, dev in enumerate(pool.devices):
        part, = [s for s in col.addressable_shards if s.device == dev]
        miss, = [s for s in missing.addressable_shards if s.device == dev]
        block, gone = np.asarray(part.data), np.asarray(miss.data)
        assert block.shape == (8, 512)
        for g in range(8):
            if g < len(view.rows[c]):
                seg = view.rows[c][g][1]
                own = np.asarray(seg.numerics["@timestamp"].vals)
                assert np.array_equal(block[g, :seg.n_pad], own)
                assert gone[g, seg.n_pad:].all()
            else:       # a padded segment: nothing, all missing
                assert not block[g].any() and gone[g].all()
    assert np.asarray(view.shard_of).reshape(4, 8)[0].tolist() == \
        [0, 0, 0, 0, 4, 4, 4, -1]


def test_eight_chips_hold_five_shards_and_three_hold_nothing(corpus):
    shards, host = corpus
    view = panels.PanelView(shards, pool_of(8))
    assert [len(rows) for rows in view.rows] == [4, 1, 2, 0, 3, 0, 0, 0]
    assert view.G == 4
    check(rows_of("terms", 3, seed=1), view, host)


def test_a_new_view_keeps_the_blocks_of_chips_whose_segments_stayed():
    shards, host = make_shards([[30, 20], [40], [10], [], [25]], seed=2)
    pool = pool_of(4)
    base = panels.PanelView(shards, pool)
    rows = {k: rows_of(k, 2, seed=4) for k in ("hist", "terms", "count")}
    for kind in rows:
        check(rows[kind], base, host)
    # a refresh gives shard 1 a second segment: G stays 3
    seg, h = make_segment(199, 15, np.random.default_rng(8))
    shards[1].append(seg)
    host[1].append(h)
    view = panels.PanelView(shards, pool, base=base)
    assert (view.G, view.n_pad) == (base.G, base.n_pad) == (3, 64)
    assert set(view._placed) == set(base._placed) - {("live",)}
    for key, arr in view._placed.items():
        old, new = base._blocks(base._placed[key]), view._blocks(arr)
        assert [_same_buffer(a, b) for a, b in zip(old, new)] == \
            [True, False, True, True], key
    for kind in rows:
        check(rows[kind], view, host)


def test_the_views_copy_is_the_fielddata_breakers(tmp_path):
    node = NodeService(str(tmp_path))
    try:
        node.create_index("logs", settings={"number_of_shards": 5},
                          mappings={"_doc": {"properties": {
                              "@timestamp": {"type": "date"}}}})
        for i in range(40):
            node.index_doc("logs", str(i), {"@timestamp": BASE + i * HOUR})
        node.refresh("logs")
        fielddata = node.breakers.breaker("fielddata")
        used0 = fielddata.used
        body = {"size": 0, "query": {"range": {"@timestamp": {
            "gte": BASE, "lt": BASE + 20 * HOUR}}}, "aggs": {"h": {
                "date_histogram": {"field": "@timestamp",
                                   "interval": "hour"}}}}
        assert node.search("logs", body)["hits"]["total"] == 20
        view = node.indices["logs"].panel_view(node._panel_pool())
        assert view.nbytes > 0 and fielddata.used - used0 == view.nbytes
        assert view.nbytes == sum(a.nbytes for a in view._placed.values())
        node.delete_index("logs")          # the view goes with its index
        assert view.nbytes == 0 and fielddata.used == 0
    finally:
        node.close()


# -- through a node: (b) the lane's name, (c) the closed set, (d) the lock ------------

MAPPING = {"_doc": {"properties": {
    "@timestamp": {"type": "date"}, "request": {"type": "string"},
    "status": {"type": "integer"}}}}


def body_of(kind, lo, hi, word="images", status=200):
    rng = {"range": {"@timestamp": {"gte": int(lo), "lt": int(hi)}}}
    if kind == "hist":
        return {"size": 0, "query": rng, "aggs": AGGS["hist"]}
    if kind == "terms":
        return {"size": 0, "query": {"bool": {
            "must": [{"match": {"request": word}}], "filter": [rng]}},
            "aggs": {"by_status": {"terms": {"field": "status",
                                             "size": 20}}}}
    return {"size": 0, "query": {"bool": {"filter": [
        rng, {"term": {"status": status}}]}}}


def fresh_body(rng):
    kind = str(rng.choice(["hist", "terms", "count"], p=[.5, .3, .2]))
    lo = BASE + int(rng.integers(-12 * HOUR, 120 * HOUR))
    hi = lo + int(rng.integers(HOUR, 7 * 24 * HOUR))
    return body_of(kind, lo, hi, WORDS[int(rng.integers(0, len(WORDS)))],
                   int(STATUS[int(rng.integers(0, len(STATUS)))]))


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    """Five shards, several segments a shard, every fifth document
    deleted; the node owns the suite's 8 devices until a test gives it a
    pool of its own."""
    node = NodeService(str(tmp_path_factory.mktemp("panels-mesh")))
    node.create_index("logs", settings={
        "number_of_shards": 5, "index.requests.cache.enable": False},
        mappings=MAPPING)
    rng = np.random.default_rng(41)
    for i in range(500):
        node.index_doc("logs", str(i), {
            "@timestamp": BASE + int(rng.integers(0, 140 * HOUR)),
            "request": " ".join(rng.choice(WORDS, 3, replace=False)),
            "status": int(STATUS[int(rng.integers(0, len(STATUS)))])})
        if i in (99, 180, 420):
            node.refresh("logs")
    node.refresh("logs")
    for i in range(0, 500, 5):
        node.delete_doc("logs", str(i))
    node.refresh("logs")
    yield node
    node.close()


def concurrently(node, bodies):
    out = [None] * len(bodies)

    def one(i):
        try:
            out[i] = node.search("logs", json.loads(json.dumps(bodies[i])))
        except Exception as e:  # noqa: BLE001 — the assertion shows it
            out[i] = e
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    return out


def _chosen():
    return {k.split(":")[0]: v for k, v in lane_decisions_snapshot().items()
            if k.endswith(":chosen")}


@pytest.mark.parametrize("chips", AXES)
def test_the_form_follows_the_chips_the_node_owns(node, monkeypatch, chips):
    """No setting: a pool of one chip runs the program as `panels`, more
    chips as `panels_mesh`, and the answers are the same bytes."""
    bodies = [fresh_body(np.random.default_rng(s)) for s in range(12)]
    want = [node.search("logs", json.loads(json.dumps(b))) for b in bodies]
    monkeypatch.setattr(node, "device_pool", pool_of(chips))
    chosen0 = _chosen()
    got = concurrently(node, bodies)
    for a, b in zip(got, want):
        assert isinstance(a, dict), a
        a.pop("took"), b.pop("took")
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    lane = "panels" if chips == 1 else "panels_mesh"
    moved = {k: v - chosen0.get(k, 0) for k, v in _chosen().items()
             if v != chosen0.get(k, 0)}
    assert moved == {lane: 12}
    view = node.indices["logs"].panel_view(node._panel_pool())
    assert view.n_chips == chips and view.n_shards == 5


def test_the_set_is_closed_over_fresh_bodies_and_a_refresh(node):
    """`program_set` is what `ensure_warm` compiles; after it 200 fresh
    bodies of the mix at Q 1-32 and one refresh that stays inside the
    warmed (row bucket, segments-a-chip bucket) compile nothing."""
    svc = node.indices["logs"]
    pool = DevicePool(jax.devices()[5:8], name="own")   # warmed by no test
    node.device_pool = pool
    try:
        view = svc.panel_view(pool)
        members = panels.program_set(view)
        assert {m[2:4] for m in members} == {(view.n_pad, view.G)}
        assert len(members) == 3 * (2 + len(list(
            panels._w_buckets(view.max_df("request")))))
        compiles0 = device_events_snapshot()[0]
        panels.ensure_warm(view)
        assert device_events_snapshot()[0] - compiles0 == len(members)
        compiles0 = device_events_snapshot()[0]
        rng = np.random.default_rng(200)
        sent, refreshed = 0, False
        while sent < 200:
            n = int(rng.choice([1, 1, 2, 3, 7, 16, 32, 40]))
            outs = concurrently(node, [fresh_body(rng) for _ in range(n)])
            assert all(isinstance(o, dict) for o in outs), outs
            sent += n
            if sent >= 100 and not refreshed:
                # over 3 chips shard 2 has its chip to itself: half the
                # rows of the fullest, so one more stays inside G
                rows0 = [len(r) for r in view.rows]
                assert rows0[2] < view.G
                node.index_doc("logs", "extra", {
                    "@timestamp": BASE + HOUR, "request": "get html gif",
                    "status": 200}, routing=_routing_to(2))
                node.refresh("logs")
                new = svc.panel_view(pool)
                assert new is not view and sum(
                    len(r) for r in new.rows) == sum(rows0) + 1
                assert new.signature() == view.signature()
                refreshed = True
        assert refreshed
        assert device_events_snapshot()[0] == compiles0
    finally:
        node.device_pool = None
        node.delete_doc("logs", "extra", routing=_routing_to(2))
        node.refresh("logs")


def _routing_to(shard: int) -> str:
    from elasticsearch_tpu.parallel.routing import shard_id
    return next(str(r) for r in range(1000)
                if shard_id("extra", 5, str(r)) == shard)


def test_leaders_of_three_shapes_dispatch_at_once_and_finish(node):
    """Three shapes have three batcher keys, so three leaders: each holds
    the pool's dispatch lock for its collective, and all finish."""
    node.search("logs", body_of("hist", BASE, BASE + HOUR))      # warm
    stats0 = exec_lock_stats()
    errors, done = [], []

    def loop(kind, seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(25):
                lo = BASE + int(rng.integers(0, 100 * HOUR))
                out = node.search("logs", body_of(
                    kind, lo, lo + 30 * HOUR,
                    WORDS[int(rng.integers(0, 7))]))
                assert out["_shards"]["failed"] == 0
            done.append(kind)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
    threads = [threading.Thread(target=loop, args=(kind, i), daemon=True)
               for i, kind in enumerate(["hist", "terms", "count"] * 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(90)                  # the test's own limit
    assert not any(t.is_alive() for t in threads), "a dispatch hangs"
    assert not errors and len(done) == 6
    stats = exec_lock_stats()
    assert stats["shared_acquisitions"] - stats0["shared_acquisitions"] > 0
