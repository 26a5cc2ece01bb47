"""The packed program's blocked slot gather (ops/slot_gather.py, ISSUE 28):
the Pallas kernel, interpreted on the CPU, against the `vmap(dynamic_slice)`
form it replaces on the TPU — the copies alone, then the whole program.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.common import tracing
from elasticsearch_tpu.common.metrics import packed_gather_snapshot
from elasticsearch_tpu.node import NodeService
from elasticsearch_tpu.ops import bm25_sparse as K
from elasticsearch_tpu.ops.slot_gather import BLOCK, gather_slots
from elasticsearch_tpu.serving import packed_view as pv

CHUNK = pv.CHUNK
EDGES = (0, 1, 127, 128, 511, 512, 513, 1023)   # a start's offset in a tile


def _streams(p_pad: int, total_p: int, seed: int):
    """doc ids as `_pack_field` pads them, tf and dl of arbitrary bits (a
    copy must keep a NaN's payload too)."""
    rng = np.random.default_rng(seed)
    doc = np.full(p_pad, K.PACKED_PAD_DOC, np.int32)
    doc[:total_p] = rng.integers(0, 1 << 20, total_p)
    bits = rng.integers(-2**31, 2**31, (2, p_pad)).astype(np.int32)
    return doc, bits[0].view(np.float32), bits[1].view(np.float32)


def _sliced(starts, streams):
    return jax.vmap(lambda s: tuple(
        jax.lax.dynamic_slice(x, (s,), (CHUNK,)) for x in streams))(starts)


@pytest.mark.parametrize("Q,S,table", [
    (1, 32, "edges"), (1, 512, "edges"), (32, 32, "edges"),
    (32, 128, "edges"), (64, 4, "edges"), (32, 32, "empty")])
def test_kernel_copies_what_the_slices_copy(Q, S, table):
    p_pad, total_p = 8192, 8192 - CHUNK - 37
    rng = np.random.default_rng(Q * 1000 + S)
    starts = np.zeros(Q * S, np.int32)      # a length-0 slot starts at 0
    if table == "edges":
        starts[:] = rng.integers(0, total_p - CHUNK + 1, Q * S)
        starts[rng.random(Q * S) < 0.25] = 0            # the unused slots
        special = [1024 * a + o for a in (0, 3) for o in EDGES] + [
            total_p - CHUNK,            # a slot that ends at total_p
            p_pad - CHUNK - 1,          # the last start before P_pad - CHUNK
            p_pad - CHUNK]              # and the last legal one
        at = rng.choice(Q * S, len(special), replace=False)
        starts[at] = special
    streams = tuple(jnp.asarray(x) for x in _streams(p_pad, total_p, seed=S))
    got = gather_slots(jnp.asarray(starts), streams, chunk=CHUNK,
                       interpret=True)
    want = _sliced(jnp.asarray(starts), streams)
    for g, w in zip(got, want):
        assert g.shape == (Q * S, CHUNK) and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g).view(np.int32),
                                      np.asarray(w).view(np.int32))


def test_block_divides_every_bucket_of_the_lane():
    # Q_pad in {1, 32, 64, ...} x S a power of two with the floors of
    # `_build_slots`: the least Q_pad * S is 1 x 32, and 64 x 4 the least
    # of the large batches
    assert all((q * s) % BLOCK == 0
               for q, s in [(1, 32), (32, 32), (64, 4), (256, 128)])
    # the smallest view (P_pad 1,024) still holds a slot's rows and one more
    streams = tuple(jnp.asarray(x) for x in _streams(1024, 512, seed=1))
    starts = jnp.asarray(np.r_[[0, 1, 511, 512], np.zeros(28)], jnp.int32)
    for g, w in zip(gather_slots(starts, streams, chunk=CHUNK,
                                 interpret=True), _sliced(starts, streams)):
        np.testing.assert_array_equal(np.asarray(g).view(np.int32),
                                      np.asarray(w).view(np.int32))


# -- the chip's compiler, without the chip ------------------------------------
# The package turns 64-bit types on, and Mosaic lowers no 64-bit index: the
# interpreter takes what the chip's compiler refuses, so compile for it here.

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or it logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("N,P", [(256 * 256, 1 << 24), (32, 1 << 24),
                                 (32, 1024)])
def test_kernel_compiles_for_the_chip(one_chip, N, P):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert jax.config.jax_enable_x64        # as the serving process has it
    compiled = jax.jit(lambda s, d, t, l: gather_slots(
        s, (d, t, l), chunk=CHUNK)).lower(
        sd((N,), jnp.int32), sd((P,), jnp.int32), sd((P,), jnp.float32),
        sd((P,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the streams reach the kernel as they lie in memory: no copy of a
    # 1-D stream into rows of 128
    assert not [ln for ln in text.splitlines()
                if f"[{P // 128},128]" in ln and " copy(" in ln]


def test_filtered_program_compiles_for_the_chip_at_the_cells_shapes(
        one_chip, monkeypatch):
    """The largest program of `wiki.filtered-top1000`: 256 bodies x 256
    slots, k 1024, two filter columns, 2^25 postings. A gather of the
    columns at every candidate once came out as [2^25, 2] with its two
    columns padded to a tile's 128 lanes, 16 GB of the 15.75 the chip has
    (measured on one v5e chip), then as 2.79 GB blocks of it. The ranks
    now come as two more streams of the slot gather: the program's
    temporaries are the plain program's at the shape and the two rank
    blocks [Q, S, CHUNK] beside them, nothing padded."""
    from elasticsearch_tpu.serving.packed_view import (F_RANGE, F_TERM,
                                                       F_TERM_VALS)
    Q, S, P, NC = 256, 256, 1 << 25, 2

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # as on the chip: the Pallas gather compiled, not interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = (sd((Q, 3 * S + 1), jnp.int32), sd((P,), jnp.int32),
            sd((P,), jnp.float32), sd((P,), jnp.float32),
            *[sd((), jnp.float32)] * 4)
    static = {"S": S, "CHUNK": CHUNK, "R": 8, "k": 1024}
    compiled = K.bm25_serve_packed_filtered.jit.lower(
        *args, (sd((P,), jnp.int32),) * NC,
        *[sd((Q, F_RANGE), jnp.int32)] * 4,
        sd((Q, F_TERM), jnp.int32), sd((Q, F_TERM, F_TERM_VALS), jnp.int32),
        sd((Q, F_TERM), jnp.int32), **static,
        FR=F_RANGE, FT=F_TERM, TV=F_TERM_VALS).compile()
    plain = K.bm25_serve_packed.jit.lower(*args, **static).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "f64[" not in text
    rank_blocks = NC * Q * S * CHUNK * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        plain.memory_analysis().temp_size_in_bytes + rank_blocks


@pytest.mark.parametrize("kind,Q", [("hist", 4), ("count", 1), ("terms", 1)])
def test_panel_programs_compile_for_the_four_chips(topo, kind, Q):
    """The panel lane's collective programs (search/aggs/panels.py; this
    file holds every compile for a described chip, so that one worker loads
    the TPU's library) at the benchmark's shapes: 5 shards over the 2x2's
    chips, 4 segments a chip of 131,072 rows, which run `_onehot_counts`'
    blocked scan inside the `shard_map` body as no small CPU test does."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from elasticsearch_tpu.parallel.mesh import CHIP_AXIS
    from elasticsearch_tpu.search.aggs import panels
    mesh = Mesh(np.asarray(topo.devices), (CHIP_AXIS,))
    rows, n_pad = 4 * 4, 131_072

    def sd(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))
    col = sd((rows, n_pad), jnp.int64, CHIP_AXIS)
    flag = sd((rows, n_pad), jnp.bool_, CHIP_AXIS)
    bounds = sd((3, Q), jnp.int64)
    args, kw = {
        "hist": ((col, flag, flag, bounds), {}),
        "count": ((col, flag, flag, col, flag, bounds), {}),
        "terms": ((col, flag, flag, sd((rows, 1 << 20), jnp.int32, CHIP_AXIS),
                   sd((rows, n_pad), jnp.int32, CHIP_AXIS),
                   sd((rows,), jnp.int32, CHIP_AXIS), bounds,
                   sd((2, rows, Q), jnp.int32, None, CHIP_AXIS)),
                  {"W": 1 << 10})}[kind]
    compiled = panels._build_program(kind, mesh, 5).lower(
        *args, **kw).compile()
    text = compiled.as_text()
    assert "all-reduce" in text             # the counts meet on the chips
    assert f"jit_panel_{kind}" in text      # the roofline reads it by name


# -- the whole program ------------------------------------------------------

MAPPING = {"_doc": {"properties": {
    "body": {"type": "text"}, "tag": {"type": "keyword"},
    "price": {"type": "long"}}}}


def _index(node, lo, hi):
    for i in range(lo, hi):
        node.index_doc("g", str(i), {
            "body": f"common word{i % 7} filler" + " common" * (i % 3),
            "tag": f"t{i % 3}", "price": i})
    node.refresh("g")


@pytest.fixture(scope="module")
def dispatched(tmp_path_factory):
    """What `PackedIndexView.search` hands the two programs, and what they
    return, on a view of several segments that was extended by a refresh and
    whose tombstones `packed_fold_ids` folded."""
    node = NodeService(data_path=str(tmp_path_factory.mktemp("gather")))
    node.create_index("g", {"number_of_shards": 2}, mappings=MAPPING)
    plain = {"query": {"match": {"body": "common word3"}}, "size": 20}
    filtered = {"query": {"bool": {
        "must": [{"match": {"body": "common filler"}}],
        "filter": [{"term": {"tag": "t1"}},
                   {"range": {"price": {"gte": 5, "lte": 70}}}],
        "must_not": [{"range": {"price": {"gt": 30, "lt": 46}}}]}},
        "size": 20}
    _index(node, 0, 60)
    node.search("g", plain)
    node.search("g", filtered)
    _index(node, 60, 90)                        # -> _extend_field
    for doc in ("3", "10", "64"):
        node.delete_doc("g", doc)
    node.refresh("g")

    def folds():
        return tracing.AGGREGATE.stats().get("packed.live_fold",
                                             {"total": 0})["total"]

    folded = folds()
    calls = {}
    real = {"plain": pv.bm25_serve_packed,
            "filtered": pv.bm25_serve_packed_filtered}

    def recorder(name):
        def call(*args, **static):
            out = real[name](*args, **static)
            calls[name] = (args, static, np.asarray(out))
            return out
        return call

    pv.bm25_serve_packed = recorder("plain")
    pv.bm25_serve_packed_filtered = recorder("filtered")
    try:
        before = packed_gather_snapshot()
        hits = {"plain": node.search("g", plain),
                "filtered": node.search("g", filtered)}
        after = packed_gather_snapshot()
    finally:
        pv.bm25_serve_packed = real["plain"]
        pv.bm25_serve_packed_filtered = real["filtered"]
    view = node.indices["g"].packed_view()
    assert view.extended_from_base and len(view.entries) >= 4
    # the old segments' tombstones as the field is extended, then the
    # appended segment's: both by list
    assert folds() == folded + 2
    assert hits["plain"]["hits"]["total"] == 87
    assert 0 < hits["filtered"]["hits"]["total"] < 30
    calls["columns"] = {f: c.vals for f, c in view._filter_cols.items()}
    yield calls, before, after
    node.close()


@pytest.mark.parametrize("program", ["plain", "filtered"])
def test_program_with_the_kernel_returns_the_same_table(dispatched, program):
    calls, _, _ = dispatched
    args, static, want = calls[program]
    static, filters, ranks = dict(static), None, ()
    if program == "filtered":
        args, ranks, filters = args[:8], args[8], args[9:] + tuple(
            static.pop(n) for n in ("FR", "FT", "TV"))
        assert len(ranks) == 2                      # the two columns
    assert K.packed_gather_form() == "sliced"       # what `want` ran
    got = jax.jit(functools.partial(
        K._serve_packed_impl, **static, filters=filters,
        gather="blocked"))(*args, ranks=ranks)
    assert got.shape == want.shape == (args[0].shape[0], 2 * static["k"] + 1)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (want[:, -1] > 0).any()                  # it found something


def _per_document_form(packed_q, doc_ids, tf, dl, k1, b, avgdl, const, cols,
                       fr_col, fr_lo, fr_hi, fr_neg, ft_col, ft_targets,
                       ft_neg, *, S, R, k, FR, FT, TV):
    """The filtered program as it stood before its ranks rode the postings:
    the plain program's phases, then every column `cols` i32[NC, Npad]
    gathered at each sorted candidate's document and compared at the ends
    of the runs. The reference of the posting-aligned form."""
    Q, W, PAD = packed_q.shape[0], S * CHUNK, jnp.int32(K.PACKED_PAD_DOC)
    lens, min_match = packed_q[:, S:2 * S], packed_q[:, 3 * S]
    weights = jax.lax.bitcast_convert_type(packed_q[:, 2 * S:3 * S],
                                           jnp.float32)
    d, t, l = jax.vmap(jax.vmap(lambda s: tuple(
        jax.lax.dynamic_slice(x, (s,), (CHUNK,))
        for x in (doc_ids, tf, dl))))(packed_q[:, :S])
    valid = jnp.arange(CHUNK, dtype=jnp.int32) < lens[:, :, None]
    d = jnp.where(valid, d, PAD)
    valid = valid & (d != PAD)
    impact = t / (t + k1 * (1.0 - b + b * l / avgdl))
    contrib = jnp.where(valid, weights[:, :, None] * impact, 0.0)
    d, total, count = jax.lax.sort(
        (d.reshape(Q, W), contrib.reshape(Q, W).astype(jnp.float32),
         valid.astype(jnp.float32).reshape(Q, W)), dimension=1, num_keys=1)
    contrib, cnt = total, count
    for j in range(1, R):
        same = (d == jnp.roll(d, j, axis=1)).at[:, :j].set(False)
        total = total + jnp.where(same, jnp.roll(contrib, j, axis=1), 0.0)
        count = count + jnp.where(same, jnp.roll(cnt, j, axis=1), 0.0)
    ends = jnp.concatenate([d[:, :-1] != d[:, 1:], jnp.ones((Q, 1), bool)],
                           axis=1) & (d != PAD)
    keep = ends & (count >= min_match[:, None].astype(jnp.float32))
    vals = cols.take(d, axis=1, mode="clip")            # [NC, Q, W]

    def slot(code, match, neg):
        v = jnp.take_along_axis(vals, jnp.maximum(code, 0)[None, :, None],
                                axis=0)[0]
        m = jnp.where((code == -2)[:, None], False, match(v))
        m = jnp.where((neg > 0)[:, None], ~m, m)
        return jnp.where((code != -1)[:, None], m, True)

    for fi in range(FR):
        keep = keep & slot(fr_col[:, fi], lambda v: (
            v >= fr_lo[:, fi, None]) & (v <= fr_hi[:, fi, None]),
            fr_neg[:, fi])
    for fi in range(FT):
        keep = keep & slot(ft_col[:, fi], lambda v: (
            v[None] == ft_targets[:, fi, :, None].swapaxes(0, 1)).any(0),
            ft_neg[:, fi])
    top, pos = jax.lax.top_k(jnp.where(keep, total + const, -jnp.inf), k)
    docs = jnp.where(top > -jnp.inf, jnp.take_along_axis(d, pos, axis=1), PAD)
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(top, jnp.int32), docs,
         jnp.sum(keep, axis=1, dtype=jnp.int32)[:, None]], axis=1)


def test_filtered_table_equals_the_per_document_forms(dispatched):
    """On a view that a refresh extended, with tombstones folded into its
    postings and two columns (a keyword term, a long range): the ranks a
    batch hands the program are the columns read at each posting's document,
    and the table is the one the column gather at the candidates (the form
    before) gives, number for number."""
    calls, _, _ = dispatched
    args, static, want = calls["filtered"]
    ranks, doc_ids = args[8], np.asarray(args[1])
    cols = jnp.stack([calls["columns"][f] for f in ("tag", "price")])
    for stream, col in zip(ranks, np.asarray(cols)):
        np.testing.assert_array_equal(
            np.asarray(stream), col[np.minimum(doc_ids, len(col) - 1)])
    assert (doc_ids[:1 << 10] == K.PACKED_PAD_DOC).any()    # folded postings
    got = jax.jit(functools.partial(
        _per_document_form, **{n: v for n, v in static.items()
                               if n != "CHUNK"}))(*args[:8], cols, *args[9:])
    np.testing.assert_array_equal(np.asarray(got), want)
    assert 0 < want[:, -1].max() < 30           # the filter kept some, not all


def test_a_search_counts_one_dispatch_under_the_form_that_ran(dispatched):
    _, before, after = dispatched
    form = K.packed_gather_form()
    other = {"sliced": "blocked", "blocked": "sliced"}[form]
    assert after[form]["dispatches_total"] \
        == before[form]["dispatches_total"] + 2     # the two searches
    assert after[other] == before[other]


def test_program_span_carries_the_callers_attributes():
    with tracing.program_attrs(gather="blocked"):
        assert tracing.flight("ops:x").attrs == {"site": "ops:x",
                                                 "gather": "blocked"}
    assert tracing.flight("ops:x").attrs == {"site": "ops:x"}
