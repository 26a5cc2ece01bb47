"""Mesh-sharded serving data plane: the whole multi-shard query phase as
ONE shard_map program over the ("replica", "shard") mesh.

PR 4 collapsed a shard's per-segment round-trips into one stacked program
and one fetch, but the coordinator still merged per-shard results in host
Python over the thread-pool fan-out — S device fetches and a host-side
sort per multi-shard query. This module packs the shards' segment stacks
one level up onto a `[S_pad, G_pad, N_pad, ...]` mesh stack sharded over
the `"shard"` axis (parallel/mesh.index_sharding), runs the stacked DSL
executor of search/stacked.py as the shard_map query step, and fuses the
cross-shard reduce on device:

    per-shard stacked execution  (exactly search/stacked.py's math, per
                                  device block — bitwise-equal scores)
    per-shard stacked_reduce     (liveness gate, totals, row-max,
                                  per-segment top-k + in-shard merge)
    cross-shard reduce           (all_gather of shard-encoded candidates
                                  + one lax.top_k; psum totals; pmax max)

so a multi-shard unsorted query pays ZERO host-side per-shard merges and
ONE device fetch total. The `"replica"` axis carries query-batch
parallelism (queries shard over it; the index replicates over it — the
reference's R-copies-serve-reads model as a mesh axis). On hardware the
reduce rides ICI collectives; across pods XLA lowers to DCN (SURVEY §5.8:
collectives inside the host, RPC only between hosts).

Candidate order inside the merge is shard order, then in-shard merge
order — exactly the (primary, shard_idx, pos) tie order the host-side
controller.sort_docs produces, and `lax.top_k` keeps the earlier
candidate on equal scores, so results are bitwise-identical to the PR-4
concurrent fan-out.

Coverage: the typed stacked handlers (match/term/terms/range/exists/ids/
bool/constant_score/dis_max/boosting). Node types that would need the
per-segment generic fallback cannot run inside a collective program —
the plan declines and the coordinator falls back to the fan-out
(fallback ladder: mesh -> fan-out -> per-segment loop). Compiled
programs memoize on the plan signature (node structure + static scalars
+ pow2 work windows), so refresh->query cycles inside a bucket compile
nothing (tests/test_no_retrace.py).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field as dc_field

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..common import tracing
from ..common.cache import Cache
from ..index.segment import Segment, next_pow2
from ..ops import bm25
from ..search.query_dsl import (
    BoolNode, BoostingNode, ConstantScoreNode, DisMaxNode, ExistsNode,
    IdsNode, MatchAllNode, MatchNoneNode, MatchNode, Node, RangeNode,
    SegmentContext, TermFilterNode, _bisect, _coerce_to_column, _next_down,
    _next_up, _pow2_window,
)
from .mesh import (REPLICA_AXIS, SHARD_AXIS, SHARED_EXEC_LOCK, index_sharding,
                   make_mesh)

SEG_SHIFT = 32

# operand placement kinds: leading-axis sharding of host-prepared arrays
_OP_S = "s"        # [S_pad, ...]            -> P("shard")
_OP_SQ = "sq"      # [S_pad, G, Q, ...]      -> P("shard", None, "replica")
_OP_Q = "q"        # [Q, ...]                -> P("replica")
_OP_R = "r"        # scalar                  -> P() (replicated)

_MESH_LOCK = threading.Lock()
_MESH_MEMO: dict[tuple[int, int], jax.sharding.Mesh] = {}

# ONE collective program in flight per device POOL: two concurrent
# shard_map executions on the SAME devices can interleave their
# collective rendezvous across devices and deadlock (observed with two
# cluster nodes' host reduces overlapping in one test process). Nodes
# that OWN a disjoint device subset (parallel/mesh.DevicePool, ISSUE 19)
# dispatch under their pool's private lock and run concurrently;
# EXEC_LOCK is the legacy lock of the SHARED pool (all of jax.devices())
# — the fallback when no ownership is configured. All dispatch sites go
# through exec_guard() below, which also counts acquisitions/waits per
# path (the sampler ring's `exec_lock_waits` + the no-retrace tripwire).
EXEC_LOCK = SHARED_EXEC_LOCK

_EXEC_STATS_LOCK = threading.Lock()
_EXEC_STATS = {"shared_acquisitions": 0, "shared_waits": 0,
               "pool_acquisitions": 0, "pool_waits": 0}


@contextmanager
def exec_guard(pool=None):
    """Serialize device dispatch per pool. pool=None (or the shared
    pool) -> the legacy EXEC_LOCK; an owned DevicePool -> its private
    lock, uncontended across nodes by construction. A "wait" is counted
    only when the lock was not immediately available, and timed as the
    span `exec.lock_wait`."""
    lock = EXEC_LOCK if pool is None else pool.lock
    shared = lock is EXEC_LOCK
    if not lock.acquire(blocking=False):
        with _EXEC_STATS_LOCK:
            _EXEC_STATS["shared_waits" if shared else "pool_waits"] += 1
        with tracing.span("exec.lock_wait"):
            lock.acquire()
    with _EXEC_STATS_LOCK:
        _EXEC_STATS["shared_acquisitions" if shared
                    else "pool_acquisitions"] += 1
    try:
        yield
    finally:
        lock.release()


def exec_lock_stats() -> dict:
    with _EXEC_STATS_LOCK:
        return dict(_EXEC_STATS)


def reset_exec_lock_stats() -> None:
    with _EXEC_STATS_LOCK:
        for k in _EXEC_STATS:
            _EXEC_STATS[k] = 0


def _mesh_devkey(mesh) -> tuple:
    """Device-identity component of compiled-program cache keys: two
    nodes with different device subsets must never share a program."""
    return tuple(int(d.id) for d in mesh.devices.flat)

# compiled shard_map programs keyed by plan signature, bounded on the
# common Cache core
_PROGRAMS = Cache("mesh_programs", max_entries=256)

# score-materialization mode of the LAST mesh execution: "blockwise"
# (search/blockwise.py scan inside the shard_map body — peak score memory
# O(Q × block) per device) | "materialized" (full [G, Q, N] tensors).
# Coordinator counters and tests read it after execute().
last_block_mode: str | None = None


def mesh_for(n_shards: int, pool=None):
    """(mesh, s_pad, n_replicas) for an S-shard index, or None when this
    pool lacks the devices (fewer than S_pad): the caller falls back to
    the thread-pool fan-out — the cross-host/undersized topology path.
    pool=None means the legacy shared pool over all of jax.devices();
    an owned DevicePool restricts the mesh to that node's device subset."""
    if n_shards < 1:
        return None
    s_pad = next_pow2(n_shards, floor=1)
    devs = pool.devices if pool is not None else jax.devices()
    n_dev = len(devs)
    if n_dev < s_pad:
        return None
    r = max(n_dev // s_pad, 1)
    if pool is not None:
        got = pool.mesh_for(s_pad, n_replicas=r)
        if got is None:
            return None
        return got
    with _MESH_LOCK:
        mesh = _MESH_MEMO.get((r, s_pad))
        if mesh is None:
            mesh = make_mesh(n_shards=s_pad, n_replicas=r)
            _MESH_MEMO[(r, s_pad)] = mesh
    return mesh, s_pad, r


# ---------------------------------------------------------------------------
# The mesh stack: S shards' live segments as [S_pad, G_pad, ...] tensors
# ---------------------------------------------------------------------------

@dataclass
class MeshTextField:
    doc_ids: jax.Array               # i32[S_pad, G_pad, P_pad]
    tf: jax.Array                    # f32[S_pad, G_pad, P_pad]
    doc_len: jax.Array               # f32[S_pad, G_pad, N_pad]
    max_postings: int = 0


@dataclass
class MeshKeywordField:
    ords: jax.Array                  # i32[S_pad, G_pad, N_pad]


@dataclass
class MeshNumericField:
    vals: jax.Array                  # [S_pad, G_pad, N_pad] i64 | f64
    missing: jax.Array               # bool[S_pad, G_pad, N_pad]
    dtype: str


@dataclass
class MeshStack:
    """Immutable packed view of an index's shards on the device mesh.

    `shard_rows[s]` lists (original segment index, Segment) per stack row
    of shard s — the reduce encodes THAT index into doc keys, so the
    coordinator's fetch phase resolves keys against the shard's full
    segment list unchanged. Liveness is re-assembled (not rebuilt) when
    any segment's tombstone generation moves, exactly like SegmentStack."""
    shard_rows: tuple                # per shard: tuple[(orig_idx, Segment)]
    s_count: int
    s_pad: int
    g_pad: int
    n_pad: int
    mesh: jax.sharding.Mesh = None
    n_replicas: int = 1
    text: dict = dc_field(default_factory=dict)
    keywords: dict = dc_field(default_factory=dict)
    numerics: dict = dc_field(default_factory=dict)
    mixed: frozenset = frozenset()
    nbytes: int = 0
    seg_ids_dev: jax.Array | None = None     # i64[S_pad, G_pad]
    pool: object = None                      # owning DevicePool (None=shared)

    def __post_init__(self):
        self._live_key = None
        self._live_dev = None

    def live_stack(self) -> jax.Array:
        """bool[S_pad, G_pad, N_pad] root-doc liveness, padding all-False;
        cached on every segment's tombstone generation."""
        key = tuple(seg.live_gen for rows in self.shard_rows
                    for _i, seg in rows)
        if self._live_key != key or self._live_dev is None:
            arr = np.zeros((self.s_pad, self.g_pad, self.n_pad), bool)
            for si, rows in enumerate(self.shard_rows):
                for gi, (_i, seg) in enumerate(rows):
                    arr[si, gi, : seg.n_pad] = np.asarray(seg.root_live_host)
            self._live_dev = jax.device_put(arr, index_sharding(self.mesh))
            self._live_key = key
        return self._live_dev


def _mesh_field_kinds(segments):
    text, kw, num = set(), set(), set()
    for seg in segments:
        text.update(seg.text)
        kw.update(seg.keywords)
        num.update(seg.numerics)
    mixed = (text & kw) | (text & num) | (kw & num)
    return text, kw, num, mixed


def estimate_mesh_stack_bytes(per_shard_segments) -> int:
    """Device bytes a mesh stack over these shards will occupy — the
    pre-build fielddata-breaker charge. Mirrors build_mesh_stack()'s
    allocation arithmetic exactly (the SegmentStack convention)."""
    live_rows = [[s for s in segs if s.n_docs > 0]
                 for segs in per_shard_segments]
    all_live = [s for rows in live_rows for s in rows]
    if not all_live:
        return 0
    s_pad = next_pow2(len(per_shard_segments), floor=1)
    g_pad = next_pow2(max(len(r) for r in live_rows), floor=1)
    n_pad = max(s.n_pad for s in all_live)
    text, kw, num, _ = _mesh_field_kinds(all_live)
    total = s_pad * g_pad * n_pad + s_pad * g_pad * 8  # live mask + seg ids
    for f in text:
        p_pad = next_pow2(max((s.text[f].n_postings for s in all_live
                               if f in s.text), default=1), floor=8)
        total += s_pad * g_pad * (p_pad * 8 + n_pad * 4)
    total += len(kw) * s_pad * g_pad * n_pad * 4
    total += len(num) * s_pad * g_pad * n_pad * 9
    return total


def build_mesh_stack(per_shard_segments, mesh, s_pad: int,
                     n_replicas: int, pool=None) -> MeshStack | None:
    """Pack every shard's live segments into mesh-sharded tensors. The
    per-shard slice mirrors search/stacked.build_stack — same fills, same
    sentinels — so per-shard scores computed over a mesh block are
    bitwise-equal to the shard's own SegmentStack execution."""
    from ..common import tracing
    with tracing.span("mesh_stack_build",
                      shards=len(per_shard_segments)) as sp:
        out = _build_mesh_stack(per_shard_segments, mesh, s_pad, n_replicas)
        if sp is not None and out is not None:
            sp.attrs["bytes"] = out.nbytes
    if out is not None:
        out.pool = pool
    return out


def _build_mesh_stack(per_shard_segments, mesh, s_pad, n_replicas):
    shard_rows = tuple(
        tuple((i, s) for i, s in enumerate(segs) if s.n_docs > 0)
        for segs in per_shard_segments)
    all_live = [seg for rows in shard_rows for _i, seg in rows]
    if not all_live:
        return None
    g_pad = next_pow2(max(len(r) for r in shard_rows), floor=1)
    n_pad = max(s.n_pad for s in all_live)
    text_f, kw_f, num_f, mixed = _mesh_field_kinds(all_live)
    sharding = index_sharding(mesh)
    nbytes = s_pad * g_pad * n_pad + s_pad * g_pad * 8

    text: dict[str, MeshTextField] = {}
    for f in sorted(text_f):
        p_max = max((s.text[f].n_postings for s in all_live if f in s.text),
                    default=1)
        p_pad = next_pow2(p_max, floor=8)
        doc_ids = np.full((s_pad, g_pad, p_pad), n_pad, np.int32)
        tf = np.zeros((s_pad, g_pad, p_pad), np.float32)
        doc_len = np.ones((s_pad, g_pad, n_pad), np.float32)
        for si, rows in enumerate(shard_rows):
            for gi, (_i, seg) in enumerate(rows):
                fx = seg.text.get(f)
                if fx is None:
                    continue
                Pn = fx.n_postings
                if Pn:
                    src = fx.doc_ids_host if fx.doc_ids_host is not None \
                        else np.asarray(fx.doc_ids)[:Pn]
                    doc_ids[si, gi, :Pn] = src[:Pn]
                    tf[si, gi, :Pn] = np.asarray(fx.tf)[:Pn]
                doc_len[si, gi, : fx.doc_len.shape[0]] = \
                    np.asarray(fx.doc_len)
        text[f] = MeshTextField(
            doc_ids=jax.device_put(doc_ids, sharding),
            tf=jax.device_put(tf, sharding),
            doc_len=jax.device_put(doc_len, sharding),
            max_postings=p_max)
        nbytes += s_pad * g_pad * (p_pad * 8 + n_pad * 4)

    keywords: dict[str, MeshKeywordField] = {}
    for f in sorted(kw_f):
        ords = np.full((s_pad, g_pad, n_pad), -1, np.int32)
        for si, rows in enumerate(shard_rows):
            for gi, (_i, seg) in enumerate(rows):
                kc = seg.keywords.get(f)
                if kc is not None:
                    o = np.asarray(kc.ords)
                    ords[si, gi, : o.shape[0]] = o
        keywords[f] = MeshKeywordField(ords=jax.device_put(ords, sharding))
        nbytes += s_pad * g_pad * n_pad * 4

    numerics: dict[str, MeshNumericField] = {}
    for f in sorted(num_f):
        dtypes = {s.numerics[f].dtype for s in all_live if f in s.numerics}
        if len(dtypes) > 1:
            mixed = mixed | {f}          # cross-shard dtype conflict
            nbytes += s_pad * g_pad * n_pad * 9
            continue
        dt = dtypes.pop()
        vals = np.zeros((s_pad, g_pad, n_pad),
                        np.int64 if dt == "i64" else np.float64)
        missing = np.ones((s_pad, g_pad, n_pad), bool)
        for si, rows in enumerate(shard_rows):
            for gi, (_i, seg) in enumerate(rows):
                nc = seg.numerics.get(f)
                if nc is not None:
                    v = np.asarray(nc.vals)
                    vals[si, gi, : v.shape[0]] = v
                    missing[si, gi, : v.shape[0]] = np.asarray(nc.missing)
        numerics[f] = MeshNumericField(
            vals=jax.device_put(vals, sharding),
            missing=jax.device_put(missing, sharding), dtype=dt)
        nbytes += s_pad * g_pad * n_pad * 9

    seg_ids = np.zeros((s_pad, g_pad), np.int64)
    for si, rows in enumerate(shard_rows):
        for gi, (orig, _seg) in enumerate(rows):
            seg_ids[si, gi] = orig
    return MeshStack(
        shard_rows=shard_rows, s_count=len(per_shard_segments),
        s_pad=s_pad, g_pad=g_pad, n_pad=n_pad, mesh=mesh,
        n_replicas=n_replicas, text=text, keywords=keywords,
        numerics=numerics, mixed=frozenset(mixed), nbytes=nbytes,
        seg_ids_dev=jax.device_put(seg_ids, index_sharding(mesh)))


# ---------------------------------------------------------------------------
# Plan: host prep emits sharded operands; device closures mirror
# search/stacked.py's handlers over one shard's block
# ---------------------------------------------------------------------------

class _Unsupported(Exception):
    """Node/field shape the collective program cannot serve — the caller
    falls back to the concurrent fan-out (which can)."""


class _PlanCtx:
    def __init__(self, stack: MeshStack, n_queries: int, stats):
        self.stack = stack
        self.Q = n_queries
        self.stats = stats
        self.ops: list[tuple[np.ndarray, str]] = []
        self.fields: dict[str, str] = {}     # field -> kind, first-use order

    def emit(self, arr, kind: str) -> None:
        self.ops.append((np.asarray(arr), kind))

    def use_field(self, name: str, kind: str) -> None:
        self.fields.setdefault(name, kind)


class _DevCtx:
    """Per-device view inside the shard_map: one shard's blocks."""

    def __init__(self, fields: dict, ops: list, g_pad: int, n_pad: int,
                 n_queries: int):
        self.fields = fields
        self._ops = iter(ops)
        self.g_pad = g_pad
        self.n_pad = n_pad
        self.Q = n_queries

    def pop(self):
        return next(self._ops)

    def zeros(self):
        return jnp.zeros((self.g_pad, self.Q, self.n_pad), jnp.float32)

    def false(self):
        return jnp.zeros((self.g_pad, self.Q, self.n_pad), bool)

    def true(self):
        return jnp.ones((self.g_pad, self.Q, self.n_pad), bool)


def _match_host(node: MatchNode, pctx: _PlanCtx):
    """[S,G,Q,T] CSR pointers per (shard, segment) + the shared
    (stats-derived, segment-independent) idf weights — the mesh analog of
    stacked._match_host."""
    stack, Q = pctx.stack, pctx.Q
    T = max((len(t) for t in node.terms_per_query), default=1) or 1
    starts = np.zeros((stack.s_pad, stack.g_pad, Q, T), np.int32)
    lens = np.zeros((stack.s_pad, stack.g_pad, Q, T), np.int32)
    weights = np.zeros((Q, T), np.float32)
    n_terms = np.zeros((Q,), np.int32)
    for si, rows in enumerate(stack.shard_rows):
        for gi, (_i, seg) in enumerate(rows):
            s_, l_, w_, n_ = node._host_arrays(
                SegmentContext(seg, Q, pctx.stats))
            starts[si, gi], lens[si, gi] = s_, l_
            weights, n_terms = w_, n_
    return starts, lens, weights, n_terms


def _p_match(node: MatchNode, pctx: _PlanCtx):
    f = node.field_name
    if node.sim in ("lm_dirichlet", "lm_jm"):
        # LM similarities fall down the ladder to the fan-out/loop lanes
        raise _Unsupported(f"lm similarity [{node.sim}]")
    if f not in pctx.stack.text:
        return (("match_absent",), lambda d: (d.zeros(), d.false()))
    pctx.use_field(f, "text")
    starts, lens, weights, n_terms = _match_host(node, pctx)
    W = _pow2_window(lens)
    pctx.emit(starts, _OP_SQ)
    pctx.emit(lens, _OP_SQ)
    pctx.emit(weights, _OP_Q)
    sim, k1, b = node.sim, float(node.k1), float(node.b)
    msm_mode = node.operator == "and" or node.minimum_should_match > 1
    if msm_mode:
        need = n_terms if node.operator == "and" else np.broadcast_to(
            np.float32(max(node.minimum_should_match, 1)), (pctx.Q,))
        pctx.emit(np.asarray(need, np.float32), _OP_Q)
    if sim != "classic":
        pctx.emit(np.float32(pctx.stats.avgdl(f)), _OP_R)
    sig = ("match", f, sim, msm_mode, k1, b, W)

    def dev(d: _DevCtx):
        sf = d.fields[f]
        st, ln, w = d.pop(), d.pop(), d.pop()
        need_b = d.pop() if msm_mode else None
        if sim == "classic":
            def one(di, tfv, dl, st_, ln_):
                return bm25.classic_score_batch(
                    di, tfv, dl, st_, ln_, w, W=W, n_pad=d.n_pad)
            scores = jax.vmap(one)(sf.doc_ids, sf.tf, sf.doc_len, st, ln)
        else:
            avgdl = d.pop()
            def one(di, tfv, dl, st_, ln_):
                return bm25.bm25_score_batch(
                    di, tfv, dl, st_, ln_, w, jnp.float32(k1),
                    jnp.float32(b), avgdl.astype(jnp.float32),
                    W=W, n_pad=d.n_pad)
            scores = jax.vmap(one)(sf.doc_ids, sf.tf, sf.doc_len, st, ln)
        if msm_mode:
            ones_w = jnp.ones_like(w)
            def cnt(di, tfv, dl, st_, ln_):
                return bm25.bm25_score_batch(
                    di, jnp.ones_like(tfv), jnp.full_like(dl, 1.0),
                    st_, ln_, ones_w, jnp.float32(0.0), jnp.float32(0.0),
                    jnp.float32(1.0), W=W, n_pad=d.n_pad)
            counts = jax.vmap(cnt)(sf.doc_ids, sf.tf, sf.doc_len, st, ln)
            match = counts >= jnp.maximum(need_b.astype(jnp.float32),
                                          1.0)[None, :, None]
        else:
            match = scores > 0
        return jnp.where(match, scores, 0.0), match

    return sig, dev


def _pm_match(node: MatchNode, pctx: _PlanCtx):
    """Presence-only filter mask (the term_match_mask fast path)."""
    if node.operator == "and" or node.minimum_should_match > 1:
        sig, dev = _p_match(node, pctx)
        return (("m", sig)), (lambda d: dev(d)[1])
    f = node.field_name
    if f not in pctx.stack.text:
        return (("m_match_absent",), lambda d: d.false())
    pctx.use_field(f, "text")
    starts, lens, _, _ = _match_host(node, pctx)
    W = _pow2_window(lens)
    pctx.emit(starts, _OP_SQ)
    pctx.emit(lens, _OP_SQ)
    sig = ("m_match", f, W)

    def dev(d: _DevCtx):
        sf = d.fields[f]
        st, ln = d.pop(), d.pop()
        def one(di, st_, ln_):
            return bm25.term_match_mask(di, st_, ln_, W=W, n_pad=d.n_pad)
        return jax.vmap(one)(sf.doc_ids, st, ln)

    return sig, dev


def _p_term(node: TermFilterNode, pctx: _PlanCtx):
    stack, Q = pctx.stack, pctx.Q
    f = node.field_name
    if f in stack.mixed:
        raise _Unsupported(f"mixed field [{f}]")
    boost = float(node.boost)
    V = max((len(v) for v in node.values_per_query), default=1) or 1
    if f in stack.keywords:
        pctx.use_field(f, "keyword")
        targets = np.full((stack.s_pad, stack.g_pad, Q, V), -2, np.int64)
        for si, rows in enumerate(stack.shard_rows):
            for gi, (_i, seg) in enumerate(rows):
                kc = seg.keywords.get(f)
                if kc is None:
                    continue
                for qi, vals in enumerate(node.values_per_query):
                    for vi, v in enumerate(vals):
                        o = kc.ord_of(str(v))
                        if o >= 0:
                            targets[si, gi, qi, vi] = o
        pctx.emit(targets, _OP_SQ)

        def dev(d: _DevCtx):
            col = d.fields[f].ords.astype(jnp.int64)
            tg = d.pop()
            match = (col[:, None, :, None]
                     == tg[:, :, None, :]).any(axis=3)
            return jnp.where(match, jnp.float32(boost), 0.0), match
        return ("term_kw", f, boost), dev

    if f in stack.numerics:
        nf = stack.numerics[f]
        pctx.use_field(f, "numeric")
        if nf.dtype == "f64":
            tf64 = np.full((Q, V), np.nan)
            for qi, vals in enumerate(node.values_per_query):
                for vi, v in enumerate(vals):
                    tf64[qi, vi] = float(v)
            pctx.emit(tf64, _OP_Q)

            def dev(d: _DevCtx):
                num = d.fields[f]
                tq = d.pop()
                match = (num.vals[:, None, :, None]
                         == tq[None, :, None, :]).any(axis=3)
                match = match & ~num.missing[:, None, :]
                return jnp.where(match, boost, 0.0), match
            return ("term_f64", f, boost), dev
        targets = np.full((Q, V), np.iinfo(np.int64).min, np.int64)
        for qi, vals in enumerate(node.values_per_query):
            for vi, v in enumerate(vals):
                targets[qi, vi] = _coerce_to_column(v, nf)
        pctx.emit(targets, _OP_Q)

        def dev(d: _DevCtx):
            num = d.fields[f]
            tq = d.pop()
            match = (num.vals[:, None, :, None]
                     == tq[None, :, None, :]).any(axis=3)
            match = match & ~num.missing[:, None, :]
            return jnp.where(match, jnp.float32(boost), 0.0), match
        return ("term_i64", f, boost), dev

    if f in stack.text:
        sub = MatchNode(boost=node.boost, field_name=f,
                        terms_per_query=[[str(v) for v in vals]
                                         for vals in node.values_per_query])
        sig, dev = _p_match(sub, pctx)
        return ("term_text", sig), dev
    return (("term_absent",), lambda d: (d.zeros(), d.false()))


def _p_range(node: RangeNode, pctx: _PlanCtx):
    stack, Q = pctx.stack, pctx.Q
    f = node.field_name
    if f in stack.mixed:
        raise _Unsupported(f"mixed field [{f}]")
    boost = float(node.boost)
    if f in stack.numerics:
        nf = stack.numerics[f]
        pctx.use_field(f, "numeric")
        if nf.dtype == "i64":
            lo_fill, hi_fill = np.iinfo(np.int64).min, np.iinfo(np.int64).max
            dt = np.int64
        else:
            lo_fill, hi_fill = -np.inf, np.inf
            dt = np.float64
        los = np.full(Q, lo_fill, dt)
        his = np.full(Q, hi_fill, dt)
        for qi, (lo, hi, inc_lo, inc_hi) in enumerate(node.bounds_per_query):
            if lo is not None:
                los[qi] = lo if inc_lo else _next_up(lo, dt)
            if hi is not None:
                his[qi] = hi if inc_hi else _next_down(hi, dt)
        pctx.emit(los, _OP_Q)
        pctx.emit(his, _OP_Q)

        def dev(d: _DevCtx):
            num = d.fields[f]
            lo_b, hi_b = d.pop(), d.pop()
            match = (num.vals[:, None, :] >= lo_b[None, :, None]) \
                & (num.vals[:, None, :] <= hi_b[None, :, None]) \
                & ~num.missing[:, None, :]
            return jnp.where(match, jnp.float32(boost), 0.0), match
        return ("range_num", f, nf.dtype, boost), dev

    if f in stack.keywords:
        pctx.use_field(f, "keyword")
        los = np.zeros((stack.s_pad, stack.g_pad, Q), np.int32)
        his = np.full((stack.s_pad, stack.g_pad, Q), -1, np.int32)
        for si, rows in enumerate(stack.shard_rows):
            for gi, (_i, seg) in enumerate(rows):
                kc = seg.keywords.get(f)
                if kc is None:
                    continue
                his[si, gi, :] = len(kc.values) - 1
                for qi, (lo, hi, inc_lo, inc_hi) \
                        in enumerate(node.bounds_per_query):
                    if lo is not None:
                        i = _bisect(kc.values, str(lo), left=True)
                        if not inc_lo and i < len(kc.values) \
                                and kc.values[i] == str(lo):
                            i += 1
                        los[si, gi, qi] = i
                    if hi is not None:
                        i = _bisect(kc.values, str(hi), left=False) - 1
                        if not inc_hi and i >= 0 and kc.values[i] == str(hi):
                            i -= 1
                        his[si, gi, qi] = i
        pctx.emit(los, _OP_SQ)
        pctx.emit(his, _OP_SQ)

        def dev(d: _DevCtx):
            ords = d.fields[f].ords
            lo_b, hi_b = d.pop(), d.pop()
            match = (ords[:, None, :] >= lo_b[:, :, None]) \
                & (ords[:, None, :] <= hi_b[:, :, None]) \
                & (ords[:, None, :] >= 0)
            return jnp.where(match, jnp.float32(boost), 0.0), match
        return ("range_kw", f, boost), dev
    return (("range_absent",), lambda d: (d.zeros(), d.false()))


def _p_exists(node: ExistsNode, pctx: _PlanCtx):
    stack = pctx.stack
    f = node.field_name
    if f in stack.mixed:
        raise _Unsupported(f"mixed field [{f}]")
    boost = float(node.boost)
    if f in stack.numerics:
        pctx.use_field(f, "numeric")

        def dev(d: _DevCtx):
            num = d.fields[f]
            match = jnp.broadcast_to(~num.missing[:, None, :],
                                     (d.g_pad, d.Q, d.n_pad))
            return jnp.where(match, jnp.float32(boost), 0.0), match
        return ("exists_num", f, boost), dev
    if f in stack.keywords:
        pctx.use_field(f, "keyword")

        def dev(d: _DevCtx):
            kw = d.fields[f]
            match = jnp.broadcast_to((kw.ords >= 0)[:, None, :],
                                     (d.g_pad, d.Q, d.n_pad))
            return jnp.where(match, jnp.float32(boost), 0.0), match
        return ("exists_kw", f, boost), dev
    if f in stack.text:
        pctx.use_field(f, "text")
        starts = np.zeros((stack.s_pad, stack.g_pad, 1, 1), np.int32)
        lens = np.zeros((stack.s_pad, stack.g_pad, 1, 1), np.int32)
        for si, rows in enumerate(stack.shard_rows):
            for gi, (_i, seg) in enumerate(rows):
                fx = seg.text.get(f)
                if fx is not None:
                    lens[si, gi, 0, 0] = fx.n_postings
        W = max(8, 1 << (max(int(lens.max()), 1) - 1).bit_length())
        pctx.emit(starts, _OP_S)
        pctx.emit(lens, _OP_S)

        def dev(d: _DevCtx):
            sf = d.fields[f]
            st, ln = d.pop(), d.pop()
            def one(di, st_, ln_):
                return bm25.term_match_mask(di, st_, ln_, W=W, n_pad=d.n_pad)
            hits = jax.vmap(one)(sf.doc_ids, st, ln)
            match = jnp.broadcast_to(hits, (d.g_pad, d.Q, d.n_pad))
            return jnp.where(match, jnp.float32(boost), 0.0), match
        return ("exists_text", f, boost, W), dev
    return (("exists_absent",), lambda d: (d.zeros(), d.false()))


def _p_ids(node: IdsNode, pctx: _PlanCtx):
    stack, Q = pctx.stack, pctx.Q
    boost = float(node.boost)
    mask = np.zeros((stack.s_pad, stack.g_pad, Q, stack.n_pad), bool)
    for si, rows in enumerate(stack.shard_rows):
        for gi, (_i, seg) in enumerate(rows):
            for qi, ids in enumerate(node.ids_per_query):
                for i in ids:
                    local = seg.id_to_local.get(i)
                    if local is not None:
                        mask[si, gi, qi, local] = True
    pctx.emit(mask, _OP_SQ)

    def dev(d: _DevCtx):
        match = d.pop()
        return jnp.where(match, jnp.float32(boost), 0.0), match
    return ("ids", boost), dev


def _p_match_all(node: MatchAllNode, pctx: _PlanCtx):
    boost = float(node.boost)
    return ("match_all", boost), (lambda d: (
        jnp.full((d.g_pad, d.Q, d.n_pad), boost, jnp.float32), d.true()))


def _p_match_none(node: MatchNoneNode, pctx: _PlanCtx):
    return ("match_none",), (lambda d: (d.zeros(), d.false()))


# -- structural -------------------------------------------------------------

def _p_bool(node: BoolNode, pctx: _PlanCtx):
    boost = float(node.boost)
    any_positive = bool(node.must or node.filter)
    musts = [_plan_exec(n, pctx) for n in node.must]
    filters = [_plan_exec(n, pctx) for n in node.filter]
    msm = node.minimum_should_match
    if node.should and msm is None:
        msm = 0 if any_positive else 1
    shoulds = [_plan_exec(n, pctx) for n in node.should]
    must_nots = [_plan_exec(n, pctx) for n in node.must_not]
    sig = ("bool", boost, msm, tuple(s for s, _ in musts),
           tuple(s for s, _ in filters), tuple(s for s, _ in shoulds),
           tuple(s for s, _ in must_nots))

    def dev(d: _DevCtx):
        scores = d.zeros()
        match = d.true()
        for _s, fn in musts:
            s, m = fn(d)
            scores = scores + s
            match = match & m
        for _s, fn in filters:
            _, m = fn(d)
            match = match & m
        if shoulds:
            should_count = jnp.zeros((d.g_pad, d.Q, d.n_pad), jnp.int32)
            for _s, fn in shoulds:
                s, m = fn(d)
                scores = scores + jnp.where(m, s, 0.0)
                should_count = should_count + m.astype(jnp.int32)
            if msm > 0:
                match = match & (should_count >= msm)
        for _s, fn in must_nots:
            _, m = fn(d)
            match = match & ~m
        return jnp.where(match, scores * boost, 0.0), match

    return sig, dev


def _pm_bool(node: BoolNode, pctx: _PlanCtx):
    pos = [_plan_match(n, pctx) for n in node.must + node.filter]
    msm = node.minimum_should_match
    if node.should and msm is None:
        msm = 0 if (node.must or node.filter) else 1
    # mirror stacked._m_bool: msm==0 shoulds don't gate the mask and are
    # never evaluated in match context
    shoulds = [_plan_match(n, pctx) for n in node.should] \
        if node.should and msm is not None and msm >= 1 else []
    must_nots = [_plan_match(n, pctx) for n in node.must_not]
    sig = ("m_bool", msm, tuple(s for s, _ in pos),
           tuple(s for s, _ in shoulds), tuple(s for s, _ in must_nots))

    def dev(d: _DevCtx):
        match = d.true()
        for _s, fn in pos:
            match = match & fn(d)
        if shoulds:
            if msm == 1:
                any_should = d.false()
                for _s, fn in shoulds:
                    any_should = any_should | fn(d)
                match = match & any_should
            else:
                cnt = jnp.zeros((d.g_pad, d.Q, d.n_pad), jnp.int32)
                for _s, fn in shoulds:
                    cnt = cnt + fn(d).astype(jnp.int32)
                match = match & (cnt >= msm)
        for _s, fn in must_nots:
            match = match & ~fn(d)
        return match

    return sig, dev


def _p_const(node: ConstantScoreNode, pctx: _PlanCtx):
    boost = float(node.boost)
    sig, fn = _plan_match(node.inner, pctx)

    def dev(d: _DevCtx):
        m = fn(d)
        return jnp.where(m, jnp.float32(boost), 0.0), m
    return ("const", boost, sig), dev


def _pm_const(node: ConstantScoreNode, pctx: _PlanCtx):
    sig, fn = _plan_match(node.inner, pctx)
    return ("m_const", sig), fn


def _p_dis_max(node: DisMaxNode, pctx: _PlanCtx):
    boost = float(node.boost)
    tie = float(node.tie_breaker)
    subs = [_plan_exec(n, pctx) for n in node.queries]
    sig = ("dis_max", boost, tie, tuple(s for s, _ in subs))

    def dev(d: _DevCtx):
        best = d.zeros()
        total = d.zeros()
        match = d.false()
        for _s, fn in subs:
            s, m = fn(d)
            s = jnp.where(m, s, 0.0)
            best = jnp.maximum(best, s)
            total = total + s
            match = match | m
        scores = best + tie * (total - best)
        return jnp.where(match, scores * boost, 0.0), match
    return sig, dev


def _p_boosting(node: BoostingNode, pctx: _PlanCtx):
    boost = float(node.boost)
    nb = float(node.negative_boost)
    psig, pfn = _plan_exec(node.positive, pctx)
    nsig, nfn = _plan_exec(node.negative, pctx)
    sig = ("boosting", boost, nb, psig, nsig)

    def dev(d: _DevCtx):
        s, m = pfn(d)
        _, nm = nfn(d)
        s = jnp.where(nm, s * nb, s)
        return jnp.where(m, s * boost, 0.0), m
    return sig, dev


_P_EXEC = {
    MatchAllNode: _p_match_all,
    MatchNoneNode: _p_match_none,
    MatchNode: _p_match,
    TermFilterNode: _p_term,
    RangeNode: _p_range,
    ExistsNode: _p_exists,
    IdsNode: _p_ids,
    BoolNode: _p_bool,
    ConstantScoreNode: _p_const,
    DisMaxNode: _p_dis_max,
    BoostingNode: _p_boosting,
}

_P_MATCH = {
    MatchNode: _pm_match,
    BoolNode: _pm_bool,
    ConstantScoreNode: _pm_const,
}


def _plan_exec(node: Node, pctx: _PlanCtx):
    h = _P_EXEC.get(type(node))
    if h is None:
        raise _Unsupported(type(node).__name__)
    return h(node, pctx)


def _plan_match(node: Node, pctx: _PlanCtx):
    h = _P_MATCH.get(type(node))
    if h is None:
        sig, fn = _plan_exec(node, pctx)
        return ("xm", sig), (lambda d: fn(d)[1])
    return h(node, pctx)


def plan_types_supported(node: Node) -> bool:
    """Cheap pre-flight: every node in the tree has a typed mesh handler
    (field-shape checks happen at plan time). False -> fan-out."""
    t = type(node)
    if t in (BoolNode,):
        return all(plan_types_supported(n) for n in
                   node.must + node.filter + node.should + node.must_not)
    if t is ConstantScoreNode:
        return plan_types_supported(node.inner)
    if t is DisMaxNode:
        return all(plan_types_supported(n) for n in node.queries)
    if t is BoostingNode:
        return plan_types_supported(node.positive) \
            and plan_types_supported(node.negative)
    return t in _P_EXEC


# ---------------------------------------------------------------------------
# Program assembly: jit(shard_map(per-shard exec + fused collective reduce))
# ---------------------------------------------------------------------------

_FIELD_TENSORS = {"text": 3, "keyword": 1, "numeric": 2}


def _build_program(mesh, devfn, field_kinds: tuple, op_kinds: tuple,
                   k: int, n_queries: int, agg_devfns: tuple = ()):
    def step(live, seg_ids, *flat):
        live = live[0]                        # [G, N]
        seg_ids = seg_ids[0]                  # [G]
        fields = {}
        i = 0
        for name, kind in field_kinds:
            if kind == "text":
                fields[name] = MeshTextField(
                    doc_ids=flat[i][0], tf=flat[i + 1][0],
                    doc_len=flat[i + 2][0])
                i += 3
            elif kind == "keyword":
                fields[name] = MeshKeywordField(ords=flat[i][0])
                i += 1
            else:
                fields[name] = MeshNumericField(
                    vals=flat[i][0], missing=flat[i + 1][0], dtype="")
                i += 2
        ops = []
        for kind in op_kinds:
            blk = flat[i]
            i += 1
            ops.append(blk[0] if kind in (_OP_S, _OP_SQ) else blk)
        d = _DevCtx(fields, ops, live.shape[0], live.shape[1], n_queries)
        scores, match = devfn(d)

        # per-shard stacked reduce — stacked.stacked_reduce's math verbatim
        m = match & live[:, None, :]
        total = jnp.sum(m, axis=(0, 2), dtype=jnp.int64)          # [Qb]
        masked = jnp.where(m, scores, -jnp.inf)
        mx = masked.max(axis=(0, 2))                              # [Qb]
        kk = min(k, masked.shape[2])
        top, idx = lax.top_k(masked, kk)                          # [G,Qb,kk]
        keys = jnp.where(top > -jnp.inf,
                         (seg_ids[:, None, None] << SEG_SHIFT)
                         | idx.astype(jnp.int64),
                         jnp.int64(-1))
        Qb = masked.shape[1]
        cand_s = jnp.moveaxis(top, 0, 1).reshape(Qb, -1)
        cand_k = jnp.moveaxis(keys, 0, 1).reshape(Qb, -1)
        ks = min(k, cand_s.shape[1])
        shard_s, pos = lax.top_k(cand_s, ks)                      # [Qb, ks]
        shard_k = jnp.take_along_axis(cand_k, pos, axis=1)

        # cross-shard reduce: candidate blocks gather in shard order, so
        # stable top_k reproduces the host merge's (score, shard, pos)
        # tie order exactly (controller.sort_docs)
        g_s = lax.all_gather(shard_s, SHARD_AXIS)                 # [S,Qb,ks]
        g_k = lax.all_gather(shard_k, SHARD_AXIS)
        S = g_s.shape[0]
        g_s = jnp.transpose(g_s, (1, 0, 2)).reshape(Qb, S * ks)
        g_k = jnp.transpose(g_k, (1, 0, 2)).reshape(Qb, S * ks)
        out_s, pos2 = lax.top_k(g_s, min(k, S * ks))
        out_k = jnp.take_along_axis(g_k, pos2, axis=1)
        valid = out_s > -jnp.inf
        out_shard = jnp.where(valid, (pos2 // ks).astype(jnp.int32),
                              jnp.int32(-1))
        out_k = jnp.where(valid, out_k, jnp.int64(-1))
        # totals/max stay PER SHARD in the output (all_gather, not psum):
        # exact-int totals sum to the same value anywhere, and the cluster
        # host reduce decomposes the merged list back into per-shard wire
        # results — which need each shard's own total/max
        total_g = lax.all_gather(total, SHARD_AXIS)       # [S, Qb]
        mx_g = lax.all_gather(mx, SHARD_AXIS)             # [S, Qb]
        # agg partials ride the SAME program + fetch: counts reduce as
        # exact integers; f64 metric rows merge host-side in segment
        # order (parallel/mesh_aggs.py)
        agg_outs = tuple(lax.all_gather(fn(d, m), SHARD_AXIS)
                         for fn in agg_devfns)
        return (out_k, out_shard, out_s, total_g, mx_g) + agg_outs

    field_specs = []
    for _name, kind in field_kinds:
        field_specs.extend([P(SHARD_AXIS)] * _FIELD_TENSORS[kind])
    op_specs = []
    for kind in op_kinds:
        if kind == _OP_S:
            op_specs.append(P(SHARD_AXIS))
        elif kind == _OP_SQ:
            op_specs.append(P(SHARD_AXIS, None, REPLICA_AXIS))
        elif kind == _OP_Q:
            op_specs.append(P(REPLICA_AXIS))
        else:
            op_specs.append(P())
    in_specs = tuple([P(SHARD_AXIS), P(SHARD_AXIS)]
                     + field_specs + op_specs)
    out_specs = (P(REPLICA_AXIS),) * 3 \
        + (P(None, REPLICA_AXIS),) * (2 + len(agg_devfns))
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _build_sorted_program(mesh, devfn, field_kinds: tuple, op_kinds: tuple,
                          nk: int, k: int, n_queries: int,
                          agg_devfns: tuple = ()):
    """jit(shard_map(per-shard sorted reduce + cross-shard sorted merge)):
    the sorted analog of _build_program (ISSUE 17). Per shard it is
    stacked.stacked_sorted_reduce's math verbatim over the encoded key
    columns (search/sort_encode.py); the cross-shard tail all_gathers the
    candidate operands and re-sorts with the shard index wedged between
    the user keys and the dockey, reproducing the host merge's
    (compare_key, shard_idx, pos) tie order bitwise."""
    def step(live, seg_ids, sort_keys, cursor, *flat):
        live = live[0]                        # [G, N]
        seg_ids = seg_ids[0]                  # [G]
        sk = sort_keys[0]                     # [nk, G, N]
        fields = {}
        i = 0
        for name, kind in field_kinds:
            if kind == "text":
                fields[name] = MeshTextField(
                    doc_ids=flat[i][0], tf=flat[i + 1][0],
                    doc_len=flat[i + 2][0])
                i += 3
            elif kind == "keyword":
                fields[name] = MeshKeywordField(ords=flat[i][0])
                i += 1
            else:
                fields[name] = MeshNumericField(
                    vals=flat[i][0], missing=flat[i + 1][0], dtype="")
                i += 2
        ops = []
        for kind in op_kinds:
            blk = flat[i]
            i += 1
            ops.append(blk[0] if kind in (_OP_S, _OP_SQ) else blk)
        d = _DevCtx(fields, ops, live.shape[0], live.shape[1], n_queries)
        scores, match = devfn(d)

        # per-shard sorted reduce — stacked_sorted_reduce's math verbatim
        m = match & live[:, None, :]
        total = jnp.sum(m, axis=(0, 2), dtype=jnp.int64)          # [Qb]
        masked = jnp.where(m, scores, -jnp.inf)
        mx = masked.max(axis=(0, 2))                              # [Qb]
        after = jnp.zeros(sk.shape[1:], bool)
        for ki in range(nk - 1, -1, -1):
            after = (sk[ki] > cursor[ki]) \
                | ((sk[ki] == cursor[ki]) & after)
        sel = m & after[:, None, :]
        G, Qb, N = match.shape
        dockey = (seg_ids[:, None] << SEG_SHIFT) \
            | jnp.arange(N, dtype=jnp.int64)[None, :]

        def flat2(x):                         # [G,Qb,N] -> [Qb,G*N]
            return jnp.moveaxis(x, 0, 1).reshape(Qb, -1)

        cand = [flat2(jnp.where(sel, sk[0][:, None, :], jnp.inf))]
        cand += [flat2(jnp.broadcast_to(sk[ki][:, None, :], (G, Qb, N)))
                 for ki in range(1, nk)]
        cand.append(flat2(jnp.broadcast_to(dockey[:, None, :], (G, Qb, N))))
        cand.append(flat2(masked))
        ks = min(k, G * N)
        shard_out = [o[:, :ks]
                     for o in lax.sort(tuple(cand), num_keys=nk + 1)]

        # cross-shard sorted merge: gather candidates in shard order and
        # re-sort with the shard index as the post-keys tiebreak
        g = [lax.all_gather(o, SHARD_AXIS) for o in shard_out]  # [S,Qb,ks]
        S = g[0].shape[0]
        shard_col = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int64)[:, None, None], (S, Qb, ks))

        def gflat(x):                         # [S,Qb,ks] -> [Qb,S*ks]
            return jnp.transpose(x, (1, 0, 2)).reshape(Qb, S * ks)

        merged = lax.sort(
            tuple(gflat(o) for o in g[:nk])
            + (gflat(shard_col), gflat(g[nk]), gflat(g[nk + 1])),
            num_keys=nk + 2)
        kf = min(k, S * ks)
        valid = merged[0][:, :kf] < jnp.inf
        out_shard = jnp.where(valid, merged[nk][:, :kf].astype(jnp.int32),
                              jnp.int32(-1))
        out_k = jnp.where(valid, merged[nk + 1][:, :kf], jnp.int64(-1))
        out_s = jnp.where(valid, merged[nk + 2][:, :kf], -jnp.inf)
        total_g = lax.all_gather(total, SHARD_AXIS)       # [S, Qb]
        mx_g = lax.all_gather(mx, SHARD_AXIS)             # [S, Qb]
        agg_outs = tuple(lax.all_gather(fn(d, m), SHARD_AXIS)
                         for fn in agg_devfns)
        return (out_k, out_shard, out_s, total_g, mx_g) + agg_outs

    field_specs = []
    for _name, kind in field_kinds:
        field_specs.extend([P(SHARD_AXIS)] * _FIELD_TENSORS[kind])
    op_specs = []
    for kind in op_kinds:
        if kind == _OP_S:
            op_specs.append(P(SHARD_AXIS))
        elif kind == _OP_SQ:
            op_specs.append(P(SHARD_AXIS, None, REPLICA_AXIS))
        elif kind == _OP_Q:
            op_specs.append(P(REPLICA_AXIS))
        else:
            op_specs.append(P())
    in_specs = tuple([P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()]
                     + field_specs + op_specs)
    out_specs = (P(REPLICA_AXIS),) * 3 \
        + (P(None, REPLICA_AXIS),) * (2 + len(agg_devfns))
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def execute_sorted(stack: MeshStack, node: Node, stats, sort_specs,
                   search_after, *, k: int, Q: int = 1, agg_specs=None):
    """Sorted mesh execution (ISSUE 17): the whole multi-shard SORTED
    query phase as one collective program over the encoded key columns.

    -> (doc_keys i64[Q,k'], shard i32[Q,k'], scores [Q,k'],
    totals i64[S, Q], max f[S, Q], agg_partials) — execute()'s contract;
    hit order is the encoded-key order, bitwise-equal to the fan-out's
    host merge. None when the tree/aggs have no collective form OR the
    sort encoding declines (search/sort_encode.decline_reason — the
    caller's recorder carries the reason). May raise on execution
    failure; the caller degrades to the fan-out."""
    from ..common.device_stats import lane_decline
    from ..search import sort_encode

    global last_block_mode
    all_segs = [seg for rows in stack.shard_rows for _i, seg in rows]
    reason = sort_encode.decline_reason(sort_specs, all_segs)
    if reason is not None:
        lane_decline("coordinator.reduce", "mesh", reason)
        return None
    R = stack.n_replicas
    q_pad = -(-Q // R) * R
    last_block_mode = "materialized"
    pctx = _PlanCtx(stack, q_pad, stats)
    try:
        sig, devfn = _plan_exec(node, pctx)
    except _Unsupported:
        return None
    agg_plan = None
    if agg_specs:
        from . import mesh_aggs
        agg_plan = mesh_aggs.plan_aggs(agg_specs, pctx)
        if agg_plan is None:
            return None
    cols_dev, vocabs = sort_encode.mesh_key_cols(stack, sort_specs)
    cursor = sort_encode.encode_cursor(sort_specs, search_after, vocabs)
    nk = len(sort_specs)
    field_kinds = tuple(pctx.fields.items())
    op_kinds = tuple(kind for _a, kind in pctx.ops)
    key = ("sorted", _mesh_devkey(stack.mesh), stack.s_pad, R, q_pad, k,
           nk, sig, field_kinds,
           agg_plan.sig if agg_plan is not None else None)
    prog = _PROGRAMS.get(key)
    if prog is None:
        from ..common.device_stats import instrument
        prog = instrument(
            "mesh:sorted",
            _build_sorted_program(
                stack.mesh, devfn, field_kinds, op_kinds, nk, k,
                q_pad // R,
                agg_devfns=tuple(agg_plan.device_fns())
                if agg_plan is not None else ()),
            key=key)
        _PROGRAMS.put(key, prog, weight=1)
    args = []
    for name, kind in field_kinds:
        if kind == "text":
            ft = stack.text[name]
            args.extend([ft.doc_ids, ft.tf, ft.doc_len])
        elif kind == "keyword":
            args.append(stack.keywords[name].ords)
        else:
            nf = stack.numerics[name]
            args.extend([nf.vals, nf.missing])
    args.extend(a for a, _kind in pctx.ops)
    from ..common.metrics import (device_fetch, note_h2d,
                                  record_score_matrix_bytes)
    note_h2d(sum(int(a.nbytes) for a, _kind in pctx.ops) + cursor.nbytes)
    record_score_matrix_bytes(stack.g_pad * (q_pad // R) * stack.n_pad * 5)
    with exec_guard(stack.pool):
        outs = prog(stack.live_stack(), stack.seg_ids_dev, cols_dev,
                    jnp.asarray(cursor), *args)
        out_k, out_shard, out_s, total, mx = outs[:5]
        got = device_fetch({"keys": out_k, "shard": out_shard,
                            "scores": out_s, "total": total, "mx": mx,
                            "aggs": list(outs[5:])})
    agg_partials = None
    if agg_plan is not None:
        agg_partials = agg_plan.finish(
            [np.asarray(a)[: stack.s_count] for a in got["aggs"]],
            stack.s_count)
    return (np.asarray(got["keys"])[:Q], np.asarray(got["shard"])[:Q],
            np.asarray(got["scores"])[:Q],
            np.asarray(got["total"])[: stack.s_count, :Q],
            np.asarray(got["mx"])[: stack.s_count, :Q],
            agg_partials)


def _build_blockwise_program(mesh, bplan, *, k: int, n_queries: int,
                             kk: int, score_dtype):
    """jit(shard_map(blockwise scan + per-shard merge + cross-shard
    reduce)): the blockwise analog of _build_program. The scan body is
    search/blockwise.run_scan — the per-shard running top-k — and the
    merge tails are _build_program's verbatim, so results stay
    bitwise-identical to the materializing mesh program."""
    from ..search import blockwise as bw

    nf = bw.n_field_arrays(bplan.field_kinds)
    g_pad, block, nb = bplan.g_pad, bplan.block, bplan.nb

    def step(live, seg_ids, *flat):
        live = live[0]                        # [G, N]
        seg_ids = seg_ids[0]                  # [G]
        fields = bw.rebuild_fields(bplan.field_kinds,
                                   [a[0] for a in flat[:nf]])
        ops = []
        for kind, v in zip(bplan.op_kinds, flat[nf:]):
            ops.append(v[0] if kind in (bw.OP_X, bw.OP_SG, bw.OP_COL,
                                        bw.OP_COLQ) else v)
        top, gi, total, mx = bw.run_scan(
            bplan.devfn, fields, ops, bplan.op_kinds, live, g_pad=g_pad,
            block=block, nb=nb, n_queries=n_queries, kk=kk,
            score_dtype=score_dtype)

        # per-shard cross-segment merge — stacked_reduce's tail verbatim
        keys = jnp.where(top > -jnp.inf,
                         (seg_ids[:, None, None] << SEG_SHIFT)
                         | gi.astype(jnp.int64),
                         jnp.int64(-1))
        Qb = top.shape[1]
        cand_s = jnp.moveaxis(top, 0, 1).reshape(Qb, -1)
        cand_k = jnp.moveaxis(keys, 0, 1).reshape(Qb, -1)
        ks = min(k, cand_s.shape[1])
        shard_s, pos = lax.top_k(cand_s, ks)
        shard_k = jnp.take_along_axis(cand_k, pos, axis=1)

        # cross-shard reduce — _build_program's tail verbatim
        g_s = lax.all_gather(shard_s, SHARD_AXIS)
        g_k = lax.all_gather(shard_k, SHARD_AXIS)
        S = g_s.shape[0]
        g_s = jnp.transpose(g_s, (1, 0, 2)).reshape(Qb, S * ks)
        g_k = jnp.transpose(g_k, (1, 0, 2)).reshape(Qb, S * ks)
        out_s, pos2 = lax.top_k(g_s, min(k, S * ks))
        out_k = jnp.take_along_axis(g_k, pos2, axis=1)
        valid = out_s > -jnp.inf
        out_shard = jnp.where(valid, (pos2 // ks).astype(jnp.int32),
                              jnp.int32(-1))
        out_k = jnp.where(valid, out_k, jnp.int64(-1))
        total_g = lax.all_gather(total, SHARD_AXIS)       # [S, Qb]
        mx_g = lax.all_gather(mx, SHARD_AXIS)
        return out_k, out_shard, out_s, total_g, mx_g

    field_specs = []
    for _name, kind in bplan.field_kinds:
        field_specs.extend([P(SHARD_AXIS)] * _FIELD_TENSORS[kind])
    op_specs = []
    for kind in bplan.op_kinds:
        if kind == bw.OP_X:            # [S, NB, G, Q, ...]
            op_specs.append(P(SHARD_AXIS, None, None, REPLICA_AXIS))
        elif kind == bw.OP_SG:         # [S, G, Q, ...]
            op_specs.append(P(SHARD_AXIS, None, REPLICA_AXIS))
        elif kind == bw.OP_COLQ:       # [S, G, Q, N]
            op_specs.append(P(SHARD_AXIS, None, REPLICA_AXIS))
        elif kind == bw.OP_COL:        # [S, G, N]
            op_specs.append(P(SHARD_AXIS))
        elif kind == bw.OP_Q:          # [Q, ...]
            op_specs.append(P(REPLICA_AXIS))
        else:                          # scalar, replicated
            op_specs.append(P())
    in_specs = tuple([P(SHARD_AXIS), P(SHARD_AXIS)]
                     + field_specs + op_specs)
    out_specs = (P(REPLICA_AXIS),) * 3 + (P(None, REPLICA_AXIS),) * 2
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _try_blockwise(stack: MeshStack, node: Node, stats, *, k: int,
                   q_pad: int, R: int, block: int):
    """Plan + run the blockwise mesh program, or None when the tree/shape
    has no blockwise form (caller materializes). Output contract is
    execute()'s device 5-tuple."""
    from ..search import blockwise as bw

    env = bw.FieldEnv(set(stack.text), set(stack.keywords),
                      set(stack.numerics), stack.mixed,
                      lambda f: stack.numerics[f].dtype)
    shard_rows = tuple(tuple(seg for _i, seg in rows)
                       for rows in stack.shard_rows)
    bplan = bw.plan(node, shard_rows, env, g_pad=stack.g_pad,
                    n_pad=stack.n_pad, block=block, n_queries=q_pad,
                    stats=stats)
    if bplan is None:
        return None
    # dtype probe over SHAPES only (no device work): shard-local field
    # views are the mesh tensors minus their leading S axis
    probe_fields = {}
    for name, kind in bplan.field_kinds:
        if kind == "text":
            ft = stack.text[name]
            probe_fields[name] = bw.BTextField(
                jax.ShapeDtypeStruct(ft.doc_ids.shape[1:], ft.doc_ids.dtype),
                jax.ShapeDtypeStruct(ft.tf.shape[1:], ft.tf.dtype),
                jax.ShapeDtypeStruct(ft.doc_len.shape[1:], ft.doc_len.dtype))
        elif kind == "keyword":
            kw = stack.keywords[name]
            probe_fields[name] = bw.BKeywordField(
                jax.ShapeDtypeStruct(kw.ords.shape[1:], kw.ords.dtype))
        else:
            nf_ = stack.numerics[name]
            probe_fields[name] = bw.BNumericField(
                jax.ShapeDtypeStruct(nf_.vals.shape[1:], nf_.vals.dtype),
                jax.ShapeDtypeStruct(nf_.missing.shape[1:],
                                     nf_.missing.dtype))
    score_dtype = bw.probe_score_dtype(bplan, probe_fields)
    Qb = q_pad // R
    kk = min(k, stack.n_pad)
    key = ("bw", _mesh_devkey(stack.mesh), stack.s_pad, R, q_pad, k, kk,
           block, bplan.sig, bplan.field_kinds, bplan.op_kinds,
           str(score_dtype))
    prog = _PROGRAMS.get(key)
    if prog is None:
        from ..common.device_stats import instrument
        prog = instrument(
            "mesh:blockwise",
            _build_blockwise_program(stack.mesh, bplan, k=k,
                                     n_queries=Qb, kk=kk,
                                     score_dtype=score_dtype),
            key=key)
        _PROGRAMS.put(key, prog, weight=1)
    args = []
    for name, kind in bplan.field_kinds:
        if kind == "text":
            ft = stack.text[name]
            args.extend([ft.doc_ids, ft.tf, ft.doc_len])
        elif kind == "keyword":
            args.append(stack.keywords[name].ords)
        else:
            nf_ = stack.numerics[name]
            args.extend([nf_.vals, nf_.missing])
    args.extend(bplan.ops)
    from ..common.metrics import note_h2d, record_score_matrix_bytes
    note_h2d(sum(int(np.asarray(a).nbytes) for a in bplan.ops))
    record_score_matrix_bytes(stack.g_pad * Qb * block * 5)
    return prog(stack.live_stack(), stack.seg_ids_dev, *args)


def execute(stack: MeshStack, node: Node, stats, *, k: int, Q: int = 1,
            block_docs: int | None = None, agg_specs=None):
    """Run the parsed tree over the mesh stack as one program.

    -> (doc_keys i64[Q,k'], shard i32[Q,k'], scores [Q,k'],
    totals i64[S, Q], max f[S, Q], agg_partials) fetched in ONE device
    round-trip, or None when the plan has no collective form (caller falls
    back to the fan-out). Totals/max come back PER SHARD — callers sum/max
    them (exact: int totals, order-free max) or decompose them into
    per-shard wire results (the cluster host reduce). May raise on
    execution failure — the caller degrades to the fan-out there too.

    `agg_specs` (parsed AggSpec list) routes the agg tree through the same
    program (parallel/mesh_aggs.py); `agg_partials` is then one partial
    dict per shard — exactly the fan-out's per-shard collect output — or
    the whole call returns None when a spec has no mesh form.

    With `block_docs` set and the stack wider than one block, the DSL tree
    runs blockwise inside the shard_map body (search/blockwise.run_scan) —
    peak score memory O(Q × block) per device — before the same cross-shard
    collective reduce; trees without a blockwise plan (and agg bodies)
    materialize."""
    global last_block_mode
    R = stack.n_replicas
    q_pad = -(-Q // R) * R
    last_block_mode = "materialized"
    if not agg_specs and block_docs and stack.n_pad > block_docs \
            and stack.n_pad % block_docs == 0:
        with exec_guard(stack.pool):
            out_d = _try_blockwise(stack, node, stats, k=k, q_pad=q_pad,
                                   R=R, block=block_docs)
            if out_d is not None:
                from ..common.metrics import device_fetch
                out_k, out_shard, out_s, total, mx = out_d
                got = device_fetch({"keys": out_k, "shard": out_shard,
                                    "scores": out_s, "total": total,
                                    "mx": mx})
        if out_d is not None:
            last_block_mode = "blockwise"
            return (np.asarray(got["keys"])[:Q],
                    np.asarray(got["shard"])[:Q],
                    np.asarray(got["scores"])[:Q],
                    np.asarray(got["total"])[: stack.s_count, :Q],
                    np.asarray(got["mx"])[: stack.s_count, :Q],
                    None)
    pctx = _PlanCtx(stack, q_pad, stats)
    try:
        sig, devfn = _plan_exec(node, pctx)
    except _Unsupported:
        return None
    agg_plan = None
    if agg_specs:
        from . import mesh_aggs
        agg_plan = mesh_aggs.plan_aggs(agg_specs, pctx)
        if agg_plan is None:
            return None       # some agg has no mesh form -> fan-out
    field_kinds = tuple(pctx.fields.items())
    op_kinds = tuple(kind for _a, kind in pctx.ops)
    key = (_mesh_devkey(stack.mesh), stack.s_pad, R, q_pad, k, sig,
           field_kinds,
           agg_plan.sig if agg_plan is not None else None)
    prog = _PROGRAMS.get(key)
    if prog is None:
        from ..common.device_stats import instrument
        prog = instrument(
            "mesh:materialized",
            _build_program(
                stack.mesh, devfn, field_kinds, op_kinds, k, q_pad // R,
                agg_devfns=tuple(agg_plan.device_fns())
                if agg_plan is not None else ()),
            key=key)
        _PROGRAMS.put(key, prog, weight=1)
    args = []
    for name, kind in field_kinds:
        if kind == "text":
            ft = stack.text[name]
            args.extend([ft.doc_ids, ft.tf, ft.doc_len])
        elif kind == "keyword":
            args.append(stack.keywords[name].ords)
        else:
            nf = stack.numerics[name]
            args.extend([nf.vals, nf.missing])
    args.extend(a for a, _kind in pctx.ops)
    from ..common.metrics import (device_fetch, note_h2d,
                                  record_score_matrix_bytes)
    note_h2d(sum(int(a.nbytes) for a, _kind in pctx.ops))
    record_score_matrix_bytes(stack.g_pad * (q_pad // R) * stack.n_pad * 5)
    with exec_guard(stack.pool):
        outs = prog(stack.live_stack(), stack.seg_ids_dev, *args)
        out_k, out_shard, out_s, total, mx = outs[:5]
        # the whole multi-shard query phase — top-k reduce AND agg
        # partials — comes down in this ONE fetch
        got = device_fetch({"keys": out_k, "shard": out_shard,
                            "scores": out_s, "total": total, "mx": mx,
                            "aggs": list(outs[5:])})
    agg_partials = None
    if agg_plan is not None:
        agg_partials = agg_plan.finish(
            [np.asarray(a)[: stack.s_count] for a in got["aggs"]],
            stack.s_count)
    return (np.asarray(got["keys"])[:Q], np.asarray(got["shard"])[:Q],
            np.asarray(got["scores"])[:Q],
            np.asarray(got["total"])[: stack.s_count, :Q],
            np.asarray(got["mx"])[: stack.s_count, :Q],
            agg_partials)


def program_cache_stats() -> dict:
    return _PROGRAMS.stats()
