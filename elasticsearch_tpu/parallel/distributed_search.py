"""The SPMD distributed query phase: one compiled program replaces the
reference's scatter-gather network protocol.

Reference flow (SURVEY.md §3.2): coordinator fans per-shard RPCs
("indices:data/read/search[phase/query]"), each data node runs Lucene top-k,
coordinator merges via TopDocs.merge (SearchPhaseController.java:147,233).

TPU-native flow (this module): the whole fan-out/gather is ONE jitted
shard_map over a ("replica", "shard") mesh:

  1. DFS stats all-reduce — psum of per-shard df / doc_count / sum_dl over
     the "shard" axis gives exact global IDF (the reference's optional
     DFS_QUERY_THEN_FETCH phase, search/dfs/DfsPhase.java:57-81, made free:
     it's a tiny psum riding ICI, not an extra network round-trip).
  2. Per-shard batched BM25 via the sort-reduce kernel (ops/bm25_sparse —
     contiguous postings DMAs, no gather/scatter, no [Q, N] score matrix).
  3. Per-shard top-k keys tagged (shard << 32 | local).
  4. Cross-shard reduce — all_gather over "shard" + top_k, the collective
     analog of SearchPhaseController.sortDocs.

total_hits is a psum; max_score a pmax. Queries are sharded over "replica"
so R replica groups serve disjoint slices of the query batch concurrently —
the reference's replica load-balancing (§2.10.2) as an SPMD axis.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import bm25 as bm25_ops
from ..ops.bm25_sparse import bm25_topk_sparse
from .mesh import SHARD_AXIS, REPLICA_AXIS
from .packed import PackedIndex

K1_DEFAULT = 1.2
B_DEFAULT = 0.75


def _query_step(doc_ids, tf, dl, sum_dl, doc_counts,
                term_starts, term_lens, boosts, *, Wt: int, n_pad: int,
                k: int, k1: float, b: float):
    """Per-device block of the distributed query phase (runs under shard_map;
    leading shard axis of every block is 1 and squeezed here)."""
    doc_ids = doc_ids[0]          # i32[P]
    tf = tf[0]                    # f32[P]
    dl = dl[0]                    # f32[P]
    term_starts = term_starts[0]  # i32[Qb, T]
    term_lens = term_lens[0]      # i32[Qb, T]
    boosts = boosts[0]            # f32[Qb, T]

    # (1) DFS stats all-reduce: exact global IDF via psum over the shard axis
    df_global = lax.psum(term_lens, SHARD_AXIS)                 # i32[Qb, T]
    doc_count_g = lax.psum(doc_counts[0], SHARD_AXIS)           # i32
    sum_dl_g = lax.psum(sum_dl[0], SHARD_AXIS)                  # f32
    avgdl = sum_dl_g / jnp.maximum(doc_count_g.astype(jnp.float32), 1.0)
    weights = (bm25_ops.idf(df_global, doc_count_g) * (k1 + 1.0) * boosts
               ).astype(jnp.float32)

    # (2) per-shard sort-reduce BM25 top-k
    top, docs, hits = bm25_topk_sparse(
        doc_ids, tf, dl, term_starts, term_lens, weights,
        jnp.float32(k1), jnp.float32(b), avgdl,
        Wt=Wt, k=k, n_docs=n_pad)

    # (3) globally-addressable keys
    my_shard = lax.axis_index(SHARD_AXIS).astype(jnp.int64)
    keys = jnp.where(top > -jnp.inf,
                     (my_shard << 32) | docs.astype(jnp.int64),
                     jnp.int64(-1))

    # (4) cross-shard top-k reduce (SearchPhaseController.sortDocs as a
    # collective): all_gather candidate sets, reduce to global top-k
    g_scores = lax.all_gather(top, SHARD_AXIS)                  # [S, Qb, kk]
    g_keys = lax.all_gather(keys, SHARD_AXIS)
    S, Qb, kk = g_scores.shape
    g_scores = jnp.transpose(g_scores, (1, 0, 2)).reshape(Qb, S * kk)
    g_keys = jnp.transpose(g_keys, (1, 0, 2)).reshape(Qb, S * kk)
    out_scores, pos = lax.top_k(g_scores, min(k, S * kk))
    out_keys = jnp.take_along_axis(g_keys, pos, axis=-1)

    total = lax.psum(hits.astype(jnp.int64), SHARD_AXIS)
    max_score = lax.pmax(top[:, 0], SHARD_AXIS)
    return out_scores, out_keys, total, max_score


@dataclass
class DistributedSearcher:
    """Compiled distributed query phase over a packed index + mesh."""
    index: PackedIndex
    mesh: jax.sharding.Mesh

    def __post_init__(self):
        # jit caches by function identity — memoize compiled steps per
        # static config or every search would retrace + recompile.
        # A bounded common.cache.Cache, not a bare dict: step configs are
        # user-driven (k, Wt vary per request shape) and an unbounded memo
        # is a slow leak (tests/test_cache_lint.py tripwire)
        from ..common.cache import Cache
        self._step_cache = Cache("dist_steps", max_entries=64)

    def place(self):
        """Shard the packed index onto the mesh (one device_put per array;
        after this, queries run with zero host→device index traffic)."""
        from .mesh import index_sharding
        sh = index_sharding(self.mesh)
        self.index.live = jax.device_put(self.index.live, sh)
        self.index.doc_counts = jax.device_put(self.index.doc_counts, sh)
        for f in self.index.text.values():
            f.doc_ids = jax.device_put(f.doc_ids, sh)
            f.tf = jax.device_put(f.tf, sh)
            f.dl = jax.device_put(f.dl, sh)
            f.sum_dl = jax.device_put(f.sum_dl, sh)
        for v in (self.index.vectors or {}).values():
            v.vecs = jax.device_put(v.vecs, sh)
        return self

    def build_step(self, *, Wt: int, k: int,
                   k1: float = K1_DEFAULT, b: float = B_DEFAULT):
        """jit(shard_map) of the query step, memoized per static config."""
        key = (Wt, k, k1, b)
        cached = self._step_cache.get(key)
        if cached is not None:
            return cached
        n_pad = self.index.n_pad
        fn = functools.partial(_query_step, Wt=Wt, n_pad=n_pad, k=k,
                               k1=k1, b=b)
        shard_specs = P(SHARD_AXIS)
        query_specs = P(SHARD_AXIS, REPLICA_AXIS)
        out_specs = (P(REPLICA_AXIS), P(REPLICA_AXIS),
                     P(REPLICA_AXIS), P(REPLICA_AXIS))
        # check_vma off: the query batch is INTENTIONALLY different per replica
        mapped = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(shard_specs,) * 5 + (query_specs,) * 3,
            out_specs=out_specs, check_vma=False)
        from ..common.device_stats import instrument
        step = instrument("dist:query_step", jax.jit(mapped), key=key)
        self._step_cache.put(key, step, weight=1)
        return step

    def build_knn_step(self, *, k: int, metric: str = "cosine"):
        """Distributed exact kNN: per-shard MXU matmul top-k + the same
        all_gather cross-shard reduce as text search. One compiled program
        for the whole mesh."""
        key = ("knn", k, metric)
        cached = self._step_cache.get(key)
        if cached is not None:
            return cached

        def knn_step(vecs, live, qv, q_valid):
            from ..ops import knn as knn_ops
            vecs = vecs[0]            # [N, D]
            live_b = live[0]          # [N]
            sims = knn_ops._sim(qv, vecs, metric)
            sims = jnp.where(live_b[None, :], sims, -jnp.inf)
            # replica-padding rows are all-zero query vectors: cosine on
            # them divides 0 by ~0, and a NaN lane would poison the
            # top-k/keys math below — mask pad rows INSIDE the step so
            # they contribute -inf (no hits), not NaN
            sims = jnp.where(q_valid[:, None], sims, -jnp.inf)
            top, idx = lax.top_k(sims, k)
            my_shard = lax.axis_index(SHARD_AXIS).astype(jnp.int64)
            keys = jnp.where(top > -jnp.inf,
                             (my_shard << 32) | idx.astype(jnp.int64),
                             jnp.int64(-1))
            g_s = lax.all_gather(top, SHARD_AXIS)
            g_k = lax.all_gather(keys, SHARD_AXIS)
            S, Qb, kk = g_s.shape
            g_s = jnp.transpose(g_s, (1, 0, 2)).reshape(Qb, S * kk)
            g_k = jnp.transpose(g_k, (1, 0, 2)).reshape(Qb, S * kk)
            out_s, pos = lax.top_k(g_s, min(k, S * kk))
            return out_s, jnp.take_along_axis(g_k, pos, axis=-1)

        from ..common.device_stats import instrument
        step = instrument(
            "dist:knn_step",
            jax.jit(jax.shard_map(
                knn_step, mesh=self.mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(REPLICA_AXIS),
                          P(REPLICA_AXIS)),
                out_specs=(P(REPLICA_AXIS), P(REPLICA_AXIS)),
                check_vma=False)),
            key=key)
        self._step_cache.put(key, step, weight=1)
        return step

    def search_knn(self, field: str, query_vectors, *, k: int = 10,
                   metric: str = "cosine"):
        """-> (scores f32[Q,k], keys i64[Q,k])."""
        from ..common.metrics import current_profiler
        vf = self.index.vectors[field]
        n_rep = self.mesh.shape[REPLICA_AXIS]
        qv = np.asarray(query_vectors, np.float32)
        Q = qv.shape[0]
        q_pad = -(-Q // n_rep) * n_rep
        if q_pad != Q:
            qv = np.concatenate([qv, np.zeros((q_pad - Q, qv.shape[1]),
                                              np.float32)])
        q_valid = np.zeros((q_pad,), bool)
        q_valid[:Q] = True
        step = self.build_knn_step(k=k, metric=metric)
        prof = current_profiler()
        from ..common.metrics import note_h2d
        note_h2d(qv.nbytes)
        if prof is not None:
            with prof.phase("spmd_query"):
                scores, keys = step(vf.vecs, self.index.live,
                                    jnp.asarray(qv), jnp.asarray(q_valid))
                scores, keys = np.asarray(scores), np.asarray(keys)
            prof.note_dispatch()
            prof.note_d2h(scores.nbytes + keys.nbytes)
            return scores[:Q], keys[:Q]
        scores, keys = step(vf.vecs, self.index.live, jnp.asarray(qv),
                            jnp.asarray(q_valid))
        return np.asarray(scores)[:Q], np.asarray(keys)[:Q]

    def search_terms(self, field: str, queries: list[list[str]], *,
                     k: int = 10, boosts: np.ndarray | None = None,
                     k1: float = K1_DEFAULT, b: float = B_DEFAULT):
        """End-to-end: host query prep -> device SPMD step -> host results.

        Returns (scores f32[Q,k], keys i64[Q,k], total i64[Q], max f32[Q]).
        """
        fx = self.index.text[field]
        n_rep = self.mesh.shape[REPLICA_AXIS]
        Q = len(queries)
        q_pad = -(-Q // n_rep) * n_rep
        queries = queries + [[] for _ in range(q_pad - Q)]
        ts, tl = self.index.prepare_term_queries(field, queries)
        Wt = self.index.slot_budget(tl)
        if boosts is None:
            bsts = jnp.ones(ts.shape, jnp.float32)
        else:
            b_arr = np.ones((q_pad,) + boosts.shape[1:], np.float32)
            b_arr[:Q] = boosts
            bsts = jnp.broadcast_to(jnp.asarray(b_arr)[None], ts.shape)
        step = self.build_step(Wt=Wt, k=k, k1=k1, b=b)
        from ..common.metrics import current_profiler, note_h2d
        prof = current_profiler()
        # term tables + boosts are this request's host→device upload;
        # the SPMD program's result fetch is its device→host leg
        note_h2d(ts.nbytes + tl.nbytes + bsts.nbytes)
        if prof is not None:
            with prof.phase("spmd_query"):
                scores, keys, total, mx = step(
                    fx.doc_ids, fx.tf, fx.dl, fx.sum_dl,
                    self.index.doc_counts, ts, tl, bsts)
                scores, keys, total, mx = (np.asarray(scores),
                                           np.asarray(keys),
                                           np.asarray(total),
                                           np.asarray(mx))
            prof.note_dispatch()
            prof.note_d2h(scores.nbytes + keys.nbytes
                          + total.nbytes + mx.nbytes)
            return scores[:Q], keys[:Q], total[:Q], mx[:Q]
        scores, keys, total, mx = step(
            fx.doc_ids, fx.tf, fx.dl, fx.sum_dl, self.index.doc_counts,
            ts, tl, bsts)
        return (np.asarray(scores)[:Q], np.asarray(keys)[:Q],
                np.asarray(total)[:Q], np.asarray(mx)[:Q])
