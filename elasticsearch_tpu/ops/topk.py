"""Top-k selection and merge — the tensor analog of Lucene's priority queues
and the coordinator's TopDocs.merge.

ref: /root/reference/src/main/java/org/elasticsearch/search/controller/SearchPhaseController.java:147,233
(coordinator-side merge of per-shard top-k) — here both the per-segment top-k
and the cross-segment/cross-shard merge are `lax.top_k` programs so they can
run on device and, across chips, over ICI collectives
(see parallel/mesh_exec.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k",))
def topk_scores(scores: jax.Array, mask: jax.Array, *, k: int):
    """Per-query top-k over one segment.

    scores: f32[Q, N]; mask: bool[Q, N] (live & filter & match).
    Returns (top_scores f32[Q,k], top_idx i32[Q,k]); masked-out entries come
    back with score -inf.
    """
    masked = jnp.where(mask, scores, -jnp.inf)
    top, idx = jax.lax.top_k(masked, k)
    return top, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(scores_a, ids_a, scores_b, ids_b, *, k: int):
    """Merge two per-query candidate sets (running top-k across segments)."""
    s = jnp.concatenate([scores_a, scores_b], axis=-1)
    i = jnp.concatenate([ids_a, ids_b], axis=-1)
    top, pos = jax.lax.top_k(s, k)
    return top, jnp.take_along_axis(i, pos, axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_concat(all_scores: jax.Array, all_ids: jax.Array, *, k: int):
    """Top-k over concatenated candidates [Q, M] -> ([Q,k], [Q,k])."""
    top, pos = jax.lax.top_k(all_scores, k)
    return top, jnp.take_along_axis(all_ids, pos, axis=-1)


def merge_running_topk(top_s: jax.Array, top_i: jax.Array,
                       blk_s: jax.Array, blk_i: jax.Array, *, k: int):
    """One step of a running top-k: merge the carried winner list with a
    new block's candidates (search/blockwise.py scan body). NOT jitted —
    traces inside the blockwise scan. Candidate order [carry, block] plus
    lax.top_k's keep-earlier-on-ties makes the running merge reproduce a
    full-axis top_k's exact tie order when blocks arrive in doc order."""
    s = jnp.concatenate([top_s, blk_s], axis=-1)
    i = jnp.concatenate([top_i, blk_i], axis=-1)
    top, pos = jax.lax.top_k(s, k)
    return top, jnp.take_along_axis(i, pos, axis=-1)


@jax.jit
def count_matches(mask: jax.Array) -> jax.Array:
    """total_hits per query: sum of the match mask (i64 to be exact)."""
    return jnp.sum(mask, axis=-1, dtype=jnp.int64)
