"""Device aggregation kernels: masked bincount + fused numeric stats.

The collect step of the aggregation framework (search/aggs/aggregators.py)
runs these on device when the query mask is already device-resident (the
sparse/packed serving lanes produce it there): one fused XLA program per
(segment, agg) pair returning a SMALL psum-able partial — counts [V] or a
5-scalar stats vector — instead of downloading a bool[N] mask per segment
and reducing on host.

ref search/aggregations/bucket/terms/TermsAggregator (collect loop) and
metrics/stats/StatsAggregator — here the whole collect is one reduction,
not a per-doc callback.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp


# bincounts with small bin counts lower to ONE-HOT MATMULS, not scatters:
# counts[b] = Σ_n mask[n]·(ids[n]==b) is a [1..Q, N] x [N, B] contraction —
# MXU work with exact f32 accumulation (0/1 inputs), where jnp.bincount's
# scatter-add serializes (13s per 64x1M batch measured on both backends).
# Large B falls back to bincount (the one-hot would not fit).
_MATMUL_BINS = 256   # one-hot is [N, B] bf16 — cap its footprint


# Above this many docs the [N, n_bins] one-hot is chunked along the doc
# axis inside a lax.scan: bucket state accumulates PER BLOCK (the blockwise
# lane's ring-attention discipline applied to agg collect), so a 4M+ doc
# terms/date_histogram materializes [block, n_bins] instead of the 2 GB
# full one-hot. Per-block counts are exact integers <= block < 2^24, so the
# i32 accumulation is exact and results match the one-shot matmul bitwise.
_ONEHOT_BLOCK = 65536


def _onehot_block(ids, v2, n_bins: int):
    oh = (ids[:, None] == jnp.arange(n_bins, dtype=ids.dtype)[None, :])
    return jax.lax.dot_general(
        v2.astype(jnp.bfloat16), oh.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _onehot_counts(ids, valid, n_bins: int):
    """ids i32[N], valid bool[..., N] -> f32[..., n_bins] exact counts."""
    v2 = valid[None, :] if valid.ndim == 1 else valid
    N = ids.shape[0]
    if N > _ONEHOT_BLOCK and N % _ONEHOT_BLOCK == 0:
        nb = N // _ONEHOT_BLOCK
        Q = v2.shape[0]
        ids_b = ids.reshape(nb, _ONEHOT_BLOCK)
        v_b = jnp.moveaxis(v2.reshape(Q, nb, _ONEHOT_BLOCK), 1, 0)

        def body(acc, x):
            i_blk, vb = x
            return acc + _onehot_block(i_blk, vb, n_bins).astype(jnp.int32), None
        acc0 = jnp.zeros((Q, n_bins), jnp.int32)
        out, _ = jax.lax.scan(body, acc0, (ids_b, v_b))
        out = out.astype(jnp.float32)
    else:
        out = _onehot_block(ids, v2, n_bins)
    return out[0] if valid.ndim == 1 else out


@partial(jax.jit, static_argnames=("n_bins",))
def masked_bincount(ords, mask, *, n_bins: int):
    """Counts per ordinal among masked docs. ords i32[N] (-1 = missing),
    mask bool[N] -> i32[n_bins]. Missing/unmasked docs fall into a spill
    bin that is sliced off."""
    if n_bins <= _MATMUL_BINS:
        valid = mask & (ords >= 0)
        return _onehot_counts(ords, valid, n_bins).astype(jnp.int32)
    idx = jnp.where(mask & (ords >= 0), ords, n_bins)
    return jnp.bincount(idx, length=n_bins + 1)[:n_bins]


@jax.jit
def masked_stats(vals, missing, mask):
    """Fused (count, sum, sum_sq, min, max) over masked present docs.
    vals f64[N]/i64[N], missing bool[N], mask bool[N] -> f64[5]."""
    sel = mask & ~missing
    v = vals.astype(jnp.float64)
    vz = jnp.where(sel, v, 0.0)
    cnt = sel.sum().astype(jnp.float64)
    s = vz.sum()
    ss = (vz * vz).sum()
    mn = jnp.where(sel, v, jnp.inf).min()
    mx = jnp.where(sel, v, -jnp.inf).max()
    return jnp.stack([cnt, s, ss, mn, mx])


@jax.jit
def count_mask(mask):
    return mask.sum()


def hist_bins(vals, base, interval):
    """Bucket ids floor((v - base) / interval) as i32. `base`/`interval`
    must be RUNTIME operands, never Python constants: XLA rewrites a
    float division by a compile-time constant into a multiplication by
    its reciprocal, which puts a value sitting exactly on a bucket
    boundary into the bucket below. Integer operands (an i64 column with
    an integral base and interval — dates, longs) bucket by exact i64
    floor-division, which no backend's float emulation can shift either;
    anything else divides in f64."""
    if jnp.issubdtype(jnp.result_type(interval), jnp.integer):
        return ((vals.astype(jnp.int64) - base) // interval
                ).astype(jnp.int32)
    return jnp.floor((vals.astype(jnp.float64) - base)
                     / interval).astype(jnp.int32)


def hist_operands(int_column: bool, base, interval: float):
    """(base, interval) as `hist_bins` operands: exact i64 when the
    column, the base(s) and the interval are all integral, f64 else.
    `base` is one segment's scalar or an array of them."""
    base = np.asarray(base, np.float64)
    if int_column and float(interval).is_integer() \
            and bool(np.all(base == np.floor(base))):
        return base.astype(np.int64), np.int64(interval)
    return base, np.float64(interval)


@partial(jax.jit, static_argnames=("n_bins",))
def masked_histogram(vals, missing, mask, base, interval, *, n_bins: int):
    """Histogram/date_histogram collect: bucket id is an affine transform
    of the numeric column (`hist_bins`); counting is a one-hot matmul
    (see _onehot_counts). vals [N] -> i32[n_bins]."""
    sel = mask & ~missing
    idx = hist_bins(vals, base, interval)
    ok = sel & (idx >= 0) & (idx < n_bins)
    if n_bins <= _MATMUL_BINS:
        return _onehot_counts(idx, ok, n_bins).astype(jnp.int32)
    idx = jnp.where(ok, idx, n_bins)
    return jnp.bincount(idx, length=n_bins + 1)[:n_bins]


@jax.jit
def masked_ranges(vals, missing, mask, los, his):
    """range/date_range collect: counts per [lo, hi) interval, all ranges
    in one program. los/his f64[R] (±inf for open ends) -> i64[R]."""
    sel = (mask & ~missing)[None, :]
    v = vals.astype(jnp.float64)[None, :]
    inr = sel & (v >= los[:, None]) & (v < his[:, None])
    return inr.sum(axis=1)


# -- row-batched variants: one device call serves a WHOLE msearch batch
# (mask [Q, N]) instead of Q per-row launches and syncs ---------------------

@partial(jax.jit, static_argnames=("n_bins",))
def masked_bincount_q(ords, mask, *, n_bins: int):
    """mask bool[Q, N] -> counts i32[Q, n_bins] (one-hot matmul)."""
    if n_bins <= _MATMUL_BINS:
        valid = mask & (ords >= 0)[None, :]
        return _onehot_counts(ords, valid, n_bins).astype(jnp.int32)
    idx = jnp.where(mask & (ords >= 0)[None, :], ords[None, :], n_bins)
    return jax.vmap(lambda ix: jnp.bincount(ix, length=n_bins + 1))(
        idx)[:, :n_bins]


@partial(jax.jit, static_argnames=("n_bins",))
def masked_histogram_q(vals, missing, mask, base, interval, *, n_bins: int):
    """mask bool[Q, N] -> counts i32[Q, n_bins] (one-hot matmul)."""
    idx = hist_bins(vals, base, interval)
    ok = (~missing) & (idx >= 0) & (idx < n_bins)
    if n_bins <= _MATMUL_BINS:
        return _onehot_counts(idx, mask & ok[None, :],
                              n_bins).astype(jnp.int32)
    idx = jnp.where(mask & ok[None, :], idx[None, :], n_bins)
    return jax.vmap(lambda ix: jnp.bincount(ix, length=n_bins + 1))(
        idx)[:, :n_bins]


@jax.jit
def masked_stats_q(vals, missing, mask):
    """mask bool[Q, N] -> f64[Q, 5] (count, sum, sum_sq, min, max)."""
    sel = mask & ~missing[None, :]
    v = vals.astype(jnp.float64)[None, :]
    vz = jnp.where(sel, v, 0.0)
    cnt = sel.sum(axis=1).astype(jnp.float64)
    s = vz.sum(axis=1)
    ss = (vz * vz).sum(axis=1)
    mn = jnp.where(sel, v, jnp.inf).min(axis=1)
    mx = jnp.where(sel, v, -jnp.inf).max(axis=1)
    return jnp.stack([cnt, s, ss, mn, mx], axis=1)


@jax.jit
def masked_ranges_q(vals, missing, mask, los, his):
    """mask bool[Q, N] -> i64[Q, R]."""
    ok = ~missing
    v = vals.astype(jnp.float64)
    inr = ok[None, :] & (v[None, :] >= los[:, None]) \
        & (v[None, :] < his[:, None])              # [R, N]
    return (mask[:, None, :] & inr[None, :, :]).sum(axis=2)


@jax.jit
def col_minmax(vals, missing):
    """(min, max) over present values — cached per immutable segment so
    histogram bucket counts can be sized without downloading the column."""
    v = vals.astype(jnp.float64)
    mn = jnp.where(missing, jnp.inf, v).min()
    mx = jnp.where(missing, -jnp.inf, v).max()
    return jnp.stack([mn, mx])
