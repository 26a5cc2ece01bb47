"""Sort-reduce BM25 top-k: the scatter-free TPU hot kernel.

Why not the dense formulation (ops/bm25.py)? On TPU, arbitrary gathers
(doc_ids[idx]) and scatter-adds into a [Q, N] score matrix serialize into
dynamic-slice loops — measured ~25x slower than this kernel at 1M docs.
This kernel reads postings ONLY as contiguous blocks (a term's block, or a
fixed-size slot of it) and never materializes per-doc state:

  1. slice    — each (query, term) loads its postings block [Wt] with three
                contiguous slices (doc ids, tf, per-posting dl). Per-posting
                dl (denormalized at segment build) kills the doc_len[doc]
                gather entirely.
  2. score    — elementwise BM25 impact × per-term weight (idf*(k1+1)*boost),
                matching Lucene's BM25Similarity term-at-a-time contribution
                (ref /root/reference/src/main/java/org/elasticsearch/index/
                similarity/BM25SimilarityProvider.java; QueryPhase hot loop
                search/query/QueryPhase.java:144-154).
  3. sort     — lax.sort the (doc, contrib) pairs per query: same-doc
                contributions become adjacent runs. Postings are doc-sorted
                per term, so a run's length is at most T (one entry per
                query term).
  4. reduce   — windowed segment-sum: run length <= T means per-doc totals
                need only T-1 shifted compare-adds — no segment_sum scatter.
  5. top-k    — lax.top_k over the W = T*Wt slots (slots, not the N-doc
                space): the "never materialize the full score vector" move
                (SURVEY.md §5.7), with doc-id-ascending tie-break like
                Lucene's priority queue.

What the trace showed of step 1 (PERF.md §5-§6, PR 25 and PR 28): a block
read written as `vmap(dynamic_slice)` is no gather to XLA, but the TPU runs
it as one `while` loop a stream with one iteration a block, each copy
waiting for the one before — 2 us a block, 75-79 % of the packed program.
The packed program (`bm25_serve_packed`) therefore copies its slots with
ops/slot_gather.py on the TPU: one Pallas kernel, a block of 32 slots x 3
streams of DMAs in flight at once. `_sorted_runs` below (the sparse lane)
still has the `vmap(dynamic_slice)` form, with Wt in CHUNK's place.

The per-term slot budget Wt is a static pow2 bucket >= the largest df among
the query batch's terms; compile cache stays small, padding is masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .slot_gather import gather_slots

# The packed lane's one sentinel doc id (bm25_serve_packed's docstring says
# why it is a constant): padding of `doc_ids`, the unused lanes of a slot, and
# every posting of a document that is not live.
PACKED_PAD_DOC = int(jnp.iinfo(jnp.int32).max)

# packed_fold_ids compares this many dead ids in one pass over the postings,
# and takes a list of at most FOLD_IDS_MAX (its one compile shape). Longer
# lists go to packed_fold_live: on the v5e a pass costs 0.022 ns a posting and
# the gather 8.1 ns, whatever the number of postings, so the two meet near
# 11,900 ids (PERF.md §6, PR 25).
FOLD_IDS_BLOCK = 32
FOLD_IDS_MAX = 8192

# A term target that no row of a filter column holds (rows hold an ordinal
# >= 0, or -1 where the document has no value): an unused target, and a value
# that no document has.
NO_ORDINAL = -2


def required_padding(n_postings: int, max_df: int) -> int:
    """Physical postings padding so any term slice start+Wt stays in bounds
    (dynamic_slice clamps out-of-range starts, which would silently read a
    neighboring term's postings). THE single source of this invariant —
    segment build and shard packing must both use it, together with
    `slot_budget` for Wt, or slices can clamp."""
    from ..index.segment import next_pow2
    return next_pow2(n_postings + next_pow2(max_df, floor=8), floor=8)


def _sorted_runs(doc_ids, tf, dl, term_starts, term_lens, weights,
                 k1, b, avgdl, *, Wt: int, n_docs: int, with_count: bool):
    """Stages 1-4 of the pipeline, shared by both kernels: slice postings,
    score, sort, windowed segment-sum. Returns (d i32[Q,W] sorted doc ids,
    total f32[Q,W] per-run score on each run's last slot, count f32[Q,W]
    per-run distinct-term count or None, ends bool[Q,W] run-end markers)."""
    Q, T = term_starts.shape
    PAD = jnp.int32(n_docs)

    def slice_term(s, ln):
        d = jax.lax.dynamic_slice(doc_ids, (s,), (Wt,))
        t = jax.lax.dynamic_slice(tf, (s,), (Wt,))
        l = jax.lax.dynamic_slice(dl, (s,), (Wt,))
        valid = jnp.arange(Wt, dtype=jnp.int32) < ln
        return jnp.where(valid, d, PAD), t, l, valid

    d, t, l, valid = jax.vmap(jax.vmap(slice_term))(term_starts, term_lens)

    norm = k1 * (1.0 - b + b * l / avgdl)
    impact = t / (t + norm)
    contrib = jnp.where(valid, weights[:, :, None] * impact, 0.0)

    W = T * Wt
    d = d.reshape(Q, W)
    contrib = contrib.reshape(Q, W).astype(jnp.float32)
    if with_count:
        cnt = valid.astype(jnp.float32).reshape(Q, W)
        d, contrib, cnt = jax.lax.sort((d, contrib, cnt),
                                       dimension=1, num_keys=1)
    else:
        cnt = None
        d, contrib = jax.lax.sort((d, contrib), dimension=1, num_keys=1)

    # windowed segment-sum: totals land on each run's last slot (runs are at
    # most T long: postings are doc-sorted per term, one entry per query term)
    total = contrib
    count = cnt
    for j in range(1, T):
        same = d == jnp.roll(d, j, axis=1)
        same = same.at[:, :j].set(False)
        total = total + jnp.where(same, jnp.roll(contrib, j, axis=1), 0.0)
        if with_count:
            count = count + jnp.where(same, jnp.roll(cnt, j, axis=1), 0.0)

    is_real = d < PAD
    ends = jnp.concatenate([d[:, :-1] != d[:, 1:], jnp.ones((Q, 1), bool)],
                           axis=1) & is_real
    return d, total, count, ends


@functools.partial(jax.jit,
                   static_argnames=("Wt", "k", "n_docs", "with_positions"))
def bm25_topk_sparse(doc_ids: jax.Array, tf: jax.Array, dl: jax.Array,
                     term_starts: jax.Array, term_lens: jax.Array,
                     weights: jax.Array, k1, b, avgdl, *,
                     Wt: int, k: int, n_docs: int,
                     with_positions: bool = False):
    """Batched BM25 top-k over one postings block.

    doc_ids i32[P], tf f32[P], dl f32[P]: postings (P >= max start + Wt —
    use `required_padding`). term_starts/term_lens i32[Q,T]; weights f32[Q,T].
    Returns (top_scores f32[Q,k], top_docs i32[Q,k], total_hits i32[Q]).
    Empty slots: score -inf, doc == n_docs.
    """
    PAD = jnp.int32(n_docs)
    d, total, _, ends = _sorted_runs(
        doc_ids, tf, dl, term_starts, term_lens, weights, k1, b, avgdl,
        Wt=Wt, n_docs=n_docs, with_count=False)
    W = d.shape[1]
    masked = jnp.where(ends, total, -jnp.inf)
    top, pos = jax.lax.top_k(masked, min(k, W))
    top_docs = jnp.where(top > -jnp.inf,
                         jnp.take_along_axis(d, pos, axis=1), PAD)
    total_hits = jnp.sum(ends, axis=1, dtype=jnp.int32)
    return top, top_docs, total_hits


def slot_budget(term_lens) -> int:
    """Static per-term slot budget for a query batch: pow2 >= max df."""
    import numpy as np
    from ..index.segment import next_pow2
    return next_pow2(int(np.asarray(term_lens).max()), floor=8)


@functools.partial(jax.jit,
                   static_argnames=("S", "CHUNK", "R", "k", "FR", "FT", "TV"))
def bm25_serve_packed_filtered(packed_q: jax.Array, doc_ids: jax.Array,
                               tf: jax.Array, dl: jax.Array,
                               k1, b, avgdl, const,
                               ranks: tuple,
                               fr_col: jax.Array, fr_lo: jax.Array,
                               fr_hi: jax.Array, fr_neg: jax.Array,
                               ft_col: jax.Array, ft_targets: jax.Array,
                               ft_neg: jax.Array, *,
                               S: int, CHUNK: int, R: int, k: int,
                               FR: int, FT: int, TV: int) -> jax.Array:
    """bm25_serve_packed + per-query COLUMNAR FILTERS evaluated on device on
    every posting the slots copy (the filter analog of Lucene's filtered
    query inside QueryPhase — BASELINE config #2's bool{match + filter}).

    ranks: NC streams i32[P], one a filter column this batch touches, each
        aligned with `doc_ids` (`packed_filter_stream`): a posting's entry is
        its document's ORDINAL in the column, the rank of the document's
        value among the view's sorted distinct values (-1 = no value, and
        every padding posting). Whatever the field's type, order and
        equality are all a filter needs of a column. The 64-bit values stay
        on the host (`PackedFilterColumn.distinct`), where
        `_filter_descriptors` turns every bound and target into an ordinal
        exactly; the program holds no float64.
    `packed.gather` copies the streams' slots with the postings' own (one
        kernel, 3 + NC streams), and `packed.filters` compares the [Q, S,
        CHUNK] rank blocks with each query's bounds and targets before the
        score: a posting that fails scores 0 and counts 0, and `min_match`
        (at least 1) drops its document. A document holds one rank in all
        its postings, so a posting's compare decides what the document's
        would. No column is read at a candidate position: on the TPU that
        gather is serial, 7.5 ns a candidate row, and it was four fifths of
        the program (PERF.md §5).
    Range slots (AND-ed): fr_col i32[Q, FR] (index into ranks; -1 = slot
        unused, -2 = active but the field has no column: matches nothing),
        fr_lo/fr_hi i32[Q, FR] an INCLUSIVE ordinal interval (an open end,
        a bound between two values and a missing bound are resolved on the
        host; lo > hi matches nothing), fr_neg i32[Q, FR].
    Term slots (AND-ed; OR within a slot's TV targets): ft_col i32[Q, FT],
        ft_targets i32[Q, FT, TV] ordinals (NO_ORDINAL = unused target, or
        a value no document holds), ft_neg i32[Q, FT].
    A document without a value fails every range and term and passes
        their negations. Still 1 upload + 1 download a batch.
    """
    return _serve_packed_impl(
        packed_q, doc_ids, tf, dl, k1, b, avgdl, const,
        S=S, CHUNK=CHUNK, R=R, k=k, gather=packed_gather_form(), ranks=ranks,
        filters=(fr_col, fr_lo, fr_hi, fr_neg,
                 ft_col, ft_targets, ft_neg, FR, FT, TV))


@functools.partial(jax.jit, static_argnames=("S", "CHUNK", "R", "k"))
def bm25_serve_packed(packed_q: jax.Array, doc_ids: jax.Array, tf: jax.Array,
                      dl: jax.Array, k1, b, avgdl, const, *,
                      S: int, CHUNK: int, R: int, k: int) -> jax.Array:
    """The serving kernel: ONE device program for a whole request batch
    over ALL shards/segments of an index, ONE packed input upload, ONE
    packed output download.

    Motivation: every host<->device interaction is a synchronization the
    host waits out regardless of size, so a per-segment kernel plus three
    separate result fetches pays several per request. This kernel serves
    the entire request in a single dispatch. It also replaces the
    per-batch `Wt = max df` slot
    budget with FIXED-SIZE postings chunks: a (query, term, segment) postings
    slice of length L becomes ceil(L/CHUNK) slots of exactly CHUNK postings,
    so the compile-cache key no longer depends on the data's df distribution
    — shapes are (Q, S) buckets only, and a single huge term can't blow the
    slot budget for the whole batch.

    packed_q i32[Q, 3S+1]: per-query slot table, one H2D transfer —
        [:, 0:S)    slot postings start
        [:, S:2S)   slot length (<= CHUNK; 0 = unused slot)
        [:, 2S:3S)  slot weight, f32 bitcast to i32
                    (idf * (k1+1) * per-query boost — slots of one term all
                    carry the same weight)
        [:, 3S]     per-query minimum distinct matching terms
    doc_ids i32[P], tf f32[P], dl f32[P]: postings packed across ALL
        segments (doc ids rebased to the global packed doc space), padded
        with >= CHUNK sentinel entries so any in-range slice stays in bounds.
    Liveness is the postings' own: the program takes no liveness row. A
        posting whose document is not live (a tombstone, a nested row)
        carries the doc id PACKED_PAD_DOC, put there by `packed_fold_live`
        / `packed_fold_ids` when liveness changes, never per request, and
        reads here as an unused lane: `valid` false, contribution 0,
        count 0. PACKED_PAD_DOC is also what pads `doc_ids` and what fills
        the unused lanes of a slot. It is int32's largest value, a constant
        and not the view's document count: a view extended by a refresh
        reaches every id below its own count, the old view's count among
        them, and a folded posting lies INSIDE its slot's `valid` lanes, so
        a sentinel that a later view can reach would come back as a hit.
        No view reaches this one; it sorts after every real id, and the
        build of a filter's rank stream (`packed_filter_stream`, a `take`
        with mode "clip") reads a padding row of the column for it.
    R: max distinct query terms — the run-length bound of the windowed
        segment-sum. A doc appears at most once per term (chunks of one term
        are disjoint doc ranges), so runs are <= R regardless of S.
    The slots' postings are copied (`packed.gather`) by the blocked kernel
        of ops/slot_gather.py on the TPU and by `vmap(dynamic_slice)` on
        any other backend (`packed_gather_form`): the same [Q, S, CHUNK]
        blocks bit for bit, so the same answer. On the chip the sliced form
        was three serial loops of Q x S copies, 386 of the 517 ms of a
        256 x 256-slot program; the kernel takes 21 ms there (PERF.md §6).

    Returns ONE i32[Q, 2k+1]: [scores f32-bitcast | top docs | total_hits]
    — a single D2H transfer; host splits and bitcasts back.

    ref: replaces the reference's per-segment BulkScorer loop
    (search/query/QueryPhase.java:91-168) with one batched program; the
    2-phase contract (ids only, fetch later) is unchanged.
    """
    return _serve_packed_impl(packed_q, doc_ids, tf, dl,
                              k1, b, avgdl, const,
                              S=S, CHUNK=CHUNK, R=R, k=k, filters=None,
                              gather=packed_gather_form())


def packed_gather_form() -> str:
    """How the packed program copies its slots' postings on this backend:
    "blocked" (ops/slot_gather.py, the Pallas kernel) on the TPU, "sliced"
    (`vmap(dynamic_slice)`) elsewhere. The backend is all that chooses: no
    setting does. `PackedIndexView.search` counts its dispatches by it."""
    return "blocked" if jax.default_backend() == "tpu" else "sliced"


def _serve_packed_impl(packed_q, doc_ids, tf, dl, k1, b, avgdl, const, *,
                       S, CHUNK, R, k, filters, gather, ranks=()):
    # each phase is a `jax.named_scope`: metadata only (same program, same
    # outputs), so a profile's operations group under stable names
    Q = packed_q.shape[0]
    starts = packed_q[:, :S]
    lens = packed_q[:, S:2 * S]
    weights = jax.lax.bitcast_convert_type(packed_q[:, 2 * S:3 * S],
                                           jnp.float32)
    min_match = packed_q[:, 3 * S]
    PAD = jnp.int32(PACKED_PAD_DOC)

    with jax.named_scope("packed.gather"):
        # a copy either way: the same [Q, S, CHUNK] blocks, bit for bit
        if gather == "blocked":
            slotted = (x.reshape(Q, S, CHUNK) for x in gather_slots(
                starts.reshape(-1), (doc_ids, tf, dl, *ranks), chunk=CHUNK,
                interpret=jax.default_backend() != "tpu"))
        else:
            slotted = jax.vmap(jax.vmap(lambda s: tuple(
                jax.lax.dynamic_slice(x, (s,), (CHUNK,))
                for x in (doc_ids, tf, dl, *ranks))))(starts)
        d, t, l, *ranks = slotted      # the filter's rank blocks, if any
        valid = jnp.arange(CHUNK, dtype=jnp.int32) < lens[:, :, None]
        d = jnp.where(valid, d, PAD)

    if filters is not None:
        with jax.named_scope("packed.filters"):
            # before the score: a posting that fails counts 0 and scores 0
            valid = valid & _filter_mask(ranks, valid.shape, *filters)

    W = S * CHUNK
    with jax.named_scope("packed.score"):
        valid = valid & (d != PAD)      # a folded posting: not live
        norm = k1 * (1.0 - b + b * l / avgdl)
        impact = t / (t + norm)
        contrib = jnp.where(valid, weights[:, :, None] * impact, 0.0)
        d = d.reshape(Q, W)
        contrib = contrib.reshape(Q, W).astype(jnp.float32)
        cnt = valid.astype(jnp.float32).reshape(Q, W)

    with jax.named_scope("packed.sort"):
        d, contrib, cnt = jax.lax.sort((d, contrib, cnt), dimension=1,
                                       num_keys=1)

    with jax.named_scope("packed.combine_runs"):
        total = contrib
        count = cnt
        for j in range(1, R):
            same = d == jnp.roll(d, j, axis=1)
            same = same.at[:, :j].set(False)
            total = total + jnp.where(same, jnp.roll(contrib, j, axis=1),
                                      0.0)
            count = count + jnp.where(same, jnp.roll(cnt, j, axis=1), 0.0)

    with jax.named_scope("packed.keep"):
        is_real = d != PAD
        ends = jnp.concatenate(
            [d[:, :-1] != d[:, 1:], jnp.ones((Q, 1), bool)], axis=1) & is_real
        keep = ends & (count >= min_match[:, None].astype(jnp.float32))

    with jax.named_scope("packed.topk"):
        masked = jnp.where(keep, total + const, -jnp.inf)
        top, pos = jax.lax.top_k(masked, min(k, W))
        top_docs = jnp.where(top > -jnp.inf,
                             jnp.take_along_axis(d, pos, axis=1), PAD)

    with jax.named_scope("packed.pack_out"):
        if k > W:   # degenerate tiny-index case: pad to the contract shape
            fill = ((Q, k - W))
            top = jnp.concatenate(
                [top, jnp.full(fill, -jnp.inf, top.dtype)], axis=1)
            top_docs = jnp.concatenate(
                [top_docs, jnp.broadcast_to(PAD, fill).astype(jnp.int32)],
                axis=1)
        total_hits = jnp.sum(keep, axis=1, dtype=jnp.int32)
        return jnp.concatenate(
            [jax.lax.bitcast_convert_type(top, jnp.int32), top_docs,
             total_hits[:, None]], axis=1)


def _filter_mask(ranks, shape, fr_col, fr_lo, fr_hi, fr_neg, ft_col,
                 ft_targets, ft_neg, FR, FT, TV):
    """bool `shape` = [Q, S, CHUNK]: whether each posting's document passes
    every filter slot of its query (`bm25_serve_packed_filtered` has the
    descriptors' meaning). `ranks` are the NC rank blocks of that shape; a
    slot's block is picked by a select over the static NC axis, elementwise,
    so nothing is gathered. A posting without a value reads -1: below every
    interval's low end (>= 0) and equal to no target (>= 0 or NO_ORDINAL)."""

    def per_query(x):               # [Q] -> beside every posting of its query
        return x[:, None, None]

    def column(code):               # the rank block of the slot's column
        v = jnp.full(shape, -1, jnp.int32)
        for c, block in enumerate(ranks):
            v = jnp.where(per_query(code == c), block, v)
        return v

    def slot(m, code, neg):
        m = jnp.where(per_query(code == -2), False, m)      # absent column
        m = jnp.where(per_query(neg > 0), ~m, m)
        return jnp.where(per_query(code != -1), m, True)    # unused slot

    ok = jnp.ones(shape, bool)
    for fi in range(FR):
        v = column(fr_col[:, fi])
        m = (v >= per_query(fr_lo[:, fi])) & (v <= per_query(fr_hi[:, fi]))
        ok = ok & slot(m, fr_col[:, fi], fr_neg[:, fi])
    for fi in range(FT):
        v = column(ft_col[:, fi])
        m = v == per_query(ft_targets[:, fi, 0])
        for vi in range(1, TV):
            m = m | (v == per_query(ft_targets[:, fi, vi]))
        ok = ok & slot(m, ft_col[:, fi], ft_neg[:, fi])
    return ok


@jax.jit
def packed_filter_stream(vals: jax.Array, doc_ids: jax.Array) -> jax.Array:
    """A filter's rank stream: i32[P], each posting's document's rank in the
    column `vals` i32[Npad] (-1 = no value, and every padding row). One
    gather over the postings, once a (view, text field, column); the
    filtered program then reads the ranks with the postings' own copies.
    PACKED_PAD_DOC clips to the column's last row, a padding row: -1."""
    return vals.take(doc_ids, mode="clip")


@functools.partial(jax.jit, donate_argnums=(0,))
def packed_fold_live(doc_ids: jax.Array, live: jax.Array) -> jax.Array:
    """Fold liveness into packed postings, in full: every posting whose
    document is not live becomes PACKED_PAD_DOC. One gather over P — for a
    newly packed field and for long lists of new tombstones.

    doc_ids i32[P] is DONATED (the folded ids take its place: no second
    per-posting array). live bool[Npad]: global liveness; its last row MUST
    be False (an id folded earlier clips to it and stays folded).
    """
    return jnp.where(live.take(doc_ids, mode="clip"), doc_ids,
                     jnp.int32(PACKED_PAD_DOC))


@functools.partial(jax.jit, donate_argnums=(0,))
def packed_fold_ids(doc_ids: jax.Array, dead: jax.Array,
                    n_blocks: jax.Array) -> jax.Array:
    """The same fold for a SHORT list of documents that died since the last
    one: a streaming compare of the postings against the list, FOLD_IDS_BLOCK
    ids a pass, no gather. Equal, array for array, to packed_fold_live with
    the liveness row those deaths give.

    doc_ids i32[P] is DONATED. dead i32[FOLD_IDS_MAX]: the global doc ids,
    padded with PACKED_PAD_DOC. n_blocks i32: ceil(len / FOLD_IDS_BLOCK) —
    dynamic, so one compile serves every length.
    """
    PAD = jnp.int32(PACKED_PAD_DOC)

    def one_pass(i, ids):
        blk = jax.lax.dynamic_slice(dead, (i * FOLD_IDS_BLOCK,),
                                    (FOLD_IDS_BLOCK,))
        hit = ids == blk[0]
        for u in range(1, FOLD_IDS_BLOCK):
            hit = hit | (ids == blk[u])
        return jnp.where(hit, PAD, ids)

    return jax.lax.fori_loop(0, n_blocks, one_pass, doc_ids)


@functools.partial(jax.jit, static_argnames=("Wt", "k", "n_docs"))
def bm25_topk_sparse_masked(doc_ids: jax.Array, tf: jax.Array, dl: jax.Array,
                            term_starts: jax.Array, term_lens: jax.Array,
                            weights: jax.Array, min_match: jax.Array,
                            doc_mask: jax.Array, k1, b, avgdl, *,
                            Wt: int, k: int, n_docs: int):
    """The served-search variant of `bm25_topk_sparse`: same sort-reduce
    pipeline, plus the two things a real request needs —

      * `min_match` i32[Q]: per-query minimum distinct matching terms
        (1 = operator "or", T = operator "and", otherwise
        minimum_should_match). Counted with a second windowed segment-sum
        over the validity indicator — reuses the same rolls as the score
        reduce, so "and" costs no extra sort.
      * `doc_mask` bool[M, n_docs+1] with M in {1, Q}: per-doc acceptance
        (tombstone liveness AND any filter/must_not context). Gathered only
        at the W candidate slots — a [Q, W] gather, never a [Q, N] one —
        so filters stay columnar and the scoring stays scatter-free.
        Index n_docs is the PAD sentinel row and MUST be False.

    Returns (top_scores f32[Q,k], top_docs i32[Q,k], total_hits i32[Q]).
    ref: the reference applies filters as Lucene FilteredQuery inside the
    same per-segment hot loop (search/query/QueryPhase.java:144-154).
    """
    PAD = jnp.int32(n_docs)
    d, total, count, ends = _sorted_runs(
        doc_ids, tf, dl, term_starts, term_lens, weights, k1, b, avgdl,
        Wt=Wt, n_docs=n_docs, with_count=True)
    W = d.shape[1]
    accepted = (doc_mask[0].take(d) if doc_mask.shape[0] == 1
                else jnp.take_along_axis(doc_mask, d, axis=1))
    keep = ends & accepted & (count >= min_match[:, None].astype(jnp.float32))
    masked = jnp.where(keep, total, -jnp.inf)

    top, pos = jax.lax.top_k(masked, min(k, W))
    top_docs = jnp.where(top > -jnp.inf,
                         jnp.take_along_axis(d, pos, axis=1), PAD)
    total_hits = jnp.sum(keep, axis=1, dtype=jnp.int32)
    return top, top_docs, total_hits


# dispatch accounting: rebind the serving entry points so host-level calls
# enter the device_stats registry (in-trace calls pass straight through)
from ..common.device_stats import instrument as _instrument  # noqa: E402

bm25_topk_sparse = _instrument("ops:bm25_topk_sparse", bm25_topk_sparse)
bm25_topk_sparse_masked = _instrument(
    "ops:bm25_topk_sparse_masked", bm25_topk_sparse_masked)
bm25_serve_packed = _instrument("ops:bm25_serve_packed", bm25_serve_packed)
bm25_serve_packed_filtered = _instrument(
    "ops:bm25_serve_packed_filtered", bm25_serve_packed_filtered)
packed_filter_stream = _instrument("ops:packed_filter_stream",
                                   packed_filter_stream)
packed_fold_live = _instrument("ops:packed_fold_live", packed_fold_live)
packed_fold_ids = _instrument("ops:packed_fold_ids", packed_fold_ids)
