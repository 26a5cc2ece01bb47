"""PackedIndexView: all shards' segments of one index fused into a single
device-resident postings structure, served by ops/bm25_sparse.bm25_serve_packed
in ONE device program per request batch.

Why: every host<->device interaction is a synchronization the host waits
out, whatever its size. Serving one kernel per segment and fetching three
result arrays per kernel costs several of them per request and leaves the
device idle in between. This view makes the whole request batch cost:

    1 program, whose dispatch uploads the i32 slot table + 1 D2H (i32 results)

It is the TPU analog of the reference's per-shard search fan-out collapsing
into a single batched program: the scatter-gather of
search/action/SearchServiceTransportAction.java becomes tensor concatenation,
and SearchPhaseController.sortDocs's cross-shard merge becomes the kernel's
global top-k (the doc space is packed across shards, so the top-k IS the
reduce). Term statistics are naturally index-global — equivalent to running
the DFS phase (search/dfs/DfsPhase.java:57-81) on every request, which is
*better* scoring parity than per-shard IDF.

The view is immutable w.r.t. the segment set (IndexService caches it keyed by
that). Liveness lives in the packed postings: when a segment's tombstones
change (Segment.live_gen) the next search folds them into each field's
`doc_ids` — a dead document's postings become PACKED_PAD_DOC — once a change,
on the device, and the program gathers no liveness per request.
"""

from __future__ import annotations

import contextlib
import math
import re
import threading
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ..common import tracing
from ..common.metrics import (device_fetch, note_h2d, record_filter_stream,
                              record_packed_dispatch)
from ..index.segment import Segment, next_pow2
from ..ops.bm25_sparse import (
    FOLD_IDS_BLOCK, FOLD_IDS_MAX, NO_ORDINAL, PACKED_PAD_DOC, bm25_serve_packed,
    bm25_serve_packed_filtered, packed_filter_stream, packed_fold_ids,
    packed_fold_live, packed_gather_form)

# Fixed postings chunk: compile-cache keys depend on (Q, S) pow2 buckets only,
# never on the corpus' df distribution.
CHUNK = 512

# static filter-slot budget per query (compile-cache keys); queries needing
# more fall back to the general path (serving/executor.py enforces)
F_RANGE = 2      # AND-ed range slots
F_TERM = 2       # AND-ed term slots
F_TERM_VALS = 4  # OR-ed values per term slot

_JSON_UNSAFE = re.compile(r'["\\\x00-\x1f]')


class FilterColumnRefused(Exception):
    """The request breaker refused a filter column — serve via the
    per-segment lane instead (not an error)."""


@dataclass
class PackedQuery:
    """One query row of a packed batch (per-query knobs the kernel supports
    without recompiling: term set, boost, operator/minimum_should_match, an
    additive constant applied host-side, and columnar filters evaluated on
    device — (negated?, TermFilterNode|RangeNode) pairs)."""
    terms: list[str]
    boost: float = 1.0
    operator: str = "or"
    msm: int = 1
    const: float = 0.0
    filters: tuple = ()


@dataclass
class PackedFilterColumn:
    """One field's filter column over the global packed doc space. The chip
    holds a row's ORDINAL: the rank of its value among `distinct`, the view's
    sorted distinct values, which stay on the host in the column's own type.
    A filter needs only order and equality of a column, ranks keep both, and
    `_filter_descriptors` finds a bound's or a target's rank where 64-bit
    values are exact (the TPU's float64 is a pair of float32)."""
    kind: str                      # "numeric" | "keyword"
    vals: jax.Array                # i32[n_pad_total]; -1 = no value, padding
    distinct: np.ndarray           # i64[D] | f64[D] | object[D] (str), sorted


class PackedField:
    """One text field's postings packed across every segment of the index.

    `doc_ids` carries liveness (module docstring): `folded_live` is the
    liveness it reflects and `live_key` the segments' `live_gen` at that
    fold. That state is the field's, not the view's: an extended view shares
    the field when no segment it appends has postings in it. The fold DONATES
    `doc_ids`, so every reader takes them through
    `PackedIndexView._folded_ids`, which counts the dispatches in flight
    (`in_use`, under `cv`); a fold waits until there are none."""

    def __init__(self, doc_ids: jax.Array, tf: jax.Array, dl: jax.Array,
                 terms: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                 sum_dl: float, total_p: int, folded_live: np.ndarray,
                 n_entries: int):
        self.doc_ids = doc_ids          # i32[P_pad] device, PAD-padded
        self.tf = tf                    # f32[P_pad]
        self.dl = dl                    # f32[P_pad]
        self.terms = terms              # U[V] sorted unique terms (host)
        self.starts = starts            # i32[V, NSEG] packed-space starts
        self.lens = lens                # i32[V, NSEG] per-segment df
        self.df = lens.sum(axis=1)      # i64[V] global df
        self.sum_dl = sum_dl
        self.total_p = total_p          # real postings (un-padded)
        self.folded_live = folded_live  # bool[N] host, over global doc ids
        self.n_entries = n_entries      # leading view entries N spans
        self.live_key: tuple = (None,) * n_entries   # no segment folded yet
        self.cv = threading.Condition()
        self.in_use = 0

    def term_ids(self, terms: list[str]) -> np.ndarray:
        """Vectorized term lookup; -1 for absent terms."""
        if not len(self.terms):
            return np.full(len(terms), -1, np.int64)
        q = np.asarray(terms)    # own U width — casting to the index's dtype
                                 # would truncate long query terms into
                                 # false matches
        idx = np.searchsorted(self.terms, q)
        idx_c = np.minimum(idx, len(self.terms) - 1)
        found = self.terms[idx_c] == q
        return np.where(found, idx_c, -1)


class PackedIndexView:
    """The fused serving structure for one index (all shards, all segments)."""

    def __init__(self, segments: list[tuple[int, Segment]], breaker=None,
                 base: "PackedIndexView | None" = None):
        """segments: (shard_idx, segment) in stable insertion order.
        breaker: optional "request" CircuitBreaker — each lazily-packed
        field charges its device bytes; a breach makes that field
        unservable by this view (field() returns None) instead of raising.
        base: a previous view whose entries are an IDENTITY PREFIX of
        `segments` — its built fields/filter columns are EXTENDED with the
        appended segments' postings instead of repacked from scratch, so an
        NRT refresh costs O(new postings), not O(index) (advisor r3)."""
        self.entries = segments
        self.breaker = breaker
        sizes = np.array([s.n_pad for _, s in segments], np.int64)
        self.bases = np.zeros(len(segments) + 1, np.int64)
        np.cumsum(sizes, out=self.bases[1:])
        self.n_total = int(self.bases[-1])
        self.n_pad_total = next_pow2(self.n_total + 1, floor=8)
        self.doc_count = sum(s.n_docs for _, s in segments)

        # host columns, a row per global doc id: `ids_packed` the _id as text
        # (tests and the raw render's reference twin read it), `ids_bytes` its
        # UTF-8 bytes NUL-padded to one width (executor.response_raw's gather)
        max_id = max((max((len(i) for i in s.ids), default=1)
                      for _, s in segments), default=1)
        self.ids_packed = np.full(self.n_pad_total, "", dtype=f"U{max_id}")
        types: set[str] = set()
        for ei, (_, seg) in enumerate(segments):
            if seg.n_docs:
                b0 = self.bases[ei]
                self.ids_packed[b0:b0 + seg.n_docs] = seg.ids
                types.update(seg.types)
        self.ids_bytes = _utf8_rows(self.ids_packed)
        self.single_type = types.pop() if len(types) == 1 else None
        # raw hits need no escaping only if every id and the type are clean
        # ("," is JSON-safe itself); a mixed-type index takes the dict lane
        joined = ",".join(",".join(s.ids) for _, s in segments)
        self.ids_json_safe = self.single_type is not None and not (
            _JSON_UNSAFE.search(joined) or _JSON_UNSAFE.search(self.single_type))

        self._fields: dict[str, PackedField | None] = {}
        self._refused: set[str] = set()   # breaker-refused (≠ absent) fields
        self._filter_cols: dict[str, PackedFilterColumn | None] = {}
        self._rank_streams: dict[tuple, jax.Array] = {}   # (field, column)
        self._consts: dict[tuple, tuple] = {}   # (field, k1, b) -> _constants
        self.device_calls = 0           # serving counters (observability)
        self.memory_bytes = 0
        self.extended_from_base = False
        if base is not None:
            self._seed_from(base)

    def _seed_from(self, base: "PackedIndexView") -> None:
        """Extend the base view's built structures with the appended
        segments (entries[len(base.entries):])."""
        assert len(base.entries) <= len(self.entries) and all(
            b[1] is s[1] for b, s in zip(base.entries, self.entries)), \
            "base must be an identity prefix"
        from ..common.breaker import CircuitBreakingException
        for fname, pf in base._fields.items():
            if pf is None:
                continue
            try:
                self._fields[fname] = self._extend_field(fname, base, pf)
            except CircuitBreakingException:
                self._refused.add(fname)
                self._fields[fname] = None
        for fname, col in base._filter_cols.items():
            if col is None:
                continue
            try:
                self._filter_cols[fname] = self._extend_filter_col(
                    fname, base, col)
            except CircuitBreakingException:
                pass    # rebuilt lazily (and re-gated) on next use
        self.extended_from_base = True

    def _extend_field(self, name: str, base: "PackedIndexView",
                      pf: PackedField) -> PackedField:
        """Append the new segments' postings BLOCKS to an existing packed
        field: device-side concat of the old buffers (no host repack of old
        data), plus a vectorized remap of the [V, NSEG] slice table into the
        union term dictionary. Host work is O(new postings + vocab)."""
        new = [(len(base.entries) + i, seg)
               for i, (_, seg) in enumerate(self.entries[len(base.entries):])]
        per_seg = []
        for ei, seg in new:
            fx = seg.text.get(name)
            if fx is None or seg.n_docs == 0:
                continue
            host_ids = fx.doc_ids_host if fx.doc_ids_host is not None \
                else np.asarray(fx.doc_ids)[:fx.n_postings]
            per_seg.append((ei, fx, host_ids[:fx.n_postings]))
        if not per_seg:
            # the arrays are reusable as they are (the PAD sentinel is no
            # view's doc id, and the fold's state is the field's own) — but
            # the old view's charge was released by IndexService, so the
            # still-resident buffers must be re-charged into THIS view
            # (check=False: memory already exists) or repeated NRT refreshes
            # progressively undercount the request breaker (advisor r4).
            reused = int(pf.doc_ids.size) * 12   # doc_ids+tf+dl at p_pad
            if self.breaker is not None and reused:
                self.breaker.add_estimate(reused, check=False)
            self.memory_bytes += reused
            return pf

        base_p = pf.total_p
        total_new = sum(len(h) for _, _, h in per_seg)
        p_pad = next_pow2(base_p + total_new + CHUNK, floor=CHUNK * 2)
        if self.breaker is not None:
            self.breaker.add_estimate(p_pad * 12)
        tail_docs = np.full(p_pad - base_p, PACKED_PAD_DOC, np.int32)
        tail_tf = np.zeros(p_pad - base_p, np.float32)
        tail_dl = np.ones(p_pad - base_p, np.float32)

        seg_term_arrays = [np.asarray(list(fx.terms), dtype="U")
                           for _, fx, _ in per_seg]
        all_terms = np.unique(np.concatenate([pf.terms] + seg_term_arrays)) \
            if len(pf.terms) else np.unique(np.concatenate(seg_term_arrays))
        V = len(all_terms)
        nseg_old = pf.starts.shape[1]
        starts = np.zeros((V, nseg_old + len(per_seg)), np.int32)
        lens = np.zeros((V, nseg_old + len(per_seg)), np.int64)
        if len(pf.terms):
            pos_old = np.searchsorted(all_terms, pf.terms)
            starts[pos_old, :nseg_old] = pf.starts
            lens[pos_old, :nseg_old] = pf.lens

        off = base_p
        sum_dl = pf.sum_dl
        for si, (ei, fx, host_ids) in enumerate(per_seg):
            P = len(host_ids)
            lo = off - base_p
            tail_docs[lo:lo + P] = host_ids + int(self.bases[ei])
            tail_tf[lo:lo + P] = np.asarray(fx.tf[:P])
            tail_dl[lo:lo + P] = np.asarray(fx.dl[:P])
            st = seg_term_arrays[si]
            pos = np.searchsorted(all_terms, st)
            starts[pos, nseg_old + si] = fx.term_starts[: len(st)] + off
            lens[pos, nseg_old + si] = fx.term_lens[: len(st)]
            sum_dl += fx.sum_dl
            off += P

        # the old postings come over as they are folded; nothing of the
        # appended segments is: the first search folds what is dead there
        with base._folded_ids(pf) as head:
            doc_ids = jnp.concatenate([head[:base_p],
                                       jnp.asarray(tail_docs)])
            folded_live = np.concatenate(
                [pf.folded_live, self._all_docs_live(pf.n_entries)])
        tf = jnp.concatenate([pf.tf[:base_p], jnp.asarray(tail_tf)])
        dl = jnp.concatenate([pf.dl[:base_p], jnp.asarray(tail_dl)])
        self.memory_bytes += p_pad * 12
        return PackedField(doc_ids=doc_ids, tf=tf, dl=dl, terms=all_terms,
                           starts=starts, lens=lens, sum_dl=sum_dl,
                           total_p=base_p + total_new,
                           folded_live=folded_live,
                           n_entries=len(self.entries))

    def _extend_filter_col(self, name: str, base: "PackedIndexView",
                           col: PackedFilterColumn) -> PackedFilterColumn:
        """Extend a filter column over the appended doc space."""
        if self.breaker is not None:
            self.breaker.add_estimate(self.n_pad_total * 4)
        return self._ranked_column(name, col.kind, len(base.entries), col)

    # -- liveness (folded into the postings when tombstones change) --------

    def _all_docs_live(self, first_entry: int) -> np.ndarray:
        """bool over the global ids of entries[first_entry:]: what postings
        nobody has folded yet reflect — every document live, padding not."""
        lo = int(self.bases[first_entry])
        rows = np.zeros(self.n_total - lo, bool)
        for ei in range(first_entry, len(self.entries)):
            at = int(self.bases[ei]) - lo
            rows[at:at + self.entries[ei][1].n_docs] = True
        return rows

    def _died_since_fold(self, pf: PackedField):
        """(live_gen key, global ids no longer live that `pf.doc_ids` still
        carries), or None when the ids are current. Caller holds pf.cv."""
        # the generations BEFORE the rows: a delete that lands in between is
        # folded now and looked at once more by the next search, never lost
        key = tuple(seg.live_gen for _, seg in self.entries[:pf.n_entries])
        if key == pf.live_key:
            return None
        died = []
        for ei, gen in enumerate(key):
            if pf.live_key[ei] == gen:
                continue
            seg = self.entries[ei][1]
            lo = int(self.bases[ei])
            was = pf.folded_live[lo:lo + seg.n_pad]
            # nested rows never serve as hits: root liveness
            died.append(lo + np.flatnonzero(was & ~seg.root_live_host))
        died = np.concatenate(died)
        if not len(died):
            pf.live_key = key
            return None
        return key, died

    def _fold(self, pf: PackedField, died: np.ndarray, by_list: bool) -> None:
        """Make the postings of `died` unused lanes, on the device, in place.
        Tombstones are never undone inside a segment, so the folded ids take
        `pf.doc_ids`' place. `by_list` streams the postings past the list,
        which a long list makes dearer than the one gather over all of them
        that the other program is. Caller holds pf.cv with no dispatch in
        flight."""
        if by_list:
            dead = np.full(FOLD_IDS_MAX, PACKED_PAD_DOC, np.int32)
            dead[:len(died)] = died
            pf.doc_ids = packed_fold_ids(
                pf.doc_ids, jnp.asarray(dead),
                jnp.int32(-(-len(died) // FOLD_IDS_BLOCK)))
        else:
            n = len(pf.folded_live)
            live = np.zeros(next_pow2(n + 1, floor=8), bool)
            live[:n] = pf.folded_live
            live[died] = False
            pf.doc_ids = packed_fold_live(pf.doc_ids, jnp.asarray(live))
        pf.folded_live[died] = False

    @contextlib.contextmanager
    def _folded_ids(self, pf: PackedField):
        """`pf.doc_ids` with this moment's tombstones folded in, held for the
        dispatches made inside the block: no refresh is needed for a delete
        to be seen, and no fold donates the buffer under a thread that is
        about to dispatch it (the batcher's leaders and the `_msearch` pool
        threads share one field). Dispatches of several threads overlap; a
        fold waits for those in flight, and whoever comes meanwhile waits
        for the fold."""
        with pf.cv:
            while (due := self._died_since_fold(pf)) is not None:
                if pf.in_use:
                    pf.cv.wait()
                    continue
                key, died = due
                by_list = len(died) <= FOLD_IDS_MAX
                with tracing.span("packed.live_fold", tombstones=len(died),
                                  kind="incremental" if by_list else "full"):
                    self._fold(pf, died, by_list)
                pf.live_key = key
            pf.in_use += 1
            doc_ids = pf.doc_ids
        try:
            yield doc_ids
        finally:
            with pf.cv:
                pf.in_use -= 1
                if not pf.in_use:
                    pf.cv.notify_all()

    # -- field packing (lazy, cached) --------------------------------------

    def field(self, name: str) -> PackedField | None:
        if name not in self._fields:
            self._fields[name] = self._pack_field(name)
            if self._fields[name] is not None:
                # precompile the solo-latency shapes for this field's
                # postings buckets so cold p99 is one compile, not many
                # (persistent XLA cache makes this ~free after first run)
                self.warmup(field=name)
        return self._fields[name]

    def servable(self, name: str) -> bool:
        """False when the request breaker refused this field's packed
        postings — the caller must fall back to the per-segment lane."""
        self.field(name)
        return name not in self._refused

    def _pack_field(self, name: str) -> PackedField | None:
        per_seg = []                    # (entry_idx, fx, host doc_ids)
        for ei, (_, seg) in enumerate(self.entries):
            fx = seg.text.get(name)
            if fx is None or seg.n_docs == 0:
                continue
            host_ids = fx.doc_ids_host
            if host_ids is None:        # segment loaded without host mirror
                host_ids = np.asarray(fx.doc_ids[:fx.n_postings])
            per_seg.append((ei, fx, host_ids[:fx.n_postings]))
        if not per_seg:
            return None

        total_p = sum(len(h) for _, _, h in per_seg)
        p_pad = next_pow2(total_p + CHUNK, floor=CHUNK * 2)
        doc_ids = np.full(p_pad, PACKED_PAD_DOC, np.int32)
        tf = np.zeros(p_pad, np.float32)
        dl = np.ones(p_pad, np.float32)

        # merged sorted term dict via per-segment searchsorted alignment
        seg_term_arrays = [np.asarray(list(fx.terms), dtype="U")
                           for _, fx, _ in per_seg]
        all_terms = (np.unique(np.concatenate(seg_term_arrays))
                     if seg_term_arrays else np.array([], "U1"))
        V = len(all_terms)
        nseg = len(per_seg)
        starts = np.zeros((V, nseg), np.int32)
        lens = np.zeros((V, nseg), np.int64)

        off = 0
        sum_dl = 0.0
        for si, (ei, fx, host_ids) in enumerate(per_seg):
            P = len(host_ids)
            doc_ids[off:off + P] = host_ids + int(self.bases[ei])
            tf[off:off + P] = np.asarray(fx.tf[:P])
            dl[off:off + P] = np.asarray(fx.dl[:P])
            st = seg_term_arrays[si]
            pos = np.searchsorted(all_terms, st)
            starts[pos, si] = fx.term_starts[:len(st)] + off
            lens[pos, si] = fx.term_lens[:len(st)]
            sum_dl += fx.sum_dl
            off += P

        if self.breaker is not None:
            from ..common.breaker import CircuitBreakingException
            try:
                self.breaker.add_estimate(p_pad * 12)
            except CircuitBreakingException:
                # NOT the same as an absent field (which legitimately serves
                # empty results): refusal must push the query to the
                # per-segment lane, so callers check servable()
                self._refused.add(name)
                return None
        self.memory_bytes += p_pad * 12
        return PackedField(
            doc_ids=jnp.asarray(doc_ids), tf=jnp.asarray(tf),
            dl=jnp.asarray(dl), terms=all_terms, starts=starts,
            lens=lens.astype(np.int64), sum_dl=sum_dl, total_p=total_p,
            folded_live=self._all_docs_live(0), n_entries=len(self.entries))

    # -- stats (parity with query_dsl.CollectionStats) ---------------------

    def avgdl(self, field: str) -> float:
        pf = self.field(field)
        sum_dl = pf.sum_dl if pf is not None else 0.0
        return max(sum_dl, 1.0) / max(self.doc_count, 1)

    # -- batch execution ---------------------------------------------------

    def search(self, field: str, queries: list[PackedQuery], *, k: int,
               k1: float = 1.2, b: float = 0.75):
        """Run the whole batch in one device program.

        Returns (scores f32[Q,k] (-inf = empty), docs i64[Q,k] global packed
        doc ids, hits i64[Q]). Q is the REAL query count (pad rows stripped).
        """
        Q = len(queries)
        pf = self.field(field)
        if pf is None or self.n_total == 0:
            return (np.full((Q, k), -np.inf, np.float32),
                    np.full((Q, k), -1, np.int64), np.zeros(Q, np.int64))

        # everything the host does before the dispatch is one span: the slot
        # table and the filter descriptors. `dev` is what goes to the device:
        # host arrays as they are, uploaded by the program's own dispatch
        prep = tracing.span("packed.build_slots", cpu=True)
        with prep:
            packed, S, R = self._build_slots(pf, queries, field, k1, b)
            k_pad = next_pow2(k, floor=8)
            dev = [packed]
            ranks, columns = None, 0
            if any(q.filters for q in queries):
                described = tracing.span(
                    "packed.filter_descriptors",
                    filters=sum(len(q.filters) for q in queries))
                with described:
                    fields, *descriptors = \
                        self._filter_descriptors(queries, packed.shape[0])
                    dev += descriptors
                    ranks = self._filter_streams(pf, field, fields)
                    described.attrs["columns"] = columns = len(fields)
            scalars, state = self._constants(field, k1, b)
            prep.attrs.update(
                consts=state, operands=len(dev), streams=columns,
                h2d_bytes=sum(a.nbytes for a in dev)
                + (4 * len(scalars) if state == "made" else 0))
            note_h2d(prep.attrs["h2d_bytes"])
        # the form of the program's slot gather and which program it is ride
        # its `program` span and /_metrics: a chip run that fell back to
        # "sliced" shows there
        form = packed_gather_form()
        program = "plain" if ranks is None else "filtered"
        with self._folded_ids(pf) as doc_ids, \
                tracing.program_attrs(gather=form, program=program,
                                      columns=columns):
            if ranks is not None:
                out = bm25_serve_packed_filtered(
                    dev[0], doc_ids, pf.tf, pf.dl, *scalars,
                    ranks, *dev[1:], S=S, CHUNK=CHUNK, R=R, k=k_pad,
                    FR=F_RANGE, FT=F_TERM, TV=F_TERM_VALS)
            else:
                out = bm25_serve_packed(
                    dev[0], doc_ids, pf.tf, pf.dl, *scalars,
                    S=S, CHUNK=CHUNK, R=R, k=k_pad)
        self.device_calls += 1
        record_packed_dispatch(form, program)
        fetch = tracing.span("packed.d2h")
        with fetch:
            arr = device_fetch(out)          # the ONE D2H transfer
            fetch.attrs["d2h_bytes"] = arr.nbytes
            arr = arr[:Q]
            scores = np.ascontiguousarray(
                arr[:, :k_pad]).view(np.float32)[:, :k]
            docs = arr[:, k_pad:2 * k_pad][:, :k].astype(np.int64)
            hits = arr[:, 2 * k_pad].astype(np.int64)
            consts = np.array([q.const for q in queries], np.float32)
            if consts.any():
                scores = np.where(scores > -np.inf,
                                  scores + consts[:, None], scores)
            docs = np.where(scores > -np.inf, docs, -1)
        return scores, docs, hits

    def _constants(self, field: str, k1: float, b: float):
        """-> ((k1, b, avgdl, 0) as f32 scalars on the device, "reused" or
        "made"): made once for the view, whose doc_count and sum_dl never
        change (a refresh builds a new view, and new constants with it).
        The state is what the `packed.build_slots` span says, `consts=`."""
        key = (field, k1, b)
        state = "reused" if key in self._consts else "made"
        if state == "made":
            self._consts[key] = _device_scalars(k1, b, self.avgdl(field))
        return self._consts[key], state

    def _build_slots(self, pf: PackedField, queries: list[PackedQuery],
                     field: str, k1: float, b: float):
        """Vectorized slot-table construction: terms -> fixed-CHUNK postings
        slots scattered into the packed i32[Q_pad, 3S+1] table, a host array
        that `search` hands to the program. One term lookup a batch."""
        Q = len(queries)
        # Q buckets are {1, 32, 64, 128, ...}: the dynamic batcher produces
        # arbitrary batch sizes, and a compile per pow2 bucket would stall
        # serving for seconds each — two warm shapes cover all solo +
        # batched traffic instead (warmup() compiles exactly these)
        Q_pad = 1 if Q == 1 else max(32, next_pow2(Q))
        nseg = pf.starts.shape[1]

        n_terms = [len(q.terms) for q in queries]
        min_match = np.ones(Q_pad, np.int32)
        min_match[:Q] = [max(n, 1) if q.operator == "and" else max(q.msm, 1)
                         for q, n in zip(queries, n_terms)]
        terms = [t for q in queries for t in q.terms]
        tids = pf.term_ids(terms) if terms else np.empty(0, np.int64)
        found = tids >= 0
        # R floor matches warmup()'s shapes: two extra rolls cost ~nothing,
        # one avoided compile shape saves seconds of cold p99
        R = next_pow2(max(n_terms + [1]), floor=4)
        if not found.any():
            # no term of the batch is in the index: the batch's floor of S
            # (as below), not a shape of its own that nothing warms
            S = 32 if Q_pad <= 32 else 4
            packed = np.zeros((Q_pad, 3 * S + 1), np.int32)
            packed[:, 3 * S] = min_match
            return packed, S, R

        # vectors over the batch's (query, term) pairs. The weight is float64
        # arithmetic in this order rounded to float32, its log math.log's (a
        # call a pair: np.log's last bit may differ, and the scores with it)
        qi_a = np.repeat(np.arange(Q), n_terms)[found]
        tid_a = tids[found]
        N, df = max(self.doc_count, 1), pf.df[tid_a]
        ratio = 1 + (N - df + 0.5) / (df + 0.5)
        idf = np.array([math.log(x) for x in ratio.tolist()], np.float64)
        boost = np.array([q.boost for q in queries], np.float64)
        w_a = (idf * (k1 + 1) * boost[qi_a]).astype(np.float32)

        # expand (query, term) -> (query, term, segment), drop empty slices
        lens_e = pf.lens[tid_a]                       # [E, NSEG]
        starts_e = pf.starts[tid_a]                   # [E, NSEG]
        qf = np.repeat(qi_a, nseg)
        lf = lens_e.reshape(-1)
        sf = starts_e.reshape(-1)
        wf = np.repeat(w_a, nseg)
        nz = lf > 0
        qf, lf, sf, wf = qf[nz], lf[nz], sf[nz], wf[nz]

        # expand each slice into ceil(len/CHUNK) fixed-size chunks
        nch = -(-lf // CHUNK)
        row = np.repeat(np.arange(len(lf)), nch)
        within = np.arange(len(row)) - np.repeat(
            np.concatenate([[0], np.cumsum(nch)[:-1]]), nch)
        slot_q = qf[row]
        slot_start = (sf[row] + within * CHUNK).astype(np.int32)
        slot_len = np.minimum(CHUNK, lf[row] - within * CHUNK).astype(np.int32)
        slot_w = wf[row]

        # per-query slot positions (row-major scatter); input is built in
        # ascending qi order, so a stable cumcount is just arange - group start
        counts = np.bincount(slot_q, minlength=Q_pad)
        group_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
        pos = np.arange(len(slot_q)) - group_start[slot_q]

        # small (latency-bound) batches get a high S floor so nearly every
        # solo query lands on ONE warm compile shape; large (throughput-
        # bound) batches size S tightly — their shape amortizes over the
        # batch and the first msearch warms it
        S = next_pow2(int(counts.max()), floor=32 if Q_pad <= 32 else 4)
        packed = np.zeros((Q_pad, 3 * S + 1), np.int32)
        packed[slot_q, pos] = slot_start
        packed[slot_q, S + pos] = slot_len
        packed[slot_q, 2 * S + pos] = slot_w.view(np.int32)
        packed[:, 3 * S] = min_match
        return packed, S, R

    # -- filter columns (lazy, cached) -------------------------------------

    def filter_column(self, name: str) -> PackedFilterColumn | None:
        """The int32 ordinal column for one field over the global doc space
        (`PackedFilterColumn`). None = no segment has the field (a filter on
        it matches nothing). Raises FilterColumnRefused when the request
        breaker refuses the device bytes — the caller serves via the
        per-segment lane."""
        if name in self._filter_cols:
            return self._filter_cols[name]
        has_kw = any(name in seg.keywords for _, seg in self.entries)
        has_num = any(name in seg.numerics for _, seg in self.entries)
        if not has_kw and not has_num:
            self._filter_cols[name] = None
            return None
        if self.breaker is not None:
            from ..common.breaker import CircuitBreakingException
            try:
                self.breaker.add_estimate(self.n_pad_total * 4)
            except CircuitBreakingException as e:
                raise FilterColumnRefused(name) from e
        col = self._ranked_column(name, "numeric" if has_num else "keyword")
        self._filter_cols[name] = col
        return col

    def _ranked_column(self, name: str, kind: str, first_entry: int = 0,
                       head: PackedFilterColumn | None = None
                       ) -> PackedFilterColumn:
        """Build the column of field `name` from entries[first_entry:],
        after `head`, a base view's column over the entries before them.
        Every segment's own distinct values are merged into the view's
        (with `head.distinct`), and a row's rank goes through its segment's
        lookup table; -1 = no value, and every padding row: the row that
        `take(..., mode="clip")` reads for PACKED_PAD_DOC is one. The old
        rows come over on the device as they are, unless the new segments
        bring values the base had not seen: then every old rank moves up by
        the new values below it (`reranked` on the span counts those rows)."""
        built = tracing.span("packed.filter_column", field=name, kind=kind,
                             bytes=self.n_pad_total * 4)
        with built:
            parts = []                  # (first row, own values, own ranks)
            for ei in range(first_entry, len(self.entries)):
                own = _segment_ranks(self.entries[ei][1], name, kind)
                if own is not None:
                    parts.append((int(self.bases[ei]), *own))
            distinct = np.unique(np.concatenate(
                ([] if head is None else [head.distinct])
                + [values for _, values, _ in parts]))
            lo = int(self.bases[first_entry])
            rows = np.full(self.n_pad_total - lo, -1, np.int32)
            for at, values, ranks in parts:
                rows[at - lo:at - lo + len(ranks)] = \
                    _ranks_in(distinct, values)[ranks]
            vals = jnp.asarray(rows)
            if head is not None:
                old = head.vals[:lo]
                grew = len(distinct) != len(head.distinct)
                if grew:
                    old = jnp.asarray(_ranks_in(
                        distinct, head.distinct)[np.asarray(old)])
                vals = jnp.concatenate([old, vals])
                built.attrs["reranked"] = lo if grew else 0
            built.attrs.update(distinct=len(distinct),
                               host_bytes=distinct.nbytes)
        self.memory_bytes += self.n_pad_total * 4
        return PackedFilterColumn(kind, vals, distinct)

    def _filter_streams(self, pf: PackedField, field: str, fields: tuple):
        """-> the batch's rank streams, one a column of `fields` in order,
        each made once a view (`_filter_stream`) and counted at every use."""
        for name in fields:
            state = "reused" if (field, name) in self._rank_streams else "made"
            if state == "made":
                self._filter_stream(pf, field, name)
            record_filter_stream(state)
        return tuple(self._rank_streams[field, name] for name in fields)

    def _filter_descriptors(self, queries: list[PackedQuery], Q_pad: int):
        """-> (fields tuple, fr_col, fr_lo, fr_hi, fr_neg, ft_col,
        ft_targets, ft_neg) numpy descriptor arrays for the kernel
        (`bm25_serve_packed_filtered` has their meaning): every bound and
        target as an ordinal, looked up among the column's distinct values
        in the column's own type, all of a batch's at once a column.
        Raises FilterColumnRefused if a needed column was breaker-refused."""
        from ..search.query_dsl import RangeNode, TermFilterNode

        fields: list[str] = []
        asked: dict[str, _ColumnKeys | None] = {}

        def column(name):
            if name not in asked:
                col = self.filter_column(name)
                asked[name] = None if col is None \
                    else _ColumnKeys(col.distinct, len(fields))
                if col is not None:
                    fields.append(name)
            keys = asked[name]
            return (-2 if keys is None else keys.index), keys

        fr_col = np.full((Q_pad, F_RANGE), -1, np.int32)
        fr_ends = np.zeros((2, Q_pad, F_RANGE), np.int32)    # low | high
        fr_neg = np.zeros((Q_pad, F_RANGE), np.int32)
        ft_col = np.full((Q_pad, F_TERM), -1, np.int32)
        ft_targets = np.full((Q_pad, F_TERM, F_TERM_VALS), NO_ORDINAL,
                             np.int32)
        ft_neg = np.zeros((Q_pad, F_TERM), np.int32)

        for qi, q in enumerate(queries):
            ri = ti = 0
            for neg, node in q.filters:
                ci, keys = column(node.field_name)
                if isinstance(node, RangeNode):
                    lo, hi, inc_lo, inc_hi = node.bounds_per_query[0]
                    if keys is not None:
                        # no bound: the least (0) / the greatest rank
                        if lo is not None:
                            keys.range_end(0, qi, ri, lo, inc_lo)
                        if hi is not None:
                            keys.range_end(1, qi, ri, hi, inc_hi)
                        else:
                            fr_ends[1, qi, ri] = len(keys.distinct) - 1
                    fr_col[qi, ri] = ci
                    if neg:
                        fr_neg[qi, ri] = 1
                    ri += 1
                elif isinstance(node, TermFilterNode):
                    vals = node.values_per_query[0] \
                        if node.values_per_query else []
                    if keys is not None:
                        for vi, v in enumerate(vals[:F_TERM_VALS]):
                            keys.target(qi, ti, vi, v)
                    ft_col[qi, ti] = ci
                    if neg:
                        ft_neg[qi, ti] = 1
                    ti += 1
        for keys in asked.values():
            if keys is not None:
                keys.resolve(fr_ends, ft_targets)
        return (tuple(fields), fr_col, fr_ends[0], fr_ends[1], fr_neg,
                ft_col, ft_targets, ft_neg)

    # -- host-side doc resolution ------------------------------------------

    def resolve(self, docs: np.ndarray):
        """global doc ids -> (entry_idx, local) via the base table."""
        ei = np.searchsorted(self.bases, docs, side="right") - 1
        ei = np.clip(ei, 0, len(self.entries) - 1)
        local = docs - self.bases[ei]
        return ei, local

    def source_of(self, doc: int):
        ei = int(np.searchsorted(self.bases, doc, side="right") - 1)
        seg = self.entries[ei][1]
        local = int(doc - self.bases[ei])
        return seg.stored[local], seg.types[local], seg.ids[local]

    def warmup(self, field: str,
               shapes=((1, 32, 16), (32, 32, 16), (1, 64, 16)),
               filtered_shapes=((1, 32, 16), (32, 32, 16))) -> None:
        """Precompile the solo + batcher shapes so first queries don't eat a
        multi-second XLA compile (p99 guard), with operands of the kinds
        `search` hands over: Q in {1, 32} at 32 slots and k 16, plain (and
        Q 1 at 64 slots) and filtered on one column, and the two folds of
        liveness (empty ones). What an `_msearch` runs (Q 256, more slots, a
        second column, k 1024) compiles on its first batch: a benchmark
        cell's replay."""
        pf = self._fields.get(field)
        if pf is None:
            return
        with pf.cv:     # both folds: a first delete must not compile
            while pf.in_use:
                pf.cv.wait()
            for by_list in (True, False):
                self._fold(pf, np.empty(0, np.int64), by_list)
        with self._folded_ids(pf) as doc_ids:
            common = doc_ids, pf.tf, pf.dl, *_device_scalars(1.2, 0.75, 1.0)
            for (q, s, k) in shapes:
                packed = np.zeros((q, 3 * s + 1), np.int32)
                packed[:, 3 * s] = 1
                bm25_serve_packed(packed, *common,
                                  S=s, CHUNK=CHUNK, R=4, k=k)
            no_column = (jnp.full(doc_ids.shape, -1, jnp.int32),)
            for (q, s, k) in filtered_shapes:
                packed = np.zeros((q, 3 * s + 1), np.int32)
                packed[:, 3 * s] = 1
                bm25_serve_packed_filtered(
                    packed, *common, no_column,
                    np.full((q, F_RANGE), -1, np.int32),
                    np.zeros((q, F_RANGE), np.int32),
                    np.zeros((q, F_RANGE), np.int32),
                    np.zeros((q, F_RANGE), np.int32),
                    np.full((q, F_TERM), -1, np.int32),
                    np.full((q, F_TERM, F_TERM_VALS), NO_ORDINAL, np.int32),
                    np.zeros((q, F_TERM), np.int32),
                    S=s, CHUNK=CHUNK, R=4, k=k,
                    FR=F_RANGE, FT=F_TERM, TV=F_TERM_VALS)

    def _filter_stream(self, pf: PackedField, field: str, name: str) -> None:
        """Make the rank stream of column `name` over text field `field`'s
        postings: i32[P_pad], each posting's document's rank in the column
        (`packed_filter_stream`), charged to the request breaker as the
        view's own (4 B a posting). Built from this view's column and
        postings, so a refresh, which makes a new view, never reads a stale
        one; a later fold leaves it as it is, since a dead posting's doc id
        is PACKED_PAD_DOC and the program masks it before its rank is read.
        Raises FilterColumnRefused when the breaker refuses the bytes: the
        batch is then served by the per-segment lane."""
        nbytes = 4 * int(pf.doc_ids.size)
        if self.breaker is not None:
            from ..common.breaker import CircuitBreakingException
            try:
                self.breaker.add_estimate(nbytes)
            except CircuitBreakingException as e:
                raise FilterColumnRefused(name) from e
        with tracing.span("packed.filter_stream", field=field, column=name,
                          postings=pf.total_p, bytes=nbytes), \
                self._folded_ids(pf) as doc_ids:
            self._rank_streams[field, name] = packed_filter_stream(
                self._filter_cols[name].vals, doc_ids)
        self.memory_bytes += nbytes


def _utf8_rows(ids: np.ndarray) -> np.ndarray:
    """U[n] -> uint8[n, w]: every string's UTF-8 bytes, NUL-padded. (At the
    end of the file: a line that moves above `search` or `warmup` gives
    every packed program a new compile-cache key, PERF.md section 6, PR 29.)"""
    points = ids.view(np.uint32).reshape(ids.shape[0], -1)
    if points.max(initial=0) < 128:          # ASCII: a code point is a byte
        return points.astype(np.uint8)
    enc = np.char.encode(ids, "utf-8")
    return enc.view(np.uint8).reshape(ids.shape[0], -1)


def _device_scalars(k1: float, b: float, avgdl: float) -> tuple:
    """(k1, b, avgdl, 0) as the f32 scalar operands of the packed programs:
    rounded on the host, one transfer, no operation on the device."""
    return tuple(jax.device_put([np.float32(k1), np.float32(b),
                                 np.float32(avgdl), np.float32(0.0)]))


def _ranks_in(distinct: np.ndarray, values: np.ndarray) -> np.ndarray:
    """i32[len(values) + 1]: the rank in `distinct` of each of `values` (all
    held by it), then -1, which a rank of -1 (no value) indexes."""
    return np.append(np.searchsorted(distinct, values), -1).astype(np.int32)


def _segment_ranks(seg: Segment, name: str, kind: str):
    """-> (the segment's own sorted distinct values of the field, in the
    column's 64-bit type: a whole-number column never passes through a
    float; each row's rank among them, -1 = no value), or None where the
    segment has no such column."""
    if kind == "keyword":
        kc = seg.keywords.get(name)
        if kc is None:
            return None
        values = np.empty(len(kc.values), object)
        values[:] = kc.values
        return values, np.asarray(kc.ords)[:seg.n_pad]
    nc = seg.numerics.get(name)
    if nc is None:
        return None
    vals = np.asarray(nc.vals)[:seg.n_pad]
    has = ~np.asarray(nc.missing)[:len(vals)]
    values, held = np.unique(vals[has], return_inverse=True)
    ranks = np.full(len(vals), -1, np.int32)
    ranks[has] = held
    return values, ranks


_I64_MIN, _I64_MAX = -2 ** 63, 2 ** 63 - 1


def _column_key(dtype_kind: str, x):
    """A bound or a target as it was sent -> (key in the column's own type,
    side), for a column whose distinct values' `dtype.kind` is `dtype_kind`
    ("i" int64, "f" float64, "O" str). side None where the key is `x` itself;
    True where `x` lies above the key and below the next value the type
    holds (a fraction over a whole-number column, or beyond int64), False
    where it lies below the least. Raises ValueError / TypeError on what is
    no value of the type."""
    if dtype_kind == "O":
        return str(x), None
    if type(x) is str:
        try:
            x = int(x)
        except ValueError:
            x = float(x)
    if dtype_kind == "f":
        return float(x), None
    side = None
    if type(x) is not int:              # a float, a bool
        if isinstance(x, float):
            if math.isinf(x):
                return (_I64_MAX, True) if x > 0 else (_I64_MIN, False)
            whole = math.floor(x)
            side = None if whole == x else True
            x = whole
        x = int(x)
    if _I64_MIN <= x <= _I64_MAX:
        return x, side
    return (_I64_MAX, True) if x > 0 else (_I64_MIN, False)


class _ColumnKeys:
    """What one batch asks of one filter column: its range ends and term
    targets as keys in the column's own type, all turned into ordinals by
    ONE `searchsorted` over the column's distinct values (`resolve`)."""

    def __init__(self, distinct: np.ndarray, index: int):
        self.distinct = distinct
        self.dtype_kind = distinct.dtype.kind
        self.index = index              # of the column in the batch's stack
        # (key, right, high, then the three indices of where the rank goes:
        # fr_ends[high, qi, ri] | ft_targets[qi, ti, vi]). A range end's
        # rank is searchsorted-left + (right and held) - high; high -1 marks
        # a term target
        self.asked: list[tuple] = []

    def range_end(self, high: int, qi: int, ri: int, x,
                  inclusive: bool) -> None:
        """`gte` / `gt` (high 0) -> the least rank inside, `lte` / `lt`
        (high 1) -> the greatest: an inclusive interval of ranks."""
        key, side = _column_key(self.dtype_kind, x)
        if side is None:
            side = inclusive if high else not inclusive
        self.asked.append((key, side, high, high, qi, ri))

    def target(self, qi: int, ti: int, vi: int, x) -> None:
        try:
            key, side = _column_key(self.dtype_kind, x)
        except (TypeError, ValueError):
            return                      # no value of the type: equals no row
        if side is None:
            self.asked.append((key, False, -1, qi, ti, vi))

    def resolve(self, fr_ends: np.ndarray, ft_targets: np.ndarray) -> None:
        if not self.asked:
            return
        d = self.distinct
        asked_keys, right, high, *at = zip(*self.asked)
        keys = np.empty(len(asked_keys), d.dtype)
        keys[:] = asked_keys
        pos = np.searchsorted(d, keys)
        held = d[np.minimum(pos, len(d) - 1)] == keys if len(d) \
            else np.zeros(len(keys), bool)
        at, high = np.asarray(at), np.asarray(high)
        ends, hits = high >= 0, (high < 0) & held
        fr_ends[tuple(at[:, ends])] = \
            (pos + (np.asarray(right) & held) - high)[ends]
        ft_targets[tuple(at[:, hits])] = pos[hits]
