"""Immutable tensor segments — the Lucene-segment analog, resident on device.

A segment is an immutable batch of documents (SURVEY.md §7 core bet):
  * text field   -> CSR postings tensors (term_offsets host-side, doc_ids/tf
                    on device) + per-doc field length (norms analog)
  * keyword      -> ordinal column i32[N] (+ host ord<->value tables) — the
                    global-ordinals analog (ref index/fielddata/ordinals/)
  * long/date/ip -> i64 column + missing mask (doc-values analog,
                    ref index/fielddata/plain/)
  * double/float -> f64 column + missing mask
  * dense_vector -> f32[N, dims] matrix for kNN / function_score
  * _source      -> host-side stored documents (fetch phase is host IO,
                    like the reference's stored-fields reads)
  * live         -> tombstone bitmap for deletes (Lucene liveDocs analog)

All device arrays are padded to size buckets (next power of two) so XLA
compile caches stay small while segments grow (SURVEY.md §7 hard part (e)).

Mutability model mirrors Lucene: segments are write-once; deletes only flip
the tombstone bitmap; updates are delete+reinsert into a newer segment; merges
rebuild (index/engine.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Any, Iterable

import numpy as np
import jax
import jax.numpy as jnp

import threading

from ..mapping.mapper import (
    ParsedDocument, FieldType, TEXT, KEYWORD, DATE, BOOLEAN, IP,
    NUMERIC_TYPES, _INT_TYPES, DENSE_VECTOR,
)
from ..ops.bm25_sparse import required_padding

# serializes fielddata builds across segments (see Segment.text_fielddata)
_FIELDDATA_LOCK = threading.Lock()


# hard cap on token positions per doc: phrase verification packs positions
# as doc * 2^21 + (pos - offset + 2^10), so pos + bias must stay < 2^21
# (search/query_dsl.py _POS_SHIFT / _POS_BIAS)
_MAX_DOC_POSITIONS = (1 << 21) - (1 << 11)


def next_pow2(n: int, floor: int = 8) -> int:
    n = max(n, floor)
    return 1 << (n - 1).bit_length()


def pad_to(arr: np.ndarray, size: int, fill=0) -> np.ndarray:
    if arr.shape[0] >= size:
        return arr
    pad_shape = (size - arr.shape[0],) + arr.shape[1:]
    return np.concatenate([arr, np.full(pad_shape, fill, dtype=arr.dtype)], axis=0)


# ---------------------------------------------------------------------------
# Per-field device structures
# ---------------------------------------------------------------------------

@dataclass
class TextFieldIndex:
    """CSR postings for one text field (ref: Lucene postings lists, consumed
    by ops/bm25.py (dense) and ops/bm25_sparse.py (sort-reduce hot path)
    instead of BulkScorer)."""
    terms: dict[str, int]            # term -> term id (lexicographic)
    term_starts: np.ndarray          # i32[V] host: CSR starts
    term_lens: np.ndarray            # i32[V] host: postings length == df
    doc_ids: jax.Array               # i32[P_pad] device
    tf: jax.Array                    # f32[P_pad] device
    doc_len: jax.Array               # f32[N_pad] device
    dl: jax.Array                    # f32[P_pad] device: per-POSTING doc len
                                     # (denormalized so the sparse kernel
                                     # needs no doc_len[doc] gather)
    sum_dl: float                    # Σ field length (for avgdl)
    n_postings: int                  # un-padded P
    max_df: int = 0                  # largest postings list (slot budgeting)
    # positions (Lucene .pos analog): per-posting slice into a flat
    # occurrence array. Host-side — phrase verification runs over candidate
    # postings slices, not the whole corpus. None when loaded from a commit
    # written before positions existed (phrase degrades to AND).
    doc_ids_host: np.ndarray | None = None   # i32[P] host mirror
    pos_starts: np.ndarray | None = None     # i32[P] into positions[]
    pos_lens: np.ndarray | None = None       # i32[P] == tf
    positions: np.ndarray | None = None      # i32[O] token positions

    def lookup(self, term: str) -> tuple[int, int, int]:
        """-> (start, length==df, term_id) or (0, 0, -1) if absent."""
        tid = self.terms.get(term, -1)
        if tid < 0:
            return 0, 0, -1
        return int(self.term_starts[tid]), int(self.term_lens[tid]), tid

    def term_range(self, lo: str | None, hi: str | None,
                   include_lo=True, include_hi=True, prefix: str | None = None,
                   limit: int = 1024) -> list[str]:
        """Terms in lexicographic range / with prefix (wildcard & range-on-text
        support). Host-side over the sorted term dict."""
        out = []
        for t in self.terms:  # insertion order == lexicographic (built sorted)
            if prefix is not None:
                if t.startswith(prefix):
                    out.append(t)
                elif out:
                    break
                continue
            if lo is not None and (t < lo or (not include_lo and t == lo)):
                continue
            if hi is not None and (t > hi or (not include_hi and t == hi)):
                break
            out.append(t)
            if len(out) >= limit:
                break
        return out


@dataclass
class KeywordColumn:
    """Ordinal-encoded keyword column (ref: index/fielddata ordinals)."""
    ord_map: dict[str, int]          # value -> ordinal (lexicographic)
    values: list[str]                # ordinal -> value
    ords: jax.Array                  # i32[N_pad], -1 = missing

    def ord_of(self, value: str) -> int:
        return self.ord_map.get(value, -1)


@dataclass
class NumericColumn:
    """Dense numeric doc-values column. i64 for long/date/ip/bool, f64 for
    double/float (x64 enabled in package __init__; TPU-hot paths cast to f32)."""
    vals: jax.Array                  # [N_pad]
    missing: jax.Array               # bool[N_pad]
    dtype: str                       # "i64" | "f64"


@dataclass
class IvfData:
    """IVF cluster layout for one vector column (ops/ann.py): k-means
    centroids + a cluster->doc CSR in exactly the postings layout text
    fields use — clusters are "terms", members sorted by doc id. Built
    once per (segment, field, nlist), cached breaker-charged in
    indices/cache_service.AnnIndexCache."""
    centroids: jax.Array             # f32[nlist, dims]
    starts: jax.Array                # i32[nlist]  CSR starts (device)
    sizes: jax.Array                 # i32[nlist]  cluster sizes (device)
    slot_docs: jax.Array             # i32[N_pad]  docs sorted by (cluster, doc)
    norms: jax.Array                 # f32[N_pad]  per-doc L2 norms
    sizes_desc_cum: np.ndarray       # i64[nlist]  cumsum of sizes, desc
    nlist: int
    n_docs: int
    dims: int
    nbytes: int


@dataclass
class QuantData:
    """Quantized storage tier for one vector column's IVF cluster scan
    (ops/ann.py, ISSUE 12): int8 per-dimension affine codes (1/4 the f32
    bytes) or IVF-PQ residual codes (m bytes/vector, 1/(4·D/m)). Built
    once per (segment, field, nlist, mode, m), cached breaker-charged in
    indices/cache_service.AnnIndexCache's `ann_quant` tier — codes and
    codebooks account as SEPARATE entries so the exposition shows both."""
    mode: str                        # "int8" | "pq"
    codes: jax.Array                 # i8[N_pad, D] (int8) | u8[N_pad, m] (pq)
    scales: jax.Array | None         # f32[D]           (int8)
    codebooks: jax.Array | None      # f32[m, 256, dsub] (pq)
    m: int                           # subquantizers (pq; 0 for int8)
    nlist: int                       # the IVF layout this encodes against
    codes_nbytes: int
    books_nbytes: int

    @property
    def nbytes(self) -> int:
        return self.codes_nbytes + self.books_nbytes


@dataclass
class VectorColumn:
    vecs: jax.Array                  # f32[N_pad, dims]
    dims: int

    def build_ivf(self, n_docs: int, nlist: int | None = None, *,
                  iters: int | None = None) -> "IvfData | None":
        """Train k-means centroids (device Lloyd iterations over a
        deterministic sample) and build the cluster->doc CSR with ONE
        composite-key argsort. None when the column is too small to
        cluster usefully (callers fall back to exact kNN)."""
        from ..ops import ann as ann_ops
        n_pad = int(self.vecs.shape[0])
        if nlist is None:
            nlist = ann_ops.auto_nlist(n_docs)
        nlist = int(nlist)
        if n_docs < 2 * nlist or nlist < 2:
            return None
        iters = int(iters or ann_ops.DEFAULT_ITERS)
        # deterministic strided sample of real docs (no RNG: refresh→query
        # cycles must reproduce the same clustering bit-for-bit). The
        # sample pads to a pow2 bucket by wrapping around, so the jitted
        # Lloyd program's shape — and its compile-cache entry — is stable
        # across same-bucket segment sizes (test_ann retrace tripwire).
        step = max(1, n_docs // ann_ops.TRAIN_SAMPLE_CAP)
        sample_idx = np.arange(0, n_docs, step,
                               dtype=np.int64)[: ann_ops.TRAIN_SAMPLE_CAP]
        s_pad = min(next_pow2(len(sample_idx)), ann_ops.TRAIN_SAMPLE_CAP)
        sample_idx = np.resize(sample_idx, s_pad).astype(np.int32)
        sample = self.vecs[jnp.asarray(sample_idx)]
        init_idx = sample_idx[:: max(1, len(sample_idx) // nlist)][:nlist]
        if len(init_idx) < nlist:
            return None
        init = self.vecs[jnp.asarray(init_idx)]
        cents = ann_ops.train_centroids(sample, init, nlist=nlist,
                                        iters=iters)
        blk = ann_ops.assign_block_size(n_pad)
        assign = np.asarray(ann_ops.assign_clusters(
            self.vecs, cents, block=blk))
        # padding rows park in a phantom cluster `nlist` that is never
        # probed; real docs keep their trained assignment
        assign = assign.astype(np.int64)
        assign[n_docs:] = nlist
        order = np.argsort(assign * (n_pad + 1)
                           + np.arange(n_pad, dtype=np.int64),
                           kind="stable").astype(np.int32)
        counts = np.bincount(assign, minlength=nlist + 1)[: nlist + 1]
        starts = np.zeros(nlist, np.int64)
        starts[1:] = np.cumsum(counts[: nlist - 1])
        starts = starts.astype(np.int32)
        sizes = counts[:nlist].astype(np.int32)
        sizes_desc = np.sort(sizes)[::-1].astype(np.int64)
        norms = jnp.linalg.norm(self.vecs, axis=1)
        return IvfData(
            centroids=cents, starts=jnp.asarray(starts),
            sizes=jnp.asarray(sizes), slot_docs=jnp.asarray(order),
            norms=norms, sizes_desc_cum=np.cumsum(sizes_desc),
            nlist=nlist, n_docs=n_docs, dims=self.dims,
            nbytes=ann_ops.ivf_nbytes(n_pad, nlist, self.dims))

    def build_quant(self, ivf: "IvfData", mode: str,
                    m: int | None = None, *,
                    iters: int | None = None) -> "QuantData | None":
        """Quantized codes for this column against `ivf`'s cluster layout
        (ISSUE 12 tentpole): int8 per-dimension affine scales + i8 codes,
        or IVF-PQ codebooks trained on residuals against each doc's
        assigned centroid + u8[N, m] codes. Deterministic throughout (the
        same no-RNG discipline as build_ivf — refresh→query cycles must
        reproduce the clustering AND the codes bit-for-bit). None when
        the shape can't quantize (dims not divisible by m, too few docs
        to train 256 codes) — callers fall back to the f32 IVF scan."""
        from ..common import tracing
        from ..ops import ann as ann_ops
        n_pad = int(self.vecs.shape[0])
        blk = ann_ops.assign_block_size(n_pad)
        if mode == "int8":
            scales = ann_ops.train_int8_scales(self.vecs)
            codes = ann_ops.quantize_int8(self.vecs, scales, block=blk)
            cb, bb = ann_ops.quant_nbytes(n_pad, self.dims, "int8", 0)
            return QuantData(mode="int8", codes=codes, scales=scales,
                             codebooks=None, m=0, nlist=ivf.nlist,
                             codes_nbytes=cb, books_nbytes=bb)
        if mode != "pq":
            return None
        m = int(m or ann_ops.DEFAULT_PQ_M)
        if m < 1 or self.dims % m or ivf.n_docs < ann_ops.PQ_CODES:
            return None
        # recover each doc's cluster from the IVF CSR (slot_docs is docs
        # sorted by (cluster, doc)): no second assignment pass needed
        sizes = np.asarray(ivf.sizes)
        slot_docs = np.asarray(ivf.slot_docs)
        assign = np.full(n_pad, ivf.nlist - 1, np.int32)  # padding: any
        total = int(sizes.sum())                          # real cluster —
        assign[slot_docs[:total]] = np.repeat(            # rows are dead
            np.arange(ivf.nlist, dtype=np.int32), sizes)
        # deterministic strided residual sample, pow2-padded by wraparound
        # (same discipline as the Lloyd sample above)
        step = max(1, ivf.n_docs // ann_ops.TRAIN_SAMPLE_CAP)
        sample_idx = np.arange(0, ivf.n_docs, step,
                               dtype=np.int64)[: ann_ops.TRAIN_SAMPLE_CAP]
        s_pad = min(next_pow2(len(sample_idx)), ann_ops.TRAIN_SAMPLE_CAP)
        sample_idx = np.resize(sample_idx, s_pad).astype(np.int32)
        sv = self.vecs[jnp.asarray(sample_idx)]
        sa = jnp.asarray(assign[sample_idx])
        resid = (sv - ivf.centroids[sa]).reshape(
            s_pad, m, self.dims // m)
        samples = jnp.moveaxis(resid, 1, 0)               # [m, S, dsub]
        stride = max(1, s_pad // ann_ops.PQ_CODES)
        inits = samples[:, ::stride, :][:, : ann_ops.PQ_CODES, :]
        if inits.shape[1] < ann_ops.PQ_CODES:
            return None
        with tracing.span("pq_train", m=m, nlist=ivf.nlist,
                          sample=s_pad):
            books = ann_ops.train_pq_codebooks(
                samples, inits,
                iters=int(iters or ann_ops.DEFAULT_ITERS))
        codes = ann_ops.encode_pq(self.vecs, jnp.asarray(assign),
                                  ivf.centroids, books, block=blk)
        cb, bb = ann_ops.quant_nbytes(n_pad, self.dims, "pq", m)
        return QuantData(mode="pq", codes=codes, scales=None,
                        codebooks=books, m=m, nlist=ivf.nlist,
                        codes_nbytes=cb, books_nbytes=bb)


# ---------------------------------------------------------------------------
# Segment
# ---------------------------------------------------------------------------

@dataclass
class Segment:
    seg_id: int
    n_docs: int                      # real docs (un-padded)
    n_pad: int
    text: dict[str, TextFieldIndex]
    keywords: dict[str, KeywordColumn]
    numerics: dict[str, NumericColumn]
    vectors: dict[str, VectorColumn]
    stored: list[dict]               # host _source per local doc
    ids: list[str]                   # host _id per local doc
    types: list[str]                 # host _type per local doc
    id_to_local: dict[str, int]
    live_host: np.ndarray            # bool[N_pad] host mirror
    live_count: int = 0
    versions: list[int] = dc_field(default_factory=list)  # per local doc
    routings: list = dc_field(default_factory=list)       # per local doc
    # block-join layout (ref Lucene block join / ObjectMapper nested mode):
    # nested sub-document rows carry the local id of their ROOT document;
    # root rows carry -1. None when the segment has no nested rows (the
    # common case — zero overhead). Nested rows also appear in the
    # `_nested_path` keyword column; they are excluded from every normal
    # query/agg via root_live and only reachable through nested queries.
    parent_of: np.ndarray | None = None   # i32[N_pad] host

    def __post_init__(self):
        # device liveness is uploaded lazily: deletes only dirty the host
        # mirror, so a burst of deletes costs ONE upload at the next search
        # instead of an O(N) device_put per delete
        self._live_dev: jax.Array | None = None
        self._live_dirty = True
        self._live_padded: jax.Array | None = None
        self._live_all_dev: jax.Array | None = None
        self._parent_dev: jax.Array | None = None
        # monotonic tombstone generation: serving views (serving/packed_view)
        # fold liveness into their packed postings keyed on this, so
        # delete-only changes cost one fold instead of rebuilding the view
        self.live_gen = 0
        if not self.live_count:
            self.live_count = int(self.live_host[: self.n_docs].sum())
        if not self.versions:
            self.versions = [1] * self.n_docs
        if not self.routings:
            self.routings = [None] * self.n_docs

    @property
    def live(self) -> jax.Array:
        """bool[N_pad] device ROOT-doc liveness: tombstone bitmap AND not a
        nested sub-row (Lucene liveDocs + the root-documents filter every
        top-level query carries, ref NonNestedDocsFilter). Queries, aggs and
        the packed/sparse lanes all consume this; nested rows are reachable
        only through `live_all` (the raw bitmap) inside nested queries."""
        if self._live_dirty or self._live_dev is None:
            self._live_dev = jnp.asarray(self.root_live_host)
            self._live_all_dev = None
            self._live_padded = None
            self._live_dirty = False
        return self._live_dev

    @property
    def root_live_host(self) -> np.ndarray:
        """bool[N_pad] host: live AND root (nested rows excluded)."""
        if self.parent_of is None:
            return self.live_host
        return self.live_host & (self.parent_of < 0)

    @property
    def live_all(self) -> jax.Array:
        """bool[N_pad] device raw tombstone bitmap INCLUDING nested rows —
        only nested-query/agg evaluation wants this."""
        if self.parent_of is None:
            return self.live
        if self._live_dirty or getattr(self, "_live_all_dev", None) is None:
            _ = self.live                       # refresh both mirrors
            self._live_all_dev = jnp.asarray(self.live_host)
        return self._live_all_dev

    @property
    def parent_dev(self) -> jax.Array | None:
        """i32[N_pad] device mirror of parent_of (lazy)."""
        if self.parent_of is None:
            return None
        if getattr(self, "_parent_dev", None) is None:
            self._parent_dev = jnp.asarray(self.parent_of)
        return self._parent_dev

    @property
    def root_live_count(self) -> int:
        """Live ROOT docs (what doc_count means to users)."""
        if self.parent_of is None:
            return self.live_count
        return int(self.root_live_host[: self.n_docs].sum())

    def delete_local(self, local: int) -> bool:
        """Flip the tombstone bit (cascading to the doc's nested block rows).
        Returns True if the doc was live."""
        if not self.live_host[local]:
            return False
        self.live_host[local] = False
        if self.parent_of is not None:
            for child in np.flatnonzero(self.parent_of == local):
                if self.live_host[child]:
                    self.live_host[child] = False
                    self.live_count -= 1
        self._live_dirty = True
        self.live_gen += 1
        self.live_count -= 1
        return True

    def live_padded(self):
        """bool[1, n_pad+1] liveness with a False PAD-sentinel column —
        the doc_mask shape ops/bm25_sparse.bm25_topk_sparse_masked gathers
        at candidate slots. Cached; invalidated on delete."""
        live = self.live                 # refreshes the dirty device mirror
        if self._live_padded is None:
            self._live_padded = jnp.concatenate(
                [live, jnp.zeros((1,), bool)])[None, :]
        return self._live_padded

    def doc_freq(self, field: str, term: str) -> int:
        fx = self.text.get(field)
        if fx is None:
            return 0
        return fx.lookup(term)[1]

    def total_term_freq(self, field: str, term: str) -> float:
        """Sum of the term's frequencies across its postings (Lucene
        totalTermFreq — the LM similarities' collection probability
        numerator). One small device slice-sum per (term, segment)."""
        fx = self.text.get(field)
        if fx is None:
            return 0.0
        s, ln, _ = fx.lookup(term)
        if ln == 0:
            return 0.0
        return float(np.asarray(fx.tf[s: s + ln]).sum())

    def field_stats(self, field: str) -> tuple[float, int]:
        """(sum_dl, doc_count) for avgdl computation across segments."""
        fx = self.text.get(field)
        if fx is None:
            return 0.0, 0
        return fx.sum_dl, self.n_docs

    def text_fielddata(self, field: str):
        """Lazily-built fielddata for sorting an ANALYZED text field:
        per-doc min/max term ordinal (Lucene's uninverted fielddata +
        MultiValueMode MIN/MAX; ref index/fielddata/plain/
        PagedBytesIndexFieldData.java — loaded on first sort, cached, and
        reported by `_cat/fielddata`).

        -> (min_ords i64[n_pad], max_ords i64[n_pad], missing bool[n_pad],
            vocab list[str], nbytes) or None if the field has no postings.
        """
        # one lock for all fielddata builds: concurrent first sorts on the
        # same field must not both build + charge the breaker (the release
        # paths only see ONE build's bytes)
        with _FIELDDATA_LOCK:
            return self._text_fielddata_locked(field)

    def _text_fielddata_locked(self, field: str):
        if self.text.get(field) is None:
            return None
        fdc = getattr(self, "fielddata_cache", None)
        if fdc is not None:
            # node-level fielddata tier (indices/cache_service): LRU
            # storage + breaker charge with eviction-under-pressure —
            # admission happens inside get_or_build, before the build
            return fdc.get_or_build(self, field,
                                    lambda: self._build_fielddata(field))
        cache = getattr(self, "_fielddata", None)
        if cache is None:
            cache = self._fielddata = {}
        fd = cache.get(field)
        if fd is not None:
            return fd
        breaker = getattr(self, "breaker", None)
        if breaker is not None:
            # admission control BEFORE building: loading fielddata under
            # memory pressure 429s cleanly (ref fielddata breaker in
            # HierarchyCircuitBreakerService)
            breaker.add_estimate(self.n_pad * 17)
        fd = self._build_fielddata(field)
        cache[field] = fd
        return fd

    def _build_fielddata(self, field: str):
        """Uninvert one text field into per-doc min/max term ordinals —
        the expensive part both caching paths share."""
        fx = self.text.get(field)
        V = len(fx.terms)
        lens = np.asarray(fx.term_lens[:V], np.int64)
        starts = np.asarray(fx.term_starts[:V], np.int64)
        docs_host = fx.doc_ids_host if fx.doc_ids_host is not None \
            else np.asarray(fx.doc_ids)
        total = int(lens.sum())
        # posting index per (term, occurrence): CSR starts + within offsets
        off = np.arange(total, dtype=np.int64) \
            - np.repeat(np.cumsum(lens) - lens, lens)
        pos = np.repeat(starts, lens) + off
        docs = np.asarray(docs_host, np.int64)[pos]
        tids = np.repeat(np.arange(V, dtype=np.int64), lens)
        mn = np.full(self.n_pad, V, np.int64)
        np.minimum.at(mn, docs, tids)
        mx = np.full(self.n_pad, -1, np.int64)
        np.maximum.at(mx, docs, tids)
        miss = mx < 0
        return (mn, mx, miss, list(fx.terms),
                mn.nbytes + mx.nbytes + miss.nbytes)

    def fielddata_bytes(self) -> dict[str, int]:
        """field -> loaded fielddata bytes (empty until a sort loads it)."""
        fdc = getattr(self, "fielddata_cache", None)
        if fdc is not None:
            return fdc.bytes_for(self)
        return {f: fd[4]
                for f, fd in getattr(self, "_fielddata", {}).items()}

    def memory_bytes(self) -> int:
        total = 0
        for fx in self.text.values():
            total += fx.doc_ids.size * 4 + fx.tf.size * 4 + fx.doc_len.size * 4 \
                + fx.dl.size * 4
        for kc in self.keywords.values():
            total += kc.ords.size * 4
        for nc in self.numerics.values():
            total += nc.vals.size * 8 + nc.missing.size
        for vc in self.vectors.values():
            total += vc.vecs.size * 4
        return total


# ---------------------------------------------------------------------------
# Builder (host-side, numpy)
# ---------------------------------------------------------------------------

class SegmentBuilder:
    """Accumulates parsed documents, then freezes them into a Segment.

    The analog of Lucene's IndexWriter in-memory buffer + flush
    (ref index/engine/InternalEngine.java — IndexWriter.updateDocument), but
    the "flush" produces dense tensors instead of an on-disk segment.
    """

    def __init__(self, seg_id: int = 0):
        self.seg_id = seg_id
        self._postings: dict[str, dict[str, list]] = {}   # field -> term -> [(doc, tf)]
        self._doc_len: dict[str, dict[int, float]] = {}   # field -> doc -> len
        self._keywords: dict[str, dict[int, str]] = {}    # field -> doc -> value (first)
        self._longs: dict[str, dict[int, int]] = {}
        self._doubles: dict[str, dict[int, float]] = {}
        self._vectors: dict[str, dict[int, list[float]]] = {}
        self._vector_dims: dict[str, int] = {}
        # columnar side-store fed by add_batch (the vectorized bulk lane):
        # text fields accumulate OCCURRENCE arrays (term id into the
        # field's growing vocab dict, doc local, within-doc position) and
        # the scalar channels accumulate (locals, values) pairs; build()
        # merges them with the per-doc dicts through one lexsort per field
        self._batch_text: dict[str, dict] = {}
        # field -> ([locals lists], [token-count lists]): columnar doc_len
        # (doc lengths are integers, so float summation is EXACT in any
        # order — vectorizing cannot drift sum_dl/avgdl)
        self._batch_doclen: dict[str, tuple[list, list]] = {}
        self._batch_keywords: dict[str, tuple[list, list]] = {}
        self._batch_longs: dict[str, tuple[list, list]] = {}
        self._batch_doubles: dict[str, tuple[list, list]] = {}
        self._batch_vectors: dict[str, tuple[list, list]] = {}
        self._csr_memo: dict | None = None
        self.stored: list[dict] = []
        self.ids: list[str] = []
        self.types: list[str] = []
        self.versions: list[int] = []
        self.routings: list = []
        self.id_to_local: dict[str, int] = {}
        self.parent_of: list[int] = []   # per row; -1 = root
        self.n_docs = 0

    def add(self, doc: ParsedDocument, type_name: str = "_doc",
            version: int = 1) -> int:
        """Add one document — and its nested block, children-first, root
        last (Lucene block-join order; ref ObjectMapper nested mode).
        Returns the ROOT row's local id."""
        # validate BEFORE mutating builder state: a mid-add raise must not
        # leave a half-indexed ghost doc behind (code review r3)
        for d in [doc] + [sub for _, sub in doc.nested]:
            for field, tokens in d.tokens.items():
                if len(tokens) > _MAX_DOC_POSITIONS:
                    # position keys pack as doc * 2^21 + (pos + bias); a
                    # longer doc would collide with its neighbor's key space
                    # (search/query_dsl.py _POS_SHIFT/_POS_BIAS; advisor r2)
                    raise ValueError(
                        f"field [{field}] has {len(tokens)} tokens; the "
                        f"maximum is {_MAX_DOC_POSITIONS} per document")
        child_rows: list[int] = []
        for path, sub in doc.nested:
            row = self._add_row(sub, "__" + path, version,
                                doc_id=f"{doc.doc_id}#n{self.n_docs}",
                                register_id=False)
            self._keywords.setdefault("_nested_path", {})[row] = path
            child_rows.append(row)
        local = self._add_row(doc, type_name, version, doc_id=doc.doc_id,
                              register_id=True)
        for r in child_rows:
            self.parent_of[r] = local
        return local

    def _add_row(self, doc: ParsedDocument, type_name: str, version: int,
                 doc_id: str, register_id: bool) -> int:
        local = self.n_docs
        self.n_docs += 1
        self._csr_memo = None
        self.stored.append(doc.source)
        self.ids.append(doc_id)
        self.types.append(type_name)
        self.versions.append(version)
        self.routings.append(doc.routing)
        self.parent_of.append(-1)
        if register_id:
            self.id_to_local[doc_id] = local

        for field, tokens in doc.tokens.items():
            fld = self._postings.setdefault(field, {})
            pos_map: dict[str, list[int]] = {}
            for p, t in enumerate(tokens):
                pos_map.setdefault(t, []).append(p)
            for t, ps in pos_map.items():
                fld.setdefault(t, []).append((local, len(ps), ps))
            self._doc_len.setdefault(field, {})[local] = float(len(tokens))
        for field, vals in doc.keywords.items():
            if vals:
                self._keywords.setdefault(field, {})[local] = vals[0]
        for field, vals in doc.longs.items():
            if vals:
                self._longs.setdefault(field, {})[local] = vals[0]
        for field, vals in doc.numerics.items():
            if vals:
                self._doubles.setdefault(field, {})[local] = vals[0]
        for field, (lat, lon) in doc.geo.items():
            # geo_point lands as two numeric columns — persistence, merge,
            # breaker accounting and columnar filters all come for free
            # (queries read <field>.lat / <field>.lon; search/query_parser)
            self._doubles.setdefault(field + ".lat", {})[local] = lat
            self._doubles.setdefault(field + ".lon", {})[local] = lon
        for field, vec in doc.vectors.items():
            self._vectors.setdefault(field, {})[local] = vec
            self._vector_dims[field] = len(vec)
        return local

    def add_batch(self, batch: list[tuple[ParsedDocument, str, int]]) -> list[int]:
        """Columnar append of a run of parsed documents — the vectorized
        bulk lane's segment write (ISSUE 7). Entries are (parsed, type,
        version) tuples WITHOUT nested blocks (the caller routes nested
        docs through add()). Builder state ends EXACTLY as sequential
        add() calls would leave it — same locals, same per-(term, doc)
        postings/positions, same ordinal/numeric/vector values — but text
        tokens land as numpy occurrence blocks and the scalar channels as
        (locals, values) runs, so build() does one lexsort per field
        instead of per-token dict work. Returns the new local ids."""
        base = self.n_docs
        # pass 1 — collect into LOCAL structures, validating as we go: no
        # builder state mutates until the whole batch has been walked, so
        # a mid-batch raise leaves no half-indexed ghost docs (mirror add())
        fld: dict[str, tuple] = {}      # field -> (locals, toks, encs, lens)
        fld_get = fld.get
        scalars: dict[int, dict] = {0: {}, 1: {}, 2: {}, 3: {}}
        kw_loc, long_loc, dbl_loc, vec_loc = (scalars[i] for i in range(4))
        max_pos = _MAX_DOC_POSITIONS
        for i, (doc, type_name, version) in enumerate(batch):
            if doc.nested:
                raise ValueError("add_batch cannot take nested blocks; "
                                 "route nested documents through add()")
            local = base + i
            enc = doc.token_enc
            for field, tokens in doc.tokens.items():
                n_tok = len(tokens)
                if n_tok > max_pos:
                    raise ValueError(
                        f"field [{field}] has {n_tok} tokens; the "
                        f"maximum is {max_pos} per document")
                ent = fld_get(field)
                if ent is None:
                    ent = fld[field] = ([], [], [], [])
                ent[0].append(local)
                ent[1].append(tokens)
                ent[2].append(enc.get(field) if enc is not None else None)
                ent[3].append(n_tok)
            if doc.keywords:
                for field, vals in doc.keywords.items():
                    if vals:
                        blk = kw_loc.get(field)
                        if blk is None:
                            blk = kw_loc[field] = ([], [])
                        blk[0].append(local)
                        blk[1].append(vals[0])
            if doc.longs:
                for field, vals in doc.longs.items():
                    if vals:
                        blk = long_loc.get(field)
                        if blk is None:
                            blk = long_loc[field] = ([], [])
                        blk[0].append(local)
                        blk[1].append(vals[0])
            if doc.numerics:
                for field, vals in doc.numerics.items():
                    if vals:
                        blk = dbl_loc.get(field)
                        if blk is None:
                            blk = dbl_loc[field] = ([], [])
                        blk[0].append(local)
                        blk[1].append(vals[0])
            if doc.geo:
                for field, (lat, lon) in doc.geo.items():
                    for suffix, val in ((".lat", lat), (".lon", lon)):
                        blk = dbl_loc.get(field + suffix)
                        if blk is None:
                            blk = dbl_loc[field + suffix] = ([], [])
                        blk[0].append(local)
                        blk[1].append(val)
            if doc.vectors:
                for field, vec in doc.vectors.items():
                    blk = vec_loc.get(field)
                    if blk is None:
                        blk = vec_loc[field] = ([], [])
                    blk[0].append(local)
                    blk[1].append(vec)
        # pass 2 — commit: one C-level extend per column instead of seven
        # appends per doc
        self._csr_memo = None
        self.stored.extend(d.source for d, _t, _v in batch)
        self.ids.extend(d.doc_id for d, _t, _v in batch)
        self.types.extend(t for _d, t, _v in batch)
        self.versions.extend(v for _d, _t, v in batch)
        self.routings.extend(d.routing for d, _t, _v in batch)
        self.parent_of.extend([-1] * len(batch))
        self.id_to_local.update(
            zip((d.doc_id for d, _t, _v in batch),
                range(base, base + len(batch))))
        for local_map, store in ((kw_loc, self._batch_keywords),
                                 (long_loc, self._batch_longs),
                                 (dbl_loc, self._batch_doubles)):
            for field, (locs, vals) in local_map.items():
                blk = store.get(field)
                if blk is None:
                    store[field] = (locs, vals)
                else:
                    blk[0].extend(locs)
                    blk[1].extend(vals)
        for field, (locs, vecs) in vec_loc.items():
            blk = self._batch_vectors.get(field)
            if blk is None:
                self._batch_vectors[field] = (locs, vecs)
            else:
                blk[0].extend(locs)
                blk[1].extend(vecs)
            self._vector_dims[field] = len(vecs[-1])
        # text: encode occurrences against the field's growing vocab dict.
        # Docs that carry analysis-time integer encodings (ParsedDocument
        # .token_enc, filled by the bulk lane's TextBatcher) skip the
        # per-token dict encode entirely: their per-flush output vocab
        # remaps onto the builder vocab once per UNIQUE token, and the
        # occurrence ids are one numpy gather.
        for field, (locals_l, tok_lists, encs, lens_l) in fld.items():
            dlblk = self._batch_doclen.get(field)
            if dlblk is None:
                dlblk = self._batch_doclen[field] = ([], [])
            dlblk[0].append(locals_l)
            dlblk[1].append(lens_l)
            blk = self._batch_text.get(field)
            if blk is None:
                blk = self._batch_text[field] = {
                    "vocab": {}, "tids": [], "docs": [], "poss": []}
            vocab = blk["vocab"]
            setd = vocab.setdefault
            # split into encoded doc groups (by shared analysis vocab) and
            # the string-encode remainder
            enc_groups: dict[int, tuple] = {}  # id(avocab) -> (avocab, locals, ids)
            str_locals: list[int] = []
            str_toklists: list[list[str]] = []
            for local, toks, enc_list in zip(locals_l, tok_lists, encs):
                if enc_list:
                    avocab = enc_list[0][0]
                    if len(enc_list) == 1:
                        ids_arr = enc_list[0][1]
                    elif all(e[0] is avocab for e in enc_list[1:]):
                        ids_arr = np.concatenate([e[1] for e in enc_list])
                    else:       # mixed vocabs can't happen in one flush;
                        avocab = None               # be safe anyway
                    if avocab is not None and len(ids_arr) == len(toks):
                        g = enc_groups.get(id(avocab))
                        if g is None:
                            g = enc_groups[id(avocab)] = (avocab, [], [])
                        g[1].append(local)
                        g[2].append(ids_arr)
                        continue
                str_locals.append(local)
                str_toklists.append(toks)
            for avocab, locs, ids_arrs in enc_groups.values():
                lens = np.fromiter(map(len, ids_arrs), np.int64,
                                   count=len(ids_arrs))
                total = int(lens.sum())
                if not total:
                    continue
                local_ids = np.concatenate(ids_arrs)
                # remap analysis-vocab ids -> field-vocab ids, registering
                # ONLY tokens this field actually uses (the analysis vocab
                # is shared across all fields of an analyzer — blanket
                # registration would leak other fields' terms in here)
                used = np.unique(local_ids)
                lut = np.zeros(int(used[-1]) + 1, np.int64)
                for i in used.tolist():
                    lut[i] = setd(avocab[i], len(vocab))
                blk["tids"].append(lut[local_ids])
                blk["docs"].append(
                    np.repeat(np.asarray(locs, np.int64), lens))
                cum = np.cumsum(lens)
                blk["poss"].append(
                    np.arange(total, dtype=np.int64)
                    - np.repeat(cum - lens, lens))
            if str_toklists:
                ids: list[int] = []
                app = ids.append
                counts = np.empty(len(str_toklists), np.int64)
                for di, toks in enumerate(str_toklists):
                    counts[di] = len(toks)
                    for t in toks:
                        app(setd(t, len(vocab)))
                total = int(counts.sum())
                if total:
                    blk["tids"].append(np.asarray(ids, np.int64))
                    blk["docs"].append(
                        np.repeat(np.asarray(str_locals, np.int64),
                                  counts))
                    # within-doc position = index into doc.tokens[field]
                    cum = np.cumsum(counts)
                    blk["poss"].append(
                        np.arange(total, dtype=np.int64)
                        - np.repeat(cum - counts, counts))
        self.n_docs = base + len(batch)
        return list(range(base, self.n_docs))

    def _text_csr_all(self) -> dict[str, dict]:
        """Merge per-doc dict postings and columnar occurrence blocks into
        the final per-field CSR layout (one lexsort per field). Memoized —
        estimate_bytes() and build() run back-to-back in refresh and must
        see the same layout; any add invalidates."""
        if self._csr_memo is not None:
            return self._csr_memo
        out: dict[str, dict] = {}
        fields = list(self._postings)
        for f in self._batch_text:
            if f not in self._postings:
                fields.append(f)
        for field in fields:
            term_map = self._postings.get(field, {})
            blk = self._batch_text.get(field)
            vocab_set = set(term_map)
            if blk is not None:
                vocab_set.update(blk["vocab"])
            union_terms = sorted(vocab_set)
            tid_of = {t: i for i, t in enumerate(union_terms)}
            V = len(union_terms)
            occ_t, occ_d, occ_p = [], [], []
            if term_map:
                # expand the per-doc dict's (term, doc) entries into
                # occurrences (same loop cost the old build paid)
                tids: list[int] = []
                docs: list[int] = []
                lens: list[int] = []
                flat: list[int] = []
                for t, lst in term_map.items():
                    ti = tid_of[t]
                    for d, c, ps in lst:
                        tids.append(ti)
                        docs.append(d)
                        lens.append(c)
                        flat.extend(ps)
                lens_a = np.asarray(lens, np.int64)
                occ_t.append(np.repeat(np.asarray(tids, np.int64), lens_a))
                occ_d.append(np.repeat(np.asarray(docs, np.int64), lens_a))
                occ_p.append(np.asarray(flat, np.int64))
            if blk is not None and blk["tids"]:
                lut = np.fromiter((tid_of[t] for t in blk["vocab"]),
                                  np.int64, count=len(blk["vocab"]))
                occ_t.append(lut[np.concatenate(blk["tids"])])
                occ_d.append(np.concatenate(blk["docs"]))
                occ_p.append(np.concatenate(blk["poss"]))
            if occ_t:
                ot = np.concatenate(occ_t)
                od = np.concatenate(occ_d)
                op = np.concatenate(occ_p)
            else:
                ot = od = op = np.zeros(0, np.int64)
            # (term, doc, pos) triples are unique, so one argsort over a
            # packed composite key equals the 3-key lexsort at ~40% of the
            # cost; positions stay < 2^21 (_MAX_DOC_POSITIONS) and the doc
            # axis < 2^22, so the pack fits i64 whenever V <= 2^20
            if V <= (1 << 20) and self.n_docs < (1 << 22):
                order = np.argsort((ot << 43) | (od << 21) | op)
            else:
                order = np.lexsort((op, od, ot))
            ot, od, op = ot[order], od[order], op[order]
            O = len(ot)
            if O:
                new_g = np.empty(O, bool)
                new_g[0] = True
                new_g[1:] = (ot[1:] != ot[:-1]) | (od[1:] != od[:-1])
                g_start = np.flatnonzero(new_g)
                g_len = np.diff(np.append(g_start, O))
                g_tid = ot[g_start]
                g_doc = od[g_start]
            else:
                g_start = g_len = g_tid = g_doc = np.zeros(0, np.int64)
            P = len(g_start)
            lens_v = np.bincount(g_tid, minlength=V).astype(np.int32) \
                if V else np.zeros(0, np.int32)
            max_df = int(lens_v.max()) if V and P else 0
            out[field] = {"union_terms": union_terms, "lens": lens_v,
                          "max_df": max_df, "P": P, "g_doc": g_doc,
                          "g_len": g_len, "g_start": g_start,
                          "positions": op}
        self._csr_memo = out
        return out

    def estimate_bytes(self) -> int:
        """Device-byte estimate from host-side builder state, BEFORE any
        device allocation — must mirror Segment.memory_bytes() exactly so
        breaker charge/release stay balanced. Lets the engine charge the
        breaker before build() uploads arrays (a tripped breaker then
        really does prevent the allocation, not just account for it)."""
        n_pad = next_pow2(self.n_docs, floor=8)
        total = 0
        for c in self._text_csr_all().values():
            p_pad = required_padding(c["P"], c["max_df"])
            # doc_ids + tf + dl are p_pad-sized; doc_len is n_pad-sized
            total += p_pad * 4 * 3 + n_pad * 4
        n_kw = len(set(self._keywords) | set(self._batch_keywords))
        total += n_kw * n_pad * 4
        n_num = len(set(self._longs) | set(self._batch_longs)) \
            + len(set(self._doubles) | set(self._batch_doubles))
        total += n_num * (n_pad * 8 + n_pad)
        for field in set(self._vectors) | set(self._batch_vectors):
            total += n_pad * self._vector_dims[field] * 4
        return total

    def build(self) -> Segment:
        n = self.n_docs
        n_pad = next_pow2(n, floor=8)

        # text: unified columnar CSR over BOTH sources (per-doc dict + batch
        # occurrence blocks) — one lexsort per field groups occurrences into
        # (term, doc) postings in exactly the order the old per-entry loop
        # produced (terms lexicographic, docs ascending, positions ascending)
        text: dict[str, TextFieldIndex] = {}
        for field, c in self._text_csr_all().items():
            union_terms = c["union_terms"]
            term_ids = {t: i for i, t in enumerate(union_terms)}
            lens = c["lens"]
            starts = np.zeros(len(union_terms), np.int32)
            if len(lens):
                starts[1:] = np.cumsum(lens)[:-1]
            P = c["P"]
            max_df = c["max_df"]
            p_pad = required_padding(P, max_df)
            doc_ids = np.full(p_pad, n_pad, np.int32)   # PAD sentinel
            doc_ids[:P] = c["g_doc"]
            tf = np.zeros(p_pad, np.float32)
            tf[:P] = c["g_len"]
            dl_map = self._doc_len.get(field, {})
            doc_len = np.ones(n_pad, np.float32)  # pad with 1 to avoid div-by-0
            for d, L in dl_map.items():
                doc_len[d] = max(L, 1.0)
            sum_dl = float(sum(dl_map.values()))
            dlblk = self._batch_doclen.get(field)
            if dlblk is not None:
                for locs, lens_l in zip(*dlblk):
                    la = np.asarray(locs, np.int64)
                    lv = np.asarray(lens_l, np.int64)
                    doc_len[la] = np.maximum(lv, 1).astype(np.float32)
                    # integer token counts: float accumulation is exact,
                    # so this np.sum cannot differ from the per-doc sum
                    sum_dl += float(lv.sum())
            dl = np.ones(p_pad, np.float32)
            dl[:P] = doc_len[np.minimum(doc_ids[:P], n_pad - 1)]
            text[field] = TextFieldIndex(
                terms=term_ids, term_starts=starts, term_lens=lens,
                doc_ids=jnp.asarray(doc_ids), tf=jnp.asarray(tf),
                doc_len=jnp.asarray(doc_len), dl=jnp.asarray(dl),
                sum_dl=sum_dl, n_postings=P,
                max_df=max_df,
                doc_ids_host=doc_ids[:P].copy(),
                pos_starts=c["g_start"].astype(np.int32),
                pos_lens=c["g_len"].astype(np.int32),
                positions=c["positions"].astype(np.int32))

        keywords: dict[str, KeywordColumn] = {}
        kw_fields = list(self._keywords)
        kw_fields += [f for f in self._batch_keywords
                      if f not in self._keywords]
        for field in kw_fields:
            val_map = self._keywords.get(field, {})
            blk = self._batch_keywords.get(field)
            vals_set = set(val_map.values())
            if blk is not None:
                vals_set.update(blk[1])
            uniq = sorted(vals_set)
            ord_map = {v: i for i, v in enumerate(uniq)}
            ords = np.full(n_pad, -1, np.int32)
            for d, v in val_map.items():
                ords[d] = ord_map[v]
            if blk is not None and blk[0]:
                ords[np.asarray(blk[0], np.int64)] = np.fromiter(
                    (ord_map[v] for v in blk[1]), np.int32,
                    count=len(blk[1]))
            keywords[field] = KeywordColumn(ord_map=ord_map, values=uniq,
                                            ords=jnp.asarray(ords))

        numerics: dict[str, NumericColumn] = {}
        for val_maps, blocks, np_dtype, tag in (
                (self._longs, self._batch_longs, np.int64, "i64"),
                (self._doubles, self._batch_doubles, np.float64, "f64")):
            num_fields = list(val_maps)
            num_fields += [f for f in blocks if f not in val_maps]
            for field in num_fields:
                val_map = val_maps.get(field, {})
                blk = blocks.get(field)
                vals = np.zeros(n_pad, np_dtype)
                missing = np.ones(n_pad, bool)
                for d, v in val_map.items():
                    vals[d] = v
                    missing[d] = False
                if blk is not None and blk[0]:
                    la = np.asarray(blk[0], np.int64)
                    vals[la] = np.asarray(blk[1], np_dtype)
                    missing[la] = False
                numerics[field] = NumericColumn(jnp.asarray(vals),
                                                jnp.asarray(missing), tag)

        vectors: dict[str, VectorColumn] = {}
        vec_fields = list(self._vectors)
        vec_fields += [f for f in self._batch_vectors
                       if f not in self._vectors]
        for field in vec_fields:
            dims = self._vector_dims[field]
            mat = np.zeros((n_pad, dims), np.float32)
            for d, v in self._vectors.get(field, {}).items():
                mat[d] = v
            blk = self._batch_vectors.get(field)
            if blk is not None and blk[0]:
                mat[np.asarray(blk[0], np.int64)] = \
                    np.asarray(blk[1], np.float32)
            vectors[field] = VectorColumn(jnp.asarray(mat), dims)

        live = np.zeros(n_pad, bool)
        live[:n] = True
        parent_of = None
        if any(p >= 0 for p in self.parent_of):
            parent_of = np.full(n_pad, -1, np.int32)
            parent_of[:n] = self.parent_of
        return Segment(
            seg_id=self.seg_id, n_docs=n, n_pad=n_pad, text=text,
            keywords=keywords, numerics=numerics, vectors=vectors,
            stored=self.stored, ids=self.ids, types=self.types,
            id_to_local=dict(self.id_to_local), live_host=live,
            versions=list(self.versions), routings=list(self.routings),
            parent_of=parent_of)


def merge_segments(segments: list[Segment], new_seg_id: int,
                   mapper_for_type=None) -> Segment:
    """Merge segments tensor-natively, dropping tombstoned docs
    (ref index/merge/ + Lucene SegmentMerger — but over CSR tensors).

    NO re-tokenization and NO mapper involvement (mapper_for_type is kept
    for call-site compatibility and ignored): postings are concatenated and
    re-grouped by a stable host argsort over the union term ids, doc ids are
    remapped through per-segment liveness compaction, keyword ordinals are
    remapped through the union vocabulary, and numeric/vector columns are
    boolean-mask concatenations. Work is O(P log V) numpy on host — merge
    cost no longer scales with analyzer complexity, and per-term postings
    stay sorted by doc id (stable sort + order-preserving remap).
    """
    # -- doc remap: old (seg, local) -> new local, dead docs dropped -------
    keeps: list[np.ndarray] = []
    remaps: list[np.ndarray] = []    # old local -> new local (-1 = dead)
    base = 0
    for seg in segments:
        keep = np.flatnonzero(seg.live_host[: seg.n_docs])
        remap = np.full(seg.n_pad + 1, -1, np.int64)  # +1: PAD sentinel slot
        remap[keep] = base + np.arange(len(keep))
        keeps.append(keep)
        remaps.append(remap)
        base += len(keep)
    n = base
    n_pad = next_pow2(n, floor=8)

    stored: list[dict] = []
    ids: list[str] = []
    types: list[str] = []
    versions: list[int] = []
    routings: list = []
    for seg, keep in zip(segments, keeps):
        for old in keep:
            stored.append(seg.stored[old])
            ids.append(seg.ids[old])
            types.append(seg.types[old])
            versions.append(seg.versions[old])
            routings.append(seg.routings[old] if seg.routings else None)

    # -- text fields: CSR concat + stable re-group by union term id --------
    text: dict[str, TextFieldIndex] = {}
    all_text_fields = {f for seg in segments for f in seg.text}
    for field in all_text_fields:
        srcs = [(si, seg.text[field]) for si, seg in enumerate(segments)
                if field in seg.text]
        union_terms = sorted(set().union(*(fx.terms for _, fx in srcs)))
        union_pos = {t: i for i, t in enumerate(union_terms)}
        V = len(union_terms)
        have_positions = all(fx.positions is not None and
                             fx.pos_starts is not None for _, fx in srcs)

        tid_parts, doc_parts, tf_parts = [], [], []
        ps_parts, pl_parts, posflat_parts = [], [], []
        pos_off = 0
        for si, fx in srcs:
            P = fx.n_postings
            if P == 0:
                continue
            docs_h = fx.doc_ids_host if fx.doc_ids_host is not None \
                else np.asarray(fx.doc_ids)[:P]
            tf_h = np.asarray(fx.tf)[:P]
            # per-posting union term id: repeat each term id by its df
            seg_terms = list(fx.terms)  # insertion order == sorted
            seg_to_union = np.array([union_pos[t] for t in seg_terms],
                                    np.int64)
            per_post_tid = np.repeat(seg_to_union, fx.term_lens[: len(seg_terms)])
            alive = remaps[si][docs_h] >= 0
            tid_parts.append(per_post_tid[alive])
            doc_parts.append(remaps[si][docs_h][alive])
            tf_parts.append(tf_h[alive])
            if have_positions:
                ps_parts.append(fx.pos_starts[:P][alive] + pos_off)
                pl_parts.append(fx.pos_lens[:P][alive])
                posflat_parts.append(fx.positions)
                pos_off += len(fx.positions)

        if tid_parts:
            tids = np.concatenate(tid_parts)
            docs = np.concatenate(doc_parts)
            tfs = np.concatenate(tf_parts)
        else:
            tids = np.zeros(0, np.int64)
            docs = np.zeros(0, np.int64)
            tfs = np.zeros(0, np.float32)
        # stable: within a term, segment order then doc order == ascending
        # new doc ids (remap preserves per-segment order, bases ascend)
        order = np.argsort(tids, kind="stable")
        tids, docs, tfs = tids[order], docs[order], tfs[order]
        P = len(tids)
        lens = np.bincount(tids, minlength=V).astype(np.int32) if V else \
            np.zeros(0, np.int32)
        starts = np.zeros(V, np.int32)
        if V:
            starts[1:] = np.cumsum(lens)[:-1]
        max_df = int(lens.max()) if V and P else 0
        p_pad = required_padding(P, max_df)
        doc_ids = np.full(p_pad, n_pad, np.int32)
        doc_ids[:P] = docs
        tf = np.zeros(p_pad, np.float32)
        tf[:P] = tfs

        # per-doc field length: gather old doc_len at kept docs
        doc_len = np.ones(n_pad, np.float32)
        for si, fx in srcs:
            old_dl = np.asarray(fx.doc_len)
            keep = keeps[si]
            doc_len[remaps[si][keep]] = old_dl[np.minimum(
                keep, old_dl.shape[0] - 1)]
        dl = np.ones(p_pad, np.float32)
        dl[:P] = doc_len[np.minimum(doc_ids[:P], n_pad - 1)]
        # Σ field length over LIVE docs == Σ tf (tf sums to token count)
        sum_dl = float(tfs.sum())

        pos_starts = pos_lens = positions = doc_ids_host = None
        doc_ids_host = docs.astype(np.int32)
        if have_positions and P:
            ps = np.concatenate(ps_parts)[order]
            pl = np.concatenate(pl_parts)[order]
            posflat = np.concatenate(posflat_parts) if posflat_parts \
                else np.zeros(0, np.int32)
            ends = np.cumsum(pl)
            total = int(ends[-1]) if len(ends) else 0
            flat_idx = np.arange(total) - np.repeat(ends - pl, pl) \
                + np.repeat(ps, pl)
            positions = posflat[flat_idx].astype(np.int32)
            pos_lens = pl.astype(np.int32)
            pos_starts = np.zeros(P, np.int32)
            if P:
                pos_starts[1:] = ends[:-1]
        elif have_positions:
            positions = np.zeros(0, np.int32)
            pos_starts = np.zeros(0, np.int32)
            pos_lens = np.zeros(0, np.int32)

        text[field] = TextFieldIndex(
            terms={t: i for i, t in enumerate(union_terms)},
            term_starts=starts, term_lens=lens,
            doc_ids=jnp.asarray(doc_ids), tf=jnp.asarray(tf),
            doc_len=jnp.asarray(doc_len), dl=jnp.asarray(dl),
            sum_dl=sum_dl, n_postings=P, max_df=max_df,
            doc_ids_host=doc_ids_host,
            pos_starts=pos_starts, pos_lens=pos_lens, positions=positions)

    # -- keyword columns: ordinal remap through the union vocabulary -------
    keywords: dict[str, KeywordColumn] = {}
    all_kw = {f for seg in segments for f in seg.keywords}
    for field in all_kw:
        srcs = [(si, seg.keywords[field]) for si, seg in enumerate(segments)
                if field in seg.keywords]
        union_vals = sorted(set().union(*(kc.values for _, kc in srcs)))
        union_of = {v: i for i, v in enumerate(union_vals)}
        ords = np.full(n_pad, -1, np.int32)
        for si, kc in srcs:
            keep = keeps[si]
            old = np.asarray(kc.ords)[keep]
            # map via the union: ord -1 (missing) stays -1
            lut = np.array([union_of[v] for v in kc.values] + [-1], np.int32)
            ords[remaps[si][keep]] = lut[old]
        keywords[field] = KeywordColumn(
            ord_map=union_of, values=union_vals, ords=jnp.asarray(ords))

    # -- numeric columns ----------------------------------------------------
    numerics: dict[str, NumericColumn] = {}
    all_num = {f for seg in segments for f in seg.numerics}
    for field in all_num:
        dtype = next(seg.numerics[field].dtype for seg in segments
                     if field in seg.numerics)
        vals = np.zeros(n_pad, np.int64 if dtype == "i64" else np.float64)
        missing = np.ones(n_pad, bool)
        for si, seg in enumerate(segments):
            nc = seg.numerics.get(field)
            if nc is None:
                continue
            keep = keeps[si]
            vals[remaps[si][keep]] = np.asarray(nc.vals)[keep]
            missing[remaps[si][keep]] = np.asarray(nc.missing)[keep]
        numerics[field] = NumericColumn(jnp.asarray(vals),
                                        jnp.asarray(missing), dtype)

    # -- vector columns ------------------------------------------------------
    vectors: dict[str, VectorColumn] = {}
    all_vec = {f for seg in segments for f in seg.vectors}
    for field in all_vec:
        dims = next(seg.vectors[field].dims for seg in segments
                    if field in seg.vectors)
        mat = np.zeros((n_pad, dims), np.float32)
        for si, seg in enumerate(segments):
            vc = seg.vectors.get(field)
            if vc is None:
                continue
            keep = keeps[si]
            mat[remaps[si][keep]] = np.asarray(vc.vecs)[keep]
        vectors[field] = VectorColumn(jnp.asarray(mat), dims)

    live = np.zeros(n_pad, bool)
    live[:n] = True

    # -- block-join parent pointers: remap through the same doc compaction.
    # Children of dead roots are themselves dead (delete_local cascades),
    # so every kept child's parent is kept too.
    parent_of = None
    if any(seg.parent_of is not None for seg in segments):
        parent_of = np.full(n_pad, -1, np.int32)
        for si, seg in enumerate(segments):
            if seg.parent_of is None:
                continue
            keep = keeps[si]
            old_p = seg.parent_of[keep]
            has_p = old_p >= 0
            parent_of[remaps[si][keep[has_p]]] = \
                remaps[si][old_p[has_p]]
        if not (parent_of >= 0).any():
            parent_of = None

    return Segment(
        seg_id=new_seg_id, n_docs=n, n_pad=n_pad, text=text,
        keywords=keywords, numerics=numerics, vectors=vectors,
        stored=stored, ids=ids, types=types,
        # nested placeholder rows (type "__<path>") are not id-addressable
        id_to_local={d: i for i, d in enumerate(ids)
                     if not types[i].startswith("__")},
        live_host=live,
        versions=versions, routings=routings, parent_of=parent_of)
