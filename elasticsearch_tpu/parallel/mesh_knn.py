"""kNN vector search through the mesh program (ISSUE 11 tentpole (b)).

`ShardSearcher.execute_knn` runs per shard, per segment — one device
dispatch and one fetch per segment, then host merges, then (on a cluster)
one transport round-trip per shard. This module packs the shards' vector
columns onto the same ("replica", "shard") mesh the text lane uses
(parallel/mesh.py) and runs the WHOLE multi-shard kNN query phase as ONE
shard_map program with the cross-shard top-k reduce on device:

    exact : per-segment [Q, N] similarity matmuls (ops/knn._sim's math,
            vmapped over the segment axis) under the shard axis
    ivf   : per-segment centroid route + gathered cluster scan
            (ops/ann.ivf_search's two stages inlined, uniform static
            nlist/nprobe/W across segments; each segment's own slot
            budget W_own masks the tail, so the candidate set equals the
            per-segment kernel's exactly — postings_slots is prefix-
            stable in W)
    int8  : the ivf scan on quantized codes (ops/ann.ivf_search_int8's
            int8×int8 GEMM + full-precision rescore, ISSUE 12) when the
            index or request selects `quantization: int8` and every
            segment's QuantData is available
    pq    : the ivf scan on u8 sub-quantizer codes (ops/ann.
            ivf_search_pq's ADC stages, ISSUE 19): per-query LUTs built
            in-program from the replicated query operand against the
            shard-sharded per-segment codebooks, candidate work = m u8
            gathers + adds, then the same full-precision rescore tail

Bitwise parity with the per-shard fan-out holds because per-doc
similarities are contractions over D only (padding the doc axis never
changes them), candidates concatenate in (segment, shard) order, and
`lax.top_k` keeps the earlier candidate on ties — the same (score,
shard, pos) order `controller.sort_docs` produces.

The fallback ladder: mixed IVF/exact segment lanes, non-uniform nlist or
nprobe, filter plans without a mesh match form, undersized meshes and any
execution error return None and the caller runs the per-shard fan-out.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..index.segment import next_pow2
from ..ops import ann as ann_ops
from ..ops import bm25 as bm25_ops
from ..ops.topk import merge_running_topk
from .mesh import REPLICA_AXIS, SHARD_AXIS, index_sharding
from . import mesh_exec
from .mesh_exec import SEG_SHIFT, _DevCtx, _PlanCtx, _Unsupported


@dataclass
class _IvfPack:
    """Uniform-(nlist, nprobe) IVF operands stacked over (shard, segment)."""
    nlist: int
    nprobe_eff: int
    centroids: jax.Array             # f32[S, G, nlist, D]
    starts: jax.Array                # i32[S, G, nlist]
    sizes: jax.Array                 # i32[S, G, nlist]
    slot_docs: jax.Array             # i32[S, G, N]
    norms: jax.Array                 # f32[S, G, N]
    sizes_desc_cum: list             # per (s, g): np i64[nlist] | None
    n_docs: np.ndarray               # i64[S, G]
    nbytes: int = 0


@dataclass
class _QuantPack:
    """Quantized codes stacked over (shard, segment) — the mesh rider of
    the per-shard `ann_quant` tier (ISSUE 12). int8: the scan gathers
    1/4-size codes instead of the f32 stack. pq (ISSUE 19): u8 sub-
    quantizer codes + per-segment codebooks ride the shard axis, and the
    per-query ADC lookup tables are built IN-program from the replicated
    query operand (one einsum per segment) so the collective surface
    stays one u8 gather + adds per candidate. The rescore tail for both
    modes gathers f32 rows from the SAME packed vecs tensor."""
    mode: str                        # "int8" | "pq"
    codes: jax.Array                 # i8[S, G, N, D] | u8[S, G, N, m]
    scales: jax.Array | None = None  # f32[S, G, D]           (int8)
    codebooks: jax.Array | None = None  # f32[S, G, m, 256, dsub] (pq)
    m: int = 0                       # pq sub-quantizer count
    nbytes: int = 0


@dataclass
class MeshVectorStack:
    """Immutable packed view of one vector field across an index's shards
    on the device mesh. Rows mirror MeshStack.shard_rows (segments with
    live docs, in segment order) so a filter plan over the text mesh
    stack aligns row-for-row."""
    field: str
    shard_rows: tuple                # per shard: tuple[(orig_idx, Segment)]
    s_count: int
    s_pad: int
    g_pad: int
    n_pad: int
    dims: int
    mesh: jax.sharding.Mesh = None
    n_replicas: int = 1
    vecs: jax.Array | None = None    # f32[S, G, N, D]
    has_field: np.ndarray | None = None      # bool[S, G] host
    seg_ids_dev: jax.Array | None = None     # i64[S, G]
    nbytes: int = 0
    ivf_packs: dict = dc_field(default_factory=dict)   # nlist -> _IvfPack
    pool: object = None              # owning DevicePool (None = shared)

    def __post_init__(self):
        self._live_key = None
        self._live_dev = None

    def live_stack(self) -> jax.Array:
        """bool[S, G, N] root-doc liveness (tombstone-generation cached,
        padding all-False) — the same mask execute_knn gates on."""
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


def estimate_vector_stack_bytes(per_shard_segments, field: str) -> int:
    """Device bytes the packed vector mesh stack will occupy — the
    pre-build fielddata-breaker charge (mirrors build arithmetic)."""
    rows = [[s for s in segs if s.n_docs > 0] for segs in per_shard_segments]
    live = [s for r in rows for s in r]
    cols = [s.vectors.get(field) for s in live]
    cols = [c for c in cols if c is not None]
    if not cols:
        return 0
    s_pad = next_pow2(len(per_shard_segments), floor=1)
    g_pad = next_pow2(max(len(r) for r in rows), floor=1)
    n_pad = max(s.n_pad for s in live)
    dims = cols[0].dims
    return s_pad * g_pad * n_pad * (dims * 4 + 1) + s_pad * g_pad * 8


def build_vector_stack(per_shard_segments, field: str, mesh, s_pad: int,
                       n_replicas: int, pool=None) -> MeshVectorStack | None:
    """Pack every shard's live segments' `field` vector columns into
    mesh-sharded tensors. None when the field is absent everywhere or the
    columns disagree on dims (per-shard fan-out handles those)."""
    from ..common import tracing
    shard_rows = tuple(
        tuple((i, s) for i, s in enumerate(segs) if s.n_docs > 0)
        for segs in per_shard_segments)
    all_live = [seg for rows in shard_rows for _i, seg in rows]
    if not all_live:
        return None
    dims_set = {seg.vectors[field].dims for seg in all_live
                if field in seg.vectors}
    if len(dims_set) != 1:
        return None
    dims = dims_set.pop()
    g_pad = next_pow2(max(len(r) for r in shard_rows), floor=1)
    n_pad = max(s.n_pad for s in all_live)
    with tracing.span("mesh_vstack_build", field=field,
                      shards=len(per_shard_segments)):
        vecs = np.zeros((s_pad, g_pad, n_pad, dims), np.float32)
        has_field = np.zeros((s_pad, g_pad), bool)
        seg_ids = np.zeros((s_pad, g_pad), np.int64)
        for si, rows in enumerate(shard_rows):
            for gi, (orig, seg) in enumerate(rows):
                seg_ids[si, gi] = orig
                vc = seg.vectors.get(field)
                if vc is None:
                    continue
                v = np.asarray(vc.vecs)
                vecs[si, gi, : v.shape[0]] = v
                has_field[si, gi] = True
        sharding = index_sharding(mesh)
        nbytes = vecs.nbytes + s_pad * g_pad * (n_pad + 8)
        return MeshVectorStack(
            field=field, shard_rows=shard_rows,
            s_count=len(per_shard_segments), s_pad=s_pad, g_pad=g_pad,
            n_pad=n_pad, dims=dims, mesh=mesh, n_replicas=n_replicas,
            vecs=jax.device_put(vecs, sharding), has_field=has_field,
            seg_ids_dev=jax.device_put(seg_ids, sharding), nbytes=nbytes,
            pool=pool)


def _build_ivf_pack(vstack: MeshVectorStack, acquire_ivf) -> _IvfPack | str:
    """Stack per-(shard, segment) IVF structures — the SAME cached IvfData
    the per-shard lane uses (acquire_ivf callback), so centroids and CSR
    layouts are bit-identical. Returns an _IvfPack, or a reason string
    when the lanes are mixed / nlist is non-uniform (-> decline)."""
    per = {}
    nlists = set()
    nprobes = set()
    n_exact = 0
    for si, rows in enumerate(vstack.shard_rows):
        for gi, (_i, seg) in enumerate(rows):
            vc = seg.vectors.get(vstack.field)
            if vc is None:
                continue
            ivf, nprobe_eff = acquire_ivf(si, seg, vc)
            if ivf is None:
                n_exact += 1
                continue
            per[(si, gi)] = (ivf, nprobe_eff)
            nlists.add(int(ivf.nlist))
            nprobes.add(int(nprobe_eff))
    if not per:
        return "exact"                  # every segment on the exact lane
    if n_exact:
        return "mixed"                  # mixed lanes: fan-out decides per seg
    if len(nlists) != 1 or len(nprobes) != 1:
        return "nlist"                  # non-uniform clustering shape
    nlist = nlists.pop()
    s_pad, g_pad, n_pad = vstack.s_pad, vstack.g_pad, vstack.n_pad
    cents = np.zeros((s_pad, g_pad, nlist, vstack.dims), np.float32)
    starts = np.zeros((s_pad, g_pad, nlist), np.int32)
    sizes = np.zeros((s_pad, g_pad, nlist), np.int32)
    slot_docs = np.full((s_pad, g_pad, n_pad), n_pad - 1, np.int32)
    norms = np.zeros((s_pad, g_pad, n_pad), np.float32)
    sdc: list = [[None] * g_pad for _ in range(s_pad)]
    n_docs = np.zeros((s_pad, g_pad), np.int64)
    for (si, gi), (ivf, _np_eff) in per.items():
        cents[si, gi] = np.asarray(ivf.centroids)
        starts[si, gi] = np.asarray(ivf.starts)
        sizes[si, gi] = np.asarray(ivf.sizes)
        sd = np.asarray(ivf.slot_docs)
        slot_docs[si, gi, : sd.shape[0]] = sd
        nm = np.asarray(ivf.norms)
        norms[si, gi, : nm.shape[0]] = nm
        sdc[si][gi] = ivf.sizes_desc_cum
        n_docs[si, gi] = ivf.n_docs
    sharding = index_sharding(vstack.mesh)
    return _IvfPack(
        nlist=nlist, nprobe_eff=nprobes.pop(),
        centroids=jax.device_put(cents, sharding),
        starts=jax.device_put(starts, sharding),
        sizes=jax.device_put(sizes, sharding),
        slot_docs=jax.device_put(slot_docs, sharding),
        norms=jax.device_put(norms, sharding),
        sizes_desc_cum=sdc, n_docs=n_docs,
        nbytes=cents.nbytes + starts.nbytes + sizes.nbytes
        + slot_docs.nbytes + norms.nbytes)


def _build_quant_pack(vstack: MeshVectorStack, base: _IvfPack,
                      acquire_ivf, acquire_quant,
                      mode: str) -> "_QuantPack | str":
    """Stack per-(shard, segment) quantized codes — the SAME cached
    QuantData the per-shard lane uses (acquire_quant callback), so codes,
    scales and codebooks are bit-identical. Returns a _QuantPack, or a
    reason string when any segment declines quantization or (pq) the
    sub-quantizer counts disagree (-> the whole mesh lane declines and
    the per-shard fan-out honors the request's mode)."""
    s_pad, g_pad, n_pad = vstack.s_pad, vstack.g_pad, vstack.n_pad
    per = {}
    for si, rows in enumerate(vstack.shard_rows):
        for gi, (_i, seg) in enumerate(rows):
            vc = seg.vectors.get(vstack.field)
            if vc is None:
                continue
            ivf, _np_eff = acquire_ivf(si, seg, vc)    # cache hit
            if ivf is None:
                return "mixed"
            quant = acquire_quant(si, seg, vc, ivf, mode)
            if quant is None or quant.mode != mode:
                return "quant"
            per[(si, gi)] = quant
    sharding = index_sharding(vstack.mesh)
    if mode == "int8":
        codes = np.zeros((s_pad, g_pad, n_pad, vstack.dims), np.int8)
        scales = np.ones((s_pad, g_pad, vstack.dims), np.float32)
        for (si, gi), quant in per.items():
            c = np.asarray(quant.codes)
            codes[si, gi, : c.shape[0]] = c
            scales[si, gi] = np.asarray(quant.scales)
        return _QuantPack(
            mode=mode,
            codes=jax.device_put(codes, sharding),
            scales=jax.device_put(scales, sharding),
            nbytes=codes.nbytes + scales.nbytes)
    # pq: u8 codes [N, m] + per-segment codebooks [m, 256, dsub]; the
    # in-program ADC LUT einsum needs ONE static m across the stack
    ms = {int(q.m) for q in per.values()}
    if len(ms) != 1:
        return "pq_shape"
    m = ms.pop()
    if m < 1 or vstack.dims % m:
        return "pq_shape"
    dsub = vstack.dims // m
    codes = np.zeros((s_pad, g_pad, n_pad, m), np.uint8)
    books = np.zeros((s_pad, g_pad, m, ann_ops.PQ_CODES, dsub), np.float32)
    for (si, gi), quant in per.items():
        c = np.asarray(quant.codes)
        codes[si, gi, : c.shape[0]] = c
        books[si, gi] = np.asarray(quant.codebooks)
    return _QuantPack(
        mode=mode,
        codes=jax.device_put(codes, sharding),
        codebooks=jax.device_put(books, sharding),
        m=m, nbytes=codes.nbytes + books.nbytes)


def _plan_filter(filter_node, filter_stack, q_pad: int):
    """Mesh match plan for the kNN pre-filter over the text mesh stack.
    The match mask is stats-independent (presence booleans), so stats
    built from the stack's own segments are safe. None -> no mesh form."""
    from ..search.query_dsl import CollectionStats, contains_joins
    if filter_stack is None or contains_joins(filter_node):
        return None
    if not mesh_exec.plan_types_supported(filter_node):
        return None
    terms_by_field: dict[str, set] = {}
    filter_node.collect_terms(terms_by_field)
    segs = [seg for rows in filter_stack.shard_rows for _i, seg in rows]
    stats = CollectionStats.from_segments(segs, terms_by_field)
    pctx = _PlanCtx(filter_stack, q_pad, stats)
    try:
        sig, mfn = mesh_exec._plan_match(filter_node, pctx)
    except _Unsupported:
        return None
    return sig, mfn, pctx


def execute(vstack: MeshVectorStack, query_vectors, *, k: int, metric: str,
            knn_opts: dict, nprobe, exact: bool, acquire_ivf,
            acquire_quant=None, quantization: str | None = None,
            filter_node=None, filter_stack=None):
    """Run a kNN query batch over the vector mesh stack as one program.

    -> (doc_keys i64[Q,k'], shard i32[Q,k'], scores f32[Q,k'],
    totals i64[S,Q], max f32[S,Q], used_ivf, used_quant) in ONE device
    fetch, or None when the shape has no single-program form (caller
    fans out). May raise on execution failure — callers degrade the
    same way."""
    qv_np = np.asarray(query_vectors, np.float32)
    if qv_np.ndim == 1:
        qv_np = qv_np[None, :]
    Q = qv_np.shape[0]
    R = vstack.n_replicas
    q_pad = -(-Q // R) * R
    if qv_np.shape[0] < q_pad:
        qv_np = np.concatenate(
            [qv_np, np.zeros((q_pad - Q, qv_np.shape[1]), np.float32)])
    precision = knn_opts["precision"]
    qmode = (quantization if quantization is not None
             else knn_opts.get("quantization", "none"))
    qmode = str(qmode).strip().lower()
    if qmode not in ("int8", "pq"):
        qmode = "none"
    from ..common.device_stats import lane_decline
    # the mesh kNN lane serves the IVF path only: the exact per-segment
    # kernel runs EAGERLY on the per-shard path, and a fused collective
    # program cannot reproduce its GEMM rounding bit-for-bit — exact and
    # mixed lanes keep the per-shard fan-out (which can)
    pack, qpack = _build_or_get_pack(vstack, acquire_ivf, knn_opts, nprobe,
                                     exact, qmode, acquire_quant)
    if not isinstance(pack, _IvfPack):
        lane_decline("knn", "mesh_knn", "knn_lane")
        return None
    if qmode != "none" and not isinstance(qpack, _QuantPack):
        # a segment declined quantization: fan-out decides
        lane_decline("knn", "mesh_knn", "quant_declined")
        return None
    used_ivf = True
    used_quant = qpack.mode if isinstance(qpack, _QuantPack) else None
    ivf: _IvfPack = pack

    nlist = ivf.nlist
    nprobe_eff = ivf.nprobe_eff          # the per-segment lane's own value
    # per-segment slot budgets; the STATIC W is their max (pow2) and
    # each segment's own budget masks its slot tail — postings_slots
    # fills slots in cluster order, so the first W_own slots of the
    # W_max enumeration ARE the W_own enumeration (prefix property)
    w_own = np.zeros((vstack.s_pad, vstack.g_pad), np.int32)
    for si in range(vstack.s_count):
        for gi in range(len(vstack.shard_rows[si])):
            sdc = ivf.sizes_desc_cum[si][gi]
            if sdc is None:
                continue
            w_own[si, gi] = ann_ops.slot_budget(
                sdc, nprobe_eff, int(ivf.n_docs[si, gi]), nlist)
    W = int(next_pow2(int(w_own.max()), floor=8))
    block = ann_ops.scan_block_size(q_pad // R, vstack.dims, W)

    fplan = None
    if filter_node is not None:
        fplan = _plan_filter(filter_node, filter_stack, q_pad)
        if fplan is None:
            lane_decline("knn", "mesh_knn", "filter_shape")
            return None
        fsig, mfn, fpctx = fplan
        # the filter stack's rows must mirror the vector stack's rows so
        # the match mask aligns segment-for-segment
        v_ids = [[seg.seg_id for _i, seg in rows]
                 for rows in vstack.shard_rows]
        f_ids = [[seg.seg_id for _i, seg in rows]
                 for rows in filter_stack.shard_rows]
        if v_ids != f_ids:
            lane_decline("knn", "mesh_knn", "stack_rows_mismatch")
            return None

    kk = min(k, W) if used_ivf else min(k, vstack.n_pad)
    rw = 0
    if used_quant:
        rw = ann_ops.rescore_width(
            kk, int(knn_opts.get("rescore_window") or 0), W)
    # g_pad MUST key the program: it is a closure constant of step(), and
    # a merge can take an index from g_pad=2 back to g_pad=1 while every
    # other component matches (chaos-harness find: the cached program
    # then broadcast-errors on the new stack and the lane falls back)
    pq_m = qpack.m if isinstance(qpack, _QuantPack) else 0
    key = ("knn", mesh_exec._mesh_devkey(vstack.mesh),
           vstack.s_pad, vstack.g_pad, R, q_pad, k, kk,
           vstack.n_pad, vstack.dims,
           metric, precision, used_ivf, nprobe_eff, W, block,
           used_quant, rw, pq_m,
           (fplan[0], tuple(fplan[2].fields.items()),
            tuple(kind for _a, kind in fplan[2].ops))
           if fplan is not None else None)
    prog = mesh_exec._PROGRAMS.get(key)
    if prog is None:
        from ..common.device_stats import instrument
        prog = instrument(
            "mesh:knn",
            _build_knn_program(
                vstack, metric=metric, precision=precision, k=k, kk=kk,
                n_queries=q_pad // R, used_ivf=used_ivf, nprobe=nprobe_eff,
                W=W, block=block, nlist=ivf.nlist if used_ivf else 0,
                quant=used_quant, rw=rw, pq_m=pq_m, fplan=fplan),
            key=key)
        mesh_exec._PROGRAMS.put(key, prog, weight=1)

    args = [vstack.live_stack(), vstack.seg_ids_dev,
            jnp.asarray(vstack.has_field),
            vstack.vecs]
    if used_ivf:
        args.extend([ivf.centroids, ivf.starts, ivf.sizes, ivf.slot_docs,
                     ivf.norms, jnp.asarray(w_own)])
    if used_quant:
        args.extend([qpack.codes,
                     qpack.scales if used_quant == "int8"
                     else qpack.codebooks])
    if fplan is not None:
        _fsig, _mfn, fpctx = fplan
        for name, kind in fpctx.fields.items():
            if kind == "text":
                ft = filter_stack.text[name]
                args.extend([ft.doc_ids, ft.tf, ft.doc_len])
            elif kind == "keyword":
                args.append(filter_stack.keywords[name].ords)
            else:
                nf = filter_stack.numerics[name]
                args.extend([nf.vals, nf.missing])
        args.extend(a for a, _kind in fpctx.ops)
    args.append(jnp.asarray(qv_np))

    from ..common.metrics import device_fetch, note_h2d
    note_h2d(int(qv_np.nbytes))
    with mesh_exec.exec_guard(vstack.pool):
        out_k, out_shard, out_s, total, mx = prog(*args)
        got = device_fetch({"keys": out_k, "shard": out_shard,
                            "scores": out_s, "total": total, "mx": mx})
    return (np.asarray(got["keys"])[:Q], np.asarray(got["shard"])[:Q],
            np.asarray(got["scores"])[:Q],
            np.asarray(got["total"])[: vstack.s_count, :Q],
            np.asarray(got["mx"])[: vstack.s_count, :Q],
            used_ivf, used_quant)


def _build_or_get_pack(vstack, acquire_ivf, knn_opts, nprobe, exact,
                       qmode: str = "none", acquire_quant=None):
    """(ivf_pack, quant_pack) for this request shape, each memoized on
    the stack (the tensors are immutable alongside the segment set);
    either slot may instead hold a reason string ("exact"/"mixed"/
    "nlist"/"quant"). Exact-pinned requests skip IVF acquisition
    entirely."""
    if exact or not knn_opts.get("ivf_enable", True):
        return "exact", None
    ck = ("req", nprobe)
    cached = vstack.ivf_packs.get(ck)
    if cached is None:
        cached = vstack.ivf_packs[ck] = _build_ivf_pack(vstack, acquire_ivf)
    if qmode == "none" or not isinstance(cached, _IvfPack) \
            or acquire_quant is None:
        return cached, None
    qk = ("quant", nprobe, qmode)
    qp = vstack.ivf_packs.get(qk)
    if qp is None:
        qp = vstack.ivf_packs[qk] = _build_quant_pack(
            vstack, cached, acquire_ivf, acquire_quant, qmode)
    return cached, qp


def _build_knn_program(vstack, *, metric, precision, k, kk, n_queries,
                       used_ivf, nprobe, W, block, nlist, fplan,
                       quant=None, rw=0, pq_m=0):
    mesh = vstack.mesh
    n_pad = vstack.n_pad
    g_pad = vstack.g_pad
    nf_specs = []
    f_op_specs = []
    if fplan is not None:
        _fsig, _mfn, fpctx = fplan
        for _name, kind in fpctx.fields.items():
            nf_specs.extend([P(SHARD_AXIS)] * mesh_exec._FIELD_TENSORS[kind])
        for kind in fpctx.ops:
            kindv = kind[1]
            if kindv == mesh_exec._OP_S:
                f_op_specs.append(P(SHARD_AXIS))
            elif kindv == mesh_exec._OP_SQ:
                f_op_specs.append(P(SHARD_AXIS, None, REPLICA_AXIS))
            elif kindv == mesh_exec._OP_Q:
                f_op_specs.append(P(REPLICA_AXIS))
            else:
                f_op_specs.append(P())

    def step(live, seg_ids, has_f, vecs, *rest):
        live = live[0]                       # [G, N]
        seg_ids = seg_ids[0]                 # [G]
        has_f = has_f[0]                     # [G]
        vecs = vecs[0]                       # [G, N, D]
        i = 0
        rest = list(rest)
        if used_ivf:
            cents, starts, sizes, slot_docs, norms, w_own = \
                (r[0] for r in rest[:6])
            rest = rest[6:]
        if quant:
            q_codes, q_scales = (r[0] for r in rest[:2])
            rest = rest[2:]
        qv = rest[-1]                        # [Qb, D]
        Qb = qv.shape[0]

        # pre-filter mask over the text mesh stack (stats-independent)
        fmask = None
        if fplan is not None:
            _fsig, mfn, fpctx = fplan
            fields = {}
            j = 0
            for name, kind in fpctx.fields.items():
                if kind == "text":
                    fields[name] = mesh_exec.MeshTextField(
                        doc_ids=rest[j][0], tf=rest[j + 1][0],
                        doc_len=rest[j + 2][0])
                    j += 3
                elif kind == "keyword":
                    fields[name] = mesh_exec.MeshKeywordField(
                        ords=rest[j][0])
                    j += 1
                else:
                    fields[name] = mesh_exec.MeshNumericField(
                        vals=rest[j][0], missing=rest[j + 1][0], dtype="")
                    j += 2
            ops = []
            for kind in fpctx.ops:
                blk = rest[j]
                j += 1
                ops.append(blk[0] if kind[1] in (mesh_exec._OP_S,
                                                 mesh_exec._OP_SQ) else blk)
            d = _DevCtx(fields, ops, g_pad, n_pad, Qb)
            fmask = mfn(d)                   # [G, Qb, N]

        eff_live = live[:, None, :] & has_f[:, None, None]
        if fmask is not None:
            eff_live = eff_live & fmask      # [G, Qb, N]
        eff_live = jnp.broadcast_to(eff_live, (g_pad, Qb, n_pad))

        dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
        qc = qv.astype(dt)

        if not used_ivf:
            # exact lane: ops/knn._sim's math. The [G, N, D] block flattens
            # into ONE [Qb, D] x [G*N, D] GEMM — a plain (unbatched)
            # contraction reproduces the per-segment kernel's per-element
            # rounding exactly, where a vmapped batch-GEMM does not
            flat = vecs.reshape(-1, vecs.shape[-1])          # [G*N, D]
            dots = lax.dot_general(
                qc, flat.astype(dt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [Qb, G*N]
            if metric == "cosine":
                qn = jnp.linalg.norm(qv, axis=1, keepdims=True)
                xn = jnp.linalg.norm(flat, axis=1)
                sims = dots / jnp.maximum(qn * xn[None, :], 1e-12)
            elif metric == "l2":
                qn2 = jnp.sum(qv * qv, axis=1, keepdims=True)
                xn2 = jnp.sum(flat * flat, axis=1)
                sims = -(qn2 + xn2[None, :] - 2.0 * dots)
            else:
                sims = dots
            sims = jnp.moveaxis(
                sims.reshape(Qb, g_pad, n_pad), 1, 0)        # [G, Qb, N]
            sims = jnp.where(eff_live, sims, -jnp.inf)
            top, idx = lax.top_k(sims, kk)                   # [G, Qb, kk]
        else:
            # IVF lane: ops/ann.ivf_search's two stages per segment
            qn_cos = jnp.linalg.norm(qv, axis=1, keepdims=True)
            qn2 = jnp.sum(qv * qv, axis=1, keepdims=True)
            nb = W // block

            scan_k = rw if quant else kk

            def one(v_g, c_g, st_g, sz_g, sd_g, nm_g, w_g, live_g,
                    *qops):
                cc = c_g.astype(dt)
                r_dot = lax.dot_general(
                    qc, cc, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [Qb, nlist]
                if metric == "cosine":
                    cn = jnp.linalg.norm(c_g, axis=1)
                    route = r_dot / jnp.maximum(qn_cos * cn[None, :], 1e-12)
                elif metric == "l2":
                    cn2 = jnp.sum(c_g * c_g, axis=1)
                    route = 2.0 * r_dot - cn2[None, :]
                else:
                    route = r_dot
                _, probe = lax.top_k(route, nprobe)          # [Qb, nprobe]
                t_starts = st_g[probe]
                t_lens = sz_g[probe]
                sidx, t_slot, valid = bm25_ops.postings_slots(t_starts,
                                                              t_lens, W)
                # the segment's OWN budget masks the tail — candidate set
                # == the per-segment kernel's
                valid = valid & (jnp.arange(W, dtype=jnp.int32)[None, :]
                                 < w_g)
                sidx = jnp.clip(sidx, 0, n_pad - 1)
                docs = sd_g[sidx]
                docs = jnp.where(valid, docs, n_pad - 1)
                docs_s = docs.reshape(-1, nb, block).transpose(1, 0, 2)
                valid_s = valid.reshape(-1, nb, block).transpose(1, 0, 2)
                xs = (docs_s, valid_s)
                if quant == "int8":
                    # int8 scan + full-precision rescore: exactly
                    # ops/ann.ivf_search_int8's stages per segment
                    codes_g, scales_g = qops
                    q8, sq = ann_ops.quantize_query_int8(qv, scales_g)
                elif quant == "pq":
                    # ADC scan: exactly ops/ann.ivf_search_pq's stages
                    # per segment — each slot's RAW centroid dot is the
                    # base term, the per-query LUT comes from the
                    # REPLICATED query operand against this segment's
                    # codebooks (one einsum per segment)
                    codes_g, books_g = qops
                    cl = jnp.take_along_axis(
                        probe, jnp.clip(t_slot, 0, nprobe - 1),
                        axis=1)                              # [Qb, W]
                    c_dot = jnp.take_along_axis(r_dot, cl, axis=1)
                    cdot_s = c_dot.reshape(-1, nb, block).transpose(1, 0, 2)
                    xs = (docs_s, valid_s, cdot_s)
                    qsub = qv.reshape(qv.shape[0], pq_m, -1).astype(dt)
                    lut = jnp.einsum(
                        "qmd,mjd->qmj", qsub, books_g.astype(dt),
                        preferred_element_type=jnp.float32)  # [Qb, m, 256]

                def body(carry, x):
                    top_s, top_i = carry
                    if quant == "pq":
                        d_blk, v_blk, cd_blk = x
                        cb = codes_g[d_blk]                  # [Qb, B, m] u8
                        cmb = jnp.moveaxis(cb, 2, 1).astype(jnp.int32)
                        vals = jnp.take_along_axis(lut, cmb, axis=2)
                        sims_b = cd_blk + jnp.sum(vals, axis=1)
                    elif quant == "int8":
                        d_blk, v_blk = x
                        cand8 = codes_g[d_blk]               # [Qb, B, D] i8
                        idot = jnp.einsum(
                            "qd,qbd->qb", q8, cand8,
                            preferred_element_type=jnp.int32)
                        sims_b = sq * idot.astype(jnp.float32)
                    else:
                        d_blk, v_blk = x
                        cand = v_g[d_blk].astype(dt)         # [Qb, B, D]
                        sims_b = jnp.einsum(
                            "qd,qbd->qb", qc, cand,
                            preferred_element_type=jnp.float32)
                    if metric == "cosine":
                        cn_b = nm_g[d_blk]
                        sims_b = sims_b / jnp.maximum(qn_cos * cn_b, 1e-12)
                    elif metric == "l2":
                        xn2 = jnp.square(nm_g[d_blk])
                        sims_b = -(qn2 + xn2 - 2.0 * sims_b)
                    ok = v_blk & jnp.take_along_axis(live_g, d_blk, axis=1)
                    sims_b = jnp.where(ok, sims_b, -jnp.inf)
                    return merge_running_topk(top_s, top_i, sims_b, d_blk,
                                              k=scan_k), None

                carry = (jnp.full((qv.shape[0], scan_k), -jnp.inf,
                                  jnp.float32),
                         jnp.full((qv.shape[0], scan_k), -1, jnp.int32))
                (top_s, top_i), _ = lax.scan(body, carry, xs)
                top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
                if quant:
                    top_s, top_i = ann_ops.rescore_topk(
                        v_g, nm_g, qv, top_s, top_i, k=kk, metric=metric,
                        precision=precision)
                return top_s, top_i

            if quant:
                top, idx = jax.vmap(one)(vecs, cents, starts, sizes,
                                         slot_docs, norms, w_own, eff_live,
                                         q_codes, q_scales)
            else:
                top, idx = jax.vmap(one)(vecs, cents, starts, sizes,
                                         slot_docs, norms, w_own, eff_live)

        # per-shard merge in segment order (the host merge's stable
        # argsort over [prev, seg] keeps earlier on ties — so does this)
        keys = jnp.where(top > -jnp.inf,
                         (seg_ids[:, None, None] << SEG_SHIFT)
                         | jnp.maximum(idx, 0).astype(jnp.int64),
                         jnp.int64(-1))
        Qb2 = top.shape[1]
        cand_s = jnp.moveaxis(top, 0, 1).reshape(Qb2, -1)
        cand_k = jnp.moveaxis(keys, 0, 1).reshape(Qb2, -1)
        ks = min(k, cand_s.shape[1])
        shard_s, pos = lax.top_k(cand_s, ks)
        shard_k = jnp.take_along_axis(cand_k, pos, axis=1)

        # cross-shard reduce — mesh_exec._build_program's tail verbatim
        g_s = lax.all_gather(shard_s, SHARD_AXIS)
        g_k = lax.all_gather(shard_k, SHARD_AXIS)
        S = g_s.shape[0]
        g_s2 = jnp.transpose(g_s, (1, 0, 2)).reshape(Qb2, S * ks)
        g_k2 = jnp.transpose(g_k, (1, 0, 2)).reshape(Qb2, S * ks)
        out_s, pos2 = lax.top_k(g_s2, min(k, S * ks))
        out_k = jnp.take_along_axis(g_k2, pos2, axis=1)
        valid_o = out_s > -jnp.inf
        out_shard = jnp.where(valid_o, (pos2 // ks).astype(jnp.int32),
                              jnp.int32(-1))
        out_k = jnp.where(valid_o, out_k, jnp.int64(-1))
        total = jnp.sum(eff_live, axis=(0, 2), dtype=jnp.int64)   # [Qb]
        total_g = lax.all_gather(total, SHARD_AXIS)               # [S, Qb]
        mx_g = lax.all_gather(shard_s[:, 0], SHARD_AXIS)          # [S, Qb]
        return out_k, out_shard, out_s, total_g, mx_g

    in_specs = [P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                P(SHARD_AXIS)]
    if used_ivf:
        in_specs.extend([P(SHARD_AXIS)] * 6)
    if quant:
        in_specs.extend([P(SHARD_AXIS)] * 2)
    in_specs.extend(nf_specs)
    in_specs.extend(f_op_specs)
    in_specs.append(P(REPLICA_AXIS))         # qv
    out_specs = (P(REPLICA_AXIS),) * 3 + (P(None, REPLICA_AXIS),) * 2
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=out_specs, check_vma=False))
