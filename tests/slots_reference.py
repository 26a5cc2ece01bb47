"""The packed lane's slot-table builder as it was up to PR 31, kept as the
plain reference twin of `PackedIndexView._build_slots`
(tests/test_packed_serving.py holds the two bit for bit equal on the table,
S and R). Moved here, not rewritten: one term lookup a body, a Python loop
over (query, term) with `math.log` and the weight in Python floats.
"""

import math

import numpy as np

from elasticsearch_tpu.index.segment import next_pow2
from elasticsearch_tpu.serving.packed_view import CHUNK


def build_slots(view, pf, queries, field: str, k1: float, b: float):
    """-> (packed i32[Q_pad, 3S+1], S, R), as `PackedIndexView._build_slots`
    returns them."""
    Q = len(queries)
    # Q buckets are {1, 32, 64, 128, ...}: the dynamic batcher produces
    # arbitrary batch sizes, and a compile per pow2 bucket would stall
    # serving for seconds each — two warm shapes cover all solo +
    # batched traffic instead (warmup() compiles exactly these)
    Q_pad = 1 if Q == 1 else max(32, next_pow2(Q))
    nseg = pf.starts.shape[1]

    qi_l: list[int] = []
    tid_l: list[int] = []
    w_l: list[float] = []
    min_match = np.ones(Q_pad, np.int32)
    max_terms = 1
    N = max(view.doc_count, 1)
    for qi, q in enumerate(queries):
        tids = pf.term_ids(q.terms) if q.terms else np.empty(0, np.int64)
        n_terms = len(q.terms)
        max_terms = max(max_terms, n_terms)
        if q.operator == "and":
            min_match[qi] = max(n_terms, 1)
        else:
            min_match[qi] = max(q.msm, 1)
        for t, tid in zip(q.terms, tids):
            if tid < 0:
                continue
            df = int(pf.df[tid])
            idf = math.log(1 + (N - df + 0.5) / (df + 0.5))
            qi_l.append(qi)
            tid_l.append(int(tid))
            w_l.append(idf * (k1 + 1) * q.boost)

    # R floor matches warmup()'s shapes: two extra rolls cost ~nothing,
    # one avoided compile shape saves seconds of cold p99
    R = next_pow2(max_terms, floor=4)
    if not qi_l:
        # no term of the batch is in the index: the batch's floor of S
        # (as below), not a shape of its own that nothing warms
        S = 32 if Q_pad <= 32 else 4
        packed = np.zeros((Q_pad, 3 * S + 1), np.int32)
        packed[:, 3 * S] = min_match
        return packed, S, R

    qi_a = np.asarray(qi_l, np.int64)
    tid_a = np.asarray(tid_l, np.int64)
    w_a = np.asarray(w_l, np.float32)

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
