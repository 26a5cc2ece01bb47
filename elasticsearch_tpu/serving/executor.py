"""Packed-path request planning + response building.

Bridges NodeService and PackedIndexView: decides which request bodies are
servable by the one-program packed kernel, extracts per-query knobs from the
parsed query tree, and assembles a batch's responses (`respond`) — as dicts
(`response_dict`: API parity with the general path, one Python dict a hit)
or, for the `_source: false` members of an `_msearch` over clean single-type
ids, as the bytes of their JSON (`response_raw`). The raw render is one
vector pass over all the hits of a batch and runs no Python call a hit: ids
are gathered from the view's bytes column `ids_bytes`, scores printed by
`g9.g9_text`, a request's hits laid out as one uint8 matrix and compacted;
only each body's head (`took`, `_shards`, `total`, `max_score`) is a Python
format. A top-1000 `_msearch` of 256 bodies is 256,000 hits a request:
dicts, or numpy's per-element string functions, cost several times the
device program there.

ref: the reference's QueryPhase + SearchPhaseController split; here the
"controller reduce" already happened on device (global top-k over the packed
doc space), so response building is the only host work left.
"""

from __future__ import annotations

import numpy as np

from ..common.metrics import record_packed_render
from .g9 import G9_WIDTH, g9_text
from .packed_view import (F_RANGE, F_TERM, F_TERM_VALS, PackedIndexView,
                          PackedQuery)

# body keys the packed path understands; anything else (sort, aggs, rescore,
# knn, search_after, highlight, ...) falls back to the general path
PACKED_BODY_KEYS = {"query", "size", "from", "_source"}


def _packable_filters(plan):
    """mask/neg nodes -> (negated?, node) pairs the packed kernel's filter
    slots can evaluate (term + range over columnar fields, within the
    static slot budget), or None if any node needs the general path."""
    from ..search.query_dsl import MatchAllNode, RangeNode, TermFilterNode

    out = []
    nr = nt = 0
    for neg, nodes in ((False, plan.mask_nodes), (True, plan.neg_nodes)):
        for n in nodes:
            if isinstance(n, MatchAllNode):
                if neg:
                    return None     # must_not match_all: matches nothing
                continue
            if isinstance(n, RangeNode):
                if not n.bounds_per_query:
                    return None
                lo, hi = n.bounds_per_query[0][0], n.bounds_per_query[0][1]
                if not all(isinstance(x, (int, float, type(None)))
                           and not isinstance(x, bool) for x in (lo, hi)):
                    # keyword (string) bounds are fine; mixed junk is not
                    if not all(isinstance(x, (str, type(None)))
                               for x in (lo, hi)):
                        return None
                nr += 1
                out.append((neg, n))
            elif isinstance(n, TermFilterNode):
                vals = n.values_per_query[0] if n.values_per_query else []
                if len(vals) > F_TERM_VALS:
                    return None
                nt += 1
                out.append((neg, n))
            else:
                return None
    if nr > F_RANGE or nt > F_TERM:
        return None
    return out


def packed_spec_of(parser, body: dict):
    """-> (PackedQuery, field, k1, b) if the body is packed-servable,
    else None. Mirrors sparse_exec.extract_sparse_plan eligibility;
    filter/must_not contexts ride the kernel's columnar filter slots
    (BASELINE config #2's bool{match + filter} shape)."""
    from ..search.sparse_exec import extract_sparse_plan

    if any(k not in PACKED_BODY_KEYS for k in body):
        return None
    try:
        node = parser.parse(body.get("query") or {"match_all": {}})
    except Exception:          # noqa: BLE001 — let the general path raise
        return None
    plan = extract_sparse_plan(node)
    if plan is None:
        return None
    filters = _packable_filters(plan)
    if filters is None:
        return None
    if filters and not plan.terms_per_query[0]:
        # pure-filter queries have no scored postings to draw candidates
        # from; the general path serves them
        return None
    return (PackedQuery(terms=plan.terms_per_query[0],
                        boost=plan.match_boost * plan.scale,
                        operator=plan.operator, msm=plan.msm,
                        const=plan.const_boost * plan.scale,
                        filters=tuple(filters)),
            plan.field, plan.k1, plan.b)


def response_dict(view: PackedIndexView, index_name: str, srow: np.ndarray,
                  drow: np.ndarray, total: int, *, n_shards: int, took: int,
                  from_: int, size: int, src_spec, src_filter_fn) -> dict:
    """Assemble one search response (general dict form)."""
    sl = srow[from_:from_ + size]
    dl = drow[from_:from_ + size]
    n = int((sl > -np.inf).sum())
    hits = []
    for i in range(n):
        src, tname, doc_id = view.source_of(int(dl[i]))
        if src_spec is False:
            src = None
        elif src_filter_fn is not None:
            src = src_filter_fn(src)
        hit = {"_index": index_name, "_type": tname, "_id": doc_id,
               "_score": float(sl[i])}
        if src is not None:      # `_source: false` omits the key
            hit["_source"] = src
        hits.append(hit)
    mx = float(srow[0]) if srow.size and srow[0] > -np.inf else None
    return {
        "took": took, "timed_out": False,
        "_shards": {"total": n_shards, "successful": n_shards, "failed": 0},
        "hits": {"total": int(total), "max_score": mx, "hits": hits},
    }


_HEAD = ('{"took":%d,"timed_out":false,"_shards":{"total":%d,"successful":%d,'
         '"failed":0},"hits":{"total":%d,"max_score":%s,"hits":[')
_MID = b'","_score":'


def response_raw(view: PackedIndexView, index_name: str, scores: np.ndarray,
                 docs: np.ndarray, totals: np.ndarray, *, n_shards: int,
                 tooks: list[int], from_: int, size: int
                 ) -> tuple[list[bytes], int, int]:
    """The `_source: false` responses of a whole batch (`scores`, `docs`:
    [Q, k]; `totals`: [Q]) as the bytes of their JSON -> (one `bytes` a
    body, hits rendered, hits that took g9_text's scalar patch).

    The Q x size hits are the rows of ONE uint8 matrix: `,{"_index":...
    "_id":"` | the id from `view.ids_bytes` | `","_score":` | the score as
    `"%.9g" %` prints it | `}`, constant text and fields as column ranges.
    A byte a row does not have is NUL (a short id's padding, a short
    score's, the comma before a body's first hit, every byte of a row past
    a body's last hit) and one compress drops them all; the count of the
    bytes kept of a body's rows is where its hits end in the buffer. NUL
    can stand for "absent" because the lane only renders ids that need no
    JSON escaping (`ids_json_safe`): no byte of an answer is NUL."""
    sl = scores[:, from_:from_ + size]
    q, k = sl.shape
    flat_s = np.ascontiguousarray(sl, np.float32).reshape(-1)
    live = flat_s > -np.inf
    n_live = int(np.count_nonzero(live))
    if n_live < live.size:
        flat_s = np.where(live, flat_s, np.float32(0))   # printed, dropped
    pre = (',{"_index":"%s","_type":"%s","_id":"'
           % (index_name, view.single_type or "_doc")).encode()
    c_id = len(pre)
    c_mid = c_id + view.ids_bytes.shape[1]
    c_score = c_mid + len(_MID)
    c_end = c_score + G9_WIDTH
    row = np.zeros(c_end + 1, np.uint8)
    row[:c_id] = np.frombuffer(pre, np.uint8)
    row[c_mid:c_score] = np.frombuffer(_MID, np.uint8)
    row[c_end] = ord("}")
    m = np.empty((q * k, row.size), np.uint8)
    m[:] = row
    m[:, c_id:c_mid] = np.take(
        view.ids_bytes, docs[:, from_:from_ + size].reshape(-1), axis=0,
        mode="clip")                        # -1 (no hit): any row, dropped
    _, patched = g9_text(flat_s, out=m[:, c_score:c_end])
    if n_live < live.size:
        m[~live] = 0
    m.reshape(q, k, row.size)[:, :1, 0] = 0     # no comma before a first hit
    flat = m.reshape(-1)
    keep = flat != 0
    buf = flat[keep]
    out = []
    end = 0
    per_body = k * row.size
    for qi in range(q):
        start = end
        end += np.count_nonzero(keep[qi * per_body:(qi + 1) * per_body])
        head = _HEAD % (
            tooks[qi], n_shards, n_shards, int(totals[qi]),
            "%.9g" % float(scores[qi, 0]) if scores[qi, 0] > -np.inf
            else "null")
        out.append(b"".join((head.encode(), buf[start:end], b"]}}")))
    return out, n_live, patched


def respond(view: PackedIndexView, index_name: str, bodies: list[dict],
            scores: np.ndarray, docs: np.ndarray, totals: np.ndarray, *,
            raw: bool, n_shards: int, tooks: list[int], from_: int, size: int,
            source_filter) -> tuple[list, dict]:
    """The responses of one packed batch, in the bodies' order, and what the
    `packed.respond` span says of them (`form`, `hits`, `patched`): `bytes`
    from `response_raw` when the request takes them (`raw`), no body wants
    a `_source` and the ids are clean, else dicts (`source_filter(src,
    spec)` cuts a `_source` down to a body's includes / excludes)."""
    if raw and view.ids_json_safe and all(
            body.get("_source", True) is False for body in bodies):
        out, hits, patched = response_raw(
            view, index_name, scores, docs, totals, n_shards=n_shards,
            tooks=tooks, from_=from_, size=size)
        record_packed_render(vector=hits - patched, patched=patched)
        return out, {"form": "raw", "hits": hits, "patched": patched}
    out = []
    for qi, body in enumerate(bodies):
        src_spec = body.get("_source", True)
        fn = (lambda s, spec=src_spec: source_filter(s, spec)) \
            if src_spec not in (True, False) else None
        out.append(response_dict(
            view, index_name, scores[qi], docs[qi], totals[qi],
            n_shards=n_shards, took=tooks[qi], from_=from_, size=size,
            src_spec=src_spec, src_filter_fn=fn))
    hits = sum(len(r["hits"]["hits"]) for r in out)
    record_packed_render(dict=hits)
    return out, {"form": "dict", "hits": hits, "patched": 0}
