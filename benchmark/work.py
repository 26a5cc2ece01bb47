"""The least work a request needs, reckoned from the request's own shapes
and the reference's own arrays: never from the program's registry, so the
count stays the same whatever implements the request.

  match      its postings: sum over its terms of df(term) x 12 B (doc id,
             term frequency, length norm) plus 8 B x size of output
  analytics  (documents inside its time range) x (bytes of each column it
             reads: 8 B for a date, 4 B for another number; a `match` in
             the filter reads its postings at 12 B each)

Both are memory-bound on the chip: a few flops per byte streamed, against a
machine balance of 240 flops per byte (197e12 / 819e9).
"""

from __future__ import annotations

POSTING_BYTES = 12
HIT_BYTES = 8
COLUMN_BYTES = {"date": 8, "choice": 4, "heavy": 4, "ipzipf": 4}


def _match_bytes(ref, spec: dict) -> float:
    (field, text), = spec.items()
    if isinstance(text, dict):
        text = text["query"]
    ranks = [int(w[1:]) for w in dict.fromkeys(text.split())]
    return float(ref.df(field, ranks).sum()) * POSTING_BYTES


def _walk(ref, query: dict, found: dict) -> None:
    (kind, spec), = query.items()
    if kind == "match":
        found["postings"] += _match_bytes(ref, spec)
    elif kind == "range":
        (field, bounds), = spec.items()
        found["columns"].add(field)
        found["ranges"].append((field, bounds))
    elif kind == "term":
        (field, _), = spec.items()
        found["columns"].add(field)
    elif kind == "bool":
        for q in spec.get("must", []) + spec.get("filter", []):
            _walk(ref, q, found)


def body_bytes(ref, body: dict) -> float:
    """Least bytes the chip must stream to answer one search body."""
    found = {"postings": 0.0, "columns": set(), "ranges": []}
    _walk(ref, body.get("query", {"match_all": {}}), found)
    for agg in body.get("aggs", {}).values():
        (_, spec), = agg.items()
        found["columns"].add(spec["field"])
    docs = ref.n
    for field, bounds in found["ranges"]:
        docs = min(docs, int(ref.range_mask(field, bounds).sum()))
    kinds = {f: ref.cfg["fields"][f]["kind"] for f in found["columns"]}
    per_doc = sum(COLUMN_BYTES[k] for k in kinds.values())
    out = found["postings"] + docs * per_doc if found["columns"] \
        else found["postings"]
    return out + HIT_BYTES * body.get("size", 10)


def least_seconds(peaks: dict, n_bytes: float) -> float:
    return n_bytes / peaks["hbm_bytes_per_s"]
