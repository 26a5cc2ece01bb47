"""The least work a request needs, reckoned from the request's own shapes
and the reference's own arrays: never from the program's registry, so the
count stays the same whatever implements the request.

  match      its postings: sum over its terms of df(term) x 12 B (doc id,
             term frequency, length norm) plus 8 B x size of output
  analytics  (documents inside its time range) x (bytes of each column it
             reads: 8 B for a date, 4 B for another number; a `match` in
             the filter reads its postings at 12 B each)

  dis_max    the sum of its sub-queries' postings

Both are memory-bound on the chip: a few flops per byte streamed, against a
machine balance of 240 flops per byte (197e12 / 819e9). Dense vectors are
not: `request_work` counts a request's bytes AND flops.

  knn, cosine function_score over the whole field
             the field's matrix once a request (N x dims x bytes of the
             stated dtype: a batch streams it once) and 2 x N x dims
             flops a body; a knn filter's columns as `body_bytes` counts
             them
  rescore    its first stage's `body_bytes`, plus, for the documents the
             semantics rescore (the top `window_size` of each shard's
             matches, `Reference._rescore`), their vectors (at most the
             matrix once a request) and 2 x dims flops each
"""

from __future__ import annotations

import numpy as np

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
    elif kind == "dis_max":
        for q in spec["queries"]:
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


ELEMENT_BYTES = {"float32": 4, "bfloat16": 2}


def _matrix(ref, field: str) -> tuple[float, int]:
    """(bytes of a vector field's whole matrix, its dims)."""
    f = ref.cfg["fields"][field]
    return (float(ref.n) * f["dims"] * ELEMENT_BYTES[f.get("dtype",
                                                          "float32")],
            f["dims"])


def _cosine_field(query: dict):
    """The field a `function_score` of `cosine` functions reads, or None."""
    (kind, spec), = query.items()
    if kind != "function_score":
        return None
    fns = spec.get("functions", [spec])
    fields = {fn["cosine"]["field"] for fn in fns if "cosine" in fn}
    return fields.pop() if len(fields) == 1 else None


def _rescored(ref, body: dict) -> int:
    """How many documents a rescore body's semantics rescore: the top
    `window_size` of each shard's matches."""
    spec = body["rescore"]
    spec = spec[0] if isinstance(spec, list) else spec
    window = int(spec.get("window_size", body.get("size", 10)))
    mask, _ = ref.evaluate(body.get("query", {"match_all": {}}))
    per_shard = np.bincount(ref.shards()[mask],
                            minlength=ref.cfg["index_settings"]
                            ["number_of_shards"])
    return int(np.minimum(per_shard, window).sum())


def request_work(ref, bodies: list[dict]) -> tuple[float, float]:
    """Least (bytes, flops) the chip must spend on one request's bodies."""
    n_bytes = flops = 0.0
    streamed: dict[str, float] = {}        # matrices read once a request
    gathered: dict[str, float] = {}        # rows a rescore reads
    for body in bodies:
        query = body.get("query", {"match_all": {}})
        field = None
        if "knn" in body:
            field = body["knn"]["field"]
            if body["knn"].get("filter"):
                n_bytes += body_bytes(ref, {"query": body["knn"]["filter"],
                                            "size": 0})
        elif _cosine_field(query):
            field = _cosine_field(query)
            inner = query["function_score"].get("query",
                                                {"match_all": {}})
            n_bytes += body_bytes(ref, {**body, "query": inner})
        else:
            n_bytes += body_bytes(ref, body)
        if field is not None:
            matrix, dims = _matrix(ref, field)
            streamed[field] = matrix
            flops += 2.0 * ref.n * dims
        if "rescore" in body:
            spec = body["rescore"]
            spec = spec[0] if isinstance(spec, list) else spec
            rfield = _cosine_field(spec["query"]["rescore_query"])
            if rfield is not None:
                matrix, dims = _matrix(ref, rfield)
                rows = _rescored(ref, body)
                gathered[rfield] = gathered.get(rfield, 0.0) \
                    + rows * matrix / ref.n
                flops += 2.0 * rows * dims
    for field, row_bytes in gathered.items():
        streamed[field] = min(_matrix(ref, field)[0],
                              streamed.get(field, 0.0) + row_bytes)
    return n_bytes + sum(streamed.values()), flops


def least_seconds_mixed(peaks: dict, n_bytes: float, flops: float) -> float:
    return max(n_bytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
