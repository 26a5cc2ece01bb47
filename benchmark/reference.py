"""The plain reference: numpy over the regenerated corpus, nothing of the program.

`Reference(cfg, seed)` rebuilds the configuration's documents from the seed
(`corpus.chunk`) and answers a search body the way the configuration states:
BM25 with index-wide statistics and exact field lengths, exact totals, exact
bucket counts on 64-bit columns. It understands the query DSL the traffic
templates use: `match_all`, `match` (operator `or`), `range`, `term` on a
numeric column, `bool` with `must` and `filter`, `dis_max` (`queries`,
`tie_breaker`), `function_score` with the `cosine` function over a vector
field (`score_mode`, `boost_mode`, `boost`, a function's `weight`); the
top-level `knn` (`field`, `query_vector`, `k`, `metric` cosine | dot | l2,
optional `filter`) and `rescore` (`window_size`, `query_weight`,
`rescore_query_weight`, `score_mode`); and the aggregations `date_histogram`
and `terms` on numeric columns.

Vector arithmetic is float64 over the float32 values as sent, in blocks of
rows, so that a million 768-d vectors fit the host; where a vector field's
`dtype` states `bfloat16`, vectors and queries are rounded to it first. The
scores are the program's documented ones: knn's `_score` is the similarity
itself (cosine; the dot product; minus the squared distance), `function_score`
combines as Elasticsearch 2.0 states (`avg` weighted by the functions'
weights). A rescore re-ranks the top `window_size` hits of EACH SHARD (ES
2.0's QueryRescorer): the reference routes each `_id` to its shard by DJB2
over its UTF-16 units, the default hash of the Elasticsearch 2.0.0-SNAPSHOT
that the system ports, written here from its definition.

The control is the same code one step down in precision, the step that would
tempt a later PR (`precision="low"`): BM25 arithmetic rounded to bfloat16
where the configuration states float32, 64-bit columns and bounds narrowed
to float32 where it states exact 64-bit arithmetic, and vectors and queries
rounded to bfloat16 where a field states float32 (float8 e4m3 where it
states bfloat16).
"""

from __future__ import annotations

import numpy as np

import corpus

INTERVAL_MS = {"second": 1_000, "minute": 60_000, "hour": 3_600_000,
               "day": 86_400_000}
BLOCK_ROWS = 8192            # vector rows a float64 block


def bf16(x):
    """Round float values to bfloat16 (nearest even), returned as float64."""
    a = np.ascontiguousarray(np.asarray(x, dtype=np.float32))
    u = a.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def e4m3(x):
    """Round float values to float8 e4m3 (nearest even), as float64."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.float8_e4m3fn) \
        .astype(np.float64)


# -- routing: the shard of an `_id` ------------------------------------------

def djb2(doc_id: str) -> int:
    """DJB2 over the id's UTF-16 code units as a Java int, the default of
    Elasticsearch 2.0.0-SNAPSHOT's OperationRouting (DjbHashFunction):
    h = 5381; h = 33 h + unit."""
    b = doc_id.encode("utf-16-le")
    h = 5381
    for i in range(0, len(b), 2):
        h = (h * 33 + (b[i] | b[i + 1] << 8)) & 0xFFFFFFFF
    return h - (1 << 32) if h >= 1 << 31 else h


def shard_of(doc_id: str, n_shards: int) -> int:
    """hash(_id) mod number_of_shards, the floor modulus of a Java int
    (OperationRouting with MathUtils.mod)."""
    return djb2(doc_id) % n_shards


class Reference:
    def __init__(self, cfg: dict, seed: int, precision: str = "stated"):
        if precision not in ("stated", "low"):
            raise ValueError(precision)
        self.cfg = cfg
        self.n = cfg["documents"]
        self.low = precision == "low"
        sim = cfg.get("similarity", {})
        self.k1, self.b = sim.get("k1", 1.2), sim.get("b", 0.75)
        self.chunks = [corpus.chunk(cfg, seed, k)
                       for k in range(corpus.n_chunks(cfg))]
        self._offsets: dict[int, dict] = {}
        self.cols, self.lens = {}, {}
        self.vecs, self._norms = {}, {}
        for f, s in cfg["fields"].items():
            if s["kind"] == "text":
                self.lens[f] = np.concatenate([c[f][0] for c in self.chunks])
            elif s["kind"] == "vector":
                self.vecs[f] = self._vector_column(f, s)
            else:
                self.cols[f] = np.concatenate([c[f] for c in self.chunks])
        self._postings: dict[str, tuple] = {}
        self._shards = None
        self._memo: dict[tuple, np.ndarray] = {}

    def _vector_column(self, f: str, spec: dict) -> np.ndarray:
        """The field's matrix as the arithmetic sees it, float32 [N, dims]
        (bfloat16 and e4m3 values are float32 values); the chunks keep
        views of the values as sent, for `_source`."""
        sent = np.concatenate([c[f] for c in self.chunks])
        for k, c in enumerate(self.chunks):
            c[f] = sent[k * corpus.CHUNK:k * corpus.CHUNK + len(c[f])]
        rnd = self._vector_rounding(f, spec)
        if rnd is None:
            return sent
        return np.concatenate([rnd(sent[i:i + BLOCK_ROWS]).astype(np.float32)
                               for i in range(0, self.n, BLOCK_ROWS)])

    def _vector_rounding(self, f: str, spec: dict | None = None):
        """The rounding the field's arithmetic applies to its inputs."""
        dtype = (spec or self.cfg["fields"][f]).get("dtype", "float32")
        if dtype not in ("float32", "bfloat16"):
            raise ValueError(f"vector dtype {dtype!r}")
        if dtype == "bfloat16":
            return e4m3 if self.low else bf16
        return bf16 if self.low else None

    def source(self, doc_id: int) -> dict:
        """The `_source` of a document as it was sent."""
        k, i = divmod(doc_id, corpus.CHUNK)
        if k not in self._offsets:
            self._offsets[k] = corpus.offsets(self.cfg, self.chunks[k])
        return corpus.render(self.cfg, self.chunks[k], i, self._offsets[k])

    # -- postings ------------------------------------------------------------

    def postings(self, field: str):
        """(post_doc, post_tf, term_start, avgdl) of a text field, built on
        first use: pairs sorted by (term, doc)."""
        if field not in self._postings:
            lens = self.lens[field]
            ranks = np.concatenate([c[field][1] for c in self.chunks])
            doc_of = np.repeat(np.arange(self.n, dtype=np.int64), lens)
            pairs, tf = np.unique(ranks * self.n + doc_of,
                                  return_counts=True)
            vocab = self.cfg["fields"][field]["vocab"]
            start = np.searchsorted(pairs // self.n, np.arange(vocab + 1))
            self._postings[field] = (pairs % self.n, tf.astype(np.float64),
                                     start, float(lens.sum()) / self.n)
        return self._postings[field]

    def n_postings(self, field: str) -> int:
        return len(self.postings(field)[0])

    def df(self, field: str, ranks) -> np.ndarray:
        start = self.postings(field)[2]
        r = np.asarray(ranks, dtype=np.int64)
        return start[r + 1] - start[r]

    # -- vectors -------------------------------------------------------------

    def _query_vector(self, f: str, q) -> np.ndarray:
        """A query vector as the program receives it: float32, then the
        field's rounding; float64."""
        qv = np.asarray(q, np.float64).astype(np.float32)
        if qv.shape != (self.vecs[f].shape[1],):
            raise ValueError(f"query vector of shape {qv.shape} for {f!r}")
        rnd = self._vector_rounding(f)
        return (rnd(qv) if rnd else qv).astype(np.float64)

    def norms(self, f: str) -> np.ndarray:
        if f not in self._norms:
            x = self.vecs[f]
            self._norms[f] = np.concatenate([
                np.sqrt(np.einsum("ij,ij->i", b, b))
                for b in (x[i:i + BLOCK_ROWS].astype(np.float64)
                          for i in range(0, self.n, BLOCK_ROWS))])
        return self._norms[f]

    def similarity(self, f: str, q, metric: str = "cosine",
                   docs=None) -> np.ndarray:
        """The metric of the query vector against the documents `docs`
        (all where None): cosine, the dot product, or minus the squared
        distance, in float64. Taken from `prepare`'s pass where it made
        it."""
        qv = self._query_vector(f, q)
        if docs is None and (f, metric, qv.tobytes()) in self._memo:
            return self._memo.pop((f, metric, qv.tobytes()))
        return self._similarities(f, qv[None, :], metric, docs)[0]

    def prepare(self, bodies: list[dict]) -> None:
        """The similarities that the `knn` bodies among `bodies` need, in
        one pass over each field's matrix (as a batch streams it once);
        `similarity` hands each out once. What an earlier request left
        unclaimed (a body that came back unanswered) is dropped first."""
        self._memo.clear()
        want: dict[tuple, list] = {}
        for b in bodies:
            if "knn" in b:
                k = b["knn"]
                want.setdefault((k["field"], k.get("metric", "cosine")),
                                []).append(self._query_vector(
                                    k["field"], k["query_vector"]))
        for (f, metric), qvs in want.items():
            for qv, row in zip(qvs, self._similarities(f, np.stack(qvs),
                                                       metric)):
                self._memo[(f, metric, qv.tobytes())] = row

    def _similarities(self, f: str, qvs: np.ndarray, metric: str,
                      docs=None) -> np.ndarray:
        if metric not in ("cosine", "dot", "l2"):
            raise ValueError(f"knn metric {metric!r}")
        x = self.vecs[f]
        rows = np.arange(self.n) if docs is None else np.asarray(docs)
        out = np.empty((len(qvs), len(rows)))
        for i in range(0, len(rows), BLOCK_ROWS):
            r = rows[i:i + BLOCK_ROWS]
            out[:, i:i + BLOCK_ROWS] = qvs @ x[r].astype(np.float64).T
        qn2 = np.einsum("ij,ij->i", qvs, qvs)[:, None]
        xn = self.norms(f)[rows][None, :]
        if metric == "l2":
            return -(qn2 + xn * xn - 2.0 * out)
        if metric == "cosine":
            den = xn * np.sqrt(qn2)
            return np.where(den > 0, out / np.where(den > 0, den, 1.0), 0.0)
        return out

    # -- routing -------------------------------------------------------------

    def shards(self) -> np.ndarray:
        """The shard of every document, as the configuration routes `_id`."""
        if self._shards is None:
            n = self.cfg["index_settings"]["number_of_shards"]
            self._shards = np.array([shard_of(str(i), n)
                                     for i in range(self.n)], np.int64)
        return self._shards

    # -- queries -------------------------------------------------------------

    def _match(self, field: str, text: str):
        post_doc, post_tf, start, avgdl = self.postings(field)
        rnd = bf16 if self.low else (lambda v: v)
        score = np.zeros(self.n)
        mask = np.zeros(self.n, dtype=bool)
        lens = self.lens[field]
        for w in dict.fromkeys(text.split()):
            t = int(w[1:])
            s, e = start[t], start[t + 1]
            if s == e:
                continue
            d, tf = post_doc[s:e], post_tf[s:e]
            df = e - s
            idf = rnd(np.log(1.0 + (self.n - df + 0.5) / (df + 0.5)))
            norm = rnd(self.k1 * (1.0 - self.b + self.b * lens[d] / avgdl))
            contrib = rnd(rnd(idf * (self.k1 + 1.0)) * rnd(tf / rnd(tf + norm)))
            score[d] = rnd(score[d] + contrib)
            mask[d] = True
        return mask, score

    def _bound(self, col, v):
        if self.low:
            return col.astype(np.float32), np.float32(v)
        return col, v

    def range_mask(self, field: str, spec: dict):
        mask = np.ones(self.n, dtype=bool)
        for op, v in spec.items():
            col, v = self._bound(self.cols[field], v)
            if op == "gte":
                mask &= col >= v
            elif op == "gt":
                mask &= col > v
            elif op == "lte":
                mask &= col <= v
            elif op == "lt":
                mask &= col < v
            else:
                raise ValueError(f"range operator {op!r}")
        return mask

    def evaluate(self, query: dict):
        """-> (mask of matching documents, score of each document)."""
        (kind, spec), = query.items()
        if kind == "match_all":
            return np.ones(self.n, dtype=bool), np.ones(self.n)
        if kind == "match":
            (field, text), = spec.items()
            if isinstance(text, dict):
                text = text["query"]
            return self._match(field, text)
        if kind == "range":
            (field, bounds), = spec.items()
            return self.range_mask(field, bounds), np.ones(self.n)
        if kind == "term":
            (field, value), = spec.items()
            col, value = self._bound(self.cols[field], value)
            return col == value, np.ones(self.n)
        if kind == "bool":
            mask = np.ones(self.n, dtype=bool)
            score = np.zeros(self.n)
            for q in spec.get("must", []):
                m, s = self.evaluate(q)
                mask &= m
                score += s
            for q in spec.get("filter", []):
                mask &= self.evaluate(q)[0]
            return mask, score
        if kind == "dis_max":
            # the best sub-query's score plus tie_breaker times the others'
            best, total = np.zeros(self.n), np.zeros(self.n)
            mask = np.zeros(self.n, dtype=bool)
            for q in spec["queries"]:
                m, sc = self.evaluate(q)
                sc = np.where(m, sc, 0.0)
                best, total, mask = np.maximum(best, sc), total + sc, mask | m
            score = best + spec.get("tie_breaker", 0.0) * (total - best)
            return mask, np.where(mask, score * spec.get("boost", 1.0), 0.0)
        if kind == "function_score":
            return self.function_score(spec)
        raise ValueError(f"the reference has no query {kind!r}")

    def function_score(self, spec: dict, docs=None):
        """`function_score` (ES 2.0 FunctionScoreQuery, the `cosine` and
        `weight` functions) -> (mask, score) of the documents `docs`, all
        where None."""
        mask, score = self.evaluate(spec.get("query", {"match_all": {}}))
        if docs is not None:
            mask, score = mask[docs], score[docs]
        fns = spec.get("functions")
        if fns is None:
            fns = [{k: spec[k] for k in ("cosine", "weight") if k in spec}]
        values, weights = [], []
        for fn in fns:
            if set(fn) - {"cosine", "weight"}:
                raise ValueError(f"the reference has no function {fn!r}")
            w = float(fn.get("weight", 1.0))
            if "cosine" in fn:
                p = fn["cosine"]
                vec, = p["query_vectors"]
                v = self.similarity(p["field"], vec, "cosine", docs)
            else:
                v = np.ones(len(score))
            values.append(v * w)
            weights.append(w)
        mode = spec.get("score_mode", "multiply")
        if mode == "multiply":
            fv = np.prod(values, axis=0)
        elif mode == "sum":
            fv = np.sum(values, axis=0)
        elif mode == "avg":
            fv = np.sum(values, axis=0) / np.sum(weights)
        elif mode in ("max", "min"):
            fv = getattr(np, mode)(values, axis=0)
        elif mode == "first":
            fv = values[0]
        else:
            raise ValueError(f"function_score score_mode {mode!r}")
        out = _combine(spec.get("boost_mode", "multiply"), score, fv,
                       ("multiply", "sum", "replace", "avg", "max", "min"))
        return mask, np.where(mask, out * spec.get("boost", 1.0), 0.0)

    def evaluate_at(self, query: dict, docs):
        """`evaluate` of the documents `docs` only; a `function_score`
        computes its functions for those alone."""
        (kind, spec), = query.items()
        if kind == "function_score":
            return self.function_score(spec, docs)
        mask, score = self.evaluate(query)
        return mask[docs], score[docs]

    # -- aggregations --------------------------------------------------------

    def aggregate(self, aggs: dict, mask: np.ndarray) -> dict:
        """{agg name: {bucket key: doc_count}}, empty buckets left out."""
        out = {}
        for name, body in aggs.items():
            (kind, spec), = body.items()
            col = self.cols[spec["field"]][mask]
            if kind == "date_histogram":
                step = INTERVAL_MS[spec["interval"]]
                if self.low:
                    keys = np.floor(col.astype(np.float32)
                                    / np.float32(step)).astype(np.int64) * step
                else:
                    keys = (col // step) * step
            elif kind == "terms":
                keys = col
            else:
                raise ValueError(f"the reference has no aggregation {kind!r}")
            k, c = np.unique(keys, return_counts=True)
            out[name] = dict(zip(k.tolist(), c.tolist()))
        return out

    # -- whole answers ---------------------------------------------------------

    def answer(self, body: dict, edge_rel: float = 1e-4) -> dict:
        """What the body must return: `mask`, `score`, `total`, `aggs`;
        for a `knn` body `size` too (at most `k` hits), and for a `rescore`
        body `alt`, `reach` and `sure` (`_rescore`). `edge_rel` is how near
        (relative) to its shard's window edge a first-stage score lies
        where the program's rounding may put it on either side."""
        if "knn" in body:
            return self._knn(body)
        mask, score = self.evaluate(body.get("query", {"match_all": {}}))
        out = {"mask": mask, "score": score, "total": int(mask.sum())}
        if "rescore" in body:
            out.update(self._rescore(body, mask, score, edge_rel))
        if "aggs" in body:
            out["aggs"] = self.aggregate(body["aggs"], mask)
        return out

    def _knn(self, body: dict) -> dict:
        knn = body["knn"]
        if "query" in body or "aggs" in body or "rescore" in body:
            raise ValueError("the reference has knn alone in a body")
        size = body.get("size", 10)
        mask = np.ones(self.n, dtype=bool)      # every document holds one
        if knn.get("filter"):
            mask &= self.evaluate(knn["filter"])[0]
        score = self.similarity(knn["field"], knn["query_vector"],
                                knn.get("metric", "cosine"))
        return {"mask": mask, "score": score, "total": int(mask.sum()),
                "size": min(size, int(knn.get("k", size)))}

    def _rescore(self, body: dict, mask, prim, edge_rel: float) -> dict:
        """ES 2.0's QueryRescorer on each shard: the shard keeps its top
        max(size, window_size) by first-stage score; the top window_size of
        those score `score_mode(query_weight x first, rescore_query_weight
        x second)` where the rescore query matches, `query_weight x first`
        elsewhere, and so do the kept hits below the window; the shards'
        kept hits are merged by that score. A document whose first-stage
        score lies within `edge_rel` of a shard's window or keep edge may be
        on either side: `alt` is its score on the other side of the window,
        `reach` the documents that may be kept, `sure` those that must."""
        spec = body["rescore"]
        if isinstance(spec, list):
            spec, = spec
        size = body.get("size", 10)
        window = int(spec.get("window_size", size))
        q = spec["query"]
        qw = float(q.get("query_weight", 1.0))
        rw = float(q.get("rescore_query_weight", 1.0))
        keep = max(size, window)
        shard = self.shards()
        docs = np.flatnonzero(mask)
        reach = np.zeros(self.n, dtype=bool)
        sure = np.zeros(self.n, dtype=bool)
        surely_in, maybe_in = [], []
        for s in np.unique(shard[docs]):
            d = docs[shard[docs] == s]
            order = d[np.lexsort((d, -prim[d]))]
            ps = prim[order]
            pos = np.arange(len(order))
            near_w = _near_edge(ps, pos, window, edge_rel)
            near_k = _near_edge(ps, pos, keep, edge_rel)
            reach[order[(pos < keep) | near_k]] = True
            sure[order[(pos < keep) & ~near_k]] = True
            surely_in.append(order[(pos < window) & ~near_w])
            maybe_in.append(order[near_w])
        score = prim * qw
        alt = score.copy()
        cand = np.concatenate([np.zeros(0, np.int64)] + surely_in + maybe_in)
        m2, s2 = self.evaluate_at(q["rescore_query"], cand)
        first = prim[cand] * qw
        combined = np.where(m2, _combine(q.get("score_mode", "total"), first,
                                         s2 * rw), first)
        n_sure = sum(len(x) for x in surely_in)
        score[cand] = combined
        alt[cand[:n_sure]] = combined[:n_sure]
        return {"score": score, "alt": alt, "reach": reach & mask,
                "sure": sure & mask}

    def respond(self, body: dict) -> dict:
        """A response in the program's shape, made from this reference's own
        answer: how the control is put in the program's place."""
        ans = self.answer(body)
        size = ans.get("size", body.get("size", 10))
        docs = np.flatnonzero(ans["mask"] & ans.get("reach", True))
        order = docs[np.lexsort((docs, -ans["score"][docs]))][:size]
        resp = {"hits": {"total": ans["total"], "hits": [
            {"_id": str(int(d)), "_score": float(ans["score"][d])}
            for d in order]}}
        if body.get("_source", True) is not False:
            for h in resp["hits"]["hits"]:
                h["_source"] = self.source(int(h["_id"]))
        if "aggs" in ans:
            resp["aggregations"] = {
                name: {"buckets": [{"key": k, "doc_count": c}
                                   for k, c in sorted(b.items())]}
                for name, b in ans["aggs"].items()}
        return resp


def _near_edge(ps, pos, rank: int, edge_rel: float) -> np.ndarray:
    """Of a shard's documents in first-stage order (scores `ps`), those
    whose side of the edge after the first `rank` the program's rounding
    may change: inside it and within `edge_rel` of the first outside, or
    outside it and within `edge_rel` of the last inside."""
    if len(ps) <= rank:
        return np.zeros(len(ps), dtype=bool)
    last_in, first_out = ps[rank - 1], ps[rank]
    return ((pos < rank) & (ps <= first_out + edge_rel * abs(first_out))) \
        | ((pos >= rank) & (ps >= last_in - edge_rel * abs(last_in)))


def _combine(mode: str, a, b, modes=("total", "multiply", "avg", "max",
                                     "min")):
    """Two scores combined: a rescore's `score_mode` (ES 2.0 QueryRescorer)
    or a function_score's `boost_mode` (`modes` says which are allowed)."""
    if mode not in modes:
        raise ValueError(f"score mode {mode!r}")
    if mode in ("total", "sum"):
        return a + b
    if mode == "multiply":
        return a * b
    if mode == "avg":
        return (a + b) / 2.0
    if mode == "replace":
        return b * np.ones_like(a)
    return np.maximum(a, b) if mode == "max" else np.minimum(a, b)
