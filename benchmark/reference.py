"""The plain reference: numpy over the regenerated corpus, nothing of the program.

`Reference(cfg, seed)` rebuilds the configuration's documents from the seed
(`corpus.chunk`) and answers a search body the way the configuration states:
BM25 with index-wide statistics and exact field lengths, exact totals, exact
bucket counts on 64-bit columns. It understands the query DSL the traffic
templates use: `match_all`, `match` (operator `or`), `range`, `term` on a
numeric column, `bool` with `must` and `filter`; and the aggregations
`date_histogram` and `terms` on numeric columns.

The control is the same code one step down in precision, the step that would
tempt a later PR (`precision="low"`): BM25 arithmetic rounded to bfloat16
where the configuration states float32, and 64-bit columns and bounds
narrowed to float32 where it states exact 64-bit arithmetic.
"""

from __future__ import annotations

import numpy as np

import corpus

INTERVAL_MS = {"second": 1_000, "minute": 60_000, "hour": 3_600_000,
               "day": 86_400_000}


def bf16(x):
    """Round float values to bfloat16 (nearest even), returned as float64."""
    a = np.ascontiguousarray(np.asarray(x, dtype=np.float32))
    u = a.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


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
        for f, s in cfg["fields"].items():
            if s["kind"] == "text":
                self.lens[f] = np.concatenate([c[f][0] for c in self.chunks])
            else:
                self.cols[f] = np.concatenate([c[f] for c in self.chunks])
        self._postings: dict[str, tuple] = {}

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
        raise ValueError(f"the reference has no query {kind!r}")

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

    def answer(self, body: dict) -> dict:
        """What the body must return: `mask`, `score`, `total`, `aggs`."""
        mask, score = self.evaluate(body.get("query", {"match_all": {}}))
        out = {"mask": mask, "score": score, "total": int(mask.sum())}
        if "aggs" in body:
            out["aggs"] = self.aggregate(body["aggs"], mask)
        return out

    def respond(self, body: dict) -> dict:
        """A response in the program's shape, made from this reference's own
        answer: how the control is put in the program's place."""
        ans = self.answer(body)
        size = body.get("size", 10)
        docs = np.flatnonzero(ans["mask"])
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
