"""The one corpus generator: documents of a configuration, made from the seed.

A configuration's file (`benchmark/configs/<name>.json`) describes its fields
as data; this module turns (configuration, seed, chunk number) into arrays and
into `_bulk` payloads. Chunks are independent (`default_rng([seed, chunk])`),
so ingest workers, the five `GET`s and the reference all regenerate the same
documents without passing them around. Imports numpy only: the ingest workers
run it in processes that never import JAX.

Field kinds (the `kind` key of a field):
  text     `vocab`, `zipf`, `length` {dist: lognormal|uniform, ...}: tokens
           are ranks drawn from a Zipf(`zipf`) law cut at `vocab`, rendered
           as words `t000123` that the standard analyzer keeps whole
  date     uniform epoch millis over `span_days` from `base_millis`; a
           tenth sit within a millisecond of an hour boundary
  choice   `values` with `weights` (an integer column)
  heavy    integers exp(normal(`mu`, `sigma`)) cut at `max`
  ipzipf   IPv4 addresses, Zipf(`zipf`) over `addresses` distinct ones
  vector   `dims` float32 components a document: a cluster drawn by
           Zipf(`zipf`) over `clusters` centres, plus Gaussian noise of
           norm about `spread`, divided by its norm where `normalize`.
           The centres come from `centres_seed`, which belongs to the
           configuration, not to the run's seed, so that queries drawn
           from a cell's `shape_seed` land near documents whatever corpus
           the seed makes. `dtype` states the precision the deployment
           computes in (`float32`, or `bfloat16`); the values sent are
           float32 whatever it says, each printed as the shortest decimal
           that reads back as the same float32 (`f32_text`)
"""

from __future__ import annotations

import functools
import json

import numpy as np

CHUNK = 5_000            # documents per chunk and per `_bulk` request
HOUR_MS = 3_600_000
P10 = 10 ** np.arange(19, dtype=np.int64)
P10F = 10.0 ** np.arange(19)     # exact in float64
DIGITS3 = np.array([list(b"%03d" % i) for i in range(1000)], np.uint8)


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def n_chunks(cfg: dict) -> int:
    return -(-cfg["documents"] // CHUNK)


@functools.lru_cache(maxsize=8)
def zipf_cdf(vocab: int, s: float) -> np.ndarray:
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -s
    c = np.cumsum(w)
    return c / c[-1]


def draw_ranks(rng, vocab: int, s: float, n: int) -> np.ndarray:
    """`n` term ranks (0 = most frequent) from Zipf(s) cut at `vocab`."""
    return np.minimum(np.searchsorted(zipf_cdf(vocab, s), rng.random(n)),
                      vocab - 1).astype(np.int64)


@functools.lru_cache(maxsize=4)
def centres(centres_seed: int, clusters: int, dims: int) -> np.ndarray:
    """The unit-norm cluster centres of a vector field, float64."""
    c = np.random.default_rng(centres_seed).standard_normal((clusters, dims))
    return c / np.linalg.norm(c, axis=1, keepdims=True)


def draw_vectors(rng, f: dict, n: int) -> np.ndarray:
    """`n` vectors from a vector field's law, float32 [n, dims]."""
    c = centres(f["centres_seed"], f["clusters"], f["dims"])
    v = c[draw_ranks(rng, f["clusters"], f["zipf"], n)] \
        + rng.standard_normal((n, f["dims"])) * (f["spread"]
                                                 / np.sqrt(f["dims"]))
    if f.get("normalize", False):
        v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v.astype(np.float32)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    if spec["dist"] == "lognormal":
        sigma = spec["sigma"]
        mu = np.log(spec["mean"]) - sigma * sigma / 2.0
        return np.clip(rng.lognormal(mu, sigma, n).astype(np.int64),
                       spec["min"], spec["max"])
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def chunk(cfg: dict, seed: int, k: int) -> dict:
    """Columns of chunk `k`: for a text field `(lens, ranks)`, for a vector
    field a float32 `[n, dims]` array, for any other an int64 array. Fields
    are drawn in the order of the file."""
    n = min(CHUNK, cfg["documents"] - k * CHUNK)
    rng = np.random.default_rng([seed, k])
    out = {}
    for name, f in cfg["fields"].items():
        kind = f["kind"]
        if kind == "text":
            lens = _lengths(rng, f["length"], n)
            out[name] = (lens, draw_ranks(rng, f["vocab"], f["zipf"],
                                          int(lens.sum())))
        elif kind == "date":
            ts = f["base_millis"] + rng.integers(
                0, f["span_days"] * 86_400_000, n)
            edge = rng.random(n) < 0.1
            hour = (ts // HOUR_MS) * HOUR_MS
            out[name] = np.where(edge, hour - rng.integers(0, 2, n), ts)
        elif kind == "choice":
            w = np.asarray(f["weights"], dtype=np.float64)
            out[name] = np.asarray(f["values"], dtype=np.int64)[
                rng.choice(len(w), size=n, p=w / w.sum())]
        elif kind == "heavy":
            out[name] = np.minimum(
                np.exp(rng.normal(f["mu"], f["sigma"], n)), f["max"]
            ).astype(np.int64)
        elif kind == "ipzipf":
            out[name] = draw_ranks(rng, f["addresses"], f["zipf"], n)
        elif kind == "vector":
            out[name] = draw_vectors(rng, f, n)
        else:
            raise ValueError(f"unknown field kind {kind!r}")
    return out


def words(ranks) -> str:
    return " ".join(["t%06d" % r for r in ranks])


def ip_of(rank: int) -> str:
    # a fixed bijection of ranks onto 10.x.y.z
    v = (int(rank) * 2654435761) & 0xFFFFFF
    return "10.%d.%d.%d" % (v >> 16, (v >> 8) & 255, v & 255)


def f32_text(x) -> tuple[bytes, np.ndarray]:
    """Every float32 of `x` (flattened) as the shortest decimal that reads
    back as the same float32 through float64, as `json.loads` and `float`
    read it; -> the decimals joined by "," and `bounds`, where element `k`
    is `text[bounds[k]:bounds[k + 1] - 1]`. Vectorized where |x| lies in
    [1e-9, 1), as a normalized vector's components do: the digits of the
    nearest p-digit decimal for the least p that reads back (a binary
    search over 1..9; 9 always does), printed `0.ddd`; numpy's shortest
    float32 form one by one elsewhere."""
    v = np.ascontiguousarray(x, dtype=np.float32).ravel()
    if not np.all(np.isfinite(v)):
        raise ValueError("a vector component is not finite")
    a = np.abs(v.astype(np.float64))
    a32 = a.astype(np.float32)
    fast = (a >= 1e-9) & (a < 1.0)
    e = np.floor(np.log10(np.where(fast, a, 0.5))).astype(np.int64)

    def nearest(p):
        sc = p - 1 - e                   # digits after the point, 1..17
        mp = np.rint(a * P10F[sc])
        return mp, sc, (mp / P10F[sc]).astype(np.float32) == a32

    lo, hi = np.ones(len(v), np.int64), np.full(len(v), 9, np.int64)
    for _ in range(4):
        mid = (lo + hi) // 2
        ok = nearest(mid)[2]
        hi, lo = np.where(ok, mid, hi), np.where(ok, lo, mid + 1)
    mp, scale, ok = nearest(hi)
    assert np.all(ok | ~fast)
    m = np.where(fast, mp, 0).astype(np.int64)
    scale = np.where(fast, scale, 1)
    z = fast & (m % 10 == 0)
    while z.any():                       # a carry: 0.0999.. -> 0.10
        m[z] //= 10
        scale[z] -= 1
        z = fast & (m % 10 == 0)
    # "1" and `scale` digits, zero-padded: the "1" becomes the point.
    # Row layout: sign or nothing, "0", ".", digits, ",", nothing; the
    # zero bytes are dropped when the rows are joined.
    chars = np.zeros((len(v), 22), np.uint8)
    chars[:, 0] = np.where(v < 0, ord("-"), 0)
    chars[:, 1] = ord("0")
    lead = (m + P10[scale]) * P10[17 - scale]     # 18 digits, "1" first
    for i in range(6):                   # three digits at a time
        chars[:, 2 + 3 * i:5 + 3 * i] = DIGITS3[(lead // P10[15 - 3 * i])
                                                % 1000]
    chars[:, 2] = ord(".")
    chars *= np.arange(22)[None, :] <= (3 + scale)[:, None]
    chars[np.arange(len(v)), 3 + scale] = ord(",")
    width = (v < 0) + 2 + scale
    for k in np.flatnonzero(~fast):
        t = _one(v[k]) + b","
        chars[k] = 0
        chars[k, :len(t)] = np.frombuffer(t, np.uint8)
        width[k] = len(t) - 1
    text = chars[chars != 0].tobytes()
    return text, np.concatenate([[0], np.cumsum(width + 1)])


def _one(x) -> bytes:
    """numpy's shortest float32 decimal of one value, as JSON reads it."""
    a = abs(float(x))
    if a == 0.0 or 1.0 <= a < 1e15:
        return np.format_float_positional(x, unique=True, trim="0").encode()
    return np.format_float_scientific(x, unique=True, trim="-").encode()


def _vector_text(text: bytes, bounds, dims: int, i: int) -> bytes:
    return b"[" + text[bounds[i * dims]:bounds[(i + 1) * dims] - 1] + b"]"


def render(cfg: dict, cols: dict, i: int, offs: dict) -> dict:
    doc = {}
    for name, f in cfg["fields"].items():
        if f["kind"] == "text":
            o = offs[name]
            doc[name] = words(cols[name][1][o[i]:o[i + 1]].tolist())
        elif f["kind"] == "ipzipf":
            doc[name] = ip_of(cols[name][i])
        elif f["kind"] == "vector":
            doc[name] = json.loads(_vector_text(
                *f32_text(cols[name][i]), f["dims"], 0))
        else:
            doc[name] = int(cols[name][i])
    return doc


def offsets(cfg: dict, cols: dict) -> dict:
    return {name: np.concatenate([[0], np.cumsum(cols[name][0])])
            for name, f in cfg["fields"].items() if f["kind"] == "text"}


def source(cfg: dict, seed: int, doc_id: int) -> dict:
    """The `_source` of document `doc_id` as it was sent."""
    k, i = divmod(doc_id, CHUNK)
    cols = chunk(cfg, seed, k)
    return render(cfg, cols, i, offsets(cfg, cols))


def payload(cfg: dict, seed: int, k: int) -> bytes:
    """The `_bulk` body of chunk `k`; ids are the global document numbers."""
    cols = chunk(cfg, seed, k)
    offs = offsets(cfg, cols)
    n = min(CHUNK, cfg["documents"] - k * CHUNK)
    vectors = {name: (f32_text(cols[name]), f["dims"])
               for name, f in cfg["fields"].items() if f["kind"] == "vector"}
    if vectors:
        return _payload_with_vectors(cfg, cols, offs, vectors, k, n)
    lines = []
    for i in range(n):
        lines.append('{"index":{"_id":"%d"}}' % (k * CHUNK + i))
        lines.append(json.dumps(render(cfg, cols, i, offs),
                                separators=(",", ":")))
    return ("\n".join(lines) + "\n").encode()


def _payload_with_vectors(cfg, cols, offs, vectors, k, n) -> bytes:
    """`payload` where vector fields are spliced in as their `f32_text`:
    each field `"name":value` in the order of the file, as `json.dumps`
    with `separators=(",", ":")` prints the others."""
    plain = {name: f for name, f in cfg["fields"].items()
             if f["kind"] != "vector"}
    lines = []
    for i in range(n):
        doc = render({**cfg, "fields": plain}, cols, i, offs)
        parts = []
        for name in cfg["fields"]:
            if name in vectors:
                (text, bounds), dims = vectors[name]
                value = _vector_text(text, bounds, dims, i)
            else:
                value = json.dumps(doc[name]).encode()
            parts.append(json.dumps(name).encode() + b":" + value)
        lines.append(b'{"index":{"_id":"%d"}}' % (k * CHUNK + i))
        lines.append(b"{" + b",".join(parts) + b"}")
    return b"\n".join(lines) + b"\n"


def mapping(cfg: dict) -> dict:
    types = {"text": {"type": "string"}, "date": {"type": "date"},
             "choice": {"type": "integer"}, "heavy": {"type": "integer"},
             "ipzipf": {"type": "ip"}}
    return {"settings": dict(cfg["index_settings"]),
            "mappings": {"_doc": {"properties": {
                name: {"type": "dense_vector", "dims": f["dims"]}
                if f["kind"] == "vector" else types[f["kind"]]
                for name, f in cfg["fields"].items()}}}}
