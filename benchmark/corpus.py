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
"""

from __future__ import annotations

import functools
import json

import numpy as np

CHUNK = 5_000            # documents per chunk and per `_bulk` request
HOUR_MS = 3_600_000


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
    """Columns of chunk `k`: for a text field `(lens, ranks)`, for any
    other an int64 array. Fields are drawn in the order of the file."""
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
        else:
            raise ValueError(f"unknown field kind {kind!r}")
    return out


def words(ranks) -> str:
    return " ".join(["t%06d" % r for r in ranks])


def ip_of(rank: int) -> str:
    # a fixed bijection of ranks onto 10.x.y.z
    v = (int(rank) * 2654435761) & 0xFFFFFF
    return "10.%d.%d.%d" % (v >> 16, (v >> 8) & 255, v & 255)


def render(cfg: dict, cols: dict, i: int, offs: dict) -> dict:
    doc = {}
    for name, f in cfg["fields"].items():
        if f["kind"] == "text":
            o = offs[name]
            doc[name] = words(cols[name][1][o[i]:o[i + 1]].tolist())
        elif f["kind"] == "ipzipf":
            doc[name] = ip_of(cols[name][i])
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
    lines = []
    for i in range(n):
        lines.append('{"index":{"_id":"%d"}}' % (k * CHUNK + i))
        lines.append(json.dumps(render(cfg, cols, i, offs),
                                separators=(",", ":")))
    return ("\n".join(lines) + "\n").encode()


def mapping(cfg: dict) -> dict:
    types = {"text": {"type": "string"}, "date": {"type": "date"},
             "choice": {"type": "integer"}, "heavy": {"type": "integer"},
             "ipzipf": {"type": "ip"}}
    return {"settings": dict(cfg["index_settings"]),
            "mappings": {"_doc": {"properties": {
                name: types[f["kind"]]
                for name, f in cfg["fields"].items()}}}}
