"""The one traffic generator: a cell's file of parameters -> its requests.

A cell's file (`benchmark/workloads/<name>.json`) gives the loop (`open` with
`rate_per_s`, or `closed` with `clients`), the endpoint, how many search
bodies one request carries, and a `mix` of weighted body templates. A
template is a search body in which these placeholders are drawn per body:

  {"$text": {"field": f, "min": a, "max": b, "skip_top": s}}
      a..b distinct terms from field f's own unigram law (the
      configuration's Zipf), its `s` most frequent terms left out
  {"$time_range": {"field": f, "min_days": a, "max_days": b}}
      {"gte": lo, "lt": hi}: a window of a..b days inside the field's span
  {"$choice": [v, ...]}
      one of the values
  {"$vector": {"field": f}}
      one query vector from vector field f's own law (a cluster by its
      Zipf, the configuration's centres, its `spread`, `normalize`), as
      the list of floats that its float32 values print as (`corpus.f32_text`)

Nothing rides in the window but the mix. `warmup.pilots` are bodies sent
before it, each `warmup.copies` times at once (`pilot_requests`): shapes the
window meets too seldom for the replay of its own traffic to find them.

Every seed gets the same set of bodies and the same set of arrival gaps
(both drawn from the cell's `shape_seed`), in another order, against a
corpus that differs: the seed changes the order of the work, not its amount.
A closed loop sends only as many requests as it gets through, so its cell
states an `order_block`: the seed changes the order inside each run of that
many requests, and the window's requests stay the same set but for its last
block.
"""

from __future__ import annotations

import json

import numpy as np

import corpus


def _expand(node, cfg: dict, rng):
    if isinstance(node, list):
        return [_expand(v, cfg, rng) for v in node]
    if not isinstance(node, dict):
        return node
    if len(node) == 1:
        (key, spec), = node.items()
        if key == "$text":
            return _text(spec, cfg, rng)
        if key == "$time_range":
            return _time_range(spec, cfg, rng)
        if key == "$choice":
            return spec[int(rng.integers(0, len(spec)))]
        if key == "$vector":
            v = corpus.draw_vectors(rng, cfg["fields"][spec["field"]], 1)
            return json.loads(b"[" + corpus.f32_text(v)[0][:-1] + b"]")
    return {k: _expand(v, cfg, rng) for k, v in node.items()}


def _text(spec: dict, cfg: dict, rng) -> str:
    f = cfg["fields"][spec["field"]]
    want = int(rng.integers(spec["min"], spec["max"] + 1))
    ranks: list[int] = []
    while len(ranks) < want:
        for r in corpus.draw_ranks(rng, f["vocab"], f["zipf"], 4 * want):
            if r >= spec.get("skip_top", 0) and r not in ranks:
                ranks.append(int(r))
                if len(ranks) == want:
                    break
    return corpus.words(ranks)


def _time_range(spec: dict, cfg: dict, rng) -> dict:
    f = cfg["fields"][spec["field"]]
    span = f["span_days"] * 86_400_000
    width = int(rng.integers(spec["min_days"] * 86_400_000,
                             spec["max_days"] * 86_400_000 + 1))
    lo = f["base_millis"] + int(rng.integers(0, span - width + 1))
    return {"gte": lo, "lt": lo + width}


def n_requests(workload: dict, seconds: float) -> int:
    if workload["loop"] == "open":
        return max(1, round(workload["rate_per_s"] * seconds))
    return int(workload["requests_per_10s"] * max(seconds, 1.0) / 10.0) + 4


def build(workload: dict, cfg: dict, seed: int,
          seconds: float) -> list[dict]:
    """The requests of one run, in the order they are sent. Each has
    `path`, `bodies` (the search bodies), `payload` (the bytes as text)
    and, in an open loop, `due` (seconds from the window's start)."""
    n = n_requests(workload, seconds)
    per = workload.get("bodies_per_request", 1)
    shape = np.random.default_rng(workload["shape_seed"])
    weights = np.array([m["weight"] for m in workload["mix"]], dtype=float)
    kinds = shape.choice(len(weights), size=n * per, p=weights / weights.sum())
    bodies = [_expand(workload["mix"][k]["body"], cfg, shape) for k in kinds]
    order = np.random.default_rng([seed, 1])
    block = workload.get("order_block", n)
    groups = np.concatenate([lo + order.permutation(min(block, n - lo))
                             for lo in range(0, n, block)])
    requests = [_request(workload, cfg, bodies[g * per:(g + 1) * per])
                for g in groups]               # whole requests change places
    if workload["loop"] == "open":
        gaps = shape.exponential(1.0, n)
        scale = seconds / (gaps.sum() + gaps.mean())
        due = np.cumsum(gaps[order.permutation(n)]) * scale
        for req, d in zip(requests, due):
            req["due"] = float(d)
    return requests


def _request(workload: dict, cfg: dict, bodies: list[dict]) -> dict:
    if workload["endpoint"] == "_msearch":
        head = json.dumps({"index": cfg["index"]})
        payload = "".join(f"{head}\n{json.dumps(b)}\n" for b in bodies)
        path = "/_msearch"
    else:
        payload = json.dumps(bodies[0])
        path = f"/{cfg['index']}/{workload['endpoint']}"
    return {"path": path, "bodies": bodies, "payload": payload}


def pilot_requests(workload: dict, cfg: dict) -> list[dict]:
    """One request for each of the cell's `warmup.pilots`, alone in it."""
    return [_request(workload, cfg, [b])
            for b in workload["warmup"].get("pilots", [])]


def sample(workload: dict, n: int, seed: int) -> list[int]:
    """Which requests' answers are kept and compared, drawn from the seed:
    `{"requests": k}` draws k of the n, `{"every": m}` takes every m-th
    from an offset the seed draws (for a closed loop, whose count is not
    known beforehand), and "all" takes all."""
    spec = workload["sample"]
    if spec == "all":
        return list(range(n))
    rng = np.random.default_rng([seed, 2])
    if "every" in spec:
        return list(range(int(rng.integers(0, spec["every"])), n,
                          spec["every"]))
    return sorted(rng.choice(n, size=min(n, spec["requests"]),
                             replace=False).tolist())
