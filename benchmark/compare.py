"""The comparison that decides `correct`: answers of the timed path against
the plain reference, reduced to a few numbers, each held to a limit of its own.

Numbers (limits in `benchmark/limits.json`, a cell's file may override one):
  unanswered          sampled requests that never came back, came back 5xx,
                      or whose `_msearch` carried an item error or too few
                      responses (a 429 is a refusal: it counts under `failed`)
  totals_wrong        answers whose `hits.total` is not the reference's
  hits_wrong          answers with the wrong number of hits, hits out of
                      order, a duplicate, a hit that does not match, or a
                      clearly better document left out
  score_rel_err_max   widest |score - reference| / reference over all hits
  source_wrong        hits whose `_source` is not the document that was sent
  buckets_wrong       answers with an aggregation bucket count off

`compare_answer` never raises on a wrong answer: it counts. A rescore's
answer carries the reference's doubts: `alt`, a hit's score on the other side
of its shard's window edge, `reach`, the documents a shard may keep, and
`sure`, those it must; a knn answer its `size` (at most `k` hits).
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NUMBERS = ("unanswered", "totals_wrong", "hits_wrong", "score_rel_err_max",
           "source_wrong", "buckets_wrong")


def load_limits(workload: dict) -> dict:
    with open(os.path.join(HERE, "limits.json")) as f:
        limits = {k: v["limit"] for k, v in json.load(f).items()}
    limits.update(workload.get("limits", {}))
    return limits


class Tally:
    """The numbers compared, accumulated over the sampled answers."""

    def __init__(self):
        self.n = {k: 0 for k in NUMBERS}
        self.n["score_rel_err_max"] = 0.0
        self.answers = 0
        self.hits = 0
        self.notes: list[str] = []

    def note(self, number: str, msg: str) -> None:
        self.n[number] += 1
        if len(self.notes) < 8:
            self.notes.append(f"{number}: {msg}")

    def verdict(self, limits: dict) -> tuple[bool, dict]:
        compared = {k: {"value": self.n[k], "limit": limits[k]}
                    for k in NUMBERS}
        ok = self.answers > 0 and all(
            c["value"] <= c["limit"] for c in compared.values())
        compared["answers_checked"] = {"value": self.answers, "limit": None}
        return ok, compared


def compare_answer(tally: Tally, label: str, body: dict, resp: dict,
                   ref, tol: float) -> None:
    """One search body's response against `ref.answer(body)`."""
    tally.answers += 1
    if "error" in resp or "hits" not in resp:
        tally.note("unanswered", f"{label}: {str(resp)[:200]}")
        return
    want = ref.answer(body, tol)
    hits = resp["hits"]
    if hits["total"] != want["total"]:
        tally.note("totals_wrong",
                   f"{label}: total {hits['total']} != {want['total']}")
    size = want.get("size", body.get("size", 10))
    got = hits["hits"]
    want_n = min(size, want["total"])
    if len(got) != want_n:
        tally.note("hits_wrong", f"{label}: {len(got)} hits, not {want_n}")
    elif want_n:
        _compare_hits(tally, label, body, got, want, ref, tol)
    if "aggs" in want:
        got_aggs = resp.get("aggregations", {})
        for name, buckets in want["aggs"].items():
            have = {b["key"]: b["doc_count"]
                    for b in got_aggs.get(name, {}).get("buckets", [])
                    if b["doc_count"]}
            if have != buckets:
                wrong = [k for k in set(have) | set(buckets)
                         if have.get(k) != buckets.get(k)]
                tally.note("buckets_wrong",
                           f"{label}: {name}: {len(wrong)} of {len(buckets)}"
                           f" buckets differ, e.g. {sorted(wrong)[:3]}")
                break


def _compare_hits(tally, label, body, got, want, reference, tol) -> None:
    ids = np.array([int(h["_id"]) for h in got], dtype=np.int64)
    scores = np.array([h["_score"] for h in got], dtype=np.float64)
    tally.hits += len(ids)
    mask, ref_score = want["mask"], want["score"]
    alt = want.get("alt", ref_score)     # a rescore's other side of its edge
    if "reach" in want:
        mask = mask & want["reach"]
    bad = None
    if not np.all(np.isfinite(scores)):
        bad = "non-finite score"
    elif np.any(np.diff(scores) > 0):
        bad = "hits not sorted by score"
    elif len(np.unique(ids)) != len(ids):
        bad = "duplicate hit"
    elif ids.min() < 0 or ids.max() >= len(mask) or not np.all(mask[ids]):
        bad = "a hit does not match the query"
    if bad:
        tally.note("hits_wrong", f"{label}: {bad}")
        return
    err = np.minimum(*(np.abs(scores - r[ids]) / np.maximum(np.abs(r[ids]),
                                                             1e-30)
                       for r in (ref_score, alt)))
    err = float(np.max(err))
    tally.n["score_rel_err_max"] = max(tally.n["score_rel_err_max"], err)
    # every matching document that scores clearly above the last returned
    # hit must have been returned
    floor = scores[-1] * (1.0 + tol if scores[-1] >= 0 else 1.0 - tol)
    better = want.get("sure", mask) & (np.minimum(ref_score, alt) > floor)
    better[ids] = False
    if better.any():
        tally.note("hits_wrong",
                   f"{label}: {int(better.sum())} better documents missing")
    if body.get("_source", True) is not False:
        for h in got:
            if h.get("_source") != reference.source(int(h["_id"])):
                tally.note("source_wrong",
                           f"{label}: _source of {h['_id']} differs")


def compare_request(tally: Tally, label: str, request: dict, data: bytes,
                    ref, tol: float) -> None:
    """One kept request that came back with 200 (`bodies` is the list of
    search bodies it carried) against what it says."""
    bodies = request["bodies"]
    resp = json.loads(data)
    if request["path"].endswith("/_msearch"):
        items = resp.get("responses", [])
        if len(items) != len(bodies):
            tally.answers += 1
            tally.note("unanswered",
                       f"{label}: {len(items)} responses to {len(bodies)}")
            return
    else:
        items = [resp]
    ref.prepare(bodies)
    for i, (body, item) in enumerate(zip(bodies, items)):
        compare_answer(tally, f"{label}[{i}]", body, item, ref, tol)
