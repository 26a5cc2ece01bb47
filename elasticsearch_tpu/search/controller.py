"""Search-phase controller: the cross-shard reduce for the 2-phase protocol.

Analog of /root/reference/src/main/java/org/elasticsearch/search/controller/
SearchPhaseController.java — sortDocs (:147,233) merges per-shard top-k,
merge (:282-399) combines hits + aggregation reduce into the final response.

On the mesh lane the same reduce runs on-device as collectives
(parallel/mesh_exec.py); this host-side controller serves the
engine-per-shard path (local multi-shard node, and later the DCN
coordinator between pods).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from ..common.metrics import current_profiler
from . import sort as sort_mod
from .shard_searcher import QuerySearchResult, ShardSearcher, FetchedHit


@dataclass
class ReducedDocs:
    """Winner list after the query-phase reduce: which docs to fetch where."""
    shard_order: list[int]          # shard id per result slot (len <= size)
    doc_keys: list[int]             # doc key per result slot
    scores: list[float]
    sort_values: list[list] | None  # materialized per-key values per slot
    total_hits: int
    max_score: float


def sort_docs(results: list[QuerySearchResult], *, from_: int, size: int,
              sort=None, query_row: int = 0) -> ReducedDocs:
    """Merge per-shard top-k into the global winner list
    (ref SearchPhaseController.sortDocs — TopDocs.merge semantics: score
    desc / sort-key asc, shard index breaks ties like the reference's
    shard-ordinal tie-break). Field sorts compare MATERIALIZED values
    (strings/numbers), never ordinals — see search/sort.py."""
    t0 = time.perf_counter()
    from ..common.device_stats import lane_chosen
    from ..common.metrics import record_host_merge
    record_host_merge()
    # the fan-out's coordinator-side reduce: when the mesh lane serves, no
    # host merge runs at all — this note marks which reduce path the
    # request actually rode
    lane_chosen("reduce", "host_merge")
    sort = sort_mod.normalize(sort)
    entries = []   # (primary_key, shard_idx, pos, doc_key, score, sort_val)
    total = 0
    max_score = float("-inf")
    for si, r in enumerate(results):
        total += int(r.total_hits[query_row])
        ms = float(r.max_score[query_row])
        if not np.isnan(ms):
            max_score = max(max_score, ms)
        keys = r.doc_keys[query_row]
        for pos in range(keys.shape[0]):
            key = int(keys[pos])
            if key < 0:
                continue
            score = float(r.scores[query_row][pos])
            if sort is None:
                primary = -score if not np.isnan(score) else float("inf")
                sv = None
            else:
                sv = r.sort_values[query_row][pos]
                primary = sort_mod.compare_key(sv, sort)
            entries.append((primary, si, pos, key, score, sv))
    entries.sort(key=lambda e: (e[0], e[1], e[2]))
    window = entries[from_: from_ + size]
    prof = current_profiler()
    if prof is not None:
        prof.record_phase("reduce", (time.perf_counter() - t0) * 1000)
    return ReducedDocs(
        shard_order=[e[1] for e in window],
        doc_keys=[e[3] for e in window],
        scores=[e[4] for e in window],
        sort_values=[e[5] for e in window] if sort is not None else None,
        total_hits=total,
        max_score=max_score if max_score > float("-inf") else float("nan"))


def fuse_hybrid(text_results: list[QuerySearchResult],
                knn_results: list[QuerySearchResult], spec, *,
                from_: int, size: int, query_row: int = 0) -> ReducedDocs:
    """First-class BM25 + vector fusion (the body's `"rank"` section,
    search/query_parser.RankSpec): each retriever's per-shard lists merge
    into a GLOBAL ranked list first (sort_docs — RRF ranks are global, as
    in the reference's coordinator-level RRF), then the two lists fuse on
    device (ops/ann.rrf_fuse / weighted_fuse) over compact candidate ids
    and the winners come back as an ordinary ReducedDocs."""
    import numpy as _np
    import jax.numpy as _jnp

    from ..ops import ann as ann_ops

    def width(results):
        return sum(r.doc_keys.shape[1] for r in results) or 1

    text_red = sort_docs(text_results, from_=0, size=width(text_results),
                         query_row=query_row)
    knn_red = sort_docs(knn_results, from_=0, size=width(knn_results),
                        query_row=query_row)
    # compact (shard, doc_key) -> small int ids so the device kernel
    # matches candidates with an exact integer-equality plane
    id_of: dict[tuple[int, int], int] = {}

    def ids_for(red):
        return [id_of.setdefault((si, dk), len(id_of))
                for si, dk in zip(red.shard_order, red.doc_keys)]

    ids_a, ids_b = ids_for(text_red), ids_for(knn_red)
    rev = {v: k for k, v in id_of.items()}
    Ka, Kb = max(len(ids_a), 1), max(len(ids_b), 1)
    keys_a = _np.full((1, Ka), -1, _np.int64)
    keys_a[0, : len(ids_a)] = ids_a
    keys_b = _np.full((1, Kb), -1, _np.int64)
    keys_b[0, : len(ids_b)] = ids_b
    w = _jnp.asarray([spec.query_weight, spec.knn_weight], _jnp.float32)
    k = max(from_ + size, 1)
    if spec.mode == "rrf":
        top, keys = ann_ops.rrf_fuse(
            _jnp.asarray(keys_a), _jnp.asarray(keys_b), w,
            _jnp.float32(spec.rank_constant), k=k)
    else:
        sc_a = _np.full((1, Ka), -_np.inf, _np.float32)
        sc_a[0, : len(ids_a)] = _np.nan_to_num(
            _np.asarray(text_red.scores, _np.float32))
        sc_b = _np.full((1, Kb), -_np.inf, _np.float32)
        sc_b[0, : len(ids_b)] = _np.nan_to_num(
            _np.asarray(knn_red.scores, _np.float32))
        top, keys = ann_ops.weighted_fuse(
            _jnp.asarray(keys_a), _jnp.asarray(sc_a),
            _jnp.asarray(keys_b), _jnp.asarray(sc_b), w, k=k,
            normalize=spec.normalize)
    top = _np.asarray(top)[0]
    keys = _np.asarray(keys)[0]
    slots = [(rev[int(kid)], float(s))
             for s, kid in zip(top, keys)
             if _np.isfinite(s) and kid >= 0][from_: from_ + size]
    return ReducedDocs(
        shard_order=[sh for (sh, _dk), _s in slots],
        doc_keys=[dk for (_sh, dk), _s in slots],
        scores=[s for _key, s in slots],
        sort_values=None,
        total_hits=max(text_red.total_hits, knn_red.total_hits),
        max_score=slots[0][1] if slots else float("nan"))


def fetch_and_merge(reduced: ReducedDocs, searchers: list[ShardSearcher],
                    source_filter=None, fields_spec=None) -> list[dict]:
    """Fetch phase fan-out to winning shards only + final hit assembly
    (ref FetchPhase + SearchPhaseController.merge). `searchers` is aligned
    with the results list passed to sort_docs."""
    t0 = time.perf_counter()
    # group result slots by shard (the docIdsToLoad structure)
    by_shard: dict[int, list[int]] = {}
    for slot, si in enumerate(reduced.shard_order):
        by_shard.setdefault(si, []).append(slot)
    hits_by_slot: dict[int, FetchedHit] = {}
    for si, slots in by_shard.items():
        keys = [reduced.doc_keys[s] for s in slots]
        scores = np.asarray([reduced.scores[s] for s in slots], np.float32)
        svs = [reduced.sort_values[s] for s in slots] \
            if reduced.sort_values is not None else None
        fetched = searchers[si].execute_fetch_phase(keys, scores, svs)
        for slot, hit in zip(slots, fetched):
            hits_by_slot[slot] = hit
    out = []
    for slot in range(len(reduced.doc_keys)):
        h = hits_by_slot[slot]
        src = h.source
        if source_filter is not None:
            src = source_filter(src)
        entry = {
            "_index": None,   # filled by the caller
            "_type": h.type_name,
            "_id": h.doc_id,
            "_score": None if np.isnan(h.score) else float(h.score),
        }
        if fields_spec is not None:
            # body `fields`: dot-path extraction from source, values as
            # lists; _source omitted unless listed (ref
            # search/fetch/fieldvisitor + FetchPhase stored-fields contract)
            flds = {}
            for f in fields_spec:
                if f == "_source":
                    continue
                v = _path_get(h.source, f)
                if v is not None:
                    flds[f] = v if isinstance(v, list) else [v]
            if flds:
                entry["fields"] = flds
            if "_source" not in fields_spec:
                src = None
        if src is not None:     # None = `_source: false` (key omitted)
            entry["_source"] = src
        if reduced.sort_values is not None:
            entry["sort"] = h.sort_value
        out.append(entry)
    prof = current_profiler()
    if prof is not None:
        prof.record_phase("fetch", (time.perf_counter() - t0) * 1000)
    return out


def _path_get(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj
