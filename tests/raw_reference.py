"""The packed lane's raw renderer as it was up to PR 29, kept as the plain
reference twin of `serving/executor.response_raw` (tests/test_raw_render.py
holds the two byte for byte equal). Moved here, not rewritten: one call a
body, numpy's per-element string functions, a `str` join and an encode.
"""

import json

import numpy as np


def response_raw(view, index_name: str, srow: np.ndarray,
                 drow: np.ndarray, total: int, *, n_shards: int, took: int,
                 from_: int, size: int) -> str:
    """Assemble one `_source: false` response as raw JSON text with
    vectorized numpy string ops — no per-hit Python objects."""
    sl = srow[from_:from_ + size]
    dl = drow[from_:from_ + size]
    n = int((sl > -np.inf).sum())
    if n:
        # %.9g survives a float32 round-trip, so raw and dict lanes
        # serialize identical score values (advisor r3)
        ids = view.ids_packed[dl[:n]]
        ss = np.char.mod("%.9g", sl[:n].astype(np.float64))
        prefix = ('{"_index":"' + index_name + '","_type":"'
                  + (view.single_type or "_doc") + '","_id":"')
        parts = np.char.add(np.char.add(np.char.add(prefix, ids),
                                        '","_score":'), ss)
        hits_str = "},".join(parts.tolist()) + "}"
    else:
        hits_str = ""
    mx = "%.9g" % float(srow[0]) \
        if srow.size and srow[0] > -np.inf else "null"
    return ('{"took":%d,"timed_out":false,"_shards":{"total":%d,'
            '"successful":%d,"failed":0},"hits":{"total":%d,"max_score":%s,'
            '"hits":[%s]}}' % (took, n_shards, n_shards, int(total), mx,
                               hits_str))


def msearch_payload(responses: list) -> bytes:
    """`node.msearch(raw=True)`'s serialisation of the same vintage: the
    raw lane's items are `str`, every other item a dict."""
    payload = '{"responses":[' + ",".join(
        r if isinstance(r, str) else json.dumps(r)
        for r in responses) + ']}'
    return payload.encode()
