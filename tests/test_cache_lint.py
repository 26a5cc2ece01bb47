"""Static tripwire: no new ad-hoc dict-as-cache attributes.

ISSUE 3 replaced the scatter of unbounded `dict`-shaped caches
(`_request_cache`, `_geo_dist_cache`, `_packed_cache`, ...) with
`common.cache.Cache` — byte-accounted, evicting, observable. This lint
(the `test_no_retrace.py` pattern: grep the source, fail on drift) keeps
it that way: assigning a bare `{}` / `dict(...)` / `OrderedDict(...)` to
any name ending in `_cache` anywhere under `elasticsearch_tpu/` fails
unless the (file, name) pair is explicitly allowlisted below with a
reason. New caches must be `Cache` instances — bounded and observable —
or argue their way onto the allowlist in review."""

import os
import re

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "elasticsearch_tpu")

# (relative path, attribute/variable name) -> why a plain dict is OK here
ALLOWLIST = {
    # keyed by the live segment-set tuple, bounded by shard count, holds
    # no payload beyond the ShardSearcher the engine owns anyway
    ("index/index_service.py", "_searcher_cache"),
}

# an assignment like `self._foo_cache = {}` / `x_cache: dict = dict()` /
# `bar_cache = OrderedDict()`. `_steps`/`_memo` names join the pattern:
# ISSUE 6 found a dict-as-cache of compiled programs under
# elasticsearch_tpu/parallel/ (`_steps`) that the `_cache` suffix alone
# never caught — dict memos by another name are still unbounded caches
_DICT_CACHE_RX = re.compile(
    r"(?:self\.)?(\w*(?:_cache|_steps|_memo))\s*(?::\s*[^=]+)?=\s*"
    r"(?:\{\}|dict\(|collections\.OrderedDict\(|OrderedDict\()")


def test_no_adhoc_dict_caches():
    offenders = []
    for root, _dirs, files in os.walk(PKG):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, PKG)
            if rel == os.path.join("common", "cache.py"):
                continue        # the one place a raw store is the point
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    m = _DICT_CACHE_RX.search(line)
                    if m and (rel, m.group(1)) not in ALLOWLIST:
                        offenders.append(f"{rel}:{lineno} [{m.group(1)}]")
    assert not offenders, (
        "ad-hoc dict-as-cache attributes found — use common.cache.Cache "
        "(bounded, byte-accounted, observable) or allowlist with a "
        "reason:\n  " + "\n  ".join(offenders))


# -- no direct EXEC_LOCK acquisition (ISSUE 19) ------------------------------
#
# Per-node device pools moved mesh dispatch onto pool-private locks via
# mesh_exec.exec_guard(pool) — which also counts acquisitions/waits into
# exec_lock_stats(). A NEW `with EXEC_LOCK` under parallel/ or cluster/
# would silently re-serialize every node through the process-wide lock
# AND dodge the contention counters, so it fails here unless the
# (file, line-content) is allowlisted as a deliberate legacy
# shared-pool fallback.

# relative path under elasticsearch_tpu/ -> why holding the shared lock
# directly is OK there (none today: every dispatch goes through
# exec_guard, which takes EXEC_LOCK itself only for pool-less stacks)
EXEC_LOCK_ALLOWLIST: dict = {}

_EXEC_LOCK_RX = re.compile(
    r"with\s+(?:mesh_exec\.)?(?:SHARED_)?EXEC_LOCK\b")


def test_no_direct_exec_lock_acquisition():
    offenders = []
    for sub in ("parallel", "cluster"):
        for root, _dirs, files in os.walk(os.path.join(PKG, sub)):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(root, fname)
                rel = os.path.relpath(path, PKG)
                with open(path) as f:
                    for lineno, line in enumerate(f, 1):
                        if _EXEC_LOCK_RX.search(line) \
                                and rel not in EXEC_LOCK_ALLOWLIST:
                            offenders.append(f"{rel}:{lineno}")
    assert not offenders, (
        "direct EXEC_LOCK acquisition found — dispatch through "
        "mesh_exec.exec_guard(pool) (per-node lock + contention "
        "counters) or allowlist as a legacy shared-pool fallback:\n  "
        + "\n  ".join(offenders))
