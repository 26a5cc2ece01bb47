"""The watchdog's limit (`benchmark/run.py`): `cold` for a cell's first run
on a compile cache, however many entries other cells have left there, and
`warm` once this cell's warm-up has settled on that cache under the same
checkout, program, benchmark code and cell files; the harness leaves the
mark only once the warm-up has settled."""

import json
import os
import shutil

import pytest

import harness
import run
from conftest import BENCH, REPO

with open(os.path.join(BENCH, "harness.json")) as f:
    LIMITS = json.load(f)["watchdog_s"]
CELL, OTHER = "wiki.match-top10", "wiki.rerank-top1000"
KEY = run.program_key(CELL)


def _filled(tmp_path, *settled):
    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / "jit_bm25_serve_packed-0123abcd-cache").write_bytes(b"\0" * 64)
    for cell in settled:
        run.mark_settled(str(cache), cell, run.program_key(cell))
    return str(cache)


def test_an_empty_cache_gives_cold(tmp_path):
    assert run.watchdog_limit(LIMITS, str(tmp_path / "cache"), CELL,
                              KEY) == LIMITS["cold"]


def test_a_cache_another_cell_filled_gives_cold(tmp_path):
    cache = _filled(tmp_path, OTHER)
    assert run.watchdog_limit(LIMITS, cache, CELL, KEY) == LIMITS["cold"]
    assert run.watchdog_limit(LIMITS, cache, OTHER,
                              run.program_key(OTHER)) == LIMITS["warm"]


def test_a_cache_this_cell_settled_on_gives_warm(tmp_path):
    cache = _filled(tmp_path, OTHER, CELL)
    assert run.watchdog_limit(LIMITS, cache, CELL, KEY) == LIMITS["warm"]
    assert LIMITS["warm"] < LIMITS["cold"]


def _checkout(tmp_path):
    """The files a key reads, in a checkout of their own."""
    root = tmp_path / "checkout"
    for rel in ("BENCHMARK.json", "benchmark/run.py",
                f"benchmark/workloads/{CELL}.json",
                "benchmark/configs/wiki-bm25-5s.json"):
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), root / rel)
    (root / "elasticsearch_tpu").mkdir()
    (root / "elasticsearch_tpu" / "node.py").write_text("")
    return root


def test_another_program_or_checkout_gives_cold(tmp_path):
    root = _checkout(tmp_path)
    elsewhere = run.program_key(CELL, str(root))
    assert elsewhere != KEY and run.program_key(CELL, REPO) == KEY
    cache = _filled(tmp_path, CELL)
    assert run.watchdog_limit(LIMITS, cache, CELL, elsewhere) == \
        LIMITS["cold"]
    # two checkouts that share the cache keep each its own mark
    run.mark_settled(cache, CELL, elsewhere)
    assert run.watchdog_limit(LIMITS, cache, CELL, elsewhere) == \
        run.watchdog_limit(LIMITS, cache, CELL, KEY) == LIMITS["warm"]
    assert run.program_key(OTHER) != KEY


@pytest.mark.parametrize("change", [
    "elasticsearch_tpu/node.py", "benchmark/run.py",
    f"benchmark/workloads/{CELL}.json", "benchmark/configs/wiki-bm25-5s.json"],
    ids=["package", "benchmark", "workload", "configuration"])
def test_a_changed_package_benchmark_or_cell_file_gives_cold(tmp_path,
                                                             change):
    root = _checkout(tmp_path)
    before = run.program_key(CELL, str(root))
    cache = _filled(tmp_path)
    run.mark_settled(cache, CELL, before)
    assert run.watchdog_limit(LIMITS, cache, CELL, before) == LIMITS["warm"]
    with open(root / change, "a") as f:
        f.write("\n")
    after = run.program_key(CELL, str(root))
    assert after != before
    assert run.watchdog_limit(LIMITS, cache, CELL, after) == LIMITS["cold"]


class _Settles:
    def __init__(self, *a):
        pass

    def window(self, *a, **kw):
        raise LookupError("no window in this test")

    def close(self):
        pass


class _NeverSettles:
    def __init__(self, *a):
        raise harness.Unsettled("the last round compiled")


@pytest.mark.parametrize("serving, marks", [(_Settles, 1),
                                            (_NeverSettles, 0)])
def test_the_mark_is_left_once_the_warm_up_settles(serving, marks,
                                                   monkeypatch):
    monkeypatch.setattr(harness, "Serving", serving)
    called = []
    with pytest.raises((LookupError, harness.Unsettled)):
        harness.run(CELL, 2 ** 31 + 7, 2.0, False, platform="cpu",
                    overrides={"chips": 8},
                    on_settled=lambda: called.append(1))
    assert len(called) == marks
