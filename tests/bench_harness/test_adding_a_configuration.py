"""A configuration and its cell come in as new files and new entries alone.
In a copy of `BENCHMARK.json`, `benchmark/` and `tests/bench_harness/`, the
vector fixture is added as a later PR adds one: its configuration and its
traffic as files under `benchmark/configs/` and `benchmark/workloads/`, the
pin of each printed by `test_vocabulary_invariance.py` into a new file under
`pins/`, its entries (`fixtures/vector-entries.json`) appended to
`BENCHMARK.json`, and a per-layer metric of its own with its reader spec
(`fixtures/vector-addition.json`). The cell joins every metric that lists
cells, `.lat` and `.dash` ones too, so that no test that holds a metric's
list of cells, or its place in the list, goes unseen. The copy's tests that
read `BENCHMARK.json`'s entries then pass; the digests of the files this
suite pins already are left out there, as they are checked here. With a pin
left out, or a pin whose file is gone, the copy's pin test fails."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import B, BENCH, FIXTURES, with_entries

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG, CELL = "vectors-768d-fixture", "vectors.hybrid-rescore"
VOCAB = "tests/bench_harness/test_vocabulary_invariance.py"
PIN_TEST = f"{VOCAB}::test_every_configuration_and_cell_file_is_pinned"
# every test that reads the entries of `BENCHMARK.json`
ENTRY_TESTS = [
    "tests/bench_harness/test_benchmark_json.py",
    "tests/bench_harness/test_span_readers.py",
    "tests/bench_harness/test_wait_readers.py",
    *(f"tests/bench_harness/test_dash_panels.py::{t}" for t in (
        "test_the_configuration_keeps_the_sources_shapes",
        "test_the_traffic_is_the_dashboards_mix")),
    *(f"tests/bench_harness/test_dashboard_mesh.py::{t}" for t in (
        "test_the_configuration_is_the_one_chip_deployments_but_for_its_"
        "layout", "test_the_traffic_is_the_one_chip_cells_mix",
        "test_the_cell_joins_the_one_chip_cells_metrics_and_adds_two")),
    *(f"tests/bench_harness/test_filtered_top1000.py::{t}" for t in (
        "test_the_configuration_is_wiki_bm25_5s_plus_two_columns",
        "test_the_traffic_is_the_rerank_cells_loop_with_a_filter_in_every_"
        "body", "test_the_cell_joins_the_rerank_cells_metrics_and_adds_two"))]


def _pytest(root, *args):
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "--rootdir", str(root), *args],
        cwd=root, capture_output=True, text=True, timeout=240)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    root = tmp_path_factory.mktemp("added")
    ignore = shutil.ignore_patterns(".run", "__pycache__")
    shutil.copytree(BENCH, root / "benchmark", ignore=ignore)
    shutil.copytree(HERE, root / "tests" / "bench_harness", ignore=ignore)
    shutil.copy(os.path.join(FIXTURES, f"{CONFIG}.json"),
                root / "benchmark" / "configs")
    shutil.copy(os.path.join(FIXTURES, f"{CELL}.json"),
                root / "benchmark" / "workloads")
    with open(os.path.join(FIXTURES, "vector-entries.json")) as f:
        entries = json.load(f)
    with open(os.path.join(FIXTURES, "vector-addition.json")) as f:
        addition = json.load(f)
    entries["configs"][0]["file"] = f"benchmark/configs/{CONFIG}.json"
    del entries["workloads"][0]["file"]
    entries["per_layer"] += addition["per_layer"]
    entries["joins"] = [m["name"] for m in B["end_to_end"] + B["per_layer"]
                        if "workloads" in m]
    for name, spec in addition["metrics"].items():
        (root / "benchmark" / "metrics" / f"{name}.json").write_text(
            json.dumps(spec, indent=2))
    (root / "BENCHMARK.json").write_text(
        json.dumps(with_entries(B, entries), indent=1))
    for kind, name in (("configs", CONFIG), ("workloads", CELL)):
        pin = subprocess.run(
            [sys.executable, VOCAB, f"benchmark/{kind}/{name}.json"],
            cwd=root, capture_output=True, text=True, timeout=120,
            check=True).stdout
        (root / "tests" / "bench_harness" / "pins" / kind /
         f"{name}.json").write_text(pin)
    return root


def test_the_added_configuration_and_cell_pass_the_copys_checks(added):
    r = _pytest(added, *ENTRY_TESTS, PIN_TEST,
                f"{VOCAB}::test_corpus_chunk_and_payload_are_the_parents"
                f"[{CONFIG}]",
                f"{VOCAB}::test_requests_bytes_and_answers_are_the_parents"
                f"[{CELL}]")
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    for case in (f"test_config_entry[{CONFIG}]",
                 f"test_workload_entry[{CELL}]",
                 "test_metric_entry[vector_program_ms.qps]",
                 "test_the_source_of_each_configuration_is_kept",
                 "test_the_entry_reads_what_this_pr_adds_where_it_is"
                 "[batcher_wake_ms.lat]",
                 "test_the_cell_joins_the_rerank_cells_metrics_and_adds_two",
                 PIN_TEST):
        assert f"{case} PASSED" in r.stdout, case


@pytest.mark.parametrize("gone", [f"pins/configs/{CONFIG}.json",
                                  f"pins/workloads/{CELL}.json",
                                  f"../../benchmark/workloads/{CELL}.json"],
                         ids=["a-configuration-pin", "a-cell-pin",
                              "a-pinned-cell-file"])
def test_the_pin_test_fails_without_a_pin_or_its_file(added, tmp_path,
                                                      gone):
    root = tmp_path / "copy"
    shutil.copytree(added, root)
    os.remove(root / "tests" / "bench_harness" / gone)
    r = _pytest(root, PIN_TEST)
    assert r.returncode == 1, r.stdout[-4000:] + r.stderr[-2000:]
    assert f"{PIN_TEST} FAILED" in r.stdout
