"""The harness end to end on the CPU at 2,000 documents, for each cell of
`BENCHMARK.json` and for the cell that waits under `benchmark/` (through
`B_PLUS`, the entries a later PR would add): the plain run is correct, the
low-precision control put in the program's place is not, and a timed path
that alters an answer where it is produced, or refuses a valid body, is not.
The command itself refuses a machine without the chip."""

import json
import os
import shutil
import subprocess
import sys
import zlib

import pytest

import harness
from conftest import B as BENCH, B_PLUS, REPO

CELLS = [w["name"] for w in B_PLUS["workloads"]]
SMALL = {"documents": 2000, "chips": 8, "rate_per_s": 12.0}


@pytest.fixture(scope="module")
def run(bench_plus_file):
    def _run(name, trace=False, **over):
        procs = []
        try:
            return harness.run(name, 2 ** 31 + 11, 3.0, trace,
                               platform="cpu", overrides={**SMALL, **over},
                               control=True, procs=procs,
                               bench_file=bench_plus_file)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p.wait()
    return _run


@pytest.fixture(scope="module", params=CELLS)
def result(request, run):
    return request.param, run(request.param, sample={"every": 2}
                              if "rerank" in request.param else "all")


def test_run_is_correct_and_reports_its_end_to_end_metrics(result):
    name, out = result
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in B_PLUS["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "compared"
    assert out["compared"]["answers_checked"]["value"] > 0
    assert out["notes"]["compiles_in_window"] >= 0
    assert not os.path.exists(os.path.join(harness.HERE, ".run", name, "data"))


def test_control_is_not_correct(result):
    name, out = result
    control = out["control"]
    assert control["correct"] is False
    over = [k for k, c in control["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over, control


def test_traced_run_reports_per_layer_metrics_and_never_a_zero_roofline(run):
    out = run("wiki.match-top10", trace=True, sample={"requests": 10})
    assert out["correct"] is True
    assert out["metrics"]["compiles_in_window.lat"]["value"] == 0
    assert out["metrics"]["device_lane_share.lat"]["value"] == 100.0
    # no device plane in a CPU trace: the readers return nothing
    assert not any("roofline" in k or "idle" in k for k in out["metrics"])
    assert out["device"]["window_s"] > 0 and "breakdown" in out


def _over(out):
    return [k for k, c in out["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]


@pytest.mark.parametrize("name", ["wiki.rerank-top1000",
                                  "httplogs.dashboard"])
def test_an_altered_answer_is_not_correct(name, run, monkeypatch):
    """The timed path broken underneath: the fault a search cell can have
    is an answer altered where it is produced."""
    from elasticsearch_tpu.node import NodeService
    from elasticsearch_tpu.serving import executor
    raw = executor.response_raw

    def skewed_raw(view, index_name, srow, *a, **kw):
        return raw(view, index_name, srow * 1.001, *a, **kw)

    search = NodeService.search

    def skewed_search(self, index, body=None, **kw):
        out = search(self, index, body, **kw)
        for agg in (out.get("aggregations") or {}).values():
            if agg.get("buckets"):
                agg["buckets"][0]["doc_count"] += 1
        return out

    monkeypatch.setattr(executor, "response_raw", skewed_raw)
    monkeypatch.setattr(NodeService, "search", skewed_search)
    out = run(name, sample={"every": 2} if "rerank" in name else "all")
    assert out["correct"] is False
    assert _over(out) in (["score_rel_err_max"], ["buckets_wrong"]), \
        out["compared"]


@pytest.mark.parametrize("status", [400, 404])
def test_a_valid_body_that_is_refused_is_not_correct(status, run,
                                                     monkeypatch):
    """Only a 429 is a stated refusal. A sampled request that the program
    answers with another status is neither compared nor right: it counts
    as unanswered, also where every other answer was compared."""
    from elasticsearch_tpu.rest.http_server import RestController
    dispatch = RestController.dispatch
    with open(os.path.join(harness.HERE, "workloads",
                           "wiki.match-top10.json")) as f:
        pilots = {json.dumps(b).encode()
                  for b in json.load(f)["warmup"]["pilots"]}

    def refusing(self, method, path, params, body, *a, **kw):
        if path.endswith("/_search") and body not in pilots \
                and zlib.crc32(body) % 5 == 0:
            return status, {"error": "refused by the test", "status": status}
        return dispatch(self, method, path, params, body, *a, **kw)

    monkeypatch.setattr(RestController, "dispatch", refusing)
    out = run("wiki.match-top10", sample={"requests": 10})
    assert out["correct"] is False and out["failed"] > 0
    assert _over(out) == ["unanswered"], out["compared"]


def _command(cwd, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 3), "--seconds", "2", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_machine_without_the_chip():
    r = _command(REPO, {})
    assert r.returncode != 0
    assert "needs 1 x [tpu]" in r.stderr
    assert '"metrics"' not in r.stdout and '"correct"' not in r.stdout


def test_command_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns(".run", "__pycache__"))
    r = _command(tmp_path, {})
    assert r.returncode != 0 and '"correct"' not in r.stdout
