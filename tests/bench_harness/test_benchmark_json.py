"""`BENCHMARK.json` keeps to the contract's form, and every file it names,
and every file the harness finds by a name in it, is there. The entries are
checked as they would stand with `fixtures/dashboard-entries.json` added
(`B_PLUS`): the cell that waits under `benchmark/` for a later PR."""

import importlib
import json
import os
import re

import pytest

from conftest import B, B_PLUS, BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

METRICS = B_PLUS["end_to_end"] + B_PLUS["per_layer"]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(B["paths"]) <= 16
    for p in B["paths"]:
        assert os.path.isdir(os.path.join(REPO, p)) and ".." not in p


def test_command_stays_inside_paths():
    assert len(B["command"]) <= 32
    for word in B["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in B["paths"])
        assert os.path.isfile(os.path.join(REPO, word))


@pytest.mark.parametrize("entry", B_PLUS["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert any(entry["file"].startswith(p + "/") for p in B["paths"])
    with open(os.path.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cfg
        assert cfg["published"][key] != cfg[key]
    assert any(w["config"] == entry["name"] for w in B_PLUS["workloads"])
    # the guarantees stand in the file
    assert cfg["index_settings"]["index.translog.durability"] == "request"
    assert "totals" in cfg["guarantees"]


@pytest.mark.parametrize("cell", B_PLUS["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in B_PLUS["configs"]}
    with open(os.path.join(BENCH, "workloads", cell["name"] + ".json")) as f:
        w = json.load(f)
    assert w["loop"] in ("open", "closed") and w["mix"] and w["warmup"]
    reported = [m for m in METRICS
                if cell["name"] in m.get("workloads", [cell["name"]])]
    kinds = {m["name"] for m in reported}
    assert "setup_s" in kinds
    assert len([m for m in reported if m in B_PLUS["end_to_end"]]) >= 2
    assert any(m in B_PLUS["per_layer"] for m in reported)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    per_layer = m in B_PLUS["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert want <= set(m) <= want | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in B_PLUS["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    if per_layer:
        moved, = [e for e in B_PLUS["end_to_end"] if e["name"] == m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("readers." + spec["reader"])
    assert callable(reader.read)


@pytest.mark.parametrize("bench", [B, B_PLUS], ids=["committed", "plus"])
def test_names_are_unique_and_every_configuration_is_used(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    for group in (bench["configs"], bench["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in bench["configs"]} == \
        {w["config"] for w in bench["workloads"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert all(set(m.get("workloads", [])) <= cells and
               m.get("workloads", True) for m in metrics)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_the_source_of_each_configuration_is_kept():
    """What is no cut of scale stays as the source has it: Elasticsearch
    2.0's shard request cache is off, and rally-tracks http_logs says so
    in its index settings; the index has the file's shard count, which is
    its source's (`published.number_of_shards` where the file states one,
    Elasticsearch 2.0's default of 5 where it states none) unless the entry
    lists `number_of_shards` under `reduced`, as one chip's share of a
    deployment spread over more."""
    for entry in B_PLUS["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            cfg = json.load(f)
        settings = cfg["index_settings"]
        assert settings.get("index.requests.cache.enable", False) is False
        assert _shard_count_kept(entry, cfg), entry["name"]


def _shard_count_kept(entry: dict, cfg: dict) -> bool:
    source = cfg.get("published", {}).get("number_of_shards", 5)
    return cfg["index_settings"]["number_of_shards"] == \
        cfg["number_of_shards"] and (cfg["number_of_shards"] == source or
                                     "number_of_shards" in entry["reduced"])


@pytest.mark.parametrize("published, shards, reduced, kept", [
    ({}, 5, [], True), ({}, 3, [], False),
    ({"number_of_shards": 2}, 2, [], True),
    ({"number_of_shards": 2}, 5, [], False),
    ({"number_of_shards": 20}, 5, ["number_of_shards"], True)],
    ids=["default", "off-the-default", "published", "off-the-published",
         "a-listed-cut"])
def test_a_shard_count_off_its_source_stands_only_as_a_listed_cut(
        published, shards, reduced, kept):
    cfg = {"number_of_shards": shards, "published": published,
           "index_settings": {"number_of_shards": shards}}
    assert _shard_count_kept({"reduced": reduced}, cfg) is kept
    cfg["index_settings"]["number_of_shards"] = shards + 1
    assert not _shard_count_kept({"reduced": reduced}, cfg)


def test_peaks_table_names_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    assert v5e["bf16_flops_per_s"] == 197e12
