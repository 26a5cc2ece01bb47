"""What every existing cell reads stays bit for bit what it was before the
harness learned dense vectors and `dis_max`: for each configuration under
`benchmark/configs/`, chunk 0 and its `_bulk` payload; for each cell's file
under `benchmark/workloads/`, the requests of a 40 s window, the least bytes
of its first 32 bodies and the reference's answer (mask and score, every
bit) to its first 8. The digests were computed before the widening and are
pinned one file each, under `pins/configs/<name>.json` and
`pins/workloads/<cell>.json`, so that a new configuration or cell brings its
pin as a new file. The reference runs at 12,000 documents: the code path is
the cell's, the size one a test run can hold.

    python3 tests/bench_harness/test_vocabulary_invariance.py <file>

prints the pin of a file under `benchmark/configs/` or
`benchmark/workloads/` (a cell's configuration as `BENCHMARK.json` names
it), as the text of its pin file."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

from conftest import B_PLUS, BENCH  # puts benchmark/ on the import path
import corpus  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
from reference import Reference  # noqa: E402

SEED = 2 ** 31 + 5
DOCS = 12_000
PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "pins")


def _pins(kind: str) -> dict:
    out = {}
    for f in sorted(os.listdir(os.path.join(PINS, kind))):
        with open(os.path.join(PINS, kind, f)) as fh:
            out[f[:-5]] = json.load(fh)
    return out


CONFIG_PINS = _pins("configs")
CELL_PINS = _pins("workloads")


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _array_digest(h, a) -> None:
    a = np.ascontiguousarray(a)
    h.update(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())


def chunk_digest(cfg: dict) -> str:
    h = hashlib.sha256()
    for name, col in corpus.chunk(cfg, SEED, 0).items():
        h.update(name.encode())
        for a in (col if isinstance(col, tuple) else (col,)):
            _array_digest(h, a)
    h.update(corpus.payload(cfg, SEED, 0))
    return h.hexdigest()


def traffic_digest(workload: dict, cfg: dict) -> str:
    h = hashlib.sha256()
    ref = Reference({**cfg, "documents": DOCS}, SEED)
    requests = traffic.build(workload, cfg, SEED, 40.0)
    for r in requests:
        h.update(json.dumps(r, sort_keys=True).encode())
    bodies = [b for r in requests for b in r["bodies"]]
    for b in bodies[:32]:
        h.update(repr(work.body_bytes(ref, b)).encode())
    for b in bodies[:8]:
        ans = ref.answer(b)
        _array_digest(h, ans["mask"])
        _array_digest(h, ans["score"])
        h.update(repr((ans["total"], ans.get("aggs"))).encode())
    return h.hexdigest()


def test_every_configuration_and_cell_file_is_pinned():
    """Both ways: every file has its pin, and every pin has its file; a
    cell is pinned with the configuration `BENCHMARK.json` gives it."""
    for kind, pins in (("configs", CONFIG_PINS), ("workloads", CELL_PINS)):
        assert set(pins) == {
            f[:-5] for f in os.listdir(os.path.join(BENCH, kind))}, kind
    for w in B_PLUS["workloads"]:
        if "file" not in w:             # a fixture's traffic lies elsewhere
            assert CELL_PINS[w["name"]]["config"] == w["config"], w["name"]


@pytest.mark.parametrize("name", sorted(CONFIG_PINS))
def test_corpus_chunk_and_payload_are_the_parents(name):
    assert chunk_digest(_load("configs", f"{name}.json")) == \
        CONFIG_PINS[name]["digest"]


@pytest.mark.parametrize("cell", sorted(CELL_PINS))
def test_requests_bytes_and_answers_are_the_parents(cell):
    pin = CELL_PINS[cell]
    cfg = _load("configs", f"{pin['config']}.json")
    assert traffic_digest(_load("workloads", f"{cell}.json"), cfg) == \
        pin["digest"]


def pin_of(path: str) -> dict:
    """The pin of a configuration's or a cell's file."""
    kind = os.path.basename(os.path.dirname(os.path.abspath(path)))
    name = os.path.basename(path)[:-5]
    with open(path) as f:
        spec = json.load(f)
    if kind == "configs":
        return {"digest": chunk_digest(spec)}
    if kind != "workloads":
        raise SystemExit(f"{path} is no configuration or cell file")
    config, = [w["config"] for w in B_PLUS["workloads"] if w["name"] == name]
    return {"config": config,
            "digest": traffic_digest(spec, _load("configs", f"{config}.json"))}


if __name__ == "__main__":
    print(json.dumps(pin_of(sys.argv[1]), indent=1))
