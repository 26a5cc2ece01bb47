"""The reduction from a profiler trace to device numbers: hand-made planes,
and a small trace recorded on the chip (`fixtures/packed.xplane.pb`: the
device plane of one traced slice of `wiki.match-top10` on a TPU v5e, cut to
its `XLA Modules` line and the first 400 events of `XLA Ops`)."""

import os

import pytest

import xtrace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "packed.xplane.pb")


def test_union_counts_overlap_once():
    assert xtrace.union_ns([]) == 0
    assert xtrace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert xtrace.union_ns([(20, 30), (0, 100), (40, 50)]) == 100
    assert xtrace.union_ns([(0, 10), (10, 20)]) == 20


def test_module_name_drops_the_fingerprint():
    assert xtrace.module_name("jit_bm25_serve_packed(14551719748616556523)") \
        == "jit_bm25_serve_packed"
    assert xtrace.module_name("jit_f") == "jit_f"


def test_reduce_hand_made_planes():
    planes = [
        ("/host:CPU", [("python", [("request", 0.0, 1000.0)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_a(1)", 100.0, 300.0),
                             ("jit_a(1)", 500.0, 100.0),
                             ("jit_b(2)", 700.0, 50.0)]),
            ("XLA Ops", [("%fusion", 100.0, 200.0), ("%sort", 250.0, 150.0),
                         ("%fusion", 500.0, 100.0), ("%copy", 700.0, 50.0)]),
            ("Steps", [("0", 0.0, 900.0)])]),
        ("/device:TPU:1", [
            ("XLA Ops", [("%fusion", 0.0, 100.0)])]),
    ]
    out = xtrace.reduce_planes(planes)
    # chip 0: [100,400) + [500,600) + [700,750) = 450 ns; chip 1: 100 ns
    assert out["busy_s"] == pytest.approx(275e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["device_planes"] == 2
    assert out["modules"]["jit_a"] == [2, pytest.approx(400e-9)]
    assert out["modules"]["jit_b"] == [1, pytest.approx(50e-9)]
    assert out["top_ops"][0] == ["%fusion", pytest.approx(400e-9)]
    assert [n for n, _ in out["top_ops"]] == ["%fusion", "%sort", "%copy"]


def test_no_device_plane_reads_nothing():
    out = xtrace.reduce_planes([("/host:CPU", [("t", [("x", 0.0, 5.0)])])])
    assert out["device_planes"] == 0 and out["busy_s"] == 0.0
    from readers import idle_share, roofline
    assert idle_share.read({"trace": out}, {}) is None
    assert roofline.read({"trace": out}, {"programs": ["."]}) is None


def test_recorded_trace_from_the_chip():
    out = xtrace.reduce_planes(xtrace.read(FIXTURE))
    assert out["device_planes"] == 1
    packed = [v for k, v in out["modules"].items()
              if "bm25_serve_packed" in k]
    assert packed and packed[0][0] >= 1 and packed[0][1] > 0
    assert 0 < out["busy_s"] <= out["window_s"]
    assert len(out["top_ops"]) >= 1 and all(s > 0 for _, s in out["top_ops"])
    # a module's time is at least the union of the operations inside it
    first = min((e for line in [l for _, ls in xtrace.read(FIXTURE)
                                for l in ls if l[0] == "XLA Modules"]
                 for e in line[1]), key=lambda e: e[1])
    assert first[2] > 0
