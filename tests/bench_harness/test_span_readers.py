"""The readers of the program's spans and of its device-gap ledger
(ISSUE 24), each on a hand-made `ctx`: the value, and nothing where the
program exports no such family (the parent commit) or the divisor is 0."""

import pytest

from conftest import B
from readers import bytes_per_request, gap_share, span_ms


def _snapshot(**families) -> dict:
    """{family: {label value: number}} -> what `harness.counters` gives."""
    label = {"es_span_total": "span", "es_span_seconds_total": "span",
             "es_device_gap_seconds_total": "during"}
    return {"metrics": {
        name: [({"node": "n", **({label[name]: k} if name in label else {})},
                v) for k, v in series.items()]
        for name, series in families.items()}}


BEFORE = _snapshot(
    es_span_total={"rest.request": 10, "rest.write": 10, "search.plan": 0},
    es_span_seconds_total={"rest.request": 1.0, "rest.write": 0.010,
                           "rest.read_body": 0.005},
    es_device_gap_seconds_total={"packed.respond": 1.0, "no_request": 2.0},
    es_transfer_bytes_from_device_total={"": 1000})
AFTER = _snapshot(
    es_span_total={"rest.request": 110, "rest.write": 110, "search.plan": 0},
    es_span_seconds_total={"rest.request": 6.0, "rest.write": 0.110,
                           "rest.read_body": 0.055},
    es_device_gap_seconds_total={"packed.respond": 4.0, "no_request": 3.0,
                                 "unattributed": 1.0},
    es_transfer_bytes_from_device_total={"": 9000})
RECORDS = [{"status": 200, "item_errors": 0}] * 4 \
    + [{"status": 429, "item_errors": 0}]
CTX = {"before": BEFORE, "after": AFTER, "window_s": 40.0,
       "records": RECORDS}
PARENT = {"before": _snapshot(es_transfer_bytes_from_device_total={"": 0}),
          "after": _snapshot(es_transfer_bytes_from_device_total={"": 0}),
          "window_s": 40.0, "records": RECORDS}


def test_span_ms_is_seconds_of_the_spans_per_occurrence():
    got = span_ms.read(CTX, {"spans": ["rest.write", "rest.read_body"],
                             "per": "rest.request"})
    assert got == pytest.approx(1000 * (0.100 + 0.050) / 100)
    assert span_ms.read(CTX, {"spans": ["rest.request"],
                              "per": "rest.request"}) == pytest.approx(50.0)


@pytest.mark.parametrize("ctx, per", [(CTX, "search.plan"),
                                      (PARENT, "rest.request")],
                         ids=["zero-divisor", "parent"])
def test_span_ms_reads_nothing_without_an_occurrence(ctx, per):
    assert span_ms.read(ctx, {"spans": ["rest.write"], "per": per}) is None


def test_gap_share_over_the_window_and_over_the_gap():
    # 3 + 1 + 1 = 5 s of gap in a 40 s window
    assert gap_share.read(CTX, {"over": "window"}) == pytest.approx(12.5)
    assert gap_share.read(CTX, {"over": "gap", "except": ["no_request"]}) \
        == pytest.approx(80.0)


def test_gap_share_reads_nothing_from_a_program_without_the_ledger():
    assert gap_share.read(PARENT, {"over": "window"}) is None
    still = {**CTX, "after": BEFORE}            # a ledger that did not move
    assert gap_share.read(still, {"over": "gap"}) is None
    assert gap_share.read(still, {"over": "window"}) == 0.0


def test_bytes_per_request_counts_the_answered_requests():
    params = {"metric": "es_transfer_bytes_from_device_total"}
    assert bytes_per_request.read(CTX, params) == pytest.approx(8000 / 4)
    assert bytes_per_request.read(PARENT, params) is None   # nothing moved
    assert bytes_per_request.read({**CTX, "records": RECORDS[4:]},
                                  params) is None           # none answered


@pytest.mark.parametrize("name", [
    "rest_self_ms.lat", "rest_self_ms.qps", "admission_wait_ms.lat",
    "plan_ms.lat", "batcher_wait_ms.lat", "packed_prep_ms.lat",
    "packed_prep_ms.qps", "packed_respond_ms.lat", "packed_respond_ms.qps",
    "program_wall_ms.qps", "device_gap_share.lat", "device_gap_share.qps",
    "gap_host_share.lat", "d2h_bytes_per_request.qps"])
def test_the_entry_keeps_to_an_accepted_layer_and_its_cells_metric(name):
    """Each entry this PR adds names a layer `BENCHMARK.json` already had,
    and moves an end-to-end metric that its cell reports."""
    entry, = [m for m in B["per_layer"] if m["name"] == name]
    first = B["per_layer"].index(entry)
    assert entry["layer"] in {m["layer"] for m in B["per_layer"][:13]}
    assert first >= 13, "new entries go to the end of the list"
    moved, = [e for e in B["end_to_end"] if e["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert entry["name"].endswith(
        ".lat" if entry["moves"] == "latency_p50_ms" else ".qps")
