"""What one stall of the serving process does to an open-loop cell (a
builder's tool; the driver never runs it): one set-up, then one window for
each `rate:seconds:stall_at:stall_s` of `--windows`, in which the serving
process is stopped (SIGSTOP, then SIGCONT) for `stall_s` seconds `stall_at`
seconds into the window. The load generator, a process of its own, is not
stopped: its requests fall due all the same, as on a host that stands still.

    python3 benchmark/stall.py --workload <cell> --seed <n> \
        --windows 128:15:0:0,128:40:10:3

Prints one line per window: requests, refusals (HTTP 429) and when the first
and the last fell due, p50 and p95 from the due time, and the program's own
admission state (`node.qos.stats()`, the batcher's follower time-outs) before
and after. After a window it sends one probe a second until admission lets
three in a row through, and prints how long that took.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import traffic  # noqa: E402
from readers import common  # noqa: E402


def admission(serving) -> dict:
    q = serving.node.qos.stats()
    return {"pressure": q["pressure"], "latency_frac": q["latency_frac"],
            "queue_frac": q["queue_frac"], "breaker_frac": q["breaker_frac"],
            "ewma_ms": q["ewma_latency_ms"],
            "deadline_ms": q["ewma_deadline_ms"],
            "degraded_total": q["degraded_total"],
            "follower_timeouts":
                serving.node._batcher.stats()["wait_timeouts_total"]}


def recover(serving, probe: dict, limit_s: float = 90.0) -> float | None:
    """Seconds until three probes in a row are let in."""
    t0, streak = time.perf_counter(), 0
    while time.perf_counter() - t0 < limit_s:
        status, _ = harness.Client(serving.server.port).send(
            "POST", probe["path"], probe["payload"])
        streak = streak + 1 if status == 200 else 0
        if streak == 3:
            return time.perf_counter() - t0
        time.sleep(1.0)
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", required=True)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--documents", type=int)
    ap.add_argument("--bench", help="a file to read in BENCHMARK.json's "
                    "place, for a cell that waits under benchmark/")
    args = ap.parse_args(argv)
    over = {"documents": args.documents} if args.documents else {}
    cell = harness.Cell(args.workload, over, args.bench)
    devices = harness.check_devices(args.platform, cell.chips)
    procs: list = []
    serving = harness.Serving(cell, args.seed, devices, procs)
    try:
        for k, spec in enumerate(args.windows.split(",")):
            rate, seconds, at, stall_s = (float(x) for x in spec.split(":"))
            cell.workload["rate_per_s"] = rate
            requests = traffic.build({**cell.workload, "shape_seed":
                                      cell.workload["shape_seed"] + k},
                                     cell.cfg, args.seed, seconds)
            before = admission(serving)

            def on_open():
                if stall_s > 0:
                    pid = os.getpid()
                    procs.append(subprocess.Popen(
                        ["sh", "-c", f"sleep {at}; kill -STOP {pid}; "
                         f"sleep {stall_s}; kill -CONT {pid}"]))

            w = serving.window(requests, set(), seconds, False, on_open)
            recs = w["records"]
            lat = common.latencies_ms({"records": recs})
            shed = [r["due"] for r in recs if r["status"] == 429]
            print(json.dumps({
                "rate_per_s": rate, "seconds": seconds, "stall_at_s": at,
                "stall_s": stall_s, "requests": len(recs),
                "refused_429": len(shed),
                "failed": sum(not common.ok(r) for r in recs),
                "first_refused_due_s": min(shed, default=None),
                "last_refused_due_s": max(shed, default=None),
                "p50_ms": common.quantile(lat, 0.5),
                "p95_ms": common.quantile(lat, 0.95),
                "compiles": common.delta(w, "es_jit_compiles_total"),
                "before": before, "after": admission(serving),
                "recovered_after_s": recover(serving, requests[0])}),
                flush=True)
    finally:
        serving.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
