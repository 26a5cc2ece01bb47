"""The rate sweep of an open-loop cell (a builder's tool; the driver never
runs it): one set-up, then a short window at each rate, lowest first.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 5,10,20

Prints one line per rate: requests, failures, p50 and p95 from the due time,
the generator's lag, and the backlog (how late the last answers came). The
knee is the highest rate at which the latencies do not grow through the
window; the cell's file then takes four fifths of it as `rate_per_s`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import traffic  # noqa: E402
from readers import common, generator_lag  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--documents", type=int)
    args = ap.parse_args(argv)
    over = {"documents": args.documents} if args.documents else {}
    cell = harness.Cell(args.workload, over)
    devices = harness.check_devices(args.platform, cell.chips)
    procs: list = []
    serving = harness.Serving(cell, args.seed, devices, procs)
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            cell.workload["rate_per_s"] = rate
            requests = traffic.build(cell.workload, cell.cfg, args.seed,
                                     args.seconds)
            warm = traffic.build({**cell.workload, "shape_seed":
                                  cell.workload["shape_seed"] + 1},
                                 cell.cfg, args.seed, args.seconds)
            serving.window(warm, set(), args.seconds, False)  # other bodies
            w = serving.window(requests, set(), args.seconds, False)
            recs = w["records"]
            ctx = {"records": recs, "never_answered": []}
            lat = common.latencies_ms(ctx)
            half = len(lat) // 2
            print(json.dumps({
                "rate_per_s": rate, "requests": len(recs),
                "failed": sum(not common.ok(r) for r in recs),
                "shed": sum(r["status"] == 429 for r in recs),
                "p50_ms": common.quantile(lat, 0.5),
                "p95_ms": common.quantile(lat, 0.95),
                "p50_first_half_ms": common.quantile(lat[:half], 0.5),
                "p50_second_half_ms": common.quantile(lat[half:], 0.5),
                "lag_p95_ms": generator_lag.read(ctx, {"q": 0.95}),
                "last_done_s": max(r["done"] for r in recs),
                "compiles": common.delta(
                    {"before": w["before"], "after": w["after"]},
                    "es_jit_compiles_total")}), flush=True)
            if max(r["done"] for r in recs) > args.seconds + 8:
                break       # far past the knee: the backlog outlasts the window
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
