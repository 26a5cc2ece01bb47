"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of `BENCHMARK.json` on the machine it is started on. The
last line of standard output is the result: one JSON object with `correct`,
`attempted`, `failed`, `metrics`, `device` and, last, `compared` (each number
the check compared, beside its limit). It exits non-zero and prints no result
unless JAX reports the TPU chips the cell asks for.

A watchdog armed before anything else ends the run with `correct: false` if
set-up, window and check together pass the limit in `benchmark/harness.json`;
a warm-up whose last allowed replay round still compiled or was refused ends
it the same way, with a non-zero exit code, before any window opens;
and the process leaves through `os._exit` once its children have ended, so no
thread, pool or request under way can keep a run alive.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def end_children(procs: list) -> None:
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        p.wait()


def leave(rc: int, procs: list) -> None:
    end_children(procs)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)


def failure_line(why: str) -> str:
    return json.dumps({
        "correct": False, "attempted": 0, "failed": 0, "metrics": {},
        "device": {}, "compared": {"run_ended": {"value": why,
                                                  "limit": None}}})


def watchdog(limit_s: float, procs: list) -> None:
    def fire():
        print(f"watchdog: {limit_s:.0f} s passed, the run is stopped",
              file=sys.stderr)
        print(failure_line(f"watchdog after {limit_s:.0f} s"))
        leave(1, procs)
    t = threading.Timer(limit_s, fire)
    t.daemon = True
    t.start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also put the low-precision reference in the "
                         "program's place and print what the check says "
                         "of it (the builder's runs, not the driver's)")
    args = ap.parse_args(argv)
    procs: list = []
    with open(os.path.join(HERE, "harness.json")) as f:
        limits = json.load(f)["watchdog_s"]
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".xla_cache")
    cold = not (os.path.isdir(cache) and os.listdir(cache))
    watchdog(limits["cold" if cold else "warm"], procs)

    import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), control=bool(args.control),
                             t_start=T_START, procs=procs)
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        leave(3, procs)
    except harness.Unsettled as e:
        print(f"no window: {e}", file=sys.stderr)
        print(failure_line(str(e)))
        leave(1, procs)
    except BaseException:
        traceback.print_exc()
        leave(1, procs)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    leave(0, procs)
    return 0


if __name__ == "__main__":
    main()
