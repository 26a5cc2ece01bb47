"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of `BENCHMARK.json` on the machine it is started on. The
last line of standard output is the result: one JSON object with `correct`,
`attempted`, `failed`, `metrics`, `device` and, last, `compared` (each number
the check compared, beside its limit). It exits non-zero and prints no result
unless JAX reports the TPU chips the cell asks for.

A watchdog armed before anything else ends the run with `correct: false` if
set-up, window and check together pass the limit in `benchmark/harness.json`:
`warm` where this cell's warm-up has settled on this compile cache before,
under the same program, benchmark, cell files and checkout, `cold` otherwise;
a warm-up whose last allowed replay round still compiled or was refused ends
it the same way, with a non-zero exit code, before any window opens;
and the process leaves through `os._exit` once its children have ended, so no
thread, pool or request under way can keep a run alive.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
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


def cache_dir() -> str:
    """The compile cache the package uses (`elasticsearch_tpu/__init__.py`)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".xla_cache")


def _sources(d: str) -> list:
    """The `.py` files under `d`, in a fixed order, hidden directories
    (a run's `.run/`) left out."""
    out = []
    for sub, dirs, files in os.walk(d):
        dirs[:] = sorted(x for x in dirs if not x.startswith("."))
        out += [os.path.join(sub, f) for f in sorted(files)
                if f.endswith(".py")]
    return out


def _cell_files(root: str, cell: str) -> list:
    """The cell's workload file and its configuration's file, as
    `BENCHMARK.json` names them; none where it names no such cell."""
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        w, = [w for w in bench["workloads"] if w["name"] == cell]
        c, = [c for c in bench["configs"] if c["name"] == w["config"]]
    except (OSError, ValueError):
        return []
    return [os.path.join(root, "benchmark", "workloads", cell + ".json"),
            os.path.join(root, c["file"])]


def program_key(cell: str, root: str = ROOT) -> str:
    """What this cell's programs are keyed by besides their shapes: the
    checkout's path (a Pallas kernel's key holds its call stack's source
    positions), the package's source, and what shapes the cell's warm-up:
    the benchmark's code and the cell's workload and configuration files."""
    h = hashlib.sha256(os.path.abspath(root).encode())
    for path in (_sources(os.path.join(root, "elasticsearch_tpu")) +
                 _sources(os.path.join(root, "benchmark")) +
                 _cell_files(root, cell)):
        h.update(os.path.relpath(path, root).encode())
        with open(path, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def settled_marker(cache: str, cell: str, key: str) -> str:
    """One mark a cell and key: checkouts that share a cache keep theirs."""
    return os.path.join(cache, ".settled", f"{cell}.{key}")


def mark_settled(cache: str, cell: str, key: str) -> None:
    path = settled_marker(cache, cell, key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    open(path, "w").close()


def watchdog_limit(limits: dict, cache: str, cell: str, key: str) -> float:
    """`warm` only where this cell's warm-up has settled on this cache under
    this program key: a cell whose programs are new compiles all of them,
    however many entries other cells have left in the cache."""
    warm = os.path.isfile(settled_marker(cache, cell, key))
    return limits["warm" if warm else "cold"]


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
    cache, key = cache_dir(), program_key(args.workload)
    limit = watchdog_limit(limits, cache, args.workload, key)
    print(f"watchdog: {limit:.0f} s", file=sys.stderr)
    watchdog(limit, procs)

    import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), control=bool(args.control),
                             t_start=T_START, procs=procs,
                             on_settled=lambda: mark_settled(
                                 cache, args.workload, key))
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
