"""One run of one cell: set-up, the measured window, the check, the result.

Nothing here knows a cell, a configuration or a metric by name. `run` finds
the cell in `BENCHMARK.json`, its parameters in `benchmark/workloads/` (or in
the file its entry names under an optional `file`, as a test's cell does),
its configuration's file by the path `BENCHMARK.json` gives, and each
metric's reader through `benchmark/metrics/<metric>.json`.

The process that calls `run` holds the chip and serves: `NodeService` +
`HttpServer` on threads. The documents are sent by ingest workers and the
measured requests by the load generator, processes of their own that never
import JAX (`ingest_worker.py`, `loadgen.py`).
"""

from __future__ import annotations

import http.client
import importlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import compare  # noqa: E402
import corpus  # noqa: E402
import traffic  # noqa: E402
from reference import Reference  # noqa: E402


class NoDevice(Exception):
    """JAX does not see the chips the cell asks for."""


class Unsettled(Exception):
    """The warm-up's last allowed round still compiled or was refused: the
    program cannot serve the cell settled, and no window is opened."""


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# the files of one cell
# ---------------------------------------------------------------------------

class Cell:
    """Everything `BENCHMARK.json` and the files it names say of one cell."""

    def __init__(self, name: str, overrides: dict | None = None,
                 bench_file: str | None = None):
        self.bench = load_json(bench_file
                               or os.path.join(ROOT, "BENCHMARK.json"))
        entry = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
        self.entry = entry[0]
        self.name = name
        self.chips = self.entry["chips"]
        conf, = [c for c in self.bench["configs"]
                 if c["name"] == self.entry["config"]]
        self.cfg = load_json(ROOT, conf["file"])
        self.workload = load_json(ROOT, self.entry["file"]) \
            if "file" in self.entry \
            else load_json(HERE, "workloads", f"{name}.json")
        self.harness = load_json(HERE, "harness.json")
        for key, value in (overrides or {}).items():
            if key == "chips":          # a test's virtual devices
                self.chips = value
                continue
            target = self.cfg if key in self.cfg else self.workload
            target[key] = value
        self.run_dir = os.path.join(HERE, ".run", name)

    def metrics(self, kind: str) -> list[dict]:
        """The cell's metrics of one kind (`end_to_end` or `per_layer`),
        each with its own file's reader and parameters."""
        out = []
        for m in self.bench[kind]:
            if self.name not in m.get("workloads", [self.name]):
                continue
            spec = load_json(HERE, "metrics", f"{m['name']}.json")
            out.append({**m, "reader": spec["reader"],
                        "params": spec.get("params", {})})
        return out


# ---------------------------------------------------------------------------
# HTTP to our own server
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, port: int, timeout: float = 900):
        self.port = port
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def send(self, method: str, path: str, body=None) -> tuple[int, bytes]:
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        if isinstance(body, str):
            body = body.encode()
        self.conn.request(method, path, body=body)
        r = self.conn.getresponse()
        return r.status, r.read()

    def call(self, method: str, path: str, body=None):
        status, data = self.send(method, path, body)
        if status != 200:
            raise RuntimeError(f"{method} {path}: HTTP {status}: "
                               f"{data[:1000]!r}")
        return json.loads(data)


def parse_metrics(text: str) -> dict:
    """OpenMetrics text -> {name: [(labels dict, value)]}."""
    out: dict[str, list] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for pair in rest.rstrip("}").split(","):
            if "=" in pair:
                k, _, v = pair.partition("=")
                labels[k] = v.strip('"')
        try:
            out.setdefault(name, []).append((labels, float(value)))
        except ValueError:
            continue
    return out


def counters(client: Client) -> dict:
    """The program's counters at one moment."""
    status, text = client.send("GET", "/_metrics")
    stats = client.call("GET", "/_nodes/device_stats")["nodes"]
    node, = stats.values()
    return {"metrics": parse_metrics(text.decode()),
            "lane_decisions": node["lane_decisions"], "hbm": node["hbm"]}


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def check_devices(platform: str, chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) != chips:
        raise NoDevice(
            f"JAX reports {len(devices)} x [{devices[0].platform}]; this "
            f"cell needs {chips} x [{platform}]")
    return devices


def ingest(cell: Cell, client: Client, seed: int, procs: list) -> float:
    """Create the index and load it through `_bulk` from the ingest workers;
    -> documents per second, `_refresh` included."""
    cfg = cell.cfg
    cfg_path = os.path.join(cell.run_dir, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    client.call("PUT", f"/{cfg['index']}", corpus.mapping(cfg))
    t = time.perf_counter()
    n = cfg["ingest"]["clients"]
    workers = [subprocess.Popen(
        [sys.executable, os.path.join(HERE, "ingest_worker.py"), cfg_path,
         str(seed), str(client.port), str(i), str(n)],
        stdout=subprocess.PIPE, text=True) for i in range(n)]
    procs.extend(workers)
    acked = 0
    for w in workers:
        out, _ = w.communicate()
        last = json.loads(out.strip().splitlines()[-1])
        if w.returncode != 0:
            raise RuntimeError(f"ingest worker failed: {last}")
        acked += last["acked"]
    client.call("POST", f"/{cfg['index']}/_refresh")
    rate = cfg["documents"] / (time.perf_counter() - t)
    count = client.call("GET", f"/{cfg['index']}/_count")["count"]
    if not count == acked == cfg["documents"]:
        raise RuntimeError(f"_count {count}, acknowledged {acked}, "
                           f"sent {cfg['documents']}")
    import numpy as np
    for i in np.random.default_rng([seed, 3]).integers(
            0, cfg["documents"], 5).tolist():
        got = client.call("GET", f"/{cfg['index']}/_doc/{i}")
        if not got["found"] or got["_source"] != corpus.source(cfg, seed, i):
            raise RuntimeError(f"GET _doc/{i} does not return its source")
    return rate


def compiles(client: Client) -> float:
    status, text = client.send("GET", "/_metrics")
    return _compiles_in({"metrics": parse_metrics(text.decode())})


def _compiles_in(snapshot: dict) -> float:
    return sum(v for _, v in snapshot["metrics"].get(
        "es_jit_compiles_total", []))


def send_pilots(cell: Cell, client: Client) -> None:
    """Each of the cell's `warmup.pilots`, `warmup.copies` times at once
    (so it runs alone and in a batch), again until a round compiles
    nothing. A pilot that is refused (HTTP 429) is sent until it is let
    in."""
    spec = cell.workload["warmup"]
    pilots = traffic.pilot_requests(cell.workload, cell.cfg)
    for round_no in range(spec["rounds"] if pilots else 0):
        before = compiles(client)
        refusals: list = []
        for request in pilots:
            threads = [threading.Thread(target=_fire,
                                        args=(client.port, request, refusals))
                       for _ in range(spec.get("copies", 1))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        left = int(compiles(client) - before)
        log(f"pilots round {round_no}: {left} compiles, "
            f"{len(refusals)} refusals waited out")
        if left == 0:
            break


def _fire(port: int, request: dict, refusals: list,
          patience_s: float = 90.0, pause_s: float = 1.0) -> None:
    """One warm-up request. A 429 warms nothing up: it is noted in
    `refusals` and the request is sent again, a second later, until it is
    let in."""
    end = time.perf_counter() + patience_s
    while True:
        status, data = Client(port).send("POST", request["path"],
                                         request["payload"])
        if status != 429 or time.perf_counter() > end:
            break
        refusals.append(request["path"])
        time.sleep(pause_s)
    if status != 200:
        raise RuntimeError(f"warm-up {request['path']}: HTTP {status}: "
                           f"{data[:500]!r}")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Serving:
    """The system under test, up and loaded: node, HTTP server, index,
    warm-up done. `window` can be called more than once (the rate sweep)."""

    def __init__(self, cell: Cell, seed: int, devices, procs: list):
        import elasticsearch_tpu  # noqa: F401 — x64 and the compile cache
        from elasticsearch_tpu.node import NodeService
        from elasticsearch_tpu.rest import HttpServer
        self.cell, self.seed, self.procs = cell, seed, procs
        self.devices = devices
        shutil.rmtree(cell.run_dir, ignore_errors=True)
        os.makedirs(cell.run_dir)
        self.node = NodeService(os.path.join(cell.run_dir, "data"))
        self.server = HttpServer(self.node, port=0).start()
        self.client = Client(self.server.port)
        try:
            self.rate = ingest(cell, self.client, seed, procs)
            log(f"ingested {cell.cfg['documents']} documents at "
                f"{self.rate:.0f}/s")
            self.warm_up()
        except BaseException:
            self.close()
            raise

    def warm_up(self) -> None:
        """The pilots, then the replay; `Unsettled` where its last allowed
        round still compiled or was refused."""
        send_pilots(self.cell, self.client)
        self.compiles_left, self.refused_left = self.replay()
        if self.compiles_left or self.refused_left:
            raise Unsettled(
                f"the warm-up did not settle: the last of "
                f"{self.cell.workload['warmup']['rounds']} replay rounds "
                f"compiled {self.compiles_left} programs and was refused "
                f"{self.refused_left} requests")

    def replay(self) -> tuple[int, int]:
        """The warm-up: the cell's own kind of traffic through its own loop
        (at its own rate, or from its own clients), `warmup.replay_s`
        seconds a round, each round from a shape seed of its own, until a
        round compiles nothing and is refused nothing (`warmup.rounds` at
        most). A refusal (HTTP 429) in a round says that the program's
        admission still carries the warm-up's own compiles and time-outs,
        and a window opened on that state is refused too;
        -> compiles and refusals of the last round."""
        spec = self.cell.workload["warmup"]
        left = refused = 0
        for round_no in range(spec["rounds"]):
            workload = {**self.cell.workload, "shape_seed":
                        self.cell.workload["shape_seed"] + 7 + round_no}
            requests = traffic.build(workload, self.cell.cfg, self.seed,
                                     spec["replay_s"])
            w = self.window(requests, set(), spec["replay_s"], False)
            left = int(_compiles_in(w["after"]) - _compiles_in(w["before"]))
            refused = sum(r["status"] == 429 for r in w["records"])
            log(f"replay round {round_no}: {left} compiles, "
                f"{refused} of {len(w['records'])} refused")
            if left == 0 and refused == 0:
                break
        return left, refused

    def window(self, requests: list[dict], keep: set, seconds: float,
               trace: bool, on_open=lambda: None) -> dict:
        """Start the load generator, open the window, wait for its end."""
        cell = self.cell
        with open(os.path.join(cell.run_dir, "requests.jsonl"), "w") as f:
            for i, r in enumerate(requests):
                f.write(json.dumps({"path": r["path"],
                                    "payload": r["payload"],
                                    "due": r.get("due"), "keep": i in keep})
                        + "\n")
        plan = {"port": self.server.port, "loop": cell.workload["loop"],
                "seconds": seconds,
                "clients": cell.workload.get("clients", 1),
                "connections": cell.workload.get("connections", 1),
                "grace_s": cell.harness["grace_s"],
                "requests": os.path.join(cell.run_dir, "requests.jsonl"),
                "out": cell.run_dir}
        with open(os.path.join(cell.run_dir, "plan.json"), "w") as f:
            json.dump(plan, f)
        gen = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             os.path.join(cell.run_dir, "plan.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.procs.append(gen)
        if gen.stdout.readline().strip() != "ready":
            raise RuntimeError("the load generator did not come up")
        out = {"before": counters(self.client)}
        on_open()
        gen.stdin.write("go\n")
        gen.stdin.flush()
        t0 = time.perf_counter()
        out["trace_dir"] = out["trace_span"] = None
        if trace:
            out["trace_dir"], out["trace_span"] = _trace_slice(
                cell, t0, seconds)
        gen.wait(timeout=seconds + cell.harness["grace_s"] + 60)
        out["after"] = counters(self.client)
        out["peak"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)
        out["header"], out["records"] = _records(cell)
        return out

    def close(self) -> None:
        self.server.stop()
        self.node.close()
        self.node = self.server = None
        shutil.rmtree(os.path.join(self.cell.run_dir, "data"),
                      ignore_errors=True)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        platform: str = "tpu", overrides: dict | None = None,
        control: bool = False, t_start: float | None = None,
        procs: list | None = None, bench_file: str | None = None,
        on_settled=lambda: None) -> dict:
    """-> the result line's object. `procs` collects the child processes so
    that the caller can end them whatever happens. `bench_file` stands in
    for `BENCHMARK.json` (a test's, with entries a later PR would add).
    `on_settled` is called once the warm-up has settled."""
    t_start = time.perf_counter() if t_start is None else t_start
    procs = [] if procs is None else procs
    cell = Cell(name, overrides, bench_file)
    devices = check_devices(platform, cell.chips)
    requests = traffic.build(cell.workload, cell.cfg, seed, seconds)
    keep = set(traffic.sample(cell.workload, len(requests), seed))
    log(f"{len(requests)} requests built, {len(keep)} kept for the check")
    opened = {}

    def on_open():
        opened["setup_s"] = time.perf_counter() - t_start
        log(f"window open after {opened['setup_s']:.1f} s of set-up")

    serving = Serving(cell, seed, devices, procs)
    on_settled()
    try:
        w = serving.window(requests, keep, seconds, trace, on_open)
    finally:
        serving.close()
    header, records, trace_dir = w["header"], w["records"], w["trace_dir"]
    ctx = {"cell": cell, "requests": requests, "records": records,
           "setup_s": opened["setup_s"], "before": w["before"],
           "after": w["after"], "device": devices[0],
           "never_answered": header["never_answered"],
           "window_s": max([seconds] + [r["done"] for r in records])}
    if trace_dir:
        import xtrace
        ctx["trace"] = xtrace.reduce_dir(trace_dir)
        ctx["trace_span"] = w["trace_span"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    log("building the reference")
    ref = Reference(cell.cfg, seed)
    ctx["reference"] = ref
    limits = compare.load_limits(cell.workload)
    tally = _check(cell, requests, records, keep, ref, limits, header)
    ok, compared = tally.verdict(limits)
    for note in tally.notes:
        log(f"  {note}")

    metrics = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        reader = importlib.import_module(f"readers.{m['reader']}")
        value = reader.read(ctx, m["params"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    failed = sum(1 for r in records
                 if r["status"] != 200 or r["item_errors"]) \
        + len(header["never_answered"])
    result = {"correct": bool(ok), "attempted": len(records)
              + len(header["never_answered"]), "failed": failed,
              "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": w["peak"]}}
    if trace_dir:
        result["device"]["busy_s"] = ctx["trace"]["busy_s"]
        result["device"]["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = {"device_ops": ctx["trace"]["top_ops"],
                               "idle_gaps": []}
    result["notes"] = {"ingest_docs_per_s": serving.rate,
                       "window_s": ctx["window_s"],
                       "answers_checked": tally.answers,
                       "hits_checked": tally.hits,
                       "warmup_compiles_left": serving.compiles_left,
                       "warmup_refused_left": serving.refused_left,
                       "compiles_in_window": _compiles_in(w["after"])
                       - _compiles_in(w["before"]),
                       "took_ms_sorted_tenths": _tenths(
                           [r["took_ms"] for r in records
                            if r["took_ms"] is not None])}
    if control:
        low = Reference(cell.cfg, seed, precision="low")
        ctally = compare.Tally()
        for i in sorted(keep & {r["i"] for r in records}):
            if i < len(requests):
                ref.prepare(requests[i]["bodies"])
                low.prepare(requests[i]["bodies"])
                for j, body in enumerate(requests[i]["bodies"]):
                    compare.compare_answer(ctally, f"control {i}[{j}]", body,
                                           low.respond(body), ref,
                                           limits["score_rel_err_max"])
        cok, ccompared = ctally.verdict(limits)
        result["control"] = {"correct": bool(cok), "compared": ccompared}
    result["compared"] = compared
    return result


def _trace_slice(cell: Cell, t0: float, seconds: float) -> str:
    """Profile a slice of the window with jax.profiler (this process holds
    the chip); -> the trace's directory and the slice as seconds into the
    window."""
    import jax
    spec = cell.harness["trace"]
    trace_dir = os.path.join(cell.run_dir, "trace")
    start = min(spec["offset_s"], seconds / 4)
    length = min(spec["slice_s"], seconds / 2)
    time.sleep(max(0.0, t0 + start - time.perf_counter()))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = spec["host_tracer_level"]
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    lo = time.perf_counter() - t0
    time.sleep(length)
    hi = time.perf_counter() - t0
    jax.profiler.stop_trace()
    log(f"traced the window from {lo:.1f} s to {hi:.1f} s")
    return trace_dir, (lo, hi)


def _tenths(values: list) -> list:
    """Every tenth of the sorted values, both ends included."""
    v = sorted(values)
    return [v[round(i * (len(v) - 1) / 10)] for i in range(11)] if v else []


def _records(cell: Cell) -> tuple[dict, list[dict]]:
    with open(os.path.join(cell.run_dir, "records.jsonl")) as f:
        lines = [json.loads(line) for line in f]
    return lines[0], lines[1:]


def _check(cell, requests, records, keep, ref, limits, header):
    """Every kept answer against the reference."""
    tally = compare.Tally()
    by_i = {r["i"]: r for r in records}
    for i in header["never_answered"]:
        tally.answers += 1
        tally.note("unanswered", f"request {i} never came back")
    for r in records:
        if r["status"] not in (200, 429):   # a 429 alone is a stated refusal
            tally.answers += 1
            tally.note("unanswered", f"request {r['i']}: status "
                       f"{r['status']} {r.get('error', '')}")
        elif r["status"] == 200 and r["item_errors"] and r["i"] not in keep:
            tally.answers += 1
            tally.note("unanswered", f"request {r['i']}: "
                       f"{r['item_errors']} item errors")
    for i in sorted(keep):
        r = by_i.get(i)
        if r is None or r["status"] != 200:
            continue
        with open(os.path.join(cell.run_dir, "kept", f"{i}.json"), "rb") as f:
            data = f.read()
        compare.compare_request(tally, f"request {i}", requests[i], data,
                                ref, limits["score_rel_err_max"])
    return tally
