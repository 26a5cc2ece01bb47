"""The load generator: a process of its own that never imports JAX.

    python loadgen.py <plan.json>

The plan names the port, the loop, the window's length, the file of requests
(one JSON object a line: `path`, `payload`, `keep`, and `due` in an open
loop) and where to put results. It connects, prints `ready`, waits for a line
on standard input, and sends:

  open loop    `connections` threads share one schedule. A thread takes the
               next request, sleeps until it is due, sends it, and records
               when it was due, sent and answered. Latency is counted from
               the due time, so a stall charges the requests behind it; how
               late a request left is the generator's lag.
  closed loop  `clients` threads, each sending its next request when the
               last is answered, until the window closes. Requests under
               way at the close are finished and counted, and the window
               ends when the last of them is answered.

It waits for every answer (up to `grace_s` past the close), writes one record
per request to `records.jsonl` and the raw bytes of the kept answers to
`kept/<i>.json`, and exits. Standard library only.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import sys
import threading
import time

TOOK = re.compile(rb'"took": ?(\d+)')


class Generator:
    def __init__(self, plan: dict):
        self.plan = plan
        with open(plan["requests"]) as f:
            self.requests = [json.loads(line) for line in f]
        for r in self.requests:
            r["payload"] = r["payload"].encode()
        self.records: list[dict] = []
        self.lock = threading.Lock()
        self.next = 0
        self.t0 = 0.0
        os.makedirs(os.path.join(plan["out"], "kept"), exist_ok=True)

    def _take(self):
        with self.lock:
            i = self.next
            if i >= len(self.requests):
                return None
            self.next += 1
            return i

    def _send(self, conn, i: int, due: float | None) -> None:
        req = self.requests[i]
        rec = {"i": i, "due": due, "status": None, "took_ms": None,
               "item_errors": 0}
        rec["sent"] = time.perf_counter() - self.t0
        try:
            conn.request("POST", req["path"], body=req["payload"])
            r = conn.getresponse()
            data = r.read()
            rec["status"] = r.status
        except (OSError, http.client.HTTPException) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
            conn.close()
            data = b""
        rec["done"] = time.perf_counter() - self.t0
        if rec["status"] == 200:
            tooks = [int(m) for m in TOOK.findall(data)]
            rec["took_ms"] = max(tooks) if tooks else None
            rec["item_errors"] = data.count(b'"error"')
            if req.get("keep"):
                with open(os.path.join(self.plan["out"], "kept",
                                       f"{i}.json"), "wb") as f:
                    f.write(data)
        with self.lock:
            self.records.append(rec)

    def _connect(self):
        return http.client.HTTPConnection("127.0.0.1", self.plan["port"],
                                          timeout=self.plan["grace_s"])

    def _open_worker(self) -> None:
        conn = self._connect()
        while (i := self._take()) is not None:
            due = self.requests[i]["due"]
            wait = self.t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._send(conn, i, due)
        conn.close()

    def _closed_worker(self) -> None:
        conn = self._connect()
        close = self.t0 + self.plan["seconds"]
        while time.perf_counter() < close and (i := self._take()) is not None:
            self._send(conn, i, None)
        conn.close()

    def run(self) -> None:
        open_loop = self.plan["loop"] == "open"
        n = self.plan["connections"] if open_loop else self.plan["clients"]
        target = self._open_worker if open_loop else self._closed_worker
        threads = [threading.Thread(target=target, daemon=True)
                   for _ in range(n)]
        print("ready", flush=True)
        sys.stdin.readline()
        self.t0 = time.perf_counter()
        for t in threads:
            t.start()
        deadline = self.t0 + self.plan["seconds"] + self.plan["grace_s"]
        for t in threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        with self.lock:
            records = sorted(self.records, key=lambda r: r["i"])
        sent = {r["i"] for r in records}
        with open(os.path.join(self.plan["out"], "records.jsonl"), "w") as f:
            f.write(json.dumps({"t0_epoch": time.time()
                                - (time.perf_counter() - self.t0),
                                "never_answered": sorted(
                                    i for i in range(self.next)
                                    if i not in sent)}) + "\n")
            for r in records:
                f.write(json.dumps(r) + "\n")


def main(argv) -> int:
    with open(argv[0]) as f:
        plan = json.load(f)
    Generator(plan).run()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
