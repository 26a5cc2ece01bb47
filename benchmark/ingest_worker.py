"""An ingest client: a process of its own that never imports JAX.

    python ingest_worker.py <config.json> <seed> <port> <worker> <workers>

It regenerates the chunks `worker, worker + workers, ...` of the
configuration's corpus from the seed and sends each as one `_bulk` request.
The last line it prints is a JSON object with what the server acknowledged.
"""

from __future__ import annotations

import http.client
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import corpus  # noqa: E402


def main(argv) -> int:
    cfg_path, seed, port, worker, workers = argv
    cfg = corpus.load_config(cfg_path)
    conn = http.client.HTTPConnection("127.0.0.1", int(port), timeout=600)
    acked = 0
    for k in range(int(worker), corpus.n_chunks(cfg), int(workers)):
        conn.request("POST", f"/{cfg['index']}/_bulk",
                     body=corpus.payload(cfg, int(seed), k))
        r = conn.getresponse()
        out = json.loads(r.read())
        if r.status != 200 or out["errors"]:
            print(json.dumps({"acked": acked, "error":
                              f"_bulk of chunk {k}: HTTP {r.status}"}))
            return 1
        acked += len(out["items"])
    print(json.dumps({"acked": acked}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
