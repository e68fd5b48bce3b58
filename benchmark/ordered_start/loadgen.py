#!/usr/bin/env python3
"""``benchmark/loadgen.py`` with one change, for a closed loop whose traffic
file has ``start_gap_s``: the clients send their first requests in client
order, that many seconds apart, and not all at once.

Why.  ``loadgen.run_closed`` wakes every client at the same moment, so the
order in which their first requests reach the server is a race among as many
threads, and the server prefills in the order of arrival.  Where a request
is thousands of tokens of prefill for a hundred of output
(``code-ctx-sat``), which prompts go first decides how many rows decode in
the window: over random orders ``serve_out_tokens_per_s`` spreads by 2.6 %
(quartiles) and 9 % (range) and ``itl_p90_ms`` by 1.3 %, in a model of the
scheduler that reads six runs on the chip to 0.5 tokens/s from their order
of arrival alone, and in the driver's two sets of six (PERF.md section 6,
PR 31).  With the order fixed the same model, and the chip, repeat to 0.3 %.
The lengths, the token ids, the loop (a client's next request when its last
is complete), the cut of the first answers, the window and the records are
``loadgen``'s, whose functions do everything here but the start.

Found by the driver that wants it (``drivers/serve_swa_moe.py``) under the
name ``loadgen.py`` in this directory; without ``start_gap_s``, and for an
open loop, it is ``loadgen.run``.  Like ``loadgen.py`` it imports neither
JAX nor the program.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time
from typing import List, Mapping

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmark_loadgen", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "loadgen.py"))
loadgen = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = loadgen  # its dataclass looks its module up
_spec.loader.exec_module(loadgen)


def run_closed(spec: Mapping) -> List[dict]:
    """``loadgen.run_closed``, client ``c``'s first request sent at
    ``t_open - ramp_s + c * start_gap_s``.  Each first request is drawn
    before its client sleeps, so that nothing but the sleep lies between the
    clients."""
    traffic, seed = spec["traffic"], spec["seed"]
    t_start = spec["t_open"] - traffic["ramp_s"]
    t_close = spec["t_close"]
    records: List[List[dict]] = [[] for _ in range(traffic["clients"])]

    def client(c: int) -> None:
        i = 0
        req = loadgen.draw_request(seed, c, 0, traffic, spec["vocab"])
        share = np.random.default_rng(
            [traffic.get("schedule_seed", seed), c, 0xF1257]).uniform()
        req["max_tokens"] = max(1, round(req["max_tokens"] * share))
        time.sleep(max(0.0, t_start + c * traffic["start_gap_s"]
                       - time.monotonic()))
        while time.monotonic() < t_close:
            if i:
                req = loadgen.draw_request(seed, c, i, traffic, spec["vocab"])
            rec = loadgen.Record(c, i, len(req["prompt"]), req["max_tokens"],
                                 due=time.monotonic())
            loadgen.stream_completion(spec["port"], req, rec, stop_at=t_close,
                                      timeout_s=spec["timeout_s"])
            records[c].append(rec.to_json(with_prompt=req["prompt"]))
            i += 1
            if rec.status not in ("ok", "cut"):
                time.sleep(0.05)  # a failing server is not hammered

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for per_client in records for r in per_client]


def run(spec: Mapping) -> dict:
    traffic = spec["traffic"]
    if traffic["loop"] != "closed" or "start_gap_s" not in traffic:
        return loadgen.run(spec)
    return {"records": run_closed(spec), "finished": time.monotonic()}


def main(argv: List[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    json.dump(run(spec), sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
