#!/usr/bin/env python3
"""The benchmark's load generator: open and closed loops over the HTTP front.

One general generator that reads a traffic file (``benchmark/traffic/*.json``)
and a seed; a new traffic mix is a new data file.  It imports neither JAX nor
the program, so it can run as a child of the process that holds the chip
(``python benchmark/loadgen.py <spec.json>`` prints one JSON result), and it
keeps its own GIL: the server's threads share theirs with the engine loop
only.

Clocks.  Every time is ``time.monotonic()``, which on Linux is one clock for
every process of the machine, so the parent can lay these records beside the
program's own spans.  A request is timed from the moment it was *due*, never
from when it was sent: in an open loop a stalled generator would otherwise
hide the wait it imposes.  How late each request was sent (``sent - due``) is
reported, so that a starved generator is not read as a fast server.

The arithmetic of arrivals and of template-free prompts follows
``deepspeed_tpu/observability/replay.py:synthesize_workload`` (seeded Gamma
inter-arrivals of a given shape, ids drawn uniformly from the vocabulary),
with length *distributions* in place of its two fixed lengths.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import math
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

LOOPS = ("open", "closed")


# -- what to send --------------------------------------------------------


def lognormal_length(rng: np.random.Generator, spec: Mapping[str, Any]) -> int:
    """``{"median": m, "sigma": s, "min": a, "max": b}`` → an integer drawn
    from a lognormal of that median and shape, clipped to ``[a, b]``."""
    x = spec["median"] * math.exp(spec["sigma"] * rng.standard_normal())
    return int(min(max(round(x), spec["min"]), spec["max"]))


def draw_request(seed: int, stream: int, index: int,
                 traffic: Mapping[str, Any], vocab: int) -> Dict[str, Any]:
    """Request ``index`` of stream ``stream`` (a client of a closed loop;
    stream 0 of an open one).  Token ids come from the run's seed.  Lengths
    come from the traffic file's ``schedule_seed`` where it has one, and then
    every run of the mix sends the same lengths in the same order (a
    recorded trace: see PERF.md on why tails need it); else from the run's
    seed too.  Either way the same seed sends the same requests."""
    shape = np.random.default_rng(
        [traffic.get("schedule_seed", seed), stream, index])
    n_prompt = lognormal_length(shape, traffic["prompt_tokens"])
    n_out = lognormal_length(shape, traffic["output_tokens"])
    # id 0 is left out, as the program's own generators leave it out
    ids = np.random.default_rng([seed, stream, index, 1])
    return {"stream": stream, "index": index,
            "prompt": ids.integers(1, vocab, size=n_prompt).tolist(),
            "max_tokens": n_out}


def gamma_arrivals(seed: int, rate: float, shape: float, start: float,
                   end: float) -> List[float]:
    """Arrival offsets in ``[start, end)``: Gamma inter-arrivals of the given
    shape (1 is Poisson, under 1 burstier) and of mean ``1 / rate``."""
    rng = np.random.default_rng([seed, 0xA771])
    out, t = [], start
    while True:
        t += rng.gamma(shape, 1.0 / (rate * shape))
        if t >= end:
            return out
        out.append(t)


# -- one request over HTTP -------------------------------------------------


@dataclasses.dataclass
class Record:
    stream: int
    index: int
    n_prompt: int
    asked: int
    due: float
    sent: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: float = 0.0
    status: str = "pending"  # ok | cut | http_<code> | error:<what> | timeout
    finish_reason: Optional[str] = None

    def to_json(self, with_prompt: Optional[List[int]] = None) -> dict:
        d = dataclasses.asdict(self)
        if with_prompt is not None:
            d["prompt"] = with_prompt
        return d


def stream_completion(port: int, req: Mapping[str, Any], rec: Record,
                      stop_at: Optional[float], timeout_s: float) -> None:
    """POST ``/v1/completions`` with ``stream`` on and stamp every token as
    its server-sent event arrives.  With ``stop_at``, hang up once that time
    has passed (status ``cut``): the server takes the disconnect as a
    cancellation."""
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "temperature": 0.0, "stream": True})
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        rec.sent = time.monotonic()
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec.status = f"http_{resp.status}"
            return
        for raw in resp:
            now = time.monotonic()
            if not raw.startswith(b"data: "):
                continue
            if raw[6:].strip() == b"[DONE]":
                break
            event = json.loads(raw[6:])
            choice = event["choices"][0]
            if choice.get("token") is not None:
                rec.tokens.append(choice["token"])
                rec.token_times.append(now)
            else:
                rec.finish_reason = choice["finish_reason"]
                if "error" in event:
                    rec.status = "error:" + str(event["error"].get("type"))
            if stop_at is not None and now >= stop_at:
                rec.status = "cut"
                return
        if rec.status == "pending":
            right = (len(rec.tokens) == rec.asked
                     and rec.finish_reason == "length")
            rec.status = "ok" if right else "error:token_count"
    except TimeoutError:
        rec.status = "timeout"
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec.status = f"error:{type(e).__name__}"
    finally:
        rec.done = time.monotonic()
        conn.close()


# -- the loops -----------------------------------------------------------


def run_closed(spec: Mapping[str, Any]) -> List[dict]:
    """``clients`` callers, each sending its next request when the last one
    is complete, from ``t_open - ramp_s`` until ``t_close``.  The first
    request of each client is cut to a uniform share of its drawn output
    length, so that the rows do not all finish together and the window opens
    on the steady mix of young and old requests.  A request in flight at
    ``t_close`` is hung up on: what it did inside the window counts, its end
    does not exist."""
    traffic, seed = spec["traffic"], spec["seed"]
    t_start = spec["t_open"] - traffic["ramp_s"]
    t_close = spec["t_close"]
    records: List[List[dict]] = [[] for _ in range(traffic["clients"])]

    def client(c: int) -> None:
        time.sleep(max(0.0, t_start - time.monotonic()))
        i = 0
        while time.monotonic() < t_close:
            req = draw_request(seed, c, i, traffic, spec["vocab"])
            if i == 0:
                share = np.random.default_rng(
                    [traffic.get("schedule_seed", seed), c, 0xF1257]).uniform()
                req["max_tokens"] = max(1, round(req["max_tokens"] * share))
            rec = Record(c, i, len(req["prompt"]), req["max_tokens"],
                         due=time.monotonic())
            stream_completion(spec["port"], req, rec, stop_at=t_close,
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


def run_open(spec: Mapping[str, Any]) -> List[dict]:
    """Requests on a schedule drawn from the seed, whatever the server does:
    from ``t_open - ramp_s`` until ``t_close``.  Everything is drawn and
    serialised before the first arrival; one thread sleeps until each
    request is due and hands it to a pool, and ``sent - due`` says how late
    that was.  Requests in flight at ``t_close`` run to their end."""
    traffic, seed = spec["traffic"], spec["seed"]
    seconds = spec["t_close"] - spec["t_open"]
    offsets = gamma_arrivals(traffic.get("schedule_seed", seed),
                             traffic["rate_per_s"],
                             traffic["arrival_shape"],
                             -traffic["ramp_s"], seconds)
    reqs = [draw_request(seed, 0, i, traffic, spec["vocab"])
            for i in range(len(offsets))]
    recs = [Record(0, i, len(r["prompt"]), r["max_tokens"],
                   due=spec["t_open"] + off)
            for i, (r, off) in enumerate(zip(reqs, offsets))]
    with ThreadPoolExecutor(max_workers=traffic["max_in_flight"]) as pool:
        futures = []
        for req, rec in zip(reqs, recs):
            time.sleep(max(0.0, rec.due - time.monotonic()))
            futures.append(pool.submit(
                stream_completion, spec["port"], req, rec, None,
                spec["timeout_s"]))
        for f in futures:
            f.result()
    return [rec.to_json(with_prompt=req["prompt"])
            for req, rec in zip(reqs, recs)]


def run(spec: Mapping[str, Any]) -> dict:
    loop = spec["traffic"]["loop"]
    if loop not in LOOPS:
        raise ValueError(f"loadgen: loop {loop!r} is not one of {LOOPS}")
    records = run_closed(spec) if loop == "closed" else run_open(spec)
    return {"records": records, "finished": time.monotonic()}


def main(argv: List[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    json.dump(run(spec), sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
