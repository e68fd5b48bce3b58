"""The benchmark's Mellum2 cell rehearsed in the tier-1 run (which collects
only ``tests/``): driver ``serve_swa_moe`` at one period of the
``tiny-mellum2`` preset through ``run.run_cell``, W8A16 at group 128,
``correct`` decided by ``benchmark/reference/swa_moe_decoder`` on the
engine's own step-program logits (through the tap that donates the pools), a
prompt past nine windows among them.  A later PR that breaks the cell's
driver, reference, tap or readers fails here.  The same rehearsal, and the
readers' unit tests, are in ``benchmark/tests/test_serve_swa_moe.py``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import mellum2_rehearsal as rehearsal  # noqa: E402

from benchmark import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("mellum2")))


def test_mellum2_cell_rehearsal(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_mellum2_cell_rehearsal_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


# -- the generator that starts a closed loop in client order ---------------

import http.server  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

from benchmark import loadgen  # noqa: E402

ORDERED = os.path.join(ROOT, "benchmark", "ordered_start", "loadgen.py")
TRAFFIC = {"loop": "closed", "clients": 8, "schedule_seed": 3,
           "prompt_tokens": {"median": 40, "sigma": 0.6, "min": 8, "max": 200},
           "output_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
           "ramp_s": 0.3, "start_gap_s": 0.02}


def _ordered():
    spec = importlib.util.spec_from_file_location("ordered_loadgen", ORDERED)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _Completions(http.server.BaseHTTPRequestHandler):
    """``/v1/completions`` as the generator reads it: one event a token,
    then the finish reason, then ``[DONE]``; notes whose connection was
    accepted when."""

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        # the handler threads race for the interpreter lock (under six test
        # workers one can wait longer for it than the 20 ms between two
        # clients' first requests: the driver's run of PR 36 saw two swapped),
        # so a request is filed under its connection's place in the accept
        # loop, which takes them one at a time in the order they were made
        self.server.arrived[self.server.accepted[self.client_address]] = \
            body["prompt"]
        # a server that answers at once has eight client threads and their
        # handlers spin on one interpreter lock: answer in 5 ms
        time.sleep(0.005)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        try:
            for t in range(body["max_tokens"]):
                self.wfile.write(b"data: " + json.dumps(
                    {"choices": [{"token": t}]}).encode() + b"\n\n")
            self.wfile.write(b"data: " + json.dumps(
                {"choices": [{"token": None, "finish_reason": "length"}]}
            ).encode() + b"\n\ndata: [DONE]\n\n")
        except OSError:
            pass  # hung up on at the window's close

    def log_message(self, *args):
        pass


class _Server(http.server.ThreadingHTTPServer):
    # past the default backlog of 5 a connection's SYN is dropped and
    # its client stalls a second, past the window's end
    request_queue_size = 128

    def __init__(self, *args):
        super().__init__(*args)
        self.accepted = {}  # a connection's (host, port) -> its place
        self.arrived = {}   # place -> the prompt it carried

    def get_request(self):
        request, address = super().get_request()
        self.accepted[address] = len(self.accepted)
        return request, address


def test_ordered_start_sends_first_requests_in_client_order():
    server = _Server(("127.0.0.1", 0), _Completions)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    # eight clients, their handlers and the accept loop share this process's
    # interpreter lock, and a thread that wakes queues for it behind every
    # runnable one for a switch interval each: at the default 5 ms a client
    # can reach its connect later than the client 20 ms behind it
    interval = sys.getswitchinterval()
    sys.setswitchinterval(0.0005)
    try:
        t_open = time.monotonic() + 0.5
        out = _ordered().run({
            "traffic": TRAFFIC, "seed": 11, "vocab": 256,
            "port": server.server_port, "t_open": t_open,
            "t_close": t_open + 0.4, "timeout_s": 10.0})
    finally:
        sys.setswitchinterval(interval)
        server.shutdown()
        server.server_close()
    firsts = [loadgen.draw_request(11, c, 0, TRAFFIC, 256)["prompt"]
              for c in range(8)]
    # client order, not a race (this server answers at once, so a client's
    # second request may come before the next client's first)
    arrived = [p for _, p in sorted(server.arrived.items())]
    assert [p for p in arrived if p in firsts] == firsts
    records = out["records"]
    assert all(r["status"] in ("ok", "cut") for r in records)
    by_client = {c: [r for r in records if r["stream"] == c] for c in range(8)}
    for c, recs in by_client.items():
        # the same requests loadgen.run_closed sends: lengths from the
        # schedule's seed, the first answer cut, then the client's next ones
        assert [r["index"] for r in recs] == list(range(len(recs)))
        assert len(recs) > 1
        want = loadgen.draw_request(11, c, 1, TRAFFIC, 256)
        assert recs[1]["prompt"] == want["prompt"]
        assert recs[1]["asked"] == want["max_tokens"]
        assert recs[0]["asked"] <= loadgen.draw_request(
            11, c, 0, TRAFFIC, 256)["max_tokens"]
        due = recs[0]["due"] - (t_open - TRAFFIC["ramp_s"])
        assert due >= c * TRAFFIC["start_gap_s"]  # never before its turn


@pytest.mark.parametrize("traffic", [
    {k: v for k, v in TRAFFIC.items() if k != "start_gap_s"},
    {**TRAFFIC, "loop": "open"}], ids=["no-gap", "open-loop"])
def test_ordered_start_is_loadgen_without_a_gap(traffic, monkeypatch):
    module = _ordered()
    monkeypatch.setattr(module.loadgen, "run", lambda spec: ("base", spec))
    assert module.run({"traffic": traffic}) == ("base", {"traffic": traffic})


def test_ordered_start_imports_neither_jax_nor_the_program():
    code = ("import sys; sys.argv=['x']; import runpy; "
            f"runpy.run_path({ORDERED!r}); "
            "assert 'jax' not in sys.modules and "
            "'deepspeed_tpu' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
