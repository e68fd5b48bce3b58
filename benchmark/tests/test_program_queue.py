"""The device's queue on the program's clock (ISSUE 53):
``benchmark/program_queue.py`` and the seven readers over it, each on span
lists made by hand, where every share is known exactly; on a program from
before ``engine/program`` each reader returns ``None`` and raises nothing."""

import json
import os

import pytest

from benchmark import program_queue, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
T_OPEN, T_CLOSE = 100.0, 110.0
SERVING = ["chat-decode-sat", "doc-prefill-loaded", "olmoe-decode-sat",
           "mellum2-code-sat", "nemotron3-chat-wide-sat", "glm52-ctx8k-sat",
           "evabyte-doc-bytes-sat", "kimilinear-reason-sat"]
DECODE = ["chat-decode-sat", "olmoe-decode-sat", "nemotron3-chat-wide-sat",
          "evabyte-doc-bytes-sat", "kimilinear-reason-sat"]
#: name -> (layer, better, moves, cells), in the order ISSUE 53 gives them
READERS = {
    "device_unqueued_pct": ("scheduler", "lower", "itl_p90_ms", SERVING),
    "unqueued_post_pct": ("scheduler", "lower", "itl_p90_ms", SERVING),
    "unqueued_pre_pct": ("scheduler", "lower", "itl_p90_ms", SERVING),
    "unqueued_turn_pct": ("entry points", "lower", "itl_p90_ms", SERVING),
    "decode_ahead_pct": ("scheduler", "higher", "serve_out_tokens_per_s",
                         DECODE),
    "ahead_late_pct": ("scheduler", "lower", "serve_out_tokens_per_s",
                       DECODE),
    "decode_fetch_wait_ms_p50": ("jitted steps", "higher",
                                 "serve_out_tokens_per_s", DECODE),
}


def span(name, t_start, t_end, **attrs):
    return {"name": name, "t_start": t_start, "t_end": t_end, "attrs": attrs}


def program(t_start, t_end, kind="decode", behind=0, gap=None, wait=1.0,
            **attrs):
    """An ``engine/program`` span; ``gap``: its (post, turn, pre) in ms."""
    if behind:
        gap = (0.0, 0.0, 0.0)
        attrs.setdefault("late", 0)
    if gap is not None:
        attrs.update(unqueued_ms=sum(gap), unqueued_post_ms=gap[0],
                     unqueued_turn_ms=gap[1], unqueued_pre_ms=gap[2])
    return span("engine/program", t_start, t_end, kind=kind, behind=behind,
                fetch_wait_ms=wait, **attrs)


def obs_of(spans):
    """What a serving driver hands the readers: the spans that ENDED inside
    the window."""
    return {"window": {"t_open": T_OPEN, "t_close": T_CLOSE,
                       "seconds": T_CLOSE - T_OPEN},
            "spans": [s for s in spans if T_OPEN <= s["t_end"] < T_CLOSE]}


def reader(name):
    return run.load_module(os.path.join(ROOT, "benchmark"), "layer_metrics",
                           name, "metric").read


# Ten seconds of one engine.  Unqueued: 100.0-100.5 (nobody's: the program
# that ends it says no parts), 102-103 (post 0.2, turn 0.5, pre 0.3), 105-107
# (post 0.4, turn 1.4 of which 105.4-106.4 lies inside a ``broker/idle``,
# pre 0.2) and 108-108.5 (post 0.1, turn 0.1, pre 0.3).  What follows the
# last program's end (109.5) is left out: the program under way there ends
# after the close and is not handed over.
SPANS = [
    program(99.0, 99.9, kind="mixed", gap=(10.0, 10.0, 10.0)),  # before
    program(100.5, 102.0, kind="mixed", wait=0.0),  # the parts unknown
    program(103.0, 104.0, gap=(200.0, 500.0, 300.0), wait=3.0),
    program(103.5, 105.0, behind=1, late=1, wait=5.0),  # overlaps the last
    program(107.0, 108.0, gap=(400.0, 1400.0, 200.0), wait=0.5),
    program(108.5, 109.0, kind="mixed", gap=(100.0, 100.0, 300.0)),
    program(108.8, 109.5, behind=1, wait=7.0),
    program(109.2, 110.5, behind=1),  # ends after the close: not handed over
    span("broker/idle", 105.4, 106.4),
    span("broker/turn", 105.3, 105.4, next="idle"),
    span("engine/step", 103.0, 104.1, kind="decode", device_ms=1000.0,
         pre_ms=1.0, post_ms=1.0),
]
WANT = {
    # 0.5 + 1.0 + (2.0 - 1.0 idle) + 0.5 of 10 s
    "device_unqueued_pct": 30.0,
    # 0.2 + 0.4 + 0.1
    "unqueued_post_pct": 7.0,
    # 0.5 + (1.4 - 1.0 idle: the turn runs 105.4-106.8) + 0.1
    "unqueued_turn_pct": 10.0,
    # 0.3 + 0.2 + 0.3
    "unqueued_pre_pct": 8.0,
    # decode programs handed over: 103, 103.5 (behind), 107, 108.8 (behind)
    "decode_ahead_pct": 50.0,
    # behind: 103.5 (late), 108.8
    "ahead_late_pct": 50.0,
    # 3.0, 5.0, 0.5, 7.0
    "decode_fetch_wait_ms_p50": 4.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_over_a_hand_made_window(name):
    read = reader(name)
    assert read(obs_of(SPANS)) == pytest.approx(WANT[name], abs=1e-9)
    assert read(obs_of([])) is None
    # the parent's program: every span but ``engine/program``
    assert read(obs_of([s for s in SPANS
                        if s["name"] != "engine/program"])) is None


def test_the_three_parts_add_up_to_the_whole_less_what_is_nobodys():
    q = program_queue.of_window(obs_of(SPANS))
    assert q["post_s"] + q["turn_s"] + q["pre_s"] == pytest.approx(
        q["unqueued_s"] - 0.5)  # 100.0-100.5: its program says no parts
    assert q["nothing_to_run_s"] == pytest.approx(1.0)
    assert q["accounted_s"] == pytest.approx(9.5) and q["programs"] == 6
    # a window whose every gap says whose it was: the parts are the whole
    whole = obs_of([s for s in SPANS if s["t_start"] >= 103.0])
    whole["window"].update(t_open=102.0, seconds=8.0)
    q = program_queue.of_window(whole)
    assert q["post_s"] + q["turn_s"] + q["pre_s"] == pytest.approx(
        q["unqueued_s"]) and q["unqueued_s"] == pytest.approx(2.5)


def test_time_inside_broker_idle_is_left_out():
    spans = [program(100.0, 101.0), span("broker/idle", 101.2, 103.7),
             program(104.0, 105.0, gap=(100.0, 2700.0, 200.0))]
    q = program_queue.unqueued(spans, 100.0, 105.0)
    assert q["unqueued_s"] == pytest.approx(0.5)
    assert (q["post_s"], q["turn_s"], q["pre_s"]) == pytest.approx(
        (0.1, 0.2, 0.2))
    spans[1] = span("broker/turn", 101.2, 103.7, next="step")
    q = program_queue.unqueued(spans, 100.0, 105.0)
    assert q["unqueued_s"] == pytest.approx(3.0)
    assert q["turn_s"] == pytest.approx(2.7)


def test_an_interval_inside_the_window_is_cut_at_both_ends():
    """The script's traced part of the window: programs on both sides are in
    the list, gaps and parts are cut to the interval."""
    spans = [program(100.0, 101.0), program(102.0, 103.0,
                                            gap=(250.0, 500.0, 250.0)),
             program(104.0, 105.0, gap=(500.0, 250.0, 250.0))]
    q = program_queue.unqueued(spans, 101.5, 103.6)
    assert q["unqueued_s"] == pytest.approx(0.5 + 0.6)
    assert (q["post_s"], q["turn_s"], q["pre_s"]) == pytest.approx(
        (0.5, 0.25 + 0.1, 0.25))
    assert q["accounted_s"] == pytest.approx(2.1) and q["programs"] == 1
    assert program_queue.unqueued(spans, 106.0, 107.0) is None


def test_a_dropped_program_covers_its_time_and_counts_in_no_share():
    spans = [program(100.0, 101.0), program(100.5, 103.0, behind=1, late=1,
                                            error=True),
             program(103.5, 104.0, gap=(100.0, 100.0, 300.0))]
    q = program_queue.unqueued(spans, 100.0, 104.0)
    assert q["unqueued_s"] == pytest.approx(0.5)
    assert program_queue.share_pct(spans, {"kind": "decode"},
                                   {"behind": 1}) == 0.0
    assert program_queue.share_pct(spans, {"behind": 1}, {"late": 1}) is None


def test_every_new_entry_finds_its_file_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # appended, in the issue's order, behind everything the parent had
    assert [m["name"] for m in spec["per_layer"][-len(READERS):]] == \
        list(READERS)
    older = {m["layer"] for m in spec["per_layer"][:-len(READERS)]}
    for m in spec["per_layer"][-len(READERS):]:
        layer, better, moves, cells = READERS[m["name"]]
        assert callable(reader(m["name"]))
        assert m == {"name": m["name"], "unit": m["unit"], "better": better,
                     "source": "program_span", "layer": layer,
                     "moves": moves, "workloads": cells}
        assert m["unit"] == ("ms" if m["name"].endswith("_p50") else "%")
        assert layer in older
        moved = next(e for e in spec["end_to_end"] if e["name"] == moves)
        assert set(cells) <= set(moved["workloads"])
        for cell in cells:
            assert m in run.metrics_of(spec, "per_layer", cell)
