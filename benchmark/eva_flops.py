"""Operations and bytes that EVA attention and its summariser *require*,
from the published sizes and the host's counters alone, whatever implements
them: the yardstick of ``eva_decode_roofline_pct``,
``eva_prefill_roofline_pct`` and ``eva_summary_roofline_pct``, kept with the
benchmark so that a change to the program cannot move it.

The counters are on the program's ``engine/step`` spans, made on the host
from the rows' positions (``engine._count_eva``), summed over rows and
layers: ``eva_window_keys`` and ``eva_summary_keys`` (the keys a step's
queries have to read: a row's window up to its newest byte, and one summary
a chunk of every window it has closed), ``eva_query_keys`` (the (query, key)
pairs under the one softmax), and over rows alone ``eva_windows_closed``.

* attention reads a key and its value once a row a step: ``heads x head_dim``
  values each, 2 bytes a value;
* a (query, key) pair costs ``q . k`` and ``p . v`` a head: ``4 x head_dim``
  FLOPs;
* a closed window's K and V are read once a layer and ``window / chunk``
  summary keys and values written.

What a tile multiplies to mask away, what it reads again for another tile of
the same row and the padding of a decode row to a sublane group are the
program's own choice and count as nothing.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

SUMMARIZE = "eva_summarize"


def head_values(model: Mapping[str, Any]) -> int:
    """Values of one token's key (or value) over all heads."""
    return model["hidden_size"]  # heads x head_dim, no grouping


def key_bytes(model: Mapping[str, Any], keys: float, kv_bytes: int = 2
              ) -> float:
    """``keys`` keys AND their values read."""
    return 2.0 * float(keys) * head_values(model) * kv_bytes


def pair_flops(model: Mapping[str, Any], pairs: float) -> float:
    """``pairs`` (query, key) pairs over all heads: ``q . k`` and ``p . v``."""
    return 4.0 * float(pairs) * head_values(model)


def window_bytes(model: Mapping[str, Any], windows: float, kv_bytes: int = 2
                 ) -> float:
    """``windows`` closed windows, every layer: K and V read, the summaries'
    written."""
    entries = model["window_size"] + model["window_size"] // \
        model["chunk_size"]
    return (float(windows) * model["num_hidden_layers"] * 2.0 * entries
            * head_values(model) * kv_bytes)


def steps_traced(t: Mapping[str, Any], model: Mapping[str, Any],
                 program: str) -> float:
    """Steps of ``program`` inside the traced window: every layer of a step
    calls the summariser once, whether a row closes a window or not."""
    return t["kernel_calls"].get(f"{program}/{SUMMARIZE}", 0) / float(
        model["num_hidden_layers"])


def roofline_share(obs, kernel: str, programs, least_of) -> Optional[float]:
    """100 x (the least time the traced steps' work could take) / (the
    device time of the Pallas kernel ``kernel``), over ``programs``
    (``(kind of step, module name)`` pairs): ``least_of(model, peaks, mean)``
    seconds for a mean step of the kind (``mean(counter)``: the counter's
    mean over the window's steps of that kind), times the steps of that kind
    the trace holds.  None where the program has no such counter or the
    trace no such kernel."""
    from benchmark import stats
    from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

    t = by_name(obs)
    model = obs.get("model") or {}
    if not t or "window_size" not in model:
        return None
    peaks = obs["device"]["peaks"]
    least = taken = 0.0
    for kind, program in programs:
        steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                       kind=kind)
                 if "eva_query_keys" in s["attrs"]]
        n = steps_traced(t, model, program)
        if not steps or not n:
            continue

        def mean(counter, steps=steps):
            return sum(a[counter] for a in steps) / len(steps)

        least += n * least_of(model, peaks, mean)
        taken += t["kernel_s"].get(f"{program}/{kernel}", 0.0)
    if not taken or not least:
        return None
    return 100.0 * least / taken


DECODE = (("decode", "jit_decode_step"),)
MIXED = (("mixed", "jit_mixed_step"),)
