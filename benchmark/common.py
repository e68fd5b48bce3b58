"""What both drivers need round the program: the published sizes checked
against the configuration that runs, a count of compilations, the peak of
device memory, and the profiler held open over a few seconds of the window."""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, Optional

from . import trace_reduce

#: published key → attribute of the program's TransformerConfig
PUBLISHED = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "sliding_window": "sliding_window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_seq_len",
}


def model_as_run(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The published sizes with this configuration's cuts applied: every key
    of ``reduced`` takes its value from ``as_run``."""
    model = {k: v for k, v in config.items() if k in PUBLISHED
             or k in ("head_dim", "hidden_act")}
    for key in config.get("reduced", ()):
        model[key] = config["as_run"][key]
    return model


def program_config(config: Mapping[str, Any]):
    """The program's own configuration object for this file: its preset with
    the file's overrides, refused if any size differs from what the file
    says is run."""
    from deepspeed_tpu.models import transformer as tfm

    cfg = tfm.get_config(config["preset"], **config.get("overrides", {}))
    model = model_as_run(config)
    for key, attr in PUBLISHED.items():
        if key in model and getattr(cfg, attr) != model[key]:
            raise ValueError(
                f"configuration {config['name']}: the file says {key} = "
                f"{model[key]}, the program's preset gives "
                f"{getattr(cfg, attr)}")
    return cfg, model


class CompileCounter:
    """Counts programs lowered, through JAX's own monitoring events: one a
    new program, whether the backend compiles it or the persistent cache
    has it.  Inside the measured window the count has to stay 0."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


def start_jax(log: Callable[[str], None]) -> CompileCounter:
    """What every run does before its first jit: the program's persistent
    compile cache (``JAX_COMPILATION_CACHE_DIR`` where set, else
    ``<checkout>/.jax_cache``), with the small programs of set-up (weights,
    reference) cached too, and the count of compilations begun."""
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CompileCounter()


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip; 0 where the backend does not
    report (the CPU, in rehearsals)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def annotated(fn: Callable, name: str) -> Callable:
    """``fn`` inside a profiler span ``name``: how the benchmark marks calls
    into the program from outside, in the traced run only."""
    import jax

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    wrapped.__wrapped__ = fn
    return wrapped


class TraceSession:
    """The profiler over ``seconds`` of the steady window, then the
    reduction.  ``start`` and ``stop`` are called by whoever owns the
    timeline (the training loop between two steps, a timer thread beside a
    server); the window span is opened right after the profiler starts and
    closed right before it stops, on the calling thread."""

    def __init__(self, log: Callable[[str], None]):
        self.log = log
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # TraceAnnotations are host events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def run_beside(self, t_start: float, seconds: float) -> threading.Thread:
        """Start at ``t_start`` (monotonic) and stop ``seconds`` later, on a
        thread of its own."""
        def body():
            time.sleep(max(0.0, t_start - time.monotonic()))
            self.start()
            time.sleep(seconds)
            self.stop()

        t = threading.Thread(target=body, name="bench-trace", daemon=True)
        t.start()
        return t

    def reduce(self) -> Optional[dict]:
        try:
            files = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not files:
                return None
            self.log(f"trace: {os.path.getsize(files[0]) / 1e6:.1f} MB")
            return trace_reduce.reduce_file(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
