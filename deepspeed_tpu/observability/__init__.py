"""deepspeed_tpu.observability — end-to-end request tracing, flight
recorder, and first-class Prometheus exposition.

Exceeds the reference DeepSpeed, which ships a monitor fan-out
(``deepspeed/monitor``) and a comms logger but nothing request-scoped:

* :mod:`.trace` — always-on span tracer (thread-safe ring buffer,
  Chrome/Perfetto export; live spans are also ``jax.profiler``
  annotations, so a profile shows them beside the device's operations)
  threaded through the whole request lifecycle: broker
  submit→queue→admit→prefill→decode/spec→finish→first SSE write, the
  broker loop's turns (emit, admit) and idle waits, engine steps with
  batch-composition attrs and their children (schedule, build, h2d,
  dispatch, sample, wait, finish), KV paging and adapter moves,
  checkpoint save/load, elastic-agent relaunches, replica lifecycle
  events, comm-collective timings (``PERF.md`` lists every name with its
  reader);
* :mod:`.recorder` — flight recorder: bounded rings of the last N request
  timelines / M engine steps / K infra events, dumped to
  ``$DSTPU_FLIGHT_DIR`` on crash or injected fault;
* :mod:`.prometheus` — text-exposition builder (HELP/TYPE, histograms,
  labels) plus a strict format parser used as the test oracle;
* :mod:`.replay` — the workload-trace schema and its capture at the
  broker (``benchmark/loadgen.py`` is the load generator).

Server surfaces (``serving/server.py``): ``GET /debug/requests`` (recent
timelines), ``GET /debug/trace`` (Perfetto JSON), ``GET /debug/profile``
(on-demand ``jax.profiler`` capture: device operations and the program's
live spans on one clock).  CLI:
``python -m deepspeed_tpu.observability <flight-dump.json>``.

Tracing never enters a jitted computation, so the analysis budgets
(zero host syncs, HLO identity) hold with tracing on — enforced by
``tests/test_observability.py`` token-identity and the tier-1 budget gate.
"""

from .prometheus import (DEFAULT_MS_BUCKETS, ExpositionBuilder,
                         ExpositionError, Histogram, parse_exposition)
from .recorder import FlightRecorder, load_dump, recorder
from .replay import (WorkloadCapture, WorkloadError, WorkloadRequest,
                     load_workload, save_workload)
from .trace import Span, Tracer, tracer

__all__ = [
    "DEFAULT_MS_BUCKETS", "ExpositionBuilder", "ExpositionError",
    "FlightRecorder", "Histogram", "Span", "Tracer", "WorkloadCapture",
    "WorkloadError", "WorkloadRequest", "load_dump", "load_workload",
    "parse_exposition", "recorder", "save_workload", "tracer",
]
