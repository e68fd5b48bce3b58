"""Entry point for one multi-process distributed test worker.

Pins the CPU platform via ``jax.config``, selects gloo CPU collectives, rendezvouses through ``deepspeed_tpu.comm.init_distributed()``
using ONLY the launcher env contract, then dispatches to the named worker
function in ``tests.dist.workers``.
"""

from __future__ import annotations

import argparse
import json
import os
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("worker")
    ap.add_argument("--out", required=True)
    ap.add_argument("--args", default="{}")
    a = ap.parse_args()

    out = {"ok": False, "rank": int(os.environ.get("PROCESS_ID", -1))}
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

        from deepspeed_tpu import comm

        # no explicit args: COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID
        # must be enough — that IS the launcher contract under test
        comm.init_distributed()

        from tests.dist import workers

        fn = getattr(workers, a.worker)
        result = fn(json.loads(a.args))
        out = {"ok": True, "rank": jax.process_index(), "result": result}
    except Exception as e:  # noqa: BLE001 — reported to the parent verbatim
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()
    with open(a.out, "w") as f:
        json.dump(out, f)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
