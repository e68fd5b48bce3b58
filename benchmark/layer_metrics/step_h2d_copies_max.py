"""Scheduler: the most host-to-device copies any step of the window made
before its program was called (``h2d_copies`` on the program's
``engine/step`` spans).  Has to be 1: the step's one packed buffer."""

from benchmark import stats


def read(obs):
    copies = [s["attrs"]["h2d_copies"]
              for s in stats.spans_named(obs, "engine/step")
              if "h2d_copies" in s["attrs"]]
    return max(copies) if copies else None
