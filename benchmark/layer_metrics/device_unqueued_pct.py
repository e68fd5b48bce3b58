"""Scheduler: share of the window in which there was work and the device
held no program of it, on the program's clock: 100 x (the window less the
union of the ``engine/program`` spans, one a call of a step program from the
call to the fetch of its tokens, less what lies inside a ``broker/idle``
span) / the window's seconds (``benchmark/program_queue.py``).  Right under
two steps in flight, where ``device_starved_pct`` (sums of a STEP's split)
reads above the device's own idle share: a program called behind another
covers the host work beside it.  ``serve_device_idle_pct`` (the device's
trace) less this and less the nothing-to-run share is the launch, the
fetch's tail and the programs that came late (``ahead_late_pct``).
``unqueued_post_pct``, ``unqueued_turn_pct`` and ``unqueued_pre_pct`` are
its three parts."""

from benchmark import program_queue


def read(obs):
    return program_queue.window_pct(obs, "unqueued_s")
