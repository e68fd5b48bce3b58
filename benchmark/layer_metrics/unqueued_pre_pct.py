"""Scheduler: the part of ``device_unqueued_pct`` that lies between a step's
entry and the call of its program (``engine/schedule``, ``build``, ``h2d``:
the pack and the unpack program's call), the device holding nothing: the sum
of ``unqueued_pre_ms`` over the window's ``engine/program`` spans, cut to the
window and less ``broker/idle``, over the window's seconds.  The lever: make
the copy before the step is entered, or behind a program (ROADMAP S5)."""

from benchmark import program_queue


def read(obs):
    return program_queue.window_pct(obs, "pre_s")
