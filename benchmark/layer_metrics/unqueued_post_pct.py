"""Scheduler: the part of ``device_unqueued_pct`` that lies between a
fetch's return and its step's return (``engine/finish``, ``engine/stage``,
the span's close): the sum of ``unqueued_post_ms`` over the window's
``engine/program`` spans, cut to the window and less ``broker/idle``, over
the window's seconds.  The lever: call the next program before this work
(ROADMAP S5)."""

from benchmark import program_queue


def read(obs):
    return program_queue.window_pct(obs, "post_s")
