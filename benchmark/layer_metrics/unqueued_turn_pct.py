"""Entry points: the part of ``device_unqueued_pct`` that lies between one
step's return and the next step's entry, the caller's (the server's
``broker/turn``: tokens handed to the streams, admissions), the device
holding nothing: the sum of ``unqueued_turn_ms`` over the window's
``engine/program`` spans, cut to the window and less ``broker/idle``, over
the window's seconds."""

from benchmark import program_queue


def read(obs):
    return program_queue.window_pct(obs, "turn_s")
