"""Scheduler: of the decode programs fetched in the window, the share that
was called BEHIND its predecessor, before that one's tokens were fetched
(``engine/program`` with ``kind`` ``"decode"``: ``behind`` 1): the yardstick
of two steps in flight (ISSUE 50), whose host work then runs beside a
program.  The rest were called with nothing queued: behind a mixed step, a
row at its budget, a request waiting."""

from benchmark import program_queue


def read(obs):
    return program_queue.share_pct(obs["spans"], {"kind": "decode"},
                                   {"behind": 1})
