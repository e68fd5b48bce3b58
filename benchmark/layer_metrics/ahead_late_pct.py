"""Scheduler: of the programs called behind a predecessor (``engine/program``
with ``behind`` 1), the share whose predecessor had FINISHED at the call
(``late`` 1: its result was ready): the device ran dry before its next
program reached it, and idled inside ``step``, where the unqueued time does
not look.  The lever: call the successor earlier in the step (ROADMAP S5
(2))."""

from benchmark import program_queue


def read(obs):
    return program_queue.share_pct(obs["spans"], {"behind": 1}, {"late": 1})
