"""Jitted steps: the median time the host waited in the fetch of a decode
program's tokens (``fetch_wait_ms`` of the ``engine/program`` spans of kind
``"decode"``: the length of the program's ``engine/wait``).  About 0: the
device had finished before the host asked, and the host sets the pace of the
decode steps; a step's length: the device does, which is what a saturated
cell should read."""

from benchmark import program_queue, stats


def read(obs):
    return stats.percentile(
        (a["fetch_wait_ms"] for a in program_queue.programs(
            obs["spans"], kind="decode")), 50)
