"""The records every session starts from stay small enough to be read.

A session reads ``PERF.md``, ``CHANGES.md`` and ``ROADMAP.md`` whole before it
does anything, and its file reader refuses a file over 256 KB.  Each record
has a cap below that (its own rule: ``PERF.md`` merges its oldest findings,
``CHANGES.md`` moves its oldest entries to ``CHANGES-archive.md``,
``ROADMAP.md`` closes what is done), so a PR that adds 20 KB merges 20 KB.
"""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LINE = 4_000


@pytest.mark.parametrize("name,max_bytes", [
    ("PERF.md", 240_000), ("CHANGES.md", 200_000), ("ROADMAP.md", 100_000)])
def test_record_fits_its_readers(name, max_bytes):
    with open(os.path.join(ROOT, name), "rb") as f:
        data = f.read()
    assert len(data) <= max_bytes, (
        f"{name} is {len(data)} bytes, over its cap of {max_bytes}: merge "
        "or archive the oldest entries (the rule is at the file's head)")
    long = [(i, len(ln)) for i, ln in
            enumerate(data.decode("utf-8").splitlines(), 1)
            if len(ln) > MAX_LINE]
    assert not long, (
        f"{name} has lines over {MAX_LINE} characters (line, length): {long}")
