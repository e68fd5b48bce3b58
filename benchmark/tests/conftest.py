"""The benchmark's own tests run on the CPU, as the repository's do:

    python -m pytest benchmark/tests -q

They rehearse every driver at a tiny preset through the same code path as a
chip run; the check for a TPU is bypassed by the tests alone."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DSTPU_ACCELERATOR", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
