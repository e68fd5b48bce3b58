import ast
import os
import subprocess
import sys

import numpy as np

from benchmark import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = {"prompt_tokens": {"median": 256, "sigma": 0.7, "min": 64,
                             "max": 1024},
           "output_tokens": {"median": 32, "sigma": 0.6, "min": 8, "max": 64}}


def test_imports_neither_jax_nor_the_program():
    path = os.path.join(os.path.dirname(HERE), "loadgen.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert not names & {"jax", "jaxlib", "deepspeed_tpu", "benchmark"}
    code = ("import sys; sys.argv=['x']; import runpy; "
            f"runpy.run_path({path!r}); "
            "assert 'jax' not in sys.modules and "
            "'deepspeed_tpu' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_same_seed_same_requests():
    a = loadgen.draw_request(7, 3, 5, TRAFFIC, 32000)
    b = loadgen.draw_request(7, 3, 5, TRAFFIC, 32000)
    c = loadgen.draw_request(8, 3, 5, TRAFFIC, 32000)
    assert a == b and a["prompt"] != c["prompt"]
    assert 0 not in a["prompt"] and max(a["prompt"]) < 32000


def test_lengths_are_lognormal_and_clipped():
    rng = np.random.default_rng(0)
    n = [loadgen.lognormal_length(rng, TRAFFIC["prompt_tokens"])
         for _ in range(4000)]
    assert min(n) >= 64 and max(n) <= 1024
    assert 230 < np.median(n) < 285
    assert len(set(n)) > 300  # a distribution, not two fixed lengths


def test_gamma_arrivals_rate_and_burstiness():
    t = loadgen.gamma_arrivals(1, rate=5.0, shape=0.5, start=-10.0,
                               end=2000.0)
    gaps = np.diff(t)
    assert t[0] >= -10.0 and t[-1] < 2000.0
    assert abs(len(t) / 2010.0 - 5.0) < 0.25
    # Gamma of shape k has a coefficient of variation 1 / sqrt(k)
    assert abs(gaps.std() / gaps.mean() - 2 ** 0.5) < 0.1
    assert t == loadgen.gamma_arrivals(1, 5.0, 0.5, -10.0, 2000.0)
