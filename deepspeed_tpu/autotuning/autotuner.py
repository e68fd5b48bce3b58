"""Autotuner.

Capability analogue of the reference's ``autotuning/autotuner.py``
(``Autotuner:42``, ``tune:404`` + the experiment ``scheduler.py``): search
over (zero stage, micro batch size, remat policy) measuring real training
throughput and return the best config.

Two execution modes:

* **in-process** (``Autotuner``): each candidate builds an engine, times a
  few steps, and is torn down; compile cache makes repeated shapes cheap.
* **subprocess** (``SubprocessAutotuner`` + ``ExperimentScheduler``): each
  candidate is a fresh ``experiment_runner`` process — matching the
  reference's scheduler/launcher round trips (``autotuning/scheduler.py:
  23,144``) — so chip OOMs or compile wedges cannot poison the sweep, and
  candidates can be dispatched to other hosts through the ``dstpu``
  launcher (``launcher_args``).

OOMs and invalid configs are recorded as failures in both modes, mirroring
the reference's fault-tolerant sweep.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..runtime.config import AutotuningConfig
from ..utils.logging import log_dist, logger


@dataclasses.dataclass
class Experiment:
    config_overrides: Dict[str, Any]
    throughput: Optional[float] = None  # samples/sec
    step_time_s: Optional[float] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.throughput is not None


DEFAULT_SPACE = {
    "zero_stage": [0, 1, 2, 3],
    "micro_batch": [1, 2, 4, 8],
    "remat_policy": None,  # model-owned; engine-level space below
}

# Communication-bucket axes (element counts).  ``reduce_bucket_size`` sizes
# the IPG gradient buckets (runtime/coalesce.py resolve_bucket_numel) —
# smaller buckets start reducing earlier and overlap deeper into backward,
# larger ones amortize collective launch cost; ``allgather_bucket_size``
# sizes the ZeRO-1/2 post-step param gather.  Merge into an Autotuner
# ``space`` to sweep them; ``apply_overrides`` maps the axis names onto the
# zero_optimization config keys.
BUCKET_SPACE = {
    "reduce_bucket_size": [2**22, 2**25, 500_000_000],
    "allgather_bucket_size": [2**22, 2**25, 500_000_000],
}


class Autotuner:
    def __init__(self, cfg: AutotuningConfig,
                 make_engine: Callable[[Dict[str, Any]], Any],
                 make_batch: Callable[[int], Dict[str, np.ndarray]],
                 space: Optional[Dict[str, Sequence]] = None):
        """``make_engine(overrides)`` builds a TrainingEngine for a candidate;
        ``make_batch(train_batch_size)`` supplies a host batch."""
        self.cfg = cfg
        self.make_engine = make_engine
        self.make_batch = make_batch
        self.space = space or {
            "zero_stage": [0, 1, 2, 3],
            "micro_batch": [1, 2, 4],
        }
        self.experiments: List[Experiment] = []

    def _candidates(self) -> List[Dict[str, Any]]:
        keys = list(self.space)
        combos = itertools.product(*(self.space[k] for k in keys))
        return [dict(zip(keys, c)) for c in combos]

    def _measure(self, overrides: Dict[str, Any]) -> Experiment:
        exp = Experiment(config_overrides=dict(overrides))
        engine = None
        try:
            engine = self.make_engine(overrides)
            batch = self.make_batch(engine.train_batch_size)
            warmup = max(1, self.cfg.start_profile_step - 1)
            steps = max(1, self.cfg.end_profile_step - self.cfg.start_profile_step)
            for _ in range(warmup):
                engine.train_batch(batch)
            engine.accelerator.synchronize()
            t0 = time.perf_counter()
            for _ in range(steps):
                engine.train_batch(batch)
            engine.accelerator.synchronize()
            dt = (time.perf_counter() - t0) / steps
            exp.step_time_s = dt
            exp.throughput = engine.train_batch_size / dt
        except Exception as e:  # OOM / invalid combos are data, not crashes
            exp.error = f"{type(e).__name__}: {e}"
            logger.warning(f"autotune candidate {overrides} failed: {exp.error}")
        finally:
            del engine
        return exp

    def _run(self, overrides: Dict[str, Any]) -> Experiment:
        exp = self._measure(overrides)
        self.experiments.append(exp)
        if exp.ok:
            log_dist(f"autotune {overrides}: "
                     f"{exp.throughput:.1f} samples/s ({exp.step_time_s * 1e3:.0f} ms)")
        return exp

    def tune(self) -> Tuple[Dict[str, Any], List[Experiment]]:
        """Reference: ``Autotuner.tune`` — returns (best overrides, all runs).

        Fast mode (two-phase, reference --fast): sweep the micro-batch axis at
        the first value of every other axis, then sweep the remaining axes at
        the winning micro batch."""
        if self.cfg.fast and "micro_batch" in self.space and len(self.space) > 1:
            others_first = {k: v[0] for k, v in self.space.items()
                            if k != "micro_batch"}
            phase1 = [dict(others_first, micro_batch=m)
                      for m in self.space["micro_batch"]
                      [: self.cfg.num_tuning_micro_batch_sizes]]
            for ov in phase1:
                self._run(ov)
            ok1 = [e for e in self.experiments if e.ok]
            best_micro = (max(ok1, key=lambda e: e.throughput)
                          .config_overrides["micro_batch"]
                          if ok1 else self.space["micro_batch"][0])
            other_keys = [k for k in self.space if k != "micro_batch"]
            for combo in itertools.product(*(self.space[k] for k in other_keys)):
                ov = dict(zip(other_keys, combo), micro_batch=best_micro)
                if not any(e.config_overrides == ov for e in self.experiments):
                    self._run(ov)
        else:
            for overrides in self._candidates():
                self._run(overrides)
        ok = [e for e in self.experiments if e.ok]
        if not ok:
            raise RuntimeError("autotuning: every candidate failed")
        if self.cfg.metric == "latency":
            best = min(ok, key=lambda e: e.step_time_s)
        else:  # throughput (default) / flops proxy
            best = max(ok, key=lambda e: e.throughput)
        log_dist(f"autotune best: {best.config_overrides} "
                 f"({best.throughput:.1f} samples/s)")
        return best.config_overrides, self.experiments


class ModelBasedAutotuner(Autotuner):
    """Cost-model-guided search (reference:
    ``autotuning/tuner/model_based_tuner.py`` — there an XGBoost cost model
    ranks unexplored configs; here a ridge-regressed log-linear model, i.e.
    multiplicative per-axis effects, which is exactly the structure of
    throughput over zero-stage/micro-batch/remat axes).

    Procedure:

    1. **seed** with a one-factor-at-a-time design: a center config plus
       one variant per axis LEVEL — every level gets measured at least
       once, at ``1 + Σ(len(axis)-1)`` experiments instead of the grid's
       ``Π len(axis)``;
    2. **fit** ridge regression on log(throughput) over one-hot levels;
    3. **probe** unmeasured candidates in predicted-best order until
       ``tuner_early_stopping`` consecutive probes fail to beat the
       incumbent (failed candidates count — they are information too).

    Returns the best MEASURED config (predictions only order the search,
    they never pick the winner)."""

    def _score(self, e: Experiment) -> float:
        """The maximized objective, honoring ``cfg.metric`` — fitting and
        early-stopping on throughput while the final pick used latency
        would let the search stop before the latency-best config is ever
        measured."""
        if self.cfg.metric == "latency":
            return 1.0 / e.step_time_s
        return e.throughput

    def _featurize(self, ov: Dict[str, Any]) -> "np.ndarray":
        feats = [1.0]
        for key in sorted(self.space):
            levels = list(self.space[key])
            # one-hot with the first level as baseline
            feats.extend(1.0 if ov[key] == lv else 0.0
                         for lv in levels[1:])
        return np.array(feats, np.float64)

    def _fit_predict(self, candidates: List[Dict[str, Any]],
                     lam: float = 1e-3) -> List[float]:
        ok = [e for e in self.experiments if e.ok]
        X = np.stack([self._featurize(e.config_overrides) for e in ok])
        y = np.log(np.array([self._score(e) for e in ok], np.float64))
        d = X.shape[1]
        theta = np.linalg.solve(X.T @ X + lam * np.eye(d), X.T @ y)
        return [float(self._featurize(c) @ theta) for c in candidates]

    def tune(self) -> Tuple[Dict[str, Any], List[Experiment]]:
        all_cands = self._candidates()
        center = {k: v[0] for k, v in self.space.items()}
        seeds = [center] + [
            dict(center, **{key: lv})
            for key in sorted(self.space)
            for lv in list(self.space[key])[1:]
        ]
        for ov in seeds:
            self._run(ov)
        if not any(e.ok for e in self.experiments):
            raise RuntimeError("autotuning: every seed candidate failed")

        def measured(ov):
            return any(e.config_overrides == ov for e in self.experiments)

        patience = max(1, self.cfg.tuner_early_stopping)
        strikes = 0
        while strikes < patience:
            remaining = [c for c in all_cands if not measured(c)]
            if not remaining:
                break
            preds = self._fit_predict(remaining)
            ov = remaining[int(np.argmax(preds))]
            incumbent = max((self._score(e) for e in self.experiments
                             if e.ok), default=0.0)
            exp = self._run(ov)
            if exp.ok and self._score(exp) > incumbent:
                strikes = 0
            else:
                strikes += 1
        ok = [e for e in self.experiments if e.ok]
        if self.cfg.metric == "latency":
            best = min(ok, key=lambda e: e.step_time_s)
        else:
            best = max(ok, key=lambda e: e.throughput)
        log_dist(f"autotune(model_based) best: {best.config_overrides} "
                 f"({best.throughput:.1f} samples/s, "
                 f"{len(self.experiments)}/{len(all_cands)} configs measured)")
        return best.config_overrides, self.experiments


class RandomAutotuner(ModelBasedAutotuner):
    """Shuffled search with early stopping (reference
    ``tuner/random_tuner.py``): measure candidates in random order, stop
    after ``tuner_early_stopping`` consecutive failures to improve — cheap
    when the grid is large and effects are monotone-ish.  Shares the
    metric-aware ``_score`` with the model-based tuner."""

    def tune(self) -> Tuple[Dict[str, Any], List[Experiment]]:
        cands = self._candidates()
        np.random.default_rng(self.cfg.mp_size + 42).shuffle(cands)
        patience = max(1, self.cfg.tuner_early_stopping)
        strikes = 0
        for ov in cands:
            incumbent = max((self._score(e) for e in self.experiments
                             if e.ok), default=0.0)
            exp = self._run(ov)
            if exp.ok and self._score(exp) > incumbent:
                strikes = 0
            elif self.experiments and any(e.ok for e in self.experiments):
                strikes += 1
                if strikes >= patience:
                    break
        ok = [e for e in self.experiments if e.ok]
        if not ok:
            raise RuntimeError("autotuning: every candidate failed")
        best = max(ok, key=self._score)
        log_dist(f"autotune(random) best: {best.config_overrides} "
                 f"({len(self.experiments)}/{len(cands)} measured)")
        return best.config_overrides, self.experiments


def make_tuner(cfg: AutotuningConfig, *args, **kwargs) -> Autotuner:
    """Dispatch on ``autotuning.tuner_type`` (reference ``tuner/__init__``:
    gridsearch | random | model_based)."""
    if cfg.tuner_type == "model_based":
        return ModelBasedAutotuner(cfg, *args, **kwargs)
    if cfg.tuner_type == "random":
        return RandomAutotuner(cfg, *args, **kwargs)
    return Autotuner(cfg, *args, **kwargs)


# ---------------------------------------------------------------------------
# subprocess mode (reference scheduler.py equivalent)
# ---------------------------------------------------------------------------


def apply_overrides(config: Dict[str, Any],
                    overrides: Dict[str, Any]) -> Dict[str, Any]:
    """Map sweep-axis names onto engine-config keys (dotted paths pass
    through, e.g. ``"zero_optimization.stage"``)."""
    import copy

    out = copy.deepcopy(config)
    alias = {"zero_stage": "zero_optimization.stage",
             "micro_batch": "train_micro_batch_size_per_gpu",
             "reduce_bucket_size": "zero_optimization.reduce_bucket_size",
             "allgather_bucket_size":
                 "zero_optimization.allgather_bucket_size"}
    for key, value in overrides.items():
        path = alias.get(key, key).split(".")
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return out


class ExperimentScheduler:
    """Run experiment specs as subprocesses (one at a time — a chip runs one
    XLA client; cross-host placement belongs to ``launcher_args``) and
    collect their JSON results (the reference ResourceManager's job)."""

    def __init__(self, exps_dir: str, launcher_args: Sequence[str] = (),
                 env: Optional[Dict[str, str]] = None,
                 timeout_s: float = 900):
        self.exps_dir = exps_dir
        self.launcher_args = list(launcher_args)
        self.env = env
        self.timeout_s = timeout_s
        os.makedirs(exps_dir, exist_ok=True)

    def command(self, spec_path: str, result_path: str) -> List[str]:
        return [*self.launcher_args, sys.executable, "-m",
                "deepspeed_tpu.autotuning.experiment_runner",
                "--spec", spec_path, "--result", result_path]

    def run_one(self, spec: Dict[str, Any], tag: str) -> Dict[str, Any]:
        spec_path = os.path.join(self.exps_dir, f"{tag}.json")
        result_path = os.path.join(self.exps_dir, f"{tag}.result.json")
        if os.path.exists(result_path):  # never read a previous sweep's file
            os.unlink(result_path)
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        env = dict(os.environ, **(self.env or {}))
        try:
            proc = subprocess.run(self.command(spec_path, result_path),
                                  env=env, timeout=self.timeout_s,
                                  capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            return {"ok": False, "error": f"timeout after {self.timeout_s}s"}
        if not os.path.exists(result_path):
            # the runner died before its except-handler could report (hard
            # abort, segfault, bad launcher args) — surface the stderr tail
            tail = (proc.stderr or "").strip().splitlines()[-8:]
            return {"ok": False,
                    "error": f"runner exited rc={proc.returncode} with no "
                             f"result file; stderr tail: {' | '.join(tail)}"}
        with open(result_path) as f:
            return json.load(f)


class SubprocessAutotuner(Autotuner):
    """Autotuner whose measurements run in fresh processes.

    ``model``: JSON-able model description for the runner
    ({"preset": ..., "overrides": {...}}); ``base_config``: the engine
    config every candidate starts from.
    """

    def __init__(self, cfg: AutotuningConfig, model: Dict[str, Any],
                 base_config: Dict[str, Any],
                 space: Optional[Dict[str, Sequence]] = None,
                 scheduler: Optional[ExperimentScheduler] = None,
                 profile_steps: int = 3, seq_len: Optional[int] = None):
        super().__init__(cfg, make_engine=None, make_batch=None, space=space)
        self.model = model
        self.base_config = base_config
        self.scheduler = scheduler or ExperimentScheduler(cfg.exps_dir)
        self.profile_steps = profile_steps
        self.seq_len = seq_len
        self._counter = 0

    def _measure(self, overrides: Dict[str, Any]) -> Experiment:
        exp = Experiment(config_overrides=dict(overrides))
        spec = {
            "model": self.model,
            "config": apply_overrides(self.base_config, overrides),
            "warmup_steps": max(1, self.cfg.start_profile_step - 1),
            "profile_steps": self.profile_steps,
        }
        if self.seq_len:
            spec["seq_len"] = self.seq_len
        self._counter += 1
        result = self.scheduler.run_one(spec, tag=f"exp_{self._counter:03d}")
        if result.get("ok"):
            exp.step_time_s = result["step_time_s"]
            exp.throughput = result["throughput"]
        else:
            exp.error = result.get("error", "unknown failure")
            logger.warning(f"autotune candidate {overrides} failed: "
                           f"{exp.error}")
        return exp
