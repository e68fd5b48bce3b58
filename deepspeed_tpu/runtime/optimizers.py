"""Optimizer factory.

Capability analogue of the reference's optimizer zoo: FusedAdam/CPUAdam
(``csrc/adam``), FusedLamb (``csrc/lamb``), Lion (``csrc/lion``), Adagrad,
plus the engine's ``_configure_basic_optimizer`` dispatch
(``runtime/engine.py:1960``).  On TPU, "fused" is what XLA does to any
jitted elementwise update over the parameter pytree — the multi-tensor-apply
machinery is unnecessary.  The Pallas fused AdamW in
``ops/fused_optimizers.py`` is a standalone op (``fused_adamw_tree``, also in
the op registry): no config key selects it and the engine never calls it.

All optimizers are optax ``GradientTransformation``s so they compose with
clipping, loss scaling, and schedule injection.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Union

import optax

from .config import OptimizerConfig
from .config_utils import ConfigError

Schedule = Union[float, Callable[[Any], Any]]


def _adam_args(params: Dict[str, Any]) -> Dict[str, Any]:
    betas = params.get("betas", (0.9, 0.999))
    return dict(
        b1=betas[0],
        b2=betas[1],
        eps=params.get("eps", 1e-8),
    )


def create_optimizer(cfg: OptimizerConfig, learning_rate: Schedule,
                     weight_decay_mask: Optional[Any] = None,
                     wire_compression: bool = False) -> optax.GradientTransformation:
    """Build the base optimizer from config (reference: engine.py:1960).

    ``wire_compression``: the engine compresses gradients on the DP wire
    (``gradient_compression.enabled``) — 1-bit optimizers then skip their
    in-optimizer compression stage (it would compress twice) and keep only
    the frozen-variance update."""
    name = cfg.type.lower().replace("_", "")
    p = cfg.params
    wd = p.get("weight_decay", 0.0)

    if name in ("adam", "fusedadam", "cpuadam"):
        if p.get("adam_w_mode", True) and wd:
            return optax.adamw(learning_rate, weight_decay=wd,
                               mask=weight_decay_mask, **_adam_args(p))
        if wd:
            # classic L2 (reference FusedAdam adam_w_mode=False adds wd*param
            # to the gradient before the update)
            return optax.chain(
                optax.add_decayed_weights(wd, mask=weight_decay_mask),
                optax.adam(learning_rate, **_adam_args(p)))
        return optax.adam(learning_rate, **_adam_args(p))
    if name in ("adamw", "fusedadamw"):
        return optax.adamw(learning_rate, weight_decay=wd,
                           mask=weight_decay_mask, **_adam_args(p))
    if name in ("lamb", "fusedlamb"):
        return optax.lamb(learning_rate, weight_decay=wd,
                          mask=weight_decay_mask, **_adam_args(p))
    if name in ("lion", "fusedlion"):
        betas = p.get("betas", (0.9, 0.99))
        return optax.lion(learning_rate, b1=betas[0], b2=betas[1], weight_decay=wd)
    if name == "sgd":
        return optax.sgd(learning_rate, momentum=p.get("momentum", 0.0),
                         nesterov=p.get("nesterov", False))
    if name == "adagrad":
        return optax.adagrad(learning_rate, eps=p.get("eps", 1e-10))
    if name == "adafactor":
        return optax.adafactor(learning_rate)
    if name in ("muon",):  # reference: stage3.py:1537 distributed Muon
        try:
            return optax.contrib.muon(learning_rate)
        except AttributeError as e:
            raise ConfigError("muon requires a newer optax") from e
    if name in ("onebitadam", "zerooneadam", "onebitlamb"):
        # error-compensated compressed-gradient optimizers; the compression
        # wrapper lives in runtime/compressed_optimizer.py and wraps adam
        from .compressed_optimizer import onebit_adam

        return onebit_adam(learning_rate, weight_decay=wd,
                           freeze_step=p.get("freeze_step", 100),
                           compress_gradients=not wire_compression,
                           mask=weight_decay_mask, **_adam_args(p))
    raise ConfigError(f"unknown optimizer type {cfg.type!r}")


def default_weight_decay_mask(params: Any) -> Any:
    """Decay matrices, skip norms/biases/embeddings-scale (standard practice;
    mirrors the reference's weight-decay grouping users do in client code)."""
    import jax

    def one(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path).lower()
        if any(s in name for s in ("ln", "norm", "bias", "scale")):
            return False
        return getattr(leaf, "ndim", 0) >= 2

    return jax.tree_util.tree_map_with_path(one, params)
