"""Test model fixtures (reference: ``tests/unit/simple_model.py``)."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.runtime.engine import ModelSpec


def tiny_lm_spec(preset: str = "tiny", seed: int = 0, **overrides) -> ModelSpec:
    cfg = tfm.get_config(preset, **overrides)
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)

    def loss_fn(p, batch, rng):
        return tfm.loss_fn(p, batch, cfg)

    return ModelSpec(loss_fn=loss_fn, params=params,
                     param_axes=tfm.param_axes(cfg))


def copy_task_batch(rng: np.random.Generator, batch_size: int, seq_len: int,
                    vocab: int = 256):
    """A learnable synthetic task: repeat a short pattern; the LM can reduce
    loss quickly, so decreasing loss is a meaningful assertion."""
    pattern = rng.integers(1, vocab, size=(batch_size, 8))
    reps = int(np.ceil(seq_len / 8))
    tokens = np.tile(pattern, (1, reps))[:, :seq_len]
    return {"input_ids": tokens.astype(np.int32)}


def mlp_spec(din=8, dh=16, seed=0):
    """Tiny regression MLP (reference SimpleModel) for optimizer tests."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {
        "w1": jax.random.normal(k1, (din, dh)) * 0.1,
        "w2": jax.random.normal(k2, (dh, 1)) * 0.1,
    }

    def loss_fn(p, batch, rng):
        x, y = batch["x"], batch["y"]
        pred = jax.nn.relu(x @ p["w1"]) @ p["w2"]
        loss = jnp.mean((pred - y) ** 2)
        return loss, {"loss": loss, "accuracy": jnp.zeros(()),
                      "tokens": jnp.asarray(x.shape[0], jnp.float32)}

    axes = {"w1": ("embed", "mlp"), "w2": ("mlp", None)}
    return ModelSpec(loss_fn=loss_fn, params=params, param_axes=axes)
