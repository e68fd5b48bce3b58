"""``transformers.Trainer``-compatible drop-in over the TPU engine.

Capability analogue of the reference's HF-Trainer integration contract
(``deepspeed/__init__.py:93 initialize`` consumed by
``transformers.integrations.deepspeed``): an unmodified HF-style training
script —

.. code-block:: python

    trainer = Trainer(model=model, args=TrainingArguments(...),
                      train_dataset=ds, data_collator=collator)
    trainer.train()
    trainer.save_model(out_dir)

— runs on the TPU mesh with no code changes.  The model may be a
``transformers.PreTrainedModel`` of any supported architecture (converted
through ``models/hf_integration.py``) or a native :class:`ModelSpec`;
``args`` may be a real ``TrainingArguments`` or any object/dict with the
same field names (``hf_args.py`` does the mapping).  ``args.deepspeed``
(dict or JSON path) is honored the reference way: its ``"auto"`` fields are
resolved from the TrainingArguments before the engine sees it.

HF semantics preserved: per-device batch size × replicas × accumulation =
global batch; ``labels`` with ``-100`` masking (HF models shift internally,
so the shim shifts here); linear/cosine/constant schedules with warmup;
``logging_steps``/``save_steps``; ``log_history`` on ``trainer.state``.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .hf_args import config_from_training_args, resolve_auto_config


def _get(args: Any, name: str, default=None):
    if isinstance(args, dict):
        return args.get(name, default)
    val = getattr(args, name, default)
    return default if val is None else val


def _to_numpy(x) -> np.ndarray:
    if hasattr(x, "detach"):  # torch tensor
        x = x.detach().cpu().numpy()
    return np.asarray(x)


@dataclasses.dataclass
class TrainOutput:
    """Shape-compatible with ``transformers.trainer_utils.TrainOutput``."""
    global_step: int
    training_loss: float
    metrics: Dict[str, float]


@dataclasses.dataclass
class TrainerState:
    """The ``trainer.state`` fields scripts actually read."""
    global_step: int = 0
    epoch: float = 0.0
    max_steps: int = 0
    log_history: List[Dict[str, float]] = dataclasses.field(
        default_factory=list)


class Trainer:
    """Drop-in for ``transformers.Trainer`` backed by ``TrainingEngine``."""

    def __init__(self, model: Any = None, args: Any = None,
                 data_collator: Optional[Callable] = None,
                 train_dataset: Any = None, eval_dataset: Any = None,
                 processing_class: Any = None, tokenizer: Any = None,
                 compute_metrics: Optional[Callable] = None, **_unused):
        if model is None:
            raise ValueError("Trainer requires model=")
        self.args = args if args is not None else {}
        self.data_collator = data_collator
        self.train_dataset = train_dataset
        self.eval_dataset = eval_dataset
        self.processing_class = processing_class or tokenizer
        if compute_metrics is not None:
            # HF's contract hands compute_metrics an EvalPrediction with the
            # full logits; this engine never materializes them (tiled loss) —
            # fail at construction, before any training/eval is paid for
            raise NotImplementedError(
                "compute_metrics needs materialized per-sample predictions, "
                "which the TPU engine does not surface; compute metrics from "
                "eval_loss or run a separate prediction pass")
        self.compute_metrics = compute_metrics
        self.state = TrainerState()

        self._hf_cfg = None  # TransformerConfig when model came from HF
        self._hf_model_type = None
        self._hf_config = None
        self._is_encoder = False
        spec = self._build_spec(model)
        config = self._build_config()
        import deepspeed_tpu

        self.engine, self.optimizer, _, self.lr_scheduler = \
            deepspeed_tpu.initialize(model=spec, config=config)

    # -- model/config assembly ------------------------------------------
    def _build_spec(self, model):
        from ..runtime.engine import ModelSpec

        if isinstance(model, ModelSpec):
            return model
        # a transformers PreTrainedModel (or (state_dict, config) pair)
        from ..models import encoder as enc
        from ..models import transformer as tfm
        from ..models.hf_integration import load_hf_model

        cfg, params = load_hf_model(model)
        self._hf_cfg = cfg
        self._hf_config = getattr(model, "config", None)
        if self._hf_config is not None:
            self._hf_model_type = getattr(self._hf_config, "model_type",
                                          "llama")

        from ..models import t5 as t5m

        if isinstance(cfg, t5m.T5ModelConfig):
            # seq2seq family: labels pass through unshifted (t5.loss_fn does
            # the decoder-input shift_right internally, HF-style)
            self._is_encoder = True

            def t5_loss(p, batch, rng):
                return t5m.loss_fn(p, batch, cfg)

            return ModelSpec(loss_fn=t5_loss, params=params,
                             param_axes=t5m.param_axes(cfg))

        if isinstance(cfg, enc.EncoderConfig):
            # encoder family (BERT): MLM objective with HF's unshifted
            # -100-masked labels — no causal shift applies
            if "mlm" not in params:
                raise ValueError(
                    "encoder model has no MLM head (pass BertForMaskedLM, "
                    "not a bare BertModel) — the Trainer trains encoders "
                    "with the masked-LM objective")
            self._is_encoder = True

            def enc_loss(p, batch, rng):
                return enc.mlm_loss_fn(p, batch, cfg)

            return ModelSpec(loss_fn=enc_loss, params=params,
                             param_axes=enc.param_axes(cfg, params=params))

        def loss_fn(p, batch, rng):
            return tfm.loss_fn(p, batch, cfg)

        return ModelSpec(loss_fn=loss_fn, params=params,
                         param_axes=tfm.param_axes(cfg))

    def _build_config(self) -> Dict[str, Any]:
        ds = _get(self.args, "deepspeed") or _get(self.args, "hf_deepspeed_config")
        total = self._planned_steps()
        if ds:
            if isinstance(ds, str):
                import json

                with open(ds) as f:
                    ds = json.load(f)
            return resolve_auto_config(ds, self.args, total_steps=total)
        return config_from_training_args(self.args, total_steps=total)

    def _planned_steps(self) -> int:
        max_steps = int(_get(self.args, "max_steps", 0) or 0)
        if max_steps > 0:
            return max_steps
        n = self._dataset_len(self.train_dataset)
        if n is None:
            return 10_000
        epochs = float(_get(self.args, "num_train_epochs", 3.0))
        per_dev = int(_get(self.args, "per_device_train_batch_size", 8))
        gas = int(_get(self.args, "gradient_accumulation_steps", 1))
        # replica count is only known post-engine; planning uses 1 replica
        # like single-process HF (the schedule length, not correctness)
        return max(1, int(epochs * math.ceil(n / max(per_dev * gas, 1))))

    @staticmethod
    def _dataset_len(ds) -> Optional[int]:
        try:
            return len(ds)
        except TypeError:
            return None

    # -- batching --------------------------------------------------------
    def _collate(self, examples: List[Any]) -> Dict[str, np.ndarray]:
        if self.data_collator is not None:
            batch = self.data_collator(examples)
            batch = {k: _to_numpy(v) for k, v in dict(batch).items()}
        else:
            keys = examples[0].keys()
            batch = {k: np.stack([_to_numpy(e[k]) for e in examples])
                     for k in keys}
        return self._hf_to_native(batch)

    def _hf_to_native(self, batch: Dict[str, np.ndarray]
                      ) -> Dict[str, np.ndarray]:
        """HF → native label semantics.  HF causal-LM models receive
        UNSHIFTED labels (ignore index −100) and shift internally; the
        native ``loss_fn`` expects pre-shifted labels, so the shift and the
        −100 mask happen here.  Encoder (MLM) batches pass through — their
        labels are positionally aligned and ``mlm_loss_fn`` consumes the
        −100 mask directly."""
        batch = dict(batch)
        batch["input_ids"] = np.asarray(batch["input_ids"], np.int32)
        if self._is_encoder:
            if "labels" in batch:
                batch["labels"] = np.asarray(batch["labels"], np.int32)
            return batch
        batch.pop("attention_mask", None)  # dense causal path (right-padded)
        ids = batch["input_ids"]
        labels = batch.pop("labels", None)
        if labels is not None:
            labels = np.asarray(labels)
            shifted = np.concatenate(
                [labels[:, 1:], np.full_like(labels[:, :1], -100)], axis=1)
            mask = (shifted != -100).astype(np.float32)
            batch["labels"] = np.where(shifted == -100, 0, shifted).astype(
                np.int32)
            prior = batch.pop("loss_mask", None)
            batch["loss_mask"] = mask if prior is None else mask * prior
        return batch

    def _global_batches(self, dataset, epochs: float, seed: int):
        """Yield global batches of ``engine.train_batch_size`` examples,
        reshuffling per epoch (HF's per-epoch sampler seed)."""
        n = self._dataset_len(dataset)
        if n is None:
            raise ValueError("train_dataset must be sized (len())")
        tb = self.engine.train_batch_size
        if n < tb:
            raise ValueError(
                f"train_dataset has {n} examples but one global batch needs "
                f"{tb} (per_device_batch x replicas x accumulation) — an "
                f"epoch would yield zero steps")
        epoch = 0
        while epochs <= 0 or epoch < math.ceil(epochs):
            order = np.random.default_rng(seed + epoch).permutation(n)
            for lo in range(0, n - tb + 1, tb):
                batch = [dataset[int(i)] for i in order[lo:lo + tb]]
                yield epoch + lo / max(n, 1), self._collate(batch)
            epoch += 1

    # -- the Trainer surface --------------------------------------------
    def train(self, resume_from_checkpoint: Any = None) -> TrainOutput:
        args = self.args
        if resume_from_checkpoint:
            load_dir = (resume_from_checkpoint
                        if isinstance(resume_from_checkpoint, str)
                        else _get(args, "output_dir", "."))
            self.engine.load_checkpoint(load_dir)
            self.state.global_step = self.engine.get_global_step()

        max_steps = int(_get(args, "max_steps", 0) or 0)
        epochs = float(_get(args, "num_train_epochs", 3.0))
        if max_steps > 0:
            epochs = 0  # step-bounded: iterate until max_steps
        logging_steps = int(_get(args, "logging_steps", 500) or 500)
        save_steps = int(_get(args, "save_steps", 0) or 0)
        # transformers stores save_strategy as an IntervalStrategy enum whose
        # str() is "IntervalStrategy.STEPS" — normalize like hf_args does
        save_strategy = str(_get(args, "save_strategy", "no") or "no") \
            .split(".")[-1].lower()
        output_dir = _get(args, "output_dir", None)
        seed = int(_get(args, "seed", 42))

        self.state.max_steps = max_steps or self._planned_steps()
        loss_sum, loss_n = 0.0, 0
        for epoch_f, batch in self._global_batches(
                self.train_dataset, epochs, seed):
            if max_steps and self.state.global_step >= max_steps:
                break
            metrics = self.engine.train_batch(batch)
            loss = float(metrics["loss"])
            loss_sum, loss_n = loss_sum + loss, loss_n + 1
            self.state.global_step = self.engine.get_global_step()
            self.state.epoch = epoch_f
            if self.state.global_step % logging_steps == 0:
                self.log({"loss": loss, "learning_rate": self.engine.get_lr(),
                          "epoch": round(epoch_f, 4)})
            if (save_strategy == "steps" and save_steps and output_dir
                    and self.state.global_step % save_steps == 0):
                self.save_state()
            if max_steps == 0 and self.state.global_step >= self.state.max_steps:
                break
        train_loss = loss_sum / max(loss_n, 1)
        metrics = {"train_loss": train_loss,
                   "train_steps": self.state.global_step}
        self.log(metrics)
        return TrainOutput(self.state.global_step, train_loss, metrics)

    def evaluate(self, eval_dataset: Any = None,
                 metric_key_prefix: str = "eval") -> Dict[str, float]:
        ds = eval_dataset if eval_dataset is not None else self.eval_dataset
        if ds is None:
            raise ValueError("no eval_dataset")
        n = self._dataset_len(ds)
        tb = self.engine.train_batch_size
        if n is None or n < tb:
            raise ValueError(
                f"eval_dataset has {n} examples but one global batch needs "
                f"{tb} — zero eval batches would report a NaN loss")
        losses = []
        for lo in range(0, n - tb + 1, tb):
            batch = self._collate([ds[i] for i in range(lo, lo + tb)])
            losses.append(self.engine.eval_batch(batch)["loss"])
        out = {f"{metric_key_prefix}_loss": float(np.mean(losses))}
        self.log(out)
        return out

    def log(self, entry: Dict[str, float]) -> None:
        entry = dict(entry)
        entry["step"] = self.state.global_step
        self.state.log_history.append(entry)

    def save_state(self) -> None:
        """Engine checkpoint into ``args.output_dir`` (resume granularity)."""
        out = _get(self.args, "output_dir", None)
        if out:
            self.engine.save_checkpoint(out)

    def save_model(self, output_dir: Optional[str] = None) -> None:
        """Export weights.  HF-born models export back to their HF state
        dict (safetensors); native specs save an engine checkpoint."""
        out = output_dir or _get(self.args, "output_dir", ".")
        os.makedirs(out, exist_ok=True)
        if self._hf_cfg is not None:
            import jax

            from ..models.hf_integration import params_to_hf

            sd = params_to_hf(jax.device_get(self.engine.state.params),
                              self._hf_cfg,
                              model_type=self._hf_model_type or "llama",
                              hf_config=self._hf_config)
            from safetensors.numpy import save_file

            save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
                      os.path.join(out, "model.safetensors"))
        else:
            self.engine.save_checkpoint(out)
