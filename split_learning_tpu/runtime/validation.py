"""Server-side full-model validation.

Parity with ``/root/reference/src/val/get_val.py`` + ``src/val/VGG16.py:8-38``:
after aggregation the server reassembles the full model and runs the real
test set, logging loss/accuracy; a NaN or exploded loss marks the round
failed (``other/Vanilla_SL/src/Validation.py:55-59``), which the round loop
uses to skip checkpointing.

Here validation is one jitted eval step scanned over a static-shape test
loader — the same ``SplitModel`` with ``start_layer=0, end_layer=-1``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax

from split_learning_tpu.data import make_data_loader
from split_learning_tpu.models import build_model

_MODEL_DATASET = {
    # model registry key -> dataset provider name
    "VGG16_CIFAR10": "CIFAR10",
    "VGG16_CIFAR100": "CIFAR100",
    "VGG16_MNIST": "MNIST",
    "BERT_AGNEWS": "AGNEWS",
    "BERT_EMOTION": "EMOTION",
    "KWT_SPEECHCOMMANDS": "SPEECHCOMMANDS",
}


def dataset_for_model(model_key: str) -> str:
    if model_key in _MODEL_DATASET:
        return _MODEL_DATASET[model_key]
    # registry convention {MODEL}_{DATASET}
    return model_key.rsplit("_", 1)[-1]


# datasets whose providers accept a ``vocab`` kwarg (token data)
_TOKEN_DATASETS = {"TINYSTORIES", "AGNEWS", "EMOTION"}


def dataset_kwargs_for_model(model_key: str,
                             model_kwargs: dict | None) -> dict:
    """Dataset-provider kwargs implied by the model's build kwargs.

    A model with an overridden ``vocab_size`` must draw token ids inside
    its own embedding table: out-of-range ids NaN-fill in ``nn.Embed``
    (jnp.take fill mode), which surfaces as every round failing with
    "NaN detected".  Threading the vocab here makes tiny-model YAMLs
    valid end-to-end."""
    mk = model_kwargs or {}
    if (dataset_for_model(model_key) in _TOKEN_DATASETS
            and mk.get("vocab_size")):
        return {"vocab": int(mk["vocab_size"])}
    return {}


@dataclasses.dataclass
class ValResult:
    loss: float
    accuracy: float
    num_samples: int

    @property
    def ok(self) -> bool:
        """Round acceptance: reject NaN/exploded loss."""
        return bool(np.isfinite(self.loss) and abs(self.loss) < 1e5)


def make_eval_step(model, has_stats: bool):
    # the function's name is the program's name in a profiler trace
    @jax.jit
    def sl_eval_step(variables, x, labels):
        logits = model.apply(variables, x, train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).sum()
        correct = jnp.sum(jnp.argmax(logits, axis=-1) == labels)
        return loss, correct
    return sl_eval_step


def evaluate(model_key: str, variables: dict, batch_size: int = 200,
             max_batches: int | None = None,
             model_kwargs: dict | None = None,
             synthetic_size: int | None = None) -> ValResult:
    """Full-model test-set evaluation; ``variables`` holds host or device
    pytrees for params (+ batch_stats)."""
    model = build_model(model_key, **(model_kwargs or {}))
    loader = make_data_loader(
        dataset_for_model(model_key), batch_size, train=False,
        synthetic_size=synthetic_size,
        dataset_kwargs=dataset_kwargs_for_model(model_key, model_kwargs))
    step = make_eval_step(model, "batch_stats" in variables)
    total_loss = 0.0
    total_correct = 0
    n = 0
    for i, (x, labels) in enumerate(loader):
        if max_batches is not None and i >= max_batches:
            break
        loss, correct = step(variables, jnp.asarray(x),
                             jnp.asarray(labels))
        total_loss += float(loss)
        total_correct += int(correct)
        n += int(np.asarray(labels).size)   # token-level for LM labels
    return ValResult(loss=total_loss / max(n, 1),
                     accuracy=total_correct / max(n, 1), num_samples=n)
