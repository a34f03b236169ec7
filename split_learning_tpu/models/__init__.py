"""Split-aware model zoo.

Every model is expressed once as an ordered list of indexed
:class:`~split_learning_tpu.models.split.LayerSpec` entries; the generic
:class:`~split_learning_tpu.models.split.SplitModel` materializes any
contiguous slice of it — the TPU-native counterpart of the reference's
per-model ``Klass(start_layer, end_layer)`` pattern
(``/root/reference/src/model/VGG16_CIFAR10.py:4-9``) without one class per
model/shard combination.

A model family is one file that registers its builders.  The causal
language models share one (``decoder.py``): an architecture of that family
is a registered builder of some 25 lines that translates its
configuration's keys, plus, where its mixer or feed-forward is new, one
module and one entry of ``decoder.MIXERS`` / ``decoder.FEED_FORWARDS``.
"""

from split_learning_tpu.models.split import (
    LayerSpec, SplitModel, build_model, model_registry, register_model,
    shard_params, merge_shard_params, num_layers,
)
import split_learning_tpu.models.vgg  # noqa: F401  (registers VGG16_*)
import split_learning_tpu.models.bert  # noqa: F401  (registers BERT_*)
import split_learning_tpu.models.kwt  # noqa: F401  (registers KWT_*)
import split_learning_tpu.models.vit  # noqa: F401  (registers ViT_*)
import split_learning_tpu.models.mobilenet  # noqa: F401  (MobileNetv1_*)
import split_learning_tpu.models.resnet  # noqa: F401  (ResNet50_*)
import split_learning_tpu.models.decoder  # noqa: F401  (TinyLlama*, Mellum2_*, Moonlight_*, NemotronH_*, Laguna_*)

__all__ = [
    "LayerSpec", "SplitModel", "build_model", "model_registry",
    "register_model", "shard_params", "merge_shard_params", "num_layers",
]
