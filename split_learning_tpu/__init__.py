"""TPU-native split-learning framework.

A ground-up JAX/XLA re-design of the capabilities of filrg/split_learning
(see SURVEY.md): layer-indexed model partitioning across pipeline stages,
pipelined activation/gradient exchange over ICI via collective permutes,
weighted FedAvg aggregation with a cluster hierarchy, and a profile-driven
planner (KMeans clustering, GMM device selection, max-min throughput cut
search) that emits a ``jax.sharding.Mesh`` assignment instead of a queue
topology.
"""

__version__ = "0.1.0"

from split_learning_tpu.planner import (  # noqa: F401
    partition,
    auto_threshold,
    kmeans_cluster,
    synthesize_label_counts,
)
from split_learning_tpu.ops.fedavg import fedavg_trees  # noqa: F401
