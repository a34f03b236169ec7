"""Device time of the vocabulary's two ends per optimizer step: own time
of the operations under the scopes ``embed`` (the gather and its
scatter-add), ``head`` (the untied head's product) and ``loss`` (the
cross-entropy over the logits), in both passes."""

import layer_trace


def read(run):
    return layer_trace.scope_ms(run, "embed", "head", "loss")
