"""Device time of attention's output gate per optimizer step: own time of
the operations under the scope ``attn_gate`` (``g_proj``, the sigmoid, the
product with each head's result), in both passes; a part of
``attn_proj_ms``, inside which the scope is opened."""

import gate_trace


def read(run):
    return gate_trace.get(run)
