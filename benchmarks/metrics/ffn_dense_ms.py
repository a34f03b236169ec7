"""Device time of the dense feed-forward per optimizer step: own time of
the operations under the scope ``ffn_dense`` (the SwiGLU's three products,
its ``silu`` and product), in both passes; neither the shared expert nor
any routed expert."""

import layer_trace


def read(run):
    return layer_trace.scope_ms(run, "ffn_dense")
