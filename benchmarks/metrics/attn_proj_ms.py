"""Device time of the attention projections per optimizer step: own time
of the operations under the scope ``attn_proj`` (``q_proj``, ``k_proj``,
``v_proj``, their reshapes and the RoPE, ``o_proj``), in both passes."""

import layer_trace


def read(run):
    return layer_trace.scope_ms(run, "attn_proj")
