"""Device time of the train step under no layer scope per optimizer step:
own time of the ``sl_train_step`` operations outside every scope of
``layer_trace.LAYERS`` and outside ``optimizer`` (the compiler's copies
with no ``op_name``, ``hop``, the tick loop, the joins between scopes)."""

import layer_trace


def read(run):
    return layer_trace.scope_ms(run, layer_trace.UNSCOPED)
