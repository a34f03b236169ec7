"""Device time of the recomputed forward pass per optimizer step
(``jax.checkpoint``'s second forward, in the backward pass): own time of
the ``XLA Ops`` events inside the ``sl_train_step`` programs whose
``op_name`` holds a ``stage<s>`` or ``loss`` scope under
``rematted_computation``, mean over the chips; 0 where no stage is
rematerialized."""

import program_trace


def read(run):
    return program_trace.phase_ms(run, "remat")
