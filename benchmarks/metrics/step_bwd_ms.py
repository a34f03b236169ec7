"""Device time of the backward pass proper per optimizer step: own time
of the ``XLA Ops`` events inside the ``sl_train_step`` programs whose
``op_name`` holds a ``stage<s>`` or ``loss`` scope under ``transpose(`` and
not under ``rematted_computation``, mean over the chips."""

import program_trace


def read(run):
    return program_trace.phase_ms(run, "bwd")
