"""Device time of the forward pass per optimizer step: own time of the
``XLA Ops`` events inside the ``sl_train_step`` programs whose ``op_name``
holds a ``stage<s>`` or ``loss`` scope and neither ``rematted_computation``
nor ``transpose(`` (``program_trace.classify``), mean over the chips."""

import program_trace


def read(run):
    return program_trace.phase_ms(run, "fwd")
