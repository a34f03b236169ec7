"""Device time of the optimizer per optimizer step: own time of the
``XLA Ops`` events inside the ``sl_train_step`` programs under the
``optimizer`` scope (``optimizer.update`` and ``optax.apply_updates``),
mean over the chips."""

import program_trace


def read(run):
    return program_trace.phase_ms(run, "opt")
