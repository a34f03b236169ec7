"""Device time of the state-space mixer outside its scan per optimizer
step: own time of the operations under the scope ``ssm_mixer`` and not
under ``ssm_scan`` (``W_in``, the convolution and its ``silu``, the
softplus, the gated group norm, ``W_out``), in both passes."""

import ssm_trace


def read(run):
    return ssm_trace.scope_ms(run, ssm_trace.MIXER)
