"""Device time of the norms and residuals per optimizer step: own time of
the operations under the scope ``norm_residual`` (every RMSNorm of a
block and its residual add, the final RMSNorm), in both passes."""

import layer_trace


def read(run):
    return layer_trace.scope_ms(run, "norm_residual")
