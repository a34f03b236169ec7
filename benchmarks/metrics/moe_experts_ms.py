"""Device time of the held experts' grouped matrix products per optimizer
step: own time of the operations under the scope ``moe_experts``."""

import mixer_trace


def read(run):
    return mixer_trace.scope_ms(run, "moe_experts")
