"""Device time of the shared expert per optimizer step: own time of the
operations under the scope ``moe_shared`` (the SwiGLU every token takes
beside the routed experts), in both passes."""

import mla_trace


def read(run):
    return mla_trace.scope_ms(run, "moe_shared")
