"""Device time of the expert layer's routing per optimizer step: own time
of the operations under the scope ``moe_route`` (router, top-k, sort, the
gathers into and out of the row buffer, the weighted sum)."""

import mixer_trace


def read(run):
    return mixer_trace.scope_ms(run, "moe_route")
