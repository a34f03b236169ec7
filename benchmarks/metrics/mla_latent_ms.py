"""Device time of latent attention outside its kernels per optimizer step:
own time of the operations under the scope ``mla_latent`` (the query and
latent projections, the latent's norm, the expansion to keys and values,
the rotary part, the shaping of the kernels' operands, ``o_proj``), in
both passes."""

import mla_trace


def read(run):
    return mla_trace.scope_ms(run, "mla_latent")
