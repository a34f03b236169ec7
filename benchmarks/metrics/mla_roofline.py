"""The latent-attention kernels' share of their roofline: for every call
of ``slt_flash_fwd``, ``slt_flash_bwd_dq`` and ``slt_flash_bwd_dkv`` in the
window the least time the chip could take — the larger of the call's
operations over the bf16 peak and its bytes over the memory's, both from
shapes (``mla_trace.mla_flops``, ``mla_bytes``: scores over 192 and values
over 128 a seen pair, the one rotary key moved once) — summed, over the
kernels' own device time (``mixer_trace``'s reading)."""

import mla_trace


def read(run):
    got = mla_trace.get(run)
    return got["mla_roofline"] if got else None
