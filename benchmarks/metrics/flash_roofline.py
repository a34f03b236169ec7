"""The flash attention kernels' share of their roofline: for every call in
the window the least time the chip could take — the larger of the call's
operations over the bf16 peak and its bytes over the memory's, both from
shapes with only the keys a query may see counted
(``mixer_trace.flash_flops``, ``flash_bytes``) — summed, over the kernels'
own device time.  At these shapes the operations bound every call."""

import mixer_trace


def read(run):
    got = mixer_trace.get(run)
    return got["flash_roofline"] if got else None
