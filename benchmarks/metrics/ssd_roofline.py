"""The state-space scan's share of its roofline: the least time the chip
could take for the scans of an optimizer step — the larger of their
operations over the bf16 peak and their bytes over the memory's, both from
shapes (``ssm_trace.ssd_flops``: the chunked algorithm's products at the
configuration's chunk, forward once and backward twice that, no
recomputation; ``ssd_bytes``: ``x``, ``B``, ``C``, ``dt``, ``y`` and their
gradients moved once) — over the own device time a step of EVERYTHING
under the scope ``ssm_scan``.  It reads the scope, never a kernel's name:
the same work whatever implements it."""

import ssm_trace


def read(run):
    got = ssm_trace.get(run)
    return got["ssd_roofline"] if got else None
