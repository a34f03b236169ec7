"""Device time of the state-space scan per optimizer step: own time of
every operation under the scope ``ssm_scan`` (whatever implements the
scan), in both passes."""

import ssm_trace


def read(run):
    return ssm_trace.scope_ms(run, ssm_trace.SCAN)
