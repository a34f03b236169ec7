"""Idle time of the worst chip during validation: the gaps of the busy
union (``device_idle``'s) whose midpoint the main thread spent in the
round loop's ``sl/validate`` span, in per cent of the traced window."""

import program_trace


def read(run):
    return program_trace.idle_share(run, ("validate",))
