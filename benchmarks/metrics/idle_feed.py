"""Idle time of the worst chip while the host built or uploaded a batch:
the gaps of the busy union (``device_idle``'s) whose midpoint the main
thread spent in an ``sl/feed`` or ``sl/upload`` span, in per cent of the
traced window."""

import program_trace


def read(run):
    return program_trace.idle_share(run, ("feed", "upload"))
