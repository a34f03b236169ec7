"""Idle time of the worst chip that no span of the program explains: the
gaps of the busy union (``device_idle``'s) whose midpoint lies in no
``sl/*`` span of the main thread, in per cent of the traced window."""

import program_trace


def read(run):
    return program_trace.idle_share(run, (None,))
