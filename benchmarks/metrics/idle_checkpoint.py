"""Idle time of the worst chip while the round loop waited for the last
save and handed over the next: the gaps of the busy union
(``device_idle``'s) whose midpoint the main thread spent in its
``sl/checkpoint`` span, in per cent of the traced window."""

import program_trace


def read(run):
    return program_trace.idle_share(run, ("checkpoint",))
