"""How uneven the held experts' load is: the fullest held expert's pairs
over the mean, the largest of a step's blocks and microbatches
(``parallel/expert.py`` sows it, the round record's ``counters``
carries the mean over the round's steps), mean over the window's rounds."""

import mixer_trace


def read(run):
    return mixer_trace.counter_mean(run, "moe_load_max_over_mean")
