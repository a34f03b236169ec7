"""Mean duration of the window's ``sl/checkpoint_write`` spans: the whole
of ``save_checkpoint`` on the round loop's worker thread (the pull to the
host and the orbax write), which overlaps the next round's steps."""

import program_trace


def read(run):
    return program_trace.span_ms(run, "checkpoint_write")
