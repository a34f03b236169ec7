"""Mean duration of the window's ``sl/upload`` spans: the two
``jnp.asarray`` calls that hand one step's batch to the device in
``MeshContext._drive_columns``."""

import program_trace


def read(run):
    return program_trace.span_ms(run, "upload")
