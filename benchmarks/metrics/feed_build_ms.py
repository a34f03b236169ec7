"""Mean duration of the window's ``sl/feed`` spans: the host building one
step's batch in ``MeshContext._drive_columns`` (the loaders' ``next``,
``cifar_augment``, ``np.asarray``, ``np.stack``)."""

import program_trace


def read(run):
    return program_trace.span_ms(run, "feed")
