"""Mean duration of the window's ``sl/feed`` spans: the host building one
step's batch in ``MeshContext._drive_columns`` — one ``np.empty`` of the
step's ``(C, M, mb, ...)`` arrays and each column's ``Epoch.fill`` into its
``M`` slots (``cifar_augment``'s index arithmetic and one ``np.take`` a
microbatch where the loader augments, a plain ``np.take`` where it does
not), each sample's bytes written once (the one-pass feed, PR 27)."""

import program_trace


def read(run):
    return program_trace.span_ms(run, "feed")
