"""1 - union of device-operation intervals over the whole traced window
(round boundaries and host gaps included), on the worst chip: the same
reduction that fills the result line's ``device.busy_s``."""


def read(run):
    t = run["trace"]
    return 100.0 * t["idle_worst"] if t else None
