"""Device time of the compiled train step per optimizer step: the
``XLA Modules`` events of the program that ran once per step, averaged
over the chips."""


def read(run):
    t = run["trace"]
    if not t or t["step_device_s"] is None:
        return None
    return 1e3 * t["step_device_s"]
