"""Device time of the on-mesh FedAvg per round: the ``XLA Modules``
events whose name holds ``sl_fedavg`` (``make_fedavg_step``: once for the
parameters, once for the batch statistics), mean over the chips.  Only
the device-resident path runs that program."""

import program_trace


def read(run):
    return (program_trace.get(run) or {}).get("fedavg_device_ms")
