"""Multi-host (DCN) initialization and mesh construction.

The reference spans machines by pointing every process at one RabbitMQ
broker (``/root/reference/README.md:144-171``); activations then cross
the data-center network per batch.  The TPU-native equivalent keeps the
per-batch hops on ICI and uses DCN only for what XLA routes across
slices: ``jax.distributed.initialize`` joins the hosts into one runtime,
and a single global mesh lays the (cluster, client, stage[, seq/model])
axes over all devices — axes that should ride ICI go innermost
(fastest-varying), the data-parallel ``client``/``cluster`` axes ride
DCN where collectives are rare (one FedAvg per round).

Single-host fallback: with no coordinator configured this is a no-op
and the mesh covers the local devices, so every entry point can call
``ensure_initialized()`` unconditionally.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np
from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class HostTopology:
    coordinator: str | None = None      # "host:port" of process 0
    num_processes: int = 1
    process_id: int = 0

    @classmethod
    def from_env(cls) -> "HostTopology":
        """SLT_COORDINATOR / SLT_NUM_PROCESSES / SLT_PROCESS_ID, falling
        back to the JAX standard variables."""
        def pick(a, b, default):
            return os.environ.get(a) or os.environ.get(b) or default
        return cls(
            coordinator=(os.environ.get("SLT_COORDINATOR")
                         or os.environ.get("JAX_COORDINATOR_ADDRESS")),
            num_processes=int(pick("SLT_NUM_PROCESSES",
                                   "JAX_NUM_PROCESSES", "1")),
            process_id=int(pick("SLT_PROCESS_ID", "JAX_PROCESS_ID",
                                "0")))


def ensure_initialized(topo: HostTopology | None = None) -> bool:
    """Join the multi-host runtime if configured; True when distributed.

    Safe to call repeatedly and on a single host (returns False, no-op).
    """
    topo = topo or HostTopology.from_env()
    if topo.coordinator is None or topo.num_processes <= 1:
        return False
    if jax.distributed.is_initialized():
        return True
    jax.distributed.initialize(
        coordinator_address=topo.coordinator,
        num_processes=topo.num_processes,
        process_id=topo.process_id)
    return True


def global_mesh(axis_sizes: dict[str, int] | None = None,
                devices=None) -> Mesh:
    """Mesh over all global devices with named axes.

    ``axis_sizes`` maps axis name -> size in declaration order; a single
    ``-1`` entry absorbs the remaining device count (like a reshape).
    Defaults to ``{"client": -1, "stage": 1}`` — pure data parallelism.
    Axis order is placement order: later axes vary fastest over the
    device list, so put the communication-heavy axis (``stage``, ``seq``,
    ``model``) LAST to keep its collectives on ICI neighbors.
    """
    devices = list(devices if devices is not None else jax.devices())
    axis_sizes = dict(axis_sizes or {"client": -1, "stage": 1})
    n = len(devices)
    known = 1
    wild = None
    for name, size in axis_sizes.items():
        if size == -1:
            if wild is not None:
                raise ValueError("only one axis may be -1")
            wild = name
        else:
            known *= size
    if wild is not None:
        if n % known:
            raise ValueError(
                f"{n} devices not divisible by fixed axes {axis_sizes}")
        axis_sizes[wild] = n // known
        known *= axis_sizes[wild]
    if known != n:
        raise ValueError(
            f"axis sizes {axis_sizes} need {known} devices, have {n}")
    shape = tuple(axis_sizes.values())
    return Mesh(np.array(devices).reshape(shape),
                tuple(axis_sizes.keys()))


def local_process_info() -> dict:
    """Process/device layout facts for logs and the planner."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
