"""Offline device/model profiler feeding the partition planner.

Parity surface (``/root/reference/profiling.py``): per-layer forward
execution times (``:22-44`` pre/post hooks, ``:68-73`` timed pass),
per-layer activation byte sizes (``:38``), device speed = batch /
total-time (``:77``), and a network bandwidth probe publishing 1–9 MB
payloads and timing them (``:80-109``); results written to
``profiling.json`` (``:111-121``) and embedded in REGISTER
(``client.py:52-59``).

TPU-native differences:

* activation sizes come from ``jax.eval_shape`` — exact, no execution;
* per-layer cost has two modes: ``"time"`` (jitted per-layer apply,
  wall-clock median — the reference's method, right for real hardware)
  and ``"flops"`` (XLA cost analysis of the compiled layer — instant and
  noise-free; the planner only needs *relative* costs, so this is the
  default for CI/virtual devices);
* the bandwidth probe times a publish+get round trip through a real
  :class:`~split_learning_tpu.runtime.bus.Transport` rather than a bare
  AMQP publish.

Output keys {exe_time, size_data, speed, network} are exactly what the
planner consumes (``runtime/plan.py`` → ``planner/partition.py``,
reference ``src/Server.py:115-117`` → ``src/Partition.py``).
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.models import build_model, shard_params


def _slice_vars(variables: dict, specs, i: int) -> dict:
    """Layer i's slice of every variable collection."""
    return {col: shard_params(tree, specs, i - 1, i)
            for col, tree in variables.items()}


def _boundary_structs(model_key: str, example: jax.ShapeDtypeStruct,
                      model_kwargs: dict | None):
    """Chained eval_shape: (boundary structs, single-layer models, full
    model)."""
    kw = dict(model_kwargs or {})
    full = build_model(model_key, **kw)
    var_shapes = jax.eval_shape(
        lambda: full.init(jax.random.key(0),
                          jnp.zeros(example.shape, example.dtype),
                          train=False))
    layer_models = [
        build_model(model_key, start_layer=i - 1, end_layer=i, **kw)
        for i in range(1, len(full.specs) + 1)
    ]
    bounds = [example]
    for i, m in enumerate(layer_models, start=1):
        out = jax.eval_shape(lambda v, x, m=m: m.apply(v, x, train=False),
                             _slice_vars(var_shapes, full.specs, i),
                             bounds[-1])
        bounds.append(out)
    return bounds, layer_models, full


def profile_model(model_key: str, batch_size: int = 32,
                  model_kwargs: dict | None = None,
                  example: jax.ShapeDtypeStruct | None = None,
                  method: str = "flops", warmup: int = 2,
                  repeats: int = 5, seed: int = 0) -> dict:
    """Per-layer cost + activation-size profile of a registered model.

    Returns ``{exe_time, size_data, speed, network}`` (network filled by
    :func:`profile_network`; 0.0 here).  ``exe_time`` is seconds in
    ``"time"`` mode and normalized FLOP-seconds-equivalent (flops / 1e12)
    in ``"flops"`` mode — the partition search is scale-invariant
    (``src/Partition.py:2-21`` compares only ratios).
    """
    kw = dict(model_kwargs or {})
    if example is None:
        from split_learning_tpu.data import make_data_loader
        from split_learning_tpu.runtime.validation import (
            dataset_for_model, dataset_kwargs_for_model,
        )
        ds = make_data_loader(dataset_for_model(model_key), 1, train=False,
                              synthetic_size=8,
                              dataset_kwargs=dataset_kwargs_for_model(
                                  model_key, model_kwargs))
        x0, _ = next(iter(ds))
        arr = np.asarray(x0)
        example = jax.ShapeDtypeStruct((batch_size,) + arr.shape[1:],
                                       arr.dtype)

    if method not in ("flops", "time"):
        raise ValueError(f"unknown method {method!r}")
    bounds, layer_models, full = _boundary_structs(model_key, example, kw)
    specs = full.specs
    # a boundary may be a pytree (e.g. BERT's (hidden, mask)): bytes sum
    # over leaves.  Float leaves are recorded at fp32 size whatever the
    # model's native dtype: the wire codec casts every float payload to
    # the configured wire dtype (fp32 default), so what crosses per hop
    # is float_elems x wire_itemsize — the planner applies the
    # wire-dtype ratio at plan time (runtime/plan.py) against this
    # fp32-equivalent record
    size_data = [
        sum(int(np.prod(leaf.shape))
            * (4 if jnp.issubdtype(leaf.dtype, jnp.floating)
               else np.dtype(leaf.dtype).itemsize)
            for leaf in jax.tree_util.tree_leaves(b))
        for b in bounds[1:]
    ]

    variables = full.init(jax.random.key(seed),
                          jnp.zeros(example.shape, example.dtype),
                          train=False)

    exe_time: list[float] = []
    for i, m in enumerate(layer_models, start=1):
        sub = _slice_vars(variables, specs, i)
        x_in = jnp.zeros(bounds[i - 1].shape, bounds[i - 1].dtype)
        fn = jax.jit(lambda v, x, m=m: m.apply(v, x, train=False))
        if method == "flops":
            cost = fn.lower(sub, x_in).compile().cost_analysis()
            flops = float((cost or {}).get("flops", 0.0))
            # param-free reshapes report 0 flops; floor at bytes-touched
            # so no layer is free (the planner divides by these)
            floor = size_data[i - 1] * 1e-3
            exe_time.append(max(flops, floor) / 1e12)
        else:
            out = fn(sub, x_in)
            jax.block_until_ready(out)
            for _ in range(warmup):
                jax.block_until_ready(fn(sub, x_in))
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(sub, x_in))
                ts.append(time.perf_counter() - t0)
            exe_time.append(float(np.median(ts)))

    # speed is ALWAYS wall-clock samples/sec of the full forward (the GMM
    # straggler selection compares speeds ACROSS devices — flop counts are
    # hardware-independent and would make selection a silent no-op)
    x_full = jnp.zeros(example.shape, example.dtype)
    full_fn = jax.jit(lambda v, x: full.apply(v, x, train=False))
    jax.block_until_ready(full_fn(variables, x_full))
    ts = []
    for _ in range(max(2, repeats // 2)):
        t0 = time.perf_counter()
        jax.block_until_ready(full_fn(variables, x_full))
        ts.append(time.perf_counter() - t0)
    speed = float(example.shape[0] / max(float(np.median(ts)), 1e-9))

    return {
        "exe_time": exe_time,
        "size_data": size_data,
        "speed": speed,
        "network": 0.0,
    }


def profile_network(transport, sizes_mb: Sequence[int] = range(1, 10),
                    repeats: int = 5,
                    queue: str = "bandwidth_probe") -> float:
    """Bytes/sec through the transport (``profiling.py:80-109``: 1–9 MB
    payloads, averaged)."""
    rates = []
    for mb in sizes_mb:
        payload = b"\x00" * (mb * 1_000_000)
        for _ in range(repeats):
            t0 = time.perf_counter()
            transport.publish(queue, payload)
            got = transport.get(queue, timeout=30.0)
            dt = time.perf_counter() - t0
            if got is None:
                # the in-flight payload would surface as a stale message
                # and corrupt the next sample's timing — drop it
                transport.purge([queue])
                continue
            rates.append(len(payload) * 2 / dt)   # round trip: 2x bytes
    return float(np.mean(rates)) if rates else 0.0


def write_profile(path: str, profile: dict) -> None:
    with open(path, "w") as f:
        json.dump(profile, f)


def main(argv=None):
    from split_learning_tpu.platform import (
        apply_compile_cache, apply_platform_env,
    )
    apply_platform_env()
    apply_compile_cache()
    ap = argparse.ArgumentParser(
        description="Profile a model + link for the partition planner "
                    "(reference profiling.py parity).")
    ap.add_argument("--config", default="config.yaml")
    ap.add_argument("--output", default="profiling.json")
    ap.add_argument("--method", choices=["flops", "time"], default="time")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--probe-network", action="store_true",
                    help="also measure transport bandwidth (needs broker)")
    args = ap.parse_args(argv)

    from split_learning_tpu.config import from_yaml
    cfg = from_yaml(args.config)
    prof = profile_model(
        cfg.model_key, batch_size=args.batch or cfg.learning.batch_size,
        model_kwargs=dict(cfg.model_kwargs or {}), method=args.method)
    if args.probe_network:
        from split_learning_tpu.runtime.bus import make_transport
        bus = make_transport(cfg.transport.kind, cfg.transport.host,
                             cfg.transport.port,
                             shards=cfg.broker.shards)
        prof["network"] = profile_network(bus)
        bus.close()
    write_profile(args.output, prof)
    print(json.dumps({"layers": len(prof["exe_time"]),
                      "speed": prof["speed"],
                      "network": prof["network"]}))


if __name__ == "__main__":
    main()
