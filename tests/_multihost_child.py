"""Child process for the real two-process multi-host test.

Each process contributes 2 virtual CPU devices to ONE global (client=2,
stage=2) mesh joined via ``jax.distributed`` (gloo over loopback — the
same control surface a DCN deployment uses, SURVEY.md §5.8).  The child
runs the framework's own multi-host entry points end to end:

* ``ensure_initialized`` from the SLT_* environment contract;
* ``global_mesh`` spanning both processes;
* one compiled pipelined split train step over the global mesh (the
  ``stage`` hop stays process-local = "ICI"; the ``client`` axis spans
  processes = "DCN");
* the weighted FedAvg psum round barrier across processes.

Prints one line ``OK <loss> <fedavg_probe>`` on success; the parent
asserts both processes print identical values (the collectives really
ran globally) and that the fedavg probe matches the host-computed
weighted mean.
"""

import os
import sys


def main() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from split_learning_tpu.parallel.multihost import (
        ensure_initialized, global_mesh, local_process_info,
    )
    assert ensure_initialized() is True, "distributed init did not run"
    info = local_process_info()
    assert info["process_count"] == 2, info
    assert info["global_devices"] == 4, info

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from split_learning_tpu.parallel.pipeline import (
        PipelineModel, init_pipeline_variables, make_fedavg_step,
        make_train_step, stack_for_clients,
    )

    mesh = global_mesh({"client": -1, "stage": 2})
    assert dict(mesh.shape) == {"client": 2, "stage": 2}

    mb, seq, M = 2, 8, 2
    tiny = dict(hidden_size=16, num_heads=2, intermediate_size=32,
                vocab_size=64, max_position_embeddings=seq, n_block=2)
    struct = jax.ShapeDtypeStruct((mb, seq), jnp.int32)
    pipe = PipelineModel("BERT_AGNEWS", cuts=[2], example_input=struct,
                         num_microbatches=M, model_kwargs=tiny)
    variables = init_pipeline_variables(pipe, jax.random.key(0), struct)
    params = variables["params"]
    optimizer = optax.sgd(1e-2)

    def put(tree, spec):
        sh = NamedSharding(mesh, spec)
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(np.asarray(a), sh), tree)

    params_c = put(stack_for_clients(params, 2), P("client"))
    opt_c = put(stack_for_clients(optimizer.init(params), 2),
                P("client"))
    stats_c = put(stack_for_clients(variables.get("batch_stats", {}), 2),
                  P("client"))
    x = put(np.zeros((2, M, mb, seq), np.int32), P("client"))
    labels = put(np.zeros((2, M, mb), np.int32), P("client"))
    rng = put(np.stack([np.asarray(jax.random.key_data(
        jax.random.key(i))) for i in range(2)]), P("client"))
    rng = jax.tree_util.tree_map(
        jax.random.wrap_key_data, rng)

    step = make_train_step(pipe, optimizer, mesh)
    params_c, opt_c, stats_c, loss, _ = step(params_c, opt_c, stats_c, x,
                                          labels, rng)
    loss_h = np.asarray(jax.device_get(
        jax.jit(lambda l: l.mean(),
                out_shardings=NamedSharding(mesh, P()))(loss)))

    # FedAvg across the process-spanning client axis: column c holds
    # (c+1) everywhere; weights (1, 3) -> weighted mean 1.75 on BOTH
    # processes only if the psum really crossed them
    probe = put(np.stack([np.full((4,), 1.0, np.float32),
                          np.full((4,), 2.0, np.float32)]), P("client"))
    fedavg = make_fedavg_step(mesh)
    avg = fedavg({"w": probe}, jnp.asarray([1.0, 3.0]))["w"]
    avg_h = np.asarray(jax.device_get(
        jax.jit(lambda a: a[0, 0],
                out_shardings=NamedSharding(mesh, P()))(avg)))

    print(f"OK {float(loss_h):.6f} {float(avg_h):.6f}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
