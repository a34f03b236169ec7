"""Multi-host mesh construction (single-host fallback path) and the
tracing utilities."""

import jax
import jax.numpy as jnp
import pytest

from split_learning_tpu.parallel.multihost import (
    HostTopology, ensure_initialized, global_mesh, local_process_info,
)
from split_learning_tpu.runtime.trace import StepTimer


def test_single_host_noop():
    assert ensure_initialized(HostTopology()) is False
    # JAX-standard env fallback populates all three fields
    import os
    os.environ["JAX_COORDINATOR_ADDRESS"] = "h:1"
    os.environ["JAX_NUM_PROCESSES"] = "4"
    os.environ["JAX_PROCESS_ID"] = "2"
    try:
        topo = HostTopology.from_env()
        assert topo == HostTopology("h:1", 4, 2)
    finally:
        for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                  "JAX_PROCESS_ID"):
            os.environ.pop(k)
    info = local_process_info()
    assert info["process_count"] == 1
    assert info["global_devices"] >= 8


def test_global_mesh_wildcard(eight_devices):
    mesh = global_mesh({"client": -1, "stage": 2})
    assert mesh.shape == {"client": 4, "stage": 2}
    mesh = global_mesh({"cluster": 2, "client": 2, "stage": -1})
    assert mesh.shape == {"cluster": 2, "client": 2, "stage": 2}


def test_global_mesh_errors(eight_devices):
    with pytest.raises(ValueError):
        global_mesh({"a": -1, "b": -1})
    with pytest.raises(ValueError):
        global_mesh({"a": 3, "b": -1})    # 8 % 3 != 0
    with pytest.raises(ValueError):
        global_mesh({"a": 2, "b": 2})     # 4 != 8


@pytest.mark.slow
def test_two_process_distributed_train_step_and_fedavg(tmp_path):
    """REAL multi-host: two processes join one ``jax.distributed``
    runtime (gloo over loopback — the same path a DCN deployment takes)
    and run the framework's compiled pipeline step plus the weighted
    FedAvg psum over one global (client=2, stage=2) mesh, the ``client``
    axis spanning the process boundary (tests/_multihost_child.py)."""
    import os
    import pathlib
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    child = pathlib.Path(__file__).with_name("_multihost_child.py")
    repo = str(child.parent.parent)

    def env(pid):
        e = dict(os.environ)
        e.update(SLT_COORDINATOR=f"127.0.0.1:{port}",
                 SLT_NUM_PROCESSES="2", SLT_PROCESS_ID=str(pid),
                 PYTHONPATH=repo + os.pathsep + e.get("PYTHONPATH", ""))
        # the child pins its own platform/device-count before jax
        # init, so it must not inherit the suite's XLA_FLAGS; and two
        # coordinated processes writing one cache directory at once is
        # a race this test has no use for — run them uncached
        e.pop("XLA_FLAGS", None)
        e.pop("JAX_COMPILATION_CACHE_DIR", None)
        return e

    procs = [subprocess.Popen([sys.executable, str(child)], env=env(i),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            assert p.returncode == 0, f"child failed:\n{err[-3000:]}"
            ok_lines = [ln for ln in out.splitlines()
                        if ln.startswith("OK ")]
            assert ok_lines, out
            outs.append(ok_lines[-1].split())
    finally:
        # a failed/hung first child must not leak the second one
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.communicate()

    # both processes observed the SAME global loss and fedavg result —
    # the collectives really crossed the process boundary
    assert outs[0] == outs[1], outs
    # weighted mean of columns (1.0, 2.0) with weights (1, 3) = 1.75
    assert float(outs[0][2]) == pytest.approx(1.75)


def test_step_timer_fences_device_work():
    t = StepTimer()
    x = jnp.ones((256, 256))
    with t.phase("matmul") as fence:
        y = jax.jit(lambda a: a @ a)(x)
        fence(y)   # block on work created INSIDE the block
    with t.phase("matmul") as fence:
        fence(jax.jit(lambda a: a @ a)(y))
    s = t.summary()
    assert s["matmul"]["count"] == 2
    assert s["matmul"]["total_s"] > 0
