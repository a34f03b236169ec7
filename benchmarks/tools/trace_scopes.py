#!/usr/bin/env python3
"""Record a small trace that holds what ``program_trace.py`` reads, and
print its reduction.  A jitted ``sl_train_step`` of two rematerialized
scoped stages under ``value_and_grad`` with an ``optimizer`` scope, and
an ``sl_fedavg`` (the tick loop under ``pipeline``), run under the program's own spans (``runtime/spans.py``:
``round`` > ``train`` > ``feed``/``upload``/``dispatch`` a step, ``sync``,
``fedavg``; ``validate``; ``checkpoint`` with ``checkpoint_write`` on a
worker thread) after a ``bench_clock_mark``.  The host sleeps 2 ms in
every ``feed`` and 3 ms in ``validate``, so the device's idle time has
known owners.  That is how ``tests/data/tiny_scopes_tpu.xplane.pb`` was
made (on a TPU v5e).

    python3 benchmarks/tools/trace_scopes.py --record chiprun_out/t.xplane.pb
    python3 benchmarks/tools/trace_scopes.py some.xplane.pb
"""

import json
import pathlib
import shutil
import sys
import tempfile
import threading
import time

BENCH = pathlib.Path(__file__).resolve().parent.parent
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
import program_trace  # noqa: E402
import trace_reduce   # noqa: E402

STEPS = 3
WIDTH = 512


def programs():
    import jax
    import jax.numpy as jnp

    def stage(name):
        @jax.checkpoint
        def apply(w, x):
            with jax.named_scope(name):
                return jnp.tanh(x @ w)
        return apply

    stages = [stage("stage1"), stage("stage2")]

    def loss_fn(ws, xs):
        def tick(acc, x):
            for apply, w in zip(stages, ws):
                x = apply(w, x)
            with jax.named_scope("loss"):
                return acc + jnp.mean(x.astype(jnp.float32) ** 2), None
        with jax.named_scope("pipeline"):
            return jax.lax.scan(tick, jnp.zeros(()), xs)[0]

    @jax.jit
    def sl_train_step(ws, xs):
        loss, grads = jax.value_and_grad(loss_fn)(ws, xs)
        with jax.named_scope("optimizer"):
            ws = [w - 0.01 * g.astype(w.dtype) for w, g in zip(ws, grads)]
        return ws, loss

    @jax.jit
    def sl_fedavg(ws):
        return [w * 0.5 + w * 0.5 for w in ws]

    ws = [jnp.full((WIDTH, WIDTH), 0.01, jnp.bfloat16) for _ in stages]
    return sl_train_step, sl_fedavg, ws


def record(dest: pathlib.Path) -> pathlib.Path:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from split_learning_tpu.runtime.spans import Laps, Tracer

    step, fedavg, ws = programs()
    batch = np.ones((2, WIDTH, WIDTH), np.float32)
    ws, loss = step(ws, jnp.asarray(batch, jnp.bfloat16))
    jax.block_until_ready(fedavg(ws))
    with tempfile.TemporaryDirectory() as tmp:
        tracer = Tracer("server", journal_dir=tmp)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # a small file: no Python calls
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_MARK):
            pass
        with tracer.span("round", round=0):
            with tracer.span("train", round=0), \
                    Laps(tracer, round=0) as laps:
                for _ in range(STEPS):
                    laps.lap("feed", always=False)
                    time.sleep(0.002)
                    laps.lap("upload", always=False)
                    x = jnp.asarray(batch, jnp.bfloat16)
                    laps.lap("dispatch", always=False)
                    ws, loss = step(ws, x)
                laps.lap("sync")
                np.asarray(loss)
                laps.lap("fedavg")
                ws = fedavg(ws)
            with tracer.span("validate", round=0):
                time.sleep(0.003)
            with tracer.span("checkpoint", round=0) as ck:
                def write():
                    with tracer.span("checkpoint_write", parent=ck.id,
                                     round=0):
                        np.asarray(ws[0])
                worker = threading.Thread(target=write)
                worker.start()
            worker.join()
        jax.block_until_ready(ws)
        jax.profiler.stop_trace()
        tracer.close()
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace_reduce.find_xplane(tmp), dest)
    print(f"recorded {dest} ({dest.stat().st_size} bytes)")
    return dest


def dump(path):
    trace = program_trace.read(path)
    for chip in trace["device"]:
        print(f"{chip['name']}: {len(chip['ops'])} operations, "
              f"{len(chip['modules'])} programs")
        for name in sorted({m[2] for m in chip["modules"]}):
            print(f"  program {name}")
        for tf_op in sorted({op[3] for op in chip["ops"]}):
            print(f"  {program_trace.classify(tf_op)} {tf_op!r}")
    for line, spans in trace["spans"].items():
        print(f"thread {line}: " + ", ".join(
            f"{name} {(e - s) / 1e6:.3f}ms" for s, e, name in sorted(spans)))
    window_s = 0.0
    if trace["mark"] and trace["spans"]:
        window_s = (max(e for spans in trace["spans"].values()
                        for _, e, _ in spans) - trace["mark"][1]) / 1e9
    print("program_trace: "
          + json.dumps(program_trace.reduce(trace, window_s, rounds=1)))


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--record":
        dump(record(pathlib.Path(args[1])))
    else:
        dump(args[0])
