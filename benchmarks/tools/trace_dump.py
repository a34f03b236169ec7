#!/usr/bin/env python3
"""Print the structure of a profiler trace: planes, lines, event counts and
each line's heaviest event names.  With ``--record <file>`` first record a
tiny trace of this machine's device (a few jitted matrix products under a
``bench_clock_mark`` annotation) and keep its ``.xplane.pb`` there: that is
how ``tests/data/tiny_tpu.xplane.pb`` was made (on a TPU v5e).

    python3 benchmarks/tools/trace_dump.py --record chiprun_out/tiny.xplane.pb
    python3 benchmarks/tools/trace_dump.py some.xplane.pb
"""

import collections
import pathlib
import shutil
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import trace_reduce  # noqa: E402


def record(dest: pathlib.Path) -> pathlib.Path:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.tanh(a @ a))
    x = jnp.ones((512, 512), jnp.bfloat16)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_MARK):
            mark = time.perf_counter()
        for _ in range(4):
            x = f(x)
            x.block_until_ready()
            time.sleep(0.002)
        jax.profiler.stop_trace()
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace_reduce.find_xplane(tmp), dest)
    print(f"recorded {dest} ({dest.stat().st_size} bytes), clock mark at "
          f"{mark!r} s on the host clock")
    return dest


def dump(path):
    profile = trace_reduce.load(path)
    for plane in profile.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names = collections.Counter()
            n = 0
            for ev in line.events:
                names[ev.name] += ev.duration_ns
                n += 1
            top = ", ".join(f"{k[:50]}={v / 1e6:.3f}ms"
                            for k, v in names.most_common(4))
            print(f"  line {line.name!r}: {n} events; {top}")


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--record":
        dump(record(pathlib.Path(args[1])))
    else:
        dump(args[0])
