#!/usr/bin/env python3
"""Run one cell of the benchmark: whole split-learning rounds through
``split_learning_tpu.run.run_local``, on the machine this is started on.

    python3 benchmarks/run_cell.py --workload <config>.<traffic> \
        --seed N --seconds S --trace 0|1

The cell's files are found by name: ``configs/<config>.yaml`` (sizes) with
``configs/<config>.py`` (plain reference) beside it, ``traffic/<traffic>.json``
and, for every per-layer metric ``BENCHMARK.json`` lists for the cell,
``metrics/<name>.py`` with one ``read(run)``.

Set-up (all of it inside ``setup_s``): the data set and the weights are made
from ``--seed`` by the benchmark and handed to the program as its dataset
directory and a round-0 checkpoint; ``run_local`` then runs rounds 0 and 1
(round 0 compiles; the tap on the compiled step reads what ``correct``
compares).  A second ``run_local`` resumes from the program's own checkpoint
with the same compiled step: one lead-in round, then the window — whole
rounds until the first round boundary ``--seconds`` past its opening (the
program's own ``limited-time`` budget ends the job there; a traced window
is the mix's ``trace_rounds``).  The window runs from the lead-in round's
end to the last round's end.

After the window: the peak memory is read, the last checkpoint is read
back, the program's state is dropped, and only then the reference follows
the tapped steps (``compare.py``).

With ``JAX_PLATFORMS=cpu`` in the environment this is a rehearsal: toy
sizes from the configuration's ``toy`` block, ``"platform": "cpu"``, and no
number under a device metric's name.  Without an accelerator and without
that variable it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import copy              # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import pathlib           # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARM_ROUNDS = 2      # rounds 0 (compiles) and 1 (times a warm round)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else copy.deepcopy(v)
    return out


def load_cell(workload: str, rehearsal: bool) -> dict:
    """Everything the cell's data files say, found by name."""
    import yaml
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = yaml.safe_load(
        (HERE / "configs" / f"{cell['config']}.yaml").read_text())
    if rehearsal:
        conf = merge(conf, conf.get("toy"))
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])
    return {
        "cell": cell, "conf": conf, "traffic": traffic,
        "reference": load_module(HERE / "configs" / f"{cell['config']}.py"),
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


class CompileLog:
    """Every backend compilation of the process, as jax reports it
    (copied from chip_smoke.py)."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


class StepTap:
    """Sits where the program keeps its compiled train step and passes
    every call through.  It times each call (the benchmark's own spans
    around the compiled-step layer) and, while armed, copies to the host
    what ``correct`` compares: the rows, labels and key of the first
    ``record_steps`` steps, each of those steps' loss, the optimizer
    state after the first, the parameters after the last, and, while
    ``finals_on``, each column chunk's final tree."""

    def __init__(self, step, record_steps: int = 0):
        self._step = step
        self.calls = []            # (t_in, t_out, first call of a chunk)
        self.record_steps = record_steps
        self.finals_on = False
        self.feed, self.losses = [], []
        self.opt1 = self.p3 = None
        self.finals = []
        self._last_out = self._last_leaf = None

    def __getattr__(self, name):
        return getattr(self._step, name)

    def rearm(self, record_steps: int):
        """Forget what was read and record the next ``record_steps``."""
        self.__init__(self._step, record_steps)

    def flush_final(self):
        """A chunk (or the round) has ended: keep its final tree."""
        if self._last_out is not None:
            self.finals.append(to_host(self._last_out))
        self._last_out = self._last_leaf = None

    def __call__(self, params, opt, stats, x, labels, rngs):
        import jax
        import numpy as np
        new_chunk = jax.tree_util.tree_leaves(params)[0] \
            is not self._last_leaf
        if new_chunk:
            self.flush_final()
        recording = self.record_steps > 0
        if recording:
            self.feed.append((np.asarray(x), np.asarray(labels),
                              np.asarray(jax.random.key_data(rngs))))
        t_in = time.perf_counter()
        out = self._step(params, opt, stats, x, labels, rngs)
        self.calls.append((t_in, time.perf_counter(), new_chunk))
        if recording:
            self.record_steps -= 1
            self.losses.append(np.asarray(out[3]))
            if len(self.losses) == 1:
                self.opt1 = to_host(out[1])
            if self.record_steps == 0:
                self.p3 = to_host(out[0])
        # one small leaf tells the next call whether the program fed this
        # step's output back; the whole tree is held only while asked for
        self._last_leaf = jax.tree_util.tree_leaves(out[0])[0]
        self._last_out = out[0] if self.finals_on else None
        return out


def to_host(tree):
    import jax
    import numpy as np
    return jax.tree_util.tree_map(np.asarray, tree)


def install_tap(taps: list, record_steps: int):
    """Wrap the program's step factory so that the step it compiles, and
    keeps in its process-wide cache for every later round, is tapped.
    The first step compiled records its first ``record_steps`` calls."""
    from split_learning_tpu.runtime import context
    make = getattr(context.make_train_step, "_bench_original",
                   context.make_train_step)

    def tapped(*a, **kw):
        taps.append(StepTap(make(*a, **kw),
                            0 if taps else record_steps))
        return taps[-1]
    tapped._bench_original = make
    context.make_train_step = tapped


class WindowBudget:
    """Stands where the program keeps its wall-clock budget
    (``limited-time``).  After every round the loop asks whether the time
    it has run exceeds the budget; this answers by the window's own clock:
    yes, once ``seconds`` have passed since the window opened."""

    def __init__(self, state: dict, seconds: float):
        self.state, self.seconds = state, seconds

    def __bool__(self):
        return True

    def __lt__(self, elapsed):      # the loop's ``elapsed > budget``
        t_open = self.state.get("t_open")
        return t_open is not None \
            and time.perf_counter() - t_open >= self.seconds

    def __str__(self):
        return f"{self.seconds} (counted from the window's opening)"


def make_probe(base):
    class RoundProbe(base):
        """The loop journals one kind=round record at each round's end;
        that is where round boundaries are read (chip_smoke.py
        RoundProbe) and where the window opens and closes."""
        on_round = None

        def metric(self, **fields):
            if fields.get("kind", "round") == "round" and self.on_round:
                self.on_round(dict(fields), time.perf_counter())
            super().metric(**fields)
    return RoundProbe


def write_checkpoint(directory: pathlib.Path, model_key: str, params,
                     stats, round_idx: int):
    """A checkpoint in the program's on-disk layout (orbax tree under a
    slot directory, published by a symlink), written by the benchmark."""
    import numpy as np
    import orbax.checkpoint as ocp
    directory.mkdir(parents=True, exist_ok=True)
    slot = directory / f".{model_key}.data0"
    ocp.PyTreeCheckpointer().save(
        slot.resolve(), {"params": to_host(params),
                         "batch_stats": to_host(stats),
                         "meta": {"round_idx": np.int64(round_idx)}},
        force=True)
    os.symlink(slot.name, directory / model_key)


def read_checkpoint(directory: pathlib.Path, model_key: str) -> dict:
    import orbax.checkpoint as ocp
    return ocp.PyTreeCheckpointer().restore(
        (directory / model_key).resolve())


def fail_line(why: str, code: int):
    print(f"run_cell: {why}", file=sys.stderr)
    raise SystemExit(code)


def steady_allocator():
    """Fix glibc malloc's thresholds for this process.  The program's
    feed builds and frees some 70 MB of numpy arrays before every step;
    with glibc's self-adjusting thresholds a process falls, at random,
    into handing each back to the kernel and faulting it in again (58 ms
    a step of ``vgg16_c7`` on the chip's host) or into reusing it (35 ms),
    and stays there: runs of one code differed by 15 % (PERF.md section
    6).  Fixed thresholds keep every process on the reusing side."""
    import ctypes
    mallopt = ctypes.CDLL("libc.so.6").mallopt
    mallopt.argtypes, mallopt.restype = [ctypes.c_int, ctypes.c_int], \
        ctypes.c_int
    for option, value in ((-3, 32 << 20),       # M_MMAP_THRESHOLD
                          (-1, 2 ** 31 - 1),    # M_TRIM_THRESHOLD
                          (-2, 64 << 20)):      # M_TOP_PAD
        if mallopt(option, value) != 1:
            fail_line(f"mallopt({option}, {value}) was refused", 3)


class Env:
    """What one process sets up once: the cell's data files, the device,
    the compile cache, the compile listener and the tap."""

    def __init__(self, workload: str):
        self.rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
        self.spec = load_cell(workload, self.rehearsal)
        self.workload = workload
        self.chips = int(self.spec["cell"]["chips"])
        if self.rehearsal and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
        for path in (str(ROOT), str(HERE)):
            if path not in sys.path:
                sys.path.insert(0, path)
        self.work = HERE / "_work" / workload
        os.environ["SLT_DATA_DIR"] = str(self.work / "data")

        import jax
        from split_learning_tpu.platform import (
            apply_compile_cache, apply_platform_env,
        )
        apply_platform_env()
        apply_compile_cache()
        devices = jax.devices()
        self.platform = devices[0].platform
        if self.platform == "cpu" and not self.rehearsal:
            fail_line("jax found no accelerator (set JAX_PLATFORMS=cpu "
                      "for a toy rehearsal)", 3)
        if len(devices) < self.chips:
            fail_line(f"the cell asks for {self.chips} chip(s), jax has "
                      f"{len(devices)}", 3)
        self.devices = devices[:self.chips]
        self.kind = self.devices[0].device_kind
        self.peaks = json.loads((HERE / "peaks.json").read_text())
        if self.platform != "cpu" and self.kind not in self.peaks:
            fail_line(f"device kind {self.kind!r} is not in peaks.json", 3)
        self.compiles = CompileLog()
        self.taps: list = []
        import compare
        install_tap(self.taps, compare.STEPS)


class Job:
    """One seed's job in one Env: data, weights and checkpoint from the
    seed; the warm rounds; the window; the comparison."""

    def __init__(self, env: Env, seed: int):
        import compare
        import traffic as traffic_gen
        from split_learning_tpu.config import from_dict
        self.env, self.seed = env, seed
        conf, traffic = env.spec["conf"], env.spec["traffic"]
        self.conf, self.traffic = conf, traffic
        self.ref = env.spec["reference"]
        self.program = merge(conf["program"], traffic.get("program"))
        self.learning = self.program["learning"]
        self.n_stage1 = int(self.program["clients"][0])
        step_batch = (self.learning["batch-size"]
                      * self.learning["control-count"])
        self.sizes = traffic_gen.job_sizes(traffic, conf["dataset"],
                                           self.n_stage1, step_batch)
        self.model_kwargs = self.program.get("model-kwargs") or {}
        shutil.rmtree(env.work, ignore_errors=True)
        env.work.mkdir(parents=True)
        self.val_rows, self.val_labels = traffic_gen.make_dataset(
            conf["dataset"]["kind"], env.work / "data", seed,
            conf["dataset"]["train"], conf["dataset"]["val"],
            vocab=self.model_kwargs.get("vocab_size"),
            seq_len=conf["dataset"].get("seq-len"))
        self.base = from_dict(merge(self.program, {
            "seed": seed % (2 ** 31),
            "log-path": str(env.work / "logs"),
            "val-batch-size": conf["dataset"]["val-batch"],
            "distribution": {"num-samples": self.sizes["per_client"]},
            "checkpoint": {"directory": str(env.work / "ckpt"),
                           "load": True,
                           "save": bool(traffic["checkpoint"]),
                           "validate": bool(traffic["validate"])}}))
        p0, s0 = self.weights()
        write_checkpoint(env.work / "ckpt", self.base.model_key, p0, s0, 0)
        del p0, s0
        for i, tap in enumerate(env.taps):
            tap.rearm(compare.STEPS if i == 0 else 0)
        self.rounds: list = []     # (record, t_end, compiles so far)
        self.state = {"open_at": None, "close_at": None, "tracing": False}
        self.trace_dir = env.work / "trace"
        self.trace = False

    def weights(self):
        """The seed's weights, made on the device in one jitted call by
        the configuration's reference module."""
        import jax
        return jax.jit(lambda k: self.ref.init(k, self.model_kwargs))(
            jax.random.key(self.seed % (2 ** 32)))

    def _arm(self, rec, t_end):
        """Runs at every round's end, before the next round starts."""
        import jax
        env, state = self.env, self.state
        r = rec["round_idx"]
        self.rounds.append((rec, t_end, env.compiles.count))
        for tap in env.taps:
            tap.flush_final()
            tap.finals_on = (r + 1 == WARM_ROUNDS - 1)
        if r == state["open_at"]:
            if self.trace:
                jax.profiler.start_trace(str(self.trace_dir))
                state["tracing"] = True
                with jax.profiler.TraceAnnotation("bench_clock_mark"):
                    state["mark_s"] = time.perf_counter()
            state["t_open"] = time.perf_counter()
        if r == state["close_at"]:
            state["t_close"] = t_end
            if state["tracing"]:
                jax.profiler.stop_trace()
                state["tracing"] = False

    def _drive(self, global_rounds: int, limited_time=None):
        import dataclasses
        from split_learning_tpu.run import run_local
        from split_learning_tpu.runtime.log import Logger
        cfg = dataclasses.replace(self.base, global_rounds=global_rounds,
                                  limited_time=limited_time)
        logger = make_probe(Logger).for_run(cfg, "server", console=False)
        logger.on_round = self._arm
        try:
            return run_local(cfg, devices=self.env.devices, logger=logger)
        finally:
            logger.close()

    def warm(self):
        """Set-up's rounds 0 and 1 through ``run_local``, tapped."""
        warm = self._drive(WARM_ROUNDS)
        self.agg = {"params": to_host(warm.params),
                    "stats": to_host(warm.stats),
                    "val_loss": warm.history[-1].val_loss}
        self.setup_compile_s = self.env.compiles.seconds
        self.warm_round_s = self.rounds[1][1] - self.rounds[0][1]

    def window(self, seconds: float, trace: bool) -> dict:
        """One lead-in round, then whole rounds: the window.  Traced, it
        is the mix's ``trace_rounds``.  Otherwise the program's own
        wall-clock budget (``limited-time``, here a ``WindowBudget``)
        ends the job at the first round boundary ``seconds`` past the
        window's opening."""
        env, state = self.env, self.state
        self.trace = bool(trace)
        state["open_at"] = WARM_ROUNDS
        if trace:
            n = int(self.traffic["trace_rounds"])
            state["close_at"] = WARM_ROUNDS + n
            result = self._drive(WARM_ROUNDS + 1 + n)
        else:
            most = 3 * math.ceil(seconds / self.warm_round_s) + 1
            result = self._drive(WARM_ROUNDS + 1 + most,
                                 limited_time=WindowBudget(state, seconds))
            last, state["t_close"], _ = self.rounds[-1]
            state["close_at"] = last["round_idx"]
            n = state["close_at"] - state["open_at"]
        at_open = next(c for rec, _, c in self.rounds
                       if rec["round_idx"] == state["open_at"])
        recs = [rec for rec, _, _ in self.rounds
                if state["open_at"] < rec["round_idx"] <= state["close_at"]]
        # this runtime counts buffers under peak_bytes_in_use and what it
        # holds back for the loaded programs' temporaries under
        # peak_bytes_reserved (PERF.md section 2): the chip holds both
        peak, fullest = 0, {}
        for d in env.devices:
            stats = d.memory_stats() or {}
            held = int(stats.get("peak_bytes_in_use", 0)) \
                + int(stats.get("peak_bytes_reserved", 0))
            if held >= peak:
                peak, fullest = held, stats
        out = {"rounds": recs, "n": n,
               "round_ends_s": [t - state["t_open"] for rec, t, _ in
                                self.rounds if rec in recs],
               "window_s": state["t_close"] - state["t_open"],
               "setup_s": state["t_open"] - T_PROCESS,
               "samples": sum(rec["num_samples"] for rec in recs),
               "failed": sum(not rec["ok"] for rec in recs)
               + (n - len(recs)),
               "compiles": env.compiles.count - at_open,
               "peak": peak, "memory_stats": fullest}
        if self.traffic["checkpoint"]:
            import compare
            back = read_checkpoint(env.work / "ckpt", self.base.model_key)
            out["ckpt"] = compare.tree_mismatch(
                {"p": back["params"], "s": back.get("batch_stats") or {}},
                {"p": to_host(result.params),
                 "s": to_host(result.stats or {})}) \
                + int(int(back["meta"]["round_idx"])
                      != state["close_at"] + 1)
        return out

    def release(self):
        """Drop what the program keeps on the device."""
        from split_learning_tpu.runtime import context
        context._GLOBAL_STEP_CACHE.clear()
        gc.collect()

    def _training_numbers(self, tap, p0, s0, cast, fault) -> dict:
        """The reference's three steps on the tapped feed (column 0: the
        columns train apart) against the tap's readings.  ``p0`` is a
        host tree: ``follow`` makes the device's one copy of it."""
        import jax
        import compare
        col = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a[0], tree)
        feed = [(x[0], y[0], k[0]) for x, y, k in tap.feed]
        learning = {k: self.learning[k] for k in
                    ("optimizer", "learning-rate", "momentum",
                     "weight-decay") if self.learning.get(k) is not None}
        ref_run = compare.follow(self.ref, learning, p0, s0, feed,
                                 cast=cast, fault=fault)
        tapped = {"losses": [float(l[0]) for l in tap.losses],
                  "opt1": col(tap.opt1),
                  "dparam": compare.diff_norms(col(tap.p3), p0)}
        return compare.numbers(self.ref, learning, tapped, ref_run)

    def compare(self, cast=None, fault=None) -> dict:
        """The reference (or, with ``cast``/``fault``, the control or a
        planted fault in its place) against what the tap read.  Faults:
        ``half_batch`` (``compare.follow``) and ``stale_val`` (the
        validation taken at the round's first weights, not its last)."""
        import jax
        import jax.numpy as jnp
        import compare
        tap = next(t for t in self.env.taps if t.feed)
        t0 = time.perf_counter()
        p0, s0 = self.weights()
        got, t1 = {}, t0
        if fault != "stale_val":
            p0 = jax.device_get(p0)     # the device's copy goes with this
            got = self._training_numbers(tap, p0, s0, cast, fault)
            t1 = time.perf_counter()
            finals = [jax.tree_util.tree_map(lambda a, i=i: a[i], f)
                      for t in self.env.taps for f in t.finals for i in
                      range(jax.tree_util.tree_leaves(f)[0].shape[0])]
            got["columns"] = len(finals)
            got["fedavg"], got["fedavg_leaf"] = compare.fedavg_gap(
                self.agg["params"], finals,
                [self.sizes["per_client"]] * len(finals))
        t2 = time.perf_counter()
        if self.traffic["validate"]:
            at = (p0, s0) if fault == "stale_val" else tuple(
                jax.tree_util.tree_map(jnp.asarray, self.agg[k])
                for k in ("params", "stats"))
            ref_val = compare.val_loss(
                self.ref, *at, self.val_rows, self.val_labels,
                self.conf["dataset"]["val-batch"], cast=cast)
            got["val_loss"] = abs(self.agg["val_loss"] - ref_val) \
                / max(abs(ref_val), 1e-30)
        self.reference_phases_s = {
            "three_steps": t1 - t0, "fedavg": t2 - t1,
            "validation": time.perf_counter() - t2}
        return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)

    steady_allocator()
    env = Env(args.workload)
    marks = [("import_and_device", time.perf_counter())]
    job = Job(env, args.seed)
    marks.append(("data_weights_checkpoint", time.perf_counter()))
    job.warm()
    marks.append(("rounds_0_and_1", time.perf_counter()))
    win = job.window(args.seconds, bool(args.trace))
    job.release()
    t_ref = time.perf_counter()
    got = job.compare()
    reference_s = time.perf_counter() - t_ref
    # a process's peak never falls: this is the reference's where it holds
    # more than the program did, and the program's where it does not
    peak_after_reference = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in env.devices)

    # the configuration's ``limits`` names the numbers it holds; what the
    # comparison read besides goes under ``info``
    if "ckpt" in win:
        got["ckpt"] = win["ckpt"]
    limits = job.conf["limits"]
    compared = {k: {"value": got[k], "limit": limit}
                for k, limit in limits.items()}
    compared["compiles_in_window"] = {"value": win["compiles"], "limit": 0}
    compared["rounds_failed"] = {"value": win["failed"], "limit": 0}
    compared["columns_missing"] = {
        "value": abs(job.n_stage1 - got["columns"]), "limit": 0}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values())

    # -- metrics ---------------------------------------------------------------
    spec, state, chips = env.spec, job.state, env.chips
    calls = [c for t in env.taps for c in t.calls]
    run = {
        "args": vars(args), "cell": spec["cell"], "chips": chips,
        "platform": env.platform, "device_kind": env.kind,
        "peaks": env.peaks.get(env.kind),
        "window_rounds": win["rounds"], "window_s": win["window_s"],
        "samples": win["samples"],
        "t_open": state["t_open"], "t_close": state["t_close"],
        "tap_calls": calls,
        "setup_compile_s": job.setup_compile_s,
        "compiles_in_window": win["compiles"],
        "train_flops_per_sample": job.ref.train_flops_per_sample(
            load_module(HERE / "flops.py"), job.model_kwargs),
        "steps_in_window": sum(
            1 for t_in, _, _ in calls
            if state["t_open"] <= t_in <= state["t_close"]),
        "trace": None,
    }
    metrics = {}
    device = {"platform": env.platform, "kind": env.kind, "count": chips,
              "memory_peak_bytes": win["peak"]}
    out = {"correct": bool(correct), "attempted": win["n"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    on_chip = env.platform != "cpu"
    if args.trace:
        if on_chip:
            reduce = load_module(HERE / "trace_reduce.py")
            run["trace"] = reduce.reduce_dir(
                job.trace_dir, win["window_s"], mark_s=state.get("mark_s"),
                spans=reduce.host_spans(run),
                n_steps=run["steps_in_window"],
                window_host=(state["t_open"], state["t_close"]))
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            out["breakdown"] = run["trace"]["breakdown"]
        for m in spec["per_layer"]:
            value = load_module(
                HERE / "metrics" / f"{m['name']}.py").read(run)
            if value is not None and (on_chip
                                      or m["source"] == "program_counter"):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif on_chip:
        values = {"round_throughput":
                  win["samples"] / win["window_s"] / chips,
                  "hbm_peak": win["peak"] / 2 ** 30,
                  "setup_s": win["setup_s"]}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    shutil.rmtree(env.work, ignore_errors=True)   # data, checkpoints, trace
    trace = run["trace"] or {}
    out["info"] = {"samples": win["samples"], "window_s": win["window_s"],
                   "setup_s": win["setup_s"],
                   "setup_phases_s": dict(
                       (name, t - t0) for (name, t), t0 in zip(
                           marks + [("resume_and_lead_in_round",
                                     state["t_open"])],
                           [T_PROCESS] + [t for _, t in marks])),
                   "memory_stats": win["memory_stats"],
                   "warm_round_s": job.warm_round_s,
                   "round_ends_s": win["round_ends_s"],
                   "round_phases_s": [
                       {k: v["total_s"] for k, v in rec["phases"].items()}
                       for rec in win["rounds"]],
                   "reference_s": reference_s,
                   "peak_in_use_after_reference": peak_after_reference,
                   "reference_phases_s": job.reference_phases_s,
                   "setup_compile_s": job.setup_compile_s,
                   "steps_in_window": run["steps_in_window"],
                   "step_module": trace.get("step_module"),
                   "clock_aligned": trace.get("clock_aligned"),
                   "not_held": {k: v for k, v in got.items()
                                if isinstance(v, float) and k not in limits},
                   "leaves": {k: got[f"{k}_leaf"] for k in
                              ("grad", "grad_all", "dparam", "fedavg")}}
    out["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
