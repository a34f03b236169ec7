#!/usr/bin/env python3
"""Chip smoke: the main path, once, on the accelerator, in one process.

    python3 chip_smoke.py              # needs a TPU; fails without one
    python3 chip_smoke.py --rehearsal  # the same phases at toy size on CPU

Four phases, none skipped because an earlier one failed:

1. device  — the backend is the TPU (the CPU under ``--rehearsal``), its
   ``device_kind`` is in the peaks table, and the persistent compilation
   cache is where ``split_learning_tpu.platform`` resolves it.
2. round   — ``configs/baseline1.yaml`` (VGG16/CIFAR-10 cut at layer 7,
   2 + 2 clients, full width, seeded synthetic data) through
   ``run_local``: YAML -> plan -> MeshContext -> PipelineModel step ->
   train, FedAvg, validate, checkpoint.  Cut only in length: two rounds
   of a few optimizer steps.  Every round ok with finite losses, the
   checkpoint loads back, the mesh has the shape expected for the device
   count, and round 1 compiles nothing.  On several chips every device
   holds buffers and the compiled programs contain the stage hop and the
   FedAvg all-reduce.
3. kernels — every Pallas kernel the config surface can switch on,
   compiled natively (``interpret=False`` on the chip), run, and held to
   the parity contract of ``tests/test_kernels.py`` and
   ``tests/test_flash_attention.py`` against its XLA twin.
4. cache   — the compilation cache directory is not empty.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with exactly those keys, naming the device as JAX reports it; the mesh
and the compile seconds are on the ``summary:`` line before it.
Exit code 0 only when every phase passed; without an
accelerator (and without ``--rehearsal``) the script exits non-zero
before printing any result.  It states no speed: compile seconds are
printed as a set-up fact.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import pathlib
import shutil
import sys
import tempfile
import time
import traceback

HERE = pathlib.Path(__file__).resolve().parent
CONFIG = HERE / "configs" / "baseline1.yaml"
CUT = 7                      # baseline1's cut layer (VGG16 first pool)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What is cut to length.  Width (the model, the microbatch, the
    microbatch count) is the YAML's on the chip; ``phase_round`` holds
    FULL to that."""
    steps_per_round: int            # optimizer steps per stage-1 client
    batch_size: int                 # microbatch
    control_count: int              # microbatches per optimizer step
    synthetic_size: int
    val_batch_size: int
    val_max_batches: int
    flash_shapes: tuple             # (B, S, H, D)
    flash_dtype: str
    update_layers: int | None       # None = the whole parameter tree


FULL = Sizes(steps_per_round=3, batch_size=32, control_count=4,
             synthetic_size=2048, val_batch_size=200, val_max_batches=2,
             flash_shapes=((2, 2048, 32, 64), (2, 2048, 16, 128)),
             flash_dtype="bfloat16", update_layers=None)
TOY = Sizes(steps_per_round=2, batch_size=4, control_count=2,
            synthetic_size=64, val_batch_size=16, val_max_batches=1,
            flash_shapes=((1, 128, 2, 16),), flash_dtype="float32",
            update_layers=CUT)


class CompileLog:
    """Every backend compilation of the process, as jax reports it."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


def expected_mesh(platform: str, n_devices: int) -> tuple:
    """(client, stage) for baseline1's 2 stages x 2 stage-1 clients:
    one device per stage while devices last (on CPU heavy stages are
    chained on one device instead), the rest across clients."""
    stage = 1 if platform == "cpu" else min(2, n_devices)
    return (max(1, min(2, n_devices // stage)), stage)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_device() -> dict:
    import jax

    from split_learning_tpu.platform import (
        apply_compile_cache, apply_platform_env,
    )
    from split_learning_tpu.runtime.perf import resolve_peak_tflops
    apply_platform_env()
    cache_dir = apply_compile_cache()
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax"):
        try:
            versions[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            versions[pkg] = None
    print(f"device: {info}  versions: {versions}")
    print(f"compile cache: {cache_dir}")
    peak = resolve_peak_tflops(dev.device_kind)   # raises when unknown
    print(f"peaks table: {dev.device_kind!r} -> {peak} bf16 TFLOP/s")
    return {"device": info, "versions": versions, "cache_dir": cache_dir}


def phase_round(sizes: Sizes, compiles: CompileLog, workdir) -> dict:
    import jax
    import numpy as np

    from split_learning_tpu.config import from_yaml
    from split_learning_tpu.run import run_local
    from split_learning_tpu.runtime import context
    from split_learning_tpu.runtime.checkpoint import load_checkpoint
    from split_learning_tpu.runtime.log import Logger

    cfg = from_yaml(CONFIG)
    lrn = dataclasses.replace(cfg.learning, batch_size=sizes.batch_size,
                              control_count=sizes.control_count)
    assert sizes is not FULL or lrn == cfg.learning, (
        "the chip run must not change the YAML's width", cfg.learning)
    per_step = lrn.batch_size * lrn.control_count
    cfg = dataclasses.replace(
        cfg, global_rounds=2, learning=lrn, log_path=str(workdir),
        synthetic_size=sizes.synthetic_size,
        val_batch_size=sizes.val_batch_size,
        val_max_batches=sizes.val_max_batches,
        distribution=dataclasses.replace(
            cfg.distribution,
            num_samples=sizes.steps_per_round * per_step),
        checkpoint=dataclasses.replace(
            cfg.checkpoint, directory=str(workdir / "checkpoints")))

    devices = jax.devices()
    rounds = []

    class RoundProbe(Logger):
        """The loop journals one kind=round record at each round's
        end; that is where the per-round facts are read."""

        def metric(self, **fields):
            if fields.get("kind", "round") == "round":
                held = {d.id: 0 for d in devices}
                for arr in jax.live_arrays():
                    for shard in arr.addressable_shards:
                        held[shard.device.id] += shard.data.nbytes
                rounds.append({
                    "compiles": compiles.count,
                    "compile_s": compiles.seconds,
                    "steps": {k: v[3]._cache_size() for k, v in
                              context._GLOBAL_STEP_CACHE.items()},
                    "held_bytes": held,
                    "train_detail": fields.get("train_detail", {})})
            super().metric(**fields)

    logger = RoundProbe.for_run(cfg, "server", console=True)
    before = compiles.count
    try:
        result = run_local(cfg, logger=logger)
    finally:
        logger.close()

    assert len(result.history) == 2, result.history
    for rec in result.history:
        assert rec.ok, f"round {rec.round_idx} not ok: {rec}"
        assert rec.num_samples > 0, rec
        assert rec.val_loss is not None and np.isfinite(rec.val_loss), rec
        assert np.isfinite(rec.val_accuracy), rec
    want_samples = 2 * sizes.steps_per_round * per_step
    assert all(r.num_samples == want_samples for r in result.history), (
        [r.num_samples for r in result.history], want_samples)

    # the mesh every compiled step ran on
    meshes = {tuple(int(n) for n in v[0].shape.values())
              for v in context._GLOBAL_STEP_CACHE.values()}
    want = expected_mesh(devices[0].platform, len(devices))
    assert meshes == {want}, f"mesh shapes {meshes}, expected {want}"

    # round 1 paid no compilation that round 0 already paid — it is the
    # same programs on the same shapes, so it compiles nothing at all
    # and adds no call signature to any step's jit cache
    r0, r1 = rounds
    assert r1["steps"] == r0["steps"], (r0["steps"], r1["steps"])
    assert r1["compiles"] == r0["compiles"], (
        f"{r1['compiles'] - r0['compiles']} compilation(s) in round 1")

    # the checkpoint of the last round loads back to the final weights
    ck = load_checkpoint(cfg.checkpoint.directory, cfg.model_key)
    assert ck is not None and ck["round_idx"] == 2, ck and ck["round_idx"]
    flat_ck = jax.tree_util.tree_leaves(ck["params"])
    flat_res = jax.tree_util.tree_leaves(result.params)
    assert len(flat_ck) == len(flat_res) > 0
    for a, b in zip(flat_ck, flat_res):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)

    out = {"mesh": list(want), "rounds": len(result.history),
           "samples_per_round": want_samples,
           "val_loss": [round(float(r.val_loss), 4)
                        for r in result.history],
           "compilations_round0": r0["compiles"] - before,
           "compilations_round1": r1["compiles"] - r0["compiles"],
           "compile_s_round0": round(r0["compile_s"], 1)}
    if len(devices) > 1:
        out.update(_check_several_devices(rounds, result))
    print(f"round: {out}")
    return out


def _check_several_devices(rounds, result) -> dict:
    """More than one chip: every mesh device held buffers at each
    round's end, the round barrier was the on-mesh FedAvg, and the
    compiled programs contain the hop and the all-reduce."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from split_learning_tpu.parallel.pipeline import make_fedavg_step
    from split_learning_tpu.runtime import context

    (mesh, pipe, optimizer, step), = context._GLOBAL_STEP_CACHE.values()
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    for r in rounds:
        empty = [i for i in mesh_ids if r["held_bytes"][i] == 0]
        assert not empty, f"devices {empty} held no buffer"
        assert "fedavg_dispatch_s" in r["train_detail"], (
            "round did not take the on-mesh FedAvg path", r)

    n_client = int(mesh.shape["client"])
    by_client = NamedSharding(mesh, P("client"))

    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                (n_client,) + tuple(a.shape), a.dtype,
                sharding=by_client), tree)

    params = jax.eval_shape(lambda t: t, result.params)
    stats = jax.eval_shape(lambda t: t, result.stats)
    opt = jax.eval_shape(optimizer.init, params)
    M, mb = pipe.num_microbatches, pipe.mb_size
    example = pipe.boundary[0]
    x = jax.ShapeDtypeStruct((n_client, M) + tuple(example.shape),
                             example.dtype, sharding=by_client)
    labels = jax.ShapeDtypeStruct((n_client, M, mb), jnp.int32,
                                  sharding=by_client)
    rngs = jax.eval_shape(
        lambda: jax.vmap(jax.random.key)(jnp.arange(n_client)))
    rngs = jax.ShapeDtypeStruct(rngs.shape, rngs.dtype,
                                sharding=by_client)
    step_hlo = step.lower(stacked(params), stacked(opt), stacked(stats),
                          x, labels, rngs).compile().as_text()
    fedavg_hlo = make_fedavg_step(mesh).lower(
        stacked(params),
        jax.ShapeDtypeStruct((n_client,), jnp.float32,
                             sharding=by_client)).compile().as_text()
    found = {"step_collective_permute": "collective-permute" in step_hlo,
             "step_all_reduce": "all-reduce" in step_hlo,
             "fedavg_all_reduce": "all-reduce" in fedavg_hlo}
    n_stage = int(mesh.shape["stage"])
    if n_stage > 1:
        assert found["step_collective_permute"], "no stage hop compiled"
        assert found["step_all_reduce"], "no stage gradient all-reduce"
    if n_client > 1:
        assert found["fedavg_all_reduce"], "no FedAvg all-reduce compiled"
    return {"devices_holding_buffers": len(mesh_ids), **found}


def _dense_attention(q, k, v):
    """Causal softmax attention in f32 at full matmul precision: the
    reference both dtypes of the flash kernel are held to."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    hi = jax.lax.Precision.HIGHEST
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) \
        / np.sqrt(q.shape[-1])
    n = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None], s,
                  -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s), v,
                      precision=hi)


def _close(got, want, tol: float, what: str):
    """max|got - want| <= tol * max(1, max|want|), everything finite."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{what}: non-finite values"
    err = float(np.max(np.abs(got - want)))
    bound = tol * max(1.0, float(np.max(np.abs(want))))
    assert err <= bound, f"{what}: max error {err:.3g} > {bound:.3g}"
    return err


def _bitwise(got, want, what: str):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape, (
        what, got.dtype, want.dtype, got.shape, want.shape)
    if got.tobytes() != want.tobytes():
        diff = int(np.sum(got != want))
        raise AssertionError(
            f"{what}: {diff} of {got.size} elements differ bitwise")


def phase_kernels(sizes: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from split_learning_tpu.models import build_model
    from split_learning_tpu.ops.flash_attention import flash_attention
    from split_learning_tpu.ops.kernels import resolve_interpret
    from split_learning_tpu.ops.kernels import update as kupd
    from split_learning_tpu.runtime.codec.quant import (
        _dequantize_dev, _quantize_dev,
    )

    on_chip = jax.default_backend() == "tpu"
    interpret = resolve_interpret(None)
    assert interpret is (not on_chip), (
        f"kernels resolve interpret={interpret} on "
        f"{jax.default_backend()}")
    out: dict = {"interpret": interpret}

    # -- flash attention, forward and backward, causal ---------------------
    dtype = jnp.dtype(sizes.flash_dtype)
    # the tests' f32 contract (2e-5 / 5e-4); bf16 carries 8 mantissa
    # bits, so its bound is a few units of 2**-8
    fwd_tol, bwd_tol = ((2e-5, 5e-4) if dtype == jnp.float32
                        else (2e-2, 2e-2))
    for shape in sizes.flash_shapes:
        kq, kk, kv, kd = jax.random.split(jax.random.key(sum(shape)), 4)
        q, k, v = (jax.random.normal(kx, shape, jnp.float32).astype(dtype)
                   for kx in (kq, kk, kv))
        do = jax.random.normal(kd, shape, jnp.float32)
        flash = jax.jit(lambda q, k, v: jax.vjp(
            lambda *a: flash_attention(*a, causal=True).astype(
                jnp.float32), q, k, v))
        dense = jax.jit(lambda q, k, v: jax.vjp(_dense_attention,
                                                q, k, v))
        o_f, vjp_f = flash(q, k, v)
        o_d, vjp_d = dense(q, k, v)
        errs = [_close(o_f, o_d, fwd_tol, f"flash fwd {shape}")]
        for name, g_f, g_d in zip("qkv", vjp_f(do), vjp_d(do)):
            errs.append(_close(g_f, g_d, bwd_tol,
                               f"flash d{name} {shape}"))
        out[f"flash{shape}"] = [float(f"{e:.2g}") for e in errs]

    # -- quantize / dequantize on the cut-layer activation and gradient ----
    mb = sizes.batch_size
    front = build_model("VGG16_CIFAR10", end_layer=CUT,
                        dtype=jnp.bfloat16)
    back = build_model("VGG16_CIFAR10", start_layer=CUT,
                       dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(1), (mb, 32, 32, 3))
    labels = jax.random.randint(jax.random.key(2), (mb,), 0, 10)
    v_front = front.init(jax.random.key(3), x, train=False)
    act = front.apply(v_front, x, train=False)
    v_back = back.init(jax.random.key(4), act, train=False)

    def loss(a):
        import optax
        logits = back.apply(v_back, a, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()

    grad = jax.grad(loss)(act)
    assert act.shape == (mb, 16, 16, 64), act.shape
    for bits in (8, 4):
        for what, leaf in (("activation", act), ("gradient", grad)):
            tag = f"int{bits}:64 {what}"
            q0, s0 = _quantize_dev(leaf, 64, bits, kernel=False)
            q1, s1 = _quantize_dev(leaf, 64, bits, kernel=True)
            _bitwise(q1, q0, f"quantize codes {tag}")
            _bitwise(s1, s0, f"quantize scales {tag}")
            assert np.isfinite(np.asarray(s1)).all(), tag
            assert np.any(np.asarray(q1) != 0), f"{tag}: all-zero codes"
            n, shp = int(leaf.size), tuple(leaf.shape)
            d0 = _dequantize_dev(q0, s0, 64, bits, n, shp, kernel=False)
            d1 = _dequantize_dev(q0, s0, 64, bits, n, shp, kernel=True)
            _bitwise(d1, d0, f"dequantize {tag}")
            _close(d1, leaf, 1.0 / (100 if bits == 8 else 6),
                   f"round trip {tag}")
    out["quant"] = "int8:64,int4:64 x activation,gradient: bitwise"

    # -- stage update over the VGG16 parameter tree -------------------------
    model = build_model("VGG16_CIFAR10",
                        end_layer=sizes.update_layers or -1)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), x, train=False))["params"]
    leaves = jax.tree_util.tree_leaves(params)

    def rand(seed):
        return [jax.random.normal(jax.random.key(seed + i), l.shape,
                                  jnp.float32)
                for i, l in enumerate(leaves)]

    acc, base, vel = rand(100), rand(200), rand(300)
    tw, m = jnp.float32(2.5), jnp.float32(0.9)

    @jax.jit
    def with_kernels(acc, base, vel, tw, m):
        fin = [kupd.finalize_leaf(a, tw, jnp.bfloat16) for a in acc]
        mom = [kupd.momentum_leaf(a, b, v, tw, m, jnp.float32)
               for a, b, v in zip(acc, base, vel)]
        return fin, mom

    @jax.jit
    def with_xla(acc, base, vel, tw, m):
        fin = [(a / tw).astype(jnp.bfloat16) for a in acc]
        mom = []
        for a, b, v in zip(acc, base, vel):
            nv = m * v + (b - a / tw)
            mom.append(((b - nv).astype(jnp.float32), nv))
        return fin, mom

    fin_k, mom_k = with_kernels(acc, base, vel, tw, m)
    fin_x, mom_x = with_xla(acc, base, vel, tw, m)
    for leaf, fk, fx, mk, mx in zip(leaves, fin_k, fin_x, mom_k, mom_x):
        _bitwise(fk, fx, f"finalize_leaf {leaf.shape}")
        _bitwise(mk[0], mx[0], f"momentum_leaf params {leaf.shape}")
        _bitwise(mk[1], mx[1], f"momentum_leaf velocity {leaf.shape}")
    shapes = sorted({tuple(l.shape) for l in leaves}, key=len)
    assert any(len(s) == 1 for s in shapes) \
        and any(len(s) == 4 for s in shapes), shapes
    out["update"] = (f"{len(leaves)} leaves, "
                     f"{sum(int(np.prod(l.shape)) for l in leaves)} "
                     "elements: bitwise")
    print(f"kernels: {out}")
    return out


def phase_cache(cache_dir: str) -> dict:
    import jax

    from split_learning_tpu.platform import compile_cache_dir
    assert cache_dir == compile_cache_dir() \
        == jax.config.jax_compilation_cache_dir, (
        cache_dir, compile_cache_dir(),
        jax.config.jax_compilation_cache_dir)
    entries = sum(1 for p in pathlib.Path(cache_dir).iterdir())
    assert entries > 0, f"compile cache {cache_dir} is empty"
    print(f"cache: {entries} entries in {cache_dir}")
    return {"entries": entries}


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="run the same phases at toy size on the CPU "
                         "backend (Pallas kernels interpreted); the "
                         "result says platform: cpu")
    args = ap.parse_args(argv)

    import jax

    import split_learning_tpu  # noqa: F401 — fail here when absent
    want = "cpu" if args.rehearsal else "tpu"
    if jax.default_backend() != want:
        print(f"chip_smoke: FAILED in phase device: the jax backend is "
              f"{jax.default_backend()!r}, this run needs {want!r}",
              file=sys.stderr)
        return 2

    sizes = TOY if args.rehearsal else FULL
    compiles = CompileLog()
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    results: dict = {}
    failed: list[str] = []

    def run(name, fn, *a):
        t0 = time.perf_counter()
        c0 = compiles.seconds
        try:
            results[name] = fn(*a)
            status = "ok"
        except Exception:  # noqa: BLE001 — every phase reports, then
            # the next one still runs
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        print(f"== phase {name}: {status} "
              f"(wall {time.perf_counter() - t0:.0f} s, of which "
              f"compilation {compiles.seconds - c0:.0f} s)", flush=True)

    try:
        run("device", phase_device)
        run("round", phase_round, sizes, compiles, workdir)
        run("kernels", phase_kernels, sizes)
        run("cache", phase_cache,
            results.get("device", {}).get("cache_dir", ""))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    if failed:
        print(f"chip_smoke: FAILED in phase(s): {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(f"summary: mesh {results['round']['mesh']}, "
          f"{compiles.count} compilations, {compiles.seconds:.1f} s "
          "compiling (set-up, not a speed)")
    # the contract's result line: these keys and no others
    dev = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
