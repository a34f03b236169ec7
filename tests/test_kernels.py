"""Pallas kernel plane (``ops/kernels/``): the fused quantize /
dequantize / stage-update kernels must be drop-in replacements for the
XLA op chains they shadow.

Parity contracts (mirroring the repo's aggregation contracts):

* kernel-on vs kernel-off **through the same XLA entry point** is
  bitwise for int8 codes+scales and for the fused update (the two
  device paths share every scalar as a jit argument, so XLA's
  reciprocal-multiply lowering applies identically to both);
* int4 is bitwise too — the nibble pack is integer math;
* vs the **numpy twins** codes are bitwise but dequantized floats are
  tolerance-pinned (rtol 1e-6): XLA lowers ``amax / qmax`` as a
  reciprocal multiply, a pre-existing 1-ulp skew the twin test in
  ``test_codec.py`` documents;
* mesh-vs-host momentum bit parity uses m=0.5 (exact products), the
  same contract as ``test_fused_mesh_vs_host_bit_identical``; the
  kernels-on vs kernels-off mesh twin is bitwise at any momentum.

All of it runs under the Pallas interpreter on CPU — the identical
kernel bodies lower natively on TPU (``resolve_interpret``).
"""

import copy
import dataclasses

import numpy as np
import pytest

from split_learning_tpu.ops import kernels as kplane
from split_learning_tpu.ops.kernels import (
    DISABLED, KernelPlan, pick_block, resolve_interpret, vmem_block,
)


def _bit_equal(a, b, path=""):
    if isinstance(a, dict) or isinstance(b, dict):
        assert isinstance(a, dict) and isinstance(b, dict), path
        assert a.keys() == b.keys(), (path, a.keys(), b.keys())
        for k in a:
            _bit_equal(a[k], b[k], f"{path}/{k}")
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, (path, a.dtype, b.dtype)
    assert a.shape == b.shape, (path, a.shape, b.shape)
    assert a.tobytes() == b.tobytes(), path   # bitwise, NaN-safe


# --------------------------------------------------------------------------
# plan plumbing: the config-to-dispatch contract
# --------------------------------------------------------------------------

class TestKernelPlan:
    def test_default_plan_is_disabled(self):
        assert kplane.plan() == DISABLED
        assert not DISABLED.any

    def test_as_plan_coerces_config_section(self):
        from split_learning_tpu.config import KernelsConfig
        kp = kplane.as_plan(KernelsConfig(quantize=True))
        assert kp == KernelPlan(quantize=True)
        assert kp.any

    def test_configure_none_is_a_noop(self):
        # scheduler codec-retune shims rebuild codecs from partial
        # configs with no `kernels` section — they must not clobber
        # the installed plan
        with kplane.override(dequantize=True):
            before = kplane.plan()
            kplane.configure(None)
            assert kplane.plan() == before
        assert kplane.plan() == DISABLED

    def test_override_restores_on_exit(self):
        with kplane.override(quantize=True, stage_update=True):
            assert kplane.plan().quantize
            assert kplane.plan().stage_update
        assert kplane.plan() == DISABLED

    def test_plan_is_frozen(self):
        with pytest.raises(dataclasses.FrozenInstanceError):
            DISABLED.quantize = True

    def test_config_round_trip(self):
        from split_learning_tpu.config import from_dict
        cfg = from_dict({"kernels": {"quantize": True,
                                     "dequantize": True,
                                     "stage_update": True}})
        kp = kplane.as_plan(cfg.kernels)
        assert kp == KernelPlan(quantize=True, dequantize=True,
                                stage_update=True)

    def test_config_has_no_block_option(self):
        """Block sizes come from the leaf shape and the VMEM budget
        (``vmem_block``); a user value could only break the lowering."""
        from split_learning_tpu.config import ConfigError, from_dict
        with pytest.raises(ConfigError):
            from_dict({"kernels": {"block": 128}})

    def test_pick_block_divides(self):
        assert pick_block(256) == 128
        assert pick_block(96) == 96
        assert pick_block(7) == 7
        for s in (1, 5, 48, 127, 384):
            b = pick_block(s)
            assert s % b == 0 and b <= 128

    def test_vmem_block_is_legal_and_bounded(self):
        """Each block dimension is the whole array dimension or a
        multiple of the native tile (32 sublanes / 128 lanes), and one
        lane-padded f32 block stays inside the budget — for tiny,
        ragged, conv-shaped and huge operands alike."""
        from split_learning_tpu.ops.kernels.util import (
            BLOCK_BYTES, LANES, SUBLANES,
        )
        for rows, cols in ((1, 10), (27, 64), (4608, 512), (8192, 64),
                           (4096, 4096), (1, 5_000_000), (1000, 64),
                           (33, 7), (32000, 2048)):
            br, bc = vmem_block(rows, cols)
            assert br == rows or br % SUBLANES == 0, (rows, cols, br)
            assert bc == cols or bc % LANES == 0, (rows, cols, bc)
            assert 1 <= br <= rows and 1 <= bc <= cols
            assert br * -(-bc // LANES) * LANES * 4 <= max(
                BLOCK_BYTES, SUBLANES * LANES * 4)
        with pytest.raises(ValueError):
            vmem_block(8, 1 << 20, split_cols=False)

    def test_resolve_interpret_on_cpu(self):
        import jax
        want = jax.default_backend() != "tpu"
        assert resolve_interpret(None) is want
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False


# --------------------------------------------------------------------------
# fused quantize / dequantize vs the XLA chain and the numpy twins
# --------------------------------------------------------------------------

SHAPES = [(7,), (33, 5), (4, 64), (257,), (1,)]


class TestQuantKernels:
    def _payload(self, shape, seed=0):
        rng = np.random.default_rng(seed)
        return (rng.standard_normal(shape) * 5.0).astype(np.float32)

    @pytest.mark.parametrize("bits,tile", [(8, 64), (8, 7), (4, 64),
                                           (4, 7), (8, 256)])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_kernel_bitwise_vs_xla_chain(self, bits, tile, shape):
        """Same entry point, kernel on vs off: codes AND scales agree
        bitwise (int8 and int4 — incl. odd leaf sizes, where the int4
        pad logic adds a whole extra tile to keep the count even)."""
        from split_learning_tpu.runtime.codec.quant import _quantize_dev
        x = self._payload(shape)
        q0, s0 = _quantize_dev(x, tile, bits, kernel=False)
        q1, s1 = _quantize_dev(x, tile, bits, kernel=True)
        _bit_equal(q0, q1)
        _bit_equal(s0, s1)

    @pytest.mark.parametrize("bits,tile", [(8, 64), (4, 7)])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_roundtrip_bitwise_vs_xla_chain(self, bits, tile, shape):
        from split_learning_tpu.runtime.codec.quant import (
            _dequantize_dev, _quantize_dev,
        )
        x = self._payload(shape, seed=1)
        n = x.size
        q, s = _quantize_dev(x, tile, bits, kernel=False)
        d0 = _dequantize_dev(q, s, tile, bits, n, shape, kernel=False)
        d1 = _dequantize_dev(q, s, tile, bits, n, shape,
                             kernel=True)
        _bit_equal(d0, d1)

    @pytest.mark.parametrize("bits,tile", [(8, 64), (4, 7), (4, 64)])
    def test_codes_bitwise_vs_numpy_twin(self, bits, tile):
        """Codes are integer math after the scale — bitwise vs the
        host twin; dequantized floats only to 1 ulp (the documented
        reciprocal-multiply skew of the DEVICE scale, kernel or not)."""
        from split_learning_tpu.runtime.codec.quant import (
            _quantize_dev, dequantize_leaf_np, quantize_np,
        )
        x = self._payload((33, 5), seed=2)
        twin = quantize_np(x, tile, bits)
        with kplane.override(quantize=True, dequantize=True):
            q, s = _quantize_dev(x, tile, bits, kernel=True)
        _bit_equal(np.asarray(q), twin.q)
        np.testing.assert_allclose(np.asarray(s), twin.scale,
                                   rtol=1e-6)
        back = dequantize_leaf_np(twin)
        from split_learning_tpu.runtime.codec.quant import (
            _dequantize_dev,
        )
        dev = _dequantize_dev(np.asarray(q), np.asarray(s), tile, bits,
                              x.size, x.shape, kernel=True)
        np.testing.assert_allclose(np.asarray(dev), back, rtol=1e-6,
                                   atol=1e-7)

    def test_nan_tile_sentinel_diverges_only_its_tile(self):
        """A non-finite tile ships a NaN scale and zero codes; every
        other tile stays clean — under the fused kernel, same as the
        XLA chain."""
        from split_learning_tpu.runtime.codec.quant import (
            _dequantize_dev, _quantize_dev,
        )
        x = np.ones((4, 64), np.float32)
        x[1, 3] = np.nan
        x[2, 0] = np.inf
        q, s = _quantize_dev(x, 64, 8, kernel=True)
        s = np.asarray(s)
        assert np.isnan(s[1]) and np.isnan(s[2])
        assert np.isfinite(s[[0, 3]]).all()
        q = np.asarray(q).reshape(4, 64)
        assert (q[1] == 0).all() and (q[2] == 0).all()
        back = np.asarray(_dequantize_dev(
            q.reshape(-1), s, 64, 8, 256, (4, 64), kernel=True))
        assert np.isnan(back[1]).all() and np.isnan(back[2]).all()
        np.testing.assert_allclose(back[[0, 3]], 1.0, atol=1e-2)

    def test_zero_tile_uses_scale_one(self):
        from split_learning_tpu.runtime.codec.quant import _quantize_dev
        q, s = _quantize_dev(np.zeros((2, 64), np.float32), 64, 8,
                             kernel=True)
        np.testing.assert_array_equal(np.asarray(s), 1.0)
        assert (np.asarray(q) == 0).all()

    @pytest.mark.parametrize("bits", [8, 4])
    def test_codec_end_to_end_bitwise_with_plan(self, bits):
        """QuantCodec with the process plan on vs off: identical wire
        leaves, identical decode — the full prepare/encode/decode
        path, not just the jitted kernels."""
        import jax.numpy as jnp

        from split_learning_tpu.runtime.codec.quant import (
            QuantCodec, dequantize_leaf,
        )
        from split_learning_tpu.runtime.codec.specs import parse_spec
        x = self._payload((9, 31), seed=3)
        spec = parse_spec(f"int{bits}:64")

        def run():
            c = QuantCodec(spec)
            wire = c.encode(c.prepare({"h": jnp.asarray(x)}))
            leaf = wire["h"]
            return leaf, np.asarray(dequantize_leaf(leaf))

        off_leaf, off_back = run()
        with kplane.override(quantize=True, dequantize=True):
            on_leaf, on_back = run()
        _bit_equal(off_leaf.q, on_leaf.q)
        _bit_equal(off_leaf.scale, on_leaf.scale)
        _bit_equal(off_back, on_back)


# --------------------------------------------------------------------------
# TPU lowering gate: interpreter parity says nothing about Mosaic
# --------------------------------------------------------------------------

def _lowering_cases(prefixes):
    from split_learning_tpu.analysis.pallas_check import lowering_cases
    return {c[0]: c for c in lowering_cases()
            if c[0].startswith(prefixes)}


_CODEC_UPDATE_CASES = _lowering_cases(
    ("quantize", "dequantize", "finalize_leaf", "momentum_leaf"))


@pytest.mark.parametrize("name", sorted(_CODEC_UPDATE_CASES))
def test_kernel_lowers_for_tpu(name):
    """Every quantize / dequantize / stage-update kernel, at the shapes
    ``chip_smoke.py`` runs on the chip (the cut-7 boundary; every leaf
    shape of the VGG16 tree), lowers for TPU with ``interpret=False``
    — on this CPU host, through the Python half of the Mosaic
    lowering.  All of the codec cases were refused before the scales
    moved to a ``(T, 1)`` column."""
    from split_learning_tpu.analysis.pallas_check import (
        check_tpu_lowering,
    )
    assert check_tpu_lowering(*_CODEC_UPDATE_CASES[name]) == []


def test_lowering_cases_cover_the_tree_and_the_codec():
    names = set(_CODEC_UPDATE_CASES)
    for must in ("quantize_int8:64", "quantize_int4:64", "dequantize:64",
                 "finalize_leaf(3, 3, 512, 512)", "momentum_leaf(512,)",
                 "finalize_leaf(10,)", "momentum_leaf(4096, 4096)"):
        assert must in names, (must, sorted(names))


# --------------------------------------------------------------------------
# fused stage update: 2-round FedAvgM velocity carry
# --------------------------------------------------------------------------

class TestStageUpdateKernel:
    def _updates(self, rng):
        from split_learning_tpu.runtime.protocol import Update
        ups = []
        for s, n in enumerate((3, 2), start=1):
            for i in range(n):
                params = {f"layer{s}": {
                    "kernel": (rng.standard_normal((8, 5)) * 10.0)
                    .astype(np.float32),
                    "bias": rng.standard_normal((5,))
                    .astype(np.float32),
                    "step": np.asarray(rng.integers(0, 100), np.int32),
                }}
                bs = {f"bn{s}": {"mean": rng.standard_normal((5,))
                                 .astype(np.float32)}}
                ups.append(Update(
                    client_id=f"client_{s}_{i}", stage=s, cluster=0,
                    params=params,
                    num_samples=int(rng.integers(1, 64)), round_idx=1,
                    batch_stats=bs))
        return ups

    def _base(self, ups):
        base: dict = {}
        for u in ups:
            for k, sub in u.params.items():
                node = base.setdefault(k, {})
                for kk, leaf in sub.items():
                    node.setdefault(kk, np.ones_like(np.asarray(leaf)))
        return base

    def _two_rounds(self, ups, backend, base, momentum):
        from split_learning_tpu.runtime.aggregate import StreamingFold
        exp: dict = {}
        for u in sorted(ups, key=lambda u: (u.stage, u.client_id)):
            exp.setdefault(u.stage, []).append(u.client_id)
        vel: dict = {}
        rs = []
        cur = base
        for _ in range(2):
            fold = StreamingFold(dict(exp), backend=backend)
            for u in ups:
                fold.add_update(copy.copy(u))
            r = fold.finish(base=cur, momentum=momentum, velocity=vel,
                            fused=True)
            rs.append(r)
            cur = r.params
        return rs, vel

    def _mesh(self, kernels):
        import jax

        from split_learning_tpu.runtime.aggregate import MeshFoldBackend
        return MeshFoldBackend(devices=jax.devices()[:2],
                               kernels=kernels)

    def test_kernel_mesh_vs_host_bit_identical(self):
        """Kernel-on mesh vs the numpy host oracle, velocity carried
        two rounds.  momentum=0.5: power-of-two products are exact, so
        XLA-vs-numpy FMA contraction cannot skew the comparison (the
        same contract ``test_fused_mesh_vs_host_bit_identical`` pins
        for the kernel-off mesh path)."""
        from split_learning_tpu.runtime.aggregate import HostFoldBackend
        rng = np.random.default_rng(89)
        ups = self._updates(rng)
        base = self._base(ups)
        host_rs, host_vel = self._two_rounds(
            [copy.copy(u) for u in ups], HostFoldBackend(), base, 0.5)
        mesh_rs, mesh_vel = self._two_rounds(
            [copy.copy(u) for u in ups],
            self._mesh(KernelPlan(stage_update=True)), base, 0.5)
        for h, m in zip(host_rs, mesh_rs):
            _bit_equal(h.params, m.params)
            _bit_equal(h.stats, m.stats)
        assert host_vel.keys() == mesh_vel.keys()
        for p in host_vel:
            assert (np.asarray(host_vel[p]).tobytes()
                    == np.asarray(mesh_vel[p]).tobytes()), p

    def test_kernel_on_vs_off_mesh_bit_identical_any_momentum(self):
        """Kernel-on vs kernel-off on the SAME mesh backend is bitwise
        at m=0.9 too — both paths see tw/momentum as jit arguments, so
        identical lowering applies to identical math."""
        rng = np.random.default_rng(97)
        ups = self._updates(rng)
        base = self._base(ups)
        off_rs, off_vel = self._two_rounds(
            [copy.copy(u) for u in ups], self._mesh(DISABLED), base,
            0.9)
        on_rs, on_vel = self._two_rounds(
            [copy.copy(u) for u in ups],
            self._mesh(KernelPlan(stage_update=True)), base, 0.9)
        for a, b in zip(off_rs, on_rs):
            _bit_equal(a.params, b.params)
            _bit_equal(a.stats, b.stats)
        for p in off_vel:
            assert (np.asarray(off_vel[p]).tobytes()
                    == np.asarray(on_vel[p]).tobytes()), p

    def test_backend_from_config_reads_kernels_section(self):
        from split_learning_tpu.config import from_dict
        from split_learning_tpu.runtime.aggregate import (
            make_fold_backend,
        )
        cfg = from_dict({"aggregation": {"sharded": True},
                         "kernels": {"stage_update": True}})
        be = make_fold_backend(cfg)
        assert be._kplan.stage_update

    def test_leaf_kernels_match_argument_scalar_oracle(self):
        """momentum_leaf / finalize_leaf vs a jitted oracle that takes
        tw and m as ARGUMENTS (the real fused program's signature) —
        bitwise, incl. the bf16 cast and the int round-divide."""
        import jax
        import jax.numpy as jnp

        from split_learning_tpu.ops.kernels import update as kupd
        rng = np.random.default_rng(5)
        acc = (rng.standard_normal((8, 5)) * 7.0).astype(np.float32)
        base = rng.standard_normal((8, 5)).astype(np.float32)
        vel = rng.standard_normal((8, 5)).astype(np.float32)
        tw = np.float32(2.5)

        @jax.jit
        def fin_oracle(a, w):
            return (a / w).astype(jnp.bfloat16)

        got = kupd.finalize_leaf(jnp.asarray(acc), jnp.asarray(tw),
                                 jnp.bfloat16)
        _bit_equal(np.asarray(got), np.asarray(fin_oracle(acc, tw)))

        @jax.jit
        def int_oracle(a, w):
            return jnp.round(a / w).astype(jnp.int32)

        got = kupd.finalize_leaf(jnp.asarray(acc), jnp.asarray(tw),
                                 jnp.int32, rnd=True)
        _bit_equal(np.asarray(got), np.asarray(int_oracle(acc, tw)))

        @jax.jit
        def mom_oracle(a, b, v, w, m):
            nv = m * v + (b - a / w)
            return (b - nv).astype(jnp.float32), nv

        got_p, got_v = kupd.momentum_leaf(
            jnp.asarray(acc), jnp.asarray(base), jnp.asarray(vel),
            jnp.asarray(tw), jnp.asarray(np.float32(0.9)), jnp.float32)
        wp, wv = mom_oracle(acc, base, vel, tw, np.float32(0.9))
        _bit_equal(np.asarray(got_p), np.asarray(wp))
        _bit_equal(np.asarray(got_v), np.asarray(wv))
