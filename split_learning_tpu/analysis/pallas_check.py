"""Pallas lowering gate (PK001).

A config-enabled Pallas kernel that silently falls back to an XLA op
chain is the worst kind of perf regression: numerically identical,
invisible to every correctness test, and the exact failure mode a
refactor of the dispatch plumbing would produce.  This analyzer closes
the gap the jaxpr audit leaves open — JX001-007 prove the hot path has
no host round-trips, but nothing proved the kernels the config claims
are on actually ARE the compiled path.

PK001 has two halves.  The dispatch half: for every kernel the plane
can enable, trace the REAL hot-path entry point with that kernel
enabled and require a ``pallas_call`` primitive somewhere in the jaxpr
(recursing through sub-jaxprs, so jit/custom-vjp wrapping doesn't hide
it):

* quantize — ``_quantize_dev`` (int8 and the int4 nibble-pack shape)
  with the kernel on;
* dequantize — ``_dequantize_dev`` mirror;
* stage_update — a real :class:`MeshFoldBackend` built with
  ``stage_update`` enabled, driven through ``stage_update`` exactly
  like the JX007 jaxpr audit, then every cached fused program traced;
* flash attention — the decoders' one ``Attention``
  (``models/decoder.py``, ``use_flash=True``): a tiny TinyLlama forward
  traced end to end, proving the builders' keyword still routes
  through the Pallas kernel in the compiled step (before this gate,
  nothing asserted that);
* grouped products — ``parallel/expert.py HeldMoEMLP`` traced through
  value and gradient: the ``pallas_call``s of ``ops/grouped_matmul.py``
  are there and no ``ragged_dot`` is;
* the state-space scan — ``models/decoder.py Mamba2`` traced through
  value and gradient: both kernels of ``ops/ssd_scan.py`` are there.

:func:`check_lowering` is a pure jaxpr->findings helper so the
negative test can prove the gate actually fires on a pallas-free
program.

The lowering half: a ``pallas_call`` in the jaxpr says nothing about
whether Mosaic accepts it — every quantize/dequantize variant and the
flash kernels sat behind a green dispatch half while their block
shapes were illegal on TPU.  So every kernel in
:func:`lowering_cases` — the shapes ``chip_smoke.py`` runs on the chip
— is lowered for TPU with ``interpret=False``
(``jit(f).trace(...).lower(lowering_platforms=("tpu",))``): the
Pallas-to-Mosaic lowering is Python and needs no TPU, so a refused
block shape or an op Mosaic has no rule for is a finding on any host.
It is evidence that a kernel lowers, never that it runs; the chip run
is ``chip_smoke.py``.

Requires tracing (jax): a ``--no-trace`` run skips this analyzer
entirely.
"""

from __future__ import annotations

import functools
import pathlib

from split_learning_tpu.analysis.findings import Finding

_REL_QUANT = "split_learning_tpu/runtime/codec/quant.py"
_REL_AGG = "split_learning_tpu/runtime/aggregate.py"
_REL_FLASH = "split_learning_tpu/ops/flash_attention.py"
_REL_GMM = "split_learning_tpu/ops/grouped_matmul.py"
_REL_SSD = "split_learning_tpu/ops/ssd_scan.py"
_REL_KQUANT = "split_learning_tpu/ops/kernels/quant.py"
_REL_KUPDATE = "split_learning_tpu/ops/kernels/update.py"

#: (B, S, H, D) the flash kernels are held to: TinyLlama's 32 x 64
#: heads and the 16 x 128 heads of ROADMAP R1, at sequence 2048
FLASH_SHAPES = ((2, 2048, 32, 64), (2, 2048, 16, 128))
#: (B, S, H, KV, D, window, block) of the benchmark's token cell
#: (``mellum2_12b_c3``): 32 query heads over 4 key-value heads, blocks of
#: 512, with the window of its sliding layers and without
FLASH_GROUPED = ((2, 4096, 32, 4, 128, 1024, 512),
                 (2, 4096, 32, 4, 128, None, 512))
#: (rows, K, N, groups) of the token cell's grouped products: a common
#: pass's ``C`` rows against the 8 held experts' ``gate``/``up`` and
#: ``down`` matrices, and the overflow pass's ``worst - C`` rows; and the
#: same of the cell whose experts are 1,856 wide (14.5 lane widths: a
#: matrix block stands over the edge)
GROUPED_SHAPES = ((16384, 2304, 896, 8), (16384, 896, 2304, 8),
                  (49152, 2304, 896, 8), (49152, 896, 2304, 8),
                  (6144, 2688, 1856, 8), (6144, 1856, 2688, 8),
                  (43008, 2688, 1856, 8), (43008, 1856, 2688, 8))
#: (B, S, H, P, G, N, chunk) of the state-space cell's scans
#: (``nemotron_twotower_30b_c5``): 64 heads of 64 in 8 groups, a state of
#: 128, chunks of 128
SSD_SHAPES = ((2, 4096, 64, 64, 8, 128, 128),)
#: one microbatch of the VGG16 cut-7 boundary (configs/baseline1.yaml):
#: the activation the codec quantizes, and its gradient
CUT7_BOUNDARY = (32, 16, 16, 64)
#: codec tiles: the smoke's ``int8:64`` / ``int4:64`` and the spec
#: grammar's default
QUANT_TILES = (64, 256)


def equations(jaxpr):
    """Every equation of the (closed) jaxpr and of what it calls:
    custom_vjp/jit carry ClosedJaxprs, ``pallas_call`` its kernel as a
    plain Jaxpr, a ``cond`` its branches as a list."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) \
                    else (param,):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    yield from equations(sub)


def primitives(jaxpr) -> set:
    """Names of every primitive of :func:`equations`."""
    return {eqn.primitive.name for eqn in equations(jaxpr)}


def pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation of :func:`equations`."""
    return [eqn for eqn in equations(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def contains_pallas_call(jaxpr) -> bool:
    """True iff a ``pallas_call`` primitive appears anywhere in the
    (closed) jaxpr, including nested sub-jaxprs."""
    return "pallas_call" in primitives(jaxpr)


def check_lowering(jaxpr, rel: str, where: str) -> list[Finding]:
    """PK001 on one traced program: the enabled kernel's
    ``pallas_call`` must be present, or the config is lying about what
    the hot path runs."""
    if contains_pallas_call(jaxpr):
        return []
    return [Finding(
        "PK001", rel, 0, where,
        f"kernel {where!r} is enabled but no pallas_call primitive "
        "appears in the traced hot-path jaxpr: the kernel silently "
        "fell back to the XLA chain")]


def grouped_lowering_cases() -> list[tuple]:
    """The three grouped-product kernels (``slt_gmm``, ``slt_gmm_t``,
    ``slt_gmm_drhs``) in bfloat16 at :data:`GROUPED_SHAPES`, with the
    tiles the operation picks itself."""
    import jax
    import jax.numpy as jnp

    from split_learning_tpu.ops import grouped_matmul as gm

    def abstract(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    cases = []
    for rows, k, n, groups in GROUPED_SHAPES:
        sizes = abstract(groups, dtype=jnp.int32)
        shape = f"({rows}, {k}, {n}, {groups})"
        cases += [
            (f"gmm{shape}", _REL_GMM,
             functools.partial(gm.gmm, interpret=False),
             (abstract(rows, k), abstract(groups, k, n), sizes)),
            (f"gmm_t{shape}", _REL_GMM,
             functools.partial(gm.gmm, transposed=True, interpret=False),
             (abstract(rows, n), abstract(groups, k, n), sizes)),
            (f"gmm_drhs{shape}", _REL_GMM,
             functools.partial(gm.gmm_drhs, interpret=False),
             (abstract(rows, k), abstract(rows, n), sizes))]
    return cases


def ssd_lowering_cases() -> list[tuple]:
    """The scan's two kernels (``slt_ssd_fwd``; ``slt_ssd_bwd`` under a
    gradient) in bfloat16 at :data:`SSD_SHAPES`."""
    import jax
    import jax.numpy as jnp

    from split_learning_tpu.ops.ssd_scan import ssd_scan

    cases = []
    for b, s, h, p, g, n, chunk in SSD_SHAPES:
        def scan(*operands, chunk=chunk):
            return ssd_scan(*operands, chunk, False)
        operands = (jax.ShapeDtypeStruct((b, s, h, p), jnp.bfloat16),
                    jax.ShapeDtypeStruct((b, s, h), jnp.float32),
                    jax.ShapeDtypeStruct((h,), jnp.float32),
                    jax.ShapeDtypeStruct((b, s, g, n), jnp.bfloat16),
                    jax.ShapeDtypeStruct((b, s, g, n), jnp.bfloat16))
        shape = f"({b}, {s}, {h}, {p}, {g}, {n}, {chunk})"
        cases += [
            (f"ssd_fwd{shape}", _REL_SSD, scan, operands),
            (f"ssd_bwd{shape}", _REL_SSD, jax.grad(
                lambda *o, f=scan: f(*o).astype(jnp.float32).sum(),
                argnums=range(5)), operands)]
    return cases


def lowering_cases() -> list[tuple]:
    """``(name, rel, fn, abstract_args)`` for every Pallas kernel at
    the shapes the chip smoke runs: flash forward and backward, the
    grouped products of the token cell, the quantize/dequantize passes
    over the cut-7 boundary, and both stage updates over every distinct
    leaf shape of the VGG16 tree (conv kernels and 1-D biases
    included)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from split_learning_tpu.models import build_model
    from split_learning_tpu.ops.flash_attention import flash_attention
    from split_learning_tpu.ops.kernels import quant as kquant
    from split_learning_tpu.ops.kernels import update as kupd

    def abstract(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    cases: list[tuple] = []
    for shape in FLASH_SHAPES:
        qkv = (abstract(shape, jnp.bfloat16),) * 3
        cases.append((f"flash_fwd{shape}", _REL_FLASH, flash, qkv))
        cases.append((
            f"flash_bwd{shape}", _REL_FLASH,
            jax.grad(lambda q, k, v: flash(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)), qkv))
    for b, s, h, kv, d, window, block in FLASH_GROUPED:
        def grouped(q, k, v, window=window, block=block):
            return flash_attention(q, k, v, causal=True, window=window,
                                   block_q=block, block_k=block,
                                   interpret=False)
        qkv = (abstract((b, s, h, d), jnp.bfloat16),
               abstract((b, s, kv, d), jnp.bfloat16),
               abstract((b, s, kv, d), jnp.bfloat16))
        name = f"({b}, {s}, {h}/{kv}, {d}) window {window}"
        cases.append((f"flash_fwd{name}", _REL_FLASH, grouped, qkv))
        cases.append((
            f"flash_bwd{name}", _REL_FLASH,
            jax.grad(lambda q, k, v, f=grouped: f(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)), qkv))
    cases += grouped_lowering_cases()
    cases += ssd_lowering_cases()
    n = int(np.prod(CUT7_BOUNDARY))
    for tile in QUANT_TILES:
        t = n // tile
        for bits in (8, 4):
            cases.append((
                f"quantize_int{bits}:{tile}", _REL_KQUANT,
                functools.partial(kquant.quantize_tiles, bits=bits,
                                  interpret=False),
                (abstract((t, tile)),)))
        cases.append((
            f"dequantize:{tile}", _REL_KQUANT,
            functools.partial(kquant.dequantize_tiles, interpret=False),
            (abstract((t, tile), jnp.int8), abstract((t,)))))
    model = build_model("VGG16_CIFAR10")
    tree = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 32, 32, 3)), train=False))
    shapes = sorted({tuple(leaf.shape) for leaf in
                     jax.tree_util.tree_leaves(tree["params"])})
    scalar = abstract(())
    for shape in shapes:
        leaf = abstract(shape)
        cases.append((
            f"finalize_leaf{shape}", _REL_KUPDATE,
            lambda a, tw: kupd.finalize_leaf(a, tw, jnp.bfloat16,
                                             interpret=False),
            (leaf, scalar)))
        cases.append((
            f"momentum_leaf{shape}", _REL_KUPDATE,
            lambda a, b, v, tw, m: kupd.momentum_leaf(
                a, b, v, tw, m, jnp.float32, interpret=False),
            (leaf, leaf, leaf, scalar, scalar)))
    return cases


def check_tpu_lowering(name: str, rel: str, fn, args) -> list[Finding]:
    """PK001 on one kernel: it must lower for TPU natively."""
    import jax
    try:
        jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    except Exception as e:  # noqa: BLE001 — any refusal is the finding
        first = (str(e).strip().splitlines() or [type(e).__name__])[0]
        return [Finding(
            "PK001", rel, 0, name,
            f"kernel {name!r} does not lower for TPU "
            f"(interpret=False): {type(e).__name__}: {first[:300]}")]
    return []


def _check_codec_kernels() -> list[Finding]:
    import jax
    import numpy as np

    from split_learning_tpu.runtime.codec.quant import (
        _dequantize_dev, _quantize_dev,
    )

    findings: list[Finding] = []
    x = np.ones((33, 5), np.float32)
    for bits, tile in ((8, 64), (4, 7)):
        jaxpr = jax.make_jaxpr(
            lambda a, b=bits, t=tile: _quantize_dev(
                a, t, b, kernel=True))(x)
        findings += check_lowering(jaxpr, _REL_QUANT,
                                   f"quantize:int{bits}")
    # mirror: well-formed tiled codes for both widths
    for bits, tile, codes in ((8, 64, np.zeros((192,), np.int8)),
                              (4, 7, np.zeros((84,), np.uint8))):
        scale = np.ones((codes.shape[0] * (2 if bits == 4 else 1)
                         // tile,), np.float32)
        jaxpr = jax.make_jaxpr(
            lambda q, s, b=bits, t=tile: _dequantize_dev(
                q, s, t, b, 160, (160,), kernel=True))(
            codes, scale)
        findings += check_lowering(jaxpr, _REL_QUANT,
                                   f"dequantize:int{bits}")
    return findings


def _check_stage_update_kernel() -> list[Finding]:
    """Build a mesh backend with the stage-update kernel enabled,
    drive one real stage_update (compiling + caching its fused
    program), then trace each cached program and require the
    pallas_call — the same trace shape as the JX007 jaxpr audit."""
    import jax
    import numpy as np

    from split_learning_tpu.ops.kernels import KernelPlan
    from split_learning_tpu.runtime.aggregate import (
        MeshFoldBackend, _StageFold,
    )

    findings: list[Finding] = []
    be = MeshFoldBackend(kernels=KernelPlan(stage_update=True))
    st = _StageFold(["c0"])
    st.dtype = {"layer0/k": np.dtype(np.float32),
                "layer0/step": np.dtype(np.int32)}
    st.total_w = 2.0
    st.acc = {"layer0/k": be.contrib(np.ones((8, 4), np.float32), 2.0),
              "layer0/step": be.contrib(np.asarray(3, np.int32), 2.0)}
    base_flat = {"layer0/k": np.ones((8, 4), np.float32)}
    be.stage_fetch(be.stage_update(st, base_flat, {}, 0.9))
    if not be._fused_cache:
        return [Finding(
            "PK001", _REL_AGG, 0, "stage_update",
            "stage_update compiled no fused program to audit")]
    for prog in be._fused_cache.values():
        jaxpr = jax.make_jaxpr(
            lambda acc, base, vel: prog(
                acc, {}, base, vel, np.float32(2.0), np.float32(1.0),
                np.float32(0.9)))(
            {"layer0/k": np.ones((8, 4), np.float32),
             "layer0/step": np.float32(6.0)},
            dict(base_flat),
            {"layer0/k": np.zeros((8, 4), np.float32)})
        findings += check_lowering(jaxpr, _REL_AGG, "stage_update")
    return findings


def _check_flash_lowering() -> list[Finding]:
    """``models/decoder.py Attention``: a tiny TinyLlama with
    ``use_flash=True`` traced end to end must keep ``flash_attention``
    as a pallas_call in the compiled step."""
    import jax
    import jax.numpy as jnp

    from split_learning_tpu.models import build_model

    m = build_model("TinyLlama_TINYSTORIES", use_flash=True,
                    vocab_size=64, hidden_size=32, num_heads=4,
                    num_kv_heads=2, intermediate_size=64, n_block=1)
    x = jnp.zeros((1, 8), jnp.int32)
    variables = jax.eval_shape(
        lambda k: m.init(k, x, train=False), jax.random.key(0))
    jaxpr = jax.make_jaxpr(
        lambda p, xx: m.apply({"params": p}, xx, train=False))(
        variables["params"], x)
    return check_lowering(jaxpr, _REL_FLASH, "llama-flash-attention")


def _check_grouped_dispatch() -> list[Finding]:
    """``parallel/expert.py HeldMoEMLP`` with the token cell's structure
    (a share of 8 of 64 experts, top-8: common pass and overflow pass
    under a ``cond``), value and gradient: every grouped product is a
    ``pallas_call`` and none a ``ragged_dot``."""
    import jax
    import jax.numpy as jnp

    from split_learning_tpu.parallel.expert import HeldMoEMLP

    layer = HeldMoEMLP(32, 16, num_experts=64, k=8, held=tuple(range(8)))
    x = jnp.zeros((1, 64, 32))
    params = jax.eval_shape(
        lambda k: layer.init(k, x), jax.random.key(0))["params"]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, xx: layer.apply({"params": p}, xx).sum(),
        argnums=(0, 1)))(params, x)
    findings = check_lowering(jaxpr, _REL_GMM, "held-moe-grouped-dot")
    left = sorted(p for p in primitives(jaxpr) if p.startswith("ragged_dot"))
    if left:
        findings.append(Finding(
            "PK001", _REL_GMM, 0, "held-moe-grouped-dot",
            f"HeldMoEMLP still multiplies through {left}: the grouped "
            "products are ops/grouped_matmul.py's kernels"))
    return findings


def _check_ssd_dispatch() -> list[Finding]:
    """``models/decoder.py Mamba2``, value and gradient: the scan's forward
    and backward kernels are ``pallas_call``s in the traced program."""
    import jax
    import jax.numpy as jnp

    from split_learning_tpu.models.decoder import Mamba2

    layer = Mamba2(hidden_size=32, num_heads=4, head_dim=8, n_groups=2,
                   ssm_state_size=16, chunk_size=8)
    x = jnp.zeros((1, 16, 32))
    params = jax.eval_shape(
        lambda k: layer.init(k, x), jax.random.key(0))["params"]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, xx: layer.apply({"params": p}, xx).sum(),
        argnums=(0, 1)))(params, x)
    found = {eqn.params["name"] for eqn in pallas_calls(jaxpr)}
    missing = sorted({"slt_ssd_fwd", "slt_ssd_bwd"} - found)
    return [Finding(
        "PK001", _REL_SSD, 0, "mamba2-ssd-scan",
        f"Mamba2's scan runs no {missing}: the scan's only path is "
        "ops/ssd_scan.py's kernels")] if missing else []


def run(root: pathlib.Path, trace: bool = True) -> list[Finding]:
    if not trace:
        return []
    findings = _check_codec_kernels()
    findings += _check_stage_update_kernel()
    findings += _check_flash_lowering()
    findings += _check_grouped_dispatch()
    findings += _check_ssd_dispatch()
    for case in lowering_cases():
        findings += check_tpu_lowering(*case)
    return findings
