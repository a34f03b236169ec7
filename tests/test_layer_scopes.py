"""The layer scopes of a decoder step (``models/decoder.py``): in the
compiled ``sl_train_step`` of each kind of decoder block, every new scope in
the forward, recomputed and backward passes by the benchmark's rule
(``benchmarks/program_trace.py classify``), every product of a stage under
exactly one layer scope (``benchmarks/layer_trace.py LAYERS``), and no
operation changed by them: the lowered step without debug info is the
same with ``jax.named_scope`` made a no-op."""

import contextlib
import importlib.util
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

from tests import test_mellum, test_moonlight, test_nemotron_h

ROOT = pathlib.Path(__file__).resolve().parent.parent
SEQ, MB, M = 32, 2, 2
NEW = ("attn_proj", "ffn_dense", "norm_residual", "embed", "head")
# each kind of block, at its test file's sizes: (model, keywords, cut, the
# scopes its step holds)
KINDS = {
    "gqa_dense": (
        "TinyLlama_TINYSTORIES",
        dict(vocab_size=128, hidden_size=32, num_heads=2, num_kv_heads=1,
             intermediate_size=64, n_block=4),
        3, ("embed", "norm_residual", "attn_proj", "attn_full", "ffn_dense",
            "head", "loss")),
    "gqa_window_experts": (
        "Mellum2_TINYSTORIES", test_mellum.TINY, 3,
        ("embed", "norm_residual", "attn_proj", "attn_window", "attn_full",
         "moe_route", "moe_experts", "head", "loss")),
    "latent_dense_shared": (
        "Moonlight_TINYSTORIES", test_moonlight.TINY, 3,
        ("embed", "norm_residual", "mla_latent", "attn_full", "ffn_dense",
         "moe_shared", "moe_route", "moe_experts", "head", "loss")),
    "mamba2_experts": (
        "NemotronH_TINYSTORIES", test_nemotron_h.TINY, 6,
        ("embed", "norm_residual", "ssm_mixer", "ssm_scan", "attn_proj",
         "attn_full", "moe_shared", "moe_route", "moe_experts", "head",
         "loss")),
}


def _bench(name):
    """A module of ``benchmarks/`` (whose modules import each other by
    their plain names)."""
    bench = str(ROOT / "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "benchmarks" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lowered(kind):
    """The kind's toy ``sl_train_step`` (two stages, both
    rematerialized), lowered on one device."""
    from split_learning_tpu.parallel import (
        PipelineModel, make_mesh, make_train_step,
    )
    from split_learning_tpu.parallel.pipeline import (
        shard_to_mesh, stack_for_clients,
    )
    model, kw, cut, _ = KINDS[kind]
    example = jax.ShapeDtypeStruct((MB, SEQ), jnp.int32)
    pipe = PipelineModel(model, [cut], example, num_microbatches=M,
                         remat="all", model_kwargs=kw, scan_unroll=1)
    mesh = make_mesh(1, 1, jax.devices()[:1])
    variables = pipe.full_model.init(jax.random.key(0),
                                     jnp.zeros((MB, SEQ), jnp.int32))
    params = variables["params"]
    opt = optax.adamw(1e-3)
    step = make_train_step(pipe, opt, mesh, donate=False)
    put = lambda tree: shard_to_mesh(stack_for_clients(tree, 1), mesh)  # noqa: E731
    ids = jnp.zeros((1, M, MB, SEQ), jnp.int32)
    return step.lower(
        put(params), put(opt.init(params)),
        put(variables.get("batch_stats", {})),
        ids, ids, jax.vmap(jax.random.key)(jnp.arange(1)))


@pytest.fixture(scope="module")
def compiled():
    """The compiled step's text of a kind, compiled once a kind with the
    persistent cache off: its key leaves the names out, so it may hand
    back the same operations under the names of an older checkout."""
    from jax.experimental.compilation_cache import compilation_cache
    texts = {}

    def text(kind):
        if kind not in texts:
            lowered = _lowered(kind)
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
            try:
                texts[kind] = lowered.compile().as_text()
            finally:
                jax.config.update("jax_enable_compilation_cache", True)
                compilation_cache.reset_cache()
        return texts[kind]
    return text


def _op_names(text):
    return sorted(set(re.findall(r'op_name="([^"]+)"', text)))


def _layers(op_name, layers):
    """The layer scopes in a path, ``ssm_scan`` counted as part of
    ``ssm_mixer`` (as ``ssm_mixer_ms`` reads it)."""
    parts = re.split(r"[/()]", op_name.split(":", 1)[0])
    return {"ssm_mixer" if p == "ssm_scan" else p
            for p in parts if p in layers}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_kinds_step_holds_its_scopes_old_and_new(compiled, kind):
    layer_trace = _bench("layer_trace")
    held = {layer_trace.classify(n) for n in _op_names(compiled(kind))}
    assert held - {"optimizer", "unscoped"} == set(KINDS[kind][3])


@pytest.mark.parametrize("phase", ["fwd", "remat", "bwd"])
@pytest.mark.parametrize("kind,scope", [
    (kind, scope) for kind in sorted(KINDS) for scope in NEW
    if scope in KINDS[kind][3]])
def test_every_new_scope_in_every_pass(compiled, kind, scope, phase):
    """By the benchmark's rule on the path: the stage's forward, its
    recomputed forward under ``jax.checkpoint`` and the backward pass."""
    classify = _bench("program_trace").classify
    mine = [n for n in _op_names(compiled(kind))
            if scope in re.split(r"[/()]", n.split(":", 1)[0])
            and classify(n)[1] == phase]
    whole = [n for n in mine if n.startswith("jit(sl_train_step)/")]
    assert whole, (kind, scope, phase, mine[:3])


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_product_of_a_stage_has_one_layer_scope(compiled, kind):
    """A ``dot`` or ``convolution`` anywhere in the step (inside fusions
    and loops too) that lies in a stage lies under exactly one layer
    scope: nothing the program multiplies is left to ``unscoped``, and no
    product is counted twice.  A product the compiler rewrites without
    any ``op_name`` (the CPU backend's split of the einsum attention's
    batched products at one key-value head) names nothing at all; on
    the chip ``layer_trace`` counts such an instruction as ``unscoped``."""
    layers = set(_bench("layer_trace").LAYERS)
    lines = [line for line in compiled(kind).splitlines()
             if re.search(r"= \S+ (?:dot|convolution)\(", line)]
    named = [m.group(1) for m in map(
        re.compile(r'op_name="([^"]+)"').search, lines) if m]
    staged = [n for n in named if re.search(r"/stage\d+/", n)]
    assert staged and len(named) > 0.75 * len(lines), kind
    wrong = [n for n in staged if len(_layers(n, layers)) != 1]
    assert not wrong, wrong[:5]


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_scopes_change_no_operation(kind, monkeypatch):
    """Names only: the lowered step without debug info is the same text
    with every ``jax.named_scope`` entered at trace time made a no-op."""
    named = _lowered(kind)
    assert "attn_" in named.as_text(debug_info=True) \
        or "mla_latent" in named.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    plain = _lowered(kind)
    assert "norm_residual" not in plain.as_text(debug_info=True)
    assert named.as_text() == plain.as_text()
