"""The program's Laguna decoder (``models/decoder.py``: a head count for
each kind of layer, a sigmoid gate a head on attention's result, RoPE over
a leading share of each head, a dense first layer, softmax-routed experts
beside a shared one) against the benchmark's plain reference
(``benchmarks/configs/laguna_xs2_c3.py``) on seeded weights at a small
size: logits, loss, gradients and one optimizer step of the compiled
pipeline step; the rotary part, the gate and the head counts alone; the
held shares against the whole layer; the configuration's file; a run
through ``run_local``; and the lowered train steps of the configurations
that were there before, which these changes leave as they were."""

import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.conftest import bench_reference

from split_learning_tpu.models import build_model, decoder
from split_learning_tpu.parallel.expert import moe_aux_loss

ROOT = pathlib.Path(__file__).resolve().parent.parent
FULL, SLIDING = decoder.FULL, decoder.SLIDING
# one period after the dense layer: a full layer of 6 query heads, three
# sliding ones of 8 (window 8), a full one; 2 key-value heads of 16, half
# of a full layer's head turned; 8 softmax-routed experts top-2 with 2 held
# (four chips), a shared one; hidden 128
TINY = dict(vocab_size=128, hidden_size=128, intermediate_size=96,
            num_hidden_layers=5,
            layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
            mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
            num_attention_heads_per_layer=[6, 8, 8, 8, 6],
            num_key_value_heads=2, head_dim=16, sliding_window=8,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_experts=8, num_experts_per_tok=2, experts_held=2)
SEQ = 32
REF = bench_reference("laguna_xs2_c3")


@pytest.fixture(scope="module", params=["tiled", "whole"])
def seeded(request):
    kw = dict(TINY, routers=request.param) if request.param == "whole" \
        else TINY
    params, stats = REF.init(jax.random.key(3), kw)
    ids = jax.random.randint(jax.random.key(4), (2, SEQ + 1), 0,
                             TINY["vocab_size"])
    return params, stats, ids[:, :-1], ids[:, 1:]


def _objective(model):
    """Mean next-token cross-entropy plus the weighted load-balancing
    terms, as the pipeline forms it from what the layers sow."""
    def fn(params, stats, x, y):
        logits, mut = model.apply(
            {"params": params, "batch_stats": stats}, x,
            mutable=["intermediates"])
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return ce + REF.AUX_WEIGHT * moe_aux_loss(mut["intermediates"]), \
            (ce, logits)
    return fn


def _ref_objective(params, stats, x, y):
    logits = REF.forward(params, stats, x, model_kwargs=TINY)
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
    return ce + REF.extra_objective(params, stats, x, None, None,
                                    model_kwargs=TINY), (ce, logits)


def _assert_trees_close(got, want, rtol, atol):
    flat, flat_r = (dict(jax.tree_util.tree_leaves_with_path(g))
                    for g in (got, want))
    assert set(flat) == set(flat_r)
    for path, g in flat.items():
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_r[path]), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["einsum", "flash"])
def test_logits_loss_and_first_gradient_match_the_reference(
        seeded, use_flash):
    params, stats, x, y = seeded
    model = build_model("Laguna_TINYSTORIES", use_flash=use_flash,
                        flash_block=8, **TINY)
    (obj, (ce, logits)), grads = jax.value_and_grad(
        _objective(model), has_aux=True)(params, stats, x, y)
    (obj_r, (ce_r, logits_r)), grads_r = jax.value_and_grad(
        _ref_objective, has_aux=True)(params, stats, x, y)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_r),
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(float(ce), float(ce_r), rtol=1e-6)
    np.testing.assert_allclose(float(obj), float(obj_r), rtol=1e-6)
    _assert_trees_close(grads, grads_r, rtol=2e-3, atol=3e-6)


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["einsum", "flash"])
def test_one_step_of_the_compiled_pipeline_matches_the_reference(use_flash):
    """The split model (cut after the first sparse layer, two microbatches)
    through ``make_train_step`` with AdamW: its loss, and its parameters
    after one step, against the reference's objective differentiated
    microbatch by microbatch and the same AdamW step."""
    from split_learning_tpu.parallel.mesh import make_mesh
    from split_learning_tpu.parallel.pipeline import (
        PipelineModel, make_train_step, shard_to_mesh, stack_for_clients,
    )
    mb, m = 2, 2
    kw = dict(TINY, routers="whole")
    params, stats = REF.init(jax.random.key(11), kw)
    pipe = PipelineModel(
        "Laguna_TINYSTORIES", cuts=[3],
        example_input=jax.ShapeDtypeStruct((mb, SEQ), jnp.int32),
        num_microbatches=m, moe_aux_weight=REF.AUX_WEIGHT,
        model_kwargs=dict(TINY, use_flash=use_flash, flash_block=8))
    mesh = make_mesh(1, 1, jax.devices()[:1])
    opt = optax.adamw(1e-3, weight_decay=0.1)
    step = make_train_step(pipe, opt, mesh, donate=False)
    ids = jax.random.randint(jax.random.key(12), (1, m, mb, SEQ + 1), 0,
                             TINY["vocab_size"])
    place = lambda t: shard_to_mesh(stack_for_clients(t, 1), mesh)  # noqa
    out = step(place(params), place(opt.init(params)), place(stats),
               ids[..., :-1], ids[..., 1:],
               jax.vmap(jax.random.key)(jnp.arange(1)))
    fn = jax.value_and_grad(_ref_objective, has_aux=True)
    grads, ces = None, []
    for i in range(m):
        REF._LAST.clear()
        (_, (ce, _)), g = fn(params, stats, ids[0, i, :, :-1],
                             ids[0, i, :, 1:])
        ces.append(float(ce))
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    grads = jax.tree_util.tree_map(lambda g: g / m, grads)
    updates, _ = opt.update(grads, opt.init(params), params)
    want = optax.apply_updates(params, updates)
    np.testing.assert_allclose(float(np.asarray(out[3]).ravel()[0]),
                               np.mean(ces), rtol=1e-5)
    got = jax.tree_util.tree_map(lambda a: np.asarray(a)[0], out[0])
    _assert_trees_close(got, want, rtol=1e-4, atol=1e-5)


def test_the_trees_are_the_references_trees(seeded):
    """Parameters under the names the reference writes: a dense first
    layer, then sparse layers with a shared expert; a gate in every
    attention; no buffer."""
    params, stats, x, _ = seeded
    model = build_model("Laguna_TINYSTORIES", **TINY)
    mine = model.init(jax.random.key(0), x)
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa
    assert shapes(mine["params"]) == shapes(params)
    assert "batch_stats" not in mine and stats == {}
    assert len(model.specs) == TINY["num_hidden_layers"] + 3
    assert "gate_proj" in params["layer2"] and "moe" not in params["layer2"]
    assert {"moe", "shared_experts"} <= set(params["layer3"])


def test_a_full_and_a_sliding_layer_build_their_own_head_counts():
    """One stack, two kinds: the full layers' projections are 6 heads
    wide, the sliding layers' 8, each with a gate a head."""
    model = build_model("Laguna_TINYSTORIES", **TINY)
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))["params"]
    hd, d = TINY["head_dim"], TINY["hidden_size"]
    for layer, heads in zip(range(2, 7), TINY["num_attention_heads_per_layer"]):
        a = shapes[f"layer{layer}"]["attention"]
        assert a["q_proj"]["kernel"].shape == (d, heads * hd)
        assert a["o_proj"]["kernel"].shape == (heads * hd, d)
        assert a["g_proj"]["kernel"].shape == (d, heads)
        assert a["k_proj"]["kernel"].shape == (d, 2 * hd)
    assert {6, 8} == set(TINY["num_attention_heads_per_layer"])


def test_what_has_no_module_is_refused():
    for kw in (dict(gating="element-wise"), dict(attention_bias=True),
               dict(moe_router_logit_softcapping=30.0),
               dict(moe_apply_router_weight_on_input=True),
               dict(norm_topk_prob=False),
               dict(num_attention_heads_per_layer=[6, 8, 8, 4, 6]),
               dict(layer_types=[FULL, SLIDING, "chunked_attention", SLIDING,
                                 FULL]),
               dict(num_hidden_layers=4)):
        with pytest.raises(ValueError, match="no module"):
            build_model("Laguna_TINYSTORIES", **{**TINY, **kw})


# -- the rotary part ------------------------------------------------------------

def _x(d=128, heads=3):
    return jax.random.normal(jax.random.key(21), (2, 16, heads, d))


def test_a_whole_head_factor_turns_as_before_bit_for_bit():
    """``partial_rotary_factor`` 1 (stated or not) gives the frequencies
    and the turned head of a plain whole-head RoPE, to the bit."""
    plain = {"rope_theta": 10000.0}
    for p in (dict(plain, partial_rotary_factor=1), plain):
        inv, factor = decoder.rope_of(SLIDING, 128, {SLIDING: p})
        np.testing.assert_array_equal(inv, decoder.rope_inv_freq(128, 1e4))
        assert factor == 1.0
    x, pos = _x(), jnp.arange(16)
    inv = decoder.rope_inv_freq(128, 1e4)
    freqs = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(freqs)[None, :, None, :], jnp.sin(freqs)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_array_equal(
        np.asarray(decoder.rope(x, pos, inv, interleaved=False)),
        np.asarray(want))


def test_half_a_head_turns_and_the_factor_scales_that_half_alone():
    """At 0.5 the YaRN frequencies are 32 (a rotated width of 64); dims
    64-127 come through untouched, and the attention factor scales dims
    0-63 alone (position 0 is not turned, only scaled)."""
    params = decoder.LAGUNA_ROPE_PARAMETERS
    inv, factor = decoder.rope_of(FULL, 128, params)
    assert inv.shape == (32,) and factor == pytest.approx(1.4158883083359672)
    x, pos = _x(), jnp.arange(16)
    turned = decoder.rope(x, pos, inv, interleaved=False, factor=factor)
    np.testing.assert_array_equal(np.asarray(turned[..., 64:]),
                                  np.asarray(x[..., 64:]))
    np.testing.assert_allclose(np.asarray(turned[:, 0, :, :64]),
                               factor * np.asarray(x[:, 0, :, :64]),
                               rtol=1e-6)
    assert float(jnp.abs(turned[:, 1:, :, :64]
                         - factor * x[:, 1:, :, :64]).max()) > 0.1
    # the reference's own rotary part agrees
    np.testing.assert_allclose(
        np.asarray(turned), np.asarray(REF.rotary(x, REF.sizes()[
            "rope_parameters"][FULL])), rtol=1e-5, atol=1e-5)


def test_yarn_over_a_rotated_width_of_64_is_the_closed_form():
    """``theta^(-2i/64)`` kept below the ramp, divided by 64 above it, the
    ramp from ``floor(cd(64))`` to ``ceil(cd(1))`` with ``cd(r) = 64 ln(4096
    / (2 pi r)) / (2 ln 500000)``."""
    inv, _ = decoder.rope_of(FULL, 128, decoder.LAGUNA_ROPE_PARAMETERS)
    i = np.arange(32)
    base = 500000.0 ** (-2.0 * i / 64)
    cd = [64 * np.log(4096 / (2 * np.pi * r)) / (2 * np.log(500000.0))
          for r in (64.0, 1.0)]
    low, high = max(int(np.floor(cd[0])), 0), min(int(np.ceil(cd[1])), 63)
    assert (low, high) == (5, 16)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, base / 64 * ramp + base * (1 - ramp),
                               rtol=1e-12)
    np.testing.assert_allclose(REF.yarn_inv_freq(
        64, **decoder.LAGUNA_ROPE_PARAMETERS[FULL]), inv, rtol=1e-12)


# -- the gate ---------------------------------------------------------------------

def _attention(gating, **over):
    return decoder.MIXERS[FULL](**{**dict(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        rope_parameters=decoder.LAGUNA_ROPE_PARAMETERS, gating=gating),
        **over})


def test_a_gate_of_nought_halves_each_heads_result():
    """``W_g`` = 0: every head's gate is sigmoid(0) = 1/2, so the layer
    gives half of what the same weights give without a gate (``o_proj`` is
    linear); and a gate that is not nought counts."""
    x = jax.random.normal(jax.random.key(22), (2, 12, 32))
    gated = _attention(True)
    params = gated.init(jax.random.key(23), x)["params"]
    plain = {k: v for k, v in params.items() if k != "g_proj"}
    assert params["g_proj"]["kernel"].shape == (32, 4)
    half = dict(params, g_proj={"kernel": jnp.zeros((32, 4))})
    np.testing.assert_allclose(
        np.asarray(gated.apply({"params": half}, x)),
        0.5 * np.asarray(_attention(False).apply({"params": plain}, x)),
        rtol=1e-6, atol=1e-7)
    assert float(jnp.abs(gated.apply({"params": params}, x)
                         - gated.apply({"params": half}, x)).max()) > 1e-3


def test_the_gate_has_its_scope_inside_the_projections():
    """``attn_gate`` nested in ``attn_proj`` in the lowered layer, and no
    ``attn_gate`` where the layer has no gate."""
    x = jnp.zeros((1, 8, 32))
    for gating in (True, False):
        layer = _attention(gating)
        params = layer.init(jax.random.key(0), x)["params"]
        text = jax.jit(lambda p: layer.apply({"params": p}, x)).lower(
            params).as_text(debug_info=True)
        assert ("attn_proj/attn_gate" in text) == gating


# -- the held share -------------------------------------------------------------

def test_the_shares_add_up_to_the_whole_layer():
    """The four chips' held shares of the expert layer (2 experts each) of
    the program, with the shared expert counted once, add up to the
    reference's layer with every expert held."""
    from split_learning_tpu.parallel.expert import HeldMoEMLP
    kw = dict(TINY, experts_held=None, routers="whole")
    params, _ = REF.init(jax.random.key(24), kw)
    p = params["layer3"]
    s = REF.sizes(kw)
    m = jax.random.normal(jax.random.key(25), (2, 16, TINY["hidden_size"]))
    mm = lambda eq, a, b: jnp.einsum(  # noqa: E731
        eq, a, b, precision=jax.lax.Precision.HIGHEST)
    want, _ = REF.moe_layer(p["moe"], m.reshape(-1, TINY["hidden_size"]), s,
                            mm, p["shared_experts"])
    shared, _ = REF.moe_layer(
        {"router": p["moe"]["router"], "experts": jax.tree_util.tree_map(
            lambda a: a[:0], p["moe"]["experts"])},
        m.reshape(-1, TINY["hidden_size"]), dict(s, experts_held=()), mm,
        p["shared_experts"])
    total = shared
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        layer = HeldMoEMLP(hidden_size=TINY["hidden_size"],
                           intermediate_size=TINY["moe_intermediate_size"],
                           num_experts=8, k=2, held=held, factor=2.5)
        part = {"router": p["moe"]["router"],
                "experts": jax.tree_util.tree_map(
                    lambda a: a[2 * chip:2 * chip + 2], p["moe"]["experts"])}
        total = total + layer.apply({"params": part}, m).reshape(
            -1, TINY["hidden_size"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_the_tiled_state_holds_two_pairs_a_token_over_four_layers():
    """The configuration's construction at the toy's shape (four chips of
    two experts, two a token): every token holds exactly one pair in two
    of the four sparse layers, whatever the key and the stream, and the
    chosen weights tie at the factor over two."""
    from split_learning_tpu.parallel.pipeline import COUNTER_FOLDS
    model = build_model("Laguna_TINYSTORIES", **TINY)
    for seed in (0, 1, 2):
        params, stats = REF.init(jax.random.key(seed), TINY)
        x = jax.random.randint(jax.random.key(10 + seed), (2, SEQ), 0,
                               TINY["vocab_size"])
        _, mut = model.apply({"params": params}, x,
                             mutable=list(COUNTER_FOLDS))
        pairs = [float(v["moe"]["moe_pairs_held"][0])
                 for _, v in sorted(mut["counters_sum"].items())]
        assert len(pairs) == 4 and sum(pairs) == 2 * x.size, pairs


# -- the configuration ------------------------------------------------------------

def test_the_configuration_says_what_the_program_is_given():
    """The reference's weight of the load-balancing term is the one the
    YAML hands the program, the YAML keeps every published width, it is
    JSON as well as YAML, and the tree has the parameters it states."""
    import yaml
    path = ROOT / "benchmarks" / "configs" / "laguna_xs2_c3.yaml"
    conf = yaml.safe_load(path.read_text())
    assert conf == json.loads(path.read_text())
    program = conf["program"]
    assert program["model"] == "Laguna"
    assert program["learning"]["moe-aux-weight"] == REF.AUX_WEIGHT
    kw = program["model-kwargs"]
    for key in ("hidden_size", "intermediate_size", "num_key_value_heads",
                "head_dim", "sliding_window", "rms_norm_eps",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size",
                "moe_routed_scaling_factor", "partial_rotary_factor"):
        assert kw[key] == conf[key] == REF.SIZES[key], key
    for key in ("gating", "layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer", "num_hidden_layers",
                "moe_apply_router_weight_on_input"):
        assert kw[key] == conf[key], key
    for kind in (FULL, SLIDING):
        assert kw["rope_parameters"][kind] == conf["rope_parameters"][kind] \
            == REF.SIZES["rope_parameters"][kind]
    # the first five of the published layers; the router keeps its
    # published width and 16 experts are held
    published = conf["published"]
    n = conf["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert conf[key] == published[key][:n], key
    assert kw["num_experts"] == published["num_experts"] == 256
    assert kw["experts_held"] == conf["num_experts"] == 16
    assert kw["vocab_size"] == conf["vocab_size"] == 100352 // 8
    assert set(conf["reduced"]) >= {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"}
    shapes = jax.eval_shape(lambda k: REF.init(k, kw), jax.random.key(0))
    count = lambda t: sum(a.size for a in jax.tree_util.tree_leaves(t))  # noqa
    held = conf["held-here"]
    assert count(shapes[0]) == held["parameters"] == 490297344
    assert count(shapes[0]["layer2"]) == held["dense_layer"]
    assert count(shapes[0]["layer3"]) == held["sliding_sparse_layer"]
    assert count(shapes[0]["layer6"]) == held["full_sparse_layer"]
    assert shapes[1] == {}
    # the program builds the same trees from the same keywords
    model = build_model("Laguna_TINYSTORIES", **kw)
    mine = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    as_shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa
    assert as_shapes(mine["params"]) == as_shapes(shapes[0])


def test_it_trains_through_run_local(tmp_path, monkeypatch):
    """Two rounds through ``run_local`` (one client a stage, cut after the
    first sparse layer, AdamW, FedAvg, validation, a checkpoint a round)
    from the reference's weights: every round ok, every parameter moved."""
    from split_learning_tpu.config import from_dict
    from split_learning_tpu.run import run_local
    from split_learning_tpu.runtime import context
    from split_learning_tpu.runtime.checkpoint import (
        load_checkpoint, save_checkpoint,
    )
    rows = np.random.default_rng(0).integers(
        0, TINY["vocab_size"], size=(24, SEQ + 1)).astype(np.int32)
    (tmp_path / "data" / "TinyStories").mkdir(parents=True)
    np.save(tmp_path / "data" / "TinyStories" / "train.npy", rows[:16])
    np.save(tmp_path / "data" / "TinyStories" / "valid.npy", rows[16:])
    monkeypatch.setenv("SLT_DATA_DIR", str(tmp_path / "data"))
    cfg = from_dict(dict(
        model="Laguna", dataset="TINYSTORIES", clients=[1, 1],
        global_rounds=2, val_batch_size=4, compute_dtype="float32",
        model_kwargs=TINY, log_path=str(tmp_path / "logs"),
        learning={"batch_size": 2, "control_count": 2, "optimizer": "adamw",
                  "learning_rate": 1e-3, "weight_decay": 0.1,
                  "moe_aux_weight": REF.AUX_WEIGHT},
        distribution={"num_samples": 8}, topology={"cut_layers": [3]},
        checkpoint={"directory": str(tmp_path / "ckpt"), "save": True,
                    "load": True, "validate": True}))
    params, stats = jax.device_get(
        REF.init(jax.random.key(8), dict(TINY, routers="whole")))
    save_checkpoint(cfg.checkpoint.directory, cfg.model_key, params, stats, 0)
    context._GLOBAL_STEP_CACHE.clear()
    try:
        result = run_local(cfg)
        back = load_checkpoint(cfg.checkpoint.directory, cfg.model_key)
    finally:
        context._GLOBAL_STEP_CACHE.clear()
    assert [r.ok for r in result.history] == [True, True]
    assert back["round_idx"] == 2
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        result.params, params)
    assert min(jax.tree_util.tree_leaves(moved)) > 0


# -- the configurations that were there before ------------------------------------

#: sha256 of each configuration's toy ``sl_train_step`` lowered on the CPU
#: (:func:`_lowered_text`), as the commit before these modules' new fields
#: lowered it: a field whose default changed what an older model compiles
#: to would show here.  The three token configurations' hashes are those of
#: the flash kernels that pass ``lse`` and ``delta`` as rows
LOWERED = {
    "bert_base_c7":
        "5dbf7d673644db0b1aab49544c79cba8e8efc00112ada4589b654ff8d47b6c05",
    "vgg16_c7":
        "6a895f5b1b25f335bc9cd759ca240cdd507272267041e41c0df6f7fb9d71b244",
    "mellum2_12b_c3":
        "1c0aefd01ee65712910dfebf1a9d7592a065942e79710f63fed9070c0c2fcac1",
    "moonlight_16b_c3":
        "82b96212a02c519f1799bf2be36aa7bff20754fe33553ddea3f9a69dbb19e04d",
    "nemotron_twotower_30b_c5":
        "33f6cb00accbada90a59effc0e60214d060edcca72fa6bd802bed375ef407508",
}


def _lowered_text(name, data_dir):
    """The configuration's train step at its ``toy`` sizes, one client a
    stage on one device, built as the runtime builds it (its model
    keywords, compute type, cut, microbatches, remat rule and optimizer),
    lowered for the CPU without debug information."""
    import yaml
    from split_learning_tpu.config import from_dict
    from split_learning_tpu.parallel.mesh import make_mesh
    from split_learning_tpu.parallel.pipeline import (
        PipelineModel, make_train_step, stack_for_clients,
    )
    from split_learning_tpu.runtime.context import MeshContext, make_optimizer

    def merge(base, over):
        out = dict(base)
        for k, v in (over or {}).items():
            out[k] = merge(out[k], v) if isinstance(v, dict) \
                and isinstance(out.get(k), dict) else v
        return out
    conf = yaml.safe_load(
        (ROOT / "benchmarks" / "configs" / f"{name}.yaml").read_text())
    program = merge(conf["program"], conf["toy"].get("program"))
    cfg = from_dict(merge(program, {"clients": [1, 1],
                                    "log-path": str(data_dir / "logs")}))
    ctx = MeshContext(cfg, jax.devices()[:1])
    lrn = cfg.learning
    pipe = PipelineModel(
        cfg.model_key, cuts=list(program["topology"]["cut-layers"]),
        example_input=ctx._example, num_microbatches=lrn.control_count,
        remat=lrn.remat, moe_aux_weight=lrn.moe_aux_weight,
        model_kwargs=ctx.model_kwargs)
    mesh = make_mesh(1, 1, jax.devices()[:1])
    opt = make_optimizer(lrn)
    step = make_train_step(pipe, opt, mesh, donate=False)
    variables = jax.eval_shape(lambda k: ctx.init_variables(k),
                               jax.random.key(0))
    shaped = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        jax.eval_shape(lambda t: stack_for_clients(t, 1), t))
    params = variables["params"]
    from split_learning_tpu.data import make_data_loader
    _, y = next(iter(make_data_loader(
        ctx.dataset, 1, train=False, synthetic_size=64,
        dataset_kwargs=ctx.dataset_kwargs)))
    y = np.asarray(y)
    lead = (1, lrn.control_count, lrn.batch_size)
    x = jax.ShapeDtypeStruct(lead + ctx._example.shape[1:],
                             ctx._example.dtype)
    labels = jax.ShapeDtypeStruct(lead + y.shape[1:], y.dtype)
    return step.lower(
        shaped(params), shaped(jax.eval_shape(opt.init, params)),
        shaped(variables.get("batch_stats", {})), x, labels,
        jax.ShapeDtypeStruct((1,), jax.random.key(0).dtype)).as_text()


@pytest.mark.parametrize("name", sorted(LOWERED))
def test_the_older_configurations_lower_as_they_did(name, tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("SLT_DATA_DIR", str(tmp_path))
    text = _lowered_text(name, tmp_path)
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[name]
