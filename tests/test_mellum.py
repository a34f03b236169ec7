"""The program's Mellum-2 decoder (``models/decoder.py``: windowed and full
attention, YaRN, a held share of experts) against the benchmark's plain
reference (``benchmarks/configs/mellum2_12b_c3.py``) on seeded weights at a
small size: logits, loss and first gradient; YaRN's frequencies against
their closed form for the published numbers."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.conftest import bench_reference

from split_learning_tpu.models import build_model
from split_learning_tpu.models import decoder
from split_learning_tpu.parallel.expert import moe_aux_loss

# 4 layers (one period), hidden 64, 4 query / 2 key-value heads of 16,
# 8 experts top-2 with 4 held, window 8 on rows of 32
TINY = dict(vocab_size=128, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
            sliding_window=8, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, experts_held=4)
SEQ = 32


REF = bench_reference("mellum2_12b_c3")


@pytest.fixture(scope="module")
def seeded():
    params, _ = REF.init(jax.random.key(3), TINY)
    ids = jax.random.randint(jax.random.key(4), (2, SEQ + 1), 0,
                             TINY["vocab_size"])
    return params, ids[:, :-1], ids[:, 1:]


def _objective(model):
    """Mean next-token cross-entropy plus the weighted load-balancing
    terms, as the pipeline forms it from what the layers sow."""
    def fn(params, x, y):
        logits, mut = model.apply({"params": params}, x,
                                  mutable=["intermediates"])
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return ce + REF.AUX_WEIGHT * moe_aux_loss(mut["intermediates"]), \
            (ce, logits)
    return fn


def _ref_objective(params, x, y):
    logits = REF.forward(params, {}, x, model_kwargs=TINY)
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
    return ce + REF.extra_objective(params, {}, x, None, None,
                                    model_kwargs=TINY), (ce, logits)


def _routers_drawn_whole(params):
    """``params`` with every router's kernel drawn whole: distinct
    columns, so a token's chosen weights are unequal and 0, 1 or 2 of its
    choices fall to the held experts."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jax.random.normal(jax.random.key(9), a.shape)
        if "router" in jax.tree_util.keystr(path) else a, params)


@pytest.mark.parametrize("routers", ["tiled", "whole"])
@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["einsum", "flash"])
def test_logits_loss_and_first_gradient_match_the_reference(
        seeded, use_flash, routers):
    params, x, y = seeded
    if routers == "whole":
        params = _routers_drawn_whole(params)
    model = build_model("Mellum2_TINYSTORIES", use_flash=use_flash,
                        flash_block=8, **TINY)
    (obj, (ce, logits)), grads = jax.value_and_grad(
        _objective(model), has_aux=True)(params, x, y)
    (obj_r, (ce_r, logits_r)), grads_r = jax.value_and_grad(
        _ref_objective, has_aux=True)(params, x, y)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(ce), float(ce_r), rtol=1e-6)
    np.testing.assert_allclose(float(obj), float(obj_r), rtol=1e-6)
    flat, flat_r = (dict(jax.tree_util.tree_leaves_with_path(g))
                    for g in (grads, grads_r))
    assert set(flat) == set(flat_r)
    for path, g in flat.items():
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_r[path]), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))


def test_the_tree_is_the_references_tree(seeded):
    params, x, _ = seeded
    model = build_model("Mellum2_TINYSTORIES", **TINY)
    mine = model.init(jax.random.key(0), x)["params"]
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa
    assert shapes(mine) == shapes(params)
    assert len(model.specs) == TINY["num_hidden_layers"] + 3


LLAMA_TINY = dict(vocab_size=128, hidden_size=32, num_heads=2,
                  num_kv_heads=1, intermediate_size=64, n_block=1)
ATTENTION = [f"attention/{p}_proj/kernel" for p in "qkvo"]
NORMS = ["input_norm/scale", "post_norm/scale"]
SWIGLU = ["gate_proj/kernel", "up_proj/kernel", "down_proj/kernel"]
# what the benchmark's reference writes (``configs/mellum2_12b_c3.py
# init``), ``tp_spec``/``ep_spec`` and ``learning.lora-targets`` match by
BLOCK_PATHS = {
    "TinyLlama_TINYSTORIES": (LLAMA_TINY, ATTENTION + NORMS + SWIGLU),
    "TinyLlamaMoE_TINYSTORIES": (
        {**LLAMA_TINY, "num_experts": 4},
        ATTENTION + NORMS + ["moe/router/kernel"]
        + [f"moe/experts/{p}" for p in SWIGLU]),
    "Mellum2_TINYSTORIES": (
        {**TINY, "num_hidden_layers": 1, "layer_types": ("full_attention",)},
        ATTENTION + NORMS + ["moe/router/kernel"]
        + [f"moe/experts/{p}" for p in SWIGLU]),
    # one sparse block: latent attention, held experts, a shared expert
    "Moonlight_TINYSTORIES": (
        dict(vocab_size=128, hidden_size=64, num_attention_heads=2,
             num_hidden_layers=1, first_k_dense_replace=0,
             moe_intermediate_size=16, n_routed_experts=4,
             num_experts_per_tok=2, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=4, v_head_dim=8, experts_held=2),
        [f"attention/{p}" for p in (
            "q_proj/kernel", "kv_a_proj_with_mqa/kernel",
            "kv_a_layernorm/scale", "kv_b_proj/kernel", "o_proj/kernel")]
        + NORMS + ["moe/router/kernel"]
        + [f"moe/experts/{p}" for p in SWIGLU]
        + [f"shared_experts/{p}" for p in SWIGLU]),
}


@pytest.mark.parametrize("name", sorted(BLOCK_PATHS))
def test_a_decoders_tree_has_these_paths(name):
    """Every registered decoder, one block: embedding, the block's leaves
    by their literal names, final norm, head."""
    kwargs, block = BLOCK_PATHS[name]
    tree = jax.eval_shape(
        build_model(name, **kwargs).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32))["params"]
    paths = {"/".join(k.key for k in path)
             for path, _ in jax.tree_util.tree_leaves_with_path(tree)}
    assert paths == {"layer1/embedding", *(f"layer2/{p}" for p in block),
                     "layer3/scale", "layer4/kernel"}


@pytest.mark.parametrize("what, kind, call", [
    ("layer type", "linear_attention", lambda kind: build_model(
        "Mellum2_TINYSTORIES", **{**TINY, "layer_types": (kind,) * 4})),
    ("feed-forward", "shared", lambda kind: decoder.decoder_specs(
        [(decoder.FULL, kind)], {decoder.FULL: {}}, {kind: {}},
        vocab_size=8, hidden_size=8, eps=1e-5)),
])
def test_a_kind_outside_the_tables_is_refused_with_the_known_ones(
        what, kind, call):
    table = decoder.MIXERS if what == "layer type" else decoder.FEED_FORWARDS
    with pytest.raises(ValueError) as e:
        call(kind)
    assert what in str(e.value) and kind in str(e.value)
    assert str(sorted(table)) in str(e.value)


def test_the_window_and_the_held_share_change_the_result(seeded):
    """The two mechanisms are not no-ops at this size: a model without
    the window, or holding all experts, gives other logits."""
    params, x, _ = seeded
    base = build_model("Mellum2_TINYSTORIES", **TINY).apply(
        {"params": params}, x)
    wide = build_model("Mellum2_TINYSTORIES",
                       **{**TINY, "sliding_window": SEQ}).apply(
        {"params": params}, x)
    assert float(jnp.abs(base - wide).max()) > 1e-3
    ref_all = REF.forward(params, {}, x,
                          model_kwargs={**TINY, "experts_held": (0, 1, 2, 3)})
    np.testing.assert_allclose(np.asarray(base), np.asarray(ref_all),
                               rtol=1e-4, atol=1e-5)
    # the reference's routers start as copies of one group's columns, so
    # every share sees the same tokens: with routers drawn whole, another
    # share of the same matrices gives another result
    drawn = _routers_drawn_whole(params)
    mine, other = (REF.forward(drawn, {}, x, model_kwargs={
        **TINY, "experts_held": held}) for held in ((0, 1, 2, 3),
                                                    (4, 5, 6, 7)))
    assert float(jnp.abs(mine - other).max()) > 1e-3


def test_the_references_routers_give_every_chip_one_choice_a_token(seeded):
    """``init``'s balanced routers: at the first step each token's
    choices are one expert of every chip's group, so the held share's
    pairs are the tokens, whatever the seed."""
    from split_learning_tpu.parallel.pipeline import (
        COUNTER_FOLDS, sown_counters,
    )
    params, x, _ = seeded
    for block in range(2, 6):
        kernel = np.asarray(params[f"layer{block}"]["moe"]["router"]["kernel"])
        np.testing.assert_array_equal(kernel[:, :4], kernel[:, 4:])
    _, mut = build_model("Mellum2_TINYSTORIES", **TINY).apply(
        {"params": params}, x, mutable=list(COUNTER_FOLDS))
    pairs = float(sown_counters(mut)["counters_sum"]["moe_pairs_held"])
    assert pairs == x.size * TINY["num_hidden_layers"]
    assert float(np.asarray(params["layer1"]["embedding"]).std()) > 0.9


# -- YaRN for this configuration's numbers ----------------------------------

PUBLISHED = decoder.ROPE_PARAMETERS[decoder.FULL]


def _closed_form(i):
    """inv_freq_i from the issue's equations, one index at a time."""
    d, theta, factor, length = 128, 500000.0, 16.0, 8192

    def cd(r):
        return d * math.log(length / (2 * math.pi * r)) \
            / (2 * math.log(theta))
    low, high = max(math.floor(cd(32)), 0), min(math.ceil(cd(1)), d - 1)
    extrap = theta ** (-2 * i / d)
    ramp = min(max((i - low) / (high - low), 0.0), 1.0)
    return extrap / factor * ramp + extrap * (1 - ramp)


@pytest.mark.parametrize("where", ["program", "reference"])
def test_yarn_frequencies_match_the_closed_form(where):
    fn = decoder.yarn_inv_freq if where == "program" else REF.yarn_inv_freq
    got = fn(128, **{k: v for k, v in PUBLISHED.items()
                     if k != "rope_type"})
    want = np.array([_closed_form(i) for i in range(64)])
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # for these numbers the blend runs over indices 17..35: the fastest
    # frequencies are kept, the slowest divided by 16
    assert got[0] == 1.0 and got[17] == want[17] == 500000.0 ** (-34 / 128)
    np.testing.assert_allclose(got[35:], 500000.0 ** (
        -2 * np.arange(35, 64) / 128) / 16, rtol=1e-12)


def test_attention_factor_scales_cos_and_sin_of_full_layers_only():
    assert PUBLISHED["attention_factor"] == 1.2772588722239782
    # 0.1 ln(16) + 1: YaRN's own rule for factor 16
    np.testing.assert_allclose(PUBLISHED["attention_factor"],
                               0.1 * math.log(16) + 1, rtol=1e-15)
    inv_full, f_full = decoder.rope_of(decoder.FULL, 128,
                                       decoder.ROPE_PARAMETERS)
    inv_win, f_win = decoder.rope_of(decoder.SLIDING, 128,
                                     decoder.ROPE_PARAMETERS)
    assert f_full == PUBLISHED["attention_factor"] and f_win == 1.0
    np.testing.assert_allclose(
        inv_win, 500000.0 ** (-2 * np.arange(64) / 128), rtol=1e-12)
    assert not np.allclose(inv_full, inv_win)
    x = jax.random.normal(jax.random.key(0), (1, 4, 2, 128))
    turned = decoder.rope(x, jnp.arange(4), inv_full, interleaved=False,
                          factor=f_full)
    # position 0 is not turned, only scaled by the factor
    np.testing.assert_allclose(np.asarray(turned[:, 0]),
                               np.asarray(x[:, 0]) * f_full, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(turned), np.asarray(REF._rope(
            x, decoder.FULL, decoder.ROPE_PARAMETERS)), rtol=1e-5, atol=1e-6)


# -- through the compiled pipeline step -----------------------------------------

def _step_outputs(model_name, kwargs, seq=SEQ, mb=2, m=2):
    from split_learning_tpu.parallel.mesh import make_mesh
    from split_learning_tpu.parallel.pipeline import (
        PipelineModel, make_train_step, shard_to_mesh, stack_for_clients,
    )
    pipe = PipelineModel(
        model_name, cuts=[3],
        example_input=jax.ShapeDtypeStruct((mb, seq), jnp.int32),
        num_microbatches=m, model_kwargs=kwargs,
        moe_aux_weight=REF.AUX_WEIGHT)
    mesh = make_mesh(1, 1, jax.devices()[:1])
    opt = optax.sgd(0.1)
    step = make_train_step(pipe, opt, mesh, donate=False)
    params = pipe.full_model.init(
        jax.random.key(0), jnp.zeros((mb, seq), jnp.int32))["params"]
    ids = jax.random.randint(jax.random.key(1), (1, m, mb, seq + 1), 0,
                             kwargs["vocab_size"])
    place = lambda t: shard_to_mesh(stack_for_clients(t, 1), mesh)  # noqa
    out = step(place(params), place(opt.init(params)), {},
               ids[..., :-1], ids[..., 1:],
               jax.vmap(jax.random.key)(jnp.arange(1)))
    return pipe, params, ids, out


def test_the_step_hands_back_what_the_expert_layers_counted():
    """Summed pairs and the largest load ratio over blocks and
    microbatches, as a plain apply of the same weights counts them."""
    from split_learning_tpu.parallel.pipeline import (
        COUNTER_FOLDS, sown_counters,
    )
    pipe, params, ids, out = _step_outputs("Mellum2_TINYSTORIES", TINY)
    got = out[4]
    assert {col: sorted(names) for col, names in got.items()} == {
        "counters_sum": ["moe_gmm_rows", "moe_overflow_passes",
                         "moe_pairs_held"],
        "counters_max": ["moe_load_max_over_mean"]}
    pairs, load, gmm_rows = 0.0, 0.0, 0.0
    for mb in range(ids.shape[1]):
        _, mut = pipe.full_model.apply({"params": params},
                                       ids[0, mb, :, :-1],
                                       mutable=list(COUNTER_FOLDS))
        count = sown_counters(mut)
        pairs += float(count["counters_sum"]["moe_pairs_held"])
        gmm_rows += float(count["counters_sum"]["moe_gmm_rows"])
        load = max(load, float(
            count["counters_max"]["moe_load_max_over_mean"]))
    assert float(got["counters_sum"]["moe_pairs_held"][0]) == pairs > 0
    assert float(got["counters_sum"]["moe_gmm_rows"][0]) == gmm_rows >= pairs
    # 4 of 8 experts held: the common pass has the worst case's rows, and
    # no call can overflow it
    assert float(got["counters_sum"]["moe_overflow_passes"][0]) == 0.0
    np.testing.assert_allclose(
        np.asarray(got["counters_max"]["moe_load_max_over_mean"])[0], load,
        rtol=1e-6)


@pytest.mark.parametrize("flash_block", [8, 16])
def test_the_step_hands_back_what_the_flash_kernels_walk(flash_block):
    """``flash_pairs_*`` summed over the four layers (three windowed, one
    full) and the microbatches: rows x heads x what one head's forward
    walks under the call's tiling."""
    from split_learning_tpu.ops.flash_attention import forward_pairs, tiling
    _, _, ids, out = _step_outputs(
        "Mellum2_TINYSTORIES",
        dict(TINY, use_flash=True, flash_block=flash_block))
    got = {name: float(v[0]) for name, v in out[4]["counters_sum"].items()
           if name.startswith("flash_pairs_")}
    m, mb = ids.shape[1:3]
    want = np.zeros(3)
    for window in (TINY["sliding_window"],) * 3 + (None,):
        tile = tiling("fwd", SEQ, window, flash_block, flash_block)
        want += np.array(forward_pairs(SEQ, window, tile)) * m * mb \
            * TINY["num_attention_heads"]
    assert got == dict(zip(("flash_pairs_seen", "flash_pairs_visited",
                            "flash_pairs_masked"), want))
    assert got["flash_pairs_seen"] <= got["flash_pairs_visited"]


def test_a_model_that_sows_no_counter_hands_back_an_empty_tree():
    pipe, _, _, out = _step_outputs(
        "TinyLlama_TINYSTORIES",
        dict(vocab_size=128, hidden_size=32, num_heads=2, num_kv_heads=1,
             intermediate_size=64, n_block=4))
    assert pipe.counters0 == {} and out[4] == {}
