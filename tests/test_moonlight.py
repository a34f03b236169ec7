"""The program's Moonlight decoder (``models/decoder.py``: latent attention,
a dense first block, shared experts beside a held share of sigmoid-routed
ones) against the benchmark's plain reference
(``benchmarks/configs/moonlight_16b_c3.py``) on seeded weights at a small
size: logits, loss and first gradient; the latent mixer alone; the buffer
``e_score_correction_bias`` through a step, FedAvg and a checkpoint; a
resume's copies of the tree."""

import gc
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.conftest import bench_reference

from split_learning_tpu.models import build_model, decoder
from split_learning_tpu.parallel.expert import moe_aux_loss

ROOT = pathlib.Path(__file__).resolve().parent.parent
# a dense block and four sparse ones, hidden 128, 4 heads scoring over
# 16 + 8 and weighing values of 16, a latent of 32; 8 sigmoid-routed
# experts top-2 with 2 held (four chips), 2 shared
TINY = dict(vocab_size=128, hidden_size=128, num_attention_heads=4,
            num_hidden_layers=5, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, experts_held=2)
SEQ = 32
REF = bench_reference("moonlight_16b_c3")


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


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["einsum", "flash"])
def test_logits_loss_and_first_gradient_match_the_reference(
        seeded, use_flash):
    params, stats, x, y = seeded
    model = build_model("Moonlight_TINYSTORIES", use_flash=use_flash,
                        flash_block=8, **TINY)
    (obj, (ce, logits)), grads = jax.value_and_grad(
        _objective(model), has_aux=True)(params, stats, x, y)
    (obj_r, (ce_r, logits_r)), grads_r = jax.value_and_grad(
        _ref_objective, has_aux=True)(params, stats, x, y)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_r),
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(float(ce), float(ce_r), rtol=1e-6)
    np.testing.assert_allclose(float(obj), float(obj_r), rtol=1e-6)
    flat, flat_r = (dict(jax.tree_util.tree_leaves_with_path(g))
                    for g in (grads, grads_r))
    assert set(flat) == set(flat_r)
    for path, g in flat.items():
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_r[path]), rtol=2e-3, atol=3e-6,
            err_msg=jax.tree_util.keystr(path))


def test_the_reference_in_token_blocks_is_the_reference_whole(
        seeded, monkeypatch):
    """At the cell's size the reference takes its tokens through a matrix
    ``TOKEN_BLOCK`` at a time; here 16 of a microbatch's 64: the same
    logits, objective and gradients as with every token at once."""
    params, stats, x, y = seeded
    fn = jax.value_and_grad(_ref_objective, has_aux=True)
    (obj, (_, logits)), grads = fn(params, stats, x, y)
    monkeypatch.setattr(REF, "TOKEN_BLOCK", 16)
    REF._LAST.clear()
    text = jax.make_jaxpr(fn)(params, stats, x, y).pretty_print()
    assert "scan" in text and "16,128" in text
    (obj_b, (_, logits_b)), grads_b = fn(params, stats, x, y)
    np.testing.assert_allclose(np.asarray(logits_b), np.asarray(logits),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(obj_b), float(obj), rtol=1e-6)
    for (path, g), g_b in zip(jax.tree_util.tree_leaves_with_path(grads),
                              jax.tree_util.tree_leaves(grads_b)):
        np.testing.assert_allclose(
            np.asarray(g_b), np.asarray(g), rtol=1e-4, atol=1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_trees_are_the_references_trees(seeded):
    """Parameters and buffers: a mixed stack (dense, then sparse with a
    shared expert) under the names the reference writes."""
    params, stats, x, _ = seeded
    model = build_model("Moonlight_TINYSTORIES", **TINY)
    mine = model.init(jax.random.key(0), x)
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa
    assert shapes(mine["params"]) == shapes(params)
    assert shapes(mine["batch_stats"]) == shapes(stats)
    assert len(model.specs) == TINY["num_hidden_layers"] + 3
    assert "gate_proj" in params["layer2"] and "moe" not in params["layer2"]
    assert {"moe", "shared_experts"} <= set(params["layer3"])
    assert set(stats) == {"layer3", "layer4", "layer5", "layer6"}


def test_the_tiled_state_holds_three_pairs_a_token_over_four_blocks():
    """The configuration's construction at the toy's shape (four chips of
    two experts, two a token): every token's best column is the same in
    all four sparse blocks, and the bias leaves chip 0 out of it in
    exactly two of them, whatever the key and the stream."""
    from split_learning_tpu.parallel.pipeline import COUNTER_FOLDS
    model = build_model("Moonlight_TINYSTORIES", **TINY)
    for seed in (0, 1, 2):
        params, stats = REF.init(jax.random.key(seed), TINY)
        x = jax.random.randint(jax.random.key(10 + seed), (2, SEQ), 0,
                               TINY["vocab_size"])
        _, mut = model.apply({"params": params, "batch_stats": stats}, x,
                             mutable=list(COUNTER_FOLDS))
        pairs = [float(v["moe"]["moe_pairs_held"][0])
                 for _, v in sorted(mut["counters_sum"].items())]
        assert sum(pairs) == 2 * x.size, pairs


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["einsum", "flash"])
def test_the_latent_mixer_alone_matches_the_references(use_flash):
    """Unequal score and value widths (24 and 16): forward and all
    gradients of the mixer against the reference's ``latent_attention``;
    and the rotary part counts (the oracle without it reads otherwise)."""
    s = REF.sizes(TINY)
    params, _ = REF.init(jax.random.key(5), TINY)
    a = params["layer3"]["attention"]
    n = jax.random.normal(jax.random.key(6), (2, SEQ, TINY["hidden_size"]))
    mixer = decoder.MIXERS[decoder.LATENT](
        hidden_size=s["hidden_size"], num_heads=s["num_attention_heads"],
        kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        rope_theta=s["rope_theta"], use_flash=use_flash, flash_block=8)
    mm = lambda eq, x, y: jnp.einsum(  # noqa: E731
        eq, x, y, precision=jax.lax.Precision.HIGHEST)

    def mine(a, n):
        return mixer.apply({"params": a}, n)

    def ref(a, n, rotary=True):
        return REF.latent_attention(a, n, s, mm, rotary)
    w = jax.random.normal(jax.random.key(7), n.shape)
    got, g = jax.value_and_grad(
        lambda a, n: (mine(a, n) * w).sum(), argnums=(0, 1))(a, n)
    want, g_r = jax.value_and_grad(
        lambda a, n: (ref(a, n) * w).sum(), argnums=(0, 1))(a, n)
    np.testing.assert_allclose(np.asarray(mine(a, n)),
                               np.asarray(ref(a, n)), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=2e-3, atol=2e-5), g, g_r)
    off = float(jnp.abs(ref(a, n) - ref(a, n, rotary=False)).max())
    assert off > 100 * float(jnp.abs(mine(a, n) - ref(a, n)).max())


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["einsum", "flash"])
def test_the_latent_mixer_sows_what_its_kernel_walks(use_flash):
    """``flash_pairs_*`` of one call (rows x heads x one head's causal
    row), and nothing where the einsum runs."""
    from split_learning_tpu.ops.flash_attention import forward_pairs, tiling
    from split_learning_tpu.parallel.pipeline import (
        COUNTER_FOLDS, sown_counters,
    )
    s = REF.sizes(TINY)
    params, _ = REF.init(jax.random.key(5), TINY)
    mixer = decoder.MIXERS[decoder.LATENT](
        hidden_size=s["hidden_size"], num_heads=s["num_attention_heads"],
        kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        rope_theta=s["rope_theta"], use_flash=use_flash, flash_block=8)
    _, mut = mixer.apply({"params": params["layer3"]["attention"]},
                         jnp.zeros((2, SEQ, TINY["hidden_size"])),
                         mutable=list(COUNTER_FOLDS))
    got = {k: float(v) for k, v in sown_counters(mut).get(
        "counters_sum", {}).items()}
    pairs = forward_pairs(SEQ, None, tiling("fwd", SEQ, None, 8, 8))
    assert got == ({f"flash_pairs_{name}": 2.0 * s["num_attention_heads"] * n
                    for name, n in zip(("seen", "visited", "masked"), pairs)}
                   if use_flash else {})


def test_what_has_no_module_is_refused():
    for kw in (dict(q_lora_rank=1536), dict(n_group=8, topk_group=4),
               dict(norm_topk_prob=False), dict(topk_method="group")):
        with pytest.raises(ValueError, match="no module"):
            build_model("Moonlight_TINYSTORIES", **{**TINY, **kw})


def test_the_configuration_says_what_the_program_is_given():
    """The reference's weight of the load-balancing term is the one the
    YAML hands the program, the YAML keeps every published width, it is
    JSON as well as YAML, and the tree has the parameters it states."""
    import yaml
    path = ROOT / "benchmarks" / "configs" / "moonlight_16b_c3.yaml"
    conf = yaml.safe_load(path.read_text())
    assert conf == json.loads(path.read_text())
    program = conf["program"]
    assert program["learning"]["moe-aux-weight"] == REF.AUX_WEIGHT
    kw = program["model-kwargs"]
    for key in ("hidden_size", "num_attention_heads", "intermediate_size",
                "moe_intermediate_size", "n_shared_experts",
                "num_experts_per_tok", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "rope_theta",
                "rms_norm_eps", "routed_scaling_factor",
                "first_k_dense_replace", "moe_layer_freq"):
        assert kw[key] == conf[key] == REF.SIZES[key], key
    for key in ("scoring_func", "topk_method", "norm_topk_prob", "n_group",
                "topk_group", "q_lora_rank"):
        assert kw[key] == conf[key], key
    # the router keeps its published width; 8 experts are held
    assert kw["n_routed_experts"] == conf["published"]["n_routed_experts"] \
        == 64
    assert kw["experts_held"] == conf["n_routed_experts"] == 8
    assert kw["vocab_size"] == conf["vocab_size"] == 163840 // 8
    assert kw["num_hidden_layers"] == conf["num_hidden_layers"] == 5
    assert set(conf["reduced"]) >= {"num_hidden_layers", "n_routed_experts",
                                    "vocab_size"}
    shapes = jax.eval_shape(lambda k: REF.init(k, kw), jax.random.key(0))
    count = lambda t: sum(a.size for a in jax.tree_util.tree_leaves(t))  # noqa
    held = conf["held-here"]
    assert count(shapes[0]) == held["parameters"] == 568484352
    assert count(shapes[0]["layer2"]) == held["dense_block"]
    assert count(shapes[0]["layer3"]) == held["sparse_block"]
    assert count(shapes[0]["layer3"]["attention"]) \
        == held["attention_a_block"]
    assert count(shapes[1]) == 4 * 64
    # the program builds the same trees from the same keywords
    model = build_model("Moonlight_TINYSTORIES", **kw)
    mine = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    as_shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa
    assert as_shapes(mine["params"]) == as_shapes(shapes[0])
    assert as_shapes(mine["batch_stats"]) == as_shapes(shapes[1])


# -- through the normal path: run_local, FedAvg, the checkpoint, a resume ------

@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A fresh start of three rounds and a resume of two more through
    ``run_local`` (one client a stage, AdamW, FedAvg, validation, a
    checkpoint a round) from the reference's weights with routers and
    biases drawn whole; at every round's end it is noted whether the
    round's INPUT tree is still alive on the device."""
    import dataclasses
    import os
    import weakref
    from split_learning_tpu.config import from_dict
    from split_learning_tpu.run import run_local
    from split_learning_tpu.runtime import context, strategies
    from split_learning_tpu.runtime.checkpoint import (
        load_checkpoint, save_checkpoint,
    )
    from split_learning_tpu.runtime.log import Logger
    tmp = tmp_path_factory.mktemp("moonlight")
    rows = np.random.default_rng(0).integers(
        0, TINY["vocab_size"], size=(24, SEQ + 1)).astype(np.int32)
    (tmp / "data" / "TinyStories").mkdir(parents=True)
    np.save(tmp / "data" / "TinyStories" / "train.npy", rows[:16])
    np.save(tmp / "data" / "TinyStories" / "valid.npy", rows[16:])
    before = os.environ.get("SLT_DATA_DIR")
    os.environ["SLT_DATA_DIR"] = str(tmp / "data")
    cfg = from_dict(dict(
        model="Moonlight", dataset="TINYSTORIES", clients=[1, 1],
        global_rounds=3, val_batch_size=4, compute_dtype="float32",
        model_kwargs=TINY, log_path=str(tmp / "logs"),
        learning={"batch_size": 2, "control_count": 2, "optimizer": "adamw",
                  "learning_rate": 1e-3, "weight_decay": 0.1,
                  "moe_aux_weight": REF.AUX_WEIGHT},
        distribution={"num_samples": 8}, topology={"cut_layers": [3]},
        checkpoint={"directory": str(tmp / "ckpt"), "save": True,
                    "load": True, "validate": True}))
    params, stats = REF.init(jax.random.key(8), dict(TINY, routers="whole"))
    params, stats = jax.device_get((params, stats))
    save_checkpoint(cfg.checkpoint.directory, cfg.model_key, params, stats, 0)
    inputs = []         # of each round: a weak reference to a leaf of its input
    run_round = strategies.FedAvgStrategy.run_round

    def noting_its_input(self, ctx, plans, r, tree, stats):
        leaf = tree["layer1"]["embedding"]
        inputs.append(weakref.ref(leaf) if isinstance(leaf, jax.Array)
                      else None)            # a loaded checkpoint: the host's
        return run_round(self, ctx, plans, r, tree, stats)

    class Counting(Logger):
        alive: list = []

        def metric(self, **fields):
            if fields.get("kind", "round") == "round":
                # the writer of the last round's checkpoint may hold that
                # round's tree (this round's input) for a moment yet
                for _ in range(40):
                    gc.collect()
                    if inputs[-1] is None or inputs[-1]() is None:
                        break
                    time.sleep(0.05)
                self.alive.append(inputs[-1] is not None
                                  and inputs[-1]() is not None)
            super().metric(**fields)

    def drive(rounds):
        Counting.alive = []
        logger = Counting.for_run(cfg, "server", console=False)
        try:
            result = run_local(dataclasses.replace(
                cfg, global_rounds=rounds), logger=logger)
        finally:
            logger.close()
        return result, list(Counting.alive)
    context._GLOBAL_STEP_CACHE.clear()
    strategies.FedAvgStrategy.run_round = noting_its_input
    try:
        fresh, fresh_counts = drive(3)
        del fresh
        resumed, resumed_counts = drive(5)
        back = load_checkpoint(cfg.checkpoint.directory, cfg.model_key)
    finally:
        strategies.FedAvgStrategy.run_round = run_round
        context._GLOBAL_STEP_CACHE.clear()
        if before is None:
            os.environ.pop("SLT_DATA_DIR")
        else:
            os.environ["SLT_DATA_DIR"] = before
    return {"params0": params, "stats0": stats, "result": resumed,
            "checkpoint": back,
            "fresh_alive": fresh_counts, "resumed_alive": resumed_counts}


def test_the_bias_is_carried_and_not_trained(trained):
    """Five rounds of AdamW with weight decay, FedAvg and checkpoints:
    every parameter has moved (the routers among them), the buffer
    ``e_score_correction_bias`` is what the first checkpoint held, in the
    result and in the last checkpoint read back."""
    result, back = trained["result"], trained["checkpoint"]
    assert [r.ok for r in result.history] == [True, True]
    assert back["round_idx"] == 5
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        result.params, trained["params0"])
    assert min(jax.tree_util.tree_leaves(moved)) > 0
    for tree in (result.stats, back["batch_stats"]):
        assert set(tree) == {"layer3", "layer4", "layer5", "layer6"}
        for name, layer in tree.items():
            want = trained["stats0"][name]["moe"]["e_score_correction_bias"]
            assert np.abs(want).max() > 0.01
            np.testing.assert_allclose(
                np.asarray(layer["moe"]["e_score_correction_bias"]), want,
                rtol=0, atol=1e-7)


def test_a_resume_holds_no_more_copies_of_the_tree_than_a_fresh_start(
        trained):
    """At a round's end nothing holds the round's INPUT tree any more, in
    a fresh start and in a resume alike: the loop kept it (for the
    rollback after a failed validation) through the whole next round, one
    more copy of the parameters on the device than training needs.  A
    round whose input came from a checkpoint has it on the host."""
    assert trained["fresh_alive"] == [False, False, False]
    assert trained["resumed_alive"] == [False, False]
