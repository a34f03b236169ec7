"""The program's ``nemotron_h`` tower (``models/decoder.py``: Mamba-2 mixers
over the chunked scan, one grouped-query attention layer without rotary
embedding, ``relu2`` experts beside a shared one, every layer a mixer OR a
feed-forward alone) against the benchmark's plain reference
(``benchmarks/configs/nemotron_twotower_30b_c5.py``, whose recurrence runs a
position at a time) on seeded weights at a small size: logits, loss and
first gradient; the Mamba-2 mixer alone; the trees by name; the
configuration's file; a run through ``run_local``."""

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
# one period: three Mamba-2 layers (8 heads of 8 in 2 groups, a state of
# 16, chunks of 8), one attention layer (4 query heads over 2 key-value
# heads of 16), three expert layers (8 sigmoid-routed relu2 experts top-2
# with 2 held: four chips; a shared one), hidden 128
TINY = dict(vocab_size=128, hidden_size=128, num_hidden_layers=7,
            hybrid_override_pattern="MEMEM*E", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
            moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=48, n_routed_experts=8,
            num_experts_per_tok=2, experts_held=2)
SEQ = 28        # three chunks and a half
REF = bench_reference("nemotron_twotower_30b_c5")


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


def _ref_objective(params, stats, x, y, kw=TINY):
    logits = REF.forward(params, stats, x, model_kwargs=kw)
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
    return ce + REF.extra_objective(params, stats, x, None, None,
                                    model_kwargs=kw), (ce, logits)


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
    model = build_model("NemotronH_TINYSTORIES", use_flash=use_flash,
                        flash_block=4, **TINY)
    (obj, (ce, logits)), grads = jax.value_and_grad(
        _objective(model), has_aux=True)(params, stats, x, y)
    (obj_r, (ce_r, logits_r)), grads_r = jax.value_and_grad(
        _ref_objective, has_aux=True)(params, stats, x, y)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_r),
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(float(ce), float(ce_r), rtol=1e-6)
    np.testing.assert_allclose(float(obj), float(obj_r), rtol=1e-6)
    _assert_trees_close(grads, grads_r, rtol=2e-3, atol=3e-6)


def test_the_reference_as_one_scan_is_the_reference_layer_by_layer(
        seeded, monkeypatch):
    """The period ``MEMEM*E`` runs as one ``lax.scan`` over (M, [*], E)
    with the attention layer under a ``cond`` (each kind of layer traced
    once); layer by layer in a plain loop it gives the same logits,
    objective and gradients."""
    params, stats, x, y = seeded
    fn = jax.value_and_grad(_ref_objective, has_aux=True)
    assert "cond" in jax.make_jaxpr(fn)(params, stats, x, y).pretty_print()
    (obj, (_, logits)), grads = fn(params, stats, x, y)
    monkeypatch.setattr(REF, "ONE_SCAN", False)
    REF._LAST.clear()
    # a fresh wrapper: ``make_jaxpr`` keeps the trace of the one it has seen
    fn = jax.value_and_grad(_ref_objective, has_aux=True)
    assert "cond" not in jax.make_jaxpr(fn)(
        params, stats, x, y).pretty_print()
    (obj_l, (_, logits_l)), grads_l = fn(params, stats, x, y)
    np.testing.assert_allclose(np.asarray(logits_l), np.asarray(logits),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(obj_l), float(obj), rtol=1e-6)
    _assert_trees_close(grads_l, grads, rtol=1e-4, atol=1e-7)


def test_the_trees_are_the_references_trees_one_norm_a_layer(seeded):
    """Parameters and buffers under the names the reference writes; a layer
    with an empty half has ONE norm and one sublayer."""
    params, stats, x, _ = seeded
    model = build_model("NemotronH_TINYSTORIES", **TINY)
    mine = model.init(jax.random.key(0), x)
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa
    assert shapes(mine["params"]) == shapes(params)
    assert shapes(mine["batch_stats"]) == shapes(stats)
    assert len(model.specs) == TINY["num_hidden_layers"] + 3
    for i, kind in enumerate(TINY["hybrid_override_pattern"]):
        layer = mine["params"][f"layer{i + 2}"]
        if kind == "E":
            assert set(layer) == {"post_norm", "moe", "shared_experts"}
            assert set(layer["moe"]["experts"]) == {"up_proj", "down_proj"}
            assert set(layer["shared_experts"]) == {"up_proj", "down_proj"}
        else:
            assert set(layer) == {"input_norm", "attention"}
            assert set(layer["attention"]) == (
                {"q_proj", "k_proj", "v_proj", "o_proj"} if kind == "*"
                else {"in_proj", "conv_kernel", "conv_bias", "dt_bias",
                      "A_log", "D", "norm_scale", "out_proj"})
    assert set(stats) == {"layer3", "layer5", "layer8"}


SIBLINGS = {
    "Mellum2_TINYSTORIES": dict(
        vocab_size=64, hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, num_hidden_layers=4,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
        experts_held=2),
    "Moonlight_TINYSTORIES": dict(
        vocab_size=64, hidden_size=32, num_attention_heads=2,
        num_hidden_layers=2, intermediate_size=24,
        moe_intermediate_size=8, n_routed_experts=4, num_experts_per_tok=2,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, experts_held=2),
    "TinyLlama_TINYSTORIES": dict(
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
        intermediate_size=48, n_block=2),
    "TinyLlamaMoE_TINYSTORIES": dict(
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
        intermediate_size=48, n_block=2, num_experts=4),
}
SIBLING_TREES = {
    "Mellum2_TINYSTORIES": {
        "attention/k_proj/kernel": (32, 16),
        "attention/o_proj/kernel": (32, 32),
        "attention/q_proj/kernel": (32, 32),
        "attention/v_proj/kernel": (32, 16),
        "input_norm/scale": (32,), "post_norm/scale": (32,),
        "moe/experts/down_proj/kernel": (2, 16, 32),
        "moe/experts/gate_proj/kernel": (2, 32, 16),
        "moe/experts/up_proj/kernel": (2, 32, 16),
        "moe/router/kernel": (32, 4)},
    "Moonlight_TINYSTORIES": {
        "attention/kv_a_layernorm/scale": (8,),
        "attention/kv_a_proj_with_mqa/kernel": (32, 12),
        "attention/kv_b_proj/kernel": (8, 32),
        "attention/o_proj/kernel": (16, 32),
        "attention/q_proj/kernel": (32, 24),
        "input_norm/scale": (32,), "post_norm/scale": (32,),
        "moe/experts/down_proj/kernel": (2, 8, 32),
        "moe/experts/gate_proj/kernel": (2, 32, 8),
        "moe/experts/up_proj/kernel": (2, 32, 8),
        "moe/router/kernel": (32, 4),
        "shared_experts/down_proj/kernel": (16, 32),
        "shared_experts/gate_proj/kernel": (32, 16),
        "shared_experts/up_proj/kernel": (32, 16)},
    "TinyLlama_TINYSTORIES": {
        "attention/k_proj/kernel": (32, 16),
        "attention/o_proj/kernel": (32, 32),
        "attention/q_proj/kernel": (32, 32),
        "attention/v_proj/kernel": (32, 16),
        "input_norm/scale": (32,), "post_norm/scale": (32,),
        "down_proj/kernel": (48, 32), "gate_proj/kernel": (32, 48),
        "up_proj/kernel": (32, 48)},
    "TinyLlamaMoE_TINYSTORIES": {
        "attention/k_proj/kernel": (32, 16),
        "attention/o_proj/kernel": (32, 32),
        "attention/q_proj/kernel": (32, 32),
        "attention/v_proj/kernel": (32, 16),
        "input_norm/scale": (32,), "post_norm/scale": (32,),
        "moe/experts/down_proj/kernel": (4, 48, 32),
        "moe/experts/gate_proj/kernel": (4, 32, 48),
        "moe/experts/up_proj/kernel": (4, 32, 48),
        "moe/router/kernel": (32, 4)},
}


@pytest.mark.parametrize("name", sorted(SIBLINGS))
def test_a_sibling_builders_last_block_is_what_it_was(name):
    """The pair with an empty half changed no builder that has none: the
    last block's parameters by name and shape, as written down at the
    parent commit."""
    model = build_model(name, **SIBLINGS[name])
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))["params"]
    n_layers = len(model.specs)
    block = shapes[f"layer{n_layers - 2}"]
    got = {"/".join(str(p.key) for p in path): leaf.shape for path, leaf
           in jax.tree_util.tree_leaves_with_path(block)}
    assert got == SIBLING_TREES[name]
    assert set(shapes[f"layer{n_layers - 1}"]) == {"scale"}
    assert set(shapes[f"layer{n_layers}"]) == {"kernel"}


def _mixer(**over):
    s = REF.sizes(TINY)
    return decoder.MIXERS[decoder.MAMBA2](**{**dict(
        hidden_size=s["hidden_size"], num_heads=s["mamba_num_heads"],
        head_dim=s["mamba_head_dim"], n_groups=s["n_groups"],
        ssm_state_size=s["ssm_state_size"], conv_kernel=s["conv_kernel"],
        chunk_size=s["chunk_size"], eps=s["layer_norm_epsilon"]), **over})


MM = lambda eq, x, y: jnp.einsum(  # noqa: E731
    eq, x, y, precision=jax.lax.Precision.HIGHEST)


@pytest.fixture(scope="module")
def mixer_case():
    params, _ = REF.init(jax.random.key(5), TINY)
    a = dict(params["layer4"]["attention"])
    # the published initial values leave little for a test to see (D_t
    # A of a few thousandths, unit scales): livelier ones
    a["dt_bias"] = a["dt_bias"] + 4.0
    a["norm_scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.key(9), a["norm_scale"].shape)
    a["D"] = a["D"] + 0.3 * jax.random.normal(jax.random.key(10),
                                              a["D"].shape)
    n = jax.random.normal(jax.random.key(6), (2, SEQ, TINY["hidden_size"]))
    w = jax.random.normal(jax.random.key(7), n.shape)
    return a, n, w


def test_the_mamba2_mixer_alone_matches_the_references(mixer_case):
    """Forward and all gradients of the mixer (the chunked scan, a row of
    three chunks and a half) against the reference's, whose recurrence runs
    a position at a time."""
    a, n, w = mixer_case
    s = REF.sizes(TINY)
    mixer = _mixer()
    mine = lambda a, n: mixer.apply({"params": a}, n)  # noqa: E731
    ref = lambda a, n: REF.mamba2(a, n, s, MM)  # noqa: E731
    np.testing.assert_allclose(np.asarray(mine(a, n)),
                               np.asarray(ref(a, n)), rtol=1e-4, atol=1e-6)
    g = jax.grad(lambda a, n: (mine(a, n) * w).sum(), argnums=(0, 1))(a, n)
    g_r = jax.grad(lambda a, n: (ref(a, n) * w).sum(), argnums=(0, 1))(a, n)
    _assert_trees_close(g, g_r, rtol=2e-3, atol=2e-6)


@pytest.mark.parametrize("fault", ["gate_after_norm", "wrong_group"])
def test_the_mixer_test_would_catch(mixer_case, fault):
    """The gate applied after the norm, and heads reading another group's
    ``B`` and ``C``: each oracle reads far from the program, which agrees
    with the sound reference."""
    a, n, _ = mixer_case
    s = REF.sizes(TINY)
    mine = _mixer().apply({"params": a}, n)
    sound = REF.mamba2(a, n, s, MM)
    faulty = REF.mamba2(a, n, s, MM, gate_before_norm=False) \
        if fault == "gate_after_norm" \
        else REF.mamba2(a, n, s, MM, group_of_head=jnp.asarray([1, 0]))
    off = float(jnp.abs(faulty - sound).max())
    assert off > 100 * float(jnp.abs(mine - sound).max())
    assert off > 0.01 * float(jnp.abs(sound).max())


def test_attention_turns_nothing_where_its_rope_is_none():
    """``rope_type`` ``none``: the scores are of the projections as they
    are; the same module under a default RoPE reads otherwise."""
    s = REF.sizes(TINY)
    params, _ = REF.init(jax.random.key(5), TINY)
    a = params["layer7"]["attention"]
    n = jax.random.normal(jax.random.key(6), (2, SEQ, TINY["hidden_size"]))
    kw = dict(hidden_size=s["hidden_size"],
              num_heads=s["num_attention_heads"],
              num_kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"])
    plain = decoder.MIXERS[decoder.FULL](
        rope_parameters={decoder.FULL: {"rope_type": "none"}}, **kw)
    turned = decoder.MIXERS[decoder.FULL](
        rope_parameters={decoder.FULL: {"rope_theta": 10000.0}}, **kw)
    want = REF.attention(a, n, s, MM)
    got = plain.apply({"params": a}, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-7)
    off = float(jnp.abs(turned.apply({"params": a}, n) - want).max())
    assert off > 100 * float(jnp.abs(got - want).max())


def test_what_has_no_module_is_refused():
    for kw in (dict(n_group=8, topk_group=4), dict(norm_topk_prob=False),
               dict(n_shared_experts=2),
               dict(hybrid_override_pattern="MEMEM-E"),
               dict(num_hidden_layers=8)):
        with pytest.raises(ValueError, match="no module"):
            build_model("NemotronH_TINYSTORIES", **{**TINY, **kw})


def test_the_tiled_state_holds_the_deployments_share():
    """The configuration's construction at its own shape (sixteen chips of
    eight experts, six a token, three expert layers; a narrow state): a
    token's column is its id's class (id mod 8) in all three layers, by a
    wide margin; seven classes keep chip 0 in one layer and one class in
    two, so the held pairs over the three layers are the tokens plus that
    one class's."""
    kw = dict(TINY, n_routed_experts=128, num_experts_per_tok=6,
              experts_held=8, hidden_size=96, moe_intermediate_size=8,
              moe_shared_expert_intermediate_size=8)
    for seed in (0, 1, 2):
        params, stats = REF.init(jax.random.key(seed), kw)
        x = jax.random.randint(jax.random.key(10 + seed), (4, 64), 0,
                               kw["vocab_size"])
        m = params["layer1"]["embedding"][x].reshape(-1, kw["hidden_size"])
        m = m / jnp.sqrt(jnp.square(m).mean(-1, keepdims=True))   # the norm
        held = []
        for name in ("layer3", "layer5", "layer8"):
            logits = m @ params[name]["moe"]["router"]["kernel"]
            first, second = jax.lax.top_k(logits[:, :8], 2)[0].T
            assert float((first - second).min()) > 1.5
            np.testing.assert_array_equal(
                np.asarray(jnp.argmax(logits[:, :8], axis=1)),
                np.asarray(x).reshape(-1) % 8)
            bias = stats[name]["moe"]["e_score_correction_bias"]
            _, top = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 6)
            held.append(np.asarray((top < 8).sum(axis=1)))
        per_token = np.sum(held, axis=0)
        classes = np.asarray(x).reshape(-1) % 8
        assert set(per_token) == {1, 2}
        twice = set(classes[per_token == 2])
        assert len(twice) == 1 and not twice & set(classes[per_token == 1])


def test_the_configuration_says_what_the_program_is_given():
    """The reference's weight of the load-balancing term is the one the
    YAML hands the program, the YAML keeps every published width, it is
    JSON as well as YAML, and the tree has the parameters it states."""
    import yaml
    path = ROOT / "benchmarks" / "configs" / "nemotron_twotower_30b_c5.yaml"
    conf = yaml.safe_load(path.read_text())
    assert conf == json.loads(path.read_text())
    program = conf["program"]
    assert program["learning"]["moe-aux-weight"] == REF.AUX_WEIGHT
    kw = program["model-kwargs"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "conv_kernel", "chunk_size",
                "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "layer_norm_epsilon",
                "time_step_min", "time_step_max", "time_step_floor"):
        assert kw[key] == conf[key] == REF.SIZES[key], key
    for key in ("mlp_hidden_act", "norm_topk_prob", "n_group", "topk_group",
                "n_shared_experts"):
        assert kw[key] == conf[key], key
    assert kw["sliding_window"] is None and conf["sliding_window"] is None
    # the router keeps its published width; 8 experts are held
    assert kw["n_routed_experts"] == conf["published"]["n_routed_experts"] \
        == 128
    assert kw["experts_held"] == conf["n_routed_experts"] == 8
    assert kw["vocab_size"] == conf["vocab_size"] == 131072 // 8
    assert kw["num_hidden_layers"] == conf["num_hidden_layers"] == 7
    assert kw["hybrid_override_pattern"] \
        == conf["hybrid_override_pattern"] == "MEMEM*E"
    assert conf["published"]["hybrid_override_pattern"].startswith(
        "MEMEM*EMEMEM*E")
    assert set(conf["reduced"]) >= {"num_hidden_layers", "n_routed_experts",
                                    "vocab_size", "hybrid_override_pattern"}
    # the program is given no setting of the allocator (config.py gains no
    # field); ``dparam`` is held between its sound and its faulty readings
    assert "host-heap-gib" not in program
    assert 0.00020 < conf["limits"]["dparam"] < 0.0020
    shapes = jax.eval_shape(lambda k: REF.init(k, kw), jax.random.key(0))
    count = lambda t: sum(a.size for a in jax.tree_util.tree_leaves(t))  # noqa
    held = conf["held-here"]
    assert count(shapes[0]) == held["parameters"] == 528092736
    assert count(shapes[0]["layer2"]) == held["mamba2_layer"] == 38744896
    assert count(shapes[0]["layer7"]) == held["attention_layer"] == 23399040
    assert count(shapes[0]["layer3"]) == held["expert_layer"] == 100125312
    assert count(shapes[1]) == 3 * 128
    # the program builds the same trees from the same keywords
    model = build_model("NemotronH_TINYSTORIES", **kw)
    mine = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    as_shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa
    assert as_shapes(mine["params"]) == as_shapes(shapes[0])
    assert as_shapes(mine["batch_stats"]) == as_shapes(shapes[1])
    # the operations a token: the scan's as the issue reckons them
    assert REF.scan_flops_per_token(REF.sizes(kw)) == 3407872


def test_the_reference_holds_host_buffers_on_a_chip_alone(monkeypatch):
    """``benchmarks/host_heap.py`` (the line ``run_cell.steady_allocator``
    lacks, until a ``benchmark`` PR writes it there): under
    ``JAX_PLATFORMS=cpu``, a rehearsal's and these tests', the reference
    leaves the allocator alone; elsewhere glibc serves large buffers from
    the heap (``mallopt(M_MMAP_MAX, 0)``: True on glibc; put back here) and
    ONE thread hands the heap's free pages back while the process stands
    over four fifths of the machine's memory."""
    import ctypes
    import importlib.util
    import threading
    import time
    watches = lambda: [t for t in threading.enumerate()  # noqa: E731
                       if t.name == "bench-host-heap"]
    assert REF.hold_host_buffers() is False and not watches()
    spec = importlib.util.spec_from_file_location(
        "host_heap", ROOT / "benchmarks" / "host_heap.py")
    heap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(heap)
    total = heap.os.sysconf("SC_PHYS_PAGES") * heap.os.sysconf("SC_PAGE_SIZE")
    assert 0 < heap.ceiling_bytes() <= 0.8 * total
    assert 0 < heap.resident_bytes() < total
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        assert heap.hold() is False
        return
    trims = []

    class Libc:
        mallopt = staticmethod(libc.mallopt)
        malloc_trim = staticmethod(lambda pad: trims.append(pad))
    monkeypatch.setattr(heap.ctypes, "CDLL", lambda name: Libc)
    monkeypatch.setattr(heap.time, "sleep",
                        lambda s, real=time.sleep: real(0.01))
    # a ceiling every process stands over: the watch trims at once
    monkeypatch.setattr(heap, "ceiling_bytes", lambda: 1)
    try:
        assert heap.hold() is True
        watch = heap._WATCH
        assert watch.daemon and watch.is_alive()
        assert heap.hold() is True and heap._WATCH is watch    # one thread
        for _ in range(200):
            if trims:
                break
            time.sleep(0.01)
        assert trims
    finally:
        libc.mallopt(heap.M_MMAP_MAX, 65536)    # glibc's default


def test_it_trains_through_run_local(tmp_path, monkeypatch):
    """Two rounds through ``run_local`` (one client a stage, cut after
    layer 5, AdamW, FedAvg, validation, a checkpoint a round) from the
    reference's weights: every round ok, every parameter moved, the bias
    carried as it was."""
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
        model="NemotronH", dataset="TINYSTORIES", clients=[1, 1],
        global_rounds=2, val_batch_size=4, compute_dtype="float32",
        model_kwargs=TINY, log_path=str(tmp_path / "logs"),
        learning={"batch_size": 2, "control_count": 2, "optimizer": "adamw",
                  "learning_rate": 1e-3, "weight_decay": 0.1,
                  "moe_aux_weight": REF.AUX_WEIGHT},
        distribution={"num_samples": 8}, topology={"cut_layers": [5]},
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
    for name, layer in back["batch_stats"].items():
        np.testing.assert_allclose(
            np.asarray(layer["moe"]["e_score_correction_bias"]),
            stats[name]["moe"]["e_score_correction_bias"], rtol=0, atol=1e-7)
