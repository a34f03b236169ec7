"""Flash attention kernel vs dense reference: forward, gradients, causal,
blocks, and the model-level use_flash path (Pallas interpreter on CPU)."""

import importlib

import jax
import numpy as np
import pytest

from split_learning_tpu.ops.flash_attention import (
    KERNELS, Tiling, flash_attention, forward_pairs, tiling,
)
from tests.conftest import dense_attention, qkv_batch


def _qkv(key, b=2, s=64, h=2, d=16):
    return qkv_batch(key, b=b, s=s, h=h, d=d)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv(jax.random.key(0))
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _qkv(jax.random.key(1), s=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=8,
                                block_k=8) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def _flash_lowering_cases():
    from split_learning_tpu.analysis.pallas_check import lowering_cases
    return {c[0]: c for c in lowering_cases()
            if c[0].startswith("flash")}


_FLASH_CASES = _flash_lowering_cases()


@pytest.mark.parametrize("name", sorted(_FLASH_CASES))
def test_flash_lowers_for_tpu(name):
    """Forward and backward kernels lower for TPU natively
    (``interpret=False``) at the shapes ``chip_smoke.py`` runs: both
    were refused while ``lse``/``delta`` travelled as ``(1, block_q)``
    slices of a ``(BH, S)`` array."""
    from split_learning_tpu.analysis.pallas_check import (
        check_tpu_lowering,
    )
    assert len(_FLASH_CASES) == 8   # two shapes, two grouped ones; fwd, bwd
    assert check_tpu_lowering(*_FLASH_CASES[name]) == []


def test_block_shrink_on_odd_sizes():
    """S=48 auto-picks a dividing block; numerics unchanged."""
    q, k, v = _qkv(jax.random.key(2), s=48)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bk", [(8, 16), (16, 8)])
def test_gradients_mismatched_blocks_causal(bq, bk):
    """Causal block-skip arithmetic (qb_start / nk_eff) at uneven
    block_q/block_k boundaries in the Pallas backward kernels."""
    q, k, v = _qkv(jax.random.key(4), s=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=bq,
                                block_k=bk) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_llama_grads_use_flash_match_einsum_path():
    """End-to-end training-step gradients agree between the flash and
    einsum attention paths through a real decoder block stack."""
    import optax
    from split_learning_tpu.models import build_model
    kw = dict(vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
              intermediate_size=64, n_block=2)
    x = jax.random.randint(jax.random.key(5), (2, 16), 0, 64)
    y = jax.random.randint(jax.random.key(6), (2, 16), 0, 64)
    m_ref = build_model("TinyLlama_TINYSTORIES", **kw)
    m_flash = build_model("TinyLlama_TINYSTORIES", use_flash=True, **kw)
    variables = m_ref.init(jax.random.key(0), x, train=False)

    def loss(params, model):
        logits = model.apply({"params": params}, x, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    g_ref = jax.grad(loss)(variables["params"], m_ref)
    g_flash = jax.grad(loss)(variables["params"], m_flash)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4),
        g_ref, g_flash)


def test_llama_use_flash_matches_einsum_path():
    from split_learning_tpu.models import build_model
    kw = dict(vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
              intermediate_size=64, n_block=2)
    x = jax.random.randint(jax.random.key(3), (2, 16), 0, 64)
    m_ref = build_model("TinyLlama_TINYSTORIES", **kw)
    variables = m_ref.init(jax.random.key(0), x, train=False)
    ref = m_ref.apply(variables, x, train=False)
    m_flash = build_model("TinyLlama_TINYSTORIES", use_flash=True, **kw)
    out = m_flash.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _band_attention(q, k, v, window):
    """Masked einsum with grouped key-value heads: query head ``h`` reads
    key-value head ``h // rep``; a query at ``p`` sees keys
    ``p - window + 1 .. p`` (``window=None``: every key up to ``p``)."""
    import jax.numpy as jnp
    s, rep = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    pos = jnp.arange(s)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# window < block, = block, between blocks, > S, and none; 4 query heads
# over 2 key-value heads
@pytest.mark.parametrize("window", [5, 8, 20, 100, None])
def test_window_and_grouped_heads_match_masked_einsum(window):
    """Forward and all three gradients of the windowed kernel with
    grouped key-value heads against the masked einsum."""
    b, s, h, kv, d = 2, 32, 4, 2, 16
    kq, kk, kv_, kw = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, kv, d))
    v = jax.random.normal(kv_, (b, s, kv, d))
    w = jax.random.normal(kw, (b, s, h, d))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                               window=window)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)),
        np.asarray(_band_attention(q, k, v, window)), rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (_band_attention(*a, window) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("blocks", [(8, 16), (16, 8)])
def test_window_with_unequal_blocks(blocks):
    """The loop bounds hold when query and key blocks differ."""
    bq, bk = blocks
    q, k, v = qkv_batch(jax.random.key(3), b=1, s=64, h=2, d=16)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          window=12)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_band_attention(q, k, v, 12)),
        rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (flash_attention(
        *a, causal=True, block_q=bq, block_k=bk, window=12) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (_band_attention(*a, 12) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_window_needs_causal():
    q, k, v = _qkv(jax.random.key(0), s=16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=4)


# -- scores over one width, values over another (latent attention) -------------

def _qkv_two_widths(key, b=2, s=32, h=4, kv=4, d=24, dv=16):
    kq, kk, kv_ = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, s, h, d)),
            jax.random.normal(kk, (b, s, kv, d)),
            jax.random.normal(kv_, (b, s, kv, dv)))


def _dense_two_widths(q, k, v, window=None):
    """Causal softmax attention with ``1 / sqrt(score width)``, a query
    head reading key-value head ``h // rep``."""
    import jax.numpy as jnp
    rep = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(q.shape[-1])
    pos = jnp.arange(s)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


@pytest.mark.parametrize("kv,window,d,dv", [
    (4, None, 24, 16), (4, None, 16, 24), (2, None, 24, 16),
    (2, 8, 24, 16)], ids=["wide_scores", "wide_values", "grouped",
                          "grouped_window"])
def test_unequal_score_and_value_widths_forward_and_all_gradients(
        kv, window, d, dv):
    """The three kernels with scores over ``d`` and values over ``dv``:
    the result is ``dv`` wide, the scale is ``1 / sqrt(d)``, and ``dq``,
    ``dk`` (``d`` wide) and ``dv`` match the dense oracle's."""
    q, k, v = _qkv_two_widths(jax.random.key(7), kv=kv, d=d, dv=dv)
    w = jax.random.normal(jax.random.key(8), (*q.shape[:3], dv))
    out = flash_attention(q, k, v, causal=True, window=window, block_q=8,
                          block_k=16)
    assert out.shape == (*q.shape[:3], dv)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_two_widths(q, k, v, window)),
        rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (flash_attention(
        *a, causal=True, window=window, block_q=8, block_k=16) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (_dense_two_widths(*a, window) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_keys_of_another_width_than_the_queries_are_refused():
    q, k, v = _qkv_two_widths(jax.random.key(9))
    with pytest.raises(ValueError, match="query heads over key/value"):
        flash_attention(q, k[..., :16], v, causal=True)


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_the_latent_kernels_lower_for_tpu_at_the_cells_widths(kernel):
    """Scores over 192 (no multiple of the 128 lanes) and values over
    128, rows of 4,096 in blocks of 512, bfloat16: forward and backward
    kernels lower natively (``interpret=False``)."""
    import jax.numpy as jnp
    shape = lambda d: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 4096, 16, d), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               block_q=512, block_k=512)
    f = fwd if kernel == "fwd" else jax.grad(
        lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(f).trace(shape(192), shape(192), shape(128)).lower(
        lowering_platforms=("tpu",)).as_text()
    names = ["slt_flash_fwd"] if kernel == "fwd" else [
        "slt_flash_bwd_dq", "slt_flash_bwd_dkv"]
    assert all(name in text for name in names)


# -- the tiling of a call, and the walk it gives ------------------------------

# the package exports the function under the module's name
fa = importlib.import_module("split_learning_tpu.ops.flash_attention")

# (S, window, cap_q, cap_k): both cells' calls, the tests' small shapes,
# and awkward ones (a window that is no multiple of any tile, of one key,
# of the row and more; a row with no divisor of 128; caps that differ)
_CALLS = [
    (4096, 1024, 512, 512), (4096, None, 512, 512), (4096, 1024, 128, 128),
    (2048, None, 128, 128), (32, None, 8, 8), (32, 5, 8, 8), (32, 20, 8, 8),
    (32, 8, 8, 16), (32, 12, 16, 8), (64, 12, 8, 16), (48, None, 128, 128),
    (48, 7, 128, 128), (96, 1, 32, 32), (96, 95, 32, 32), (96, 96, 32, 32),
    (96, 200, 32, 32), (1000, 300, 512, 512), (1000, None, 512, 256),
    (4096, 1000, 512, 512), (4096, 2048, 512, 512), (4096, 3900, 512, 512),
    (120, 33, 24, 40), (120, None, 24, 40), (256, 64, 64, 64),
]
_CALL_IDS = ["-".join(map(str, c)) for c in _CALLS]


def _band_mask(s, window):
    pos = np.arange(s)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    return seen


def _walked(s, window, tile, over_keys):
    """Per tile of ``tile``'s walk (over keys: a query tile's; else a key
    tile's): ``[(start of the other side's block, edges)]`` as the kernel
    takes it, by the walk run on Python ints."""
    window = fa._band_window(s, window)
    own = tile.block_q if over_keys else tile.block_k
    out = {}
    for start in range(0, s, own):
        done = []
        fa._walk(start, tile, s, True, window, over_keys,
                 lambda at, c, edges: c + [(int(at), edges)], [],
                 done.append, on=fa._COUNTED)
        assert len(done) == 1               # ``finish`` runs exactly once
        out[start] = done[0]
    return out


@pytest.mark.parametrize("call", _CALLS, ids=_CALL_IDS)
def test_every_tile_divides_the_row_and_stays_under_the_cap(call):
    s, window, cap_q, cap_k = call
    for kernel in KERNELS:
        grid, block_q, block_k = tile = tiling(kernel, s, window, cap_q,
                                               cap_k)
        assert s % block_q == 0 and s % block_k == 0, tile
        assert block_q <= cap_q and block_k <= cap_k, tile
        own, cap = (block_k, cap_k) if kernel == "dkv" else (block_q, cap_q)
        assert s % grid == 0 and grid % own == 0 and grid <= cap, tile
    with pytest.raises(ValueError, match="one of"):
        tiling("bwd", s, window, cap_q, cap_k)


def test_small_caps_resolve_to_themselves_and_the_cells_to_what_was_measured():
    """Explicit small blocks stay the tiles a test names; the cells' calls
    get the tilings PERF.md section 6 (PR 35) measured."""
    for kernel in KERNELS:
        assert tiling(kernel, 32, None, 8, 16)[1:] == (8, 16)
        assert tiling(kernel, 32, 12, 16, 8)[1:] == (16, 8)
        assert tiling(kernel, 4096, None, 512, 512) == (512, 512, 512)
    assert tiling("fwd", 4096, 1024, 512, 512) == (512, 512, 512)
    assert tiling("dq", 4096, 1024, 512, 512) == (512, 256, 256)
    assert tiling("dkv", 4096, 1024, 512, 512) == (512, 256, 256)


@pytest.mark.parametrize("call", _CALLS, ids=_CALL_IDS)
def test_the_exported_counts_equal_a_brute_force_count_over_the_band(call):
    s, window, cap_q, cap_k = call
    tile = tiling("fwd", s, window, cap_q, cap_k)
    visited = np.zeros((s, s), int)
    masked = np.zeros((s, s), int)
    for q0, blocks in _walked(s, window, tile, True).items():
        for k0, edges in blocks:
            here = (slice(q0, q0 + tile.block_q),
                    slice(k0, k0 + tile.block_k))
            visited[here] += 1
            masked[here] += edges is not None
    assert forward_pairs(s, window, tile) == (
        _band_mask(s, window).sum(), visited.sum(), masked.sum())


@pytest.mark.parametrize("over_keys", [True, False], ids=["keys", "queries"])
@pytest.mark.parametrize("call", _CALLS, ids=_CALL_IDS)
def test_every_seen_pair_is_visited_once_and_no_unmasked_block_hides_one(
        call, over_keys):
    """The property whose failure is a silent wrong answer: with each
    block's scores masked by the edges the walk names for it (none: not at
    all), the walks of all tiles rebuild the band exactly, every pair
    once; blocks stay inside the row."""
    s, window, cap_q, cap_k = call
    band = _band_mask(s, window)
    w = fa._band_window(s, window)
    pos = np.arange(s)
    causal = pos[None, :] <= pos[:, None]
    behind = pos[None, :] > pos[:, None] - (w or 0)
    for kernel in ("fwd", "dq") if over_keys else ("dkv",):
        tile = tiling(kernel, s, window, cap_q, cap_k)
        built = np.zeros((s, s), int)
        for start, blocks in _walked(s, window, tile, over_keys).items():
            for at, edges in blocks:
                q0, k0 = (start, at) if over_keys else (at, start)
                assert 0 <= at and at + (
                    tile.block_k if over_keys else tile.block_q) <= s
                here = (slice(q0, q0 + tile.block_q),
                        slice(k0, k0 + tile.block_k))
                kept = np.ones((s, s), bool)
                if edges is not None and edges[0]:
                    kept &= causal
                if edges is not None and edges[1]:
                    kept &= behind
                built[here] += kept[here]
        np.testing.assert_array_equal(built, band.astype(int))


def test_a_banded_walk_enters_no_loop_where_the_band_lies_inside_the_row():
    """A steady tile of the cell's windowed call walks the same three
    blocks laid from the band's end: the window's edge, a block seen
    whole, the diagonal."""
    walks = _walked(4096, 1024, Tiling(512, 512, 512), True)
    for q0 in range(1024, 4096, 512):
        assert walks[q0] == [(q0 - 1024, (False, True)), (q0 - 512, None),
                             (q0, (True, False))]
    assert walks[0] == [(0, (True, True))]
    assert forward_pairs(4096, 1024, Tiling(512, 512, 512)) == (
        3670528, 21 * 512 * 512, 15 * 512 * 512)
    assert forward_pairs(4096, 1024, Tiling(512, 256, 256))[1] == 4587520
    assert forward_pairs(4096, None, Tiling(512, 512, 512)) == (
        8390656, 36 * 512 * 512, 8 * 512 * 512)


# the cells' calls scaled down by 16 (rows of 256, a window of 64, a cap of
# 32) under every tiling the function can return for them, sub-tiles walked
# inside a grid step, a window that crosses sub-tiles, a band past
# ``_UNROLL`` blocks, tiles that divide neither way
_SCALED = [
    ("cap", 64, (Tiling(32, 32, 32),) * 3),
    ("halved_backward", 64, (Tiling(32, 32, 32), Tiling(32, 16, 16),
                             Tiling(32, 16, 16))),
    ("sub_tiles", 64, (Tiling(64, 16, 32), Tiling(64, 32, 16),
                       Tiling(64, 16, 32))),
    ("window_crosses_sub_tiles", 50, (Tiling(32, 16, 16),) * 3),
    ("long_band", 100, (Tiling(32, 8, 8),) * 3),
    ("full", None, (Tiling(32, 32, 32),) * 3),
    ("full_sub_tiles", None, (Tiling(64, 16, 32), Tiling(64, 32, 16),
                              Tiling(32, 16, 32))),
]


@pytest.mark.parametrize("rep,d,dv", [(1, 16, 16), (8, 24, 16)],
                         ids=["heads_alone", "grouped_two_widths"])
@pytest.mark.parametrize("name,window,tiles", _SCALED,
                         ids=[c[0] for c in _SCALED])
def test_forward_and_all_gradients_at_the_cells_tilings_scaled_down(
        name, window, tiles, rep, d, dv):
    b, s, h = 1, 256, 8
    kq, kk, kv_, kw = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, h // rep, d))
    v = jax.random.normal(kv_, (b, s, h // rep, dv))
    w = jax.random.normal(kw, (b, s, h, dv))
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        b * t.shape[2], s, t.shape[3])

    def flash(q, k, v):
        out = fa._flash(to_bhsd(q), to_bhsd(k), to_bhsd(v), True, True,
                        tiles, window, rep)
        return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)),
        np.asarray(_dense_two_widths(q, k, v, window)), rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (_dense_two_widths(*a, window) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for which, a, b_ in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4, err_msg=which)


# (heads, key-value heads, D, Dv, window): the cells' widths under every
# kind of walk ``tiling`` can give them at a cap of 512: the backward's
# halved tiles as sub-tiles (a band of at most two tiles), the cap with
# three blocks between the edges, a band past ``_UNROLL`` blocks (one loop
# between the edges), and the same at the latent layer's two widths
_LOWERED = [(32, 4, 128, 128, 1024), (32, 4, 128, 128, 2048),
            (32, 4, 128, 128, 3072), (16, 16, 192, 128, 1024),
            (16, 16, 192, 128, 3072)]


@pytest.mark.parametrize("h,kv,d,dv,window", _LOWERED,
                         ids=["-".join(map(str, c)) for c in _LOWERED])
def test_the_cells_widths_lower_for_tpu_under_each_walk(h, kv, d, dv,
                                                        window):
    import jax.numpy as jnp
    shape = lambda n, w: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 4096, n, w), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               block_q=512, block_k=512, window=window)
    text = jax.jit(jax.value_and_grad(
        lambda *a: fwd(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).trace(
            shape(h, d), shape(kv, d), shape(kv, dv)).lower(
                lowering_platforms=("tpu",)).as_text()
    assert all(name in text for name in (
        "slt_flash_fwd", "slt_flash_bwd_dq", "slt_flash_bwd_dkv"))


# -- the cells' head groups and widths -----------------------------------------

# (heads, key-value heads, D, Dv) with heads and rows scaled down: groups of
# 1, 6 (Laguna's 48 over 8), 8 (its 64 over 8, Mellum's 32 over 4) and 16
# (Nemotron's 32 over 2) at 128; Moonlight's 192-wide scores over 128-wide
# values; 64 wide
_WIDTHS = [(2, 2, 128, 128), (12, 2, 128, 128), (16, 2, 128, 128),
           (32, 2, 128, 128), (4, 4, 192, 128), (4, 2, 64, 64)]
_WIDTH_IDS = ["rep1", "rep6", "rep8", "rep16", "mixed_192_128", "narrow_64"]


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
@pytest.mark.parametrize("h,kv,d,dv", _WIDTHS, ids=_WIDTH_IDS)
def test_the_cells_head_groups_and_widths_forward_and_all_gradients(
        h, kv, d, dv, window):
    b, s = 2, 64
    kq, kk, kv_, kw = jax.random.split(jax.random.key(13), 4)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, kv, d))
    v = jax.random.normal(kv_, (b, s, kv, dv))
    w = jax.random.normal(kw, (b, s, h, dv))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=16, block_k=32)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)),
        np.asarray(_dense_two_widths(q, k, v, window)), rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (_dense_two_widths(*a, window) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for which, a, b_ in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4, err_msg=which)


def _shapes(jaxpr) -> list:
    """The shape of every value made in ``jaxpr`` and the jaxprs its
    equations call."""
    found = []
    for eqn in jaxpr.eqns:
        found += [tuple(v.aval.shape) for v in eqn.outvars
                  if hasattr(v.aval, "shape")]
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _shapes(inner)
    return found


@pytest.mark.parametrize("window", [None, 96], ids=["full", "window"])
def test_the_row_statistics_travel_as_rows(window):
    """``lse`` and ``delta`` pass between the calls as (B*H, 1, S) rows:
    the forward and its gradients traced whole make no (.., S, 1) column,
    which HBM would pad to 128 lanes, and both are there as rows."""
    import jax.numpy as jnp
    b, s, h, kv, d = 2, 256, 8, 2, 128
    shapes = _shapes(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v, w: (flash_attention(
            q, k, v, causal=True, window=window, block_q=128,
            block_k=128) * w).sum(), argnums=(0, 1, 2)))(
        *(jax.ShapeDtypeStruct((b, s, n, d), jnp.bfloat16)
          for n in (h, kv, kv, h))).jaxpr)
    assert not [t for t in shapes if t[-2:] == (s, 1)]
    assert shapes.count((b * h, 1, s)) >= 2


def test_a_column_and_a_row_turn_into_each_other_exactly():
    """:func:`_as_row` and :func:`_as_column` move values, they do not
    round them (both kernels read the statistics through them)."""
    col = jax.random.normal(jax.random.key(14), (256, 1)) * 1e3
    row = fa._as_row(col)
    assert row.shape == (1, 256)
    np.testing.assert_array_equal(np.asarray(row), np.asarray(col).T)
    np.testing.assert_array_equal(np.asarray(fa._as_column(row)),
                                  np.asarray(col))
