"""Plain reference for ``laguna_xs2_c3``: the UNSPLIT decoder of Laguna-XS.2
(``model_type`` ``laguna``) in float32 ``jax.numpy`` at full matmul
precision, with the share of the routed experts and of the vocabulary that
the configuration's one chip holds.  It imports nothing of the program and
has no kernel, no sorted dispatch and no capacity: attention is a masked
softmax over whole rows of scores (a windowed layer's over the band of its
window where the window tiles the row), taken a few heads at a time, and
every held expert is applied to every token and weighted by what the router
gave it (nought for the tokens that did not pick it).

Written from the keys of the published configuration
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json).  For
layer ``i`` with input ``x`` (rows, S, hidden), ``h = x + Attn_i(RMSNorm(x))``,
``y = h + FF_i(RMSNorm(h))``, RMSNorm at ``rms_norm_eps``, no bias anywhere:

* attention of the kind ``layer_types[i]`` names, with
  ``num_attention_heads_per_layer[i]`` query heads over
  ``num_key_value_heads`` of ``head_dim``: ``q = u Wq``, ``k = u Wk``,
  ``v = u Wv``; RoPE by the kind's ``rope_parameters``: the first
  ``head_dim * partial_rotary_factor`` dims of each ``q`` and ``k`` head
  turn in the half-split (``rotate_half``) convention, the rest pass
  unchanged; ``full_attention`` with YaRN's frequencies computed over that
  rotated width and cos and sin multiplied by ``attention_factor`` (so the
  factor scales the rotated dims alone), ``sliding_attention`` with
  ``theta^(-2i / head_dim)`` over the whole head; scores ``q k^T /
  sqrt(head_dim)``, query head ``h`` reading key-value head ``h // (heads /
  key-value heads)``, causal, in ``sliding_attention`` layers over keys
  ``p - sliding_window + 1 .. p``, softmax in float32; the gate (``gating``,
  one a head): ``o_h <- sigmoid(u W_g)_h o_h``; ``Attn = concat_h(o_h) Wo``;
* ``mlp_layer_types[i]`` ``dense``: a SwiGLU of ``intermediate_size``,
  ``(silu(u W_gate) * u W_up) W_down``;
* ``sparse``: ``s = softmax(u W_r)`` over ALL ``num_experts``; ``I`` = the
  ``num_experts_per_tok`` largest of ``s``; ``w_i =
  moe_routed_scaling_factor * s_i / sum_{j in I} s_j``; ``FF = sum_{i in I,
  i held} w_i E_i(u) + S(u)``, ``E`` and the shared ``S`` SwiGLUs of
  ``moe_intermediate_size`` and ``shared_expert_intermediate_size``.  What
  the absent experts would add is left out, here as in the program; the
  shared expert is whole;
* after the last layer RMSNorm and the untied head (the held slice of the
  vocabulary).

Departures and readings the configuration does not state, each
``assumed`` in the YAML: the gate is one a head; softmax scores; the
chosen weights renormalized; no ``q``/``k`` norm; the load-balancing term
``E * sum_e f_e P_e`` over the tokens of a microbatch (``f_e``: the pairs
routed to ``e`` over the tokens; ``P_e``: the mean score) with weight
``AUX_WEIGHT``; the routers' initial values (:func:`init`).

Tree names are the program's (``layer1`` embedding, ``layer2``.. layers,
then the final norm and the head; ``attention/{q,k,v,o,g}_proj``, a dense
layer's SwiGLU under the layer's own ``gate_proj``/``up_proj``/``down_proj``,
a sparse layer's experts under ``moe/experts/{gate,up,down}_proj/kernel``
with the held experts leading and its shared expert under
``shared_experts``), so the trees this makes are the trees the program's
checkpoint holds.

One thing here is not a reference's: :func:`hold_host_buffers`, called when
the module is loaded on a machine with a chip, stands in for a line that
``run_cell.steady_allocator`` lacks until a ``benchmark`` PR may write it
there (``nemotron_twotower_30b_c5.py``'s docstring; PERF.md section 7).
"""

from __future__ import annotations

import importlib.util
import itertools
import math
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"
SIZES = dict(
    vocab_size=100352, hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=40, layer_types=None, mlp_layer_types=None,
    num_attention_heads=48, num_attention_heads_per_layer=None,
    num_key_value_heads=8, head_dim=128, sliding_window=512,
    partial_rotary_factor=0.5, rms_norm_eps=1e-6, num_experts=256,
    num_experts_per_tok=8, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, moe_routed_scaling_factor=2.5,
    experts_held=None, seq_len=4096,
    rope_parameters={
        FULL: {"rope_theta": 500000.0, "rope_type": "yarn", "factor": 64.0,
               "original_max_position_embeddings": 4096, "beta_slow": 1.0,
               "beta_fast": 64.0, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                  "partial_rotary_factor": 1}})
PERIOD = (FULL, SLIDING, SLIDING, SLIDING)
DATASET = "tokens"
# weight of the load-balancing term: the siblings'; the program is given the
# same under ``learning.moe-aux-weight``
AUX_WEIGHT = 0.0001
# query heads whose scores are held at once: (rows, 4, S, S) float32 is
# 256 MB a row of 4,096 tokens
HEAD_CHUNK = 4
# the coordinates of the state that the routers read and that no layer
# writes to (``init``), and the logit a chip's copy of a column loses where
# the token's half of the chips is the other one
ROUTED_DIMS = 64
LEFT_OUT = 0.5
HI = jax.lax.Precision.HIGHEST
# tokens that go through a matrix at once (``_run``'s ``mm``)
TOKEN_BLOCK = 512


def hold_host_buffers() -> bool:
    """NOT the reference's business (``nemotron_twotower_30b_c5.py``'s
    function of the same name says why and what): the q, o and stacked
    experts' leaves of this tree are 64 MiB each, over the 32 MiB that
    ``run_cell.steady_allocator`` pins as glibc's mmap threshold, so every
    pass ``compare.py`` makes over them on the host would run on freshly
    faulted pages.  A rehearsal on the CPU (``JAX_PLATFORMS=cpu``) leaves
    the allocator alone."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return False
    spec = importlib.util.spec_from_file_location(
        "bench_host_heap",
        pathlib.Path(__file__).resolve().parent.parent / "host_heap.py")
    host_heap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(host_heap)
    return host_heap.hold()


hold_host_buffers()


def sizes(model_kwargs=None) -> dict:
    s = dict(SIZES)
    s.update({k: v for k, v in (model_kwargs or {}).items() if k in s})
    n = s["num_hidden_layers"]
    s["layer_types"] = tuple(s["layer_types"] or (
        PERIOD[i % 4] for i in range(n)))
    s["mlp_layer_types"] = tuple(s["mlp_layer_types"] or (
        ("dense",) + ("sparse",) * (n - 1)))
    s["num_attention_heads_per_layer"] = tuple(
        s["num_attention_heads_per_layer"]
        or (s["num_attention_heads"],) * n)
    held = s["experts_held"]
    s["experts_held"] = tuple(range(s["num_experts"])) if held is None \
        else tuple(range(held)) if isinstance(held, int) else tuple(held)
    return s


# the sizes ``init`` was last given: the harness hands ``model-kwargs`` to
# ``init`` and ``train_flops_per_sample`` alone, and a tree does not show
# the window, the rotary parts, the experts a token picks or the factor
_KWARGS: dict = {}


def init(key, model_kwargs=None):
    """(params, batch_stats) from one key: normal(0.02) matrices (the gate's
    too), unit norm scales, the embedding normal(1) (the siblings' reason:
    a residual stream of unit size, as a trained model's is), and, where
    the held experts are an even share with twice as many chips as experts
    a token picks (``chips = num_experts / held = 2 * num_experts_per_tok``;
    16 chips of 16 experts, 8 a token, in the configuration), routers that
    hold the held share to the deployment's load whatever the seed:

    * the state's first ``ROUTED_DIMS`` coordinates are the routers' alone:
      those columns of every output projection (``o_proj``, each
      ``down_proj``) start at nought, so no layer writes there, and the
      embedding lights, in token ``t``'s row, the share of column ``j = t
      mod held`` (1 in its ``(ROUTED_DIMS - 2) // held`` coordinates,
      nought in the other columns' shares) and one of the last two routed
      coordinates, the token's half ``a = (t // held) mod 2`` (1 there,
      nought in the other);
    * every router's kernel is ``chips`` copies (one a chip: expert ``c *
      held + j`` is chip ``c``'s copy of column ``j``) of the SAME columns:
      column ``j`` reads its share of the routed coordinates (a logit of
      two where lit); and a copy loses ``LEFT_OUT`` of its logit, by the
      half coordinates, where the chip is not of the token's half of the
      chips for that column.  In sparse layer ``l`` half ``0`` of column
      ``j`` is the ``num_experts_per_tok`` chips ``(perm[j] * chips / held
      + l * num_experts_per_tok + i) mod chips``, ``i`` under that many
      (``perm`` drawn from the key), half ``1`` the others.

    A token's choice is then the ``num_experts_per_tok`` copies of its
    column on its half's chips, by a margin of ``LEFT_OUT`` times the
    norm's factor in the logit, the same in the program's bfloat16 as here
    (the routed coordinates are exact in bfloat16, no layer changes them,
    and a norm scales a token's coordinates alike); its chosen scores tie,
    so every chosen weight is ``moe_routed_scaling_factor / 8``.  Its two
    halves alternate over the layers, so chip 0 is of its half in exactly
    half of the sparse layers: over the configuration's four the held
    share computes exactly 2 pairs a token, 0.5 a token and layer (the
    deployment's ``8 x 16 / 256``), and every held expert gets the tokens
    of one column's half in every layer.  ``tests/test_laguna.py`` holds
    the program to this reference with routers drawn whole at small sizes
    (``routers="whole"``).

    Traceable: the harness jits it.  Nothing here is an indexed update, a
    gather or a sort (PERF.md section 6)."""
    _KWARGS.clear()
    _KWARGS.update(model_kwargs or {})
    s = sizes(model_kwargs)
    whole = (model_kwargs or {}).get("routers") == "whole"
    d, f, hd = s["hidden_size"], s["moe_intermediate_size"], s["head_dim"]
    kv = s["num_key_value_heads"] * hd
    e, n_held, k = s["num_experts"], len(s["experts_held"]), \
        s["num_experts_per_tok"]
    chips = e // n_held
    share = (ROUTED_DIMS - 2) // n_held
    tiled = not whole and e % n_held == 0 and chips == 2 * k \
        and chips % n_held == 0 and share > 0 and d > ROUTED_DIMS
    count = [0]

    def draw(*shape, std=0.02):
        """Drawn flat and reshaped: the same numbers as a draw of
        ``shape`` (threefry counts an element by its flat index), and the
        chip's compiler takes seconds for each draw of three axes."""
        count[0] += 1
        return std * jax.random.normal(jax.random.fold_in(key, count[0]),
                                       (math.prod(shape),)).reshape(shape)

    def w(*shape):
        return {"kernel": draw(*shape)}

    def out(*shape):
        """An output projection: the routed coordinates start at nought."""
        kernel = draw(*shape)
        return {"kernel": kernel * (jnp.arange(d) >= ROUTED_DIMS)
                if tiled else kernel}

    coord = jnp.arange(d)[:, None]
    if tiled:
        half_at = ROUTED_DIMS - 2
        # (coordinate, column): a lit share gives a logit of two
        columns = ((coord // share == jnp.arange(n_held)) & (coord < half_at)
                   ) * (2.0 / share)
        # a permutation of the columns: each one's rank among the draws
        order = draw(n_held)
        perm = (order[None, :] < order[:, None]).sum(axis=1)

    def router(layer):
        """The kernel of sparse layer ``layer`` (0, 1, ..)."""
        if not tiled:
            return w(d, e)
        # (chip, column): whether the chip is of half 0 of the column
        past = (jnp.arange(chips)[:, None] - perm[None, :] * (chips // n_held)
                - layer * k) % chips
        first = jnp.where(past < k, 0.0, -LEFT_OUT).reshape(e)
        halves = jnp.where(coord == half_at, first, 0.0) + jnp.where(
            coord == half_at + 1, -LEFT_OUT - first, 0.0)
        return {"kernel": jnp.tile(columns, (1, chips)) + halves}

    def swiglu(width):
        return {"gate_proj": w(d, width), "up_proj": w(d, width),
                "down_proj": out(width, d)}

    embedding = draw(s["vocab_size"], d, std=1.0)
    if tiled:
        ids = jnp.arange(s["vocab_size"])[None, :]
        lit = ((coord // share == ids % n_held) & (coord < half_at)) \
            | (coord == half_at + (ids // n_held) % 2)
        embedding = jnp.where(coord.T < ROUTED_DIMS,
                              lit.T.astype(jnp.float32), embedding)
    params = {"layer1": {"embedding": embedding}}
    sparse = 0
    for i, (kind, ff, h) in enumerate(zip(
            s["layer_types"], s["mlp_layer_types"],
            s["num_attention_heads_per_layer"])):
        p = {"input_norm": {"scale": jnp.ones((d,))},
             "attention": {"q_proj": w(d, h * hd), "k_proj": w(d, kv),
                           "v_proj": w(d, kv), "g_proj": w(d, h),
                           "o_proj": out(h * hd, d)},
             "post_norm": {"scale": jnp.ones((d,))}}
        if ff == "sparse":
            p["moe"] = {"router": router(sparse),
                        "experts": {"gate_proj": w(n_held, d, f),
                                    "up_proj": w(n_held, d, f),
                                    "down_proj": out(n_held, f, d)}}
            p["shared_experts"] = swiglu(s["shared_expert_intermediate_size"])
            sparse += 1
        else:
            p.update(swiglu(s["intermediate_size"]))
        params[f"layer{i + 2}"] = p
    last = s["num_hidden_layers"] + 2
    params[f"layer{last}"] = {"scale": jnp.ones((d,))}
    params[f"layer{last + 1}"] = w(d, s["vocab_size"])
    return params, {}


# -- rotary embedding ---------------------------------------------------------

def yarn_inv_freq(dim: int, rope_theta: float, factor: float,
                  original_max_position_embeddings: int, beta_fast: float,
                  beta_slow: float, **_) -> np.ndarray:
    """Over a rotated width ``dim``: ``extrap_i = theta^(-2i / dim)``,
    ``interp_i = extrap_i / factor``, ``cd(r) = dim ln(L / (2 pi r)) / (2 ln
    theta)``, ``low = max(floor(cd(beta_fast)), 0)``, ``high =
    min(ceil(cd(beta_slow)), dim - 1)``, ``ramp_i = clip((i - low) / (high -
    low), 0, 1)``, ``inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i)``."""
    i = np.arange(dim // 2)
    extrap = rope_theta ** (-2.0 * i / dim)

    def cd(rotations):
        return dim * math.log(
            original_max_position_embeddings / (2 * math.pi * rotations)) \
            / (2 * math.log(rope_theta))
    low = max(math.floor(cd(beta_fast)), 0)
    high = min(math.ceil(cd(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (extrap / factor) * ramp + extrap * (1 - ramp)


def rotary(x, p: dict):
    """(B, S, H, D) with the kind's parameters ``p``: the first ``R = D *
    partial_rotary_factor`` dims turn, the pair (i, i + R/2) by ``position
    * inv_freq_i`` (``x cos + rotate_half(x) sin`` over them, cos and sin
    times the attention factor); dims ``R ..`` pass unchanged."""
    seq, width = x.shape[1], int(x.shape[-1] * p["partial_rotary_factor"])
    if p.get("rope_type", "default") == "yarn":
        inv_freq, factor = yarn_inv_freq(width, **p), p["attention_factor"]
    else:
        inv_freq = p["rope_theta"] ** (-2.0 * np.arange(width // 2) / width)
        factor = 1.0
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = (factor * fn(jnp.concatenate([angle, angle], axis=-1))[
        None, :, None, :] for fn in (jnp.cos, jnp.sin))
    turned, kept = x[..., :width], x[..., width:]
    x1, x2 = jnp.split(turned, 2, axis=-1)
    return jnp.concatenate(
        [turned * cos + jnp.concatenate([-x2, x1], axis=-1) * sin, kept],
        axis=-1)


# -- one layer ------------------------------------------------------------------

def _rms(p, x, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * p["scale"]


def _kernels(p, *names):
    """The kernels ``names`` of ``p`` side by side, as one matrix: products
    that share their input are ONE product here (the chip's compiler takes
    a second for every float32 product at full precision: PERF.md
    section 6)."""
    return jnp.concatenate([p[name]["kernel"] for name in names], axis=-1)


def attention(a, u, kind: str, s: dict, mm, gate: bool = True, rope=None):
    """``Attn(u)`` of a layer of ``kind`` for the normed state ``u`` (rows,
    S, hidden), before the residual.  ``HEAD_CHUNK`` query heads' scores at
    a time (or fewer: a chunk's heads share one key-value head), each chunk
    recomputed in the backward pass.  ``gate=False`` (the gate left out)
    and ``rope`` (other rotary parameters for the kind) are planted faults'
    oracles."""
    rows, seq, _ = u.shape
    hd, kv = s["head_dim"], s["num_key_value_heads"]
    h = a["g_proj"]["kernel"].shape[-1]
    q, k, v, g = jnp.split(
        mm("bsd,de->bse", u, _kernels(a, "q_proj", "k_proj", "v_proj",
                                      "g_proj")),
        np.cumsum([h * hd, kv * hd, kv * hd]), axis=-1)
    p = dict(rope or s["rope_parameters"][kind])
    p.setdefault("partial_rotary_factor", s["partial_rotary_factor"])
    q, k = (rotary(t.reshape(rows, seq, -1, hd), p) for t in (q, k))
    v = v.reshape(rows, seq, kv, hd)
    rep = h // kv
    c = math.gcd(HEAD_CHUNK, rep)
    window = s["sliding_window"] if kind == SLIDING else seq
    # a window that tiles the row: queries ``window`` at a time, each block
    # against its own keys and the block before (a query sees no further
    # back), so the scores are ``2 window`` wide where the row's are ``seq``
    banded = window < seq and seq % window == 0
    blocks = seq // window if banded else 1
    span = 2 * window if banded else seq
    # (block, query, key) positions in the row
    start = jnp.arange(blocks)[:, None, None] * (seq // blocks)
    qpos = start + jnp.arange(seq // blocks)[None, :, None]
    kpos = start - (window if banded else 0) + jnp.arange(span)
    seen = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)
    # chunk i holds query heads i*c .. i*c + c - 1, all of key-value head
    # i*c // rep, each key-value head broadcast beside its chunks
    q = q.reshape(rows, seq, h // c, c, hd).transpose(2, 0, 1, 3, 4)
    shared = lambda t: jnp.broadcast_to(  # noqa: E731
        t[:, :, :, None], (rows, seq, kv, rep // c, hd)
    ).reshape(rows, seq, h // c, hd).transpose(2, 0, 1, 3)

    def band(t):
        """(rows, seq, hd) keys or values -> (rows, blocks, span, hd): a
        block's own and, banded, the block before's (noughts before the
        first, which ``seen`` leaves out)."""
        t = t.reshape(rows, blocks, -1, hd)
        if not banded:
            return t
        before = jnp.concatenate([jnp.zeros_like(t[:, :1]), t[:, :-1]], 1)
        return jnp.concatenate([before, t], axis=2)

    @jax.checkpoint
    def chunk(args):
        qc, kc, vc = args
        qc = qc.reshape(rows, blocks, -1, c, hd)
        probs = jax.nn.softmax(jnp.where(
            seen[:, None], mm("bnqcd,bnkd->bncqk", qc, band(kc)) / hd ** 0.5,
            jnp.finfo(jnp.float32).min), axis=-1)
        return mm("bncqk,bnkd->bnqcd", probs, band(vc)).reshape(
            rows, seq, c, hd)
    ctx = jax.lax.map(chunk, (q, shared(k), shared(v)))
    ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(rows, seq, h, hd)
    if gate:
        ctx = ctx * jax.nn.sigmoid(g)[..., None]
    return mm("bse,ed->bsd", ctx.reshape(rows, seq, h * hd),
              a["o_proj"]["kernel"])


def _gated(gate_up):
    """``silu(gate) * up`` of ``[gate, up]`` side by side."""
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _swiglu(p, m, mm):
    return mm("tf,fd->td", _gated(mm(
        "td,df->tf", m, _kernels(p, "gate_proj", "up_proj"))),
        p["down_proj"]["kernel"])


def moe_layer(p, m, s: dict, mm, shared=None, factor=None):
    """``(y, aux)`` for ``m`` (tokens, hidden): the held experts' part of
    the expert layer's output plus the shared expert's (``shared``: its
    tree, or None), and the load-balancing term over all experts.  Every
    held expert runs on every token; a token's weight for an expert it did
    not pick is nought.  ``factor`` in place of
    ``moe_routed_scaling_factor`` is a planted fault's oracle."""
    t, d = m.shape
    k, e, held = s["num_experts_per_tok"], s["num_experts"], \
        np.asarray(s["experts_held"])
    ex = p["experts"]
    n, f = ex["down_proj"]["kernel"].shape[:2]
    # the router, every held expert's gate and up and the shared expert's:
    # ONE product of the layer's input, and the way down ONE more
    kernels = [p["router"]["kernel"], _kernels(
        ex, "gate_proj", "up_proj").transpose(1, 0, 2).reshape(d, -1)]
    if shared is not None:
        kernels.append(_kernels(shared, "gate_proj", "up_proj"))
    logits, inner, gate_up = jnp.split(
        mm("td,de->te", m, jnp.concatenate(kernels, axis=-1)),
        [e, e + n * 2 * f], axis=-1)
    g = jax.nn.softmax(logits, axis=-1)
    # (tokens, experts): whether the token picked the expert, the ``k``
    # largest one after another (of equals the first, as a top-k takes
    # them): a mask and sums where a sort or an indexed update would do
    chose = jnp.zeros((t, e), bool)
    for _ in range(k):
        best = jnp.argmax(jnp.where(chose, -jnp.inf, g), axis=-1)
        chose |= jnp.arange(e) == best[:, None]
    scale = s["moe_routed_scaling_factor"] if factor is None else factor
    picked = scale * g * chose / (g * chose).sum(-1, keepdims=True)
    mine = (np.arange(e)[:, None] == held[None, :]).astype(np.float32)
    weights = (picked[:, :, None] * mine).sum(axis=1)
    # sum_n w_n h_n D_n + S(m): one contraction over (expert, width) and
    # the shared expert's width
    hidden = (weights[:, :, None] * _gated(inner.reshape(t, n, 2 * f))
              ).reshape(t, -1)
    down = ex["down_proj"]["kernel"].reshape(-1, d)
    if shared is not None:
        hidden = jnp.concatenate([hidden, _gated(gate_up)], axis=-1)
        down = jnp.concatenate([down, shared["down_proj"]["kernel"]])
    y = mm("tf,fd->td", hidden, down)
    aux = e * jnp.sum(chose.sum(axis=0) / t * g.mean(axis=0))
    return y, aux


def _layer(p, x, kind: str, s: dict, mm):
    """One layer: its attention of ``kind``, then a SwiGLU or, where ``p``
    has ``moe``, the experts; ``(y, load-balancing term)``."""
    b, seq, d = x.shape
    eps = s["rms_norm_eps"]
    h1 = x + attention(p["attention"], _rms(p["input_norm"], x, eps), kind,
                       s, mm)
    m = _rms(p["post_norm"], h1, eps).reshape(b * seq, d)
    if "moe" in p:
        y, aux = moe_layer(p["moe"], m, s, mm, p["shared_experts"])
    else:
        y, aux = _swiglu(p, m, mm), 0.0
    return h1 + y.reshape(b, seq, d), aux


def _run(params, stats, ids, cast, model_kwargs=None):
    """``(logits, sum of the sparse layers' load-balancing terms)``.  A run
    of layers of one kind is ONE ``lax.scan`` over their stacked trees:
    they are the same program, traced and compiled once."""
    del stats
    q = cast or (lambda a: a)

    def mm(eq, a, b):
        """``einsum(eq, a, b)`` at full precision.  Where ``b`` is a matrix
        of the model (no token axis) the tokens of ``a`` go through it
        ``TOKEN_BLOCK`` at a time: the chip's compiler takes seconds for
        one float32 product at full precision, the longer the wider its
        contraction, and a matrix's gradient contracts over the tokens
        (PERF.md section 6)."""
        (mine, theirs), out = eq.split("->")[0].split(","), eq.split("->")[1]
        lead = len(mine) - len(mine.lstrip("bst"))
        tokens = int(np.prod(a.shape[:lead]))
        if set(theirs) & set("bstqk") or tokens <= TOKEN_BLOCK \
                or tokens % TOKEN_BLOCK:
            return jnp.einsum(eq, q(a), q(b), precision=HI)
        blocks = jax.lax.map(
            lambda rows: jnp.einsum(
                f"t{mine[lead:]},{theirs}->t{out[lead:]}", q(rows), q(b),
                precision=HI),
            a.reshape(-1, TOKEN_BLOCK, *a.shape[lead:]))
        return blocks.reshape(*a.shape[:lead], *blocks.shape[2:])

    names = sorted(params, key=lambda k: int(k[5:]))
    s = sizes(_KWARGS if model_kwargs is None else model_kwargs)
    x = params[names[0]]["embedding"][ids]
    aux = 0.0
    layers = list(zip(names[1:-2], s["layer_types"]))
    group = lambda at: (at[1], "moe" in params[at[0]])  # noqa: E731
    for (kind, _), run in itertools.groupby(layers, key=group):
        run = [name for name, _ in run]

        @jax.checkpoint
        def body(x, p, kind=kind):
            return _layer(p, x, kind, s, mm)
        if len(run) == 1:
            x, term = body(x, params[run[0]])
        else:
            x, terms = jax.lax.scan(body, x, jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *(params[n] for n in run)))
            term = terms.sum()
        aux = aux + term
    x = _rms(params[names[-2]], x, s["rms_norm_eps"])
    return mm("bsd,dv->bsv", x, params[names[-1]]["kernel"]), aux


# the last pass: the harness asks for a microbatch's logits (``forward``)
# and then for its load-balancing terms (``extra_objective``) with the same
# arguments, inside one trace; the second call is handed the first one's
# pass, where it would otherwise be computed (and differentiated) twice
_LAST: list = []


def _pass(params, stats, ids, cast, model_kwargs):
    args = (params, stats, ids, cast, model_kwargs)
    if not (_LAST and all(a is b for a, b in zip(_LAST[0], args))):
        _LAST[:] = [args, _run(*args)]
    return _LAST[1]


def forward(params, stats, ids, *, train=False, key=None, cast=None,
            model_kwargs=None):
    """Next-token logits (B, S, vocab) for token ids (B, S).  ``cast``
    (the control) rounds every matmul operand."""
    del train, key
    return _pass(params, stats, ids, cast, model_kwargs)[0]


def extra_objective(params, stats, ids, key, cast, model_kwargs=None):
    """One microbatch's load-balancing terms, weighted as the program
    weights what its expert layers sow."""
    del key
    return AUX_WEIGHT * _pass(params, stats, ids, cast, model_kwargs)[1]


# -- operations -----------------------------------------------------------------

def keys_seen(seq: int, window=None) -> float:
    """Keys a row's queries see, summed: ``min(p + 1, window)`` over
    ``p = 0 .. seq - 1``."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def train_flops_per_sample(flops, model_kwargs=None) -> float:
    """Forward+backward FLOPs of one training sample (one row), from
    shapes: 3x the forward multiply-adds of every layer's attention (its
    ``q``, ``k``, ``v``, gate and output projections at that layer's head
    count; scores and values over the keys a query may really see: the
    window's count in ``sliding_attention`` layers, the causal triangle in
    ``full_attention`` ones), of a dense layer's SwiGLU, of a sparse
    layer's router, shared expert and the held experts' three products for
    the pairs that fall to them on average (``num_experts_per_tok * held /
    num_experts`` a token), and of the head.  The embedding lookup counts
    nothing; recomputation neither."""
    s = sizes(model_kwargs)
    d, seq, hd = s["hidden_size"], s["seq_len"], s["head_dim"]
    kv = s["num_key_value_heads"]
    pairs = seq * s["num_experts_per_tok"] * len(s["experts_held"]) \
        / s["num_experts"]
    total = flops.dense(seq, d, s["vocab_size"])
    for kind, ff, h in zip(s["layer_types"], s["mlp_layer_types"],
                           s["num_attention_heads_per_layer"]):
        window = s["sliding_window"] if kind == SLIDING else None
        total += (flops.dense(seq, d, h * hd + 2 * kv * hd + h)
                  + flops.dense(seq, h * hd, d)
                  + 2 * flops.dense(keys_seen(seq, window), hd, h))
        if ff == "sparse":
            total += (flops.dense(seq, d, s["num_experts"])
                      + 3 * flops.dense(
                          seq, d, s["shared_expert_intermediate_size"])
                      + 3 * flops.dense(pairs, d, s["moe_intermediate_size"]))
        else:
            total += 3 * flops.dense(seq, d, s["intermediate_size"])
    return 3.0 * total
