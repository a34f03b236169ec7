"""Plain reference for ``mellum2_12b_c3``: the UNSPLIT decoder of
Mellum2-12B-A2.5B-Instruct in float32 ``jax.numpy`` at full matmul
precision, with the share of experts and of the vocabulary that the
configuration's one chip holds.  It imports nothing of the program and has
no kernel, no sorted dispatch and no capacity: attention is a masked
softmax over whole rows of scores, taken a few query heads at a time, and
every held expert is applied to every token and weighted by what the
router gave it (nought for the tokens that did not pick it).

Written from the keys of the published configuration
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json).
For block ``l`` with input ``x`` (rows, S, hidden):

* ``n = RMSNorm(x; rms_norm_eps)``; ``q = n Wq`` (heads x head_dim),
  ``k = n Wk``, ``v = n Wv`` (key-value heads x head_dim), no bias;
* RoPE on ``q`` and ``k`` in the half-split (``rotate_half``) convention:
  ``sliding_attention`` layers with ``inv_freq_i = theta^(-2i / head_dim)``,
  ``full_attention`` layers with YaRN's frequencies (``yarn_inv_freq``,
  computed once, not per length) and cos and sin multiplied by
  ``attention_factor``;
* scores ``q k^T / sqrt(head_dim)``; query head ``h`` reads key-value head
  ``h // (heads / key-value heads)``; causal; in ``sliding_attention``
  layers a query at ``p`` sees keys ``p - window + 1 .. p``; softmax in
  float32; ``h1 = x + concat(heads) Wo``;
* ``m = RMSNorm(h1)``; ``g = softmax(m Wr)`` over ALL ``num_experts``;
  ``I = top-k(g)``; ``w_i = g_i / sum_{j in I} g_j``;
  ``y = sum_{i in I, i held} w_i Wdown_i (silu(Wgate_i m) * Wup_i m)``;
  ``out = h1 + y``.  What the absent experts would add is left out, here
  as in the program;
* after the last block RMSNorm and the untied head (the held slice of the
  vocabulary).

``assumed`` (no key in the configuration; the family's convention): no
normalisation of ``q``/``k``, no router bias, and the load-balancing term
``E * sum_e f_e P_e`` over all experts (``f_e``: the (token, choice) pairs
routed to ``e`` over the tokens; ``P_e``: the mean router probability) with
weight ``AUX_WEIGHT``.  Left out: the "MTP head" of the model card, which
has no key in the configuration.

Tree names are the program's (``layer1`` embedding, ``layer2``.. blocks,
then the final norm and the head; a block's experts under
``moe/experts/{gate,up,down}_proj/kernel`` with the held experts leading),
so the tree this makes is the tree the program's checkpoint holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"
SIZES = dict(
    vocab_size=98304, hidden_size=2304, num_attention_heads=32,
    num_key_value_heads=4, head_dim=128, num_hidden_layers=28,
    layer_types=None, sliding_window=1024, rms_norm_eps=1e-6,
    num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
    experts_held=None, seq_len=4096,
    rope_parameters={
        FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
               "original_max_position_embeddings": 8192,
               "beta_fast": 32.0, "beta_slow": 1.0,
               "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000.0}})
DATASET = "tokens"
# weight of the load-balancing term: the Qwen-MoE default
# (``router_aux_loss_coef``) for configurations with these key names; the
# program is given the same under ``learning.moe-aux-weight``
AUX_WEIGHT = 0.001
# query heads whose scores are held at once: (rows, 4, S, S) float32 is
# 256 MB a row of 4,096 tokens, where all 32 heads would be 2 GiB
HEAD_CHUNK = 4
HI = jax.lax.Precision.HIGHEST


def sizes(model_kwargs=None) -> dict:
    s = dict(SIZES)
    s.update({k: v for k, v in (model_kwargs or {}).items() if k in s})
    n = s["num_hidden_layers"]
    s["layer_types"] = tuple(s["layer_types"] or (
        FULL if i % 4 == 3 else SLIDING for i in range(n)))
    held = s["experts_held"]
    s["experts_held"] = tuple(range(s["num_experts"])) if held is None \
        else tuple(range(held)) if isinstance(held, int) else tuple(held)
    return s


# the sizes ``init`` was last given: the harness hands ``model-kwargs`` to
# ``init`` and ``train_flops_per_sample`` alone, and a tree does not show a
# head's width, the window, the experts a token picks or a layer's kind
_KWARGS: dict = {}


def init(key, model_kwargs=None):
    """(params, batch_stats) from one key: normal(0.02) matrices, unit norm
    scales, and two departures from a flat normal(0.02), both so that the
    work of a step does not hang on the seed (PERF.md section 6, PR 30):

    * the embedding is normal(1): a residual stream of unit size, as a
      trained model's is.  At 0.02 the attention's common part (the mean
      value of a Zipf-like stream) is 8 times a token's own embedding,
      every token of a block routes alike, and how many pairs fall to the
      held experts is a draw of 0, 1, 2 or 3 a token for the whole block;
    * the router's kernel is ``num_experts / held`` copies of ``held``
      drawn columns, one copy for each chip's group of experts, where
      that many are the experts a token picks (64 / 8 = 8 = top-8): a
      token's choices are the copies of its best column, one on every
      chip, so the held share computes exactly one pair a token, the
      eighth of the routed work that an expert-parallel job's
      load-balancing term holds a chip to.  Drawn whole, the routers give
      the held experts 0.92-1.07 pairs a token by the seed (a Zipf-like
      stream has few kinds of token, and each kind's 8 choices are one
      draw), and the grouped products' time goes with the pairs.

    What the tiling costs the comparison: the 8 chosen logits tie, so
    every chosen weight is 1/8 and a weight attached to another of a
    token's choices reads the same.  ``tests/test_mellum.py`` and
    ``tests/test_moe_held.py`` hold the program to this reference with
    routers drawn whole (unequal weights, 0-2 held pairs a token) at
    small sizes; on the chip the comparison holds the tiled state.

    Traceable: the harness jits it."""
    _KWARGS.clear()
    _KWARGS.update(model_kwargs or {})
    s = sizes(model_kwargs)
    d, f, hd = s["hidden_size"], s["moe_intermediate_size"], s["head_dim"]
    q, kv = s["num_attention_heads"] * hd, s["num_key_value_heads"] * hd
    e, n_held = s["num_experts"], len(s["experts_held"])
    chips = e // n_held
    balanced = e % n_held == 0 and chips == s["num_experts_per_tok"]
    n = [0]

    def w(*shape, std=0.02):
        n[0] += 1
        return {"kernel": std * jax.random.normal(
            jax.random.fold_in(key, n[0]), shape)}

    def router():
        if not balanced:
            return w(d, e)
        return {"kernel": jnp.tile(w(d, n_held)["kernel"], (1, chips))}

    params = {"layer1": {"embedding": w(s["vocab_size"], d,
                                        std=1.0)["kernel"]}}
    for b in range(s["num_hidden_layers"]):
        params[f"layer{b + 2}"] = {
            "input_norm": {"scale": jnp.ones((d,))},
            "attention": {"q_proj": w(d, q), "k_proj": w(d, kv),
                          "v_proj": w(d, kv), "o_proj": w(q, d)},
            "post_norm": {"scale": jnp.ones((d,))},
            "moe": {"router": router(),
                    "experts": {"gate_proj": w(n_held, d, f),
                                "up_proj": w(n_held, d, f),
                                "down_proj": w(n_held, f, d)}}}
    last = s["num_hidden_layers"] + 2
    params[f"layer{last}"] = {"scale": jnp.ones((d,))}
    params[f"layer{last + 1}"] = w(d, s["vocab_size"])
    return params, {}


# -- rotary embedding ---------------------------------------------------------

def yarn_inv_freq(head_dim: int, rope_theta: float, factor: float,
                  original_max_position_embeddings: int, beta_fast: float,
                  beta_slow: float, **_) -> np.ndarray:
    """``extrap_i = theta^(-2i / D)``, ``interp_i = extrap_i / factor``,
    ``cd(r) = D ln(L / (2 pi r)) / (2 ln theta)``, ``low =
    max(floor(cd(beta_fast)), 0)``, ``high = min(ceil(cd(beta_slow)),
    D - 1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
    ``inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i)``."""
    i = np.arange(head_dim // 2)
    extrap = rope_theta ** (-2.0 * i / head_dim)

    def cd(rotations):
        return head_dim * math.log(
            original_max_position_embeddings / (2 * math.pi * rotations)) \
            / (2 * math.log(rope_theta))
    low = max(math.floor(cd(beta_fast)), 0)
    high = min(math.ceil(cd(beta_slow)), head_dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (extrap / factor) * ramp + extrap * (1 - ramp)


def _rope(x, kind: str, rope_parameters: dict):
    """(B, S, H, D): the pair (i, i + D/2) of a head turns by
    ``position * inv_freq_i`` (``x cos + rotate_half(x) sin``)."""
    seq, hd = x.shape[1], x.shape[-1]
    p = rope_parameters[kind]
    if p.get("rope_type", "default") == "yarn":
        inv_freq, factor = yarn_inv_freq(hd, **{
            k: v for k, v in p.items() if k != "rope_type"}), \
            p["attention_factor"]
    else:
        inv_freq = p["rope_theta"] ** (-2.0 * np.arange(hd // 2) / hd)
        factor = 1.0
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = (factor * f(jnp.concatenate([angle, angle], axis=-1))[
        None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


# -- one block ------------------------------------------------------------------

def _rms(p, x, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * p["scale"]


def _attention(q, k, v, window, mm):
    """Masked softmax attention, ``HEAD_CHUNK`` query heads at a time
    (each chunk recomputed in the backward pass, so that one chunk's
    probabilities are all that is ever held)."""
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    c = math.gcd(HEAD_CHUNK, rep)
    pos = jnp.arange(s)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    # chunk i holds query heads i*c .. i*c + c - 1, all of one key-value
    # head: head j reads key-value head j // rep
    kv_of = np.arange(h // c) * c // rep
    qs = q.reshape(b, s, h // c, c, hd).transpose(2, 0, 1, 3, 4)
    ks, vs = (t.transpose(2, 0, 1, 3)[kv_of] for t in (k, v))

    @jax.checkpoint
    def chunk(args):
        qc, kc, vc = args
        scores = mm("bqcd,bkd->bcqk", qc, kc) / hd ** 0.5
        probs = jax.nn.softmax(
            jnp.where(seen, scores, jnp.finfo(jnp.float32).min), axis=-1)
        return mm("bcqk,bkd->bqcd", probs, vc)
    out = jax.lax.map(chunk, (qs, ks, vs))       # (h / c, B, S, c, D)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, s, h * hd)


def moe_layer(p, m, s: dict, mm):
    """``(y, aux)`` for ``m`` (tokens, hidden): the held experts' part of
    the expert layer's output, and the load-balancing term over all
    experts.  Every held expert runs on every token; a token's weight for
    an expert it did not pick is nought."""
    t = m.shape[0]
    k, e, held = s["num_experts_per_tok"], s["num_experts"], \
        np.asarray(s["experts_held"])
    g = jax.nn.softmax(mm("td,de->te", m, p["router"]["kernel"]), axis=-1)
    top_g, top_i = jax.lax.top_k(g, k)
    w = top_g / top_g.sum(-1, keepdims=True)
    picked = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], top_i].set(w)
    ex = p["experts"]
    hidden = jax.nn.silu(mm("td,ndf->tnf", m, ex["gate_proj"]["kernel"])) \
        * mm("td,ndf->tnf", m, ex["up_proj"]["kernel"])
    y = mm("tnf,nfd->tnd", hidden, ex["down_proj"]["kernel"])
    y = (picked[:, held, None] * y).sum(axis=1)
    pairs = jnp.zeros((e,)).at[top_i.reshape(-1)].add(1.0)
    aux = e * jnp.sum(pairs / t * g.mean(axis=0))
    return y, aux


def _block(p, x, kind: str, s: dict, mm):
    b, seq, d = x.shape
    hd, a, eps = s["head_dim"], p["attention"], s["rms_norm_eps"]
    n = _rms(p["input_norm"], x, eps)
    q, k, v = (mm("bsd,de->bse", n, a[name]["kernel"]).reshape(
        b, seq, -1, hd) for name in ("q_proj", "k_proj", "v_proj"))
    q, k = (_rope(t, kind, s["rope_parameters"]) for t in (q, k))
    ctx = _attention(q, k, v,
                     s["sliding_window"] if kind == SLIDING else None, mm)
    h1 = x + mm("bse,ed->bsd", ctx, a["o_proj"]["kernel"])
    y, aux = moe_layer(p["moe"], _rms(p["post_norm"], h1, eps).reshape(
        b * seq, d), s, mm)
    return h1 + y.reshape(b, seq, d), aux


def _run(params, ids, cast, model_kwargs=None):
    """``(logits, sum of the blocks' load-balancing terms)``."""
    q = cast or (lambda a: a)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a), q(b), precision=HI)

    names = sorted(params, key=lambda k: int(k[5:]))
    # the tree says how deep and how many held; the rest are the sizes
    s = sizes({**(_KWARGS if model_kwargs is None else model_kwargs),
               "num_hidden_layers": len(names) - 3})
    x = params[names[0]]["embedding"][ids]
    aux = 0.0
    for name, kind in zip(names[1:-2], s["layer_types"]):
        x, a = jax.checkpoint(
            lambda p, x, kind=kind: _block(p, x, kind, s, mm))(
                params[name], x)
        aux = aux + a
    x = _rms(params[names[-2]], x, s["rms_norm_eps"])
    return mm("bsd,dv->bsv", x, params[names[-1]]["kernel"]), aux


# the last pass: the harness asks for a microbatch's logits (``forward``)
# and then for its load-balancing terms (``extra_objective``) with the same
# arguments, inside one trace; the second call is handed the first one's
# pass, where it would otherwise be computed (and differentiated) twice
_LAST: list = []


def _pass(params, ids, cast, model_kwargs):
    args = (params, ids, cast, model_kwargs)
    if not (_LAST and all(a is b for a, b in zip(_LAST[0], args))):
        _LAST[:] = [args, _run(params, ids, cast, model_kwargs)]
    return _LAST[1]


def forward(params, stats, ids, *, train=False, key=None, cast=None,
            model_kwargs=None):
    """Next-token logits (B, S, vocab) for token ids (B, S).  ``cast``
    (the control) rounds every matmul operand."""
    del stats, train, key
    return _pass(params, ids, cast, model_kwargs)[0]


def extra_objective(params, stats, ids, key, cast, model_kwargs=None):
    """One microbatch's load-balancing terms, weighted as the program
    weights what its expert layers sow."""
    del stats, key
    return AUX_WEIGHT * _pass(params, ids, cast, model_kwargs)[1]


# -- operations -----------------------------------------------------------------

def keys_seen(seq: int, window=None) -> float:
    """Keys a row's queries see, summed: ``min(p + 1, window)`` over
    ``p = 0 .. seq - 1``."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def train_flops_per_sample(flops, model_kwargs=None) -> float:
    """Forward+backward FLOPs of one training sample (one row), from
    shapes: 3x the forward multiply-adds of every block's four
    projections, of scores and context over the keys a query may really
    see (the window's count in ``sliding_attention`` layers, the causal
    triangle in ``full_attention`` ones), of the router, of the held
    experts' three products for the pairs that fall to them on average
    (``num_experts_per_tok * held / num_experts`` a token), and of the
    head.  The embedding lookup counts nothing; recomputation neither."""
    s = sizes(model_kwargs)
    d, seq, hd = s["hidden_size"], s["seq_len"], s["head_dim"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    pairs = seq * s["num_experts_per_tok"] * len(s["experts_held"]) \
        / s["num_experts"]
    total = flops.dense(seq, d, s["vocab_size"])
    for kind in s["layer_types"]:
        window = s["sliding_window"] if kind == SLIDING else None
        total += (2 * flops.dense(seq, d, h * hd)
                  + 2 * flops.dense(seq, d, kv * hd)
                  + 2 * flops.dense(keys_seen(seq, window), hd, h)
                  + flops.dense(seq, d, s["num_experts"])
                  + 3 * flops.dense(pairs, d, s["moe_intermediate_size"]))
    return 3.0 * total
