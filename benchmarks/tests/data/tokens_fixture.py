"""Plain reference for the harness's token-model FIXTURE (never a cell):
the UNSPLIT decoder the program registers as ``TinyLlama_TINYSTORIES`` in
float32 ``jax.numpy`` at full matmul precision.  It imports nothing of
the program.

Llama-family geometry (Touvron et al. 2023, arXiv:2302.13971; sizes of
TinyLlama-1.1B, Zhang et al. 2024, arXiv:2401.02385): token embedding;
pre-RMSNorm blocks of causal grouped-query attention with rotary position
embeddings (pairs interleaved, base 10,000) and a SwiGLU feed-forward;
final RMSNorm; untied head; no bias anywhere.  The loss is the mean
next-token cross-entropy over every position.  Tree names are the
program's (``layer1`` embedding, ``layer2``.. blocks, then the final norm
and the head), so the tree this makes is the tree the program's checkpoint
holds.

Each block is rematerialized (``jax.checkpoint``): the same numbers, and
a row of 2,048 tokens then keeps one block's attention probabilities
(0.5 GB) on the device, not ten blocks'.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(vocab_size=32000, hidden_size=2048, num_heads=32,
             num_kv_heads=4, intermediate_size=5632, n_block=22,
             seq_len=2048)
DATASET = "tokens"
# a head's width: the tree's two-dimensional kernels do not show how many
# heads share a projection, so ``forward`` counts them by this
HEAD_DIM = 64
RMS_EPS = 1e-5
ROPE_BASE = 10000.0
# no batch statistics and no term of the whole microbatch: a microbatch is
# taken row by row, so that one row's float32 activations fit beside the
# weights
ROW_BLOCK = 1
HI = jax.lax.Precision.HIGHEST


def sizes(model_kwargs=None) -> dict:
    s = dict(SIZES)
    s.update({k: v for k, v in (model_kwargs or {}).items() if k in s})
    return s


def init(key, model_kwargs=None):
    """(params, batch_stats) from one key: normal(0.02) matrices and
    embedding, unit norm scales.  Traceable: the harness jits it."""
    s = sizes(model_kwargs)
    d, f = s["hidden_size"], s["intermediate_size"]
    if d != s["num_heads"] * HEAD_DIM:
        raise ValueError(f"heads of {HEAD_DIM} only: hidden_size {d}, "
                         f"num_heads {s['num_heads']}")
    kv = s["num_kv_heads"] * HEAD_DIM
    n = [0]

    def w(*shape):
        n[0] += 1
        return {"kernel": 0.02 * jax.random.normal(
            jax.random.fold_in(key, n[0]), shape)}

    params = {"layer1": {"embedding": w(s["vocab_size"], d)["kernel"]}}
    for b in range(s["n_block"]):
        params[f"layer{b + 2}"] = {
            "input_norm": {"scale": jnp.ones((d,))},
            "attention": {"q_proj": w(d, d), "k_proj": w(d, kv),
                          "v_proj": w(d, kv), "o_proj": w(d, d)},
            "post_norm": {"scale": jnp.ones((d,))},
            "gate_proj": w(d, f), "up_proj": w(d, f), "down_proj": w(f, d)}
    params[f"layer{s['n_block'] + 2}"] = {"scale": jnp.ones((d,))}
    params[f"layer{s['n_block'] + 3}"] = w(d, s["vocab_size"])
    return params, {}


def _rms(p, x):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + RMS_EPS) * p["scale"]


def _rope(x):
    """Rotary embedding of (B, S, H, D): the pair (2i, 2i + 1) of a head
    turns by position / base^(2i / D)."""
    seq, hd = x.shape[1], x.shape[-1]
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        / ROPE_BASE ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    cos, sin = (f(freqs)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _block(p, x, mm):
    b, seq, d = x.shape
    hd, n_heads = HEAD_DIM, d // HEAD_DIM
    a = p["attention"]
    h = _rms(p["input_norm"], x)
    q = mm("bsd,de->bse", h, a["q_proj"]["kernel"]).reshape(b, seq, -1, hd)
    k = mm("bsd,de->bse", h, a["k_proj"]["kernel"]).reshape(b, seq, -1, hd)
    v = mm("bsd,de->bse", h, a["v_proj"]["kernel"]).reshape(b, seq, -1, hd)
    q, k = _rope(q), _rope(k)
    # query head j reads key/value head j // (heads / kv heads)
    rep = n_heads // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(
        jnp.where(causal, scores, jnp.finfo(jnp.float32).min), axis=-1)
    ctx = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, seq, d)
    x = x + mm("bsd,de->bse", ctx, a["o_proj"]["kernel"])
    h = _rms(p["post_norm"], x)
    gate = jax.nn.silu(mm("bsd,df->bsf", h, p["gate_proj"]["kernel"]))
    up = mm("bsd,df->bsf", h, p["up_proj"]["kernel"])
    return x + mm("bsf,fd->bsd", gate * up, p["down_proj"]["kernel"])


def forward(params, stats, ids, *, train=False, key=None, cast=None):
    """Next-token logits (B, S, vocab) for token ids (B, S).  ``cast``
    (the control) rounds every matmul operand."""
    del stats, train, key
    q = cast or (lambda a: a)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a), q(b), precision=HI)

    names = sorted(params, key=lambda k: int(k[5:]))
    x = params[names[0]]["embedding"][ids]
    block = jax.checkpoint(lambda p, x: _block(p, x, mm))
    for name in names[1:-2]:
        x = block(params[name], x)
    x = _rms(params[names[-2]], x)
    return mm("bsd,dv->bsv", x, params[names[-1]]["kernel"])


def train_flops_per_sample(flops, model_kwargs=None) -> float:
    """Forward+backward FLOPs of one training sample (one sequence), from
    shapes: 3x the forward multiply-adds of every block's four
    projections (keys and values at the key-value heads' width), causal
    attention (half of the full scores and context), the three SwiGLU
    products, and the head.  The embedding lookup counts nothing."""
    s = sizes(model_kwargs)
    d, seq, f = s["hidden_size"], s["seq_len"], s["intermediate_size"]
    kv = s["num_kv_heads"] * HEAD_DIM
    block = (2 * flops.dense(seq, d, d) + 2 * flops.dense(seq, d, kv)
             + flops.attention(seq, d) / 2 + 3 * flops.dense(seq, d, f))
    return 3.0 * (s["n_block"] * block
                  + flops.dense(seq, d, s["vocab_size"]))
