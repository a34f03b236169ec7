"""Plain reference for ``bert_base_c7``: the UNSPLIT BERT-base sequence
classifier (bert-base-cased sizes, 4 AG-News labels) in float32
``jax.numpy`` at full matmul precision.  It imports nothing of the
program.

Follows ``src/model/BERT_AGNEWS.py`` of the paper's repository / Devlin et
al. 2018: word + position + token-type embeddings, LayerNorm; 12 post-LN
blocks (12-head self-attention with padded keys masked, GELU feed-forward
3072); tanh pooler on [CLS]; linear classifier.  Tree names are the
program's (``layer1`` embeddings, ``layer2``..``layer13`` blocks,
``layer14`` pooler, ``layer15`` classifier), so the tree this makes is
the tree the program's checkpoint holds.

Departures, noted: GELU is the tanh approximation (what the program
runs); dropout is 0 in this configuration (``reduced`` in the YAML), so
there is none here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SIZES = dict(vocab_size=28996, hidden_size=768, num_heads=12,
             intermediate_size=3072, max_position_embeddings=512,
             n_block=12, type_vocab_size=2, num_labels=4, seq_len=128)
NUM_CLASSES = 4
INPUT_SHAPE = (128,)
DATASET = "agnews"
LN_EPS = 1e-12
# no batch statistics: a microbatch may be taken in blocks of rows so that
# the float32 activations of 128-token sequences fit beside the weights
ROW_BLOCK = 32
PAD_ID = 0
HI = jax.lax.Precision.HIGHEST


def sizes(model_kwargs=None) -> dict:
    s = dict(SIZES)
    s.update({k: v for k, v in (model_kwargs or {}).items() if k in s})
    return s


def init(key, model_kwargs=None):
    """(params, batch_stats) from one key: normal(0.02) matrices and
    embeddings, zero biases, unit LayerNorm scales (BERT's own
    initializer).  Traceable: the harness jits it."""
    s = sizes(model_kwargs)
    d, h, f = s["hidden_size"], s["num_heads"], s["intermediate_size"]
    hd = d // h
    n = [0]

    def w(*shape):
        n[0] += 1
        return 0.02 * jax.random.normal(jax.random.fold_in(key, n[0]), shape)

    def ln():
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}

    params = {"layer1": {
        "word_embeddings": {"embedding": w(s["vocab_size"], d)},
        "position_embeddings": {
            "embedding": w(s["max_position_embeddings"], d)},
        "token_type_embeddings": {"embedding": w(s["type_vocab_size"], d)},
        "LayerNorm": ln()}}
    for b in range(s["n_block"]):
        params[f"layer{b + 2}"] = {
            "attention": {
                "query": {"kernel": w(d, h, hd), "bias": jnp.zeros((h, hd))},
                "key": {"kernel": w(d, h, hd), "bias": jnp.zeros((h, hd))},
                "value": {"kernel": w(d, h, hd), "bias": jnp.zeros((h, hd))},
                "out": {"kernel": w(h, hd, d), "bias": jnp.zeros((d,))}},
            "attention_norm": ln(),
            "intermediate": {"kernel": w(d, f), "bias": jnp.zeros((f,))},
            "output": {"kernel": w(f, d), "bias": jnp.zeros((d,))},
            "output_norm": ln()}
    nb = s["n_block"]
    params[f"layer{nb + 2}"] = {
        "dense": {"kernel": w(d, d), "bias": jnp.zeros((d,))}}
    params[f"layer{nb + 3}"] = {"classifier": {
        "kernel": w(d, s["num_labels"]),
        "bias": jnp.zeros((s["num_labels"],))}}
    return params, {}


def _ln(p, x):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x).mean(-1, keepdims=True) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def forward(params, stats, ids, *, train=False, key=None, cast=None):
    """Logits for token ids (B, S).  ``cast`` (the control) rounds every
    matmul operand."""
    del stats, train, key
    q = cast or (lambda a: a)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a), q(b), precision=HI)

    e = params["layer1"]
    seq = ids.shape[1]
    x = (e["word_embeddings"]["embedding"][ids]
         + e["position_embeddings"]["embedding"][jnp.arange(seq)][None]
         + e["token_type_embeddings"]["embedding"][0][None, None])
    x = _ln(e["LayerNorm"], x)
    keep = (ids != PAD_ID)[:, None, None, :]
    blocks = sorted((k for k in params if k != "layer1"),
                    key=lambda k: int(k[5:]))
    for name in blocks[:-2]:
        p = params[name]
        a = p["attention"]
        qh = mm("bsd,dhk->bshk", x, a["query"]["kernel"]) + a["query"]["bias"]
        kh = mm("bsd,dhk->bshk", x, a["key"]["kernel"]) + a["key"]["bias"]
        vh = mm("bsd,dhk->bshk", x, a["value"]["kernel"]) + a["value"]["bias"]
        logits = mm("bqhk,bshk->bhqs", qh / (qh.shape[-1] ** 0.5), kh)
        logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1)
        ctx = mm("bhqs,bshk->bqhk", probs, vh)
        attn = mm("bqhk,hkd->bqd", ctx, a["out"]["kernel"]) + a["out"]["bias"]
        x = _ln(p["attention_norm"], x + attn)
        h = mm("bsd,df->bsf", x, p["intermediate"]["kernel"]) \
            + p["intermediate"]["bias"]
        h = jax.nn.gelu(h, approximate=True)
        h = mm("bsf,fd->bsd", h, p["output"]["kernel"]) + p["output"]["bias"]
        x = _ln(p["output_norm"], x + h)
    pool = params[blocks[-2]]["dense"]
    x = jnp.tanh(mm("bd,de->be", x[:, 0], pool["kernel"]) + pool["bias"])
    cls = params[blocks[-1]]["classifier"]
    return mm("bd,dc->bc", x, cls["kernel"]) + cls["bias"]


def train_flops_per_sample(flops, model_kwargs=None) -> float:
    """Forward+backward FLOPs of one training sample (one sequence), from
    shapes: 3x the forward multiply-adds of every block's projections,
    attention scores and context, feed-forward, plus pooler and
    classifier.  Embedding lookups are not matmuls and count nothing."""
    s = sizes(model_kwargs)
    d, seq = s["hidden_size"], s["seq_len"]
    block = flops.transformer_block(seq, d, s["num_heads"],
                                    s["intermediate_size"])
    head = flops.dense(1, d, d) + flops.dense(1, d, s["num_labels"])
    return 3.0 * (s["n_block"] * block + head)
