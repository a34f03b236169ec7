"""TinyLlama-style causal LM as indexed layers (north-star config #5).

Fresh design — the reference tops out at BERT-base/128 tokens; the
4-stage-pipeline target config needs a modern decoder.  LLaMA family
geometry: RMSNorm pre-norm blocks, rotary position embeddings, grouped-
query attention, SwiGLU MLP, untied LM head.  TinyLlama-1.1B defaults
(2048 hidden, 22 blocks, 32 Q / 4 KV heads, 5632 intermediate, 32000
vocab); tests pass tiny overrides through the same builder.

Split-layer contract: 1 = token embedding, 2..n_block+1 = decoder blocks,
n_block+2 = final RMSNorm, n_block+3 = LM head (25 layers at full size).
The streaming activation between any two stages is the (B, S, H) hidden
state — exactly what ``ppermute``/the wire carries.  Causality needs no
mask plumbing across stages: each block rebuilds its own causal mask from
the sequence length.

Loss: next-token CE — the labels tensor is the input shifted by the data
pipeline (``data/datasets.py`` TINYSTORIES provider), so the pipeline's
``softmax_cross_entropy`` path broadcasts over (B, S) unchanged.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.models.split import (
    LayerSpec, register_model, module_plain_fn as _plain_fn,
)


def rope_inv_freq(head_dim: int, base: float) -> np.ndarray:
    """Plain rotary frequencies ``base^(-2i / head_dim)``, i = 0..D/2-1."""
    return 1.0 / (base ** (np.arange(0, head_dim, 2) / head_dim))


def _rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: np.ndarray,
          interleaved: bool = True, factor: float = 1.0) -> jnp.ndarray:
    """Rotary embedding over the last dim of (B, S, H, D).  The caller
    says which frequencies (``inv_freq``, D/2 of them), which pairing —
    ``interleaved`` turns the pairs (2i, 2i + 1), the half-split
    (``rotate_half``) convention the pairs (i, i + D/2) — and a
    ``factor`` on cos and sin (YaRN's attention factor)."""
    freqs = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = factor * jnp.cos(freqs)[None, :, None, :]
    sin = factor * jnp.sin(freqs)[None, :, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return rot.astype(x.dtype)


class LlamaAttention(nn.Module):
    """Causal GQA with RoPE.

    ``use_flash`` routes the score/softmax/value contraction through the
    fused Pallas kernel (``ops/flash_attention.py``) instead of the
    XLA einsum path — same math, O(S) memory.
    """
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    dtype: jnp.dtype = jnp.float32
    use_flash: bool = False
    rope_base: float = 10000.0
    # sequence-parallel mode (parallel/sequence.py): when set, this
    # module runs inside shard_map with `seq_axis` defined, x is the
    # LOCAL token block, RoPE positions offset by the global block
    # index, and attention goes through ring_attention
    seq_axis: str | None = None

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        hd = self.hidden_size // self.num_heads
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        q = dense(self.num_heads * hd, name="q_proj")(x)
        k = dense(self.num_kv_heads * hd, name="k_proj")(x)
        v = dense(self.num_kv_heads * hd, name="v_proj")(x)
        q = q.reshape(b, s, self.num_heads, hd)
        k = k.reshape(b, s, self.num_kv_heads, hd)
        v = v.reshape(b, s, self.num_kv_heads, hd)

        if self.seq_axis is not None:
            import jax
            pos = jax.lax.axis_index(self.seq_axis) * s + jnp.arange(s)
        else:
            pos = jnp.arange(s)
        inv_freq = rope_inv_freq(hd, self.rope_base)
        q, k = _rope(q, pos, inv_freq), _rope(k, pos, inv_freq)
        rep = self.num_heads // self.num_kv_heads
        if rep > 1 and not (self.use_flash and self.seq_axis is None):
            # the flash kernel picks a query head's key-value head by
            # index; the ring and the einsum want one of each
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        if self.seq_axis is not None:
            from split_learning_tpu.parallel.sequence import ring_attention
            out = ring_attention(q, k, v, axis_name=self.seq_axis,
                                 causal=True).reshape(b, s, -1)
        elif self.use_flash:
            from split_learning_tpu.ops.flash_attention import (
                flash_attention,
            )
            out = flash_attention(q, k, v, causal=True).reshape(b, s, -1)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
            mask = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(mask[None, None], scores, -1e30)
            probs = nn.softmax(
                scores.astype(jnp.float32)).astype(self.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        return dense(self.hidden_size, name="o_proj")(out)


class LlamaBlock(nn.Module):
    """Pre-RMSNorm: x + attn(norm(x)); x + swiglu(norm(x))."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    dtype: jnp.dtype = jnp.float32
    use_flash: bool = False
    seq_axis: str | None = None

    @nn.compact
    def __call__(self, x):
        h = nn.RMSNorm(epsilon=1e-5, dtype=self.dtype,
                       name="input_norm")(x)
        x = x + LlamaAttention(
            hidden_size=self.hidden_size, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, dtype=self.dtype,
            use_flash=self.use_flash, seq_axis=self.seq_axis,
            name="attention")(h)
        h = nn.RMSNorm(epsilon=1e-5, dtype=self.dtype,
                       name="post_norm")(x)
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        gate = nn.silu(dense(self.intermediate_size, name="gate_proj")(h))
        up = dense(self.intermediate_size, name="up_proj")(h)
        return x + dense(self.hidden_size, name="down_proj")(gate * up)


class MoELlamaBlock(nn.Module):
    """LlamaBlock with the dense SwiGLU MLP swapped for a top-k
    mixture-of-experts FFN (:class:`~split_learning_tpu.parallel.expert.
    MoEMLP`) — the expert-parallel scale-out variant (no reference
    counterpart; SURVEY.md §2.2 EP row)."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    num_experts: int = 8
    k: int = 2
    dtype: jnp.dtype = jnp.float32
    use_flash: bool = False
    seq_axis: str | None = None

    @nn.compact
    def __call__(self, x):
        from split_learning_tpu.parallel.expert import MoEMLP
        h = nn.RMSNorm(epsilon=1e-5, dtype=self.dtype,
                       name="input_norm")(x)
        x = x + LlamaAttention(
            hidden_size=self.hidden_size, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, dtype=self.dtype,
            use_flash=self.use_flash, seq_axis=self.seq_axis,
            name="attention")(h)
        h = nn.RMSNorm(epsilon=1e-5, dtype=self.dtype,
                       name="post_norm")(x)
        return x + MoEMLP(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_experts=self.num_experts, k=self.k, dtype=self.dtype,
            name="moe")(h)


def _llama_specs(vocab_size: int = 32000, hidden_size: int = 2048,
                 num_heads: int = 32, num_kv_heads: int = 4,
                 intermediate_size: int = 5632, n_block: int = 22,
                 use_flash: bool = False, dtype=jnp.float32,
                 num_experts: int = 0, k: int = 2,
                 seq_axis: str | None = None) -> tuple:
    specs = [LayerSpec("layer1", make=functools.partial(
        nn.Embed, num_embeddings=vocab_size, features=hidden_size,
        dtype=dtype), fn=_plain_fn)]
    for i in range(n_block):
        if num_experts > 0:
            block = functools.partial(
                MoELlamaBlock, hidden_size=hidden_size,
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                intermediate_size=intermediate_size,
                num_experts=num_experts, k=k, use_flash=use_flash,
                seq_axis=seq_axis, dtype=dtype)
        else:
            block = functools.partial(
                LlamaBlock, hidden_size=hidden_size, num_heads=num_heads,
                num_kv_heads=num_kv_heads,
                intermediate_size=intermediate_size, use_flash=use_flash,
                seq_axis=seq_axis, dtype=dtype)
        specs.append(LayerSpec(f"layer{2 + i}", make=block, fn=_plain_fn))
    specs.append(LayerSpec(f"layer{2 + n_block}",
                           make=functools.partial(nn.RMSNorm, epsilon=1e-5,
                                                  dtype=dtype),
                           fn=_plain_fn))
    specs.append(LayerSpec(f"layer{3 + n_block}", make=functools.partial(
        nn.Dense, features=vocab_size, use_bias=False, dtype=dtype),
        fn=_plain_fn))
    return tuple(specs)


@register_model("TinyLlama_TINYSTORIES")
def tinyllama_tinystories(dtype=jnp.float32, **kw) -> tuple:
    """TinyLlama-1.1B geometry; input (B, S) int32 token ids, output
    (B, S, vocab) next-token logits.  25 layers at full size."""
    return _llama_specs(dtype=dtype, **kw)


@register_model("TinyLlamaMoE_TINYSTORIES")
def tinyllama_moe_tinystories(dtype=jnp.float32, num_experts: int = 8,
                              **kw) -> tuple:
    """Sparse-MoE variant: every decoder block's MLP is a top-k
    mixture of ``num_experts`` SwiGLU experts, shardable over an
    ``expert`` mesh axis (``parallel/expert.py``).  Same split-layer
    contract as the dense model."""
    return _llama_specs(dtype=dtype, num_experts=num_experts, **kw)
