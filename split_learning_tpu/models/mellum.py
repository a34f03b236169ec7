"""Mellum-2-style causal LM as indexed layers: windowed and full attention
in one period, every block a sparse mixture of experts of which this
program may hold a share.

Written from the keys of the published configuration
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
pre-RMSNorm blocks; grouped-query attention, no bias, rotary embedding in
the half-split (``rotate_half``) convention; ``layer_types`` names each
block ``sliding_attention`` (a query at ``p`` sees keys ``p - window + 1 ..
p``; plain RoPE) or ``full_attention`` (YaRN frequencies, cos and sin
multiplied by its attention factor); after attention a top-k router over
``num_experts`` experts with renormalized weights and SwiGLU experts of
width ``moe_intermediate_size``, no shared expert; final RMSNorm; untied
head.  ``experts_held`` says which experts live here
(:class:`~split_learning_tpu.parallel.expert.HeldMoEMLP`): the others'
part of each block's result is left out.

Split-layer contract, as :mod:`~split_learning_tpu.models.llama`:
1 = token embedding, 2..n+1 = blocks, n+2 = final norm, n+3 = head.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.models.llama import _rope, rope_inv_freq
from split_learning_tpu.models.split import (
    LayerSpec, register_model, module_plain_fn as _plain_fn,
)

SLIDING, FULL = "sliding_attention", "full_attention"
#: the published ``rope_parameters`` (both kinds at theta 500,000)
ROPE_PARAMETERS = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
           "original_max_position_embeddings": 8192, "beta_fast": 32.0,
           "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000.0},
}


def yarn_inv_freq(head_dim: int, rope_theta: float, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0,
                  **_) -> np.ndarray:
    """YaRN's frequencies, computed once (not per length): each of the
    ``head_dim / 2`` plain frequencies is kept where it turns more than
    ``beta_fast`` times over the original context, divided by ``factor``
    where it turns fewer than ``beta_slow`` times, and blended linearly
    by index between the two."""
    extrap = rope_inv_freq(head_dim, rope_theta)
    interp = extrap / factor

    def correction_dim(rotations):
        return head_dim * math.log(original_max_position_embeddings
                                   / (rotations * 2 * math.pi)) \
            / (2 * math.log(rope_theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / max(high - low, 1e-3), 0, 1)
    return interp * ramp + extrap * (1 - ramp)


def rope_of(kind: str, head_dim: int, rope_parameters: dict) -> tuple:
    """``(inv_freq, factor on cos and sin)`` of one kind of layer."""
    p = rope_parameters[kind]
    if p.get("rope_type", "default") == "yarn":
        return yarn_inv_freq(head_dim, **{k: v for k, v in p.items()
                                          if k != "rope_type"}), \
            float(p.get("attention_factor", 1.0))
    return rope_inv_freq(head_dim, p["rope_theta"]), 1.0


class MellumAttention(nn.Module):
    """Causal grouped-query attention of one kind of layer.  ``use_flash``
    takes the Pallas kernel, which skips the key blocks outside the
    window; the einsum path masks them (small sizes only)."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    kind: str = FULL
    window: int | None = None
    rope_parameters: dict | None = None
    use_flash: bool = False
    flash_block: int = 512
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        hd = self.head_dim
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        q = dense(self.num_heads * hd, name="q_proj")(x)
        k = dense(self.num_kv_heads * hd, name="k_proj")(x)
        v = dense(self.num_kv_heads * hd, name="v_proj")(x)
        q = q.reshape(b, s, self.num_heads, hd)
        k = k.reshape(b, s, self.num_kv_heads, hd)
        v = v.reshape(b, s, self.num_kv_heads, hd)
        inv_freq, factor = rope_of(
            self.kind, hd, self.rope_parameters or ROPE_PARAMETERS)
        pos = jnp.arange(s)
        q = _rope(q, pos, inv_freq, interleaved=False, factor=factor)
        k = _rope(k, pos, inv_freq, interleaved=False, factor=factor)
        window = self.window if self.kind == SLIDING else None
        scope = "attn_window" if window is not None else "attn_full"
        with jax.named_scope(scope):
            if self.use_flash:
                from split_learning_tpu.ops.flash_attention import (
                    flash_attention,
                )
                out = flash_attention(
                    q, k, v, causal=True, window=window,
                    block_q=self.flash_block, block_k=self.flash_block)
            else:
                rep = self.num_heads // self.num_kv_heads
                qg = q.reshape(b, s, self.num_kv_heads, rep, hd)
                scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) \
                    / np.sqrt(hd)
                seen = pos[None, :] <= pos[:, None]
                if window is not None:
                    seen &= pos[None, :] > pos[:, None] - window
                probs = nn.softmax(jnp.where(
                    seen, scores.astype(jnp.float32), -1e30)).astype(
                        self.dtype)
                out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
        return dense(self.hidden_size, name="o_proj")(
            out.reshape(b, s, self.num_heads * hd))


class MellumBlock(nn.Module):
    """``h = x + attn(norm(x))``; ``h + experts(norm(h))``."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: tuple | None = None
    kind: str = FULL
    window: int | None = None
    rope_parameters: dict | None = None
    rms_norm_eps: float = 1e-6
    use_flash: bool = False
    flash_block: int = 512
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from split_learning_tpu.parallel.expert import HeldMoEMLP
        norm = functools.partial(nn.RMSNorm, epsilon=self.rms_norm_eps,
                                 dtype=self.dtype)
        x = x + MellumAttention(
            hidden_size=self.hidden_size, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            kind=self.kind, window=self.window,
            rope_parameters=self.rope_parameters,
            use_flash=self.use_flash, flash_block=self.flash_block,
            dtype=self.dtype, name="attention")(norm(name="input_norm")(x))
        return x + HeldMoEMLP(
            hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            num_experts=self.num_experts, k=self.num_experts_per_tok,
            held=self.experts_held, dtype=self.dtype,
            name="moe")(norm(name="post_norm")(x))


@register_model("Mellum2_TINYSTORIES")
def mellum2_tinystories(
        vocab_size: int = 98304, hidden_size: int = 2304,
        num_attention_heads: int = 32, num_key_value_heads: int = 4,
        head_dim: int = 128, num_hidden_layers: int = 28,
        layer_types: tuple | None = None, sliding_window: int = 1024,
        rope_parameters: dict | None = None, rms_norm_eps: float = 1e-6,
        num_experts: int = 64, num_experts_per_tok: int = 8,
        moe_intermediate_size: int = 896,
        experts_held: int | tuple | None = None, use_flash: bool = False,
        flash_block: int = 512, dtype=jnp.float32) -> tuple:
    """Mellum2-12B-A2.5B geometry under the configuration's own key names;
    input (B, S) int32 token ids, output (B, S, vocab) next-token logits.
    ``layer_types`` defaults to the published period (three sliding, one
    full); ``experts_held`` is a count (experts ``0 .. n - 1``) or the
    ids."""
    kinds = tuple(layer_types) if layer_types is not None else tuple(
        FULL if i % 4 == 3 else SLIDING for i in range(num_hidden_layers))
    if len(kinds) != num_hidden_layers or set(kinds) - {SLIDING, FULL}:
        raise ValueError(f"{num_hidden_layers} layers, layer_types {kinds}")
    held = tuple(range(experts_held)) if isinstance(experts_held, int) \
        else (tuple(experts_held) if experts_held is not None else None)
    rope = dict(rope_parameters or ROPE_PARAMETERS)
    specs = [LayerSpec("layer1", make=functools.partial(
        nn.Embed, num_embeddings=vocab_size, features=hidden_size,
        dtype=dtype), fn=_plain_fn)]
    for i, kind in enumerate(kinds):
        specs.append(LayerSpec(f"layer{2 + i}", make=functools.partial(
            MellumBlock, hidden_size=hidden_size,
            num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            moe_intermediate_size=moe_intermediate_size,
            num_experts=num_experts,
            num_experts_per_tok=num_experts_per_tok, experts_held=held,
            kind=kind, window=sliding_window, rope_parameters=rope,
            rms_norm_eps=rms_norm_eps, use_flash=use_flash,
            flash_block=flash_block, dtype=dtype), fn=_plain_fn))
    n = num_hidden_layers
    specs.append(LayerSpec(f"layer{2 + n}", make=functools.partial(
        nn.RMSNorm, epsilon=rms_norm_eps, dtype=dtype), fn=_plain_fn))
    specs.append(LayerSpec(f"layer{3 + n}", make=functools.partial(
        nn.Dense, features=vocab_size, use_bias=False, dtype=dtype),
        fn=_plain_fn))
    return tuple(specs)
