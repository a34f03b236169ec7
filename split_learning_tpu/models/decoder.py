"""Causal language models as indexed layers: ONE pre-RMSNorm decoder block,
whose mixer and feed-forward each layer picks from a table.

A block is ``x + mixer(norm(x))``; ``x + feed_forward(norm(x))``
(:class:`DecoderBlock`), and either half may be empty: a layer that is a
mixer OR a feed-forward alone has one norm and one residual.  Which mixer
and which feed-forward layer ``i`` has is decided here and nowhere else:
:data:`MIXERS` and :data:`FEED_FORWARDS` map a configuration's kind names
(the published ``layer_types`` / ``mlp_layer_types`` values, the letters of
a ``hybrid_override_pattern``) to code, and :func:`decoder_specs` looks each
layer's pair up.  An architecture of this family is a registered builder
that translates its configuration's own key names; a new mixer or
feed-forward is one module and one table entry.

The mixers there are: grouped-query attention, no bias (:class:`Attention`),
``full_attention`` or ``sliding_attention`` (a query at ``p`` sees keys
``p - window + 1 .. p``), each kind with its own head count, its own RoPE
(over the whole head, a leading share of it, or none) and, where asked, a
sigmoid gate a head on its result;
``latent_attention`` (:class:`LatentAttention`: keys and values expanded
from one low-rank latent a token, a rotary part that all heads share); and
``mamba2`` (:class:`Mamba2`: a state-space layer, its recurrence the
chunked scan of ``ops/ssd_scan.py``).  The feed-forwards: a ``dense``
SwiGLU, ``sparse`` experts of which this program may hold a share
(:class:`~split_learning_tpu.parallel.expert.HeldMoEMLP`),
``sparse_shared`` (the same beside a shared expert that every token takes;
the experts' form, SwiGLU or ``relu2``, is a keyword), and ``capacity``
experts that drop what overflows
(:class:`~split_learning_tpu.parallel.expert.MoEMLP`).

Split-layer contract: 1 = token embedding, 2..n+1 = decoder blocks,
n+2 = final RMSNorm, n+3 = untied LM head.  The streaming activation
between any two stages is the (B, S, H) hidden state, exactly what
``ppermute``/the wire carries.  Causality needs no mask plumbing across
stages: each block rebuilds its own mask from the sequence length.

Loss: next-token CE.  The labels tensor is the input shifted by the data
pipeline (``data/datasets.py`` TINYSTORIES provider), so the pipeline's
``softmax_cross_entropy`` path broadcasts over (B, S) unchanged.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from split_learning_tpu.models.split import (
    LayerSpec, register_model, module_plain_fn as _plain_fn,
)
# parallel/pipeline.py imports this package for ``build_model``, which
# models/__init__.py binds before it imports this module
from split_learning_tpu.parallel.expert import (
    ExpertFFN, HeldMoEMLP, MoEMLP, feed_forward,
)

SLIDING, FULL = "sliding_attention", "full_attention"
LATENT = "latent_attention"
MAMBA2 = "mamba2"
#: epsilon of the norm inside latent attention (``kv_a_layernorm``): the
#: published implementation builds it with its class's default, not with
#: the configuration's ``rms_norm_eps``
LATENT_EPS = 1e-6
#: Mellum-2's published ``rope_parameters`` (both kinds at theta 500,000)
ROPE_PARAMETERS = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
           "original_max_position_embeddings": 8192, "beta_fast": 32.0,
           "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000.0},
}


# --------------------------------------------------------------------------
# rotary embedding
# --------------------------------------------------------------------------

def rope_inv_freq(head_dim: int, base: float) -> np.ndarray:
    """Plain rotary frequencies ``base^(-2i / head_dim)``, i = 0..D/2-1."""
    return 1.0 / (base ** (np.arange(0, head_dim, 2) / head_dim))


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


def rope_of(kind: str, head_dim: int, rope_parameters: dict):
    """``(inv_freq, factor on cos and sin)`` of one kind of layer; None
    where its ``rope_type`` is ``none`` (a layer that turns nothing:
    position reaches it through other layers).  A ``partial_rotary_factor``
    under 1 turns the first ``head_dim * factor`` dims of a head alone: the
    frequencies (YaRN's too) are those of a head that wide, and
    :func:`rope` passes the rest through."""
    p = rope_parameters[kind]
    if p.get("rope_type", "default") == "none":
        return None
    rotary_dim = int(head_dim * p.get("partial_rotary_factor", 1))
    if p.get("rope_type", "default") == "yarn":
        return yarn_inv_freq(rotary_dim, **{
            k: v for k, v in p.items()
            if k not in ("rope_type", "partial_rotary_factor")}), \
            float(p.get("attention_factor", 1.0))
    return rope_inv_freq(rotary_dim, p["rope_theta"]), 1.0


def rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: np.ndarray,
         interleaved: bool = True, factor: float = 1.0) -> jnp.ndarray:
    """Rotary embedding over the first ``2 * len(inv_freq)`` dims of the
    last axis of (B, S, H, D); the dims past them pass unchanged.  The
    caller says which frequencies (``inv_freq``), which pairing —
    ``interleaved`` turns the pairs (2i, 2i + 1), the half-split
    (``rotate_half``) convention the pairs (i, i + R/2) of the rotated
    width R — and a ``factor`` on cos and sin (YaRN's attention factor,
    so it scales the rotated dims alone)."""
    rotary_dim = 2 * len(inv_freq)
    if rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, inv_freq, interleaved,
                  factor), x[..., rotary_dim:]], axis=-1)
    freqs = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = factor * jnp.cos(freqs)[None, :, None, :]
    sin = factor * jnp.sin(freqs)[None, :, None, :]
    if interleaved:
        # the pairs by a reshape: ``x[..., 0::2]`` is a gather (and its
        # gradient a scatter-add) in the compiled step
        pairs = x.reshape(*x.shape[:-1], -1, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return rot.astype(x.dtype)


# --------------------------------------------------------------------------
# the mixer and the block
# --------------------------------------------------------------------------

def _causal_attention(module: nn.Module, q, k, v, dtype, use_flash: bool,
                      flash_block: int, window: int | None = None):
    """Causal attention over whole rows or the last ``window`` keys: ``q``
    (B, S, H, D), ``k`` (B, S, G, D), ``v`` (B, S, G, Dv), a key-value head
    for every ``H / G`` query heads; the result reshapes to (B, S, H * Dv).
    The Pallas kernel, or (small sizes only; the kernel's parity oracle)
    an einsum with the mask applied to the scores.

    With the kernel, ``module`` sows what its forward walks in this call
    under ``counters_sum`` (``parallel/pipeline.py COUNTER_FOLDS``), over
    all rows and heads: ``flash_pairs_seen`` (the (query, key) pairs the
    algorithm owes), ``flash_pairs_visited`` (those the kernel's blocks
    cover) and ``flash_pairs_masked`` (those of them in blocks run with the
    mask), by the walk the kernel takes."""
    b, s, h, hd = q.shape
    if use_flash:
        from split_learning_tpu.ops.flash_attention import (
            flash_attention, forward_pairs, tiling,
        )
        tile = tiling("fwd", s, window, flash_block, flash_block)
        for name, pairs in zip(("seen", "visited", "masked"),
                               forward_pairs(s, window, tile)):
            module.sow("counters_sum", f"flash_pairs_{name}",
                       jnp.float32(b * h * pairs))
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=flash_block, block_k=flash_block)
    groups, pos = k.shape[2], jnp.arange(s)
    qg = q.reshape(b, s, groups, h // groups, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) / np.sqrt(hd)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    probs = nn.softmax(jnp.where(
        seen, scores.astype(jnp.float32), -1e30)).astype(dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


class Attention(nn.Module):
    """Causal grouped-query attention with the RoPE of its ``kind`` of
    layer (or none: :func:`rope_of`), over all earlier keys or the last
    ``window`` of them.

    Three back ends, same math.  With ``seq_axis`` set the module runs
    inside ``shard_map`` (``parallel/sequence.py``): ``x`` is the LOCAL
    token block, positions are offset by the block's index on that axis
    and the blocks' keys go round the ring.  ``use_flash`` takes the Pallas
    kernel (``ops/flash_attention.py``: O(S) memory; it picks a query
    head's key-value head by index and skips the key blocks outside the
    window).  Else the scores are an einsum and the mask is applied to
    them (small sizes only; the kernel's parity oracle).  With ``gating``
    each head's result is multiplied by its own gate before ``o_proj``:
    ``sigmoid(x W_g)``, ``g_proj`` one scalar a head and token, no bias
    (:func:`head_gate`).  Scopes, in both passes: ``attn_proj`` around the
    projections, their reshapes and the RoPE, and again around the gate
    and ``o_proj``, with ``attn_gate`` inside it around the gate alone;
    ``attn_window`` or ``attn_full`` between them, around the scores and
    values.
    """
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_parameters: dict
    kind: str = FULL
    interleaved: bool = False
    window: int | None = None
    gating: bool = False
    use_flash: bool = False
    flash_block: int = 512
    seq_axis: str | None = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        hd, window = self.head_dim, self.window
        if window is not None and self.seq_axis is not None:
            raise ValueError("the ring (seq_axis) has no window")
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        with jax.named_scope("attn_proj"):
            q = dense(self.num_heads * hd, name="q_proj")(x)
            k = dense(self.num_kv_heads * hd, name="k_proj")(x)
            v = dense(self.num_kv_heads * hd, name="v_proj")(x)
            q = q.reshape(b, s, self.num_heads, hd)
            k = k.reshape(b, s, self.num_kv_heads, hd)
            v = v.reshape(b, s, self.num_kv_heads, hd)
            turned = rope_of(self.kind, hd, self.rope_parameters)
            if turned is not None:
                inv_freq, factor = turned
                pos = jnp.arange(s)
                if self.seq_axis is not None:
                    pos = jax.lax.axis_index(self.seq_axis) * s + pos
                q = rope(q, pos, inv_freq, self.interleaved, factor)
                k = rope(k, pos, inv_freq, self.interleaved, factor)
        rep = self.num_heads // self.num_kv_heads
        with jax.named_scope(
                "attn_window" if window is not None else "attn_full"):
            if self.seq_axis is not None:
                from split_learning_tpu.parallel.sequence import (
                    ring_attention,
                )
                # the ring wants a key-value head a query head
                out = ring_attention(
                    q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                    axis_name=self.seq_axis, causal=True)
            else:
                out = _causal_attention(self, q, k, v, self.dtype,
                                        self.use_flash, self.flash_block,
                                        window)
        with jax.named_scope("attn_proj"):
            if self.gating:
                with jax.named_scope("attn_gate"):
                    out = head_gate(
                        out.reshape(b, s, self.num_heads, hd),
                        dense(self.num_heads, name="g_proj")(x))
            return dense(self.hidden_size, name="o_proj")(
                out.reshape(b, s, self.num_heads * hd))


def head_gate(out: jnp.ndarray, gate_logits: jnp.ndarray) -> jnp.ndarray:
    """Each head's result (B, S, H, D) times the sigmoid of its own gate
    logit (B, S, H)."""
    return out * nn.sigmoid(gate_logits)[..., None]


class LatentAttention(nn.Module):
    """Causal multi-head latent attention (DeepSeek-V2/V3's layer, under
    the published keys' names), over all earlier keys.

    ``q = x W_q``, a head ``qk_nope_head_dim + qk_rope_head_dim`` wide
    (``q_lora_rank`` null: no low-rank step on the queries).
    ``[c, k_rope] = x W_kva``: a latent of ``kv_lora_rank`` and ONE rotary
    key of ``qk_rope_head_dim`` a token, for all heads.  ``[k_nope, v] =
    RMSNorm(c) W_kvb``, a head ``qk_nope_head_dim + v_head_dim`` wide.
    RoPE (``rope_theta`` over ``qk_rope_head_dim``, no scaling) turns the
    rotary part of every query head and the one rotary key.  Head ``i``
    scores ``(q_nope_i . k_nope_i + q_rope_i . k_rope) / sqrt(nope +
    rope)``; its result is ``v_head_dim`` wide.

    The kernel (``ops/flash_attention.py``, ``use_flash``) is handed the
    concatenated form: keys ``nope + rope`` wide with the rotary key copied
    to every head, values ``v_head_dim`` wide.  Else the einsum, its
    parity oracle at small sizes.  Scopes, in both passes: ``mla_latent``
    (the projections, the latent's norm, the rotary part and the shaping of
    the kernel's operands, ``o_proj``) and ``attn_full`` around the scores
    and values, as :class:`Attention` has it.
    """
    hidden_size: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    use_flash: bool = False
    flash_block: int = 512
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, nope, rot = self.num_heads, self.qk_nope_head_dim, \
            self.qk_rope_head_dim
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        with jax.named_scope("mla_latent"):
            q = dense(h * (nope + rot), name="q_proj")(x).reshape(
                b, s, h, nope + rot)
            kva = dense(self.kv_lora_rank + rot,
                        name="kv_a_proj_with_mqa")(x)
            latent = nn.RMSNorm(epsilon=LATENT_EPS, dtype=self.dtype,
                                name="kv_a_layernorm")(
                kva[..., :self.kv_lora_rank])
            kv = dense(h * (nope + self.v_head_dim), name="kv_b_proj")(
                latent).reshape(b, s, h, nope + self.v_head_dim)
            inv_freq, pos = rope_inv_freq(rot, self.rope_theta), \
                jnp.arange(s)
            # DeepSeek stores the rotary part's pairs as (2i, 2i + 1)
            q = jnp.concatenate([
                q[..., :nope],
                rope(q[..., nope:], pos, inv_freq, interleaved=True)], -1)
            k_rope = rope(
                jnp.expand_dims(kva[..., self.kv_lora_rank:], 2), pos,
                inv_freq, interleaved=True)
            k = jnp.concatenate([
                kv[..., :nope],
                jnp.broadcast_to(k_rope, (b, s, h, rot))], -1)
        with jax.named_scope("attn_full"):
            out = _causal_attention(self, q, k, kv[..., nope:], self.dtype,
                                    self.use_flash, self.flash_block)
        with jax.named_scope("mla_latent"):
            return dense(self.hidden_size, name="o_proj")(
                out.reshape(b, s, h * self.v_head_dim))


# The element-wise halves of a Mamba-2 mixer keep their operands for the
# backward pass and nothing between them: the float32 steps are as cheap to
# make again as to read back, and a stage holds a layer's residuals whole.

@jax.checkpoint
def _causal_conv_silu(x, taps, bias):
    """``silu`` of a causal depthwise convolution over the last
    ``len(taps)`` positions of every channel of ``x`` (B, S, C): position
    ``t`` reads ``t - len(taps) + 1 .. t``, the row shifted against itself
    (no convolution instruction)."""
    width, seq = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return nn.silu(bias + sum(taps[i] * padded[:, i:i + seq]
                              for i in range(width)))


@functools.partial(jax.checkpoint, static_argnums=(5, 6))
def _skip_gate_norm(y, x, z, skip, scale, groups: int, eps: float):
    """``GroupRMSNorm((y + skip * x) * silu(z))`` in float32, cast back:
    ``y`` and ``x`` (B, S, H, P), ``z`` (B, S, H * P), ``skip`` (H,), the
    norm over each of ``groups`` shares of the width with a learnt
    ``scale``: the gate BEFORE the norm."""
    b, s, h, p = y.shape
    f32 = jnp.float32
    y = y.astype(f32) + skip[:, None] * x.astype(f32)
    y = (y.reshape(b, s, h * p) * nn.silu(z.astype(f32))).reshape(
        b, s, groups, h * p // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return (y.reshape(b, s, h * p) * scale).astype(x.dtype)


def _dt_bias_init(dt_min: float, dt_max: float, floor: float):
    """``dt`` log-uniform in ``[dt_min, dt_max]``, floored, stored through
    the inverse of the softplus (the published Mamba-2 initial value)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.maximum(jnp.exp(
            jax.random.uniform(key, shape, dtype)
            * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min)),
            floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class Mamba2(nn.Module):
    """A Mamba-2 state-space mixer (the published ``nemotron_h`` layer,
    under its configuration's names).

    ``[z, xBC, dt] = x W_in`` (``num_heads * head_dim`` + that and ``2 *
    n_groups * ssm_state_size`` + ``num_heads`` wide, no bias).  ``xBC =
    silu(conv(xBC))``: a causal depthwise convolution over the last
    ``conv_kernel`` positions of every channel, with bias; split into ``x``
    (heads x ``head_dim``), ``B`` and ``C`` (``n_groups`` x
    ``ssm_state_size`` each; head ``h`` reads group ``h // (num_heads /
    n_groups)``).  ``D_t = softplus(dt_t + dt_bias)`` a head, ``A =
    -exp(A_log)``; per head, with a float32 state ``S``: ``S_t = exp(D_t
    A) S_{t-1} + D_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
    (``ops/ssd_scan.py``'s kernels: in chunks of ``chunk_size``).  Then
    ``y = GroupRMSNorm(y * silu(z))`` (``n_groups`` groups, a learnt scale:
    the gate BEFORE the norm) and ``y W_out``.

    Scopes, in both passes: ``ssm_mixer`` around the whole mixer and
    ``ssm_scan`` inside it around the scan alone.  Under ``counters_sum``
    (``parallel/pipeline.py COUNTER_FOLDS``) the mixer sows
    ``ssd_kernel_chunks``: the (row, group, chunk) blocks the scan's
    forward kernel walks in this call."""
    hidden_size: int
    num_heads: int
    head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from split_learning_tpu.ops.ssd_scan import ssd_scan
        b, s, _ = x.shape
        h, p, g, n = self.num_heads, self.head_dim, self.n_groups, \
            self.ssm_state_size
        inner, wide = h * p, h * p + 2 * g * n
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=self.dtype)
        f32 = jnp.float32
        with jax.named_scope("ssm_mixer"):
            z, xbc, dt = jnp.split(
                dense(inner + wide + h, name="in_proj")(x),
                [inner, inner + wide], axis=-1)
            taps = self.param("conv_kernel", nn.initializers.lecun_normal(),
                              (self.conv_kernel, wide)).astype(self.dtype)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (wide,)).astype(self.dtype)
            xbc = _causal_conv_silu(xbc, taps, bias)
            xs, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            xs = xs.reshape(b, s, h, p)
            dt = jax.nn.softplus(dt.astype(f32) + self.param(
                "dt_bias", _dt_bias_init(
                    self.time_step_min, self.time_step_max,
                    self.time_step_floor), (h,)))
            a = -jnp.exp(self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, f32, 1.0, 16.0)), (h,)))
            skip = self.param("D", nn.initializers.ones, (h,))
            with jax.named_scope("ssm_scan"):
                y = ssd_scan(xs, dt, a, bm.reshape(b, s, g, n),
                             cm.reshape(b, s, g, n), self.chunk_size)
            self.sow("counters_sum", "ssd_kernel_chunks",
                     jnp.float32(b * g * -(-s // self.chunk_size)))
            y = _skip_gate_norm(
                y, xs, z, skip, self.param(
                    "norm_scale", nn.initializers.ones, (inner,)), g,
                self.eps)
            return dense(self.hidden_size, name="out_proj")(y)


class DecoderBlock(nn.Module):
    """``h = x + mixer(norm(x))``; ``h + feed_forward(norm(h))``; a half
    that is None is left out, with its norm and its residual.

    ``mixer`` makes the mixer's module, given its name; ``feed_forward``
    is applied to the normed state and creates what it needs in this
    block's scope (a dense SwiGLU its three kernels, an expert layer its
    submodule ``moe``): both are the builder's partials over an entry of
    :data:`MIXERS` / :data:`FEED_FORWARDS`, and the block knows neither
    attention nor experts.  Each norm and each residual add is under the
    scope ``norm_residual``, in both passes.
    """
    mixer: Callable[..., nn.Module] | None
    feed_forward: Callable[[jnp.ndarray], jnp.ndarray] | None
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        def half(x, norm_name, f):
            with jax.named_scope("norm_residual"):
                normed = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                                    name=norm_name)(x)
            out = f(normed)
            with jax.named_scope("norm_residual"):
                return x + out

        if self.mixer is not None:
            x = half(x, "input_norm", self.mixer(name="attention"))
        if self.feed_forward is not None:
            x = half(x, "post_norm", self.feed_forward)
        return x


def _experts(layer) -> Callable:
    """An expert layer of ``parallel/expert.py`` as a feed-forward: the
    caller's submodule ``moe``, as wide as the state it is given."""
    def apply(x, **kw):
        return layer(hidden_size=x.shape[-1], name="moe", **kw)(x)
    return apply


def _experts_and_shared(x, shared_intermediate_size: int, **kw):
    """Held experts beside a shared expert of the same form (the caller's
    submodule ``shared_experts``) that every token takes: computed whole
    here, by every chip of an expert-parallel job over its own rows alike,
    and added to the held experts' partial sum.  Scope ``moe_shared``."""
    with jax.named_scope("moe_shared"):
        shared = ExpertFFN(
            shared_intermediate_size, kw.get("dtype", jnp.float32),
            kw.get("form", "swiglu"), name="shared_experts")(x)
    return _experts(HeldMoEMLP)(x, **kw) + shared


def _dense(x, **kw):
    """A dense feed-forward in the caller's scope; scope ``ffn_dense``."""
    with jax.named_scope("ffn_dense"):
        return feed_forward(x, **kw)


# The one place where a layer's kind becomes code.  A mixer is a module
# class (the block names it ``attention``); a feed-forward a function of the
# normed state that runs in the block's scope.  Both take that kind's
# keywords from the builder.
MIXERS = {FULL: functools.partial(Attention, kind=FULL),
          SLIDING: functools.partial(Attention, kind=SLIDING),
          LATENT: LatentAttention, MAMBA2: Mamba2}
FEED_FORWARDS = {"dense": _dense, "sparse": _experts(HeldMoEMLP),
                 "sparse_shared": _experts_and_shared,
                 "capacity": _experts(MoEMLP)}


def _entry(table: dict, what: str, kind: str | None, keywords: dict):
    if kind is None:        # the block's empty half
        return None
    if kind not in table:
        raise ValueError(f"{what} {kind!r}: the known ones are "
                         f"{sorted(table)}")
    return functools.partial(table[kind], **keywords[kind])


def _in_scope(scope: str) -> Callable:
    """A layer's ``fn`` that applies its module under ``scope``."""
    def fn(mod, x, train):
        with jax.named_scope(scope):
            return _plain_fn(mod, x, train)
    return fn


_EMBED_FN, _FINAL_NORM_FN, _HEAD_FN = map(
    _in_scope, ("embed", "norm_residual", "head"))


def decoder_specs(layers, mixers: dict, feed_forwards: dict, *,
                  vocab_size: int, hidden_size: int, eps: float,
                  dtype=jnp.float32) -> tuple:
    """The split layers of a decoder: the embedding, one
    :class:`DecoderBlock` for each ``(mixer kind, feed-forward kind)`` of
    ``layers`` (None for a half the layer lacks), the final RMSNorm, the
    untied head.  ``mixers`` and ``feed_forwards`` hold, by kind, the
    keywords of the table's entry.  Scopes, in both passes: ``embed``
    (the gather, and its scatter-add), ``norm_residual`` (the final norm,
    as a block's norms) and ``head``."""
    specs = [LayerSpec("layer1", make=functools.partial(
        nn.Embed, num_embeddings=vocab_size, features=hidden_size,
        dtype=dtype), fn=_EMBED_FN)]
    for mixer, feed_forward in layers:
        specs.append(LayerSpec(
            f"layer{1 + len(specs)}", make=functools.partial(
                DecoderBlock,
                mixer=_entry(MIXERS, "layer type", mixer, mixers),
                feed_forward=_entry(FEED_FORWARDS, "feed-forward",
                                    feed_forward, feed_forwards),
                eps=eps, dtype=dtype), fn=_plain_fn))
    specs.append(LayerSpec(f"layer{1 + len(specs)}", make=functools.partial(
        nn.RMSNorm, epsilon=eps, dtype=dtype), fn=_FINAL_NORM_FN))
    specs.append(LayerSpec(f"layer{1 + len(specs)}", make=functools.partial(
        nn.Dense, features=vocab_size, use_bias=False, dtype=dtype),
        fn=_HEAD_FN))
    return tuple(specs)


# --------------------------------------------------------------------------
# the registered architectures
# --------------------------------------------------------------------------

def _llama_specs(vocab_size: int = 32000, hidden_size: int = 2048,
                 num_heads: int = 32, num_kv_heads: int = 4,
                 intermediate_size: int = 5632, n_block: int = 22,
                 use_flash: bool = False, dtype=jnp.float32,
                 num_experts: int = 0, k: int = 2,
                 seq_axis: str | None = None) -> tuple:
    """LLaMA geometry (TinyLlama-1.1B's sizes): full attention in every
    block, heads of ``hidden_size / num_heads``, interleaved RoPE at base
    10,000, RMSNorm 1e-5; capacity experts where ``num_experts`` > 0."""
    attention = dict(
        hidden_size=hidden_size, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=hidden_size // num_heads,
        rope_parameters={FULL: {"rope_theta": 10000.0}}, interleaved=True,
        use_flash=use_flash, flash_block=128, seq_axis=seq_axis, dtype=dtype)
    dense = dict(intermediate_size=intermediate_size, dtype=dtype)
    return decoder_specs(
        [(FULL, "capacity" if num_experts > 0 else "dense")] * n_block,
        {FULL: attention},
        {"dense": dense,
         "capacity": dict(dense, num_experts=num_experts, k=k)},
        vocab_size=vocab_size, hidden_size=hidden_size, eps=1e-5,
        dtype=dtype)


@register_model("TinyLlama_TINYSTORIES")
def tinyllama_tinystories(dtype=jnp.float32, **kw) -> tuple:
    """TinyLlama-1.1B geometry (2048 hidden, 22 blocks, 32 Q / 4 KV heads,
    5632 intermediate, 32000 vocab); input (B, S) int32 token ids, output
    (B, S, vocab) next-token logits.  25 layers at full size."""
    return _llama_specs(dtype=dtype, **kw)


@register_model("TinyLlamaMoE_TINYSTORIES")
def tinyllama_moe_tinystories(dtype=jnp.float32, num_experts: int = 8,
                              **kw) -> tuple:
    """Sparse-MoE variant: every decoder block's MLP is a top-k
    mixture of ``num_experts`` SwiGLU experts, shardable over an
    ``expert`` mesh axis (``parallel/expert.py``; no reference
    counterpart, SURVEY.md §2.2 EP row).  Same split-layer contract as
    the dense model."""
    return _llama_specs(dtype=dtype, num_experts=num_experts, **kw)


def _held(experts_held) -> tuple | None:
    """``experts_held`` as ids: a count means experts ``0 .. n - 1``."""
    if isinstance(experts_held, int):
        return tuple(range(experts_held))
    return None if experts_held is None else tuple(experts_held)


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
    """Mellum2-12B-A2.5B geometry under the keys of the published
    configuration
    (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json);
    input (B, S) int32 token ids, output (B, S, vocab) next-token logits.
    ``layer_types`` names each block ``sliding_attention`` (plain RoPE) or
    ``full_attention`` (YaRN frequencies, cos and sin multiplied by its
    attention factor), by default the published period (three sliding, one
    full); RoPE is half-split (``rotate_half``); after attention a top-k
    router over ``num_experts`` experts with renormalized weights and
    SwiGLU experts of width ``moe_intermediate_size``, no shared expert.
    ``experts_held`` says which experts live here, a count (experts
    ``0 .. n - 1``) or the ids: the others' part of each block's result
    is left out."""
    kinds = tuple(layer_types) if layer_types is not None else tuple(
        FULL if i % 4 == 3 else SLIDING for i in range(num_hidden_layers))
    if len(kinds) != num_hidden_layers:
        raise ValueError(f"{num_hidden_layers} layers, layer_types {kinds}")
    attention = dict(
        hidden_size=hidden_size, num_heads=num_attention_heads,
        num_kv_heads=num_key_value_heads, head_dim=head_dim,
        rope_parameters=dict(rope_parameters or ROPE_PARAMETERS),
        use_flash=use_flash, flash_block=flash_block, dtype=dtype)
    return decoder_specs(
        [(kind, "sparse") for kind in kinds],
        {FULL: attention, SLIDING: dict(attention, window=sliding_window)},
        {"sparse": dict(intermediate_size=moe_intermediate_size,
                        num_experts=num_experts, k=num_experts_per_tok,
                        held=_held(experts_held), dtype=dtype)},
        vocab_size=vocab_size, hidden_size=hidden_size, eps=rms_norm_eps,
        dtype=dtype)


@register_model("Moonlight_TINYSTORIES")
def moonlight_tinystories(
        vocab_size: int = 163840, hidden_size: int = 2048,
        num_attention_heads: int = 16, num_hidden_layers: int = 27,
        first_k_dense_replace: int = 1, moe_layer_freq: int = 1,
        intermediate_size: int = 11264, moe_intermediate_size: int = 1408,
        n_routed_experts: int = 64, n_shared_experts: int = 2,
        num_experts_per_tok: int = 6, scoring_func: str = "sigmoid",
        topk_method: str = "noaux_tc", norm_topk_prob: bool = True,
        routed_scaling_factor: float = 2.446, n_group: int = 1,
        topk_group: int = 1, kv_lora_rank: int = 512,
        q_lora_rank: int | None = None, qk_nope_head_dim: int = 128,
        qk_rope_head_dim: int = 64, v_head_dim: int = 128,
        rope_theta: float = 50000.0, rms_norm_eps: float = 1e-5,
        experts_held: int | tuple | None = None, use_flash: bool = False,
        flash_block: int = 512, dtype=jnp.float32) -> tuple:
    """Moonlight-16B-A3B geometry (``model_type`` ``deepseek_v3``) under
    the keys of the published configuration
    (https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json);
    input (B, S) int32 token ids, output (B, S, vocab) next-token logits.
    Latent attention in every block (:class:`LatentAttention`).  The first
    ``first_k_dense_replace`` blocks have a dense SwiGLU of
    ``intermediate_size``; of the others every ``moe_layer_freq``-th has
    ``n_routed_experts`` experts of ``moe_intermediate_size`` beside
    ``n_shared_experts`` shared ones (one SwiGLU of that many times the
    width): ``num_experts_per_tok`` a token, chosen by ``scoring_func`` of
    the router's logits plus the bias ``e_score_correction_bias``
    (``topk_method`` ``noaux_tc``), weighted by the scores alone,
    renormalized and multiplied by ``routed_scaling_factor``.
    ``experts_held`` as in ``Mellum2_TINYSTORIES``.  What has no module
    here is refused: a low-rank step on the queries (``q_lora_rank``), a
    choice limited to groups of experts (``n_group`` over 1) or made
    without the bias (a ``topk_method`` other than ``noaux_tc``), weights
    that are not renormalized, a stack without routed or without shared
    experts."""
    if q_lora_rank is not None or n_group != 1 or topk_group != 1 \
            or not norm_topk_prob or topk_method != "noaux_tc" \
            or n_routed_experts < 1 or n_shared_experts < 1:
        raise ValueError(
            f"no module for q_lora_rank={q_lora_rank}, n_group={n_group}, "
            f"topk_group={topk_group}, norm_topk_prob={norm_topk_prob}, "
            f"topk_method={topk_method!r}, "
            f"n_routed_experts={n_routed_experts}, "
            f"n_shared_experts={n_shared_experts}")
    experts = dict(
        intermediate_size=moe_intermediate_size,
        num_experts=n_routed_experts, k=num_experts_per_tok,
        held=_held(experts_held), scoring=scoring_func, score_bias=True,
        factor=routed_scaling_factor, dtype=dtype)
    return decoder_specs(
        [(LATENT, "sparse_shared" if i >= first_k_dense_replace
          and i % moe_layer_freq == 0 else "dense")
         for i in range(num_hidden_layers)],
        {LATENT: dict(
            hidden_size=hidden_size, num_heads=num_attention_heads,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, use_flash=use_flash,
            flash_block=flash_block, dtype=dtype)},
        {"dense": dict(intermediate_size=intermediate_size, dtype=dtype),
         "sparse_shared": dict(
             experts, shared_intermediate_size=(
                 n_shared_experts * moe_intermediate_size))},
        vocab_size=vocab_size, hidden_size=hidden_size, eps=rms_norm_eps,
        dtype=dtype)


#: Laguna-XS.2's published ``rope_parameters``: YaRN over half of each head
#: in the full layers, plain RoPE over the whole head in the sliding ones
LAGUNA_ROPE_PARAMETERS = {
    FULL: {"rope_theta": 500000.0, "rope_type": "yarn", "factor": 64.0,
           "original_max_position_embeddings": 4096, "beta_slow": 1.0,
           "beta_fast": 64.0, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
              "partial_rotary_factor": 1}}
LAGUNA_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


@register_model("Laguna_TINYSTORIES")
def laguna_tinystories(
        vocab_size: int = 100352, hidden_size: int = 2048,
        intermediate_size: int = 8192, num_hidden_layers: int = 40,
        num_attention_heads: int = 48, num_key_value_heads: int = 8,
        head_dim: int = 128, rms_norm_eps: float = 1e-6,
        layer_types: tuple | None = None,
        mlp_layer_types: tuple | None = None,
        num_attention_heads_per_layer: tuple | None = None,
        sliding_window: int = 512, rope_parameters: dict | None = None,
        partial_rotary_factor: float = 1.0, gating: bool | str = True,
        attention_bias: bool = False, num_experts: int = 256,
        num_experts_per_tok: int = 8, moe_intermediate_size: int = 512,
        shared_expert_intermediate_size: int = 512,
        moe_routed_scaling_factor: float = 2.5, norm_topk_prob: bool = True,
        moe_apply_router_weight_on_input: bool = False,
        moe_router_logit_softcapping: float = 0.0,
        experts_held: int | tuple | None = None, use_flash: bool = False,
        flash_block: int = 512, dtype=jnp.float32) -> tuple:
    """Laguna-XS.2 (``model_type`` ``laguna``) under the keys of the
    published configuration
    (https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json);
    input (B, S) int32 token ids, output (B, S, vocab) next-token logits.
    Layer ``i`` is the attention ``layer_types[i]`` names (by default the
    published period: one ``full_attention``, three ``sliding_attention``
    over the last ``sliding_window`` keys) with
    ``num_attention_heads_per_layer[i]`` query heads over
    ``num_key_value_heads``, the RoPE of its kind in ``rope_parameters``
    (each kind's ``partial_rotary_factor``, else the top-level one: the
    share of a head that turns) and, with ``gating``, a sigmoid gate a head
    on its result; then the feed-forward ``mlp_layer_types[i]`` names: a
    ``dense`` SwiGLU of ``intermediate_size``, or ``sparse``:
    ``num_experts`` SwiGLU experts of ``moe_intermediate_size``,
    ``num_experts_per_tok`` a token by softmax score, the weights
    renormalized and multiplied by ``moe_routed_scaling_factor``, beside a
    shared SwiGLU expert of ``shared_expert_intermediate_size``.
    ``experts_held`` as in ``Mellum2_TINYSTORIES``.  What has no module
    here is refused: a gate that is not one a head, a bias in attention,
    router logits soft-capped, the router's weight applied to an expert's
    input, weights that are not renormalized, a head count that differs
    between two layers of one kind, a kind outside the tables."""
    kinds = tuple(layer_types) if layer_types is not None else tuple(
        LAGUNA_PERIOD[i % 4] for i in range(num_hidden_layers))
    ffs = tuple(mlp_layer_types) if mlp_layer_types is not None else (
        ("dense",) + ("sparse",) * (num_hidden_layers - 1))
    heads = tuple(num_attention_heads_per_layer) \
        if num_attention_heads_per_layer is not None \
        else (num_attention_heads,) * num_hidden_layers
    heads_of = dict(zip(kinds, heads))
    if gating not in (True, False, "per-head", "per_head") or attention_bias \
            or moe_router_logit_softcapping or moe_apply_router_weight_on_input \
            or not norm_topk_prob \
            or any(heads_of[k] != n for k, n in zip(kinds, heads)) \
            or not set(kinds) <= {FULL, SLIDING} \
            or not set(ffs) <= {"dense", "sparse"} \
            or not len(kinds) == len(ffs) == len(heads) == num_hidden_layers:
        raise ValueError(
            f"no module for gating={gating!r}, "
            f"attention_bias={attention_bias}, "
            f"moe_router_logit_softcapping={moe_router_logit_softcapping}, "
            f"moe_apply_router_weight_on_input="
            f"{moe_apply_router_weight_on_input}, "
            f"norm_topk_prob={norm_topk_prob}, or {num_hidden_layers} "
            f"layers of kinds {kinds}, {ffs} and heads {heads}")
    turns = dict(rope_parameters or LAGUNA_ROPE_PARAMETERS)
    attention = dict(
        hidden_size=hidden_size, num_kv_heads=num_key_value_heads,
        head_dim=head_dim, rope_parameters={
            kind: {"partial_rotary_factor": partial_rotary_factor,
                   **turns[kind]} for kind in heads_of},
        gating=bool(gating), use_flash=use_flash, flash_block=flash_block,
        dtype=dtype)
    # a published ``sparse`` layer holds the shared expert too
    return decoder_specs(
        [(kind, "sparse_shared" if ff == "sparse" else ff)
         for kind, ff in zip(kinds, ffs)],
        {kind: dict(attention, num_heads=n,
                    window=sliding_window if kind == SLIDING else None)
         for kind, n in heads_of.items()},
        {"dense": dict(intermediate_size=intermediate_size, dtype=dtype),
         "sparse_shared": dict(
             intermediate_size=moe_intermediate_size,
             num_experts=num_experts, k=num_experts_per_tok,
             held=_held(experts_held), scoring="softmax",
             factor=moe_routed_scaling_factor, dtype=dtype,
             shared_intermediate_size=shared_expert_intermediate_size)},
        vocab_size=vocab_size, hidden_size=hidden_size, eps=rms_norm_eps,
        dtype=dtype)


#: ``hybrid_override_pattern``'s letters: a layer is ONE sublayer, a mixer
#: or a feed-forward alone.  ``-`` (a dense feed-forward layer) has no entry:
#: the published tower has none, and :data:`FEED_FORWARDS`' ``dense`` is a
#: SwiGLU.
NEMOTRON_H_LAYERS = {"M": (MAMBA2, None), "*": (FULL, None),
                     "E": (None, "sparse_shared")}
NEMOTRON_H_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@register_model("NemotronH_TINYSTORIES")
def nemotron_h_tinystories(
        vocab_size: int = 131072, hidden_size: int = 2688,
        num_hidden_layers: int = 52,
        hybrid_override_pattern: str = NEMOTRON_H_PATTERN,
        num_attention_heads: int = 32, num_key_value_heads: int = 2,
        head_dim: int = 128, sliding_window: int | None = None,
        mamba_num_heads: int = 64, mamba_head_dim: int = 64,
        n_groups: int = 8,
        ssm_state_size: int = 128, conv_kernel: int = 4,
        chunk_size: int = 128, time_step_min: float = 0.001,
        time_step_max: float = 0.1, time_step_floor: float = 1e-4,
        mlp_hidden_act: str = "relu2", moe_intermediate_size: int = 1856,
        moe_shared_expert_intermediate_size: int = 3712,
        n_routed_experts: int = 128, n_shared_experts: int = 1,
        num_experts_per_tok: int = 6, routed_scaling_factor: float = 2.5,
        norm_topk_prob: bool = True, n_group: int = 1, topk_group: int = 1,
        layer_norm_epsilon: float = 1e-5,
        experts_held: int | tuple | None = None, use_flash: bool = False,
        flash_block: int = 512, dtype=jnp.float32) -> tuple:
    """One ``nemotron_h`` tower (the language model that
    https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/config.json
    states) under the keys of the published configuration; input (B, S)
    int32 token ids, output (B, S, vocab) next-token logits.  Layer ``i`` is
    the ONE sublayer ``hybrid_override_pattern[i]`` names
    (:data:`NEMOTRON_H_LAYERS`): ``M`` a Mamba-2 mixer (:class:`Mamba2`),
    ``*`` grouped-query attention over all earlier keys (``sliding_window``
    null, as published) with NO rotary embedding (position reaches it
    through the Mamba-2 layers), ``E``
    ``n_routed_experts`` experts of ``moe_intermediate_size`` beside a
    shared one of ``moe_shared_expert_intermediate_size``, all of the form
    ``mlp_hidden_act`` (``relu2``: two matrices, no gate), ``num_experts_per_tok``
    a token chosen by the sigmoid of the router's logits plus the bias
    ``e_score_correction_bias``, weighted by the scores alone, renormalized
    and multiplied by ``routed_scaling_factor``.  ``experts_held`` as in
    ``Mellum2_TINYSTORIES``.  What has no module here is refused: a choice
    limited to groups of experts, weights that are not renormalized, more
    than one shared expert, a pattern's letter outside the table.  The
    second, denoising tower of the published pair and its objective are
    not in the configuration and not here."""
    pattern = hybrid_override_pattern
    unknown = sorted(set(pattern) - set(NEMOTRON_H_LAYERS))
    if n_group != 1 or topk_group != 1 or not norm_topk_prob \
            or n_shared_experts != 1 or unknown \
            or len(pattern) != num_hidden_layers:
        raise ValueError(
            f"no module for n_group={n_group}, topk_group={topk_group}, "
            f"norm_topk_prob={norm_topk_prob}, "
            f"n_shared_experts={n_shared_experts}, the letters {unknown} "
            f"of hybrid_override_pattern, or {num_hidden_layers} layers "
            f"for a pattern of {len(pattern)}")
    return decoder_specs(
        [NEMOTRON_H_LAYERS[c] for c in pattern],
        {MAMBA2: dict(
            hidden_size=hidden_size, num_heads=mamba_num_heads,
            head_dim=mamba_head_dim, n_groups=n_groups,
            ssm_state_size=ssm_state_size, conv_kernel=conv_kernel,
            chunk_size=chunk_size, eps=layer_norm_epsilon,
            time_step_min=time_step_min, time_step_max=time_step_max,
            time_step_floor=time_step_floor, dtype=dtype),
         FULL: dict(
            hidden_size=hidden_size, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            rope_parameters={FULL: {"rope_type": "none"}},
            window=sliding_window, use_flash=use_flash,
            flash_block=flash_block, dtype=dtype)},
        {"sparse_shared": dict(
            intermediate_size=moe_intermediate_size,
            num_experts=n_routed_experts, k=num_experts_per_tok,
            held=_held(experts_held), scoring="sigmoid", score_bias=True,
            factor=routed_scaling_factor, form=mlp_hidden_act, dtype=dtype,
            shared_intermediate_size=moe_shared_expert_intermediate_size)},
        vocab_size=vocab_size, hidden_size=hidden_size,
        eps=layer_norm_epsilon, dtype=dtype)
