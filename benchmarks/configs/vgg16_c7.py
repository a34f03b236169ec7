"""Plain reference for ``vgg16_c7``: the UNSPLIT VGG16 (+BatchNorm) for
CIFAR-10 in float32 ``jax.numpy`` at full matmul precision, its loss,
gradients and SGD-momentum step.  It imports nothing of the program.

Follows ``src/model/VGG16_CIFAR10.py`` of the paper's repository (13
conv3x3+BN+ReLU in five blocks, 5 max-pools, dropout-4096-dropout-4096-10)
with the program's layout: NHWC, layer names ``layer1`` .. ``layer52``
(conv, bn, relu, pool, flatten, dropout and dense each take one index),
so the tree this makes is the tree the program's checkpoint holds.

Departure, noted: the two dropout layers draw their masks from the key
the program's step is fed (``fold_in(key, microbatch)``, then flax's
static fold of the layer's path and call count), so that both sides drop
the same units.  Everything else is the published network.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

BLOCKS = ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3))
NUM_CLASSES = 10
INPUT_SHAPE = (32, 32, 3)
DATASET = "cifar10"
BN_EPS, BN_MOMENTUM = 1e-5, 0.9
HI = jax.lax.Precision.HIGHEST


def layer_table() -> list:
    """[(index, kind, width)] for the 52 layers."""
    out, i = [], 0
    for width, n in BLOCKS:
        for _ in range(n):
            out += [(i + 1, "conv", width), (i + 2, "bn", width),
                    (i + 3, "relu", 0)]
            i += 3
        out.append((i + 1, "pool", 0))
        i += 1
    out += [(i + 1, "flatten", 0), (i + 2, "dropout", 0),
            (i + 3, "dense", 4096), (i + 4, "relu", 0),
            (i + 5, "dropout", 0), (i + 6, "dense", 4096),
            (i + 7, "relu", 0), (i + 8, "dense", NUM_CLASSES)]
    return out


def init(key, model_kwargs=None):
    """(params, batch_stats) from one key: He-normal kernels, zero
    biases, unit BN scales.  Traceable: the harness jits it."""
    del model_kwargs
    params, stats = {}, {}
    c_in = INPUT_SHAPE[-1]
    for idx, kind, width in layer_table():
        k = jax.random.fold_in(key, idx)
        name = f"layer{idx}"
        if kind == "conv":
            std = (2.0 / (9 * c_in)) ** 0.5
            params[name] = {
                "kernel": std * jax.random.normal(k, (3, 3, c_in, width)),
                "bias": jnp.zeros((width,))}
            c_in = width
        elif kind == "bn":
            params[name] = {"scale": jnp.ones((width,)),
                            "bias": jnp.zeros((width,))}
            stats[name] = {"mean": jnp.zeros((width,)),
                           "var": jnp.ones((width,))}
        elif kind == "dense":
            std = (2.0 / c_in) ** 0.5
            params[name] = {
                "kernel": std * jax.random.normal(k, (c_in, width)),
                "bias": jnp.zeros((width,))}
            c_in = width
    return params, stats


def _flax_dropout_key(key, name: str):
    """flax.linen's key for the first ``make_rng('dropout')`` of the
    top-level module ``name``: SHA-1 of the path and the call count,
    folded into the key (flax/core/scope.py ``_fold_in_static``)."""
    m = hashlib.sha1()
    m.update(name.encode())
    m.update((1).to_bytes(1, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def forward(params, stats, x, *, train=False, key=None, cast=None):
    """Logits for ``x`` (B, 32, 32, 3).  ``train`` uses batch statistics
    and dropout (masks from ``key``); otherwise the running ``stats``.
    ``cast`` (the control) rounds every matmul/conv operand."""
    q = cast or (lambda a: a)
    x = x.astype(jnp.float32)
    for idx, kind, _ in layer_table():
        name = f"layer{idx}"
        if kind == "conv":
            p = params[name]
            x = jax.lax.conv_general_dilated(
                q(x), q(p["kernel"]), (1, 1), ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=HI) + p["bias"]
        elif kind == "bn":
            p = params[name]
            if train:
                mean = x.mean((0, 1, 2))
                var = jnp.square(x).mean((0, 1, 2)) - jnp.square(mean)
            else:
                mean, var = stats[name]["mean"], stats[name]["var"]
            x = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] \
                + p["bias"]
        elif kind == "relu":
            x = jnp.maximum(x, 0)
        elif kind == "pool":
            b, h, w, c = x.shape
            x = x.reshape(b, h // 2, 2, w // 2, 2, c).max((2, 4))
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "dropout":
            if train:
                keep = jax.random.bernoulli(
                    _flax_dropout_key(key, name), 0.5, x.shape)
                x = jnp.where(keep, x / 0.5, 0.0)
        elif kind == "dense":
            p = params[name]
            x = jnp.dot(q(x), q(p["kernel"]), precision=HI) + p["bias"]
    return x


def train_flops_per_sample(flops, model_kwargs=None) -> float:
    """Forward+backward FLOPs of one training sample, from shapes: 3x
    the forward multiply-adds of every conv and dense layer (no
    recomputation, no elementwise work)."""
    del model_kwargs
    total, (h, w, c_in) = 0.0, INPUT_SHAPE
    for _, kind, width in layer_table():
        if kind == "conv":
            total += flops.conv2d(h, w, c_in, width, 3)
            c_in = width
        elif kind == "pool":
            h, w = h // 2, w // 2
        elif kind == "flatten":
            c_in = h * w * c_in
        elif kind == "dense":
            total += flops.dense(1, c_in, width)
            c_in = width
    return 3.0 * total
