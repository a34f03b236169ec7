"""The comparison that decides ``correct``.

The program's side is what the tap read off the timed path (its own
compiled step, at the timed sizes, in set-up's first rounds); the
reference's side is a configuration's plain float32 module
(``configs/<name>.py``) driven here through the same three optimizer
steps on the same rows, with weights made by that module from the seed.
Nothing here imports the program.

Numbers (a configuration's ``limits`` names those it holds, each with its
own limit; the others are printed under ``info``):

``loss``     widest relative gap of the three steps' losses (``loss1``:
             the first step's alone)
``grad``     first gradient as the optimizer got it (from its state after
             one step): worst leaf's gap of norms (``grad_med``: the
             median leaf's)
``dparam``   parameters' change over the three steps: worst leaf's gap of
             norms (``dparam_med``: the median leaf's); leaves whose
             reference gradient is under a thousandth of the median
             leaf's are left out of ``grad``, ``grad_med`` and these
``fedavg``   the round's aggregate against the sample-weighted mean of the
             columns' final trees, worst leaf, relative
``val_loss`` the round's validation loss against the reference's forward
             pass over the same rows at the same (checkpointed) weights
``ckpt``     leaves of the last checkpoint, read back, that differ from
             the weights the last round returned (exact: limit 0)

A gap of norms is ``| ||program|| - ||reference|| |`` over the larger of
the reference's norm of that leaf and of its median leaf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
STEPS = 3


def flat(tree) -> dict:
    """{'a/b/c': leaf} for a nested dict tree."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(p, "key", getattr(p, "name", p)))
                     for p in path)] = leaf
    return out


def leaf_norms(tree) -> dict:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))
            for k, v in flat(tree).items()}


def diff_norms(a, b) -> dict:
    fa, fb = flat(a), flat(b)
    return {k: float(np.sqrt(np.sum(np.square(
        np.asarray(fa[k], np.float64) - np.asarray(fb[k], np.float64)))))
        for k in fa}


def norm_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """{leaf: gap} by the measure in the module docstring."""
    med = float(np.median(list(ref.values())))
    return {k: abs(prog[k] - r) / max(r, med, 1e-30)
            for k, r in ref.items() if keep is None or k in keep}


def worst(gaps: dict) -> tuple:
    """(widest gap, its leaf)."""
    where = max(gaps, key=gaps.get, default="")
    return gaps.get(where, 0.0), where


def moving_leaves(g_ref: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding."""
    med = float(np.median(list(g_ref.values())))
    return {k for k, v in g_ref.items() if v >= 1e-3 * med}


# -- the reference's three steps ------------------------------------------

def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


@functools.lru_cache(maxsize=8)
def _grad_fn(ref, cast, denom):
    def loss_sum(params, stats, x, labels, key):
        logits = ref.forward(params, stats, x, train=True, key=key,
                             cast=cast)
        return _ce(logits, labels).sum() / denom
    return jax.jit(jax.value_and_grad(loss_sum))


def microbatch_grad(ref, params, stats, x, labels, key, cast=None):
    """Mean loss and its gradient over one microbatch, in row blocks
    where the module allows it (no batch statistics)."""
    block = getattr(ref, "ROW_BLOCK", None) or x.shape[0]
    fn = _grad_fn(ref, cast, x.shape[0])
    loss, grads = 0.0, None
    for lo in range(0, x.shape[0], block):
        l, g = fn(params, stats, x[lo:lo + block], labels[lo:lo + block],
                  key)
        loss = loss + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return loss, grads


def optimizer_step(learning: dict, params, state, grads, t: int):
    """SGD with momentum, or AdamW, as optax defines them."""
    lr = learning["learning-rate"]
    tm = jax.tree_util.tree_map
    if learning["optimizer"] == "sgd":
        mom = learning.get("momentum", 0.9)
        trace = grads if state is None else tm(
            lambda g, s: g + mom * s, grads, state)
        return tm(lambda p, s: p - lr * s, params, trace), trace
    wd = learning.get("weight-decay", 0.0)
    mu, nu = state or (tm(jnp.zeros_like, grads), tm(jnp.zeros_like, grads))
    mu = tm(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = tm(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    new = tm(lambda p, m, v: p - lr * (
        (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS) + wd * p), params, mu, nu)
    return new, (mu, nu)


def follow(ref, learning: dict, params, stats, feed, cast=None,
           fault=None) -> dict:
    """Drive the reference through ``feed`` — a list of
    ``(x[M, mb, ...], labels[M, mb], key_data)``, one per optimizer step —
    and return its losses, first gradient's and final change's leaf
    norms.  ``cast`` makes it the low-precision control; ``fault`` plants
    ``half_batch`` (the second half of the microbatches left out, the
    mean taken over the rest)."""
    p0, state, losses, g1 = params, None, [], None
    for t, (x, labels, key_data) in enumerate(feed, start=1):
        key = jax.random.wrap_key_data(jnp.asarray(key_data))
        n_mb = x.shape[0]
        if fault == "half_batch":
            n_mb = max(1, n_mb // 2)
        loss, grads = 0.0, None
        for m in range(n_mb):
            l, g = microbatch_grad(
                ref, params, stats, jnp.asarray(x[m]),
                jnp.asarray(labels[m]), jax.random.fold_in(key, m), cast)
            loss = loss + l / n_mb
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda g: g / n_mb, grads)
        if g1 is None:
            g1 = leaf_norms(grads)
        params, state = optimizer_step(learning, params, state, grads, t)
        losses.append(float(loss))
    return {"losses": losses, "grad1": g1,
            "dparam": diff_norms(params, p0)}


def first_gradient(opt_state, optimizer: str):
    """The first gradient as the optimizer got it, from the program's
    optimizer state after one step (optax: ``trace`` of sgd-momentum is
    g; ``mu`` of adam is (1 - b1) g)."""
    head = opt_state[0]
    if optimizer == "sgd":
        return head.trace
    return jax.tree_util.tree_map(lambda m: m / (1 - ADAM_B1), head.mu)


# -- the extras: aggregate, validation ---------------------------------------

def fedavg_gap(result, columns, weights) -> tuple:
    """Worst leaf of ||result - weighted mean(columns)|| / ||mean||."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    res = flat(result)
    cols = [flat(c) for c in columns]
    worst, where = 0.0, ""
    for k, r in res.items():
        mean = sum(wi * np.asarray(c[k], np.float64)
                   for wi, c in zip(w, cols))
        gap = float(np.linalg.norm(np.asarray(r, np.float64) - mean)
                    / max(np.linalg.norm(mean), 1e-30))
        if gap > worst:
            worst, where = gap, k
    return worst, where


def val_loss(ref, params, stats, rows, labels, batch: int,
             cast=None) -> float:
    """The reference's mean cross-entropy over the validation rows."""
    fn = jax.jit(lambda p, s, x, y: _ce(
        ref.forward(p, s, x, train=False, cast=cast), y).sum())
    total = 0.0
    for lo in range(0, len(labels), batch):
        total += float(fn(params, stats, jnp.asarray(rows[lo:lo + batch]),
                          jnp.asarray(labels[lo:lo + batch])))
    return total / len(labels)


def tree_mismatch(a, b) -> int:
    fa, fb = flat(a), flat(b)
    if set(fa) != set(fb):
        return len(set(fa) ^ set(fb)) or 1
    return sum(not np.array_equal(np.asarray(fa[k]), np.asarray(fb[k]))
               for k in fa)


def numbers(ref, learning: dict, tapped: dict, ref_run: dict) -> dict:
    """The training numbers from the tap's readings of one column and the
    reference's run on the same feed.  ``leaf_gaps`` keeps every leaf's
    gap, for the tools that set limits."""
    rel = [abs(p - r) / max(abs(r), 1e-30) for p, r in
           zip(tapped["losses"], ref_run["losses"])]
    out = {"loss": max(rel), "loss1": rel[0]}
    g_prog = leaf_norms(first_gradient(tapped["opt1"],
                                       learning["optimizer"]))
    keep = moving_leaves(ref_run["grad1"])
    grad_all = norm_gaps(g_prog, ref_run["grad1"])
    grad = {k: v for k, v in grad_all.items() if k in keep}
    dparam = norm_gaps(tapped["dparam"], ref_run["dparam"], keep)
    out["grad_all"], out["grad_all_leaf"] = worst(grad_all)
    out["grad"], out["grad_leaf"] = worst(grad)
    out["dparam"], out["dparam_leaf"] = worst(dparam)
    out["grad_med"] = float(np.median(list(grad.values())))
    out["dparam_med"] = float(np.median(list(dparam.values())))
    out["leaf_gaps"] = {"grad": grad_all, "dparam": dparam}
    return out
