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
#
# What the device holds at the peak of ``follow``: the parameters once, one
# accumulating gradient, the gradient of the microbatch (or row block) in
# hand and that block's activations: 12 bytes a parameter in float32.  The
# starting parameters and the optimizer's state live on the host between
# steps, the update goes leaf by leaf, and a sum takes the place of what it
# sums (donation).  Every leaf sees the operations it always saw, in the
# same order, so the numbers are the same.

def _ce(logits, labels):
    """Cross-entropy of every labelled position: logits ``(..., classes)``,
    labels ``(...)``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


@functools.lru_cache(maxsize=8)
def _grad_fn(ref, cast, denom):
    extra = getattr(ref, "extra_objective", None)

    def loss_sum(params, stats, x, labels, key):
        logits = ref.forward(params, stats, x, train=True, key=key,
                             cast=cast)
        return _ce(logits, labels).sum() / denom
    if extra is None:
        return jax.jit(jax.value_and_grad(loss_sum))

    def objective(params, stats, x, labels, key):
        # what the program differentiates: CE plus the terms its modules
        # sow, weighted as it weights them; what it reports: CE alone
        ce = loss_sum(params, stats, x, labels, key)
        return ce + extra(params, stats, x, key, cast), ce
    both = jax.jit(jax.value_and_grad(objective, has_aux=True))

    def ce_and_grad(*args):
        (_, ce), grads = both(*args)
        return ce, grads
    return ce_and_grad


@functools.partial(jax.jit, donate_argnums=0)
def _sum_in_place(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def _add_into(acc, g):
    """``acc + g``, in ``acc``'s place (the caller then drops ``g``).  An
    ``acc`` that waits on the host is brought back first."""
    if acc is None:
        return g
    return _sum_in_place(jax.tree_util.tree_map(jnp.asarray, acc), g)


def _row_block(ref, rows: int) -> int:
    """A module without batch statistics may take a microbatch in blocks
    of ``ROW_BLOCK`` rows; one whose objective has a term of the whole
    microbatch (``extra_objective``) takes it whole."""
    block = getattr(ref, "ROW_BLOCK", None)
    if not block or hasattr(ref, "extra_objective"):
        return rows
    return min(block, rows)


def microbatch_grad(ref, params, stats, x, labels, key, cast=None):
    """Mean loss over one microbatch's labels and its gradient, in row
    blocks where the module allows it."""
    block = _row_block(ref, x.shape[0])
    fn = _grad_fn(ref, cast, labels.size)
    loss, grads = 0.0, None
    for lo in range(0, x.shape[0], block):
        l, g = fn(params, stats, x[lo:lo + block], labels[lo:lo + block],
                  key)
        loss = loss + l
        # the sum is waited for and ``g`` dropped, or the next block's
        # gradient would be allocated beside this one's
        grads = jax.block_until_ready(_add_into(grads, g))
        del g
    return loss, grads


def optimizer_step(learning: dict, params, state, grads, t: int):
    """SGD with momentum, or AdamW, as optax defines them, leaf by leaf:
    ``state`` (``None`` before the first step) is a list of host arrays,
    a leaf's in ``params``' flattening order, so the device holds one
    leaf's state at a time beside the old and the new parameters and the
    gradient."""
    lr = learning["learning-rate"]
    sgd = learning["optimizer"] == "sgd"
    mom = learning.get("momentum", 0.9)
    wd = learning.get("weight-decay", 0.0)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    leaves, treedef = jax.tree_util.tree_flatten(params)
    new, new_state = [], []
    # each expression is waited for: buffers are allocated when an operation
    # is enqueued, and a leaf's whole chain of temporaries enqueued at once
    # stood beside one another (13 of the largest leaf's size on the chip)
    done = jax.block_until_ready
    for i, (p, g) in enumerate(zip(leaves, treedef.flatten_up_to(grads))):
        if sgd:
            trace = g if state is None else g + mom * jnp.asarray(state[i])
            new.append(done(p - lr * trace))
            new_state.append(jax.device_get(trace))
        else:
            m, v = (jnp.zeros_like(g), jnp.zeros_like(g)) if state is None \
                else (jnp.asarray(state[i][0]), jnp.asarray(state[i][1]))
            mu = done(ADAM_B1 * m + (1 - ADAM_B1) * g)
            nu = done(ADAM_B2 * v + (1 - ADAM_B2) * g * g)
            new.append(done(p - lr * (
                (mu / c1) / (jnp.sqrt(nu / c2) + ADAM_EPS) + wd * p)))
            new_state.append(jax.device_get((mu, nu)))
    return jax.tree_util.tree_unflatten(treedef, new), new_state


def follow(ref, learning: dict, params, stats, feed, cast=None,
           fault=None) -> dict:
    """Drive the reference through ``feed`` — a list of
    ``(x[M, mb, ...], labels[M, mb, ...], key_data)``, one per optimizer
    step — from ``params`` (best a host tree: the device then holds the
    one copy made here) and return its losses, first gradient's and final
    change's leaf norms.  ``cast`` makes it the low-precision control;
    ``fault`` plants ``half_batch`` (the second half of the microbatches
    left out, the mean taken over the rest)."""
    p0 = jax.device_get(params)
    params = jax.tree_util.tree_map(jnp.asarray, p0)
    state, losses, g1 = None, [], None
    for t, (x, labels, key_data) in enumerate(feed, start=1):
        key = jax.random.wrap_key_data(jnp.asarray(key_data))
        n_mb = x.shape[0]
        if fault == "half_batch":
            n_mb = max(1, n_mb // 2)
        in_blocks = _row_block(ref, x.shape[1]) < x.shape[1]
        loss, grads = 0.0, None
        for m in range(n_mb):
            if grads is not None and in_blocks:
                # the microbatch's own sum takes its place on the device
                grads = jax.device_get(grads)
            l, g = microbatch_grad(
                ref, params, stats, jnp.asarray(x[m]),
                jnp.asarray(labels[m]), jax.random.fold_in(key, m), cast)
            loss = loss + l / n_mb
            grads = _add_into(grads, g)
            del g
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
    return total / labels.size


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
