"""Plain reference for ``nemotron_twotower_30b_c5``: the UNSPLIT ``nemotron_h``
tower that the published configuration of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 states, in float32 ``jax.numpy`` at
full matmul precision, with the share of the routed experts and of the
vocabulary that the configuration's one chip holds.  It imports nothing of
the program and has no kernel, no chunked scan, no sorted dispatch and no
capacity: the state-space recurrence runs a position at a time, attention
is a masked softmax over whole rows of scores taken a few heads at a time,
and every held expert is applied to every token and weighted by what the
router gave it (nought for the tokens that did not pick it).

Written from the keys of the published configuration
(https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/config.json,
``model_type`` ``nemotron_h``) and the published ``nemotron_h`` layer.  Layer
``i`` is ONE sublayer, ``x <- x + f(RMSNorm(x))``, of the kind the letter
``hybrid_override_pattern[i]`` names:

* ``M``, Mamba-2: ``[z, xBC, dt] = u W_in`` (``heads x head_dim`` + that and
  ``2 x n_groups x ssm_state_size`` + ``heads``); ``xBC = silu(conv(xBC))``, a
  causal depthwise convolution over the last ``conv_kernel`` positions of
  each channel with bias; ``x`` (heads x head_dim), ``B``, ``C`` (groups x
  state each; head ``h`` reads group ``h // (heads / groups)``); ``D_t =
  softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``; per head, a float32 state
  ``S``: ``S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T``, ``y_t = S_t C_t + D
  x_t``; ``y = GroupRMSNorm(y * silu(z))`` (the gate BEFORE the norm, groups
  of ``heads x head_dim / n_groups``, a learnt scale), ``y W_out``;
* ``*``, attention: ``q`` (heads x head_dim), ``k``, ``v`` (key-value heads x
  head_dim), causal ``softmax(q k^T / sqrt(head_dim)) v`` in float32,
  ``W_o``; no bias, no window, NO rotary embedding;
* ``E``, experts: ``s = sigmoid(u W_g)`` over ALL ``n_routed_experts``;
  ``I`` = the ``num_experts_per_tok`` largest of ``s + b`` (``b``: the
  ``e_score_correction_bias``, a buffer under ``batch_stats``); ``w_i =
  routed_scaling_factor * s_i / (sum_{j in I} s_j + 1e-20)``; ``FF = sum_{i
  in I, i held} w_i E_i(u) + S(u)`` with ``E(u) = relu(u W_up)^2 W_down``
  (two matrices, no gate) of ``moe_intermediate_size`` and ``S`` the same
  form at ``moe_shared_expert_intermediate_size``.  What the absent experts
  would add is left out, here as in the program; the shared expert is whole;
* after the last layer RMSNorm and the untied head (the held slice of the
  vocabulary).

Departures from the published model, each ``assumed`` in the YAML: the
second, denoising tower and its objective are not in the configuration and
not here (the tower is trained with the next-token loss); attention turns
nothing (``rope_theta`` stands in the configuration unused); ``b`` is held
at what ``init`` made; the load-balancing term is ``E * sum_e f_e P_e`` over
the tokens of a microbatch with weight ``AUX_WEIGHT``; the state of a
Mamba-2 layer is carried across the ends of the documents packed into a
row, as attention sees across them.

Tree names are the program's (``layer1`` embedding, ``layer2``.. the
layers, then the final norm and the head; a mixer layer ``input_norm`` and
``attention``, an expert layer ``post_norm``, ``moe`` with the held experts
leading under ``experts/{up,down}_proj/kernel`` and ``shared_experts``;
``batch_stats`` holds ``layer<n>/moe/e_score_correction_bias``), so the
trees this makes are the trees the program's checkpoint holds.

One thing here is not a reference's: :func:`hold_host_buffers`, called when
the module is loaded on a machine with a chip, stands in for a line that
``run_cell.steady_allocator`` lacks until a ``benchmark`` PR may write it
there (its docstring; PERF.md section 7 (xiv)).
"""

from __future__ import annotations

import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np

SIZES = dict(
    vocab_size=131072, hidden_size=2688, num_hidden_layers=52,
    hybrid_override_pattern=(
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
    num_attention_heads=32, num_key_value_heads=2, head_dim=128,
    mamba_num_heads=64, mamba_head_dim=64, n_groups=8, ssm_state_size=128,
    conv_kernel=4, chunk_size=128, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_routed_experts=128,
    num_experts_per_tok=6, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5, experts_held=None, seq_len=4096)
DATASET = "tokens"
# the published depth: ``rescale_prenorm_residual`` divides the output
# projections' initial values by its root, whatever depth is held here
PUBLISHED_LAYERS = 52
# weight of the load-balancing term; the program is given the same under
# ``learning.moe-aux-weight``
AUX_WEIGHT = 0.0001
# query heads whose scores are held at once: (rows, 4, S, S) float32 is
# 256 MB a row of 4,096 tokens
HEAD_CHUNK = 4
# positions of the recurrence whose states the backward pass holds at once
# (a block is recomputed from the state it started with)
SCAN_BLOCK = 64
# the coordinates of the state that the routers read and that no layer
# writes to (``init``), and the bias that leaves a chip's experts out
ROUTED_DIMS = 64
LEFT_OUT = -0.1
HI = jax.lax.Precision.HIGHEST
# tokens that go through a matrix at once (``_run``'s ``mm``)
TOKEN_BLOCK = 512
# whether a pattern of ``ME`` and ``M*E`` runs is one ``lax.scan`` (``_run``;
# off, every layer is traced on its own: the tests compare the two)
ONE_SCAN = True


def hold_host_buffers() -> bool:
    """NOT the reference's business, and here until a ``benchmark`` PR may
    write it where it belongs (``run_cell.steady_allocator``; PERF.md
    section 7 (xiv)): every matrix of this tree is over the 32 MiB that
    function pins as glibc's mmap threshold, so every pass ``compare.py``
    makes over it on the host runs on freshly faulted pages, and the cell's
    whole process does not fit the driver's 360 s (on an empty compile
    cache 385 s untraced without this, 306-308 s TRACED with it; my chip
    runs, PR 36).
    ``benchmarks/host_heap.py`` has the mechanism and its price.  The
    harness loads this module right after ``steady_allocator()``; a
    rehearsal on the CPU (``JAX_PLATFORMS=cpu``, the harness's own test,
    and what the tests run under) leaves the allocator alone."""
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
    held = s["experts_held"]
    s["experts_held"] = tuple(range(s["n_routed_experts"])) if held is None \
        else tuple(range(held)) if isinstance(held, int) else tuple(held)
    return s


# the sizes ``init`` was last given: the harness hands ``model-kwargs`` to
# ``init`` and ``train_flops_per_sample`` alone, and a tree does not show
# the heads' sizes, the experts a token picks or the factor on their weights
_KWARGS: dict = {}


def init(key, model_kwargs=None):
    """(params, batch_stats) from one key.  Matrices normal(0.02), the
    output projections (``out_proj``, ``o_proj``, every ``down_proj``)
    divided by ``sqrt(PUBLISHED_LAYERS)`` (``rescale_prenorm_residual``);
    unit norm scales; the Mamba-2 layers' own values as published (``dt``
    log-uniform in ``[time_step_min, time_step_max]``, floored, stored
    through the inverse softplus; ``A_log = log(uniform(1, 16))``; ``D`` 1;
    the convolution uniform in ``+-1 / sqrt(conv_kernel)``).  And, as the
    siblings' and for their reason (the work of a step must not hang on
    the seed: PERF.md section 6, PRs 30, 34 and 36):

    * the embedding is normal(1);
    * where the held experts are an even share (``chips = n_routed_experts
      / held`` chips of ``held`` experts each, more chips than experts a
      token picks) every router's kernel is ``chips`` copies of the SAME
      ``held`` columns, one copy a chip, in every expert layer.  The
      columns read only the state's first ``ROUTED_DIMS`` coordinates,
      which no layer writes to (those columns of every output projection
      start at nought), column ``j`` its own ``ROUTED_DIMS / held`` of
      them, and the embedding lights, in token ``t``'s row, the share of
      column ``t mod held`` and no other (1 there, nought in the other
      routed coordinates): a token's column is its id's class, in every
      expert layer, by a margin of two in the logit (drawn columns and
      rows left heavy ids all but tied on one seed in six, and where
      bfloat16 rounding of the state then flips a choice the program and
      this reference differentiate other pairs: PERF.md section 6,
      PR 36); the column's copies tie, and which ``num_experts_per_tok``
      of them are chosen is decided by ``b`` alone;
    * ``b`` is nought on ``num_experts_per_tok`` neighbouring chips' copy of
      a column and ``LEFT_OUT`` on the others: in expert layer ``l`` column
      ``j`` keeps the chips ``(perm[j] * chips / held + l *
      num_experts_per_tok + i) mod chips``, ``i`` under
      ``num_experts_per_tok`` (``perm`` drawn from the key).  With 16
      chips, 6 a token and the 3 expert layers of the configuration a
      column's three runs of six go once round the sixteen chips and two
      further: chip 0 is kept once by seven columns and twice by one, so
      the held share computes ``tokens x (1 + m)`` pairs a step over the
      three layers, ``m`` the stream's mass on that one column's class
      of ids (0.119-0.140 under the ``tokens`` stream whichever class it
      is: the deployment's ``6 x 8 / 128 = 0.375`` a token and layer to
      1.3 %).

    What the tiling costs the comparison: the chosen scores tie, so every
    chosen weight is ``routed_scaling_factor / 6``.  ``tests/
    test_nemotron_h.py`` and ``tests/test_moe_held.py`` hold the program to
    this reference with routers and biases drawn whole at small sizes
    (``routers="whole"``).

    Traceable: the harness jits it.  Nothing here is an indexed update, a
    gather or a sort (PERF.md section 6, PR 34)."""
    _KWARGS.clear()
    _KWARGS.update(model_kwargs or {})
    s = sizes(model_kwargs)
    whole = (model_kwargs or {}).get("routers") == "whole"
    d, f = s["hidden_size"], s["moe_intermediate_size"]
    h, kv, hd = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    mh, mp, g, n = s["mamba_num_heads"], s["mamba_head_dim"], \
        s["n_groups"], s["ssm_state_size"]
    inner, wide = mh * mp, mh * mp + 2 * g * n
    e, n_held, k = s["n_routed_experts"], len(s["experts_held"]), \
        s["num_experts_per_tok"]
    chips = e // n_held
    tiled = not whole and e % n_held == 0 and chips > k \
        and chips % n_held == 0 and d > ROUTED_DIMS \
        and ROUTED_DIMS % n_held == 0
    out_std = 0.02 / PUBLISHED_LAYERS ** 0.5
    count = [0]

    def fresh():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def draw(*shape, std=0.02):
        return std * jax.random.normal(fresh(), shape)

    def uniform(*shape, lo, hi):
        return jax.random.uniform(fresh(), shape, jnp.float32, lo, hi)

    def w(*shape):
        return {"kernel": draw(*shape)}

    def out(*shape):
        """An output projection: the routed coordinates start at nought."""
        kernel = draw(*shape, std=out_std)
        return {"kernel": kernel * (jnp.arange(d) >= ROUTED_DIMS)
                if tiled else kernel}

    if tiled:
        share = ROUTED_DIMS // n_held
        # (coordinate, column): whether the coordinate is of the column's
        # share of the routed ones; a lit share gives a logit of two
        mine = (jnp.arange(d)[:, None] // share == jnp.arange(n_held)) \
            & (jnp.arange(d)[:, None] < ROUTED_DIMS)
        columns = mine * (2.0 / share)
        # a permutation of the columns: each one's rank among the draws
        order = draw(n_held)
        perm = (order[None, :] < order[:, None]).sum(axis=1)

    def router(layer):
        """(kernel, bias) of expert layer ``layer`` (0, 1, ..)."""
        if not tiled:
            return w(d, e), draw(e, std=0.1) if whole else jnp.zeros((e,))
        # (chip, column): how far the chip lies past the first one the
        # column keeps here; the first ``k`` are kept
        past = (jnp.arange(chips)[:, None] - perm[None, :] * (chips // n_held)
                - layer * k) % chips
        return {"kernel": jnp.tile(columns, (1, chips))}, \
            jnp.where(past < k, 0.0, LEFT_OUT).reshape(e)

    def mamba():
        dt = jnp.maximum(jnp.exp(uniform(
            mh, lo=np.log(s["time_step_min"]),
            hi=np.log(s["time_step_max"]))), s["time_step_floor"])
        bound = s["conv_kernel"] ** -0.5
        return {"in_proj": w(d, inner + wide + mh),
                "conv_kernel": uniform(s["conv_kernel"], wide, lo=-bound,
                                       hi=bound),
                "conv_bias": uniform(wide, lo=-bound, hi=bound),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(uniform(mh, lo=1.0, hi=16.0)),
                "D": jnp.ones((mh,)),
                "norm_scale": jnp.ones((inner,)),
                "out_proj": out(inner, d)}

    embedding = draw(s["vocab_size"], d, std=1.0)
    if tiled:
        # token t's row lights the share of column t mod held alone
        lit = jnp.arange(d)[None, :] // share \
            == jnp.arange(s["vocab_size"])[:, None] % n_held
        embedding = jnp.where(jnp.arange(d)[None, :] < ROUTED_DIMS,
                              lit.astype(jnp.float32), embedding)
    params = {"layer1": {"embedding": embedding}}
    stats, experts = {}, 0
    for i, kind in enumerate(s["hybrid_override_pattern"]):
        scale = {"scale": jnp.ones((d,))}
        if kind == "M":
            p = {"input_norm": scale, "attention": mamba()}
        elif kind == "*":
            p = {"input_norm": scale, "attention": {
                "q_proj": w(d, h * hd), "k_proj": w(d, kv * hd),
                "v_proj": w(d, kv * hd), "o_proj": out(h * hd, d)}}
        else:
            kernel, bias = router(experts)
            experts += 1
            p = {"post_norm": scale,
                 "moe": {"router": kernel,
                         "experts": {"up_proj": w(n_held, d, f),
                                     "down_proj": out(n_held, f, d)}},
                 "shared_experts": {
                     "up_proj": w(d, s["moe_shared_expert_intermediate_size"]),
                     "down_proj": out(
                         s["moe_shared_expert_intermediate_size"], d)}}
            stats[f"layer{i + 2}"] = {
                "moe": {"e_score_correction_bias": bias}}
        params[f"layer{i + 2}"] = p
    last = len(s["hybrid_override_pattern"]) + 2
    params[f"layer{last}"] = {"scale": jnp.ones((d,))}
    params[f"layer{last + 1}"] = w(d, s["vocab_size"])
    return params, stats


# -- the layers -----------------------------------------------------------------

def _rms(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * scale


def recurrence(x, dt, a, b, c):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    a position at a time: ``x`` (rows, S, G, R, P), ``dt`` (rows, S, G, R),
    ``a`` (G, R), ``b`` and ``c`` (rows, S, G, N); the state (rows, G, R, P,
    N) starts at nought and is carried across the whole row.  The backward
    pass holds ``SCAN_BLOCK`` positions' states at once: a block is
    recomputed from the state it started with."""
    rows, seq = x.shape[:2]
    block = SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] \
            * b_t[:, :, None, None, :]
        return state, (state * c_t[:, :, None, None, :]).sum(-1)

    @jax.checkpoint
    def run(state, at):
        return jax.lax.scan(step, state, at)
    by_block = lambda v: jnp.moveaxis(v, 1, 0).reshape(  # noqa: E731
        seq // block, block, rows, *v.shape[2:])
    _, y = jax.lax.scan(
        run, jnp.zeros((*x.shape[:1], *x.shape[2:], b.shape[-1]),
                       jnp.float32),
        tuple(by_block(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape(seq, *y.shape[2:]), 0, 1)


def mamba2(a, u, s: dict, mm, gate_before_norm: bool = True,
           group_of_head=None):
    """The Mamba-2 mixer for the normed state ``u`` (rows, S, hidden),
    before the residual.  ``gate_before_norm=False`` (the gate applied
    after the norm) and ``group_of_head`` (another group for each head: a
    permutation of the groups) are planted faults' oracles."""
    rows, seq, _ = u.shape
    h, p, g, n = s["mamba_num_heads"], s["mamba_head_dim"], s["n_groups"], \
        s["ssm_state_size"]
    inner, taps = h * p, s["conv_kernel"]
    z, xbc, dt = jnp.split(
        mm("bsd,de->bse", u, a["in_proj"]["kernel"]),
        [inner, 2 * inner + 2 * g * n], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(a["conv_bias"] + sum(
        a["conv_kernel"][i] * padded[:, i:i + seq] for i in range(taps)))
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(rows, seq, g, h // g, p)
    b, c = b.reshape(rows, seq, g, n), c.reshape(rows, seq, g, n)
    if group_of_head is not None:
        b, c = b[:, :, group_of_head], c[:, :, group_of_head]
    dt = jax.nn.softplus(dt + a["dt_bias"]).reshape(rows, seq, g, h // g)
    y = recurrence(x, dt, -jnp.exp(a["A_log"]).reshape(g, h // g), b, c) \
        + a["D"].reshape(g, h // g, 1) * x
    if gate_before_norm:
        y = y.reshape(rows, seq, g, inner // g) \
            * jax.nn.silu(z).reshape(rows, seq, g, inner // g)
    else:
        y = y.reshape(rows, seq, g, inner // g)
    y = (y * jax.lax.rsqrt(jnp.square(y).mean(-1, keepdims=True)
                           + s["layer_norm_epsilon"])
         ).reshape(rows, seq, inner) * a["norm_scale"]
    if not gate_before_norm:
        y = y * jax.nn.silu(z)
    return mm("bse,ed->bsd", y, a["out_proj"]["kernel"])


def attention(a, u, s: dict, mm):
    """Causal grouped-query attention without rotary embedding for the
    normed state ``u``, ``HEAD_CHUNK`` query heads' scores at a time (each
    chunk recomputed in the backward pass)."""
    rows, seq, _ = u.shape
    h, kv, hd = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    q, k, v = jnp.split(mm("bsd,de->bse", u, jnp.concatenate(
        [a[name]["kernel"] for name in ("q_proj", "k_proj", "v_proj")],
        axis=-1)), [h * hd, (h + kv) * hd], axis=-1)
    c = min(HEAD_CHUNK, h // kv)
    seen = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    # a chunk of query heads beside the one key-value head they share
    q = q.reshape(rows, seq, h // c, c, hd).transpose(2, 0, 1, 3, 4)
    shared = lambda t: jnp.broadcast_to(  # noqa: E731
        t.reshape(rows, seq, kv, 1, hd), (rows, seq, kv, h // kv // c, hd)
    ).reshape(rows, seq, h // c, hd).transpose(2, 0, 1, 3)

    @jax.checkpoint
    def chunk(args):
        qc, kc, vc = args
        probs = jax.nn.softmax(jnp.where(
            seen, mm("bqcd,bkd->bcqk", qc, kc) / hd ** 0.5,
            jnp.finfo(jnp.float32).min), axis=-1)
        return mm("bcqk,bkd->bqcd", probs, vc)
    ctx = jax.lax.map(chunk, (q, shared(k), shared(v)))
    return mm("bse,ed->bsd", ctx.transpose(1, 2, 0, 3, 4).reshape(
        rows, seq, h * hd), a["o_proj"]["kernel"])


def _relu2(m, up, down, mm, eq_in="td,df->tf", eq_out="tf,fd->td"):
    return mm(eq_out, jnp.square(jax.nn.relu(mm(eq_in, m, up))), down)


def moe_layer(p, bias, m, s: dict, mm, shared=None):
    """``(y, aux)`` for ``m`` (tokens, hidden): the held experts' part of
    the expert layer's output plus the shared expert's (``shared``: its
    tree, or None), and the load-balancing term over all experts.  Every
    held expert runs on every token; a token's weight for an expert it did
    not pick is nought."""
    t = m.shape[0]
    k, e, held = s["num_experts_per_tok"], s["n_routed_experts"], \
        np.asarray(s["experts_held"])
    g = jax.nn.sigmoid(mm("td,de->te", m, p["router"]["kernel"]))
    # (tokens, experts): whether the token picked the expert, the ``k``
    # largest of ``s + b`` one after another (of equals the first, as a
    # top-k takes them): a mask and sums where a sort would do
    score = g + jax.lax.stop_gradient(bias)[None, :]
    chose = jnp.zeros((t, e), bool)
    for _ in range(k):
        best = jnp.argmax(jnp.where(chose, -jnp.inf, score), axis=-1)
        chose |= jnp.arange(e) == best[:, None]
    picked = s["routed_scaling_factor"] * g * chose / (
        (g * chose).sum(-1, keepdims=True) + 1e-20)
    y = _relu2(m, p["experts"]["up_proj"]["kernel"],
               p["experts"]["down_proj"]["kernel"], mm,
               "td,ndf->tnf", "tnf,nfd->tnd")
    mine = (np.arange(e)[:, None] == held[None, :]).astype(np.float32)
    y = ((picked[:, :, None] * mine).sum(axis=1)[:, :, None] * y).sum(axis=1)
    if shared is not None:
        y = y + _relu2(m, shared["up_proj"]["kernel"],
                       shared["down_proj"]["kernel"], mm)
    pairs = chose.sum(axis=0)
    aux = e * jnp.sum(pairs / t * (g / g.sum(-1, keepdims=True)).mean(0))
    return y, aux


def _mixer_layer(p, x, s, mm, mixer):
    return x + mixer(p["attention"], _rms(
        p["input_norm"]["scale"], x, s["layer_norm_epsilon"]), s, mm)


def _expert_layer(p, bias, x, s, mm):
    b, seq, d = x.shape
    y, aux = moe_layer(p["moe"], bias, _rms(
        p["post_norm"]["scale"], x, s["layer_norm_epsilon"]
    ).reshape(b * seq, d), s, mm, p.get("shared_experts"))
    return x + y.reshape(b, seq, d), aux


def _run(params, stats, ids, cast, model_kwargs=None):
    """``(logits, sum of the expert layers' load-balancing terms)``.  Where
    the pattern is a run of ``ME`` and ``M*E`` (the published period is
    ``MEMEM*E``) it is ONE ``lax.scan`` over the runs' stacked trees, the
    attention layer under a ``cond``: each kind of layer is traced and
    compiled once."""
    q = cast or (lambda a: a)

    def mm(eq, a, b):
        """``einsum(eq, a, b)`` at full precision.  Where ``b`` is a matrix
        of the model (no token axis) the tokens of ``a`` go through it
        ``TOKEN_BLOCK`` at a time: the chip's compiler takes seconds for
        one float32 product at full precision, the longer the wider its
        contraction, and a matrix's gradient contracts over the tokens
        (PERF.md section 6, PR 34)."""
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
    layers = names[1:-2]
    kinds = "".join(
        "E" if "moe" in params[n] else
        "M" if "A_log" in params[n]["attention"] else "*" for n in layers)
    bias = lambda n: stats[n]["moe"]["e_score_correction_bias"]  # noqa: E731
    x = params[names[0]]["embedding"][ids]
    aux = 0.0
    runs = re.findall(r"M\*?E", kinds)
    if ONE_SCAN and "".join(runs) == kinds and len(runs) > 1 \
            and kinds.count("*") == 1:
        stack = lambda trees: jax.tree_util.tree_map(  # noqa: E731
            lambda *a: jnp.stack(a), *trees)
        of = lambda kind: [n for n, c in zip(layers, kinds)  # noqa: E731
                           if c == kind]
        star = params[of("*")[0]]

        @jax.checkpoint
        def body(x, at):
            m, e, b, with_star = at
            x = _mixer_layer(m, x, s, mm, mamba2)
            x = jax.lax.cond(
                with_star, lambda x: _mixer_layer(star, x, s, mm, attention),
                lambda x: x, x)
            return _expert_layer(e, b, x, s, mm)
        x, terms = jax.lax.scan(body, x, (
            stack([params[n] for n in of("M")]),
            stack([params[n] for n in of("E")]),
            stack([bias(n) for n in of("E")]),
            jnp.asarray(["*" in run for run in runs])))
        aux = terms.sum()
    else:
        for n, kind in zip(layers, kinds):
            if kind == "E":
                x, term = jax.checkpoint(
                    lambda p, b, x: _expert_layer(p, b, x, s, mm))(
                    params[n], bias(n), x)
                aux = aux + term
            else:
                mixer = mamba2 if kind == "M" else attention
                x = jax.checkpoint(lambda p, x, mixer=mixer: _mixer_layer(
                    p, x, s, mm, mixer))(params[n], x)
    x = _rms(params[names[-2]]["scale"], x, s["layer_norm_epsilon"])
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

def scan_flops_per_token(s: dict) -> float:
    """Forward operations of the chunked state-space scan a token and
    layer, at the configuration's chunk: a head's product inside the chunk
    (``2 x chunk x head_dim``), its part of the chunk's state and its read
    of the state it was handed (``2 x 2 x state x head_dim``), and a
    group's scores (``2 x chunk x state``)."""
    q, p, n = s["chunk_size"], s["mamba_head_dim"], s["ssm_state_size"]
    return s["mamba_num_heads"] * (2 * q * p + 4 * n * p) \
        + s["n_groups"] * 2 * q * n


def train_flops_per_sample(flops, model_kwargs=None) -> float:
    """Forward+backward FLOPs of one training sample (one row), from
    shapes: 3x the forward multiply-adds of every layer: a Mamba-2
    layer's two projections, its convolution and its scan
    (:func:`scan_flops_per_token`); attention's four projections and its
    scores and values over the keys of the causal triangle; an expert
    layer's router, shared expert and the held experts' two products for
    the pairs that fall to them on average (``num_experts_per_tok * held /
    n_routed_experts`` a token); the head.  The embedding lookup counts
    nothing; recomputation neither."""
    s = sizes(model_kwargs)
    d, seq = s["hidden_size"], s["seq_len"]
    h, kv, hd = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    inner = s["mamba_num_heads"] * s["mamba_head_dim"]
    wide = inner + 2 * s["n_groups"] * s["ssm_state_size"]
    pairs = seq * s["num_experts_per_tok"] * len(s["experts_held"]) \
        / s["n_routed_experts"]
    triangle = seq * (seq + 1) / 2
    per = {"M": flops.dense(seq, d, inner + wide + s["mamba_num_heads"])
           + flops.dense(seq, inner, d)
           + 2.0 * seq * wide * s["conv_kernel"]
           + seq * scan_flops_per_token(s),
           "*": flops.dense(seq, d, (h + 2 * kv) * hd)
           + flops.dense(seq, h * hd, d)
           + 2 * flops.dense(triangle, hd, h),
           "E": flops.dense(seq, d, s["n_routed_experts"])
           + 2 * flops.dense(seq, d, s["moe_shared_expert_intermediate_size"])
           + 2 * flops.dense(pairs, d, s["moe_intermediate_size"])}
    return 3.0 * (flops.dense(seq, d, s["vocab_size"]) + sum(
        per[kind] for kind in s["hybrid_override_pattern"]))
