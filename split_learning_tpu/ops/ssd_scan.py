"""The state-space scan of a Mamba-2 layer (SSD), chunked.

For one head with a float32 state ``S`` (P x N), decay ``a_t = dt_t * A``
(``A`` < 0, so ``a_t`` <= 0) and inputs ``x_t`` (P), ``B_t``, ``C_t`` (N; a
group of heads shares them)::

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

:func:`ssd_scan` computes it in chunks of ``chunk`` positions: inside a
chunk the result is a masked product (``(C_i . B_j) exp(cum_i - cum_j)
dt_j`` over ``j <= i``, times ``x``), a chunk's own state one product, the
state each chunk is handed a product of the chunks' states with the decays
between them (``chunks`` x ``chunks``, float32), and what a chunk reads of
the state it was handed one product more.  Nothing of a row's length is
held a position at a time, and nothing loops over positions or chunks:
the position-by-position recurrence (:func:`ssd_scan_reference`, the
parity oracle at small sizes) keeps a state a position for its backward
pass, 2 MiB a row at the published sizes.

Forward and backward are one custom VJP, each a loop over the groups of
heads that share ``B`` and ``C`` (what a pass holds of the ``Q x Q``
decays is a group's).  The forward pass keeps its operands and nothing
else; the backward pass builds a group's chunk states again and
differentiates the group's chunks all at once.

Types: the decays (``dt``, ``A``, every ``exp``) and the state are
float32; ``x``, ``B`` and ``C`` are multiplied in the type they come in
(bfloat16 in the token cells) with float32 accumulation.  Every decay is
``exp`` of a DIFFERENCE of cumulative sums that is taken first and is never
positive: a chunk whose whole decay underflows costs nothing but that
chunk's memory of what came before it (the factored form ``exp(cum_i) *
exp(-cum_j)`` would overflow there).

The state is carried from one chunk to the next across the whole row, and
so across the ends of the documents packed into it, as attention sees
across them (PERF.md section 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 128


def ssd_scan_reference(x, dt, a_log_neg, b, c):
    """The recurrence as written, a position at a time, in float32:
    ``x`` (B, S, H, P), ``dt`` (B, S, H), ``a_log_neg`` = ``A`` (H,),
    ``b``, ``c`` (B, S, G, N) -> ``y`` (B, S, H, P).  Small sizes only."""
    bsz, _, h, p = x.shape
    g, n = b.shape[2:]
    rep = h // g
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    x, dt, b, c = f32(x), f32(dt), f32(b), f32(c)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))
        state = jnp.exp(dt_t * a_log_neg)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _dot(eq, lhs, rhs):
    return jnp.einsum(eq, lhs, rhs, preferred_element_type=jnp.float32)


# One group of heads at a time (``lax.map``): what a pass holds of the
# chunks' ``Q x Q`` decays is a group's, not the layer's.  Of one group:
# ``x`` (B, c, R, Q, P), ``dt`` and its running sum ``cum`` (B, c, R, Q),
# ``a`` (R,), ``b`` and ``c`` (B, c, Q, N): chunks, then the group's heads,
# positions before widths.

def _chunk_states(x, dt, cum, b):
    """What each chunk adds to the state by its end: (B, c, R, P, N),
    float32."""
    to_end = jnp.exp(cum[..., -1:] - cum) * dt
    return _dot("bcrqp,bcqn->bcrpn",
                (to_end[..., None] * x).astype(x.dtype), b)


def _handed(states, whole):
    """The state each chunk is handed: nought for the first, then what the
    chunks before it added, each decayed over the chunks between
    (``whole`` (B, c, R): the decay summed over each chunk; ``exp`` of a
    difference of its running sums, taken first and never positive).  One
    small product in float32 at full precision where a recurrence would be
    a loop ``chunks`` long: a row has 32 chunks, and a loop's step costs
    the chip more than it computes."""
    k = whole.shape[1]
    upto = jnp.cumsum(whole, axis=1)
    # over[i, j]: the decay from chunk j's end to chunk i's start, j < i
    between = (upto - whole)[:, :, None] - upto[:, None, :]
    over = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((k, k), bool), -1)[None, :, :, None], between,
        -jnp.inf))
    return jnp.einsum("bijr,bjrpn->birpn", over, states,
                      precision=jax.lax.Precision.HIGHEST)


def _chunk_outputs(x, dt, cum, b, c, handed):
    """``y`` of every chunk given the state it was handed."""
    q = x.shape[-2]
    # (C_i . B_j) exp(cum_i - cum_j) dt_j over j <= i: the difference first,
    # masked before the exp, so nothing overflows
    within = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((q, q), bool)),
        cum[..., :, None] - cum[..., None, :], -jnp.inf))
    mixed = _dot("bcin,bcjn->bcij", c, b)[:, :, None] * within \
        * dt[..., None, :]
    y = _dot("bcrij,bcrjp->bcrip", mixed.astype(x.dtype), x)
    read = _dot("bcin,bcrpn->bcrip", c, handed.astype(x.dtype))
    return y + jnp.exp(cum)[..., None] * read


def _group(x, dt, a, b, c):
    """One group's ``y`` (B, c, R, Q, P), float32."""
    # the decay summed from a chunk's start to each position (<= 0)
    cum = jnp.cumsum(dt * a[:, None], axis=-1)
    handed = _handed(_chunk_states(x, dt, cum, b), cum[..., -1])
    return _chunk_outputs(x, dt, cum, b, c, handed)


def _group_backward(at):
    """A group's cotangents from its operands alone: the chunks' states
    and what each was handed are built again, then differentiated."""
    *operands, dy = at
    return jax.vjp(_group, *operands)[1](dy)


def _heads_by_group(v, groups: int, chunk: int):
    """``v`` (B, S, H, ...) of every head as (G, B, c, R, Q, ...): the
    groups leading, chunks, a group's heads, positions before widths.  A
    row that is no multiple of the chunk is padded with noughts (a
    position of ``dt`` nought neither decays the state nor adds to it)."""
    bsz, s, h = v.shape[:3]
    v = jnp.pad(v, ((0, 0), (0, -s % chunk)) + ((0, 0),) * (v.ndim - 2))
    v = v.reshape(bsz, -1, chunk, groups, h // groups, *v.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(v, 3, 0), 3, 4)


def _by_group(x, dt, a, b, c, chunk):
    """The operands a group at a time, in chunks: ``x`` (G, B, c, R, Q, P),
    ``dt`` (G, B, c, R, Q), ``a`` (G, R), ``b`` and ``c`` (G, B, c, Q, N)."""
    g = b.shape[2]
    # a group is its own one "head" of ``b`` and ``c``
    b, c = (_heads_by_group(v, g, chunk)[:, :, :, 0] for v in (b, c))
    return _heads_by_group(x, g, chunk), \
        _heads_by_group(dt.astype(jnp.float32), g, chunk), \
        a.astype(jnp.float32).reshape(g, -1), b, c


def _rows_of(y, shape):
    """``y`` (G, B, c, R, Q, P) back as rows (B, S, H, P) of ``shape``."""
    bsz, s, h, p = shape
    return y.transpose(1, 2, 4, 0, 3, 5).reshape(bsz, -1, h, p)[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan(x, dt, a, b, c, chunk: int = CHUNK):
    """``y`` (B, S, H, P) of the recurrence in the module's docstring for
    ``x`` (B, S, H, P), ``dt`` (B, S, H; after the softplus), ``a`` (H,;
    negative), ``b`` and ``c`` (B, S, G, N; head ``h`` reads group ``h //
    (H / G)``), in chunks of ``chunk`` positions."""
    return _rows_of(jax.lax.map(lambda at: _group(*at), _by_group(
        x, dt, a, b, c, chunk)), x.shape).astype(x.dtype)


def _ssd_fwd(x, dt, a, b, c, chunk):
    return ssd_scan(x, dt, a, b, c, chunk), (x, dt, a, b, c)


def _ssd_bwd(chunk, res, dy):
    grouped, pull_layout = jax.vjp(
        lambda *operands: _by_group(*operands, chunk), *res)
    dy = _heads_by_group(dy.astype(jnp.float32), res[3].shape[2], chunk)
    return pull_layout(jax.lax.map(_group_backward, (*grouped, dy)))


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
