"""Operation counts from shapes.  A multiply-add is 2 FLOPs.  These are
the operations the algorithm needs for one FORWARD pass; a reference
module multiplies by 3 for forward + backward.  Recomputation (remat) is
never counted, so ``cost_analysis()`` of a compiled step reads higher.
"""

from __future__ import annotations


def dense(rows: int, d_in: int, d_out: int) -> float:
    """``rows`` x (d_in -> d_out) matrix product."""
    return 2.0 * rows * d_in * d_out


def conv2d(h_out: int, w_out: int, c_in: int, c_out: int, k: int) -> float:
    """k x k convolution producing an (h_out, w_out, c_out) map."""
    return 2.0 * h_out * w_out * c_out * c_in * k * k


def attention(seq: int, d_model: int) -> float:
    """Scores (QK^T) and context (PV) of full self-attention over
    ``seq`` positions, all heads together."""
    return 2.0 * (2.0 * seq * seq * d_model)


def transformer_block(seq: int, d_model: int, n_heads: int,
                      d_ff: int) -> float:
    """One encoder block on one sequence: Q, K, V and output projections,
    attention, two feed-forward products.  ``n_heads`` divides d_model
    and does not change the count."""
    del n_heads
    return (4 * dense(seq, d_model, d_model) + attention(seq, d_model)
            + dense(seq, d_model, d_ff) + dense(seq, d_ff, d_model))
