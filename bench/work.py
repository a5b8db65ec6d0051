"""Operations and bytes the HAN algorithm needs, from real edges.

Counted from the number of real (deduplicated) edges of each metapath
graph, the vertex count and the published widths, never from tiles,
block slots, lanes or masks: a kernel that skips masked pairs or padded
slots is judged against the same count.  FLOPs count multiply-adds as 2
and every elementwise operation as 1; 4 bytes per float32 or int32.

NA forward, per graph with E edges over N vertices, H heads of Dh:
  FLOPs  E*H*(2*Dh + 6): logit (2 adds + LeakyReLU), running max,
         exp, denominator add, and the weighted sum of Dh features;
         plus N*H*Dh for the final division.
  bytes  each edge's (src, dst) index pair once, each source row
         (H*Dh features and its theta_src) and each theta_dst once per
         graph, and each output row written once.
NA backward, per graph:
  FLOPs  E*H*(4*Dh + 12): the forward logit and p recomputed (6), the
         g_out . h_src product (2*Dh), the softmax and LeakyReLU
         backward (4), the theta_src/theta_dst sums (2) and p^T g_out
         (2*Dh).
  bytes  the edge indices, h_src, theta_src, theta_dst, g_out, the
         per-row log-sum-exp and delta read once; d_h_src, d_theta_src,
         d_theta_dst written once.
"""
from __future__ import annotations

F32 = 4


def na_forward(edges: list[int], n: int, heads: int, hidden: int) -> tuple[float, float]:
    f = heads * hidden
    flops = sum(e * heads * (2 * hidden + 6) + n * f for e in edges)
    nbytes = sum(e * 2 * F32 + n * (f + heads) * F32 + n * heads * F32 + n * f * F32 for e in edges)
    return float(flops), float(nbytes)


def na_backward(edges: list[int], n: int, heads: int, hidden: int) -> tuple[float, float]:
    f = heads * hidden
    flops = sum(e * heads * (4 * hidden + 12) for e in edges)
    reads = lambda e: e * 2 * F32 + n * (f + heads + heads + f + heads + heads) * F32
    writes = n * (f + heads + heads) * F32
    nbytes = sum(reads(e) + writes for e in edges)
    return float(flops), float(nbytes)


def han_forward_flops(edges: list[int], n: int, d_in: int, heads: int, hidden: int,
                      att_dim: int, n_classes: int | None) -> dict[str, float]:
    """Forward FLOPs of one HAN layer over ``len(edges)`` metapath graphs;
    ``n_classes=None`` leaves out the output head (serving)."""
    f = heads * hidden
    g = len(edges)
    out = {
        "fp": 2.0 * n * d_in * f + n * f,
        "theta": 2 * 2.0 * g * n * f,
        "na": na_forward(edges, n, heads, hidden)[0],
        # ELU, W_g z + b, tanh, q ., the mean; then softmax and the weighted sum
        "fusion": g * (n * f + 2.0 * n * f * att_dim + 2 * n * att_dim + 2 * n * att_dim) + 2.0 * g * n * f,
        "out": 0.0 if n_classes is None else 2.0 * n * f * n_classes + n * n_classes,
    }
    out["total"] = sum(out.values())
    return out


def han_train_step_flops(edges: list[int], n: int, d_in: int, heads: int, hidden: int,
                         att_dim: int, n_classes: int) -> dict[str, float]:
    """FLOPs of one full-batch training step: forward, backward, update.
    The backward of a matmul is twice its forward (input and weight
    gradients), except FP, whose input x needs no gradient; NA's
    backward is counted by ``na_backward``."""
    fwd = han_forward_flops(edges, n, d_in, heads, hidden, att_dim, n_classes)
    f = heads * hidden
    n_params = d_in * f + f + 2 * len(edges) * f + f * att_dim + 2 * att_dim + f * n_classes + n_classes
    bwd = {
        "fp": 2.0 * n * d_in * f + n * f,
        "theta": 2 * fwd["theta"],
        "na": na_backward(edges, n, heads, hidden)[0],
        "fusion": 2 * fwd["fusion"],
        "out": 2 * fwd["out"],
    }
    out = {f"{k}_fwd": v for k, v in fwd.items() if k != "total"}
    out.update({f"{k}_bwd": v for k, v in bwd.items()})
    out["update"] = 12.0 * n_params  # AdamW: ~12 elementwise ops per parameter
    out["total"] = sum(out.values())
    return out
