"""Operations and bytes the S-HGN algorithm needs, from real edges.

Counted from the real edges of the union graph (self-loops included),
its vertex count and the published widths, never from tiles, block slots
or padding: a kernel that skips empty pairs or dead slots is judged
against the same count, and the output layer counts its C classes, not
the lanes the kernel pads them to.  FLOPs count multiply-adds as 2 and
every elementwise operation as 1; 4 bytes per float32 or int32.

NA forward, per layer with E edges over N vertices, H heads of Dh, and
a bias table of T types:
  FLOPs  E*H*(2*Dh + 7): logit (3 adds: both coefficients and the type's
         bias; LeakyReLU), running max, exp, denominator add, and the
         weighted sum of Dh features; plus N*H*Dh for the division.
  bytes  each edge's (src, dst, type) once, each source row (H*Dh
         features and its theta_src) and each theta_dst once, the bias
         table, and each output row written once.
NA backward, per layer:
  FLOPs  E*H*(4*Dh + 13): HAN's 12 (bench/work.py) and the bias
         gradient's add.
  bytes  the edges, h_src, theta_src, theta_dst, g_out, the per-row
         log-sum-exp and delta read once; d_h_src, d_theta_src,
         d_theta_dst and the bias table's gradient written once.
The attention residual (hidden layers after the first) is counted as a
stored attention: reading one value per edge and head, forward and
backward (E*H*4 bytes each), and mixing it, (1 - beta) a + beta a_prev,
3 FLOPs per edge and head each way; whatever the kernel does instead.
"""
from __future__ import annotations

F32 = 4


def layers(cfg: dict) -> list[tuple[int, int, bool]]:
    """(heads, head width, residual) of each NA layer: the hidden layers,
    then the output layer of 1 head over the classes."""
    hidden = [(cfg["heads"], cfg["hidden"], i > 0) for i in range(cfg["layers"])]
    return hidden + [(1, cfg["graph"]["num_classes"], False)]


def na_forward(e: int, n: int, n_types: int, spec) -> tuple[float, float]:
    flops = nbytes = 0.0
    for heads, dh, residual in spec:
        f = heads * dh
        flops += e * heads * (2 * dh + 7) + n * f + (3 * e * heads if residual else 0)
        nbytes += (e * 3 + n * (f + heads) + n * heads + n * f + n_types * heads
                   + (e * heads if residual else 0)) * F32
    return flops, nbytes


def na_backward(e: int, n: int, n_types: int, spec) -> tuple[float, float]:
    flops = nbytes = 0.0
    for heads, dh, residual in spec:
        f = heads * dh
        flops += e * heads * (4 * dh + 13) + (3 * e * heads if residual else 0)
        reads = e * 3 + n * (f + heads + heads + f + heads + heads) + n_types * heads
        writes = n * (f + heads + heads) + n_types * heads
        nbytes += (reads + writes + (e * heads if residual else 0)) * F32
    return flops, nbytes


def n_params(dims: dict[str, int], n_types: int, cfg: dict) -> int:
    hid, heads, k, c = cfg["hidden"], cfg["heads"], cfg["edge_dim"], cfg["graph"]["num_classes"]
    total = sum(d * hid + hid for d in dims.values())
    d_in = hid
    for h, dh, _ in layers(cfg):
        total += d_in * h * dh + 2 * h * dh + n_types * k + k * h * k + h * k
        d_in = h * dh
    return total + heads * hid * c


def train_step_flops(e: int, dims: dict[str, int], counts: dict[str, int], n_target: int,
                     n_types: int, cfg: dict) -> dict[str, float]:
    """FLOPs of one full-batch training step: forward, backward, update.
    The backward of a matmul is twice its forward (input and weight
    gradients), except the input projection, whose x needs no gradient;
    NA's backward is counted by ``na_backward``."""
    n = sum(counts.values())
    hid, k, c = cfg["hidden"], cfg["edge_dim"], cfg["graph"]["num_classes"]
    spec = layers(cfg)
    fwd = {"fp": sum(2.0 * counts[t] * dims[t] * hid + counts[t] * hid for t in dims),
           "proj": 0.0, "theta": 0.0, "epilogue": 0.0}
    d_in = hid
    for i, (h, dh, residual) in enumerate(spec):
        f = h * dh
        fwd["proj"] += 2.0 * n * d_in * f
        # a_src.g, a_dst.g; the bias table W_r e_t then a_e.(.)
        fwd["theta"] += 2 * 2.0 * n * f + n_types * (2.0 * k * h * k + 2 * h * k)
        if i < len(spec) - 1:  # residual add after the first layer, ELU
            fwd["epilogue"] += (n * f if i else 0) + n * f
        else:  # W_res h and its add, then the L2 normalisation of the target rows
            fwd["epilogue"] += 2.0 * n * d_in * c + n * c + 3.0 * n_target * c
        d_in = f
    fwd["na"] = na_forward(e, n, n_types, spec)[0]
    bwd = {"fp": fwd["fp"], "proj": 2 * fwd["proj"], "theta": 2 * fwd["theta"],
           "epilogue": 2 * fwd["epilogue"], "na": na_backward(e, n, n_types, spec)[0]}
    out = {f"{key}_fwd": v for key, v in fwd.items()}
    out.update({f"{key}_bwd": v for key, v in bwd.items()})
    out["update"] = 12.0 * n_params(dims, n_types, cfg)  # AdamW: ~12 elementwise ops a parameter
    out["total"] = sum(out.values())
    return out
