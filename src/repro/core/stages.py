"""HGNN execution stages (reference semantics, pure jnp).

The paper decomposes HGNN execution into FP -> (theta) -> NA -> LSF -> GSF
(Algorithm 2).  This module is the functional ground truth for each
fine-grained stage; fusion.py composes them into fused/staged execution
paths and kernels/ provides the TPU Pallas implementations.

Conventions:
  * multi-head features are [N, H, Dh]; attention coefficients are [N, H]
  * edge lists are dst-sorted PaddedEdges (src, dst, valid)
  * all ops are jit/vmap/shard_map friendly (static shapes, no host sync)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def feature_projection(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray | None = None) -> jnp.ndarray:
    """FP stage: h' = x @ W (+ b).  x: [N, Din], w: [Din, H*Dh] -> [N, H*Dh].

    Type-specific projection is expressed by calling this once per vertex
    type — the functional RAB: each vertex is projected exactly once and
    the result is *gathered* everywhere it is needed (DESIGN.md §2).
    """
    h = x @ w
    if b is not None:
        h = h + b
    return h


def attention_coefficients(
    h: jnp.ndarray, a_src: jnp.ndarray, a_dst: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The fused first-half of NA (paper Alg. 2 line 8): per-vertex GAT
    coefficients theta_src[u] = <h'_u, a_src>, theta_dst[v] = <h'_v, a_dst>.

    h: [N, H, Dh]; a_*: [H, Dh] -> ([N, H], [N, H]).  Computed once per
    (vertex, semantic graph) and reused for every incident edge — the
    second reuse the RAB tracks.
    """
    th_s = jnp.einsum("nhd,hd->nh", h, a_src)
    th_d = jnp.einsum("nhd,hd->nh", h, a_dst)
    return th_s, th_d


def segment_softmax_aggregate(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    valid: jnp.ndarray,
    theta_src: jnp.ndarray,
    theta_dst: jnp.ndarray,
    h_src: jnp.ndarray,
    num_dst: int,
    *,
    leaky_slope: float = 0.2,
    edge_bias: jnp.ndarray | float = 0.0,
) -> jnp.ndarray:
    """NA stage reference: two-pass segment softmax attention aggregation.

    z_v = sum_u softmax_u(LeakyReLU(theta_dst[v] + theta_src[u] + bias)) h'_u

    Shapes: src/dst/valid [E]; theta_* [N, H]; h_src [Ns, H, Dh] -> [Nd, H, Dh].
    """
    logits = jax.nn.leaky_relu(theta_dst[dst] + theta_src[src] + edge_bias, leaky_slope)
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    m = jax.ops.segment_max(logits, dst, num_segments=num_dst)  # [Nd, H]
    m = jnp.maximum(m, NEG_INF)  # isolated vertices: keep finite
    p = jnp.exp(logits - m[dst])
    p = jnp.where(valid[:, None], p, 0.0)
    denom = jax.ops.segment_sum(p, dst, num_segments=num_dst)  # [Nd, H]
    num = jax.ops.segment_sum(p[:, :, None] * h_src[src], dst, num_segments=num_dst)
    return num / jnp.maximum(denom, 1e-9)[:, :, None]


def segment_mean_aggregate(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    valid: jnp.ndarray,
    h_src: jnp.ndarray,
    num_dst: int,
) -> jnp.ndarray:
    """R-GCN NA: z_v = (1/|N_v|) sum_{u in N_v} h'_u.  h_src [Ns, ...]."""
    w = valid.astype(h_src.dtype)
    deg = jax.ops.segment_sum(w, dst, num_segments=num_dst)
    shaped = w.reshape((-1,) + (1,) * (h_src.ndim - 1))
    num = jax.ops.segment_sum(h_src[src] * shaped, dst, num_segments=num_dst)
    return num / jnp.maximum(deg, 1.0).reshape((-1,) + (1,) * (h_src.ndim - 1))


def block_softmax_aggregate(
    col_index: jnp.ndarray,   # int32 [R, W]   (-1 = padding)
    masks: jnp.ndarray,       # bool  [R, W, B, B]
    theta_src: jnp.ndarray,   # [Ns_pad, H]
    theta_dst: jnp.ndarray,   # [Nd_pad, H]
    h_src: jnp.ndarray,       # [Ns_pad, H, Dh]
    *,
    leaky_slope: float = 0.2,
    edge_bias: jnp.ndarray | float = 0.0,
) -> jnp.ndarray:
    """Block-CSR *online-softmax* NA — the paper's softmax decomposition
    (numerator and denominator accumulated simultaneously, Fig. 6), in the
    block-densified TPU layout.  Pure-jnp oracle for kernels/seg_gat_agg_multigraph.

    Returns [Nd_pad, H, Dh].
    """
    R, W = col_index.shape
    B = masks.shape[-1]
    H, Dh = theta_src.shape[1], h_src.shape[-1]
    th_d = theta_dst.reshape(R, B, H)

    def row(carry_r, row_inputs):
        cols, mrow = row_inputs  # [W], [W, B, B]

        def step(carry, inp):
            m_run, l_run, acc = carry  # [B,H], [B,H], [B,H,Dh]
            c, mask = inp  # scalar, [B, B]
            c_safe = jnp.maximum(c, 0)
            th_s = jax.lax.dynamic_slice_in_dim(theta_src, c_safe * B, B, 0)  # [B,H]
            hs = jax.lax.dynamic_slice_in_dim(h_src, c_safe * B, B, 0)  # [B,H,Dh]
            logits = jax.nn.leaky_relu(
                carry_r[:, None, :] + th_s[None, :, :] + edge_bias, leaky_slope
            )  # [B(dst), B(src), H]
            live = mask[:, :, None] & (c >= 0)
            logits = jnp.where(live, logits, NEG_INF)
            m_blk = jnp.max(logits, axis=1)  # [B, H]
            m_new = jnp.maximum(m_run, m_blk)
            scale = jnp.exp(m_run - m_new)
            p = jnp.exp(logits - m_new[:, None, :])  # [B, B, H]
            p = jnp.where(live, p, 0.0)
            l_new = l_run * scale + p.sum(axis=1)
            acc_new = acc * scale[:, :, None] + jnp.einsum("dsh,shf->dhf", p, hs)
            return (m_new, l_new, acc_new), None

        # f32 carries regardless of input dtype — matches the Pallas
        # kernels' f32 accumulation; only the final output is cast back.
        init = (
            jnp.full((B, H), NEG_INF, jnp.float32),
            jnp.zeros((B, H), jnp.float32),
            jnp.zeros((B, H, Dh), jnp.float32),
        )
        (m_f, l_f, acc_f), _ = jax.lax.scan(step, init, (cols, mrow))
        return acc_f / jnp.maximum(l_f, 1e-9)[:, :, None]

    out = jax.vmap(row)(th_d, (col_index, masks))  # [R, B, H, Dh]
    return out.reshape(R * B, H, Dh).astype(h_src.dtype)


def local_semantic_fusion(
    z: jnp.ndarray, w_g: jnp.ndarray, b_g: jnp.ndarray, q: jnp.ndarray, valid_dst: jnp.ndarray
) -> jnp.ndarray:
    """LSF stage (paper Alg. 2 line 21): per-semantic-graph partial semantic
    importance w_P = (1/|V|) sum_v q^T tanh(W_g z_v + b).  Fusable into NA
    completion — it only needs each vertex's finished aggregate once.

    z: [Nd, D]; w_g: [D, Da]; q: [Da]; valid_dst: [Nd] -> scalar.
    """
    s = jnp.tanh(z @ w_g + b_g) @ q  # [Nd]
    s = jnp.where(valid_dst, s, 0.0)
    return s.sum() / jnp.maximum(valid_dst.sum(), 1.0)


def global_semantic_fusion(
    w_p: jnp.ndarray, z_stack: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """GSF stage: beta = softmax_P(w_P); h_v = sum_P beta_P z_v^P.

    w_p: [P]; z_stack: [P, Nd, D] -> ([Nd, D], beta [P]).
    """
    beta = jax.nn.softmax(w_p)
    return jnp.einsum("p,pnd->nd", beta, z_stack), beta
