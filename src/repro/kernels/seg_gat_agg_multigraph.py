"""Multi-graph fused NA kernel — the paper's multi-lane execution (§4.2)
at the Pallas level, forward AND backward.

One kernel launch processes work units from *different* semantic graphs:
each unit is a (graph, dst-block-row) pair, exactly the work unit of
core/multilane.py.  Scalar-prefetched ``graph_id``/``dst_row`` tables
drive the BlockSpec index maps, so the per-unit theta tables (per-graph
attention coefficients — the RAB-cached values) and the shared h_src
stream in without any host-side regrouping: the hardware analogue of the
Local Scheduler dispatching mixed-graph workloads onto one lane.

Grid: (U, W) — U work units, W block slots per unit; all heads of a
unit run inside one grid step.  Scratch (m, l, acc) carries across W
(online softmax, Fig. 6).  The forward additionally emits the per-row
log-sum-exp (lse = m + log l), the only residual the backward needs
beyond the inputs.

A dead slot (``col_index < 0``: the padding after a row's last block,
or every slot of an all-padding unit) fetches nothing and runs no body,
forward and backward.  The wrapper forward-fills the flattened (u, w)
grid, so a dead slot's mask / theta_src / h_src index maps repeat those
of the last live slot (slot 0 before any) and the Pallas pipeline issues
no copy for the unchanged block index; the bodies run their per-head
loops under ``pl.when(col >= 0)``.  A computed dead slot would add p = 0
under scale 1 and zero gradients, so skipping it changes no bit of any
result.

The backward is itself one fused multigraph launch (the
kernel-consolidation result of arXiv 2408.08490 applied to training):
it *recomputes* the attention probabilities online from lse
(p = exp(logits - lse), flash-attention style — no [U, W, B, B, H]
probability tensor is ever materialized) and produces

  * d_theta_dst  — accumulated across the W axis in VMEM scratch,
    written once per unit;
  * per-(unit, slot) d_theta_src / d_h_src block partials — the GSF-like
    scatter-add onto the shared src vertex space happens outside the
    kernel with segment sums (Pallas TPU cannot safely revisit output
    blocks in non-consecutive grid steps).

``seg_gat_agg_multigraph`` carries a ``jax.custom_vjp``, so HAN training
consolidates all relations of a step into a single forward and a single
backward launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# TPU block layout.  Mosaic tiles the last two dims of every block by
# (8, 128) unless a block dim spans its whole array dim, so no block may
# carry a single head: every block keeps the head axis whole and the
# kernel bodies loop over heads.  theta_src is fed head-major
# ([G, H, Ns_pad]) so a src tile's coefficients arrive as lane-dense
# [H, B] rows; h_src / outputs are fed flat as [N, H*Dh].


def _logits(thd_ref, ths_ref, bias, hh, leaky_slope):
    """Pre-activation and LeakyReLU logits [B(dst), B(src)] of head hh."""
    pre = (
        thd_ref[0, :, hh : hh + 1].astype(jnp.float32)    # [B, 1]
        + ths_ref[0, hh : hh + 1, :].astype(jnp.float32)  # [1, B]
        + bias
    )
    return pre, jnp.where(pre >= 0, pre, leaky_slope * pre)


def _fwd_kernel(
    # scalar prefetch
    col_ref,    # int32 [U, W]
    gid_ref,    # int32 [U]
    row_ref,    # int32 [U]
    bias_ref,   # f32   [G, H]
    fslot_ref,  # int32 [U*W]  filled slot (index maps only)
    fcol_ref,   # int32 [U*W]  filled col  (index maps only)
    # inputs
    mask_ref,   # bool [1, 1, B, B]
    thd_ref,    # [1, B, H]  dst coefficients of the unit's graph
    ths_ref,    # [1, H, B]  src coefficients of the slot's block
    hs_ref,     # [B, H*Dh]  shared source features
    # outputs
    out_ref,    # [B, H*Dh]
    lse_ref,    # f32 [B, H]
    # scratch
    acc_ref,    # f32 [B, H*Dh]
    m_ref,      # f32 [B, H]
    l_ref,      # f32 [B, H]
    *,
    heads: int,
    head_dim: int,
    leaky_slope: float,
):
    u = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(col_ref[u, w] >= 0)
    def _body():
        live = mask_ref[0, 0]
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            _, logits = _logits(thd_ref, ths_ref, bias_ref[gid_ref[u], hh], hh, leaky_slope)
            logits = jnp.where(live, logits, NEG_INF)
            m_prev = m_ref[:, hh : hh + 1]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
            scale = jnp.exp(m_prev - m_new)
            p = jnp.where(live, jnp.exp(logits - m_new), 0.0)
            l_ref[:, hh : hh + 1] = l_ref[:, hh : hh + 1] * scale + jnp.sum(
                p, axis=1, keepdims=True
            )
            acc_ref[:, sl] = acc_ref[:, sl] * scale + jnp.dot(
                p, hs_ref[:, sl].astype(jnp.float32), preferred_element_type=jnp.float32
            )
            m_ref[:, hh : hh + 1] = m_new

    @pl.when(w == nw - 1)
    def _finalize():
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            out_ref[:, sl] = (
                acc_ref[:, sl] / jnp.maximum(l_ref[:, hh : hh + 1], 1e-9)
            ).astype(out_ref.dtype)
        # lse of a fully-masked row degenerates to ~NEG_INF; the backward
        # masks those positions with `live` before any use.
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _bwd_kernel(
    # scalar prefetch
    col_ref,    # int32 [U, W]
    gid_ref,    # int32 [U]
    row_ref,    # int32 [U]
    bias_ref,   # f32   [G, H]
    fslot_ref,  # int32 [U*W]  filled slot (index maps only)
    fcol_ref,   # int32 [U*W]  filled col  (index maps only)
    # inputs
    mask_ref,   # bool [1, 1, B, B]
    thd_ref,    # [1, B, H]
    ths_ref,    # [1, H, B]
    hs_ref,     # [B, H*Dh]
    gout_ref,   # [B, H*Dh]  cotangent of the per-unit output
    lse_ref,    # f32 [B, H]  forward log-sum-exp residual
    delta_ref,  # f32 [B, H]  sum_f g_out * out (flash-attention delta)
    # outputs
    dths_ref,   # f32 [1, 1, H, B]     per-(unit, slot) src-coeff partial
    dhs_ref,    # f32 [1, 1, B, H*Dh]  per-(unit, slot) src-feature partial
    dthd_ref,   # f32 [B, H]           per-unit dst-coeff gradient
    # scratch
    dthd_acc_ref,  # f32 [B, H]
    *,
    heads: int,
    head_dim: int,
    leaky_slope: float,
):
    u = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    @pl.when(w == 0)
    def _init():
        dthd_acc_ref[...] = jnp.zeros_like(dthd_acc_ref)

    live_slot = col_ref[u, w] >= 0

    @pl.when(live_slot)
    def _body():
        live = mask_ref[0, 0]  # [B(dst), B(src)]
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            pre, logits = _logits(thd_ref, ths_ref, bias_ref[gid_ref[u], hh], hh, leaky_slope)
            # recompute-p: attention probabilities from the lse residual
            p = jnp.where(live, jnp.exp(logits - lse_ref[:, hh : hh + 1]), 0.0)
            g_out = gout_ref[:, sl].astype(jnp.float32)  # [B, Dh]
            hs = hs_ref[:, sl].astype(jnp.float32)       # [B, Dh]
            dp = jax.lax.dot_general(                    # g_out @ hs.T  [Bd, Bs]
                g_out, hs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            dlogit = p * (dp - delta_ref[:, hh : hh + 1])  # softmax backward
            dpre = jnp.where(pre >= 0, dlogit, leaky_slope * dlogit)
            dths_ref[0, 0, hh : hh + 1, :] = jnp.sum(dpre, axis=0, keepdims=True)
            dhs_ref[0, 0, :, sl] = jax.lax.dot_general(  # p.T @ g_out  [Bs, Dh]
                p, g_out, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dthd_acc_ref[:, hh : hh + 1] += jnp.sum(dpre, axis=1, keepdims=True)

    # a dead slot's partial blocks are still written back: zeros, not stale
    @pl.when(jnp.logical_not(live_slot))
    def _dead():
        dths_ref[...] = jnp.zeros_like(dths_ref)
        dhs_ref[...] = jnp.zeros_like(dhs_ref)

    @pl.when(w == nw - 1)
    def _finalize():
        dthd_ref[...] = dthd_acc_ref[...]


def _fill_dead(col_index):
    """Forward-fill of the flattened (u, w) grid: each dead slot takes the
    slot index and col of the most recent live slot (slot 0 before any),
    so consecutive dead steps present unchanged input block indices."""
    flat = col_index.reshape(-1)
    slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
    fslot = jnp.maximum(
        jax.lax.cummax(jnp.where(flat >= 0, slots, -1), axis=0), 0
    )
    return fslot, jnp.maximum(flat[fslot], 0)


def _unit_map(u, w, col, gid, row, bias, fslot, fcol):
    return (u, 0)


def _in_specs(B, H, hdh, W):
    def mask_map(u, w, col, gid, row, bias, fslot, fcol):
        return (fslot[u * W + w], 0, 0, 0)

    def thd_map(u, w, col, gid, row, bias, fslot, fcol):
        return (gid[u], row[u], 0)

    def ths_map(u, w, col, gid, row, bias, fslot, fcol):
        return (gid[u], 0, fcol[u * W + w])

    def hs_map(u, w, col, gid, row, bias, fslot, fcol):
        return (fcol[u * W + w], 0)

    return [
        pl.BlockSpec((1, 1, B, B), mask_map),  # masks fed as [U*W, 1, B, B]
        pl.BlockSpec((1, B, H), thd_map),
        pl.BlockSpec((1, H, B), ths_map),
        pl.BlockSpec((B, hdh), hs_map),
    ]


def _fwd_call(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
              h_src, edge_bias, leaky_slope, interpret):
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    hdh = H * Dh

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(U, W),
        in_specs=_in_specs(B, H, hdh, W),
        out_specs=[
            pl.BlockSpec((B, hdh), _unit_map),
            pl.BlockSpec((B, H), _unit_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, hdh), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, heads=H, head_dim=Dh, leaky_slope=leaky_slope
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((U * B, hdh), h_src.dtype),
            jax.ShapeDtypeStruct((U * B, H), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="seg_gat_agg_multigraph",
    )(col_index, graph_id, dst_row, edge_bias, *_fill_dead(col_index),
      masks.reshape(U * W, 1, B, B), theta_dst, theta_src.swapaxes(1, 2),
      h_src.reshape(ns_pad, hdh))
    return out.reshape(U * B, H, Dh), lse


def _bwd_call(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
              h_src, edge_bias, g_out, lse, delta, leaky_slope, interpret):
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    hdh = H * Dh

    def slot_map(u, w, col, gid, row, bias, fslot, fcol):
        return (u, w, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(U, W),
        in_specs=_in_specs(B, H, hdh, W) + [
            pl.BlockSpec((B, hdh), _unit_map),
            pl.BlockSpec((B, H), _unit_map),
            pl.BlockSpec((B, H), _unit_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, B), slot_map),
            pl.BlockSpec((1, 1, B, hdh), slot_map),
            pl.BlockSpec((B, H), _unit_map),
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
    )
    dths, dhs, dthd = pl.pallas_call(
        functools.partial(
            _bwd_kernel, heads=H, head_dim=Dh, leaky_slope=leaky_slope
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((U, W, H, B), jnp.float32),
            jax.ShapeDtypeStruct((U, W, B, hdh), jnp.float32),
            jax.ShapeDtypeStruct((U * B, H), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="seg_gat_agg_multigraph_bwd",
    )(col_index, graph_id, dst_row, edge_bias, *_fill_dead(col_index),
      masks.reshape(U * W, 1, B, B), theta_dst, theta_src.swapaxes(1, 2),
      h_src.reshape(ns_pad, hdh), g_out.reshape(U * B, hdh), lse, delta)
    return dths.swapaxes(2, 3), dhs.reshape(U, W, B, H, Dh), dthd


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _multigraph(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
                h_src, edge_bias, leaky_slope, interpret):
    out, _ = _fwd_call(col_index, graph_id, dst_row, masks, theta_src,
                       theta_dst, h_src, edge_bias, leaky_slope, interpret)
    return out


def _multigraph_fwd(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
                    h_src, edge_bias, leaky_slope, interpret):
    out, lse = _fwd_call(col_index, graph_id, dst_row, masks, theta_src,
                         theta_dst, h_src, edge_bias, leaky_slope, interpret)
    res = (col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
           edge_bias, out, lse)
    return out, res


def _multigraph_bwd(leaky_slope, interpret, res, g):
    (col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
     edge_bias, out, lse) = res
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    nblk = ns_pad // B
    rd = theta_dst.shape[1] // B

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dths_blk, dhs_blk, dthd_units = _bwd_call(
        col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
        edge_bias, g, lse, delta, leaky_slope, interpret,
    )

    # GSF-like scatter of the per-(unit, slot) partials onto the shared
    # src vertex space.  Padding slots (col < 0) carry exact zeros (the
    # kernel writes them), but mask them anyway so their block-0 landing
    # spot stays clean.
    flat_col = col_index.reshape(U * W)
    live_blk = flat_col >= 0
    col_safe = jnp.maximum(flat_col, 0)
    gid_blk = jnp.repeat(graph_id, W)

    dths_blk = jnp.where(live_blk[:, None, None], dths_blk.reshape(U * W, B, H), 0.0)
    d_theta_src = jax.ops.segment_sum(
        dths_blk, gid_blk * nblk + col_safe, num_segments=G * nblk
    ).reshape(G, ns_pad, H)

    dhs_blk = jnp.where(
        live_blk[:, None, None, None], dhs_blk.reshape(U * W, B, H, Dh), 0.0
    )
    d_h_src = jax.ops.segment_sum(
        dhs_blk, col_safe, num_segments=nblk
    ).reshape(ns_pad, H, Dh)

    d_theta_dst = (
        jnp.zeros((G, rd, B, H), jnp.float32)
        .at[graph_id, dst_row]
        .add(dthd_units.reshape(U, B, H))
        .reshape(G, rd * B, H)
    )
    # bias enters every logit additively: its gradient is the total dpre
    # mass per graph, already summed over dst inside dths_blk.
    d_bias = jax.ops.segment_sum(dths_blk.sum(axis=1), gid_blk, num_segments=G)

    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (
        f0(col_index), f0(graph_id), f0(dst_row), f0(masks),
        d_theta_src.astype(theta_src.dtype),
        d_theta_dst.astype(theta_dst.dtype),
        d_h_src.astype(h_src.dtype),
        d_bias.astype(edge_bias.dtype),
    )


_multigraph.defvjp(_multigraph_fwd, _multigraph_bwd)


@functools.partial(jax.jit, static_argnames=("leaky_slope", "interpret"))
def seg_gat_agg_multigraph(
    col_index: jnp.ndarray,  # int32 [U, W]  src block columns (-1 pad, unique/row)
    graph_id: jnp.ndarray,   # int32 [U]
    dst_row: jnp.ndarray,    # int32 [U]     dst block row within the graph
    masks: jnp.ndarray,      # bool  [U, W, B, B]
    theta_src: jnp.ndarray,  # f32   [G, Ns_pad, H]
    theta_dst: jnp.ndarray,  # f32   [G, Nd_pad, H]
    h_src: jnp.ndarray,      # f32   [Ns_pad, H, Dh] (shared across graphs)
    edge_bias: jnp.ndarray | None = None,  # [G, H]
    *,
    leaky_slope: float = 0.2,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns per-unit aggregates [U*B, H, Dh] (caller scatters by
    (graph_id, dst_row) — disjoint by construction).  Differentiable wrt
    theta_src / theta_dst / h_src / edge_bias via a fused Pallas backward."""
    G, _, H = theta_src.shape
    if edge_bias is None:
        edge_bias = jnp.zeros((G, H), jnp.float32)
    edge_bias = jnp.asarray(edge_bias, jnp.float32)
    return _multigraph(
        col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
        edge_bias, float(leaky_slope), bool(interpret),
    )
