"""Stage-fusion megakernel: fused FP+NA forward and backward (Pallas TPU).

Paper Alg. 2 bound-aware stage fusion, executed instead of modeled: the
kernel streams **raw** source-feature tiles from HBM, projects them
on-chip against a scalar-prefetched per-graph weight table (``W[g]`` via
the ``wsel`` graph->table map), contracts the projected tile with
a_src/a_dst into attention coefficients while it is VMEM-resident (the
``fused_fp_coeff`` tile-matmul pattern), and feeds it straight into the
online-softmax aggregation of ``seg_gat_agg_multigraph`` — projected
features never round-trip through HBM.

Work units are the multigraph kernel's (graph, dst-block-row) pairs,
grid (U, W) with W the sequential block-slot sweep.  While unit/slot
(u, w) runs its projection matmul on the MXU, the Pallas grid/BlockSpec
pipeline is already fetching slot (u, w+1)'s raw-feature tile (and, at a
unit boundary, the next graph's weight table) from HBM — compute-bound FP
of the current tile overlapped with the memory-bound feature fetch of the
next, which is exactly the paper's FP/NA overlap (DESIGN.md §10).  The
dst tile of a unit is projected once at w == 0 and its theta_dst kept in
VMEM scratch for the whole sweep.

The backward is one fused launch too: it *recomputes* the projection
(flash-attention style recompute-p from the lse residual, extended one
stage earlier to the FP matmul) and emits

  * per-(unit, slot) projection-space src gradients ``dhs`` and per-unit
    dst gradients ``dhd`` — the chain into dW[g]/db[g]/dx happens
    *outside* the kernel via per-weight-table segment sums + two einsums.
    The ISSUE sketch accumulates dW[g] in VMEM scratch across the
    sequential axis; that is only safe when all units of a table are
    contiguous in the grid, which the multilane plan does not guarantee
    (lanes interleave graphs), and Pallas TPU cannot revisit an output
    block in non-consecutive grid steps.  The segment-sum scatter is the
    same trick the multigraph backward already uses for d_theta_src.
  * per-unit d_theta_dst (VMEM-scratch accumulated over W) and per-unit
    d_a_src / d_a_dst partials, scattered per graph outside.

``seg_gat_agg_fused_fp`` carries a ``jax.custom_vjp``; HAN training with
``NABackend.FUSED_FP`` runs one forward and one backward launch per layer
with no materialized h'.

The weight table rides in whole (``Din`` untiled): one (Din, H*Dh) block
per table.  For the repo's HGNN widths (Din up to a few thousand) that is
well inside VMEM; K-tiling the projection would force the softmax state
machine to nest under a reduction axis for no measured benefit yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(
    # scalar prefetch
    col_ref,    # int32 [U, W]
    gid_ref,    # int32 [U]
    row_ref,    # int32 [U]
    wsel_ref,   # int32 [G]   graph -> weight-table row
    bias_ref,   # f32   [G, H]
    # inputs
    mask_ref,   # bool [1, 1, B, B]
    xd_ref,     # [B, Din]      raw dst tile (row_ref-indexed)
    xs_ref,     # [B, Din]      raw src tile (col-indexed)
    w_ref,      # [1, Din, HDh] weight table of the unit's graph
    b_ref,      # [1, 1, HDh]
    asrc_ref,   # [1, H, Dh]
    adst_ref,   # [1, H, Dh]
    # outputs
    out_ref,    # [B, HDh]
    lse_ref,    # f32 [B, H]
    # scratch
    acc_ref,    # f32 [B, HDh]
    m_ref,      # f32 [B, H]
    l_ref,      # f32 [B, H]
    thd_ref,    # f32 [B, H]   dst coefficients, computed once per unit
    *,
    heads: int,
    head_dim: int,
    leaky_slope: float,
):
    u = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    wmat = w_ref[0].astype(jnp.float32)  # [Din, HDh]
    bvec = b_ref[0].astype(jnp.float32)  # [1, HDh]

    @pl.when(w == 0)
    def _init():
        # FP of the unit's dst tile, once per unit — theta_dst stays
        # VMEM-resident for the whole W sweep (amortized over the slots).
        hd = jnp.dot(
            xd_ref[...].astype(jnp.float32), wmat,
            preferred_element_type=jnp.float32,
        ) + bvec
        for hh in range(heads):
            seg = hd[:, hh * head_dim : (hh + 1) * head_dim]
            thd_ref[:, hh] = jnp.dot(
                seg, adst_ref[0, hh].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col = col_ref[u, w]
    live = jnp.logical_and(mask_ref[0, 0], col >= 0)
    # FP of the current src tile — on-chip, straight off the raw fetch
    hs = jnp.dot(
        xs_ref[...].astype(jnp.float32), wmat,
        preferred_element_type=jnp.float32,
    ) + bvec  # [B, HDh]
    for hh in range(heads):
        sl = slice(hh * head_dim, (hh + 1) * head_dim)
        seg = hs[:, sl]
        ths = jnp.dot(
            seg, asrc_ref[0, hh].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # [B]
        pre = thd_ref[:, hh][:, None] + ths[None, :] + bias_ref[gid_ref[u], hh]
        logits = jnp.where(pre >= 0, pre, leaky_slope * pre)
        logits = jnp.where(live, logits, NEG_INF)
        m_prev = m_ref[:, hh]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        scale = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(logits - m_new[:, None]), 0.0)
        l_ref[:, hh] = l_ref[:, hh] * scale + jnp.sum(p, axis=1)
        acc_ref[:, sl] = acc_ref[:, sl] * scale[:, None] + jnp.dot(
            p, seg, preferred_element_type=jnp.float32
        )
        m_ref[:, hh] = m_new

    @pl.when(w == nw - 1)
    def _finalize():
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            out_ref[:, sl] = (
                acc_ref[:, sl]
                / jnp.maximum(l_ref[:, hh], 1e-9)[:, None]
            ).astype(out_ref.dtype)
        # lse of a fully-masked row degenerates to ~NEG_INF; the backward
        # masks those positions with `live` before any use.
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _bwd_kernel(
    # scalar prefetch
    col_ref, gid_ref, row_ref, wsel_ref, bias_ref,
    # inputs (forward operands + residuals)
    mask_ref, xd_ref, xs_ref, w_ref, b_ref, asrc_ref, adst_ref,
    gout_ref,   # [B, HDh]  cotangent of the per-unit output
    lse_ref,    # f32 [B, H]
    delta_ref,  # f32 [B, H]  sum_f g_out * out (flash-attention delta)
    # outputs
    dhs_ref,    # f32 [1, 1, B, HDh]  per-(unit, slot) src projection grad
    dhd_ref,    # f32 [1, B, HDh]     per-unit dst projection grad
    dthd_ref,   # f32 [B, H]          per-unit dst-coeff gradient
    das_ref,    # f32 [1, H, Dh]      per-unit d a_src partial
    dad_ref,    # f32 [1, H, Dh]      per-unit d a_dst partial
    # scratch
    thd_scr,    # f32 [B, H]
    hd_scr,     # f32 [B, HDh]  recomputed dst projection (kept for da_dst)
    dthd_acc,   # f32 [B, H]
    das_acc,    # f32 [H, Dh]
    *,
    heads: int,
    head_dim: int,
    leaky_slope: float,
):
    u = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    wmat = w_ref[0].astype(jnp.float32)
    bvec = b_ref[0].astype(jnp.float32)

    @pl.when(w == 0)
    def _init():
        hd = jnp.dot(
            xd_ref[...].astype(jnp.float32), wmat,
            preferred_element_type=jnp.float32,
        ) + bvec
        hd_scr[...] = hd
        for hh in range(heads):
            seg = hd[:, hh * head_dim : (hh + 1) * head_dim]
            thd_scr[:, hh] = jnp.dot(
                seg, adst_ref[0, hh].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
        dthd_acc[...] = jnp.zeros_like(dthd_acc)
        das_acc[...] = jnp.zeros_like(das_acc)

    col = col_ref[u, w]
    live = jnp.logical_and(mask_ref[0, 0], col >= 0)  # [B(dst), B(src)]
    # recompute the src projection (the FP stage) and, from lse, the
    # attention probabilities — nothing was materialized in the forward
    hs = jnp.dot(
        xs_ref[...].astype(jnp.float32), wmat,
        preferred_element_type=jnp.float32,
    ) + bvec
    g_out = gout_ref[...].astype(jnp.float32)  # [B, HDh]
    for hh in range(heads):
        sl = slice(hh * head_dim, (hh + 1) * head_dim)
        seg = hs[:, sl]  # [Bs, Dh]
        ths = jnp.dot(
            seg, asrc_ref[0, hh].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        pre = thd_scr[:, hh][:, None] + ths[None, :] + bias_ref[gid_ref[u], hh]
        logits = jnp.where(pre >= 0, pre, leaky_slope * pre)
        p = jnp.where(live, jnp.exp(logits - lse_ref[:, hh][:, None]), 0.0)
        gseg = g_out[:, sl]  # [Bd, Dh]
        dp = jnp.dot(gseg, seg.T, preferred_element_type=jnp.float32)  # [Bd, Bs]
        dlogit = p * (dp - delta_ref[:, hh][:, None])  # softmax backward
        dpre = jnp.where(pre >= 0, dlogit, leaky_slope * dlogit)
        dths_vec = jnp.sum(dpre, axis=0)  # [Bs]
        dthd_acc[:, hh] += jnp.sum(dpre, axis=1)
        # src projection grad: aggregation term + coefficient term
        dhs_ref[0, 0, :, sl] = jnp.dot(
            p.T, gseg, preferred_element_type=jnp.float32
        ) + dths_vec[:, None] * asrc_ref[0, hh].astype(jnp.float32)[None, :]
        das_acc[hh, :] += jnp.dot(
            dths_vec[None, :], seg, preferred_element_type=jnp.float32
        )[0]

    @pl.when(w == nw - 1)
    def _finalize():
        dthd_ref[...] = dthd_acc[...]
        das_ref[0] = das_acc[...]
        hd = hd_scr[...]
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            dad_ref[0, hh, :] = jnp.dot(
                dthd_acc[:, hh][None, :], hd[:, sl],
                preferred_element_type=jnp.float32,
            )[0]
            # dst projection grad: theta_dst is hd @ a_dst, so d hd is rank-1
            dhd_ref[0, :, sl] = (
                dthd_acc[:, hh][:, None]
                * adst_ref[0, hh].astype(jnp.float32)[None, :]
            )


def _common_maps():
    def mask_map(u, w, col, gid, row, wsel, bias):
        return (u, w, 0, 0)

    def xd_map(u, w, col, gid, row, wsel, bias):
        return (row[u], 0)

    def xs_map(u, w, col, gid, row, wsel, bias):
        return (jnp.maximum(col[u, w], 0), 0)

    def w_map(u, w, col, gid, row, wsel, bias):
        return (wsel[gid[u]], 0, 0)

    def b_map(u, w, col, gid, row, wsel, bias):
        return (wsel[gid[u]], 0, 0)

    def a_map(u, w, col, gid, row, wsel, bias):
        return (gid[u], 0, 0)

    return mask_map, xd_map, xs_map, w_map, b_map, a_map


def _in_specs(B, din, hdh, heads, head_dim):
    mask_map, xd_map, xs_map, w_map, b_map, a_map = _common_maps()
    return [
        pl.BlockSpec((1, 1, B, B), mask_map),
        pl.BlockSpec((B, din), xd_map),
        pl.BlockSpec((B, din), xs_map),
        pl.BlockSpec((1, din, hdh), w_map),
        # [T, 1, HDh]: a (1, HDh) block of a [T, HDh] table is not a legal
        # TPU tile for T > 1 (second-minor dim neither 8-aligned nor whole)
        pl.BlockSpec((1, 1, hdh), b_map),
        pl.BlockSpec((1, heads, head_dim), a_map),
        pl.BlockSpec((1, heads, head_dim), a_map),
    ]


def _fwd_call(col_index, graph_id, dst_row, wsel, masks, x, w, b,
              a_src, a_dst, edge_bias, leaky_slope, interpret):
    U, W = col_index.shape
    B = masks.shape[-1]
    G, heads, head_dim = a_src.shape
    din = x.shape[-1]
    hdh = heads * head_dim

    def out_map(u, w_, col, gid, row, wsel_, bias):
        return (u, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(U, W),
        in_specs=_in_specs(B, din, hdh, heads, head_dim),
        out_specs=[
            pl.BlockSpec((B, hdh), out_map),
            pl.BlockSpec((B, heads), out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, hdh), jnp.float32),
            pltpu.VMEM((B, heads), jnp.float32),
            pltpu.VMEM((B, heads), jnp.float32),
            pltpu.VMEM((B, heads), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, heads=heads, head_dim=head_dim, leaky_slope=leaky_slope
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((U * B, hdh), x.dtype),
            jax.ShapeDtypeStruct((U * B, heads), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="seg_gat_agg_fused_fp",
    )(col_index, graph_id, dst_row, wsel, edge_bias, masks, x, x, w,
      b[:, None, :], a_src, a_dst)


def _bwd_call(col_index, graph_id, dst_row, wsel, masks, x, w, b, a_src,
              a_dst, edge_bias, g_out, lse, delta, leaky_slope, interpret):
    U, W = col_index.shape
    B = masks.shape[-1]
    G, heads, head_dim = a_src.shape
    din = x.shape[-1]
    hdh = heads * head_dim

    def unit_map(u, w_, col, gid, row, wsel_, bias):
        return (u, 0)

    def dhs_map(u, w_, col, gid, row, wsel_, bias):
        return (u, w_, 0, 0)

    def unit3_map(u, w_, col, gid, row, wsel_, bias):
        return (u, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(U, W),
        in_specs=_in_specs(B, din, hdh, heads, head_dim) + [
            pl.BlockSpec((B, hdh), unit_map),
            pl.BlockSpec((B, heads), unit_map),
            pl.BlockSpec((B, heads), unit_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, B, hdh), dhs_map),
            pl.BlockSpec((1, B, hdh), unit3_map),
            pl.BlockSpec((B, heads), unit_map),
            pl.BlockSpec((1, heads, head_dim), unit3_map),
            pl.BlockSpec((1, heads, head_dim), unit3_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, heads), jnp.float32),
            pltpu.VMEM((B, hdh), jnp.float32),
            pltpu.VMEM((B, heads), jnp.float32),
            pltpu.VMEM((heads, head_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, heads=heads, head_dim=head_dim, leaky_slope=leaky_slope
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((U, W, B, hdh), jnp.float32),
            jax.ShapeDtypeStruct((U, B, hdh), jnp.float32),
            jax.ShapeDtypeStruct((U * B, heads), jnp.float32),
            jax.ShapeDtypeStruct((U, heads, head_dim), jnp.float32),
            jax.ShapeDtypeStruct((U, heads, head_dim), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="seg_gat_agg_fused_fp_bwd",
    )(col_index, graph_id, dst_row, wsel, edge_bias, masks, x, x, w,
      b[:, None, :], a_src, a_dst, g_out, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12))
def _fused(col_index, graph_id, dst_row, wsel, masks, x, w, b, a_src,
           a_dst, edge_bias, leaky_slope, interpret):
    out, _ = _fwd_call(col_index, graph_id, dst_row, wsel, masks, x, w, b,
                       a_src, a_dst, edge_bias, leaky_slope, interpret)
    U = col_index.shape[0]
    B = masks.shape[-1]
    heads, head_dim = a_src.shape[1:]
    return out.reshape(U * B, heads, head_dim)


def _fused_fwd(col_index, graph_id, dst_row, wsel, masks, x, w, b, a_src,
               a_dst, edge_bias, leaky_slope, interpret):
    out_flat, lse = _fwd_call(col_index, graph_id, dst_row, wsel, masks, x,
                              w, b, a_src, a_dst, edge_bias, leaky_slope,
                              interpret)
    U = col_index.shape[0]
    B = masks.shape[-1]
    heads, head_dim = a_src.shape[1:]
    out = out_flat.reshape(U * B, heads, head_dim)
    res = (col_index, graph_id, dst_row, wsel, masks, x, w, b, a_src, a_dst,
           edge_bias, out, lse)
    return out, res


def _fused_bwd(leaky_slope, interpret, res, g):
    (col_index, graph_id, dst_row, wsel, masks, x, w, b, a_src, a_dst,
     edge_bias, out, lse) = res
    U, W = col_index.shape
    B = masks.shape[-1]
    G, heads, head_dim = a_src.shape
    T = w.shape[0]
    n_pad = x.shape[0]
    hdh = heads * head_dim
    nblk = n_pad // B

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    g_flat = g.reshape(U * B, hdh)
    dhs_blk, dhd_units, dthd_units, das_units, dad_units = _bwd_call(
        col_index, graph_id, dst_row, wsel, masks, x, w, b, a_src, a_dst,
        edge_bias, g_flat, lse, delta, leaky_slope, interpret,
    )

    # Scatter the projection-space gradients onto the shared vertex space,
    # segmented per weight table: src-side per-slot partials and dst-side
    # per-unit partials share one segment sum.  Padding slots (col < 0)
    # carry exact zeros (p = 0), but mask them anyway so their block-0
    # landing spot stays clean.
    flat_col = col_index.reshape(U * W)
    live_blk = flat_col >= 0
    col_safe = jnp.maximum(flat_col, 0)
    gid_blk = jnp.repeat(graph_id, W)
    dhs_blk = jnp.where(
        live_blk[:, None, None], dhs_blk.reshape(U * W, B, hdh), 0.0
    )
    keys = jnp.concatenate([
        wsel[gid_blk] * nblk + col_safe,
        wsel[graph_id] * nblk + dst_row,
    ])
    vals = jnp.concatenate([dhs_blk, dhd_units], axis=0)
    dh_t = jax.ops.segment_sum(
        vals, keys, num_segments=T * nblk
    ).reshape(T, n_pad, hdh)

    # chain h = x @ W[t] + b[t] outside the kernel (see module docstring)
    xf = x.astype(jnp.float32)
    d_w = jnp.einsum("nd,tnk->tdk", xf, dh_t)
    d_b = dh_t.sum(axis=1)
    d_x = jnp.einsum("tnk,tdk->nd", dh_t, w.astype(jnp.float32))
    d_a_src = jax.ops.segment_sum(das_units, graph_id, num_segments=G)
    d_a_dst = jax.ops.segment_sum(dad_units, graph_id, num_segments=G)
    # bias enters every logit additively: its gradient is the total dpre
    # mass per graph, already summed over src inside dthd.
    d_bias = jax.ops.segment_sum(
        dthd_units.reshape(U, B, heads).sum(axis=1), graph_id, num_segments=G
    )

    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (
        f0(col_index), f0(graph_id), f0(dst_row), f0(wsel), f0(masks),
        d_x.astype(x.dtype),
        d_w.astype(w.dtype),
        d_b.astype(b.dtype),
        d_a_src.astype(a_src.dtype),
        d_a_dst.astype(a_dst.dtype),
        d_bias.astype(edge_bias.dtype),
    )


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.jit, static_argnames=("leaky_slope", "interpret"))
def seg_gat_agg_fused_fp(
    col_index: jnp.ndarray,  # int32 [U, W]  src block columns (-1 pad, unique/row)
    graph_id: jnp.ndarray,   # int32 [U]
    dst_row: jnp.ndarray,    # int32 [U]     dst block row within the graph
    wsel: jnp.ndarray,       # int32 [G]     graph -> weight-table row
    masks: jnp.ndarray,      # bool  [U, W, B, B]
    x: jnp.ndarray,          # [N_pad, Din]  raw features, shared src/dst space
    w: jnp.ndarray,          # [T, Din, H*Dh] (or [Din, H*Dh] shared)
    b: jnp.ndarray,          # [T, H*Dh]      (or [H*Dh] shared)
    a_src: jnp.ndarray,      # [G, H, Dh]
    a_dst: jnp.ndarray,      # [G, H, Dh]
    edge_bias: jnp.ndarray | None = None,  # [G, H]
    *,
    leaky_slope: float = 0.2,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused FP+NA: returns per-unit aggregates [U*B, H, Dh] (same contract
    as ``seg_gat_agg_multigraph`` — caller scatters by (graph_id, dst_row)).
    ``x`` must cover every block index in ``col_index``/``dst_row``
    (N_pad = n_blocks * B; src and dst share the vertex space).
    Differentiable wrt x / w / b / a_src / a_dst / edge_bias via a fused
    Pallas backward that recomputes the projection."""
    G, heads, _ = a_src.shape
    if w.ndim == 2:
        w = w[None]
    if b.ndim == 1:
        b = b[None]
    if edge_bias is None:
        edge_bias = jnp.zeros((G, heads), jnp.float32)
    edge_bias = jnp.asarray(edge_bias, jnp.float32)
    return _fused(
        col_index, graph_id, dst_row, jnp.asarray(wsel, jnp.int32), masks,
        x, w, b, a_src, a_dst, edge_bias, float(leaky_slope), bool(interpret),
    )


def fused_fp_na_reference(
    col_index, graph_id, dst_row, wsel, masks, x, w, b, a_src, a_dst,
    edge_bias=None, *, leaky_slope: float = 0.2,
) -> jnp.ndarray:
    """Pure-jnp oracle for the fused kernel (materialize-then-NA, exact
    softmax).  Differentiable by plain autodiff — the gradcheck target."""
    U, W = col_index.shape
    B = masks.shape[-1]
    G, heads, head_dim = a_src.shape
    if w.ndim == 2:
        w = w[None]
    if b.ndim == 1:
        b = b[None]
    if edge_bias is None:
        edge_bias = jnp.zeros((G, heads), jnp.float32)
    edge_bias = jnp.asarray(edge_bias, jnp.float32)
    n = x.shape[0]
    h_all = jnp.einsum(
        "nd,tdk->tnk", x.astype(jnp.float32), w.astype(jnp.float32)
    ) + b.astype(jnp.float32)[:, None, :]
    hg = h_all[wsel].reshape(G, n, heads, head_dim)  # per-graph projections
    ths = jnp.einsum("gnhd,ghd->gnh", hg, a_src.astype(jnp.float32))
    thd = jnp.einsum("gnhd,ghd->gnh", hg, a_dst.astype(jnp.float32))

    def one(cols, mrow, gi, r):
        td = jax.lax.dynamic_slice(thd, (gi, r * B, 0), (1, B, heads))[0]
        c_safe = jnp.maximum(cols, 0)
        idx = (c_safe[:, None] * B + jnp.arange(B)[None, :]).reshape(-1)
        ts = ths[gi][idx]   # [W*B, H]
        hs = hg[gi][idx]    # [W*B, H, Dh]
        live = (
            mrow.transpose(1, 0, 2).reshape(B, W * B)
            & jnp.repeat(cols >= 0, B)[None, :]
        )
        pre = td[:, None, :] + ts[None, :, :] + edge_bias[gi][None, None, :]
        logits = jnp.where(pre >= 0, pre, leaky_slope * pre)
        logits = jnp.where(live[:, :, None], logits, NEG_INF)
        m = jnp.max(logits, axis=1, keepdims=True)
        p = jnp.where(live[:, :, None], jnp.exp(logits - m), 0.0)
        agg = jnp.einsum("bsh,shf->bhf", p, hs)
        return agg / jnp.maximum(p.sum(axis=1), 1e-9)[:, :, None]

    out = jax.vmap(one)(col_index, masks, graph_id, dst_row)  # [U, B, H, Dh]
    return out.reshape(U * B, heads, head_dim).astype(x.dtype)
