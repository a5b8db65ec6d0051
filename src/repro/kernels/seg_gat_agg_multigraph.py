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

Two static options serve S-HGN's layers over the union graph; with both
off the launch is the one HAN's relations run through:

* typed tiles — ``masks`` holds int8 edge types (type + 1, 0 = no edge)
  in place of booleans, and ``edge_bias`` is a [T, H] table looked up per
  pair from the tile, in place of one bias per graph.  One unit's slots
  span every type, so the softmax over a dst vertex's in-edges is joint
  over types.  The backward sums the table's gradient per type in VMEM.
* the attention residual — ``attn_prev`` (an :class:`Attention`: a
  previous layer's coefficients, bias table and per-row lse, all per
  vertex or per type) with a static ``beta``.  The previous layer's
  probabilities are rebuilt in-tile, p_prev = exp(logits_prev - lse_prev),
  so no per-edge state is stored, and the output is the aggregate of the
  mixed attention, (1 - beta) sum p h + beta sum p_prev h.  p_prev takes no
  gradient (its operands get zeros); h_src's gradient counts both parts.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

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


class Attention(NamedTuple):
    """What a layer's attention is rebuilt from in-tile: its coefficients,
    its [T, H] bias table and the per-row log-sum-exp its forward returns
    (units in plan order, so the next layer must run the same plan)."""

    theta_src: jnp.ndarray  # [G, Ns_pad, H]
    theta_dst: jnp.ndarray  # [G, Nd_pad, H]
    bias: jnp.ndarray       # f32 [T, H]
    lse: jnp.ndarray        # f32 [U*B, H]


class _Opts(NamedTuple):
    leaky_slope: float
    interpret: bool
    beta: float | None  # attention-residual weight; None = no residual
    precision: jax.lax.Precision | None = None  # of the bodies' dots; None: Mosaic's default


def _tile(mask_ref, n_types):
    """(live [B, B], type tile or None) of the slot's mask block."""
    if not n_types:
        return mask_ref[0, 0], None
    tile = mask_ref[0, 0].astype(jnp.int32)
    return tile != 0, tile


def _bias(bias_ref, gid, hh, tile, n_types):
    """Head hh's logit bias: the graph's scalar, or per pair from the tile."""
    if not n_types:
        return bias_ref[gid, hh]
    bias = jnp.zeros(tile.shape, jnp.float32)
    for t in range(n_types):
        bias = jnp.where(tile == t + 1, bias_ref[t, hh], bias)
    return bias


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
    bias_ref,   # f32   [G, H], or [T, H] with typed tiles
    fslot_ref,  # int32 [U*W]  filled slot (index maps only)
    fcol_ref,   # int32 [U*W]  filled col  (index maps only)
    *refs,
    heads: int,
    head_dim: int,
    leaky_slope: float,
    n_types: int,
    beta: float | None,
    precision: jax.lax.Precision | None,
):
    residual = beta is not None
    it = iter(refs)
    pbias_ref = next(it) if residual else None  # scalar prefetch: f32 [T, H]
    # inputs: mask bool/int8 [1, 1, B, B]; thd [1, B, H] dst coefficients of
    # the unit's graph; ths [1, H, B] src coefficients of the slot's block;
    # hs [B, H*Dh] shared source features
    mask_ref, thd_ref, ths_ref, hs_ref = (next(it) for _ in range(4))
    if residual:  # the previous layer's thd, ths and lse [B, H]
        pthd_ref, pths_ref, plse_ref = (next(it) for _ in range(3))
    out_ref = next(it)                          # [B, H*Dh]
    cur_ref = next(it) if residual else None    # f32 [B, H*Dh] unmixed output
    lse_ref = next(it)                          # f32 [B, H]
    acc_ref, m_ref, l_ref = (next(it) for _ in range(3))  # scratch
    pacc_ref = next(it) if residual else None   # f32 [B, H*Dh]

    u = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if residual:
            pacc_ref[...] = jnp.zeros_like(pacc_ref)

    @pl.when(col_ref[u, w] >= 0)
    def _body():
        live, tile = _tile(mask_ref, n_types)
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            _, logits = _logits(
                thd_ref, ths_ref, _bias(bias_ref, gid_ref[u], hh, tile, n_types), hh, leaky_slope
            )
            logits = jnp.where(live, logits, NEG_INF)
            m_prev = m_ref[:, hh : hh + 1]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
            scale = jnp.exp(m_prev - m_new)
            p = jnp.where(live, jnp.exp(logits - m_new), 0.0)
            l_ref[:, hh : hh + 1] = l_ref[:, hh : hh + 1] * scale + jnp.sum(
                p, axis=1, keepdims=True
            )
            acc_ref[:, sl] = acc_ref[:, sl] * scale + jnp.dot(
                p, hs_ref[:, sl].astype(jnp.float32), preferred_element_type=jnp.float32,
                precision=precision,
            )
            m_ref[:, hh : hh + 1] = m_new
            if residual:  # the previous layer's probabilities, exact from its lse
                _, plogits = _logits(
                    pthd_ref, pths_ref, _bias(pbias_ref, gid_ref[u], hh, tile, n_types),
                    hh, leaky_slope,
                )
                pp = jnp.where(live, jnp.exp(plogits - plse_ref[:, hh : hh + 1]), 0.0)
                pacc_ref[:, sl] += jnp.dot(
                    pp, hs_ref[:, sl].astype(jnp.float32), preferred_element_type=jnp.float32,
                    precision=precision,
                )

    @pl.when(w == nw - 1)
    def _finalize():
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            cur = acc_ref[:, sl] / jnp.maximum(l_ref[:, hh : hh + 1], 1e-9)
            if residual:
                cur_ref[:, sl] = cur
                cur = (1.0 - beta) * cur + beta * pacc_ref[:, sl]
            out_ref[:, sl] = cur.astype(out_ref.dtype)
        # lse of a fully-masked row degenerates to ~NEG_INF; the backward
        # masks those positions with `live` before any use.
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _bwd_kernel(
    # scalar prefetch
    col_ref,    # int32 [U, W]
    gid_ref,    # int32 [U]
    row_ref,    # int32 [U]
    bias_ref,   # f32   [G, H], or [T, H] with typed tiles
    fslot_ref,  # int32 [U*W]  filled slot (index maps only)
    fcol_ref,   # int32 [U*W]  filled col  (index maps only)
    *refs,
    heads: int,
    head_dim: int,
    leaky_slope: float,
    n_types: int,
    beta: float | None,
    precision: jax.lax.Precision | None,
):
    residual = beta is not None
    it = iter(refs)
    pbias_ref = next(it) if residual else None  # scalar prefetch: f32 [T, H]
    # inputs as the forward's, then g_out [B, H*Dh] (cotangent of the
    # per-unit output), lse [B, H] (forward residual) and delta [B, H]
    # (sum_f g_out * unmixed out, flash-attention delta)
    mask_ref, thd_ref, ths_ref, hs_ref, gout_ref, lse_ref, delta_ref = (
        next(it) for _ in range(7)
    )
    if residual:
        pthd_ref, pths_ref, plse_ref = (next(it) for _ in range(3))
    # outputs: per-(unit, slot) d_theta_src [1, 1, H, B] and d_h_src
    # [1, 1, B, H*Dh] partials, per-unit d_theta_dst [B, H], and with typed
    # tiles the per-unit bias-table partial [1, T*H, B] (row t*H + h)
    dths_ref, dhs_ref, dthd_ref = (next(it) for _ in range(3))
    dtb_ref = next(it) if n_types else None
    dthd_acc_ref = next(it)                            # scratch f32 [B, H]
    dtb_acc_ref = next(it) if n_types else None        # scratch f32 [T*H, B]

    u = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)

    @pl.when(w == 0)
    def _init():
        dthd_acc_ref[...] = jnp.zeros_like(dthd_acc_ref)
        if n_types:
            dtb_acc_ref[...] = jnp.zeros_like(dtb_acc_ref)

    live_slot = col_ref[u, w] >= 0

    @pl.when(live_slot)
    def _body():
        live, tile = _tile(mask_ref, n_types)  # [B(dst), B(src)]
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            pre, logits = _logits(
                thd_ref, ths_ref, _bias(bias_ref, gid_ref[u], hh, tile, n_types), hh, leaky_slope
            )
            # recompute-p: attention probabilities from the lse residual
            p = jnp.where(live, jnp.exp(logits - lse_ref[:, hh : hh + 1]), 0.0)
            g_out = gout_ref[:, sl].astype(jnp.float32)  # [B, Dh]
            hs = hs_ref[:, sl].astype(jnp.float32)       # [B, Dh]
            dp = jax.lax.dot_general(                    # g_out @ hs.T  [Bd, Bs]
                g_out, hs, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32,
            )
            dlogit = p * (dp - delta_ref[:, hh : hh + 1])  # softmax backward
            if residual:
                dlogit = (1.0 - beta) * dlogit
            dpre = jnp.where(pre >= 0, dlogit, leaky_slope * dlogit)
            dths_ref[0, 0, hh : hh + 1, :] = jnp.sum(dpre, axis=0, keepdims=True)
            weights = p
            if residual:
                _, plogits = _logits(
                    pthd_ref, pths_ref, _bias(pbias_ref, gid_ref[u], hh, tile, n_types),
                    hh, leaky_slope,
                )
                pp = jnp.where(live, jnp.exp(plogits - plse_ref[:, hh : hh + 1]), 0.0)
                weights = (1.0 - beta) * p + beta * pp
            dhs_ref[0, 0, :, sl] = jax.lax.dot_general(  # weights.T @ g_out  [Bs, Dh]
                weights, g_out, (((0,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32,
            )
            dthd_acc_ref[:, hh : hh + 1] += jnp.sum(dpre, axis=1, keepdims=True)
            for t in range(n_types):
                r = t * heads + hh
                dtb_acc_ref[r : r + 1, :] += jnp.sum(
                    jnp.where(tile == t + 1, dpre, 0.0), axis=0, keepdims=True
                )

    # a dead slot's partial blocks are still written back: zeros, not stale
    @pl.when(jnp.logical_not(live_slot))
    def _dead():
        dths_ref[...] = jnp.zeros_like(dths_ref)
        dhs_ref[...] = jnp.zeros_like(dhs_ref)

    @pl.when(w == nw - 1)
    def _finalize():
        dthd_ref[...] = dthd_acc_ref[...]
        if n_types:
            dtb_ref[0] = dtb_acc_ref[...]


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


# Index maps take (u, w, *scalar-prefetch refs): col, gid, row, bias,
# fslot, fcol, and the previous layer's bias table with the residual.
def _unit_map(u, w, *_):
    return (u, 0)


def _unit_map3(u, w, *_):
    return (u, 0, 0)


def _specs(B, H, hdh, W):
    """BlockSpecs by operand: masks (fed as [U*W, 1, B, B]), dst and src
    coefficients, source features."""

    def mask_map(u, w, col, gid, row, bias, fslot, fcol, *_):
        return (fslot[u * W + w], 0, 0, 0)

    def thd_map(u, w, col, gid, row, *_):
        return (gid[u], row[u], 0)

    def ths_map(u, w, col, gid, row, bias, fslot, fcol, *_):
        return (gid[u], 0, fcol[u * W + w])

    def hs_map(u, w, col, gid, row, bias, fslot, fcol, *_):
        return (fcol[u * W + w], 0)

    return (
        pl.BlockSpec((1, 1, B, B), mask_map),
        pl.BlockSpec((1, B, H), thd_map),
        pl.BlockSpec((1, H, B), ths_map),
        pl.BlockSpec((B, hdh), hs_map),
    )


def _n_types(masks, edge_bias):
    """0 for boolean masks, else the rows of the typed tiles' bias table."""
    return 0 if masks.dtype == jnp.bool_ else int(edge_bias.shape[0])


def _prefetch_and_inputs(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
                         h_src, edge_bias, attn_prev, B, W):
    """Scalar-prefetch operands, and the block inputs before the backward's
    own (the previous layer's thd/ths/lse follow them, see ``_prev``)."""
    U = col_index.shape[0]
    ns_pad = h_src.shape[0]
    prefetch = [col_index, graph_id, dst_row, edge_bias, *_fill_dead(col_index)]
    if attn_prev is not None:
        prefetch.append(attn_prev.bias)
    inputs = [masks.reshape(U * W, 1, B, B), theta_dst, theta_src.swapaxes(1, 2),
              h_src.reshape(ns_pad, -1)]
    return prefetch, inputs


def _prev(attn_prev, specs, B, H):
    """Block inputs and specs of the previous layer's attention."""
    return (
        [attn_prev.theta_dst, attn_prev.theta_src.swapaxes(1, 2), attn_prev.lse],
        [specs[1], specs[2], pl.BlockSpec((B, H), _unit_map)],
    )


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _fwd_call(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
              h_src, edge_bias, attn_prev, opts):
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    hdh = H * Dh
    residual = opts.beta is not None

    prefetch, inputs = _prefetch_and_inputs(
        col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src, edge_bias,
        attn_prev, B, W)
    specs = _specs(B, H, hdh, W)
    in_specs = list(specs)
    out_specs = [pl.BlockSpec((B, hdh), _unit_map)]
    out_shape = [jax.ShapeDtypeStruct((U * B, hdh), h_src.dtype)]
    scratch = [
        pltpu.VMEM((B, hdh), jnp.float32),
        pltpu.VMEM((B, H), jnp.float32),
        pltpu.VMEM((B, H), jnp.float32),
    ]
    if residual:
        more, more_specs = _prev(attn_prev, specs, B, H)
        inputs += more
        in_specs += more_specs
        out_specs.append(pl.BlockSpec((B, hdh), _unit_map))
        out_shape.append(jax.ShapeDtypeStruct((U * B, hdh), jnp.float32))
        scratch.append(pltpu.VMEM((B, hdh), jnp.float32))
    out_specs.append(pl.BlockSpec((B, H), _unit_map))
    out_shape.append(jax.ShapeDtypeStruct((U * B, H), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(U, W),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(
            _fwd_kernel, heads=H, head_dim=Dh, leaky_slope=opts.leaky_slope,
            n_types=_n_types(masks, edge_bias), beta=opts.beta, precision=opts.precision,
        ),
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        compiler_params=_compiler_params(),
        interpret=opts.interpret,
        name="seg_gat_agg_multigraph",
    )(*prefetch, *inputs)
    cur = outs[1].reshape(U * B, H, Dh) if residual else None
    return outs[0].reshape(U * B, H, Dh), cur, outs[-1]


def _bwd_call(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
              h_src, edge_bias, attn_prev, g_out, lse, delta, opts):
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    hdh = H * Dh
    n_types = _n_types(masks, edge_bias)

    def slot_map(u, w, *_):
        return (u, w, 0, 0)

    prefetch, inputs = _prefetch_and_inputs(
        col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src, edge_bias,
        attn_prev, B, W)
    specs = _specs(B, H, hdh, W)
    inputs += [g_out.reshape(U * B, hdh), lse, delta]
    in_specs = list(specs) + [
        pl.BlockSpec((B, hdh), _unit_map),
        pl.BlockSpec((B, H), _unit_map),
        pl.BlockSpec((B, H), _unit_map),
    ]
    if attn_prev is not None:
        more, more_specs = _prev(attn_prev, specs, B, H)
        inputs += more
        in_specs += more_specs
    out_specs = [
        pl.BlockSpec((1, 1, H, B), slot_map),
        pl.BlockSpec((1, 1, B, hdh), slot_map),
        pl.BlockSpec((B, H), _unit_map),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((U, W, H, B), jnp.float32),
        jax.ShapeDtypeStruct((U, W, B, hdh), jnp.float32),
        jax.ShapeDtypeStruct((U * B, H), jnp.float32),
    ]
    scratch = [pltpu.VMEM((B, H), jnp.float32)]
    if n_types:
        out_specs.append(pl.BlockSpec((1, n_types * H, B), _unit_map3))
        out_shape.append(jax.ShapeDtypeStruct((U, n_types * H, B), jnp.float32))
        scratch.append(pltpu.VMEM((n_types * H, B), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(U, W),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, heads=H, head_dim=Dh, leaky_slope=opts.leaky_slope,
            n_types=n_types, beta=opts.beta, precision=opts.precision,
        ),
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        compiler_params=_compiler_params(),
        interpret=opts.interpret,
        name="seg_gat_agg_multigraph_bwd",
    )(*prefetch, *inputs)
    dths, dhs, dthd = outs[:3]
    dtb = outs[3] if n_types else None
    return dths.swapaxes(2, 3), dhs.reshape(U, W, B, H, Dh), dthd, dtb


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _multigraph(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
                h_src, edge_bias, attn_prev, opts):
    out, _, lse = _fwd_call(col_index, graph_id, dst_row, masks, theta_src,
                            theta_dst, h_src, edge_bias, attn_prev, opts)
    return out, lse


def _multigraph_fwd(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
                    h_src, edge_bias, attn_prev, opts):
    out, cur, lse = _fwd_call(col_index, graph_id, dst_row, masks, theta_src,
                              theta_dst, h_src, edge_bias, attn_prev, opts)
    # the backward's delta needs the unmixed output
    res = (col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
           edge_bias, attn_prev, out if cur is None else cur, lse)
    return (out, lse), res


def _multigraph_bwd(opts, res, cts):
    (col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
     edge_bias, attn_prev, out, lse) = res
    g, _ = cts  # lse carries no gradient (the wrapper stops it)
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    nblk = ns_pad // B
    rd = theta_dst.shape[1] // B

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dths_blk, dhs_blk, dthd_units, dtb = _bwd_call(
        col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
        edge_bias, attn_prev, g, lse, delta, opts,
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
    if dtb is None:
        # bias enters every logit additively: its gradient is the total dpre
        # mass per graph, already summed over dst inside dths_blk.
        d_bias = jax.ops.segment_sum(dths_blk.sum(axis=1), gid_blk, num_segments=G)
    else:
        # typed tiles: the kernel summed dpre per (type, head) over dst
        d_bias = dtb.sum(axis=(0, 2)).reshape(edge_bias.shape)

    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (
        f0(col_index), f0(graph_id), f0(dst_row), f0(masks),
        d_theta_src.astype(theta_src.dtype),
        d_theta_dst.astype(theta_dst.dtype),
        d_h_src.astype(h_src.dtype),
        d_bias.astype(edge_bias.dtype),
        jax.tree_util.tree_map(jnp.zeros_like, attn_prev),  # p_prev: no gradient
    )


_multigraph.defvjp(_multigraph_fwd, _multigraph_bwd)


@functools.partial(
    jax.jit, static_argnames=("leaky_slope", "interpret", "beta", "return_lse", "precision")
)
def seg_gat_agg_multigraph(
    col_index: jnp.ndarray,  # int32 [U, W]  src block columns (-1 pad, unique/row)
    graph_id: jnp.ndarray,   # int32 [U]
    dst_row: jnp.ndarray,    # int32 [U]     dst block row within the graph
    masks: jnp.ndarray,      # bool [U, W, B, B], or int8 type tiles (type + 1)
    theta_src: jnp.ndarray,  # f32   [G, Ns_pad, H]
    theta_dst: jnp.ndarray,  # f32   [G, Nd_pad, H]
    h_src: jnp.ndarray,      # f32   [Ns_pad, H, Dh] (shared across graphs)
    edge_bias: jnp.ndarray | None = None,  # [G, H]; [T, H] with type tiles
    attn_prev: Attention | None = None,     # the attention residual's source
    *,
    leaky_slope: float = 0.2,
    interpret: bool = False,
    beta: float | None = None,   # residual weight, given with attn_prev
    return_lse: bool = False,
    precision: jax.lax.Precision | None = None,
):
    """Returns per-unit aggregates [U*B, H, Dh] (caller scatters by
    (graph_id, dst_row) — disjoint by construction), and with
    ``return_lse`` the per-unit-row log-sum-exp [U*B, H] as well, which
    carries no gradient (a next layer's ``Attention.lse``).
    Differentiable wrt theta_src / theta_dst / h_src / edge_bias via a
    fused Pallas backward.  ``precision`` is that of the bodies' dots: on
    a TPU, Mosaic's default for float32 operands is one bfloat16 pass;
    ``HIGHEST`` computes them in float32."""
    G, _, H = theta_src.shape
    if (attn_prev is None) != (beta is None):
        raise ValueError("the attention residual takes attn_prev and beta together")
    if edge_bias is None:
        if masks.dtype != jnp.bool_:
            raise ValueError("typed tiles take a [T, H] bias table")
        edge_bias = jnp.zeros((G, H), jnp.float32)
    edge_bias = jnp.asarray(edge_bias, jnp.float32)
    if attn_prev is not None:
        attn_prev = attn_prev._replace(bias=jnp.asarray(attn_prev.bias, jnp.float32))
    out, lse = _multigraph(
        col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
        edge_bias, attn_prev,
        _Opts(float(leaky_slope), bool(interpret), None if beta is None else float(beta),
              precision),
    )
    if return_lse:
        return out, jax.lax.stop_gradient(lse)
    return out
