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
probability tensor is ever materialized).  Its grid walks the flattened
(u, w) slots in source-block order: one stable sort by (col, graph),
dead slots last.  A source block's slots are then consecutive steps, and
so are its slots of one graph, and Pallas TPU keeps an output block
resident while consecutive steps revisit it.  So the GSF-like
scatter-add onto the shared src vertex space happens in VMEM:

  * d_h_src — summed in its output block over the col's run of steps,
    written to HBM once per source block;
  * d_theta_src — summed likewise over each (col, graph) run;
  * d_theta_dst — one lane-dense [H, B] block per slot, summed onto the
    units outside the kernel (a unit's slots are no longer consecutive);
  * with typed tiles, the [T, H] table's gradient, summed per source
    block like d_h_src and over the blocks outside.

h_src and theta_src stay resident across a run; the unit's g_out, lse,
delta and theta_dst stream in per step.  Dead steps repeat the last live
step's block indices, so they fetch and write nothing.  A source block
that no live slot references is never visited, and the wrapper zeroes
its rows.

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


# Step flags of the backward's sorted grid.
_LIVE, _NEW_COL, _NEW_SUB = 1, 2, 4


def _bwd_kernel(
    # scalar prefetch: per unit, then per step of the sorted grid
    gid_ref,    # int32 [U]
    row_ref,    # int32 [U]
    bias_ref,   # f32   [G, H], or [T, H] with typed tiles
    slot_ref,   # int32 [U*W]  the step's flat (u, w) slot
    unit_ref,   # int32 [U*W]  the step's unit
    col_ref,    # int32 [U*W]  the step's src block column
    flag_ref,   # int32 [U*W]  _LIVE | _NEW_COL | _NEW_SUB
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
    # outputs, f32: d_theta_src [1, H, B] of the step's (graph, col),
    # d_h_src [B, H*Dh] of its col, both summed in place over their runs;
    # the slot's d_theta_dst [1, H, B]; with typed tiles the col's
    # bias-table partial [T*H, B] (row t*H + h), summed over the col run
    dths_ref, dhs_ref, dthd_ref = (next(it) for _ in range(3))
    dtb_ref = next(it) if n_types else None
    dthd_col_ref = next(it)  # scratch f32 [B, 128k]: column h holds head h's d_theta_dst

    i = pl.program_id(0)
    flags = flag_ref[i]
    gid = gid_ref[unit_ref[i]]

    @pl.when((flags & _NEW_COL) != 0)
    def _init_col():
        dhs_ref[...] = jnp.zeros_like(dhs_ref)
        if n_types:
            dtb_ref[...] = jnp.zeros_like(dtb_ref)

    @pl.when((flags & _NEW_SUB) != 0)
    def _init_sub():
        dths_ref[...] = jnp.zeros_like(dths_ref)

    @pl.when((flags & _LIVE) != 0)
    def _body():
        live, tile = _tile(mask_ref, n_types)  # [B(dst), B(src)]
        for hh in range(heads):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            pre, logits = _logits(
                thd_ref, ths_ref, _bias(bias_ref, gid, hh, tile, n_types), hh, leaky_slope
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
            dths_ref[0, hh : hh + 1, :] += jnp.sum(dpre, axis=0, keepdims=True)
            weights = p
            if residual:
                _, plogits = _logits(
                    pthd_ref, pths_ref, _bias(pbias_ref, gid, hh, tile, n_types),
                    hh, leaky_slope,
                )
                pp = jnp.where(live, jnp.exp(plogits - plse_ref[:, hh : hh + 1]), 0.0)
                weights = (1.0 - beta) * p + beta * pp
            dhs_ref[:, sl] += jax.lax.dot_general(  # weights.T @ g_out  [Bs, Dh]
                weights, g_out, (((0,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32,
            )
            dthd_col_ref[:, hh : hh + 1] = jnp.sum(dpre, axis=1, keepdims=True)
            for t in range(n_types):
                r = t * heads + hh
                dtb_ref[r : r + 1, :] += jnp.sum(
                    jnp.where(tile == t + 1, dpre, 0.0), axis=0, keepdims=True
                )
        # lane-dense [H, B]: a [B, H] block would pad H to 128 lanes in HBM
        dthd_ref[0] = dthd_col_ref[...].T[:heads, :]


def _bwd_order(col_index, graph_id, n_graphs):
    """The backward's grid: one step per flat (u, w) slot, live slots
    stably sorted by (col, graph), dead slots last.  Returns per step the
    slot, its unit, its col and its flags (live; first step of its col
    run; first of its (col, graph) run).  Dead steps repeat the last live
    step's slot and col, so they fetch nothing and write nothing."""
    U, W = col_index.shape
    flat = col_index.reshape(-1)
    gid = jnp.repeat(graph_id, W)
    live = flat >= 0
    key = jnp.where(live, flat * n_graphs + gid, jnp.iinfo(jnp.int32).max)
    n_live = jnp.sum(live, dtype=jnp.int32)
    steps = jnp.arange(U * W, dtype=jnp.int32)
    slot = jnp.argsort(key, stable=True).astype(jnp.int32)
    slot = slot[jnp.minimum(steps, jnp.maximum(n_live - 1, 0))]
    col = jnp.maximum(flat[slot], 0)
    step_live = steps < n_live

    def starts(x):
        return step_live & ((steps == 0) | (x != jnp.roll(x, 1)))

    new_col = starts(col)
    new_sub = new_col | starts(gid[slot])
    flags = (step_live * _LIVE + new_col * _NEW_COL + new_sub * _NEW_SUB).astype(jnp.int32)
    return slot, slot // W, col, flags


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


# The forward's index maps take (u, w, *scalar-prefetch refs): col, gid,
# row, bias, fslot, fcol, and the previous layer's bias table with the
# residual.
def _unit_map(u, w, *_):
    return (u, 0)


def _specs(B, H, hdh, W):
    """The forward's BlockSpecs by operand: masks (fed as [U*W, 1, B, B]),
    dst and src coefficients, source features."""

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


def _fwd_call(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
              h_src, edge_bias, attn_prev, opts):
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    hdh = H * Dh
    residual = opts.beta is not None

    prefetch = [col_index, graph_id, dst_row, edge_bias, *_fill_dead(col_index)]
    if residual:
        prefetch.append(attn_prev.bias)
    inputs = [masks.reshape(U * W, 1, B, B), theta_dst, theta_src.swapaxes(1, 2),
              h_src.reshape(ns_pad, hdh)]
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
        # the previous layer's thd, ths and lse
        inputs += [attn_prev.theta_dst, attn_prev.theta_src.swapaxes(1, 2), attn_prev.lse]
        in_specs += [specs[1], specs[2], pl.BlockSpec((B, H), _unit_map)]
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
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=opts.interpret,
        name="seg_gat_agg_multigraph",
    )(*prefetch, *inputs)
    cur = outs[1].reshape(U * B, H, Dh) if residual else None
    return outs[0].reshape(U * B, H, Dh), cur, outs[-1]


def _bwd_call(col_index, graph_id, dst_row, masks, theta_src, theta_dst,
              h_src, edge_bias, attn_prev, g_out, lse, delta, opts):
    """The backward launch over the grid of ``_bwd_order``.  Returns
    d_theta_src [G, H, Ns_pad], d_h_src [Ns_pad, H*Dh] and, with typed
    tiles, the bias-table partials [T*H, Ns_pad] (else None), whose blocks
    no live slot references hold whatever the output buffer held; and the
    per-slot d_theta_dst [U*W, H, B], unwritten at dead slots."""
    U, W = col_index.shape
    B = masks.shape[-1]
    G, ns_pad, H = theta_src.shape
    Dh = h_src.shape[-1]
    hdh = H * Dh
    n_types = _n_types(masks, edge_bias)

    prefetch = [graph_id, dst_row, edge_bias, *_bwd_order(col_index, graph_id, G)]
    if attn_prev is not None:
        prefetch.append(attn_prev.bias)

    # index maps take (step, gid, row, bias, slot, unit, col, flags, ...)
    def mask_map(i, gid, row, bias, slot, *_):
        return (slot[i], 0, 0, 0)

    def thd_map(i, gid, row, bias, slot, unit, *_):
        return (gid[unit[i]], row[unit[i]], 0)

    def ths_map(i, gid, row, bias, slot, unit, col, *_):
        return (gid[unit[i]], 0, col[i])

    def col_map(i, gid, row, bias, slot, unit, col, *_):
        return (col[i], 0)

    def table_map(i, gid, row, bias, slot, unit, col, *_):
        return (0, col[i])

    def unit_map(i, gid, row, bias, slot, unit, *_):
        return (unit[i], 0)

    def slot_map(i, gid, row, bias, slot, *_):
        return (slot[i], 0, 0)

    inputs = [masks.reshape(U * W, 1, B, B), theta_dst, theta_src.swapaxes(1, 2),
              h_src.reshape(ns_pad, hdh), g_out.reshape(U * B, hdh), lse, delta]
    in_specs = [
        pl.BlockSpec((1, 1, B, B), mask_map),
        pl.BlockSpec((1, B, H), thd_map),
        pl.BlockSpec((1, H, B), ths_map),
        pl.BlockSpec((B, hdh), col_map),
        pl.BlockSpec((B, hdh), unit_map),
        pl.BlockSpec((B, H), unit_map),
        pl.BlockSpec((B, H), unit_map),
    ]
    if attn_prev is not None:
        inputs += [attn_prev.theta_dst, attn_prev.theta_src.swapaxes(1, 2), attn_prev.lse]
        in_specs += [in_specs[1], in_specs[2], pl.BlockSpec((B, H), unit_map)]
    out_specs = [
        pl.BlockSpec((1, H, B), ths_map),
        pl.BlockSpec((B, hdh), col_map),
        pl.BlockSpec((1, H, B), slot_map),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((G, H, ns_pad), jnp.float32),
        jax.ShapeDtypeStruct((ns_pad, hdh), jnp.float32),
        jax.ShapeDtypeStruct((U * W, H, B), jnp.float32),
    ]
    if n_types:
        out_specs.append(pl.BlockSpec((n_types * H, B), table_map))
        out_shape.append(jax.ShapeDtypeStruct((n_types * H, ns_pad), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(U * W,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((B, -(-H // 128) * 128), jnp.float32)],
    )
    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, heads=H, head_dim=Dh, leaky_slope=opts.leaky_slope,
            n_types=n_types, beta=opts.beta, precision=opts.precision,
        ),
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        # consecutive steps revisit the output blocks of a run
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=opts.interpret,
        name="seg_gat_agg_multigraph_bwd",
    )(*prefetch, *inputs)
    return outs[0], outs[1], outs[3] if n_types else None, outs[2]


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
    dths, d_h_src, dtb, dthd_slots = _bwd_call(
        col_index, graph_id, dst_row, masks, theta_src, theta_dst, h_src,
        edge_bias, attn_prev, g, lse, delta, opts,
    )

    # src blocks that no live slot references were never visited: zeros
    flat_col = col_index.reshape(U * W)
    live = flat_col >= 0
    seen = (
        jnp.zeros((G, nblk), bool)
        .at[jnp.repeat(graph_id, W), jnp.where(live, flat_col, nblk)]
        .set(True, mode="drop")
    )
    dths = jnp.where(seen[:, None, :, None], dths.reshape(G, H, nblk, B), 0.0)
    d_theta_src = dths.reshape(G, H, ns_pad).swapaxes(1, 2)
    d_h_src = jnp.where(
        seen.any(axis=0)[:, None, None], d_h_src.reshape(nblk, B, H * Dh), 0.0
    ).reshape(ns_pad, H, Dh)

    dthd_units = jnp.where(live[:, None, None], dthd_slots, 0.0).reshape(U, W, H, B).sum(1)
    d_theta_dst = (
        jnp.zeros((G, rd, B, H), jnp.float32)
        .at[graph_id, dst_row]
        .add(dthd_units.swapaxes(1, 2))
        .reshape(G, rd * B, H)
    )
    if dtb is None:
        # bias enters every logit additively: its gradient is the total dpre
        # mass per graph, already summed over dst inside d_theta_src.
        d_bias = dths.sum(axis=(2, 3))
    else:
        # typed tiles: the kernel summed dpre per (type, head) over dst
        n_types = edge_bias.shape[0]
        d_bias = jnp.where(
            seen.any(axis=0)[None, None, :, None], dtb.reshape(n_types, H, nblk, B), 0.0
        ).sum(axis=(2, 3))

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
