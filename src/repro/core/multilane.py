"""Independency-aware parallel execution (paper §4.2) — multi-lane NA.

Work units are (semantic graph, dst-block row) pairs: each dst vertex
lives in exactly one unit, so units are embarrassingly parallel until the
GSF barrier, exactly the independency the paper exploits.  Units are
assigned to lanes by the workload-aware scheduler (scheduling.py); lanes
execute as a vmapped axis on one chip or as a `shard_map` mesh axis across
chips — "adding hardware resources to further improve performance"
(paper §4.2.1) becomes adding devices to the lane axis.

All units share one static shape (W block slots, padded with -1 columns),
so lane execution is a single dense program regardless of how irregular
the semantic graphs are — the TPU answer to the crossbar/scheduler
machinery of the accelerator.

This module is the one door to the NA kernels: every launch of
kernels/seg_gat_agg_multigraph and kernels/seg_gat_agg_fused_fp goes
through a plan from :func:`build_multilane_plan` (one lane where nothing
is split: HAN's single-chip forward, R-GAT, serving) and
:func:`multilane_na`, :func:`multilane_na_sharded` or :func:`typed_na`.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .fusion import FusedFPInputs, NABackend, SemanticGraphBatch, require_tpu
from .scheduling import LanePlan, lane_assignment, naive_lane_assignment

NEG_INF = -1e30


@dataclasses.dataclass
class MultiLanePlan:
    """Static multi-lane execution plan (device arrays).

    Shapes: L lanes × U units/lane (padded) × W block slots × B×B masks.
    """

    col_index: jnp.ndarray  # int32 [L, U, W]
    masks: jnp.ndarray      # bool  [L, U, W, B, B]
    graph_id: jnp.ndarray   # int32 [L, U]
    dst_row: jnp.ndarray    # int32 [L, U]
    valid: jnp.ndarray      # bool  [L, U]
    block: int
    num_graphs: int
    n_dst_blocks: int       # per graph (shared dst space)
    lane_plan: LanePlan | None  # host-side scheduling metadata (not traced)

    @property
    def num_lanes(self) -> int:
        return int(self.col_index.shape[0])

    def na_slots(self) -> dict:
        """NA grid slots per lane and how many of them are live (the
        kernel fetches nothing and runs no body for the rest), and the
        source blocks the live slots reference: the backward visits each
        in one run of steps and writes its d_h_src block once.  Counted
        from a host copy of ``col_index`` when asked."""
        col = np.asarray(self.col_index)
        _, units, w = col.shape
        return {"grid": int(units * w), "live": [int((c >= 0).sum()) for c in col],
                "src_runs": [int(np.unique(c[c >= 0]).size) for c in col]}


def _flatten_unflatten():
    arr = ("col_index", "masks", "graph_id", "dst_row", "valid")
    # lane_plan holds host-side numpy arrays; it must NOT ride in the
    # pytree aux (aux must be hashable) — reconstructed copies carry None
    # there, which multilane_na never reads.
    meta = ("block", "num_graphs", "n_dst_blocks")

    def fl(p):
        return tuple(getattr(p, f) for f in arr), tuple(getattr(p, f) for f in meta)

    def unfl(aux, children):
        kw = dict(zip(meta, aux))
        kw.update(dict(zip(arr, children)))
        return MultiLanePlan(lane_plan=None, **kw)

    jax.tree_util.register_pytree_node(MultiLanePlan, fl, unfl)


_flatten_unflatten()


def build_multilane_plan(
    batches: list[SemanticGraphBatch],
    num_lanes: int,
    *,
    balanced: bool = True,
    threshold: float | None = None,
) -> MultiLanePlan:
    """Partition the block rows of all semantic graphs onto lanes.

    This is the one builder of the kernels' work-unit tables: one unit
    per (graph, dst-block row), col widths padded to the max across
    graphs.  At one lane every unit sits on lane 0 in graph-major order,
    so the tables are the graphs' rows stacked.  Each graph's tables are
    copied to the host once.

    A unit costs the scheduler its live block slots (``col_index >= 0``
    along its row): the kernel pays per live tile, whatever its edge
    count, and every lane runs the same padded grid, so a lane's time
    grows with its live slots alone (DESIGN.md §5).

    Requires all graphs to share the dst vertex space (HAN's metapath
    graphs do).  Host-side; build once per layer, or per serving step.
    """
    assert batches, "no semantic graphs"
    b = batches[0].block
    n_rows = int(batches[0].col_index.shape[0])
    for bb in batches:
        assert bb.col_index is not None, "batch built without block CSR"
        assert bb.block == b and int(bb.col_index.shape[0]) == n_rows

    # the graph-major stack: unit u is row u % n_rows of graph u // n_rows
    g_n = len(batches)
    w_max = max(int(bb.col_index.shape[1]) for bb in batches)
    col = np.full((g_n, n_rows, w_max), -1, np.int32)
    masks = np.zeros((g_n, n_rows, w_max, b, b), batches[0].masks.dtype)
    for i, bb in enumerate(batches):
        wg = int(bb.col_index.shape[1])
        col[i, :, :wg] = np.asarray(bb.col_index)
        masks[i, :, :wg] = np.asarray(bb.masks)

    row_costs = list((col >= 0).sum(axis=2))
    plan = (
        lane_assignment(row_costs, num_lanes, threshold=threshold)
        if balanced
        else naive_lane_assignment(row_costs, num_lanes)
    )
    col = col.reshape(1, g_n * n_rows, w_max)
    masks = masks.reshape(1, g_n * n_rows, w_max, b, b)
    gid = plan.unit_graph[None]
    drow = plan.unit_row[None]
    valid = np.ones_like(gid, bool)
    if num_lanes > 1:
        # each lane's units, in unit order, padded to the longest lane
        lanes_units = [np.flatnonzero(plan.unit_lane == l) for l in range(num_lanes)]
        u_max = max(1, max(lu.size for lu in lanes_units))
        lane_col = np.full((num_lanes, u_max, w_max), -1, np.int32)
        lane_masks = np.zeros((num_lanes, u_max, w_max, b, b), masks.dtype)
        gid = np.zeros((num_lanes, u_max), np.int32)
        drow = np.zeros((num_lanes, u_max), np.int32)
        valid = np.zeros((num_lanes, u_max), bool)
        for l, lu in enumerate(lanes_units):
            lane_col[l, : lu.size] = col[0, lu]
            lane_masks[l, : lu.size] = masks[0, lu]
            gid[l, : lu.size] = plan.unit_graph[lu]
            drow[l, : lu.size] = plan.unit_row[lu]
            valid[l, : lu.size] = True
        col, masks = lane_col, lane_masks
    col_d, masks_d, gid_d, drow_d, valid_d = jax.device_put((col, masks, gid, drow, valid))
    return MultiLanePlan(
        col_index=col_d,
        masks=masks_d,
        graph_id=gid_d,
        dst_row=drow_d,
        valid=valid_d,
        block=b,
        num_graphs=g_n,
        n_dst_blocks=n_rows,
        lane_plan=plan,
    )


def _unit_na(
    cols: jnp.ndarray,   # [W]
    mrow: jnp.ndarray,   # [W, B, B]
    gid: jnp.ndarray,    # scalar
    drow: jnp.ndarray,   # scalar
    theta_src: jnp.ndarray,  # [G, Ns_pad, H]
    theta_dst: jnp.ndarray,  # [G, Nd_pad, H]
    h_src: jnp.ndarray,      # [Ns_pad, H, Dh]
    edge_bias: jnp.ndarray,  # [G, H]
    leaky_slope: float,
) -> jnp.ndarray:
    b = mrow.shape[-1]
    h_dim, dh = theta_src.shape[-1], h_src.shape[-1]
    th_d = jax.lax.dynamic_slice(
        theta_dst, (gid, drow * b, 0), (1, b, h_dim)
    )[0]  # [B, H]
    bias = edge_bias[gid]  # [H]

    def step(carry, inp):
        m_run, l_run, acc = carry
        c, mask = inp
        c_safe = jnp.maximum(c, 0)
        th_s = jax.lax.dynamic_slice(theta_src, (gid, c_safe * b, 0), (1, b, h_dim))[0]
        hs = jax.lax.dynamic_slice_in_dim(h_src, c_safe * b, b, 0)
        logits = jax.nn.leaky_relu(
            th_d[:, None, :] + th_s[None, :, :] + bias, leaky_slope
        )
        live = mask[:, :, None] & (c >= 0)
        logits = jnp.where(live, logits, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(logits, axis=1))
        scale = jnp.exp(m_run - m_new)
        p = jnp.where(live, jnp.exp(logits - m_new[:, None, :]), 0.0)
        l_new = l_run * scale + p.sum(axis=1)
        acc_new = acc * scale[:, :, None] + jnp.einsum("dsh,shf->dhf", p, hs)
        return (m_new, l_new, acc_new), None

    # f32 carries regardless of input dtype — bf16 online-softmax state
    # drifts badly over long W sweeps; the Pallas kernels accumulate in
    # f32 too, so this keeps the reference and kernel paths comparable.
    init = (
        jnp.full((b, h_dim), NEG_INF, jnp.float32),
        jnp.zeros((b, h_dim), jnp.float32),
        jnp.zeros((b, h_dim, dh), jnp.float32),
    )
    (m_f, l_f, acc_f), _ = jax.lax.scan(step, init, (cols, mrow))
    out = acc_f / jnp.maximum(l_f, 1e-9)[:, :, None]  # [B, H, Dh]
    return out.astype(h_src.dtype)


MULTILANE_BACKENDS = ("reference", "kernel", "kernel_interpret", "fused_fp", "fused_fp_interpret")
_FUSED_FP = ("fused_fp", "fused_fp_interpret")
# The executor each kernel NABackend runs: the one place where a caller
# that holds an NABackend (HAN's forward, R-GAT, the serving engine)
# reaches a kernel.  SEGMENT and BLOCK run per graph (core/fusion.py).
_PLAN_BACKEND = {
    NABackend.MULTIGRAPH: "kernel",
    NABackend.MULTIGRAPH_INTERPRET: "kernel_interpret",
    NABackend.FUSED_FP: "fused_fp",
    NABackend.FUSED_FP_INTERPRET: "fused_fp_interpret",
}


def _executor(backend: str | NABackend) -> str:
    """The plan executor ``backend`` names.  A compiled kernel on a host
    without a TPU is refused under the caller's own spelling, so the
    error names the interpret twin the caller can ask for."""
    name = _PLAN_BACKEND.get(backend, backend)
    if name not in MULTILANE_BACKENDS:
        raise ValueError(f"backend={backend!r}, expected one of {MULTILANE_BACKENDS}")
    require_tpu(getattr(backend, "value", backend))
    return name


def multilane_na(
    plan: MultiLanePlan,
    theta_src: jnp.ndarray | None,  # [G, Ns_pad, H]   (None with fused_fp)
    theta_dst: jnp.ndarray | None,  # [G, Nd_pad, H]   (None with fused_fp)
    h_src: jnp.ndarray | None,      # [Ns_pad, H, Dh]  (None with fused_fp)
    *,
    edge_bias: jnp.ndarray | None = None,  # [G, H]
    leaky_slope: float = 0.2,
    backend: str | NABackend = "reference",
    fp: FusedFPInputs | None = None,
) -> jnp.ndarray:
    """Run NA for all semantic graphs across lanes.

    Returns z [G, Nd_pad, H, Dh].

    ``backend`` selects the per-unit executor:
      * ``"reference"`` — vmap over (lanes, units) of the scan oracle;
      * ``"kernel"`` — one fused Pallas launch for *all* lanes' units
        (kernels/seg_gat_agg_multigraph): the paper's mixed-graph lane
        datapath as a single TPU kernel;
      * ``"kernel_interpret"`` — same kernel under the Pallas interpreter
        (CPU validation / CI); ``"kernel"`` on a host without a TPU
        raises instead (``fusion.require_tpu``);
      * ``"fused_fp"`` / ``"fused_fp_interpret"`` — the stage-fusion
        megakernel (kernels/seg_gat_agg_fused_fp): pass
        ``fp=FusedFPInputs`` (raw features padded to [N_pad, Din] +
        projection/attention params) and leave the theta/h operands None;
        the FP stage runs inside the launch (DESIGN.md §10).
    A kernel ``NABackend`` names its executor: ``MULTIGRAPH[_INTERPRET]``
    is ``"kernel[_interpret]"``, ``FUSED_FP[_INTERPRET]`` is
    ``"fused_fp[_interpret]"``.
    All backends scatter identically, so they agree to f32 tolerance.
    """
    backend = _executor(backend)
    fused_fp = backend in _FUSED_FP
    if fused_fp:
        if fp is None:
            raise ValueError(f"backend={backend!r} needs fp=FusedFPInputs")
        g_n, h_dim, dh = fp.a_src.shape
        out_dtype = fp.x.dtype
    else:
        g_n, _, h_dim = theta_src.shape
        dh = h_src.shape[-1]
        out_dtype = h_src.dtype
    if edge_bias is None:
        edge_bias = jnp.zeros((g_n, h_dim), out_dtype)

    lanes, units, w = plan.col_index.shape
    if backend == "reference":
        unit_fn = lambda c, m, g, r: _unit_na(
            c, m, g, r, theta_src, theta_dst, h_src, edge_bias, leaky_slope
        )
        per_unit = jax.vmap(jax.vmap(unit_fn))(
            plan.col_index, plan.masks, plan.graph_id, plan.dst_row
        )  # [L, U, B, H, Dh]
    elif fused_fp:
        from repro.kernels.seg_gat_agg_fused_fp import seg_gat_agg_fused_fp

        flat = seg_gat_agg_fused_fp(
            plan.col_index.reshape(lanes * units, w),
            plan.graph_id.reshape(lanes * units),
            plan.dst_row.reshape(lanes * units),
            fp.wsel,
            plan.masks.reshape(lanes * units, w, plan.block, plan.block),
            fp.x, fp.w, fp.b, fp.a_src, fp.a_dst, edge_bias,
            leaky_slope=leaky_slope,
            interpret=(backend == "fused_fp_interpret"),
        )  # [L*U*B, H, Dh]
        per_unit = flat.reshape(lanes, units, plan.block, h_dim, dh)
    else:
        from repro.kernels.seg_gat_agg_multigraph import seg_gat_agg_multigraph

        flat = seg_gat_agg_multigraph(
            plan.col_index.reshape(lanes * units, w),
            plan.graph_id.reshape(lanes * units),
            plan.dst_row.reshape(lanes * units),
            plan.masks.reshape(lanes * units, w, plan.block, plan.block),
            theta_src,
            theta_dst,
            h_src,
            edge_bias,
            leaky_slope=leaky_slope,
            interpret=(backend == "kernel_interpret"),
        )  # [L*U*B, H, Dh]
        per_unit = flat.reshape(lanes, units, plan.block, h_dim, dh)

    return _scatter_units(plan, per_unit, g_n, out_dtype)


def _scatter_units(plan: MultiLanePlan, per_unit, g_n: int, dtype) -> jnp.ndarray:
    """Per-unit outputs [L, U, B, H, Dh] -> [G, Nd_pad, H, Dh]: each valid
    unit lands on its (graph, dst block row); the rows are disjoint."""
    _, _, b, h_dim, dh = per_unit.shape
    out = jnp.zeros((g_n, plan.n_dst_blocks, b, h_dim, dh), dtype)
    contrib = jnp.where(plan.valid[:, :, None, None, None], per_unit, 0.0)
    out = out.at[plan.graph_id, plan.dst_row].add(contrib)
    return out.reshape(g_n, plan.n_dst_blocks * b, h_dim, dh)


def typed_na(
    plan: MultiLanePlan,
    theta_src: jnp.ndarray,  # [1, Ns_pad, H]
    theta_dst: jnp.ndarray,  # [1, Nd_pad, H]
    h_src: jnp.ndarray,      # [Ns_pad, H, Dh]
    type_bias: jnp.ndarray,  # [T, H]
    *,
    attn_prev=None,
    beta: float | None = None,
    leaky_slope: float = 0.2,
    backend: str = "kernel",
):
    """NA over a typed plan: one graph whose tiles hold edge types (int8,
    type + 1, 0 = no edge), so each dst vertex's softmax runs jointly over
    its in-edges of every type, each logit biased by ``type_bias[type]``.
    One fused multigraph launch, forward and backward, on one lane shard.

    ``attn_prev`` (a previous layer's ``Attention`` over the same plan)
    with ``beta`` mixes that layer's attention in, rebuilt in-tile (the
    attention residual; see kernels/seg_gat_agg_multigraph).  Returns
    ``(z [Nd_pad, H, Dh], Attention)``: this layer's aggregate and what the
    next layer rebuilds its attention from.  The kernel's dots run in
    float32 (``HIGHEST``; Mosaic's default is one bfloat16 pass).
    """
    from repro.kernels.seg_gat_agg_multigraph import Attention, seg_gat_agg_multigraph

    if backend not in ("kernel", "kernel_interpret"):
        raise ValueError(f"typed NA runs the multigraph kernel, not backend={backend!r}")
    require_tpu(backend)
    lanes, units, w = plan.col_index.shape
    flat, lse = seg_gat_agg_multigraph(
        plan.col_index.reshape(lanes * units, w),
        plan.graph_id.reshape(lanes * units),
        plan.dst_row.reshape(lanes * units),
        plan.masks.reshape(lanes * units, w, plan.block, plan.block),
        theta_src, theta_dst, h_src, type_bias, attn_prev,
        leaky_slope=leaky_slope, beta=beta, return_lse=True,
        precision=jax.lax.Precision.HIGHEST, interpret=(backend == "kernel_interpret"),
    )  # [L*U*B, H, Dh], [L*U*B, H]
    h_dim, dh = h_src.shape[1:]
    per_unit = flat.reshape(lanes, units, plan.block, h_dim, dh)
    z = _scatter_units(plan, per_unit, 1, h_src.dtype)[0]
    attn = Attention(theta_src, theta_dst, type_bias, lse)
    return z, jax.tree_util.tree_map(jax.lax.stop_gradient, attn)


def _plan_specs(plan: MultiLanePlan, lane_axes: tuple[str, ...]) -> MultiLanePlan:
    """PartitionSpecs that split every plan array on its leading lane dim."""
    lane_part = lane_axes[0] if len(lane_axes) == 1 else tuple(lane_axes)
    lane_spec = lambda ndim: PartitionSpec(lane_part, *([None] * (ndim - 1)))
    return MultiLanePlan(
        col_index=lane_spec(3),
        masks=lane_spec(5),
        graph_id=lane_spec(2),
        dst_row=lane_spec(2),
        valid=lane_spec(2),
        block=plan.block,
        num_graphs=plan.num_graphs,
        n_dst_blocks=plan.n_dst_blocks,
        lane_plan=None,
    )


def place_plan(plan: MultiLanePlan, mesh, lane_axes: tuple[str, ...]) -> MultiLanePlan:
    """Put each lane shard of the plan on the device of ``mesh`` that runs
    it under ``multilane_na_sharded`` (host scheduling metadata kept)."""
    specs = _plan_specs(plan, lane_axes)
    placed = {
        f: jax.device_put(getattr(plan, f), NamedSharding(mesh, getattr(specs, f)))
        for f in ("col_index", "masks", "graph_id", "dst_row", "valid")
    }
    return dataclasses.replace(plan, **placed)


def multilane_na_sharded(
    plan: MultiLanePlan,
    theta_src: jnp.ndarray | None,  # [G, Ns_pad, H]   (None with fused_fp)
    theta_dst: jnp.ndarray | None,  # [G, Nd_pad, H]   (None with fused_fp)
    h_src: jnp.ndarray | None,      # [Ns_pad, H, Dh]  (None with fused_fp)
    *,
    mesh,
    lane_axes: tuple[str, ...] = ("lane",),
    edge_bias: jnp.ndarray | None = None,  # [G, H]
    leaky_slope: float = 0.2,
    backend: str | NABackend = "reference",
    fp: FusedFPInputs | None = None,
) -> jnp.ndarray:
    """``multilane_na`` with the lane dimension dispatched over mesh chips.

    The plan's lane axis is `shard_map`ped over ``lane_axes`` (paper
    §4.2.1: adding hardware = adding devices to the lane axis).  Each
    shard runs its local lanes' work units against the *replicated*
    projected features — every lane gathers what it needs from the shared
    FP output, the functional RAB of DESIGN.md §2 — and scatters into a
    zero-initialised full dst space; a single psum over the lane axes is
    the only cross-lane communication (the GSF barrier).

    Numerically identical to ``multilane_na`` for any lane-axis size that
    divides the plan's lane count (size 1 = the vmap path, exactly).
    """
    n_shards = math.prod(mesh.shape[a] for a in lane_axes)
    assert plan.num_lanes % n_shards == 0, (plan.num_lanes, n_shards)
    backend = _executor(backend)
    fused_fp = backend in _FUSED_FP
    if fused_fp:
        if fp is None:
            raise ValueError(f"backend={backend!r} needs fp=FusedFPInputs")
        g_n, h_dim, _ = fp.a_src.shape
        bias_dtype = fp.x.dtype
    else:
        g_n, _, h_dim = theta_src.shape
        bias_dtype = h_src.dtype
    if edge_bias is None:
        edge_bias = jnp.zeros((g_n, h_dim), bias_dtype)

    plan_specs = _plan_specs(plan, lane_axes)
    rep = PartitionSpec()

    if fused_fp:
        # raw features + weight tables replicate like the thetas do: every
        # lane shard projects the tiles its units touch on-chip (the
        # functional RAB, now fed from raw x instead of materialized h')
        fp_specs = jax.tree_util.tree_map(lambda _: rep, fp)

        def local_fp(plan_loc, fp_loc, bias):
            partial = multilane_na(
                plan_loc, None, None, None, edge_bias=bias,
                leaky_slope=leaky_slope, backend=backend, fp=fp_loc,
            )
            return jax.lax.psum(partial, lane_axes)

        fn = jax.shard_map(
            local_fp,
            mesh=mesh,
            in_specs=(plan_specs, fp_specs, rep),
            out_specs=rep,
            check_vma=False,
        )
        return fn(plan, fp, edge_bias)

    def local(plan_loc, ths, thd, hs, bias):
        # backend applies per shard: "kernel" = one fused Pallas launch
        # per chip over that chip's lanes, shard_map across chips.
        partial = multilane_na(
            plan_loc, ths, thd, hs, edge_bias=bias, leaky_slope=leaky_slope,
            backend=backend,
        )
        return jax.lax.psum(partial, lane_axes)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(plan_specs, rep, rep, rep, rep),
        out_specs=rep,
        check_vma=False,
    )
    return fn(plan, theta_src, theta_dst, h_src, edge_bias)
