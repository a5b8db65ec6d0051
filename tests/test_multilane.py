"""Independency-aware parallel execution: multilane NA correctness +
workload balancing effect + the training equivalence contract.

The differential tests pin the contract DESIGN.md §11 documents: for a
jitted HAN train step the LOSS is bit-identical across NA backends
(BLOCK / MULTIGRAPH / MULTIGRAPH_INTERPRET) and across lane counts
L∈{1,2,4} under shard_map; gradients are bit-deterministic per topology
and agree across topologies/backends to f32 tolerance (measured ~1e-9 —
the lane partition regroups the cross-unit d_h_src reduction).

The property tests fuzz the plan builders and the multigraph VJP over
random unit tables and degenerate shapes (empty graph, single edge,
all-padded block) — degenerate rows must produce exact zeros, never NaN.
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    NABackend,
    batch_semantic_graph,
    lane_assignment,
    naive_lane_assignment,
    neighbor_aggregate,
)
from repro.core.multilane import build_multilane_plan, multilane_na
from repro.graphs import build_semantic_graphs, dataset_metapaths, synthetic_hetgraph, union_graph
from repro.graphs.hetgraph import SemanticGraph
from repro.launch.hgnn_train import build_problem, run_training
from repro.launch.mesh import make_lane_mesh
from repro.models.hgnn import han_forward_multilane
from repro.models.hgnn.common import HGNNData
from repro.models.hgnn.han import han_forward, init_han
from repro.serve import GraphRequest, HGNNEngine
from repro.serve import hgnn_engine


@pytest.fixture(scope="module")
def dblp_setup():
    rng = np.random.default_rng(0)
    g = synthetic_hetgraph("dblp", scale=0.05, feat_scale=0.1)
    sgs = build_semantic_graphs(g, dataset_metapaths("dblp"))
    B, H, Dh = 16, 2, 8
    batches = [batch_semantic_graph(s, block=B) for s in sgs]
    G = len(batches)
    ns = batches[0].num_src
    ns_pad = ((ns + B - 1) // B) * B
    nd_pad = batches[0].num_dst_pad
    hs = np.zeros((ns_pad, H, Dh), np.float32)
    hs[:ns] = rng.standard_normal((ns, H, Dh))
    ths = np.zeros((G, ns_pad, H), np.float32)
    thd = np.zeros((G, nd_pad, H), np.float32)
    for i in range(G):
        ths[i, :ns] = rng.standard_normal((ns, H))
        thd[i, :ns] = rng.standard_normal((ns, H))
    return batches, jnp.asarray(ths), jnp.asarray(thd), jnp.asarray(hs)


@pytest.mark.parametrize("lanes", [1, 2, 4, 8])
def test_multilane_matches_reference_any_lane_count(dblp_setup, lanes):
    batches, ths, thd, hs = dblp_setup
    plan = build_multilane_plan(batches, lanes)
    z = multilane_na(plan, ths, thd, hs)
    for i, b in enumerate(batches):
        ref = neighbor_aggregate(
            b, ths[i, : b.num_src], thd[i, : b.num_dst], hs[: b.num_src],
            backend=NABackend.SEGMENT,
        )
        np.testing.assert_allclose(
            np.asarray(z[i, : b.num_dst]), np.asarray(ref), rtol=5e-5, atol=5e-5
        )


def _with_dead_slots_computed(plan):
    """The plan with every dead slot made a live slot of block 0 under an
    all-false mask: the kernel then runs its full body there, as it did
    on padding before dead slots were skipped."""
    dead = np.asarray(plan.col_index) < 0
    return dataclasses.replace(
        plan,
        col_index=jnp.where(dead, 0, plan.col_index),
        masks=jnp.where(dead[..., None, None], False, plan.masks),
    )


def _na_and_vjp(plan, ths, thd, hs, bias, backend):
    f = lambda *a: multilane_na(plan, *a[:3], edge_bias=a[3], backend=backend)
    out, vjp = jax.vjp(f, ths, thd, hs, bias)
    cot = jnp.asarray(
        np.random.default_rng(1).standard_normal(out.shape).astype(np.float32)
    )
    return [np.asarray(out)] + [np.asarray(x) for x in vjp(cot)]


@pytest.mark.parametrize("lanes", [1, 4])
def test_multilane_kernel_backend_matches_reference(dblp_setup, lanes):
    """backend="kernel_interpret" (one fused Pallas launch for all lanes'
    units) must match the vmap reference on the same plan.  The plans
    hold a row of live slots followed by padding, units whose first slot
    is padding, and (lanes=4) all-padding units of a short lane; dead
    slots fetch nothing and run no body.  The forward equals the
    reference bit for bit; the forward and the VJP (d_theta_src,
    d_theta_dst, d_h_src, d_bias) equal bit for bit the kernel made to
    compute every dead slot, and the VJP agrees with the reference's
    autodiff to f32 tolerance."""
    batches, ths, thd, hs = dblp_setup
    plan = build_multilane_plan(batches, lanes)
    col, valid = np.asarray(plan.col_index), np.asarray(plan.valid)
    assert ((col[..., 0] >= 0) & (col[..., -1] < 0)).any()  # live, then padding
    assert (valid & (col[..., 0] < 0)).any()  # a unit whose first slot is padding
    assert (~valid).any() == (lanes == 4)  # all-padding units of a short lane
    bias = jnp.asarray(
        np.random.default_rng(2).standard_normal((len(batches), ths.shape[-1]))
        .astype(np.float32)
    )

    ker = _na_and_vjp(plan, ths, thd, hs, bias, "kernel_interpret")
    computed = _na_and_vjp(
        _with_dead_slots_computed(plan), ths, thd, hs, bias, "kernel_interpret"
    )
    ref = _na_and_vjp(plan, ths, thd, hs, bias, "reference")
    np.testing.assert_array_equal(ker[0], ref[0])
    for k, c in zip(ker, computed):
        np.testing.assert_array_equal(k, c)
    for k, r in zip(ker[1:], ref[1:]):
        np.testing.assert_allclose(k, r, rtol=1e-5, atol=1e-5)


def _dense_na(col, gid, row, masks, ths, thd, hs, bias, slope):
    """Block reference of one multigraph launch, dense over each unit's
    slots: [U, B, H, Dh].  Typed tiles (int8, type + 1) look their bias up
    in a [T, H] table, boolean masks take one bias per graph."""
    B = masks.shape[-1]
    src = jnp.maximum(col, 0)[..., None] * B + jnp.arange(B)     # [U, W, Bs]
    dst = row[:, None] * B + jnp.arange(B)                        # [U, Bd]
    live = (masks != 0) & (col >= 0)[..., None, None]             # [U, W, Bd, Bs]
    if masks.dtype == jnp.bool_:
        b = bias[gid][:, None, None, None, :]
    else:
        b = bias[jnp.maximum(masks.astype(jnp.int32) - 1, 0)]
    pre = (thd[gid[:, None], dst][:, None, :, None] + ths[gid[:, None, None], src][:, :, None]
           + b)                                                   # [U, W, Bd, Bs, H]
    logit = jnp.where(live[..., None], jnp.where(pre >= 0, pre, slope * pre), -jnp.inf)
    m = jax.lax.stop_gradient(logit.max(axis=(1, 3), keepdims=True))
    e = jnp.where(live[..., None], jnp.exp(logit - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    p = e / jnp.maximum(e.sum(axis=(1, 3), keepdims=True), 1e-30)
    return jnp.einsum("uwdsh,uwshe->udhe", p, hs[src])


def _bwd_case(name, dblp_setup):
    """Unit tables and operands of one backward case: (col, gid, row,
    masks, ths, thd, hs, bias, beta)."""
    if name == "lanes":  # HAN's graphs over 4 lanes, flattened into one launch
        batches, ths, thd, hs = dblp_setup
        plan = build_multilane_plan(batches, 4)
        lanes, units, w = plan.col_index.shape
        bias = np.random.default_rng(4).standard_normal((len(batches), ths.shape[-1]))
        return (plan.col_index.reshape(-1, w), plan.graph_id.reshape(-1),
                plan.dst_row.reshape(-1),
                plan.masks.reshape(lanes * units, w, plan.block, plan.block),
                ths, thd, hs, jnp.asarray(bias, jnp.float32), None)
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    B, U, W, H, Dh, nblk = 8, 6, 3, 2, 8, 5
    typed = name.startswith("typed")
    G = 1 if typed else 3
    # the last block is referenced by no slot
    col = np.stack([rng.permutation(nblk - 1)[:W] for _ in range(U)]).astype(np.int32)
    col[rng.random((U, W)) < 0.3] = -1
    dead = {"dead-unit-first": 0, "dead-unit-middle": U // 2, "dead-unit-last": U - 1}
    if name in dead:
        col[dead[name]] = -1
    gid = rng.integers(0, G, U).astype(np.int32)
    row = rng.integers(0, nblk, U).astype(np.int32)
    edge = rng.random((U, W, B, B)) < 0.4
    masks = np.where(edge, rng.integers(1, 4, edge.shape), 0).astype(np.int8) if typed else edge
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    ths, thd, hs = f(G, nblk * B, H), f(G, nblk * B, H), f(nblk * B, H, Dh)
    bias = f(3, H) if typed else f(G, H)
    beta = 0.05 if name == "typed+residual" else None
    return (jnp.asarray(col), jnp.asarray(gid), jnp.asarray(row), jnp.asarray(masks),
            ths, thd, hs, bias, beta)


@pytest.mark.parametrize("case", [
    "dead-unit-first", "dead-unit-middle", "dead-unit-last", "unreferenced-block",
    "lanes", "typed", "typed+residual",
])
def test_multigraph_bwd_source_order_matches_block_autodiff(dblp_setup, case):
    """The backward walks the slots sorted by (col, graph) and sums
    d_h_src and d_theta_src over each run in VMEM.  Its VJP matches
    autodiff of the dense block reference for all-dead units anywhere in
    the grid, col runs across graphs and lanes, and typed tiles with and
    without the attention residual; the rows of a source block that no
    live slot references (never visited) are exact zeros."""
    from repro.kernels.seg_gat_agg_multigraph import Attention, seg_gat_agg_multigraph

    col, gid, row, masks, ths, thd, hs, bias, beta = _bwd_case(case, dblp_setup)
    B = masks.shape[-1]
    colh, gidh = np.asarray(col), np.asarray(gid)
    slot_gid = np.broadcast_to(gidh[:, None], colh.shape)
    if case.startswith("dead-unit"):
        assert (colh < 0).all(axis=1).any()
    if case == "lanes":  # a col run spans units of several graphs and lanes
        lane = np.broadcast_to(np.arange(colh.shape[0])[:, None] // (colh.shape[0] // 4), colh.shape)
        assert any(
            len(set(slot_gid[colh == c])) > 1 and len(set(lane[colh == c])) > 1
            for c in set(colh[colh >= 0].tolist())
        )
    prev, kw = None, {}
    if beta is not None:
        rng = np.random.default_rng(9)
        pths, pthd, pbias = (jnp.asarray(rng.standard_normal(x.shape).astype(np.float32))
                             for x in (ths, thd, bias))
        _, plse = seg_gat_agg_multigraph(col, gid, row, masks, pths, pthd, hs, pbias,
                                         interpret=True, return_lse=True)
        prev, kw = Attention(pths, pthd, pbias, plse), {"beta": beta}

    def f_kernel(a, b, c, d):
        out = seg_gat_agg_multigraph(col, gid, row, masks, a, b, c, d, prev,
                                     interpret=True, **kw)
        return out.reshape(-1, B, *c.shape[1:])

    def f_ref(a, b, c, d):
        out = _dense_na(col, gid, row, masks, a, b, c, d, 0.2)
        if beta is None:
            return out
        return (1 - beta) * out + beta * _dense_na(col, gid, row, masks, prev.theta_src,
                                                   prev.theta_dst, c, prev.bias, 0.2)

    args = (ths, thd, hs, bias)
    out_k, vjp_k = jax.vjp(f_kernel, *args)
    out_r, vjp_r = jax.vjp(f_ref, *args)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(out_k.shape).astype(np.float32))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-4, atol=1e-5)
    grads_k = [np.asarray(x) for x in vjp_k(cot)]
    for k, r in zip(grads_k, vjp_r(cot)):
        np.testing.assert_allclose(k, np.asarray(r), rtol=1e-4, atol=1e-5)

    nblk = hs.shape[0] // B
    seen = np.zeros((ths.shape[0], nblk), bool)
    seen[slot_gid[colh >= 0], colh[colh >= 0]] = True
    d_ths = grads_k[0].reshape(ths.shape[0], nblk, B, -1)
    d_hs = grads_k[2].reshape(nblk, B, -1)
    assert np.all(d_ths[~seen] == 0.0) and np.all(d_hs[~seen.any(axis=0)] == 0.0)
    if case == "unreferenced-block":
        assert not seen.any(axis=0).all()
    assert np.abs(d_hs).max() > 0.0


@pytest.mark.parametrize("lanes", [1, 2, 4, 8])
def test_plan_counts_live_slots_per_lane(dblp_setup, lanes):
    batches, *_ = dblp_setup
    plan = build_multilane_plan(batches, lanes)
    col = np.asarray(plan.col_index)
    assert plan.na_slots() == {
        "grid": col.shape[1] * col.shape[2],
        "live": [int((col[l] >= 0).sum()) for l in range(lanes)],
        "src_runs": [len(np.unique(col[l][col[l] >= 0])) for l in range(lanes)],
    }


def _live_slot_costs(batches):
    """Each row's live block slots, reduced from the device col_index."""
    return [np.asarray((b.col_index >= 0).sum(axis=1), np.int64) for b in batches]


def _edge_costs(batches):
    """Each row's edges, reduced from the device masks (the lane cost of
    the paper's datapath, which pays per edge)."""
    return [np.asarray((b.masks != 0).sum(axis=(1, 2, 3)), np.int64) for b in batches]


def _per_row_plan(batches, num_lanes, balanced, costs=_live_slot_costs):
    """The plan by the per-row construction: row costs reduced on the
    device by ``costs``, and each unit's row sliced from the device on its
    own."""
    row_costs = costs(batches)
    lp = (lane_assignment(row_costs, num_lanes) if balanced
          else naive_lane_assignment(row_costs, num_lanes))
    lanes_units = [[] for _ in range(num_lanes)]
    for u in range(lp.unit_graph.shape[0]):
        lanes_units[int(lp.unit_lane[u])].append(u)
    u_max = max(1, max(len(lu) for lu in lanes_units))
    w_max = max(int(b.col_index.shape[1]) for b in batches)
    blk = batches[0].block
    col = np.full((num_lanes, u_max, w_max), -1, np.int32)
    masks = np.zeros((num_lanes, u_max, w_max, blk, blk), batches[0].masks.dtype)
    gid = np.zeros((num_lanes, u_max), np.int32)
    drow = np.zeros((num_lanes, u_max), np.int32)
    valid = np.zeros((num_lanes, u_max), bool)
    for l, lu in enumerate(lanes_units):
        for j, u in enumerate(lu):
            g, r = int(lp.unit_graph[u]), int(lp.unit_row[u])
            wg = int(batches[g].col_index.shape[1])
            col[l, j, :wg] = np.asarray(batches[g].col_index[r])
            masks[l, j, :wg] = np.asarray(batches[g].masks[r])
            gid[l, j], drow[l, j], valid[l, j] = g, r, True
    arrays = dict(col_index=col, masks=masks, graph_id=gid, dst_row=drow, valid=valid)
    slots = {"grid": u_max * w_max, "live": (col >= 0).sum(axis=(1, 2)).tolist(),
             "src_runs": [np.unique(c[c >= 0]).size for c in col]}
    return arrays, slots, lp


@pytest.fixture(scope="module")
def shgn_union():
    g = synthetic_hetgraph("acm", scale=0.05, feat_scale=0.1)
    return [batch_semantic_graph(union_graph(g), block=16)]


@pytest.fixture(scope="module")
def acm_full():
    """The metapath graphs of the han-acm benchmark configuration: ACM at
    Table-5 size, B=128, PPSP capped at 400k edges, graph seed 0."""
    _, data = build_problem(
        "acm", scale=1.0, feat_scale=1.0, block=128, max_edges=400_000, seed=0
    )
    return data.graphs


@pytest.mark.parametrize(
    "view, lanes, balanced",
    [("han", 1, True), ("han", 2, True), ("han", 4, True), ("han", 4, False),
     ("shgn", 1, True)],
    ids=["han-1", "han-2", "han-4", "han-4-naive", "shgn-1"],
)
def test_plan_matches_per_row_construction(dblp_setup, shgn_union, view, lanes, balanced):
    """build_multilane_plan (one host copy per graph, each row costing its
    live slots, counted from that copy) gives the per-row construction's
    arrays, NA slot counts and lane plan, bit for bit and dtype for dtype:
    HAN's bool masks at 1, 2 and 4 lanes, balanced and naive, and S-HGN's
    int8 type tiles at one lane."""
    batches = dblp_setup[0] if view == "han" else shgn_union
    plan = build_multilane_plan(batches, lanes, balanced=balanced)
    arrays, slots, lp = _per_row_plan(batches, lanes, balanced)
    assert arrays["masks"].dtype == (np.bool_ if view == "han" else np.int8)
    for f, want in arrays.items():
        got = np.asarray(getattr(plan, f))
        assert got.dtype == want.dtype, f
        np.testing.assert_array_equal(got, want, err_msg=f)
    assert plan.na_slots() == slots
    for f in ("unit_graph", "unit_row", "unit_cost", "unit_lane", "lane_load"):
        a, b = getattr(plan.lane_plan, f), getattr(lp, f)
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(a, b, err_msg=f)


@pytest.mark.parametrize("view", ["han", "shgn", "acm-full"])
def test_one_lane_plan_does_not_depend_on_row_costs(dblp_setup, shgn_union, acm_full, view):
    """At one lane every unit sits on lane 0 in graph-major order whatever
    the row costs: the plan balanced on live slots holds the arrays, the
    slot counts and the unit order of the plan balanced on edges, bit for
    bit, so every one-lane program (single-chip training, serving) is
    unchanged by the cost the scheduler balances."""
    batches = {"han": dblp_setup[0], "shgn": shgn_union, "acm-full": acm_full}[view]
    plan = build_multilane_plan(batches, 1)
    arrays, slots, lp = _per_row_plan(batches, 1, True, costs=_edge_costs)
    for f, want in arrays.items():
        got = np.asarray(getattr(plan, f))
        assert got.dtype == want.dtype, f
        np.testing.assert_array_equal(got, want, err_msg=f)
    assert plan.na_slots() == slots
    for f in ("unit_graph", "unit_row", "unit_lane"):
        np.testing.assert_array_equal(getattr(plan.lane_plan, f), getattr(lp, f), err_msg=f)


def test_acm_four_lane_plan_levels_live_slots(acm_full):
    """Full-size ACM on four lanes: ACM's rows cost 24 live slots in PAP
    and PSP and 19 in PPAP and PPSP, so balancing edges left the lanes at
    833 / 570 / 528 / 133 live slots of an 888-slot grid; balancing live
    slots levels them to 528 / 528 / 504 / 504 of a 27 x 24 grid."""
    plan = build_multilane_plan(acm_full, 4)
    col = np.asarray(plan.col_index)
    assert col.shape == (4, 27, 24)
    assert plan.na_slots()["live"] == [528, 528, 504, 504]
    np.testing.assert_array_equal(plan.lane_plan.lane_load, [528, 528, 504, 504])
    _, slots, _ = _per_row_plan(acm_full, 4, True, costs=_edge_costs)
    assert (slots["grid"], slots["live"]) == (888, [833, 570, 528, 133])


def test_engine_step_runs_one_lane_plan(monkeypatch):
    """One serving step under MULTIGRAPH_INTERPRET runs its NA as
    ``multilane_na`` over ``build_multilane_plan(batches, 1)`` of the
    step's graphs in slot order: the engine's plan and NA output equal
    that call's on the same operands, bit for bit."""
    graph = synthetic_hetgraph("imdb", scale=0.05, feat_scale=0.02, seed=0)
    eng = HGNNEngine(
        graph, target_type="movie", hidden=4, heads=2, num_slots=2, admission="fifo",
        backend=NABackend.MULTIGRAPH_INTERPRET, block=8, max_edges=2_000,
    )
    calls, plan_na = [], hgnn_engine._plan_na

    def spy(plan, *args, **kw):
        out = plan_na(plan, *args, **kw)
        calls.append((plan, args, out))
        return out

    monkeypatch.setattr(hgnn_engine, "_plan_na", spy)
    mps = [("movie", "director", "movie"), ("movie", "actor", "movie")]
    for rid, mp in enumerate(mps):
        eng.submit(GraphRequest(rid=rid, metapaths=[mp]))
    assert eng.step() == 2
    [(plan, (th_s, th_d, hh, fp), out)] = calls
    assert fp is None
    want_plan = build_multilane_plan([eng._batch(mp) for mp in mps], 1)
    for f in ("col_index", "masks", "graph_id", "dst_row", "valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(plan, f)), np.asarray(getattr(want_plan, f)), err_msg=f
        )
    n, n_pad = eng.n_target, want_plan.n_dst_blocks * want_plan.block
    pad = lambda t: jnp.pad(t, [(0, 0), (0, n_pad - n)] + [(0, 0)] * (t.ndim - 2))
    want = multilane_na(
        want_plan, pad(th_s), pad(th_d), pad(hh[None])[0],
        backend=NABackend.MULTIGRAPH_INTERPRET,
    )[:, :n]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_multilane_backend_rejects_unknown():
    with pytest.raises(ValueError, match="backend"):
        multilane_na(None, None, None, None, backend="nope")


def _han_forward(batches, backend):
    """HAN logits over ``batches``, all-ones features 4 wide."""
    data = HGNNData(
        features={"v": jnp.ones((batches[0].num_dst, 4))}, graphs=batches,
        target_type="v", num_classes=2,
    )
    params = init_han(jax.random.key(0), data, hidden=4, heads=2, att_dim=8)
    return han_forward(params, data, backend=backend)


@pytest.mark.parametrize(
    "call, twin",
    [
        (lambda s: multilane_na(build_multilane_plan(s[0], 1), *s[1:], backend="kernel"),
         "kernel_interpret"),
        (lambda s: _han_forward(s[0], NABackend.MULTIGRAPH), "multigraph_interpret"),
        (lambda s: _han_forward(s[0], NABackend.FUSED_FP), "fused_fp_interpret"),
        (lambda s: run_training(steps=1, backend="kernel"), "kernel_interpret"),
    ],
    ids=["multilane", "multigraph", "fused_fp", "run_training"],
)
def test_compiled_backend_without_tpu_raises(dblp_setup, call, twin):
    """A compiled Pallas backend never degrades to the interpreter: on a
    host without a TPU it raises and names the interpret variant."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match=twin):
        call(dblp_setup)


def test_balanced_beats_naive_on_skewed_workload(dblp_setup):
    batches, *_ = dblp_setup
    plan_b = build_multilane_plan(batches, 4, balanced=True)
    plan_n = build_multilane_plan(batches, 4, balanced=False)
    assert plan_b.lane_plan.imbalance() <= plan_n.lane_plan.imbalance()
    # critical path (max lane load) strictly better on DBLP's skewed graphs
    assert plan_b.lane_plan.lane_load.max() < plan_n.lane_plan.lane_load.max()


def test_multilane_unbalanced_still_correct(dblp_setup):
    batches, ths, thd, hs = dblp_setup
    plan = build_multilane_plan(batches, 4, balanced=False)
    z = multilane_na(plan, ths, thd, hs)
    for i, b in enumerate(batches):
        ref = neighbor_aggregate(
            b, ths[i, : b.num_src], thd[i, : b.num_dst], hs[: b.num_src],
            backend=NABackend.SEGMENT,
        )
        np.testing.assert_allclose(
            np.asarray(z[i, : b.num_dst]), np.asarray(ref), rtol=5e-5, atol=5e-5
        )


# --- differential tests: the training equivalence contract -----------------

GRAD_ATOL = 1e-8  # measured max |Δgrad| across backends/lanes: ~1e-9


@pytest.fixture(scope="module")
def acm_han():
    _, data = build_problem("acm", scale=0.05, block=16, max_edges=20_000)
    params = init_han(jax.random.key(0), data, hidden=8, heads=2, att_dim=16)
    return data, params


def _loss_and_grad(data, params, fwd):
    def f(p):
        logp = jax.nn.log_softmax(fwd(p).astype(jnp.float32))
        return -jnp.take_along_axis(logp, data.labels[:, None], 1).mean()

    loss, grads = jax.jit(jax.value_and_grad(f))(params)
    return float(loss), grads


def _grad_maxdiff(a, b):
    return max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def test_han_train_step_differential_backends(acm_han):
    """Jitted HAN loss+grad across NA backends: loss bit-identical, grads
    at f32 tolerance (MULTIGRAPH's custom-VJP recompute backward vs
    autodiff)."""
    data, params = acm_han
    backends = [NABackend.BLOCK, NABackend.MULTIGRAPH_INTERPRET]
    results = [
        _loss_and_grad(data, params, lambda p, b=b: han_forward(p, data, backend=b))
        for b in backends
    ]
    base_loss, base_grads = results[0]
    for loss, grads in results[1:]:
        assert loss == base_loss  # bitwise
        assert _grad_maxdiff(grads, base_grads) <= GRAD_ATOL


@pytest.mark.parametrize("lanes", [1, 2, 4])
def test_han_train_step_differential_lane_counts(acm_han, lanes):
    """Jitted HAN loss+grad through the lane-sharded kernel path under
    shard_map: loss bit-identical to the single-chip BLOCK path for every
    lane count, grads at f32 tolerance, and bit-deterministic on repeat
    (fixed topology)."""
    data, params = acm_han
    base_loss, base_grads = _loss_and_grad(
        data, params, lambda p: han_forward(p, data, backend=NABackend.BLOCK)
    )
    plan = build_multilane_plan(data.graphs, lanes)
    mesh = make_lane_mesh(lanes, 1)
    fwd = lambda p: han_forward_multilane(
        p, data, plan, mesh=mesh, backend="kernel_interpret"
    )
    loss, grads = _loss_and_grad(data, params, fwd)
    assert loss == base_loss  # bitwise, any lane count
    assert _grad_maxdiff(grads, base_grads) <= GRAD_ATOL
    loss2, grads2 = _loss_and_grad(data, params, fwd)
    assert loss2 == loss and _grad_maxdiff(grads2, grads) == 0.0  # deterministic


# --- property tests: plan builders + multigraph VJP on degenerate shapes ---


def _sg(name, src, dst, n):
    return SemanticGraph(
        name=name, src_type="v", dst_type="v",
        src_ids=np.asarray(src, np.int32), dst_ids=np.asarray(dst, np.int32),
        num_src=n, num_dst=n, path_types=("v", "v"),
    )


def _draw_batches(data_obj, *, with_degenerates: bool):
    block = data_obj.draw(st.sampled_from([4, 8]))
    n_blocks = data_obj.draw(st.integers(1, 3))
    n = block * n_blocks
    graphs = []
    if with_degenerates:
        graphs.append(_sg("empty", [], [], n))  # zero edges: all rows padded
        graphs.append(_sg("single", [n - 1], [0], n))
    n_rand = data_obj.draw(st.integers(1, 2))
    for gi in range(n_rand):
        n_edges = data_obj.draw(st.integers(0, 30))
        # unique (src, dst) pairs: block masks are boolean, duplicates
        # would break the edge-conservation invariant
        pairs = data_obj.draw(
            st.lists(st.integers(0, n * n - 1), min_size=n_edges, max_size=n_edges)
        )
        pairs = sorted(set(pairs))
        src = [p // n for p in pairs]
        dst = [p % n for p in pairs]
        graphs.append(_sg(f"rand{gi}", src, dst, n))
    return [batch_semantic_graph(s, block=block) for s in graphs], n


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_plan_builders_fuzz_invariants(data_obj):
    """build_multilane_plan over random unit tables: the one-lane plan
    holds every (graph, dst-row) as exactly one work unit in graph-major
    order, edges are conserved through the block masks, and lane loads
    account for every live slot, lane by lane."""
    batches, n = _draw_batches(data_obj, with_degenerates=True)
    lanes = data_obj.draw(st.integers(1, 4))
    G = len(batches)
    n_rows = int(batches[0].col_index.shape[0])
    total_edges = sum(int(np.asarray(b.masks).sum()) for b in batches)
    total_live = sum(int((np.asarray(b.col_index) >= 0).sum()) for b in batches)

    one = build_multilane_plan(batches, 1)
    col, gid, drow, masks = (
        np.asarray(a)[0] for a in (one.col_index, one.graph_id, one.dst_row, one.masks)
    )
    assert col.shape[0] == G * n_rows == gid.shape[0] == drow.shape[0]
    units = list(zip(gid.tolist(), drow.tolist()))
    assert units == [(g, r) for g in range(G) for r in range(n_rows)]
    assert np.asarray(one.valid).all()
    assert int(masks.sum()) == total_edges

    plan = build_multilane_plan(batches, lanes)
    valid = np.asarray(plan.valid)
    assert int(valid.sum()) == G * n_rows
    plan_units = sorted(
        (int(g), int(r))
        for g, r, v in zip(
            np.asarray(plan.graph_id).ravel(),
            np.asarray(plan.dst_row).ravel(),
            valid.ravel(),
        )
        if v
    )
    assert plan_units == units  # disjoint + complete partition
    assert int(np.asarray(plan.masks).sum()) == total_edges
    live = plan.na_slots()["live"]
    assert sum(live) == total_live == int(plan.lane_plan.lane_load.sum())
    np.testing.assert_array_equal(plan.lane_plan.lane_load, live)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_plan_lane_live_slots_within_threshold_bound(data_obj):
    """The scheduler's bound, read from the built plan: no lane holds more
    live slots than the home-lane threshold, ceil(total / L), plus the
    largest unit's live slots (an overflow unit goes to the least-loaded
    lane, which is at most the mean before it lands), for 1-4 lanes."""
    batches, _ = _draw_batches(data_obj, with_degenerates=data_obj.draw(st.booleans()))
    unit_live = np.concatenate([(np.asarray(b.col_index) >= 0).sum(axis=1) for b in batches])
    total, biggest = int(unit_live.sum()), int(unit_live.max())
    for lanes in range(1, 5):
        live = build_multilane_plan(batches, lanes).na_slots()["live"]
        assert sum(live) == total
        assert max(live) <= -(-total // lanes) + biggest, (lanes, live)


@settings(max_examples=6, deadline=None)
@given(st.data())
def test_multilane_vjp_fuzz_degenerate_shapes(data_obj):
    """Forward and VJP of the multigraph kernel over random plans with
    forced degenerate members (empty graph, single edge, all-padded rows):
    reference and kernel agree, degenerate rows are exact zeros (forward
    AND gradient), and nothing is NaN."""
    batches, n = _draw_batches(data_obj, with_degenerates=True)
    lanes = data_obj.draw(st.integers(1, 4))
    plan = build_multilane_plan(batches, lanes)
    G, H, Dh = len(batches), 2, 4
    n_pad = plan.n_dst_blocks * plan.block
    rng = np.random.default_rng(data_obj.draw(st.integers(0, 2**31)))
    hs = jnp.asarray(rng.standard_normal((n_pad, H, Dh)).astype(np.float32))
    ths = jnp.asarray(rng.standard_normal((G, n_pad, H)).astype(np.float32))
    thd = jnp.asarray(rng.standard_normal((G, n_pad, H)).astype(np.float32))

    outs, grads = {}, {}
    for be in ("reference", "kernel_interpret"):
        z = multilane_na(plan, ths, thd, hs, backend=be)
        assert np.isfinite(np.asarray(z)).all(), be
        assert np.all(np.asarray(z[0]) == 0.0), be  # empty graph: exact zeros
        outs[be] = np.asarray(z)
        g = jax.grad(
            lambda a, b, c: jnp.sum(multilane_na(plan, a, b, c, backend=be) ** 2),
            argnums=(0, 1, 2),
        )(ths, thd, hs)
        for leaf in g:
            assert np.isfinite(np.asarray(leaf)).all(), be
        assert np.all(np.asarray(g[0][0]) == 0.0), be  # d_theta_src of empty graph
        assert np.all(np.asarray(g[1][0]) == 0.0), be  # d_theta_dst of empty graph
        grads[be] = g
    np.testing.assert_allclose(outs["kernel_interpret"], outs["reference"], atol=1e-5)
    for a, b in zip(grads["kernel_interpret"], grads["reference"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
