"""FP+NA stage-fusion megakernel (DESIGN.md §10): kernel vs reference,
VJP gradcheck, multigraph equivalence, multilane + sharded backends,
HAN end-to-end, and the serving engine's cache-aware dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NABackend, batch_semantic_graph
from repro.core.fusion import FusedFPInputs, _pad_rows
from repro.core.multilane import build_multilane_plan, multilane_na, multilane_na_sharded
from repro.graphs import (
    build_semantic_graphs,
    dataset_metapaths,
    dataset_target,
    synthetic_hetgraph,
    synthetic_labels,
)
from repro.kernels import fused_fp_na_reference, seg_gat_agg_fused_fp
from repro.models.hgnn import MODELS, cross_entropy, prepare_data

B, H, DH = 8, 2, 4


def _rand_tables(rng, *, units=6, width=3, nblk=5, graphs=3, tables=2, din=12):
    """Random flat unit tables + fused-FP operands (multi weight table)."""
    col = rng.integers(-1, nblk, (units, width)).astype(np.int32)
    col[:, 0] = np.maximum(col[:, 0], 0)  # at least one live block per unit
    gid = rng.integers(0, graphs, (units,)).astype(np.int32)
    row = rng.integers(0, nblk, (units,)).astype(np.int32)
    wsel = rng.integers(0, tables, (graphs,)).astype(np.int32)
    masks = rng.random((units, width, B, B)) < 0.6
    masks[:, 0, 0, 0] = True  # no fully-dead dst rows in live blocks
    n = nblk * B
    x = rng.standard_normal((n, din)).astype(np.float32)
    w = (rng.standard_normal((tables, din, H * DH)) / np.sqrt(din)).astype(np.float32)
    b = rng.standard_normal((tables, H * DH)).astype(np.float32) * 0.1
    a_s = rng.standard_normal((graphs, H, DH)).astype(np.float32)
    a_d = rng.standard_normal((graphs, H, DH)).astype(np.float32)
    bias = rng.standard_normal((graphs, H)).astype(np.float32) * 0.3
    return tuple(map(jnp.asarray, (col, gid, row, wsel, masks, x, w, b, a_s, a_d, bias)))


def test_fused_fp_forward_matches_reference_multi_table():
    args = _rand_tables(np.random.default_rng(0))
    out = seg_gat_agg_fused_fp(*args, interpret=True)
    ref = fused_fp_na_reference(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-6)


def test_fused_fp_matches_multigraph_on_materialized_h():
    """Fused FP+NA == project-then-multigraph-NA (the tentpole identity).

    Tolerance is pinned loose-ish (rtol 1e-4): the kernel reassociates the
    projection matmul per tile, so it is NOT bit-identical to a single
    HBM-materialized x@W."""
    from repro.kernels import seg_gat_agg_multigraph

    col, gid, row, wsel, masks, x, w, b, a_s, a_d, bias = _rand_tables(
        np.random.default_rng(1), tables=1)
    out = seg_gat_agg_fused_fp(
        col, gid, row, wsel, masks, x, w, b, a_s, a_d, bias, interpret=True)
    h = (x @ w[0] + b[0]).reshape(x.shape[0], H, DH)
    th_s = jnp.einsum("nhd,ghd->gnh", h, a_s)
    th_d = jnp.einsum("nhd,ghd->gnh", h, a_d)
    mg = seg_gat_agg_multigraph(
        col, gid, row, masks, th_s, th_d, h, bias, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(mg), rtol=1e-4, atol=1e-6)


def test_fused_fp_vjp_matches_reference_autodiff():
    args = _rand_tables(np.random.default_rng(2))
    fixed, diff = args[:5], args[5:]

    def loss_k(x, w, b, a_s, a_d, bias):
        return jnp.sin(seg_gat_agg_fused_fp(
            *fixed, x, w, b, a_s, a_d, bias, interpret=True)).sum()

    def loss_r(x, w, b, a_s, a_d, bias):
        return jnp.sin(fused_fp_na_reference(*fixed, x, w, b, a_s, a_d, bias)).sum()

    gk = jax.grad(loss_k, argnums=tuple(range(6)))(*diff)
    gr = jax.grad(loss_r, argnums=tuple(range(6)))(*diff)
    for name, a, e in zip(("x", "w", "b", "a_src", "a_dst", "bias"), gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-5, err_msg=name)


def test_fused_fp_dead_unit_is_zero_with_zero_grads():
    col, gid, row, wsel, masks, x, w, b, a_s, a_d, bias = _rand_tables(
        np.random.default_rng(3), units=4)
    col = col.at[2].set(-1)  # unit 2: every source block dead

    def f(x_):
        return seg_gat_agg_fused_fp(
            col, gid, row, wsel, masks, x_, w, b, a_s, a_d, bias, interpret=True)

    out = f(x)
    assert np.all(np.asarray(out[2 * B:3 * B]) == 0.0)
    g_x = jax.grad(lambda x_: f(x_)[2 * B:3 * B].sum())(x)
    np.testing.assert_allclose(np.asarray(g_x), 0.0, atol=1e-7)


@pytest.fixture(scope="module")
def acm():
    g = synthetic_hetgraph("acm", scale=0.12, feat_scale=0.1, seed=0)
    target, ncls = dataset_target("acm")
    labels = synthetic_labels(g, "acm")
    mp = build_semantic_graphs(g, dataset_metapaths("acm"), max_edges=20000)
    return g, target, ncls, labels, mp


def test_neighbor_aggregate_multi_fused_fp_matches_multigraph(acm):
    g, target, ncls, labels, mp = acm
    data = prepare_data(g, mp, target, ncls, labels, block=16)
    rng = np.random.default_rng(0)
    gn = len(data.graphs)
    x = data.features[target]
    din, heads, dh = x.shape[1], 2, 4
    w = jnp.asarray((rng.standard_normal((din, heads * dh)) / np.sqrt(din)
                     ).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((heads * dh,)).astype(np.float32))
    a_s = jnp.asarray(rng.standard_normal((gn, heads, dh)).astype(np.float32))
    a_d = jnp.asarray(rng.standard_normal((gn, heads, dh)).astype(np.float32))
    plan = build_multilane_plan(data.graphs, 1)
    n_pad = plan.n_dst_blocks * plan.block
    fp = FusedFPInputs.shared(_pad_rows(x, n_pad), w, b, a_s, a_d)
    z_f = multilane_na(plan, None, None, None, backend=NABackend.FUSED_FP_INTERPRET, fp=fp)
    h = _pad_rows((x @ w + b).reshape(x.shape[0], heads, dh), n_pad)
    th_s = jnp.einsum("nhd,ghd->gnh", h, a_s)
    th_d = jnp.einsum("nhd,ghd->gnh", h, a_d)
    z_m = multilane_na(plan, th_s, th_d, h, backend=NABackend.MULTIGRAPH_INTERPRET)
    np.testing.assert_allclose(np.asarray(z_f), np.asarray(z_m), rtol=1e-4, atol=1e-6)


def test_neighbor_aggregate_multi_fused_fp_requires_fp(acm):
    g, target, ncls, labels, mp = acm
    data = prepare_data(g, mp, target, ncls, labels, block=16)
    with pytest.raises(ValueError, match="fp"):
        multilane_na(
            build_multilane_plan(data.graphs, 1), None, None, None,
            backend=NABackend.FUSED_FP_INTERPRET)


@pytest.fixture(scope="module")
def dblp_fp():
    rng = np.random.default_rng(0)
    g = synthetic_hetgraph("dblp", scale=0.05, feat_scale=0.1)
    sgs = build_semantic_graphs(g, dataset_metapaths("dblp"))
    batches = [batch_semantic_graph(s, block=16) for s in sgs]
    G, ns = len(batches), batches[0].num_src
    ns_pad = max(((ns + 15) // 16) * 16, batches[0].num_dst_pad)
    din = 24
    x = np.zeros((ns_pad, din), np.float32)
    x[:ns] = rng.standard_normal((ns, din))
    w = (rng.standard_normal((din, H * DH)) / np.sqrt(din)).astype(np.float32)
    b = rng.standard_normal((H * DH,)).astype(np.float32) * 0.1
    a_s = rng.standard_normal((G, H, DH)).astype(np.float32)
    a_d = rng.standard_normal((G, H, DH)).astype(np.float32)
    fp = FusedFPInputs.shared(*map(jnp.asarray, (x, w, b, a_s, a_d)))
    h = (fp.x @ jnp.asarray(w) + jnp.asarray(b)).reshape(ns_pad, H, DH)
    ths = jnp.einsum("nhd,ghd->gnh", h, jnp.asarray(a_s))
    thd = jnp.einsum("nhd,ghd->gnh", h, jnp.asarray(a_d))
    return batches, fp, ths, thd, h


def test_multilane_fused_fp_matches_reference(dblp_fp):
    batches, fp, ths, thd, h = dblp_fp
    plan = build_multilane_plan(batches, 4)
    ref = multilane_na(plan, ths, thd, h)
    out = multilane_na(plan, None, None, None, backend="fused_fp_interpret", fp=fp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_multilane_sharded_fused_fp_matches(dblp_fp):
    from repro.launch.mesh import make_lane_mesh

    batches, fp, ths, thd, h = dblp_fp
    plan = build_multilane_plan(batches, 4)
    ref = multilane_na(plan, ths, thd, h)
    mesh = make_lane_mesh(1, 1)
    out = multilane_na_sharded(
        plan, None, None, None, mesh=mesh, backend="fused_fp_interpret", fp=fp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_han_fused_fp_backend_matches_and_trains(acm):
    """The megakernel is a drop-in HAN backend: one launch per layer, h'
    never materialized, grads agree with the multigraph path."""
    g, target, ncls, labels, mp = acm
    data = prepare_data(g, mp, target, ncls, labels, block=16)
    model = MODELS["HAN"]
    params = model.init(jax.random.key(2), data)
    l_mg = model.forward(params, data, backend=NABackend.MULTIGRAPH_INTERPRET)
    l_ff = model.forward(params, data, backend=NABackend.FUSED_FP_INTERPRET)
    np.testing.assert_allclose(np.asarray(l_ff), np.asarray(l_mg), rtol=5e-5, atol=5e-5)

    def loss(p, be):
        return cross_entropy(model.forward(p, data, backend=be), data.labels)

    g_ff = jax.grad(loss)(params, NABackend.FUSED_FP_INTERPRET)
    g_mg = jax.grad(loss)(params, NABackend.MULTIGRAPH_INTERPRET)
    for k in g_mg:
        np.testing.assert_allclose(
            np.asarray(g_ff[k]), np.asarray(g_mg[k]), rtol=1e-3, atol=1e-5, err_msg=k)


# -- serving: cache-aware dispatch ----------------------------------------


def test_engine_fused_fp_matches_multigraph_and_bypasses_on_cache_hit():
    from repro.serve import GraphRequest, HGNNEngine

    g = synthetic_hetgraph("acm", scale=0.1, feat_scale=0.1, seed=0)
    mps = [("paper", "author", "paper"), ("paper", "subject", "paper")]

    def run(backend, prewarm=False):
        eng = HGNNEngine(g, target_type="paper", backend=backend,
                         max_edges=6_000, seed=0)
        if prewarm:
            eng.cache.project("paper", eng.features["paper"],
                              eng.params["w_fp"]["paper"], eng.params["b_fp"]["paper"])
        for rid in range(3):
            eng.submit(GraphRequest(rid=rid, metapaths=list(mps)))
        eng.run()
        return eng

    em = run(NABackend.MULTIGRAPH_INTERPRET)
    ef = run(NABackend.FUSED_FP_INTERPRET)
    ew = run(NABackend.FUSED_FP_INTERPRET, prewarm=True)

    # cache miss: every step went through the megakernel, same numbers
    assert ef.fused_steps == ef.steps_run and ef.fused_cache_bypasses == 0
    # full-table cache hit: FP is sunk cost -> projected multigraph path
    assert ew.fused_steps == 0 and ew.fused_cache_bypasses == ew.steps_run
    assert ew.cache.table_coverage("paper", ew.n_target) == 1.0
    for a, b_, c in zip(em.finished, ef.finished, ew.finished):
        np.testing.assert_allclose(
            np.asarray(b_.result), np.asarray(a.result), rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(c.result), np.asarray(a.result), rtol=1e-4, atol=1e-6)
    m = ef.metrics()
    assert m["fused_steps"] == ef.fused_steps
    assert "fused_cache_bypasses" in m
