"""S-HGN on the union graph: the union builder and its typed tiles, the
multigraph kernel's typed-tile and attention-residual options against
autodiff of the edge-list reference, and S-HGN trained as the launcher
composes it against the plain reference (CPU, kernel interpreted).

Tolerances are HAN's multigraph tests' (tests/test_hgnn_models.py):
logits within 5e-5, the f32 rounding of two orders of summation over the
same in-edges; gradients within rtol 1e-3 / atol 1e-5, the fused
backward's per-slot partials summed in another order than autodiff's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stages
from repro.core.multilane import build_multilane_plan
from repro.graphs import (
    block_csr_to_dense,
    dataset_target,
    synthetic_hetgraph,
    synthetic_labels,
    to_block_csr,
    union_graph,
)
from repro.graphs.hetgraph import SemanticGraph
from repro.kernels.seg_gat_agg_multigraph import Attention, seg_gat_agg_multigraph
from repro.launch.hgnn_train import build_problem, run_training
from repro.models.hgnn import SHGN, cross_entropy, prepare_data
from repro.models.hgnn.shgn import shgn_forward_plan, shgn_reference
from repro.optim import AdamWConfig
from repro.train import init_hgnn_train_state

LOGITS_TOL = dict(rtol=5e-5, atol=5e-5)
GRAD_TOL = dict(rtol=1e-3, atol=1e-5)
SMALL = dict(hidden=8, heads=2, layers=2, edge_dim=4)


@pytest.fixture(scope="module")
def acm():
    return synthetic_hetgraph("acm", scale=0.12, feat_scale=0.1, seed=0)


def test_union_graph_counts(acm):
    u = union_graph(acm)
    n = sum(acm.vertex_counts.values())
    assert u.num_src == u.num_dst == n and u.path_types == tuple(acm.vertex_types)
    assert u.edge_type_names == tuple(acm.edge_types) + ("self",)
    loops = u.src_ids == u.dst_ids
    assert loops.sum() == n and (u.edge_type[loops] == len(acm.edge_types)).all()
    # every relation pair kept but a same-type (v, v) pair, which its
    # self-loop replaces
    dropped = sum(int(((r.src_ids == r.dst_ids) & (r.src_type == r.dst_type)).sum())
                  for r in acm.relations.values())
    for i, rel in enumerate(acm.relations.values()):
        same = (rel.src_ids == rel.dst_ids) & (rel.src_type == rel.dst_type)
        assert (u.edge_type == i).sum() == rel.num_edges - same.sum()
    assert u.num_edges == sum(r.num_edges for r in acm.relations.values()) - dropped + n
    pairs = u.src_ids.astype(np.int64) * n + u.dst_ids
    assert np.unique(pairs).size == u.num_edges  # one type per pair


def test_union_graph_drops_same_type_self_pairs():
    from repro.graphs import HetGraph, make_relation

    g = HetGraph(
        vertex_counts={"a": 3, "b": 2},
        features={"a": np.zeros((3, 2), np.float32), "b": np.zeros((2, 2), np.float32)},
        relations={"AA": make_relation("AA", "a", "a", [0, 1, 2], [0, 2, 2]),
                   "AB": make_relation("AB", "a", "b", [1, 1], [1, 0])},
    )
    u = union_graph(g)
    got = sorted(zip(u.src_ids.tolist(), u.dst_ids.tolist(), u.edge_type.tolist()))
    # a1->b1 is vertex 1 -> 3 + 1; self-loops are type 2
    assert got == sorted([(1, 2, 0), (1, 4, 1), (1, 3, 1)] + [(v, v, 2) for v in range(5)])


@pytest.mark.parametrize("block", [16, 32])
def test_typed_tiles_convert_back_to_the_edge_list(acm, block):
    u = union_graph(acm)
    bc = to_block_csr(u, block=block)
    assert bc.masks.dtype == np.int8
    dense = block_csr_to_dense(bc)
    dst, src = np.nonzero(dense)
    assert dst.size == u.num_edges
    got = np.stack([src, dst, dense[dst, src].astype(np.int32) - 1], 1)
    want = np.stack([u.src_ids, u.dst_ids, u.edge_type], 1)
    order = lambda a: a[np.lexsort((a[:, 0], a[:, 1]))]
    np.testing.assert_array_equal(order(got), order(want))


def _typed_case(seed=0, n=40, e=150, n_types=3, B=8, H=2, Dh=8):
    """A random typed graph with self-loops, its plan, and random operands."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    keep = np.unique(src * n + dst, return_index=True)[1]
    src, dst = src[keep], dst[keep]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    src = np.concatenate([src, np.arange(n)]).astype(np.int32)
    dst = np.concatenate([dst, np.arange(n)]).astype(np.int32)
    # relation types 0 .. T-2 and the self-loop T-1 (one type: all the same)
    rel = rng.integers(0, max(n_types - 1, 1), keep.sum())
    et = np.concatenate([rel, np.full(n, n_types - 1)]).astype(np.int32)
    sg = SemanticGraph("u", "*", "*", src, dst, n, n, ("v",), et, tuple(f"t{i}" for i in range(n_types)))
    bc = to_block_csr(sg, block=B)
    n_pad = bc.num_dst_pad
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    ops = dict(ths=f(n_pad, H), thd=f(n_pad, H), hs=f(n_pad, H, Dh), bias=f(n_types, H),
               pths=f(n_pad, H), pthd=f(n_pad, H), pbias=f(n_types, H))
    r = bc.n_dst_blocks
    tables = (jnp.asarray(bc.col_index), jnp.zeros((r,), jnp.int32),
              jnp.arange(r, dtype=jnp.int32), jnp.asarray(bc.masks))
    edges = tuple(jnp.asarray(a) for a in (src, dst, et))
    return tables, edges, ops, n, n_pad


def _segment(edges, n_pad, th_s, th_d, bias, hs):
    src, dst, et = edges
    return stages.segment_softmax_aggregate(
        src, dst, jnp.ones(src.shape, bool), th_s, th_d, hs, n_pad,
        leaky_slope=0.05, edge_bias=bias[et])


@pytest.mark.parametrize("residual", [False, True], ids=["typed", "typed+residual"])
def test_kernel_typed_tiles_match_reference_autodiff(residual):
    """One joint softmax over every type of in-edge, each logit biased
    from the [T, H] table; with the residual, the previous attention is
    rebuilt in-tile from its lse: forward and VJP against autodiff of
    the edge-list reference."""
    tables, edges, ops, n, n_pad = _typed_case()
    beta = 0.05
    # the previous layer's lse, as that layer's launch returns it
    _, plse = seg_gat_agg_multigraph(*tables, ops["pths"][None], ops["pthd"][None], ops["hs"],
                                     ops["pbias"], leaky_slope=0.05, interpret=True, return_lse=True)
    prev = Attention(ops["pths"][None], ops["pthd"][None], ops["pbias"], plse)

    def f_kernel(ths, thd, hs, bias):
        out = seg_gat_agg_multigraph(
            *tables, ths[None], thd[None], hs, bias, prev if residual else None,
            leaky_slope=0.05, beta=beta if residual else None, interpret=True)
        return jnp.sum(jnp.sin(out)), out

    def f_ref(ths, thd, hs, bias):
        out = _segment(edges, n_pad, ths, thd, bias, hs)
        if residual:
            out = (1 - beta) * out + beta * _segment(edges, n_pad, ops["pths"], ops["pthd"], ops["pbias"], hs)
        return jnp.sum(jnp.sin(out)), out

    args = (ops["ths"], ops["thd"], ops["hs"], ops["bias"])
    (_, ok), gk = jax.value_and_grad(f_kernel, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    with jax.default_matmul_precision("highest"):
        (_, orf), gr = jax.value_and_grad(f_ref, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(orf), **LOGITS_TOL)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


def test_one_type_no_residual_is_bit_identical_to_boolean_masks():
    """Typed tiles of one type with a [1, H] table are the boolean masks
    with a per-graph bias: the output and the coefficients' and features'
    gradients bit for bit.  The bias gradient is the same sum taken in
    another order (per type in the kernel, over the unit's slots first;
    per graph outside, over the src columns first): equal to rounding."""
    tables, _, ops, _, _ = _typed_case(n_types=1)
    col, gid, row, tiles = tables
    masks = tiles != 0
    assert set(np.unique(np.asarray(tiles))) <= {0, 1}

    def run(m, bias):
        f = lambda ths, thd, hs, b: seg_gat_agg_multigraph(
            col, gid, row, m, ths[None], thd[None], hs, b, leaky_slope=0.05, interpret=True)
        out, vjp = jax.vjp(f, ops["ths"], ops["thd"], ops["hs"], bias)
        return [out, *vjp(jnp.cos(out))]

    typed = run(tiles, ops["bias"])          # [T = 1, H] table
    boolean = run(masks, ops["bias"])        # [G = 1, H] per-graph bias
    for a, b in zip(typed[:4], boolean[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(typed[4]), np.asarray(boolean[4]), rtol=1e-6)


@pytest.fixture(scope="module")
def shgn_problem():
    """S-HGN as ``run_training`` composes it: the union data, its one-lane
    plan, and the train state drawn from a seed."""
    _, data = build_problem("acm", scale=0.12, feat_scale=0.1, block=32, model_name="S-HGN")
    plan = build_multilane_plan(data.graphs, 1)
    state = init_hgnn_train_state(SHGN, jax.random.key(5), data, AdamWConfig(), **SMALL)
    return data, plan, state.params


def test_shgn_kernel_matches_the_plain_reference(shgn_problem):
    data, plan, params = shgn_problem

    def loss(p, fwd):
        logits = fwd(p)
        return cross_entropy(logits, data.labels), logits

    kernel = lambda p: shgn_forward_plan(p, data, plan, backend="kernel_interpret")
    reference = lambda p: shgn_reference(p, data)
    with jax.default_matmul_precision("highest"):
        (lk, zk), gk = jax.value_and_grad(loss, has_aux=True)(params, kernel)
    (lr, zr), gr = jax.value_and_grad(loss, has_aux=True)(params, reference)
    assert zk.shape == (data.features["paper"].shape[0], 3)
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zr), **LOGITS_TOL)
    np.testing.assert_allclose(float(lk), float(lr), **LOGITS_TOL)
    assert set(gk) == set(gr) == set(params)
    for k in gr:
        np.testing.assert_allclose(np.asarray(gk[k]), np.asarray(gr[k]), err_msg=k, **GRAD_TOL)


@pytest.mark.parametrize("beta", [0.0, 0.5])
def test_shgn_attention_residual_moves_layer2(shgn_problem, beta):
    """The residual is in the kernel path, and its weight is what the
    reference gives at that weight."""
    data, plan, params = shgn_problem
    base = shgn_forward_plan(params, data, plan, backend="kernel_interpret")
    with jax.default_matmul_precision("highest"):
        other = shgn_forward_plan(params, data, plan, backend="kernel_interpret", beta=beta)
    assert not np.allclose(np.asarray(base), np.asarray(other), atol=1e-6)
    ref = shgn_reference(params, data, beta=beta)
    np.testing.assert_allclose(np.asarray(other), np.asarray(ref), **LOGITS_TOL)


def test_run_training_shgn_matches_reference_and_lowers_the_loss():
    kw = dict(model_name="S-HGN", scale=0.12, feat_scale=0.1, block=32, hidden=8, heads=2,
              lr=5e-3, weight_decay=1e-4, seed=3, log=lambda *_: None)
    _, hist, meta = run_training(backend="kernel_interpret", steps=10, **kw)
    _, ref, ref_meta = run_training(backend="reference", steps=1, **kw)
    assert meta["backend"] == "kernel_interpret" and ref_meta["backend"] == "segment"
    np.testing.assert_allclose(hist[0]["loss"], ref[0]["loss"], rtol=1e-4)
    losses = [h["loss"] for h in hist]
    assert losses[-1] < 0.9 * losses[0], losses
    assert meta["na_layers"] == 3 and meta["na_edge_types"] == 8
    live, grid = meta["na_slots"]["live"][0], meta["na_slots"]["grid"]
    assert 0 < live < grid
