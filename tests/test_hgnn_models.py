"""HGNN model semantics: all four Table-2 models, backend equivalence,
staged-vs-fused equivalence, and end-to-end training on synthetic ACM."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NABackend
from repro.graphs import (
    build_semantic_graphs,
    dataset_metapaths,
    dataset_target,
    relation_semantic_graphs,
    synthetic_hetgraph,
    synthetic_labels,
    union_graph,
)
from repro.models.hgnn import MODELS, cross_entropy, prepare_data
from repro.models.hgnn.han import han_forward_staged


@pytest.fixture(scope="module")
def acm():
    g = synthetic_hetgraph("acm", scale=0.12, feat_scale=0.1, seed=0)
    target, ncls = dataset_target("acm")
    labels = synthetic_labels(g, "acm")
    mp = build_semantic_graphs(g, dataset_metapaths("acm"), max_edges=20000)
    rel = relation_semantic_graphs(g)
    return g, target, ncls, labels, mp, rel


def _views(acm, name):
    """The semantic graphs each model runs on: HAN metapaths, S-HGN the
    union graph, the relation-wise models one graph per relation."""
    g, _, _, _, mp, rel = acm
    return {"HAN": mp, "S-HGN": [union_graph(g)]}.get(name, rel)


@pytest.mark.parametrize("name", ["HAN", "R-GCN", "R-GAT", "S-HGN"])
def test_model_forward_shapes_finite(acm, name):
    g, target, ncls, labels, mp, rel = acm
    data = prepare_data(g, _views(acm, name), target, ncls, labels, block=16)
    model = MODELS[name]
    params = model.init(jax.random.key(0), data)
    logits = model.forward(params, data, backend=NABackend.SEGMENT)
    assert logits.shape == (g.num_vertices(target), ncls)
    assert np.isfinite(np.asarray(logits)).all()


def test_han_backends_and_staged_agree(acm):
    g, target, ncls, labels, mp, _ = acm
    data = prepare_data(g, mp, target, ncls, labels, block=16)
    model = MODELS["HAN"]
    params = model.init(jax.random.key(1), data)
    l_seg = model.forward(params, data, backend=NABackend.SEGMENT)
    l_blk = model.forward(params, data, backend=NABackend.BLOCK)
    l_staged = han_forward_staged(params, data)
    np.testing.assert_allclose(np.asarray(l_seg), np.asarray(l_blk), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(l_seg), np.asarray(l_staged), rtol=5e-4, atol=5e-4)


def test_han_kernel_backend_matches(acm):
    """The Pallas kernel (interpret mode) is a drop-in single-graph NA
    backend: one semantic graph runs as the multigraph kernel at G=1."""
    g, target, ncls, labels, mp, _ = acm
    data = prepare_data(g, mp[:1], target, ncls, labels, block=16)
    model = MODELS["HAN"]
    params = model.init(jax.random.key(2), data)
    l_seg = model.forward(params, data, backend=NABackend.SEGMENT)
    l_ker = model.forward(params, data, backend=NABackend.MULTIGRAPH_INTERPRET)
    np.testing.assert_allclose(np.asarray(l_seg), np.asarray(l_ker), rtol=5e-4, atol=5e-4)


def test_han_multigraph_backend_matches_and_trains(acm):
    """The consolidated path (ONE fused multigraph launch for all
    relations, fwd + custom-VJP bwd) is a drop-in HAN backend."""
    g, target, ncls, labels, mp, _ = acm
    data = prepare_data(g, mp, target, ncls, labels, block=16)
    model = MODELS["HAN"]
    params = model.init(jax.random.key(2), data)
    l_blk = model.forward(params, data, backend=NABackend.BLOCK)
    l_mg = model.forward(params, data, backend=NABackend.MULTIGRAPH_INTERPRET)
    np.testing.assert_allclose(np.asarray(l_mg), np.asarray(l_blk), rtol=5e-5, atol=5e-5)

    # gradients flow through the fused backward kernel and agree with
    # autodiff of the BLOCK oracle
    def loss(p, be):
        logits = model.forward(p, data, backend=be)
        return cross_entropy(logits, data.labels)

    g_mg = jax.grad(loss)(params, NABackend.MULTIGRAPH_INTERPRET)
    g_blk = jax.grad(loss)(params, NABackend.BLOCK)
    for k in g_blk:
        np.testing.assert_allclose(
            np.asarray(g_mg[k]), np.asarray(g_blk[k]), rtol=1e-3, atol=1e-5
        )


def test_shgn_edge_bias_matters(acm):
    """S-HGN's edge-type term must influence the output, and only through
    the types: one embedding row moved moves the logits."""
    g, target, ncls, labels, _, _ = acm
    data = prepare_data(g, _views(acm, "S-HGN"), target, ncls, labels, block=16)
    model = MODELS["S-HGN"]
    params = model.init(jax.random.key(3), data)
    base = model.forward(params, data)
    bumped = dict(params)
    bumped["layer1.edge_emb"] = params["layer1.edge_emb"].at[0].add(3.0)  # the TP relation
    assert not np.allclose(np.asarray(base), np.asarray(model.forward(bumped, data)))


def test_han_trains_on_synthetic_acm(acm):
    from repro.optim import AdamWConfig, apply_updates, init_opt_state
    import jax.numpy as jnp

    g, target, ncls, labels, mp, _ = acm
    data = prepare_data(g, mp, target, ncls, labels, block=16)
    model = MODELS["HAN"]
    params = model.init(jax.random.key(4), data)
    opt = AdamWConfig(lr=5e-3, weight_decay=0.0)
    ostate = init_opt_state(params, opt)

    @jax.jit
    def step(p, s):
        def loss_fn(p):
            logits = model.forward(p, data, backend=NABackend.SEGMENT)
            return cross_entropy(logits, data.labels)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        p, s, _ = apply_updates(p, grads, s, opt, jnp.asarray(5e-3))
        return p, s, loss

    losses = []
    for _ in range(120):
        params, ostate, loss = step(params, ostate)
        losses.append(float(loss))
    # isolated vertices carry an irreducible class-prior loss; connected
    # vertices must be fit (loss well below ln(3)=1.1)
    assert losses[-1] < losses[0] * 0.8, losses[::16]
