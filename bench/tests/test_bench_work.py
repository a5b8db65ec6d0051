"""``bench/work.py`` counts from real edges; ``bench/graphgen.py`` and
``bench/reference.py`` agree with the program at a tiny size."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import graphgen  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

# a tiny edge list, 4 vertices: graph A has 5 edges, graph B 2
EDGES_A = [(0, 1), (1, 1), (2, 1), (3, 0), (2, 3)]
EDGES_B = [(0, 2), (3, 2)]


def test_na_counts_by_hand():
    e = [len(EDGES_A), len(EDGES_B)]
    n, heads, hidden = 4, 2, 3  # H*Dh = 6
    # forward: E*H*(2*Dh+6) per graph plus N*H*Dh for the division
    assert work.na_forward(e, n, heads, hidden)[0] == (5 * 2 * 12 + 24) + (2 * 2 * 12 + 24)
    # bytes: 8 per edge; per graph src rows + theta_src (4*8*4), theta_dst (4*2*4), out (4*6*4)
    assert work.na_forward(e, n, heads, hidden)[1] == (40 + 256) + (16 + 256)
    assert work.na_backward(e, n, heads, hidden)[0] == 5 * 2 * 24 + 2 * 2 * 24
    # reads 8 per edge + 4*(6+2+2+6+2+2)*4, writes 4*(6+2+2)*4
    assert work.na_backward(e, n, heads, hidden)[1] == (40 + 320 + 160) + (16 + 320 + 160)


def test_model_flops_by_hand():
    e = [len(EDGES_A), len(EDGES_B)]
    got = work.han_forward_flops(e, 4, 5, 2, 3, 7, 3)
    assert got["fp"] == 2 * 4 * 5 * 6 + 4 * 6
    assert got["theta"] == 2 * 2 * 2 * 4 * 6
    assert got["na"] == 216
    assert got["fusion"] == 2 * (24 + 2 * 4 * 6 * 7 + 2 * 4 * 7 + 2 * 4 * 7) + 2 * 2 * 4 * 6
    assert got["out"] == 2 * 4 * 6 * 3 + 4 * 3
    assert got["total"] == 264 + 192 + 216 + 1040 + 156
    serve = work.han_forward_flops(e, 4, 5, 2, 3, 7, None)
    assert serve["total"] == got["total"] - got["out"]
    step = work.han_train_step_flops(e, 4, 5, 2, 3, 7, 3)
    assert step["fp_bwd"] == got["fp"] and step["na_bwd"] == work.na_backward(e, 4, 2, 3)[0]


@pytest.mark.parametrize("dataset", ["acm", "imdb"])
def test_counts_ignore_block_size(dataset):
    from repro.launch.hgnn_train import build_problem

    counts = {}
    for block in (8, 16):
        _, data = build_problem(dataset, scale=0.05, feat_scale=0.05, block=block,
                                max_edges=3000, seed=0)
        # real edges as the block layout holds them: set mask bits
        edges = [int(np.asarray(b.masks).sum()) for b in data.graphs]
        assert edges == [b.num_edges for b in data.graphs]
        n = int(data.labels.shape[0])
        counts[block] = (work.na_forward(edges, n, 8, 8), work.na_backward(edges, n, 8, 8),
                         work.han_train_step_flops(edges, n, 64, 8, 8, 128, 3)["total"])
    assert counts[8] == counts[16]


@pytest.mark.parametrize("dataset", ["acm", "imdb"])
def test_graphgen_matches_the_program(dataset):
    from repro.graphs import build_semantic_graph, dataset_metapaths, synthetic_hetgraph, synthetic_labels

    spec = common.load_json("configs", f"han-{dataset}")["graph"]
    prog = synthetic_hetgraph(dataset, scale=0.08, feat_scale=0.05, seed=0)
    mine = graphgen.hetgraph(spec, seed=0, scale=0.08, feat_scale=0.05)
    assert mine.counts == dict(prog.vertex_counts)
    for t in prog.features:
        np.testing.assert_array_equal(mine.features[t], prog.features[t])
    np.testing.assert_array_equal(graphgen.labels(mine, spec, seed=0), synthetic_labels(prog, dataset, seed=0))
    train = graphgen.training_graphs(mine, spec, 2000)
    for i, mp in enumerate(dataset_metapaths(dataset)):
        for seed in (i, graphgen.serving_seed(mp)):
            sg = build_semantic_graph(prog, mp, max_edges=2000, seed=seed)
            src, dst = graphgen.metapath_edges(mine, mp, max_edges=2000, seed=seed)
            np.testing.assert_array_equal(src, sg.src_ids)
            np.testing.assert_array_equal(dst, sg.dst_ids)
        np.testing.assert_array_equal(train[graphgen.metapath_name(mp)][0],
                                      build_semantic_graph(prog, mp, max_edges=2000, seed=i).src_ids)


def tiny_context(cell: str, **kw) -> common.Context:
    ctx = run.make_context(cell, 2147483659, 0.3, False, backend="kernel_interpret",
                           scale=0.04, feat_scale=0.05, **kw)
    ctx.config = copy.deepcopy(ctx.config)
    ctx.config.update(block=16, max_edges=3000)
    return ctx


def test_reference_agrees_with_the_programs_step():
    drv = common.load_module("drivers", "train")
    ctx = tiny_context("han-acm.train")
    prog = drv.Program(ctx, common.Phases(ctx))
    _, mine = prog.first_steps(prog.init_state(ctx.seed), prog.batch(ctx.seed), 3)
    ref = drv.reference_readings(ctx, drv.reference_inputs(ctx, prog.graph_names), ctx.seed)
    gaps = drv.compare(mine, ref)
    assert all(v < 1e-4 for v in gaps.values()), gaps
    assert mine["losses"][2] < mine["losses"][0]
