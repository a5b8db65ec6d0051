"""The S-HGN cell: ``bench/work_shgn.py`` counts from real edges, the
reference's union graph and weights are the program's, and whole runs at
a tiny size on the CPU (the Pallas kernel interpreted, the widths cut)
come out correct, and not correct with a fault planted in the program or
with the control or a planted fault in the reference's place."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import common  # noqa: E402
import graphgen  # noqa: E402
import reference_shgn  # noqa: E402
import work_shgn  # noqa: E402
from tiny import execute, tiny  # noqa: E402

CELL = "shgn-acm.train"
# a tiny typed graph, 3 vertices, 2 types: 4 real edges (the self-loops included)
EDGES = [(0, 1, 0), (2, 1, 0), (0, 0, 1), (1, 1, 1)]


def small(seconds: float = 0.3):
    """The cell at a tiny size, its widths cut so the interpreter is quick."""
    ctx = tiny(CELL, seconds)
    ctx.config.update(hidden=4, heads=2, edge_dim=4)
    return ctx


def test_na_counts_by_hand():
    cfg = {"heads": 2, "hidden": 3, "layers": 2, "graph": {"num_classes": 5}}
    spec = work_shgn.layers(cfg)
    assert spec == [(2, 3, False), (2, 3, True), (1, 5, False)]
    e, n, t = len(EDGES), 3, 2
    # forward: E*H*(2*Dh+7) + N*H*Dh, the residual layer + 3*E*H
    fl = [4 * 2 * 13 + 18, 4 * 2 * 13 + 18 + 24, 4 * 1 * 17 + 15]
    assert work_shgn.na_forward(e, n, t, spec)[0] == sum(fl)
    # bytes: 12 per edge, src rows + theta_src 3*(6+2), theta_dst 3*2,
    # out 3*6, table 2*2; the residual + E*H
    by = [(12 + 24 + 6 + 18 + 4), (12 + 24 + 6 + 18 + 4 + 8), (12 + 18 + 3 + 15 + 2)]
    assert work_shgn.na_forward(e, n, t, spec)[1] == 4 * sum(by)
    assert work_shgn.na_backward(e, n, t, spec)[0] == (4 * 2 * 25) * 2 + 24 + 4 * 1 * 33
    # reads 12 per edge + N*(2f + 4H) + table, writes N*(f + 2H) + table
    rb = [12 + 3 * 20 + 4 + 3 * 10 + 4, 12 + 3 * 20 + 4 + 3 * 10 + 4 + 8, 12 + 3 * 14 + 2 + 3 * 7 + 2]
    assert work_shgn.na_backward(e, n, t, spec)[1] == 4 * sum(rb)


def test_step_flops_by_hand():
    cfg = {"heads": 2, "hidden": 3, "layers": 2, "edge_dim": 2, "graph": {"num_classes": 5}}
    dims, counts = {"a": 4, "b": 6}, {"a": 2, "b": 1}
    got = work_shgn.train_step_flops(len(EDGES), dims, counts, 2, 2, cfg)
    assert got["fp_fwd"] == (2 * 2 * 4 * 3 + 6) + (2 * 1 * 6 * 3 + 3)
    assert got["proj_fwd"] == 2 * 3 * (3 * 6 + 6 * 6 + 6 * 5)
    # a.g twice per layer; the bias table: T*(2*K*H*K + 2*H*K)
    assert got["theta_fwd"] == 4 * 3 * (6 + 6 + 5) + 2 * (2 * (2 * 2 * 2 * 2 + 2 * 2 * 2) + (2 * 2 * 1 * 2 + 2 * 1 * 2))
    assert got["epilogue_fwd"] == 18 + (18 + 18) + (2 * 3 * 6 * 5 + 15 + 3 * 2 * 5)
    assert got["na_bwd"] == work_shgn.na_backward(4, 3, 2, work_shgn.layers(cfg))[0]
    n_params = (4 * 3 + 3) + (6 * 3 + 3) + sum(
        d * h * dh + 2 * h * dh + 2 * 2 + 2 * h * 2 + h * 2
        for d, (h, dh) in ((3, (2, 3)), (6, (2, 3)), (6, (1, 5)))) + 6 * 5
    assert work_shgn.n_params(dims, 2, cfg) == n_params
    assert got["update"] == 12 * n_params and got["total"] == sum(
        v for k, v in got.items() if k != "total")


def test_union_edges_and_weights_match_the_program():
    from repro.graphs import synthetic_hetgraph, union_graph
    from repro.models.hgnn import SHGN, prepare_data

    spec = common.load_json("configs", "shgn-acm")["graph"]
    prog = union_graph(synthetic_hetgraph("acm", scale=0.08, feat_scale=0.05, seed=0))
    types, offsets, n, src, dst, et, names = reference_shgn.union_edges(
        graphgen.hetgraph(spec, seed=0, scale=0.08, feat_scale=0.05), spec)
    assert tuple(types) == prog.path_types and tuple(names) == prog.edge_type_names
    assert n == prog.num_dst
    for a, b in ((src, prog.src_ids), (dst, prog.dst_ids), (et, prog.edge_type)):
        np.testing.assert_array_equal(a, b)

    g = synthetic_hetgraph("acm", scale=0.08, feat_scale=0.05, seed=0)
    data = prepare_data(g, [prog], "paper", 3, with_blocks=False)
    mine = SHGN.init(jax.random.key(7), data, hidden=4, heads=2, layers=2, edge_dim=4)
    ref = reference_shgn.init_shgn(7, data.feature_dims, len(names), hidden=4, heads=2,
                                   layers=2, edge_dim=4, n_classes=3)
    assert set(mine) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(mine[k]), np.asarray(ref[k]), err_msg=k)


def test_sound_run_is_correct():
    out = execute(small())
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_step_ms"]["value"] > 0 and out["attempted"] > 0


def test_half_the_batch_left_out(monkeypatch):
    drv = common.load_module("drivers", "train_shgn")
    monkeypatch.setattr(drv.Program, "batch",
                        lambda self, seed: {"idx": jnp.arange(self.n_target // 2, dtype=jnp.int32)})
    out = execute(small())
    assert not out["correct"], out["checks"]


def test_attention_residual_left_out(monkeypatch):
    drv = common.load_module("drivers", "train_shgn")
    real = drv.shgn_forward_plan
    monkeypatch.setattr(drv, "shgn_forward_plan", lambda *a, **kw: real(*a, **dict(kw, beta=0.0)))
    out = execute(small())
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("what", ["control_bf16", "fault_beta_0", "fault_per_type_softmax"])
def test_control_and_reference_faults_are_not_correct(what):
    """In the program's place: the reference in bfloat16, or with the
    residual left out, or with a softmax per edge type."""
    drv = common.load_module("drivers", "train_shgn")
    ctx = small()
    inputs = drv.reference_inputs(ctx)
    ref = drv.reference_readings(ctx, inputs, ctx.seed)
    fault = {"control_bf16": dict(dtype=jnp.bfloat16), "fault_beta_0": dict(beta=0.0),
             "fault_per_type_softmax": dict(per_type=True)}[what]
    got = drv.train.compare(drv.reference_readings(ctx, inputs, ctx.seed, **fault), ref)
    assert any(got[k] > lim for k, lim in ctx.cell["limits"].items()), got
