"""Stage scopes of the HAN and S-HGN training steps (DESIGN.md §12):
every stage's ``jax.named_scope`` reaches the compiled step's ``op_name``
metadata, forward and backward, and the scopes change nothing but
metadata."""
import contextlib
import re

import jax
import pytest

from repro.core.multilane import build_multilane_plan, place_plan
from repro.data import SyntheticHGNNData
from repro.dist.sharding import lane_axes, make_rules, use_rules
from repro.launch.hgnn_train import build_problem
from repro.launch.mesh import make_lane_mesh
from repro.models.hgnn import HAN, SHGN, han_forward_multilane
from repro.models.hgnn.shgn import shgn_forward_plan
from repro.optim import AdamWConfig
from repro.train import init_hgnn_train_state, make_hgnn_train_step

# stage -> the name-stack forms it must take: forward, and the backward
# that autodiff transposes from it (the optimizer runs after the grads)
STAGES = {
    "fp": ("jvp(fp)", "transpose(jvp(fp))"),
    "theta": ("jvp(theta)", "transpose(jvp(theta))"),
    "na": ("jvp(na)", "transpose(jvp(na))"),
    "fusion": ("jvp(fusion)", "transpose(jvp(fusion))"),
    "head": ("jvp(head)", "transpose(jvp(head))"),
    "optimizer": ("optimizer",),
}


def compiled_step_hlo() -> str:
    """Optimized HLO of the tiny ACM training step, as the launcher builds
    it (one lane, the multigraph kernel interpreted)."""
    _, data = build_problem("acm", scale=0.04, feat_scale=0.05, block=16,
                            max_edges=5000, seed=0)
    mesh, rules = make_lane_mesh(1, 1), make_rules(parallelism="lanes")
    axes = lane_axes(rules)
    plan = place_plan(build_multilane_plan(data.graphs, 1), mesh, axes)
    opt = AdamWConfig(lr=5e-3, weight_decay=0.0)
    fwd = lambda p: han_forward_multilane(
        p, data, plan, mesh=mesh, lane_axes=axes, backend="kernel_interpret")
    step = jax.jit(make_hgnn_train_step(fwd, data, opt))
    n = int(data.labels.shape[0])
    with mesh, use_rules(rules):
        state = init_hgnn_train_state(HAN, jax.random.key(0), data, opt,
                                      hidden=8, heads=2, att_dim=16)
        batch = SyntheticHGNNData(num_vertices=n, batch_size=n, seed=0).next()
        return step.lower(state, batch).compile().as_text()


def shgn_step_op_names() -> set[str]:
    """op_names of the tiny S-HGN training step, as the launcher builds it."""
    _, data = build_problem("acm", scale=0.04, feat_scale=0.05, block=16, seed=0,
                            model_name="S-HGN")
    mesh, rules = make_lane_mesh(1, 1), make_rules(parallelism="lanes")
    plan = place_plan(build_multilane_plan(data.graphs, 1), mesh, lane_axes(rules))
    opt = AdamWConfig(lr=5e-4, weight_decay=1e-4)
    fwd = lambda p: shgn_forward_plan(p, data, plan, backend="kernel_interpret")
    step = jax.jit(make_hgnn_train_step(fwd, data, opt))
    n = int(data.labels.shape[0])
    with mesh, use_rules(rules):
        state = init_hgnn_train_state(SHGN, jax.random.key(0), data, opt,
                                      hidden=4, heads=2, edge_dim=4)
        batch = SyntheticHGNNData(num_vertices=n, batch_size=n, seed=0).next()
        hlo = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def without_metadata(hlo: str) -> str:
    """HLO text without op metadata and the stack-frame tables it indexes."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", hlo, flags=re.S)


@pytest.fixture(scope="module")
def op_names() -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', compiled_step_hlo()))


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_stage_scope_in_op_name_metadata(op_names, stage):
    for form in STAGES[stage]:
        assert any(f"/{form}/" in name for name in op_names), (stage, form)


@pytest.fixture(scope="module")
def shgn_op_names() -> set[str]:
    return shgn_step_op_names()


@pytest.mark.parametrize("stage", ["fp", "theta", "na", "head"])
def test_shgn_stage_scope_in_op_name_metadata(shgn_op_names, stage):
    for form in STAGES[stage]:
        assert any(f"/{form}/" in name for name in shgn_op_names), (stage, form)


def test_scopes_change_metadata_only(monkeypatch):
    scoped = compiled_step_hlo()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = compiled_step_hlo()
    assert "/jvp(na)/" in scoped and "/jvp(na)/" not in bare
    assert without_metadata(scoped) == without_metadata(bare)
