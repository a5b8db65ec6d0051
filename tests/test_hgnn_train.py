"""Mesh-scale HGNN training launcher: convergence, fault injection,
elastic lane resharding.

The numerical contract (DESIGN.md §11, measured in tests/test_multilane):
checkpoint RESTORE is bit-identical for any lane count (leaves are
logical arrays), same-topology crash-resume replays bit-identically
(counter-based data state), and a trajectory continued on a different
lane count tracks the original to f32 tolerance (the lane partition
regroups the cross-unit gradient reduction).
"""
import jax
import numpy as np
import pytest

from repro.core.multilane import build_multilane_plan
from repro.launch.hgnn_train import build_problem, run_training

_SILENT = lambda *_: None

# tiny-but-real problem: every run here shares it (fixture-free so each
# test documents its own configuration)
_KW = dict(
    dataset="acm", model_name="HAN", hidden=8, heads=2, scale=0.05,
    block=16, max_edges=20_000, log=_SILENT, log_every=1,
)


def _leaves(state):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]


def test_han_loss_decreases_lane_sharded_kernel():
    """HAN trains with decreasing loss through the lane-sharded fused
    kernel path (the tentpole configuration, in interpret mode on CPU)."""
    state, history, meta = run_training(steps=12, lanes=2, backend="kernel_interpret", **_KW)
    assert history[-1]["loss"] < history[0]["loss"]
    assert meta["plan_lanes"] == 2
    assert meta["backend"] == "kernel_interpret"
    _, data = build_problem("acm", scale=_KW["scale"], block=_KW["block"],
                            max_edges=_KW["max_edges"])
    assert meta["na_slots"] == build_multilane_plan(data.graphs, 2).na_slots()
    assert len(meta["na_slots"]["live"]) == 2


def test_lane_imbalance_reads_live_slots_per_lane():
    """run_training's meta reports ``lane_imbalance``: the largest lane's
    live NA slots over the mean of the plan's lanes."""
    _, _, meta = run_training(steps=1, lanes=2, backend="reference", **_KW)
    live = meta["na_slots"]["live"]
    assert len(live) == 2
    assert meta["lane_imbalance"] == max(live) / np.mean(live)
    assert meta["lane_imbalance"] >= 1.0


def test_rgat_loss_decreases():
    state, history, meta = run_training(
        steps=8, lanes=1, backend="kernel_interpret", **{**_KW, "model_name": "R-GAT"},
    )
    assert history[-1]["loss"] < history[0]["loss"]


def test_crash_at_step_k_resume_bit_identical(tmp_path):
    """Fault injection: crash at step k, relaunch, resume from the atomic
    checkpoint — final params bit-identical to an uninterrupted run."""
    kw = dict(steps=10, lanes=2, backend="kernel_interpret", ckpt_every=4, **_KW)

    ref_state, _, _ = run_training(ckpt_dir=str(tmp_path / "ref"), **kw)

    crashed = str(tmp_path / "crashed")
    with pytest.raises(RuntimeError, match="injected failure at step 7"):
        run_training(ckpt_dir=crashed, crash_at=7, **kw)
    resumed_state, history, _ = run_training(ckpt_dir=crashed, **kw)

    assert history[0]["step"] == 4  # resumed from the step-4 checkpoint
    for a, b in zip(_leaves(ref_state), _leaves(resumed_state)):
        np.testing.assert_array_equal(a, b)


def test_elastic_reshard_roundtrip_lane_mesh(tmp_path):
    """Checkpoint written on an L=2 lane mesh restores bit-identically
    onto L=4 and L=1 meshes (leaves are logical arrays; param_shardings
    re-derives placement from the same logical axes), and the continued
    trajectory tracks the L=2 one to f32 tolerance."""
    ckpt = str(tmp_path / "ckpt")
    kw = dict(backend="kernel_interpret", ckpt_every=3, **_KW)

    state2, _, _ = run_training(steps=6, lanes=2, ckpt_dir=ckpt, **kw)
    ref2 = _leaves(state2)

    # restore-only relaunches (steps already complete): any lane count
    for lanes in (4, 1):
        restored, _, _ = run_training(steps=6, lanes=lanes, ckpt_dir=ckpt, **kw)
        for a, b in zip(ref2, _leaves(restored)):
            np.testing.assert_array_equal(a, b)

    # continuation on the L=4 mesh vs uninterrupted L=2
    cont4, _, _ = run_training(steps=9, lanes=4, ckpt_dir=ckpt, **kw)
    ref9, _, _ = run_training(steps=9, lanes=2, ckpt_dir=str(tmp_path / "ref9"), **kw)
    for a, b in zip(_leaves(ref9), _leaves(cont4)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
