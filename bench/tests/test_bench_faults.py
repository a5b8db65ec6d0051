"""Whole training runs with the timed path broken underneath come out not
correct.

Each test drives ``bench/run.py``'s ``execute`` on the CPU at a tiny size
(the look for a chip skipped, the Pallas kernel interpreted) with one
fault planted in the program, and sees ``correct`` false; the sound run
of the same size comes out true.  The limits are the cells' own.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

# the four-lane cell needs four devices: on a CPU host, four virtual ones
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()

import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import common  # noqa: E402
import graphgen  # noqa: E402
from tiny import execute, tiny  # noqa: E402


def test_train_sound_run_is_correct():
    out = execute(tiny("han-acm.train", 0.3))
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_step_ms"]["value"] > 0 and out["attempted"] > 0


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    drv = common.load_module("drivers", "train")
    real = drv.make_hgnn_train_step

    def unchanged(*a, **kw):
        step = real(*a, **kw)

        def f(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        return f

    monkeypatch.setattr(drv, "make_hgnn_train_step", unchanged)
    out = execute(tiny("han-acm.train", 0.3))
    assert not out["correct"]
    assert out["checks"]["delta_gap"]["value"] > out["checks"]["delta_gap"]["limit"]


def test_train_half_the_batch_left_out(monkeypatch):
    drv = common.load_module("drivers", "train")
    monkeypatch.setattr(drv.Program, "batch",
                        lambda self, seed: {"idx": jnp.arange(self.n_target // 2, dtype=jnp.int32)})
    out = execute(tiny("han-acm.train", 0.3))
    assert not out["correct"], out["checks"]


def test_lanes4_sound_run_is_correct():
    out = execute(tiny("han-acm.train-lanes4", 0.3))
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4


def test_lanes4_exchange_between_chips_left_out():
    drv = common.load_module("drivers", "train")
    with drv.exchange_left_out():
        out = execute(tiny("han-acm.train-lanes4", 0.3))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["han-acm.train", "han-imdb.train"])
def test_train_control_is_not_correct(cell):
    """The control: the reference in bfloat16 in the program's place."""
    drv = common.load_module("drivers", "train")
    ctx = tiny(cell, 0.3)
    names = [graphgen.metapath_name(m) for m in ctx.config["graph"]["metapaths"]]
    inputs = drv.reference_inputs(ctx, names)
    ref = drv.reference_readings(ctx, inputs, ctx.seed)
    ctl = drv.compare(drv.reference_readings(ctx, inputs, ctx.seed, dtype=jnp.bfloat16), ref)
    assert any(ctl[k] > lim for k, lim in ctx.cell["limits"].items()), ctl
