"""Whole serving runs with the timed path broken underneath come out not
correct.

Each test drives ``bench/run.py``'s ``execute`` on the CPU at a tiny size
(the look for a chip skipped, the Pallas kernel interpreted) with one
fault planted in the program, and sees ``correct`` false; the sound run
of the same size comes out true.  The limits are the cells' own.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import common  # noqa: E402
import run  # noqa: E402
from tiny import execute, tiny  # noqa: E402


def test_serve_sound_run_is_correct():
    out = execute(tiny("han-imdb.serve", 2.0, update_share=0.3))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["metrics"]["serve_p50_ms"]["value"] > 0


def test_serve_answer_altered_where_it_is_produced(monkeypatch):
    from repro.serve import hgnn_engine

    real = hgnn_engine.stages.global_semantic_fusion

    def altered(w_p, z_stack):
        fused, beta = real(w_p, z_stack)
        return fused.at[0, 0].set(2.0 * jnp.max(jnp.abs(fused))), beta

    monkeypatch.setattr(hgnn_engine.stages, "global_semantic_fusion", altered)
    out = execute(tiny("han-imdb.serve", 2.0))
    assert not out["correct"]
    assert out["checks"]["emb_gap"]["value"] > out["checks"]["emb_gap"]["limit"]


def test_serve_stale_projection_after_an_update(monkeypatch):
    from repro.serve import fp_cache

    monkeypatch.setattr(fp_cache.FPCache, "invalidate", lambda self, vtype: None)
    out = execute(tiny("han-imdb.serve", 2.0, update_share=0.3))
    assert not out["correct"]
    assert out["checks"]["emb_gap"]["value"] > out["checks"]["emb_gap"]["limit"]


def test_serve_control_is_not_correct():
    """The control: the reference in bfloat16 in the program's place, at
    the cell's own size (the reference alone runs; no engine is needed)."""
    import types

    from repro.serve.hgnn_engine import GraphRequest

    drv = common.load_module("drivers", "serve")
    ctx = run.make_context("han-imdb.serve", 2147483663, 1.0, False)
    spec = ctx.config["graph"]
    mps = [tuple(m) for m in spec["metapaths"]]
    stand_in = types.SimpleNamespace(target=spec["target"], metapaths=mps, pool={},
                                     step_content={k: -1 for k in range(len(mps))})
    reqs = [GraphRequest(rid=i, metapaths=list(kind), admitted_step=0,
                         finished_step=len(kind) - 1)
            for i, kind in enumerate(drv.request_kinds(mps) + [tuple(mps)])]
    ref = drv.Reference(ctx, stand_in, ctx.seed)
    ctl = drv.Reference(ctx, stand_in, ctx.seed, dtype=jnp.bfloat16)
    assert drv.widest_gap(reqs, ref, ref) == 0.0
    assert drv.widest_gap(reqs, ref, ctl) > ctx.cell["limits"]["emb_gap"]
