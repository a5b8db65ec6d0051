"""HGNN training benchmark — the mesh-scale launcher end to end.

Runs a short HAN and R-GAT trajectory through ``launch.hgnn_train``'s
``run_training`` (the fused kernel in interpret mode, on the CPU) and
reports the measured step time plus the loss trajectory — the regression
baseline for the training path (BENCH_hgnn_train.json).  Also emits the
lane-vs-model mesh-split autotune sweep (``lanes.sweep_mesh_split``) so
the training artifact carries the split the launcher should be run with.
"""
from __future__ import annotations

from repro.launch.hgnn_train import run_training

from .lanes import sweep_mesh_split

_STEPS = 8


def run(report):
    for model_name, dataset in (("HAN", "acm"), ("R-GAT", "imdb")):
        state, history, meta = run_training(
            dataset=dataset,
            model_name=model_name,
            steps=_STEPS,
            lanes=1,
            backend="kernel_interpret",
            hidden=8,
            heads=2,
            scale=0.06,
            max_edges=60_000,
            log_every=1,
            log=lambda *_: None,
        )
        first, last = history[0], history[-1]
        # skip the step-0 compile; median of the steady-state step times
        secs = sorted(m["sec"] for m in history[1:])
        step_us = secs[len(secs) // 2] * 1e6
        report(
            f"hgnn_train/{dataset}/{model_name}",
            step_us,
            f"loss0={first['loss']:.4f} lossN={last['loss']:.4f} "
            f"decreasing={last['loss'] < first['loss']} steps={_STEPS} "
            f"params={meta['n_params']}",
            backend=str(meta["backend"]),
        )
        assert last["loss"] < first["loss"], (model_name, first, last)

    sweep_mesh_split(report, prefix="hgnn_train/autotune")
