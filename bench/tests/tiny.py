"""A cell at a tiny size on the CPU, for the tests: the Pallas kernel
interpreted, the graph scaled down, the look for a chip skipped."""
from __future__ import annotations

import copy

import jax

import common
import run


def tiny(cell: str, seconds: float, feat_scale: float = 0.05, **traffic) -> common.Context:
    ctx = run.make_context(cell, 2147483663, seconds, False, backend="kernel_interpret",
                           scale=0.04, feat_scale=feat_scale)
    ctx.config = copy.deepcopy(ctx.config)
    ctx.config.update(block=16, max_edges=3000)
    ctx.traffic = dict(ctx.traffic, **traffic)
    return ctx


def execute(ctx):
    return run.execute(ctx, jax.devices()[: ctx.workload["chips"]])
