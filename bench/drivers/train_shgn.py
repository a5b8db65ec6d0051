"""Training driver: full-batch S-HGN steps on the union graph, as the
training launcher builds them.

The step is composed as ``launch/hgnn_train.run_training`` composes it
for ``model_name="S-HGN"`` (``build_problem`` with the union graph,
``build_multilane_plan`` + ``place_plan`` on one lane,
``shgn_forward_plan``, ``init_hgnn_train_state``,
``make_hgnn_train_step``).  The set-up, the window, its queue and the
check are those of ``drivers/train.py``, imported: the first steps'
losses, the first gradient as AdamW took it and the parameters' change,
leaf by leaf, against ``bench/reference_shgn.py`` run from the same seed
on the benchmark's own copy of the graph.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

import common
import graphgen
import reference_shgn
import work_shgn
from repro.core.multilane import build_multilane_plan, place_plan
from repro.dist.sharding import lane_axes, make_rules, param_shardings, use_rules
from repro.launch.hgnn_train import build_problem
from repro.launch.mesh import make_lane_mesh
from repro.models.hgnn import SHGN
from repro.models.hgnn.shgn import shgn_forward_plan
from repro.optim import AdamWConfig
from repro.train import hgnn_train_state_axes, init_hgnn_train_state, make_hgnn_train_step

train = common.load_module("drivers", "train")


class Program(train.Program):
    """The jitted S-HGN training step and what it closes over."""

    def __init__(self, ctx: common.Context, phases: common.Phases):
        cfg = ctx.config
        self.cfg = cfg
        with phases("graph build and transfer"):
            _, self.data = build_problem(
                cfg["graph"]["dataset"], scale=ctx.scale, feat_scale=ctx.feat_scale,
                block=cfg["block"], seed=cfg["assumed"]["graph_seed"], model_name="S-HGN",
            )
            jax.block_until_ready(self.data)
        self.mesh = make_lane_mesh(1, 1)
        self.rules = make_rules(parallelism="lanes")
        with phases("plan build and transfer"):
            self.plan = place_plan(
                build_multilane_plan(self.data.graphs, 1), self.mesh, lane_axes(self.rules)
            )
            jax.block_until_ready(self.plan.masks)
        o = cfg["optimizer"]
        self.opt = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                               weight_decay=o["weight_decay"], grad_clip=o["grad_clip"])
        fwd = lambda p: shgn_forward_plan(p, self.data, self.plan, backend=ctx.backend)
        self.step = jax.jit(make_hgnn_train_step(fwd, self.data, self.opt))
        self.n_target = int(self.data.labels.shape[0])
        self.na_slots = self.plan.na_slots()

    def init_state(self, seed: int):
        """The train state drawn from ``seed`` on the device, in one jitted call."""
        cfg = self.cfg
        init = lambda key: init_hgnn_train_state(
            SHGN, key, self.data, self.opt, hidden=cfg["hidden"], heads=cfg["heads"],
            layers=cfg["layers"], edge_dim=cfg["edge_dim"],
        )
        key = jax.random.key(seed)
        with self.mesh, use_rules(self.rules):
            axes = hgnn_train_state_axes(jax.eval_shape(init, key), self.opt)
            shardings = param_shardings(self.mesh, self.rules, axes)
            return jax.jit(init, out_shardings=shardings)(key)


def reference_inputs(ctx: common.Context):
    """Features per type, the union graph and the labels, all from
    ``bench/graphgen.py``."""
    cfg = ctx.config
    spec = cfg["graph"]
    gs = cfg["assumed"]["graph_seed"]
    g = graphgen.hetgraph(spec, seed=gs, scale=ctx.scale, feat_scale=ctx.feat_scale)
    feats = {t: jnp.asarray(x) for t, x in g.features.items()}
    labels = jnp.asarray(graphgen.labels(g, spec, seed=gs))
    return feats, reference_shgn.union_edges(g, spec), labels


def reference_readings(ctx: common.Context, inputs, seed: int, **fault) -> dict:
    cfg = ctx.config
    feats, graph, labels = inputs
    dims = {t: int(feats[t].shape[1]) for t in graph[0]}
    params = reference_shgn.init_shgn(
        seed, dims, len(graph[-1]), hidden=cfg["hidden"], heads=cfg["heads"],
        layers=cfg["layers"], edge_dim=cfg["edge_dim"], n_classes=cfg["graph"]["num_classes"],
    )
    return reference_shgn.train_readings(
        params, feats, graph, labels, cfg, steps=ctx.traffic["first_steps"], **fault)


def run(ctx: common.Context, counter: common.CompileCounter) -> dict:
    phases = common.Phases(ctx)
    cfg, tr = ctx.config, ctx.traffic
    prog = Program(ctx, phases)
    with phases("weights"):
        state = prog.init_state(ctx.seed)
        batch = prog.batch(ctx.seed)
        jax.block_until_ready(state)
    with phases(f"compile or cache load + first {tr['first_steps']} steps"):
        state, readings = prog.first_steps(state, batch, tr["first_steps"])
    ctx.say(f"[setup] programs {counter.count('program')} (cache hits {counter.count('cache_hit')})")
    ctx.say(f"[program] losses {readings['losses']}")

    common.settle()
    if ctx.trace:
        common.start_trace(ctx.trace_dir)
    setup_s = time.perf_counter() - ctx.t_start
    state, steps, t0, t1 = prog.window(state, batch, ctx.seconds, tr["queue_depth"])
    if ctx.trace:
        jax.profiler.stop_trace()
    in_window = counter.count("program", t0, t1)
    ctx.say(f"[window] {steps} steps in {t1 - t0:.4f}s, programs obtained in the window: {in_window}")
    peak = common.memory_peak(prog.mesh.devices.flat)
    na_slots = prog.na_slots
    del state, prog, batch

    inputs = reference_inputs(ctx)
    ref = reference_readings(ctx, inputs, ctx.seed)
    gaps = train.compare(readings, ref)
    ctx.say(f"[reference] losses {ref['losses']}")
    limits = ctx.cell["limits"]
    checks = [(k, gaps[k], limits[k]) for k in ("loss_gap", "grad_gap", "delta_gap")]

    feats, graph, labels = inputs
    types, _, n, src, _, _, names = graph
    dims = {t: int(feats[t].shape[1]) for t in types}
    counts = {t: int(feats[t].shape[0]) for t in types}
    e, spec = int(src.size), work_shgn.layers(cfg)
    return {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": steps,
        "failed": 0,
        "checks": checks,
        "memory_peak_bytes": peak,
        "e2e": {"train_step_ms": (t1 - t0) / steps * 1e3, "setup_s": setup_s},
        "steps": steps,
        "window_s": t1 - t0,
        "flops_per_step": work_shgn.train_step_flops(
            e, dims, counts, int(labels.shape[0]), len(names), cfg)["total"],
        "na_fwd": work_shgn.na_forward(e, n, len(names), spec),
        "na_bwd": work_shgn.na_backward(e, n, len(names), spec),
        # run_training's meta counters: live NA slots per lane, launches a step
        "na_slots": na_slots,
        "na_layers": len(spec),
    }


def calibrate(ctx: common.Context, seeds: list[int], control_seeds: list[int]):
    """Readings that the check's limits are set from, in one process: the
    program against the reference on every seed, then on ``control_seeds``
    the control (the reference in bfloat16) and the planted faults of half
    the batch left out, the attention residual left out (beta 0) and a
    softmax per edge type in place of the joint one.  Yields one dict per
    reading."""
    phases = common.Phases(ctx)
    prog = Program(ctx, phases)
    readings = {}
    for seed in seeds:
        state = prog.init_state(seed)
        _, readings[seed] = prog.first_steps(state, prog.batch(seed), ctx.traffic["first_steps"])
    del prog, state
    inputs = reference_inputs(ctx)
    n = int(inputs[2].shape[0])
    faults = {"fault_half_batch": dict(keep_rows=jnp.arange(n) < n // 2),
              "fault_beta_0": dict(beta=0.0),
              "fault_per_type_softmax": dict(per_type=True)}
    for seed in seeds:
        ref = reference_readings(ctx, inputs, seed)
        yield {"seed": seed, "what": "program", **train.compare(readings[seed], ref, True)}
        if seed in control_seeds:
            ctl = reference_readings(ctx, inputs, seed, dtype=jnp.bfloat16)
            yield {"seed": seed, "what": "control_bf16", **train.compare(ctl, ref, True)}
            for what, fault in faults.items():
                got = reference_readings(ctx, inputs, seed, **fault)
                yield {"seed": seed, "what": what, **train.compare(got, ref, True)}
