"""Training driver: full-batch HAN steps as the training launcher builds them.

The step is composed as ``launch/hgnn_train.run_training`` composes it
(``build_problem``, ``build_multilane_plan`` + ``place_plan``,
``han_forward_multilane``, ``init_hgnn_train_state``,
``make_hgnn_train_step``); ``run_training`` itself has no time-bounded
form.  Set-up builds that one step and its state, drives it from the
seed through the first steps (which compiles every shape the window
uses) and hands the same state to the window.  The window enqueues steps
back to back, waits only for the step ``queue_depth`` steps back, and
ends in ``block_until_ready``; no step fetches anything to the host.

The check: the first steps' losses, the first gradient as AdamW took it
(its first moment over 1 - b1) and the parameters' change after the
first steps, leaf by leaf, against ``bench/reference.py`` run from the
same seed on the benchmark's own copy of the graph.
"""
from __future__ import annotations

import collections
import contextlib
import time

import jax
import jax.numpy as jnp

import common
import graphgen
import reference
import work
from repro.core.multilane import build_multilane_plan, place_plan
from repro.data import SyntheticHGNNData
from repro.dist.sharding import lane_axes, make_rules, param_shardings, use_rules
from repro.launch.hgnn_train import build_problem
from repro.launch.mesh import make_lane_mesh
from repro.models.hgnn import HAN, han_forward_multilane
from repro.optim import AdamWConfig
from repro.train import hgnn_train_state_axes, init_hgnn_train_state, make_hgnn_train_step

_norms = jax.jit(lambda tree: {k: jnp.linalg.norm(v.astype(jnp.float32).ravel()) for k, v in tree.items()})
_delta_norms = jax.jit(lambda a, b: {k: jnp.linalg.norm((a[k] - b[k]).astype(jnp.float32).ravel()) for k in a})


def _host(tree) -> dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


class Program:
    """The jitted training step and what it closes over."""

    def __init__(self, ctx: common.Context, phases: common.Phases):
        cfg, tr = ctx.config, ctx.traffic
        self.cfg = cfg
        with phases("graph build and transfer"):
            _, self.data = build_problem(
                cfg["graph"]["dataset"], scale=ctx.scale, feat_scale=ctx.feat_scale,
                block=cfg["block"], max_edges=cfg["max_edges"], seed=cfg["assumed"]["graph_seed"],
            )
            jax.block_until_ready(self.data)
        self.mesh = make_lane_mesh(tr["lanes"], 1)
        self.rules = make_rules(parallelism="lanes")
        axes = lane_axes(self.rules)
        with phases("plan build and transfer"):
            self.plan = place_plan(
                build_multilane_plan(self.data.graphs, tr["plan_lanes"]), self.mesh, axes
            )
            jax.block_until_ready(self.plan.masks)
        o = cfg["optimizer"]
        self.opt = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                               weight_decay=o["weight_decay"], grad_clip=o["grad_clip"])
        fwd = lambda p: han_forward_multilane(
            p, self.data, self.plan, mesh=self.mesh, lane_axes=axes, backend=ctx.backend
        )
        self.step = jax.jit(make_hgnn_train_step(fwd, self.data, self.opt))
        self.n_target = int(self.data.labels.shape[0])
        self.graph_names = [b.name for b in self.data.graphs]

    def init_state(self, seed: int):
        """The train state drawn from ``seed`` on the device, in one jitted call."""
        cfg = self.cfg
        init = lambda key: init_hgnn_train_state(
            HAN, key, self.data, self.opt,
            hidden=cfg["hidden"], heads=cfg["heads"], att_dim=cfg["att_dim"],
        )
        key = jax.random.key(seed)
        with self.mesh, use_rules(self.rules):
            axes = hgnn_train_state_axes(jax.eval_shape(init, key), self.opt)
            shardings = param_shardings(self.mesh, self.rules, axes)
            return jax.jit(init, out_shardings=shardings)(key)

    def batch(self, seed: int) -> dict:
        return SyntheticHGNNData(num_vertices=self.n_target, batch_size=self.n_target, seed=seed).next()

    def first_steps(self, state, batch, k: int):
        """Run k steps; returns the state and the program's readings."""
        p0 = state.params
        losses, s1 = [], None
        with self.mesh, use_rules(self.rules):
            for i in range(k):
                state, m = self.step(state, batch)
                losses.append(m["loss"])
                if i == 0:
                    s1 = state
            grad = _host(_norms(s1.opt["m"]))
            delta = _host(_delta_norms(state.params, p0))
        b1 = self.opt.b1
        readings = {
            "losses": [float(x) for x in losses],
            "grad": {k_: v / (1.0 - b1) for k_, v in grad.items()},
            "delta": delta,
        }
        return state, readings

    def window(self, state, batch, seconds: float, depth: int):
        """Steps back to back for ``seconds``; returns (state, steps, t0, t1)."""
        pending = collections.deque()
        n = 0
        with self.mesh, use_rules(self.rules):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                while True:
                    with jax.profiler.TraceAnnotation("bench.train_step"):
                        state, m = self.step(state, batch)
                    n += 1
                    pending.append(m["loss"])
                    if len(pending) > depth:
                        with jax.profiler.TraceAnnotation("bench.queue_wait"):
                            pending.popleft().block_until_ready()
                    if time.perf_counter() - t0 >= seconds:
                        break
                jax.block_until_ready(state)
            t1 = time.perf_counter()
        return state, n, t0, t1


def reference_inputs(ctx: common.Context, names: list[str]):
    """Target features, edge lists in the program's graph order, labels
    and real edge counts, all from ``bench/graphgen.py``."""
    cfg = ctx.config
    spec = cfg["graph"]
    gs = cfg["assumed"]["graph_seed"]
    g = graphgen.hetgraph(spec, seed=gs, scale=ctx.scale, feat_scale=ctx.feat_scale)
    edges = graphgen.training_graphs(g, spec, cfg["max_edges"])
    graphs = [(jnp.asarray(edges[n][0]), jnp.asarray(edges[n][1])) for n in names]
    x = jnp.asarray(g.features[spec["target"]])
    labels = jnp.asarray(graphgen.labels(g, spec, seed=gs))
    return x, graphs, labels, [int(edges[n][0].size) for n in names]


def reference_readings(ctx: common.Context, inputs, seed: int, *, dtype=jnp.float32,
                       keep_rows=None) -> dict:
    cfg = ctx.config
    x, graphs, labels, _ = inputs
    params = reference.init_han(
        seed, int(x.shape[1]), len(graphs), cfg["heads"], cfg["hidden"], cfg["att_dim"],
        cfg["graph"]["num_classes"],
    )
    return reference.train_readings(
        params, x, graphs, labels, heads=cfg["heads"], slope=cfg["leaky_slope"],
        opt=cfg["optimizer"], steps=ctx.traffic["first_steps"], dtype=dtype, keep_rows=keep_rows,
    )


def compare(prog: dict, ref: dict, leaves: bool = False) -> dict:
    """The three compared numbers (see the module docstring); with
    ``leaves``, also the leaf that sets each leaf-wise one."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_leaf = common.worst_leaf_gap(prog["grad"], ref["grad"])
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: left out of the change, by this rule
    med = common.median(list(ref["grad"].values()))
    keep = {k for k, v in ref["grad"].items() if v >= 1e-3 * med}
    delta_gap, delta_leaf = common.worst_leaf_gap(prog["delta"], ref["delta"], keep)
    out = {"loss_gap": loss_gap, "grad_gap": grad_gap, "delta_gap": delta_gap}
    if leaves:
        out.update(grad_leaf=grad_leaf, delta_leaf=delta_leaf, left_out=sorted(set(ref["grad"]) - keep))
    return out


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
    names = prog.graph_names
    del state, prog, batch

    inputs = reference_inputs(ctx, names)
    ref = reference_readings(ctx, inputs, ctx.seed)
    gaps = compare(readings, ref)
    ctx.say(f"[reference] losses {ref['losses']}")
    limits = ctx.cell["limits"]
    checks = [(k, gaps[k], limits[k]) for k in ("loss_gap", "grad_gap", "delta_gap")]
    correct = all(v <= lim for _, v, lim in checks)

    x, graphs, _, edges = inputs
    n, d_in = int(x.shape[0]), int(x.shape[1])
    h, dh = cfg["heads"], cfg["hidden"]
    return {
        "correct": correct,
        "attempted": steps,
        "failed": 0,
        "checks": checks,
        "memory_peak_bytes": peak,
        "e2e": {"train_step_ms": (t1 - t0) / steps * 1e3, "setup_s": setup_s},
        "steps": steps,
        "window_s": t1 - t0,
        "flops_per_step": work.han_train_step_flops(
            edges, n, d_in, h, dh, cfg["att_dim"], cfg["graph"]["num_classes"])["total"],
        "na_fwd": work.na_forward(edges, n, h, dh),
        "na_bwd": work.na_backward(edges, n, h, dh),
    }


@contextlib.contextmanager
def exchange_left_out():
    """A planted fault: the lane psum returns each chip's own partial."""
    real = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **kw: x
    try:
        yield
    finally:
        jax.lax.psum = real


def calibrate(ctx: common.Context, seeds: list[int], control_seeds: list[int]):
    """Readings that the check's limits are set from, in one process: the
    program against the reference on every seed, then on ``control_seeds``
    the control (the reference in bfloat16) and the planted faults of half
    the batch left out and, across chips, of the exchange left out.
    Yields one dict per reading."""
    phases = common.Phases(ctx)
    prog = Program(ctx, phases)
    readings, no_exchange = {}, {}
    for seed in seeds:
        state = prog.init_state(seed)
        _, readings[seed] = prog.first_steps(state, prog.batch(seed), ctx.traffic["first_steps"])
    if ctx.traffic["lanes"] > 1:
        faulty = Program(ctx, phases)
        with exchange_left_out():
            for seed in control_seeds:
                state = faulty.init_state(seed)
                _, no_exchange[seed] = faulty.first_steps(state, faulty.batch(seed), ctx.traffic["first_steps"])
        del faulty
    names = prog.graph_names
    del prog, state
    inputs = reference_inputs(ctx, names)
    n = int(inputs[2].shape[0])
    half = jnp.arange(n) < n // 2
    for seed in seeds:
        ref = reference_readings(ctx, inputs, seed)
        yield {"seed": seed, "what": "program", **compare(readings[seed], ref, True)}
        if seed in control_seeds:
            ctl = reference_readings(ctx, inputs, seed, dtype=jnp.bfloat16)
            yield {"seed": seed, "what": "control_bf16", **compare(ctl, ref, True)}
            fault = reference_readings(ctx, inputs, seed, keep_rows=half)
            yield {"seed": seed, "what": "fault_half_batch", **compare(fault, ref, True)}
        if seed in no_exchange:
            yield {"seed": seed, "what": "fault_exchange_left_out", **compare(no_exchange[seed], ref, True)}
