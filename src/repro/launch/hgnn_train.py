"""Mesh-scale HGNN training launcher (DESIGN.md §11).

    PYTHONPATH=src python -m repro.launch.hgnn_train --dataset acm --model HAN \
        --steps 100 --lanes 2 --backend kernel

Composes the pieces the repo already had into the paper's training
posture: the ``lanes`` sharding rules + a dedicated lane mesh
(independency-aware parallel execution, §4.2.1), a MultiLanePlan built by
the workload-aware scheduler, and HAN's NA running through the fused
multigraph Pallas kernel — one forward and one backward launch per lane
shard (``multilane_na_sharded(backend="kernel")``, custom VJP).  The
fault-tolerant ``train_loop`` is reused end to end: atomic checkpoints,
counter-based data state, ``--crash-at`` fault injection, and *elastic
lane restarts* — resume the same checkpoint directory with a different
``--lanes`` and the state restores bit-identically onto the new mesh
(checkpoints store logical arrays; the plan is rebuilt per run, the
forward is bit-identical for any lane count, and gradients agree to f32
tolerance — the lane partition only regroups the cross-unit reduction).

The launcher trains three models.  HAN runs as above.  S-HGN
(``--model S-HGN``) attends over the union graph of the HetG
(``graphs.union_graph``): one typed plan of int8 type tiles, and each of
its three layers (two hidden, one output) one typed multigraph launch,
forward and backward (``models/hgnn/shgn.shgn_forward_plan``), on one
lane of one chip; ``--hidden 64 --heads 8 --lr 5e-4 --weight-decay 1e-4``
are its published settings.  R-GAT trains through its per-relation
forward, the same multigraph kernel over a one-lane plan per relation
(its relation-specific projections keep it off the consolidated
one-launch plan).  ``--backend kernel`` compiles the Pallas kernels for the TPU and
refuses to run without one; on a CPU host ask for ``kernel_interpret``,
which runs the same kernel body under the Pallas interpreter;
``reference`` runs HAN's multilane oracle, S-HGN's plain edge-list
reference, and R-GAT's BLOCK path.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from ..core import NABackend, require_tpu, similarity_schedule
from ..core.multilane import build_multilane_plan, place_plan
from ..data import SyntheticHGNNData
from ..dist.sharding import lane_axes, make_rules, param_shardings, use_rules
from ..graphs import (
    build_semantic_graphs,
    dataset_metapaths,
    dataset_target,
    synthetic_hetgraph,
    synthetic_labels,
    union_graph,
)
from ..models.hgnn import MODELS, han_forward_multilane, prepare_data
from ..models.hgnn.shgn import shgn_forward_plan, shgn_reference
from ..obs import get_registry, profile
from ..optim import AdamWConfig
from ..train import (
    hgnn_train_state_axes,
    init_hgnn_train_state,
    make_hgnn_train_step,
    train_loop,
)
from .compile_cache import enable_compile_cache
from .mesh import make_lane_mesh

DATASETS = ("acm", "imdb", "dblp")

# model.init keyword vocabularies differ (HAN takes att_dim, R-GAT layers,
# S-HGN its published depth and edge-embedding width)
_INIT_KW = {
    "HAN": lambda hidden, heads: dict(hidden=hidden, heads=heads, att_dim=2 * hidden),
    "R-GAT": lambda hidden, heads: dict(hidden=hidden, heads=heads, layers=2),
    "S-HGN": lambda hidden, heads: dict(hidden=hidden, heads=heads, layers=2, edge_dim=64),
}


def build_problem(
    dataset: str,
    *,
    scale: float = 0.1,
    feat_scale: float = 0.1,
    block: int = 128,
    max_edges: int = 400_000,
    seed: int = 0,
    model_name: str = "HAN",
):
    """Synthesize the Table-5 HetG and its device-resident training data:
    for S-HGN the union graph (typed tiles, every relation uncapped), for
    the others the metapath semantic graphs ordered by the similarity
    schedule (FP reuse)."""
    g = synthetic_hetgraph(dataset, scale=scale, feat_scale=feat_scale, seed=seed)
    target, ncls = dataset_target(dataset)
    labels = synthetic_labels(g, dataset, seed=seed)
    if model_name == "S-HGN":
        return g, prepare_data(g, [union_graph(g)], target, ncls, labels, block=block)
    sgs = build_semantic_graphs(g, dataset_metapaths(dataset), max_edges=max_edges)
    order, _ = similarity_schedule(sgs, g.vertex_counts)
    data = prepare_data(g, [sgs[i] for i in order], target, ncls, labels, block=block)
    return g, data


def run_training(
    *,
    dataset: str = "acm",
    model_name: str = "HAN",
    steps: int = 100,
    lanes: int = 1,
    model_split: int = 1,
    plan_lanes: int | None = None,
    backend: str = "kernel",
    hidden: int = 16,
    heads: int = 4,
    lr: float = 5e-3,
    weight_decay: float = 0.0,
    batch: int = 0,  # labeled minibatch size; 0 = full batch
    block: int = 128,
    scale: float = 0.1,
    feat_scale: float = 0.1,
    max_edges: int = 400_000,
    seed: int = 0,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    resume: bool = True,
    crash_at: int | None = None,
    log_every: int = 10,
    log=print,
    trace: str | None = None,        # profiler trace directory
    metrics_out: str | None = None,  # metrics-registry snapshot path
    registry=None,
):
    """Train one HGNN on one dataset under the lanes posture.

    Returns ``(state, history, meta)`` — meta records the resolved mesh /
    plan / backend so callers (benchmarks, tests) can assert on them.

    ``trace=`` profiles the training loop into that directory
    (``obs.profile``): one Perfetto timeline with the ``train`` step
    markers, the garbage collector's passes and the device's ops, each op
    named by its stage scope (``fp``, ``theta``, ``na``, ``fusion``,
    ``head``, ``optimizer``) in its ``op_name`` metadata.  ``metrics_out=``
    snapshots the metrics registry (step-time histogram, loss/grad-norm
    gauges) to JSON.
    """
    require_tpu(backend)
    reg = registry if registry is not None else get_registry()
    g, data = build_problem(
        dataset, scale=scale, feat_scale=feat_scale, block=block,
        max_edges=max_edges, seed=seed, model_name=model_name,
    )
    model = MODELS[model_name]
    n_target = g.vertex_counts[data.target_type]

    n_dev = len(jax.devices())
    assert lanes * model_split <= n_dev, (
        f"mesh {lanes}x{model_split} needs {lanes * model_split} devices, have {n_dev}"
    )
    mesh = make_lane_mesh(lanes, model_split)
    rules = make_rules(parallelism="lanes")

    if model_name == "HAN":
        # consolidated path: ONE fused NA dispatch for all relations per
        # step, lane-sharded over the mesh (the tentpole configuration)
        n_plan_lanes = plan_lanes or lanes
        assert n_plan_lanes % lanes == 0, (n_plan_lanes, lanes)
        # each lane shard of the plan lives on the device that runs it
        plan = place_plan(
            build_multilane_plan(data.graphs, n_plan_lanes), mesh, lane_axes(rules)
        )
        forward_fn = lambda p: han_forward_multilane(
            p, data, plan, mesh=mesh, lane_axes=lane_axes(rules), backend=backend
        )
        meta_backend = backend
    elif model_name == "S-HGN":
        # one typed plan over the union graph, every layer one typed launch
        # on one lane; the attention residual reads the previous layer's
        # per-row lse in unit order, so the plan is not split over lanes
        if lanes != 1 or model_split != 1 or (plan_lanes or 1) != 1:
            raise ValueError("S-HGN trains on one lane of one chip")
        plan = place_plan(build_multilane_plan(data.graphs, 1), mesh, lane_axes(rules))
        if backend == "reference":
            forward_fn = lambda p: shgn_reference(p, data)
            meta_backend = NABackend.SEGMENT.value
        else:
            forward_fn = lambda p: shgn_forward_plan(p, data, plan, backend=backend)
            meta_backend = backend
    else:
        # per-relation projections -> per-relation fused kernel launches
        plan = None
        nab = NABackend.BLOCK if backend == "reference" else backend
        forward_fn = lambda p: model.forward(p, data, backend=nab)
        meta_backend = getattr(nab, "value", nab)

    opt = AdamWConfig(lr=lr, weight_decay=weight_decay)
    pipeline = SyntheticHGNNData(
        num_vertices=n_target,
        batch_size=batch if batch > 0 else n_target,
        seed=seed,
    )

    with mesh, use_rules(rules):
        state = init_hgnn_train_state(
            model, jax.random.key(seed), data, opt, **_INIT_KW[model_name](hidden, heads)
        )
        axes = hgnn_train_state_axes(state, opt)
        state_sh = param_shardings(mesh, rules, axes)
        state = jax.device_put(state, state_sh)
        n_params = sum(
            int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(state.params)
        )
        log(
            f"[hgnn_train] {model_name}/{dataset} params={n_params/1e6:.2f}M "
            f"edges={sum(b.num_edges for b in data.graphs)} mesh=lane{lanes}xmodel"
            f"{model_split} backend={meta_backend}"
        )
        step_fn = make_hgnn_train_step(forward_fn, data, opt)
        with profile(trace):
            state, history = train_loop(
                state=state, train_step=step_fn, data=pipeline, steps=steps,
                ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, resume=resume,
                crash_at=crash_at, log_every=log_every, log=log,
                registry=reg, state_shardings=state_sh,
            )
    if metrics_out:
        reg.export_json(metrics_out)

    na_slots = None if plan is None else plan.na_slots()
    meta = dict(
        dataset=dataset, model=model_name, backend=str(meta_backend),
        lanes=lanes, model_split=model_split,
        plan_lanes=None if plan is None else plan.num_lanes,
        # device -> shape of the plan's mask shard it holds
        plan_shards=None if plan is None else {
            str(s.device): list(s.data.shape) for s in plan.masks.addressable_shards
        },
        # NA grid slots per lane, and how many are live (not padding); the
        # NA launches a step makes over the plan, forward (one per layer);
        # the edge types of a typed plan
        na_slots=na_slots,
        # max / mean live slots over the plan's lanes: how far the lane the
        # psum waits for is above the mean NA work (1.0 on one lane)
        lane_imbalance=None if plan is None else float(
            max(na_slots["live"]) / max(np.mean(na_slots["live"]), 1e-9)
        ),
        na_layers=None if plan is None else max(
            1, sum(k.endswith(".attn_src") for k in state.params)  # S-HGN: one a layer
        ),
        na_edge_types=len(data.graphs[0].edge_type_names) or None,
        n_params=n_params, n_target=n_target,
    )
    return state, history, meta


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="acm", choices=DATASETS)
    ap.add_argument("--model", default="HAN", choices=sorted(_INIT_KW))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lanes", type=int, default=1, help="lane mesh axis size")
    ap.add_argument("--model-split", type=int, default=1, help="model mesh axis size")
    ap.add_argument(
        "--plan-lanes", type=int, default=None,
        help="work-unit partition lanes (default: mesh lanes; must be a multiple)",
    )
    ap.add_argument(
        "--backend", default="kernel",
        choices=("reference", "kernel", "kernel_interpret"),
        help="multilane NA executor (kernel = fused multigraph Pallas launch per "
             "shard, TPU only; kernel_interpret = the same kernel interpreted on CPU)",
    )
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--weight-decay", type=float, default=0.0, help="AdamW's decoupled decay")
    ap.add_argument("--batch", type=int, default=0, help="labeled minibatch (0 = full)")
    ap.add_argument("--block", type=int, default=128, help="dst block size (paper: 128)")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--feat-scale", type=float, default=0.1)
    ap.add_argument("--max-edges", type=int, default=400_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--crash-at", type=int, default=None, help="fault injection (tests)")
    ap.add_argument("--out", default=None, help="write the loss trajectory as JSON")
    ap.add_argument(
        "--trace", default=None, metavar="DIR",
        help="profile the training loop into DIR: an .xplane.pb and a "
             "perfetto_trace.json.gz with step markers, GC passes and the "
             "device's ops by stage scope",
    )
    ap.add_argument(
        "--metrics", default=None, metavar="PATH",
        help="write a metrics-registry JSON snapshot (step-time histogram, "
             "loss/grad-norm gauges)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    state, history, meta = run_training(
        dataset=args.dataset, model_name=args.model, steps=args.steps,
        lanes=args.lanes, model_split=args.model_split, plan_lanes=args.plan_lanes,
        backend=args.backend, hidden=args.hidden, heads=args.heads, lr=args.lr,
        weight_decay=args.weight_decay,
        batch=args.batch, block=args.block, scale=args.scale,
        feat_scale=args.feat_scale, max_edges=args.max_edges, seed=args.seed,
        ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every, resume=not args.no_resume,
        crash_at=args.crash_at, trace=args.trace, metrics_out=args.metrics,
    )
    print(
        f"final loss {history[-1]['loss']:.4f} (start {history[0]['loss']:.4f}) "
        f"acc {history[-1]['acc']:.3f}"
    )
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"meta": meta, "history": history}, f, indent=1)
        print(f"wrote {args.out}")
    if args.trace:
        print(f"wrote a profile under {args.trace} (perfetto_trace.json.gz "
              f"opens at https://ui.perfetto.dev)")
    if args.metrics:
        print(f"wrote {args.metrics}")


if __name__ == "__main__":
    main()
