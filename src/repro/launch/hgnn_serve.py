"""HGNN serving launcher: stepped graph-request inference with the
cross-request FP cache and similarity-aware admission.

    PYTHONPATH=src python -m repro.launch.hgnn_serve --dataset imdb --compare

Builds the named Table-5 HetGraph, submits a round-robin request mix over
its metapaths, and drives serve/hgnn_engine.py.  ``--compare`` runs the
same mix under FIFO and similarity-aware admission and reports the
measured FP-stage compute reduction (the serving-tier counterpart of the
paper's Fig. 15 DRAM-fetch reduction).  ``--na-backend multigraph`` is
the TPU path (one fused Pallas launch per step); ``multigraph_interpret``
validates the same kernel on CPU; ``block`` is the pure-jnp fallback.
``--na-backend fused-fp`` runs the stage-fusion megakernel: on a cache
miss the target type's FP happens inside the NA launch (DESIGN.md §10);
on a full-table cache hit the engine dispatches the projected multigraph
path instead.  The compiled Pallas backends (``multigraph``,
``fused_fp``) run only on a TPU and raise on a host without one; their
``*_interpret`` variants run the same kernel bodies on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from ..core.fusion import NABackend, require_tpu
from ..graphs import dataset_metapaths, dataset_target, synthetic_hetgraph
from ..obs import MetricsRegistry, profile
from ..serve.hgnn_engine import HGNNEngine, make_request_mix
from .compile_cache import enable_compile_cache

_BACKENDS = {
    "segment": NABackend.SEGMENT,
    "block": NABackend.BLOCK,
    "multigraph": NABackend.MULTIGRAPH,
    "multigraph_interpret": NABackend.MULTIGRAPH_INTERPRET,
    "fused_fp": NABackend.FUSED_FP,
    "fused-fp": NABackend.FUSED_FP,  # alias
    "fused_fp_interpret": NABackend.FUSED_FP_INTERPRET,
}


def _target_metapaths(name: str, target: str) -> list[tuple[str, ...]]:
    return [tuple(mp) for mp in dataset_metapaths(name) if mp[0] == target and mp[-1] == target]


def serve_mix(graph, target, clusters, args, admission, registry=None) -> dict:
    eng = HGNNEngine(
        graph,
        target_type=target,
        hidden=args.hidden,
        heads=args.heads,
        num_slots=args.slots,
        cache_bytes=args.cache_kb * 1024,
        cache_block_rows=args.cache_block_rows,
        cache_policy=args.policy,
        admission=admission,
        backend=_BACKENDS[args.na_backend],
        block=args.block,
        max_edges=args.max_edges,
        registry=registry,
    )
    for req in make_request_mix(0, clusters, repeats=args.repeats):
        eng.submit(req)
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    m = eng.metrics()
    m["wall_s"] = dt
    m["admission"] = admission
    return m


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="imdb", choices=("imdb", "acm", "dblp"))
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--feat-scale", type=float, default=0.02)
    ap.add_argument("--repeats", type=int, default=4, help="requests per metapath cluster")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--cache-kb", type=int, default=48, help="FP cache capacity (0 disables)")
    ap.add_argument("--cache-block-rows", type=int, default=64)
    ap.add_argument("--policy", default="lru", choices=("lru", "similarity"))
    ap.add_argument("--admission", default="similarity", choices=("similarity", "fifo"))
    ap.add_argument("--na-backend", default="block", choices=sorted(_BACKENDS))
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--block", type=int, default=8, help="dst block size for the NA formats")
    ap.add_argument("--max-edges", type=int, default=20_000)
    ap.add_argument("--compare", action="store_true", help="run FIFO vs similarity admission")
    ap.add_argument(
        "--trace", default=None, metavar="DIR",
        help="profile the serving run into DIR: an .xplane.pb and a "
             "perfetto_trace.json.gz with the engine's serve.* spans and the "
             "device's ops on one timeline",
    )
    ap.add_argument(
        "--metrics", default=None, metavar="PATH",
        help="write the engine metrics registry (counters, cache gauges, "
             "per-step latency histogram) as JSON",
    )
    args = ap.parse_args()
    require_tpu(_BACKENDS[args.na_backend].value)
    enable_compile_cache()

    graph = synthetic_hetgraph(args.dataset, scale=args.scale, feat_scale=args.feat_scale, seed=0)
    target, _ = dataset_target(args.dataset)
    clusters = [[mp] for mp in _target_metapaths(args.dataset, target)]
    assert clusters, f"{args.dataset}: no target->target metapaths"

    # one registry across runs: --compare accumulates both admissions'
    # counters; gauges reflect the last engine built
    reg = MetricsRegistry() if args.metrics else None
    with profile(args.trace):
        if args.compare:
            fifo = serve_mix(graph, target, clusters, args, "fifo", registry=reg)
            sim = serve_mix(graph, target, clusters, args, "similarity", registry=reg)
            reduction = fifo["fp_rows_computed"] / max(sim["fp_rows_computed"], 1)
            print(json.dumps(dict(fifo=fifo, similarity=sim,
                                  fp_rows_fifo_over_similarity=reduction), indent=1))
        else:
            print(json.dumps(
                serve_mix(graph, target, clusters, args, args.admission, registry=reg),
                indent=1,
            ))
    if args.trace:
        print(f"wrote a profile under {args.trace} (perfetto_trace.json.gz opens "
              f"at https://ui.perfetto.dev)", file=sys.stderr)
    if reg is not None:
        reg.export_json(args.metrics)
        print(f"wrote {args.metrics}", file=sys.stderr)


if __name__ == "__main__":
    main()
