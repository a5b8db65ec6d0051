"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived[,backend=...]`` CSV rows:
  breakdown/*        — Fig. 2  execution-time breakdown (FP/NA/SF)
  fusion/*           — Fig. 13 bound-aware stage fusion vs staged
  lanes/*            — Fig. 14 lane scaling + workload-aware scheduling
  similarity/*       — Fig. 15 similarity-aware scheduling (DRAM fetch)
  kernel/*           — kernel-level backends (fused online-softmax NA)
  multilane/*        — fused multigraph kernel vs vmap reference vs
                       per-graph loop across G semantic graphs
  fp_cache/*         — serving-tier FP cache: hit rate vs capacity,
                       similarity vs FIFO admission (measured Fig. 15)
  stage_fusion/*     — FP+NA stage-fusion megakernel vs materialize-
                       then-NA vs staged reference (Alg. 2, DESIGN.md §10)
  hgnn_train/*       — mesh-scale training launcher: measured step time +
                       loss trajectory, plus the lane-vs-model mesh-split
                       autotune sweep (collective-vs-compute crossover)
  roofline/*         — §Roofline terms per (arch × shape × mesh), from
                       the dry-run artifacts (run launch/dryrun first)

``--json`` additionally writes the rows as ``BENCH_<only>.json`` (or
``BENCH.json`` for a full run): a list of
``{name, us_per_call, backend, derived}`` records — the regression
baseline later PRs compare against.  Rows measured with ``timeit_stats``
also carry ``p10_us/p50_us/p90_us/iters`` so spread is separable from
regression.  ``--list`` prints the registered bench names; duplicate
registrations abort the run.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from .common import row


def _registry() -> dict:
    from . import (
        breakdown,
        fp_cache,
        fusion_ablation,
        hgnn_train,
        kernels_bench,
        lanes,
        multilane_bench,
        roofline,
        similarity,
        stage_fusion,
        stage_roofline,
    )

    benches: dict = {}

    def register(name: str, fn) -> None:
        # fail LOUDLY: a silent overwrite would drop a whole bench family
        # from the regression baseline without any signal in CI
        if name in benches:
            raise SystemExit(f"duplicate benchmark registration: {name!r}")
        benches[name] = fn

    register("breakdown", breakdown.run)
    register("fusion", fusion_ablation.run)
    register("lanes", lanes.run)
    register("similarity", similarity.run)
    register("kernels", kernels_bench.run)
    register("multilane", multilane_bench.run)
    register("fp_cache", fp_cache.run)
    register("stage_fusion", stage_fusion.run)
    register("hgnn_train", hgnn_train.run)
    register("stage_roofline", stage_roofline.run)
    register("roofline", roofline.run)
    return benches


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-list of bench names")
    ap.add_argument(
        "--json", action="store_true",
        help="write rows to BENCH_<only>.json (BENCH.json for a full run)",
    )
    ap.add_argument(
        "--list", action="store_true", help="list registered benches and exit"
    )
    args = ap.parse_args()

    benches = _registry()
    if args.list:
        for name in benches:
            print(name)
        return
    if args.only:
        keep = set(args.only.split(","))
        unknown = keep - set(benches)
        if unknown:
            raise SystemExit(f"unknown benches: {sorted(unknown)} (see --list)")
        benches = {k: v for k, v in benches.items() if k in keep}

    records: list[dict] = []

    def report(
        name: str,
        us_per_call: float,
        derived: str,
        backend: str | None = None,
        stats: tuple[float, float, float, int] | None = None,
    ):
        rec = dict(
            name=name, us_per_call=float(us_per_call), backend=backend, derived=derived,
        )
        if stats is not None:
            rec.update(
                p10_us=float(stats[0]), p50_us=float(stats[1]),
                p90_us=float(stats[2]), iters=int(stats[3]),
            )
        records.append(rec)
        return row(name, us_per_call, derived, backend=backend, stats=stats)

    failures = 0
    for name, fn in benches.items():
        try:
            fn(report)
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        tag = "_" + "_".join(sorted(benches)) if args.only else ""
        path = f"BENCH{tag}.json"
        with open(path, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {path} ({len(records)} rows)", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benches failed")


if __name__ == "__main__":
    main()
