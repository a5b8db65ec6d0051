"""Smoke run of HGNN training and serving on a TPU, in one process.

    python chip_smoke.py              # one chip: train, kernel check, serve
    python chip_smoke.py --chips 4    # four chips: lane-sharded training only

Default run, at full Table-5 size (scale 1.0, feature scale 1.0, block
128, at most 400k edges per semantic graph), with random weights from
seed 0:

  (a) check that JAX's first device is a TPU, else exit non-zero;
  (b) train HAN on ACM (8 heads x 8, the HAN paper's widths) for a few
      steps through ``launch.hgnn_train.run_training`` with the fused
      Pallas NA kernel; the loss must be finite and fall;
  (c) compare the HAN forward at the initial weights under the kernel
      with the pure-jnp reference executor (the same online-softmax
      recurrence) and with the staged two-pass segment-softmax forward;
  (d) serve six requests over IMDB's three movie metapaths through
      ``serve.hgnn_engine.HGNNEngine``, once with the multigraph kernel
      and once with the fused FP+NA kernel; both must finish every
      request and agree.

``--chips 4`` runs only the lane-sharded training (lanes=4, one lane
shard of the plan per chip) against lanes=1 on the same host, and checks
that every chip holds its shard.

Differently ordered formulations differ by more than f32 rounding on a
TPU (f32 matmuls run as bf16 passes by default), so results are compared by
normwise relative error, max|a - b| / max|b| <= 2e-2.  Times printed
are smoke observations, not benchmark results.  Any failed check raises; the
last line printed on success is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.fusion import NABackend  # noqa: E402
from repro.core.multilane import build_multilane_plan, place_plan  # noqa: E402
from repro.dist.sharding import lane_axes, make_rules, use_rules  # noqa: E402
from repro.graphs import dataset_metapaths, dataset_target, synthetic_hetgraph  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.hgnn_train import build_problem, run_training  # noqa: E402
from repro.launch.mesh import make_lane_mesh  # noqa: E402
from repro.models.hgnn import han_forward_multilane  # noqa: E402
from repro.models.hgnn.han import han_forward, init_han  # noqa: E402
from repro.serve.hgnn_engine import HGNNEngine, make_request_mix  # noqa: E402

TOL = 2e-2
SIZE = dict(scale=1.0, feat_scale=1.0, block=128, max_edges=400_000)
HEADS, HIDDEN = 8, 8  # HAN paper: 8 heads of 8
SEED = 0


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def train(lanes: int, steps: int, backend: str = "kernel", size: dict = SIZE) -> list[float]:
    """HAN/ACM training through the launcher; returns the losses."""
    _, history, meta = run_training(
        dataset="acm", model_name="HAN", steps=steps, lanes=lanes,
        backend=backend, hidden=HIDDEN, heads=HEADS, seed=SEED,
        log_every=1, log=lambda *_: None, **size,
    )
    losses = [m["loss"] for m in history]
    secs = sorted(m["sec"] for m in history[1:])
    print(f"[train lanes={lanes}] compile+first step {history[0]['sec']:.3f}s  "
          f"steady step (median) {secs[len(secs) // 2]:.4f}s")
    print(f"[train lanes={lanes}] losses {losses}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    shards = meta["plan_shards"]
    print(f"[train lanes={lanes}] plan mask shard per device {shards}")
    check(len(shards) == lanes, f"plan spread over {len(shards)} devices, want {lanes}")
    check(all(s[0] == 1 for s in shards.values()), f"uneven lane shards: {shards}")
    return losses


def han_logits(lanes: int, backends: tuple[str, ...], size: dict = SIZE,
               segment: bool = False) -> list[np.ndarray]:
    """HAN/ACM logits at the initial weights, one array per multilane
    backend, then (``segment``) the staged segment-softmax forward's."""
    _, data = build_problem("acm", seed=SEED, **size)
    params = init_han(jax.random.key(SEED), data, hidden=HIDDEN, heads=HEADS, att_dim=2 * HIDDEN)
    mesh = make_lane_mesh(lanes, 1)
    rules = make_rules(parallelism="lanes")
    axes = lane_axes(rules)
    plan = place_plan(build_multilane_plan(data.graphs, lanes), mesh, axes)
    outs = []
    with mesh, use_rules(rules):
        for backend in backends:
            fwd = jax.jit(lambda p, b=backend: han_forward_multilane(
                p, data, plan, mesh=mesh, lane_axes=axes, backend=b))
            out = np.asarray(fwd(params))
            check(out.shape == (data.labels.shape[0], data.num_classes),
                  f"{backend} logits shape {out.shape}")
            check(bool(np.isfinite(out).all()), f"{backend} logits not finite")
            outs.append(out)
    if segment:
        fwd = jax.jit(lambda p: han_forward(p, data, backend=NABackend.SEGMENT))
        outs.append(np.asarray(fwd(params)))
    return outs


def serve(backends: tuple[NABackend, ...], size: dict = SIZE) -> list[dict]:
    """Six IMDB requests per backend; returns {rid: embedding} per backend."""
    graph = synthetic_hetgraph(
        "imdb", scale=size["scale"], feat_scale=size["feat_scale"], seed=SEED
    )
    target, _ = dataset_target("imdb")
    clusters = [[tuple(mp)] for mp in dataset_metapaths("imdb")
                if mp[0] == target and mp[-1] == target]
    results = []
    for backend in backends:
        eng = HGNNEngine(
            graph, target_type=target, hidden=HIDDEN, heads=HEADS,
            backend=backend, block=size["block"], max_edges=size["max_edges"],
            seed=SEED,
        )
        reqs = make_request_mix(0, clusters, repeats=2)
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run()
        jax.block_until_ready([r.result for r in done])
        m = eng.metrics()
        print(f"[serve {backend.value}] {len(done)}/{len(reqs)} requests in "
              f"{m['steps']} steps, {time.perf_counter() - t0:.3f}s, "
              f"fused steps {m['fused_steps']}")
        check(len(done) == len(reqs), f"{backend.value}: {len(done)}/{len(reqs)} finished")
        out = {r.rid: np.asarray(r.result) for r in done}
        check(all(np.isfinite(v).all() for v in out.values()), f"{backend.value}: non-finite")
        if backend in (NABackend.FUSED_FP, NABackend.FUSED_FP_INTERPRET):
            check(m["fused_steps"] == m["steps"], f"fused kernel bypassed: {m}")
        results.append(out)
    return results


def one_chip(steps: int, kernel: str = "kernel", size: dict = SIZE,
             serve_backends=(NABackend.MULTIGRAPH, NABackend.FUSED_FP)) -> None:
    t0 = time.perf_counter()
    train(1, steps, kernel, size)
    print(f"[phase] train {time.perf_counter() - t0:.3f}s")

    t0 = time.perf_counter()
    ker, ref, seg = han_logits(1, (kernel, "reference"), size, segment=True)
    check(np.abs(ker).max() > 0, "kernel logits are all zero")
    for name, other in (("reference", ref), ("segment", seg)):
        err = rel_err(ker, other)
        print(f"[kernel vs {name}] HAN logits rel err {err:.3e} (tol {TOL}, "
              f"max|logit| {np.abs(other).max():.3e})")
        check(err <= TOL, f"kernel forward off the {name} forward by {err:.3e}")
    print(f"[phase] forward check {time.perf_counter() - t0:.3f}s")

    t0 = time.perf_counter()
    multi, fused = serve(serve_backends, size)
    check(multi.keys() == fused.keys(), "request sets differ")
    err = max(rel_err(fused[k], multi[k]) for k in multi)
    print(f"[serve] fused_fp vs multigraph max rel err {err:.3e} (tol {TOL})")
    check(err <= TOL, f"serving backends disagree by {err:.3e}")
    print(f"[phase] serve {time.perf_counter() - t0:.3f}s")


def four_chips(steps: int, kernel: str = "kernel", size: dict = SIZE) -> None:
    t0 = time.perf_counter()
    one, four = han_logits(1, (kernel,), size)[0], han_logits(4, (kernel,), size)[0]
    err = rel_err(four, one)
    print(f"[lanes 4 vs 1] HAN logits rel err {err:.3e} (tol {TOL})")
    check(err <= TOL, f"lane-sharded forward off lanes=1 by {err:.3e}")
    l1 = train(1, steps, kernel, size)
    l4 = train(4, steps, kernel, size)
    err = rel_err(l4, l1)
    print(f"[lanes 4 vs 1] loss trajectory rel err {err:.3e} (tol {TOL})")
    check(err <= TOL, f"lane-sharded losses off lanes=1 by {err:.3e}")
    for d in jax.devices()[:4]:
        stats = d.memory_stats() or {}
        print(f"[memory] {d} bytes_in_use={stats.get('bytes_in_use')} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(f"[phase] lanes {time.perf_counter() - t0:.3f}s")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} device(s)")
    kind = devices[0].device_kind
    print(f"[device] platform=tpu kind={kind} count={len(devices)}")
    print(f"[cache] {enable_compile_cache()}")

    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.steps)
    print(f"[total] {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
