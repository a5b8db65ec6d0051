"""On-chip benchmark of HAN training and HGNN serving.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``)
and a traffic mix (``bench/traffic``); the mix's ``kind`` names the
driver (``bench/drivers/<kind>.py``), ``bench/cells/<cell>.json`` holds
the limits of the check that decides ``correct``, and each per-layer
metric is read by ``bench/metrics/<metric>.py``.  The graph and every
shape come from the configuration; ``--seed`` draws the weights,
arrivals and update payloads.

The run fails, printing no result, unless JAX finds a TPU with as many
chips as the cell asks for.  ``--trace 1`` profiles the measured window
and prints the per-layer metrics in place of the end-to-end ones.  The
last line of standard output is one JSON object; the last lines of
standard error give each compared number beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_context(workload: str, seed: int, seconds: float, trace: bool, **kw) -> common.Context:
    bench = common.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    return common.Context(
        workload=w,
        config=common.load_json("configs", w["config"]),
        traffic=common.load_json("traffic", w["traffic"]),
        cell=common.load_json("cells", workload),
        seed=seed, seconds=seconds, trace=trace, t_start=kw.pop("t_start", T_START), **kw,
    )


def metrics_for(bench: dict, workload: str):
    """(end-to-end, per-layer) metric entries that this cell reports."""
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]
    return e2e, layer


def execute(ctx: common.Context, devices) -> dict:
    """Drive one run of a cell on ``devices``; returns the result object."""
    import jax

    counter = common.CompileCounter.get()
    kind = devices[0].device_kind
    peaks = common.load_peaks(kind) if devices[0].platform == "tpu" else None
    driver = common.load_module("drivers", ctx.traffic["kind"])
    own_dir = ctx.trace and ctx.trace_dir is None
    if own_dir:
        ctx.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    rec = driver.run(ctx, counter)
    rec.update(peaks=peaks, chips=ctx.workload["chips"])

    e2e, layer = metrics_for(common.load_benchmark(), ctx.workload["name"])
    device = {"platform": devices[0].platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": rec["correct"], "attempted": rec["attempted"], "failed": rec["failed"]}
    metrics = {}
    if ctx.trace:
        import trace_reduce

        reduced = trace_reduce.reduce(trace_reduce.find_xplane(ctx.trace_dir))
        if own_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        for m in layer:
            val = common.load_module("metrics", m["name"]).read(reduced, rec)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        out["breakdown"] = reduced["breakdown"]
    else:
        for m in e2e:
            if m["name"] in rec["e2e"]:
                metrics[m["name"]] = {"value": rec["e2e"][m["name"]], "unit": m["unit"]}
    out.update(metrics=metrics, device=device)
    if "breakdown" in out:  # keep the keys in the documented order
        out["breakdown"] = out.pop("breakdown")
    out["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in rec["checks"]}
    return out


def emit(out: dict) -> None:
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    ctx = make_context(args.workload, args.seed, args.seconds, bool(args.trace))
    import jax

    devices = jax.devices()
    ctx.say(f"[setup] imports and TPU runtime start {time.perf_counter() - T_START:.3f}s")
    chips = ctx.workload["chips"]
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); no result", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}; no result", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ctx.say(f"[bench] {ctx.workload['name']} seed={args.seed} seconds={args.seconds} "
            f"trace={args.trace} device={devices[0].device_kind} x{len(devices)} "
            f"cache={enable_compile_cache()}")
    common.CompileCounter.get()
    emit(execute(ctx, devices[:chips]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
