"""Readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3

Runs, in one process on the chip, the program's check on every seed and
the control and planted faults on ``--control-seeds`` (see the driver's
``calibrate``), and prints one JSON object per reading.  The benchmark's
own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sweep", default="", help="serving: rates (requests/s) to sweep instead")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    ctx = run.make_context(args.workload, seeds[0], args.seconds, False, t_start=T_START)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    driver = common.load_module("drivers", ctx.traffic["kind"])
    if args.sweep:
        readings = driver.sweep(ctx, [float(r) for r in args.sweep.split(",")])
    else:
        readings = driver.calibrate(ctx, seeds, control)
    for reading in readings:
        print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
