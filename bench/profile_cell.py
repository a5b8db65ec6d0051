"""One traced run of a cell, kept for reading by hand and by ``program_trace``.

    python3 bench/profile_cell.py --workload <cell> --seed <n> --seconds <s> --out <dir>
        [--trim <seconds>]

Runs the cell as ``bench/run.py --trace 1`` does and prints the same
result line, but keeps the profile under ``<dir>/trace``, records the
garbage collector's passes as ``py.gc`` spans where the program has
``obs.trace.gc_spans``, and then writes into ``<dir>``:

* ``step.hlo.txt`` (training cells): the optimized HLO of the window's
  step, compiled again after the run (a cache hit), for stages by
  ``metadata op_name``;
* ``summary.json``: ``program_trace.summary`` of the window;
* with ``--trim``, ``<cell>.xplane.pb``: the first ``--trim`` seconds of
  the window, trimmed by ``tests/trim_program_trace.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def step_hlo(ctx) -> str:
    """The optimized HLO of the training step that ``drivers/train.py``
    builds for ``ctx``, compiled as the window ran it."""
    import common
    from repro.dist.sharding import use_rules

    train = common.load_module("drivers", "train")
    prog = train.Program(ctx, common.Phases(ctx))
    state, batch = prog.init_state(ctx.seed), prog.batch(ctx.seed)
    with prog.mesh, use_rules(prog.rules):
        return prog.step.lower(state, batch).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trim", type=float, default=None)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    ctx = run.make_context(args.workload, args.seed, args.seconds, True, t_start=T_START,
                           trace_dir=os.path.join(args.out, "trace"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < ctx.workload["chips"]:
        print(f"profile_cell: the cell needs {ctx.workload['chips']} TPU chips", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    try:
        from repro.obs.trace import gc_spans
    except ImportError:  # a program without it: no py.gc spans
        gc_spans = contextlib.nullcontext
    with gc_spans():
        run.emit(run.execute(ctx, devices[: ctx.workload["chips"]]))

    import program_trace
    import trace_reduce

    path = trace_reduce.find_xplane(ctx.trace_dir)
    hlo = None
    if ctx.traffic["kind"] == "train":
        hlo = step_hlo(ctx)
        with open(os.path.join(args.out, "step.hlo.txt"), "w") as f:
            f.write(hlo)
    out = program_trace.summary(program_trace.reduce(path, hlo_text=hlo))
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(out, f, indent=1)
    if args.trim:
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
        from trim_program_trace import main as trim

        trim(path, os.path.join(args.out, f"{args.workload}.xplane.pb"), args.trim)
    return 0


if __name__ == "__main__":
    sys.exit(main())
