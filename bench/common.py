"""Shared pieces of the benchmark harness: files found by name, the
compile counter, the set-up log, and the gap arithmetic of the checks."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration, traffic mix or cell."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_peaks(device_kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return peaks[device_kind]


_MODULES: dict = {}


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (a driver or a metric reader),
    loaded once per process."""
    import importlib.util

    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


@dataclasses.dataclass
class Context:
    """What a driver needs for one run."""

    workload: dict
    config: dict
    traffic: dict
    cell: dict              # bench/cells/<workload>.json: the check's limits
    seed: int
    seconds: float
    trace: bool
    t_start: float          # perf_counter at process start (set-up runs from here)
    backend: str = "kernel"  # multilane NA backend; tests pass "kernel_interpret"
    scale: float = 1.0       # graph scale; tests shrink it
    feat_scale: float = 1.0
    trace_dir: str | None = None

    def say(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA programs obtained (compiled or read from the persistent
    cache) through ``jax.monitoring`` events, with their times."""

    _instance = None

    @classmethod
    def get(cls) -> "CompileCounter":
        """The process's one counter (listeners cannot be removed)."""
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(("program", time.perf_counter()))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append(("cache_hit", time.perf_counter()))

    def count(self, kind: str, t0: float = 0.0, t1: float = float("inf")) -> int:
        return sum(1 for k, t in self.events if k == kind and t0 <= t <= t1)


class Phases:
    """Wall time of named set-up parts, printed on stderr as they end."""

    def __init__(self, ctx: Context):
        self.ctx = ctx

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        yield
        self.ctx.say(f"[setup] {name} {time.perf_counter() - t:.3f}s")


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def settle() -> None:
    """Collect the set-up's garbage and freeze what is left, so that the
    cyclic collector does not walk it during the window."""
    import gc

    gc.collect()
    gc.freeze()


def start_trace(logdir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def norm_gap(prog: float, ref: float, floor: float) -> float:
    """Gap between two norms, relative to the larger of ``ref`` and ``floor``."""
    return abs(prog - ref) / max(abs(ref), floor, 1e-30)


def worst_leaf_gap(prog: dict[str, float], ref: dict[str, float], keep=None) -> tuple[float, str]:
    """Worst leaf of ``|prog - ref| / max(ref_leaf, median ref leaf)``."""
    names = [k for k in ref if keep is None or k in keep]
    vals = sorted(ref[k] for k in names)
    med = vals[len(vals) // 2] if vals else 0.0
    worst, which = 0.0, ""
    for k in names:
        g = norm_gap(prog[k], ref[k], med)
        if g > worst:
            worst, which = g, k
    return worst, which


def median(xs):
    xs = sorted(xs)
    if not xs:
        return None
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def percentile(xs, q: float):
    """Nearest-rank percentile (q in 0..100)."""
    xs = sorted(xs)
    if not xs:
        return None
    import math

    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]
