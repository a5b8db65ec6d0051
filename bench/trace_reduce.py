"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else:

* the traced window: the host span ``bench.window`` written by the
  harness (``jax.profiler.TraceAnnotation``);
* per device plane (``/device:TPU:<i>``), the operations on its
  ``XLA Ops`` line, clipped to the window: busy time is the union of
  their intervals, and each operation's time is summed by name;
* kernels are found by name (a Pallas kernel's ``name=`` is the name of
  its operation), collectives by their HLO opcode (a psum is named
  ``psum.<n>`` and is an ``all-reduce``);
* the idle gaps of the first device, each labelled by the harness span
  on the host that overlaps it most;
* per harness span name, the time its spans cover in the window and how
  much of it the device was busy (averaged over the chips used).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def op_name(name: str) -> str:
    """An operation's HLO instruction name: TPU traces name an XLA op by
    its whole instruction text, ``%name = shape opcode(...)``."""
    if name.startswith("%"):
        name = name[1:].split(" = ", 1)[0]
    return name


def opcode(text: str) -> str | None:
    """The HLO opcode of an op's instruction text (``... = shape opcode(``)."""
    if " = " not in text:
        return None
    m = _OPCODE.search(text.split(" = ", 1)[1])
    return m.group(1) if m else None


def is_collective(text: str) -> bool:
    op = opcode(text)
    return op is not None and op.startswith(COLLECTIVES)


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def planes_of(data):
    """(device planes sorted by index, host planes) of a ProfileData."""
    dev, host = [], []
    for p in data.planes:
        if p.name.startswith(DEVICE_PREFIX):
            dev.append(p)
        elif p.name.startswith("/host:"):
            host.append(p)
    dev.sort(key=lambda p: int("".join(c for c in p.name[len(DEVICE_PREFIX):] if c.isdigit()) or 0))
    return dev, host


def host_spans(host_planes, prefix: str = "bench.") -> list[tuple[str, float, float]]:
    out = []
    for p in host_planes:
        for line in p.lines:
            for name, s, e in _events(line):
                if name.startswith(prefix):
                    out.append((name, s, e))
    return out


def reduce(data, *, top: int = 10, min_gap_ns: float = 1e4) -> dict:
    """Numbers of one traced window.  ``data`` is a ``ProfileData`` or a
    path to an ``.xplane.pb``.  Times are seconds."""
    if isinstance(data, (str, os.PathLike)):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(data))
    dev_planes, host_planes = planes_of(data)
    if not dev_planes:
        raise ValueError("trace holds no TPU device plane")
    spans = host_spans(host_planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} host span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    window_ns = w1 - w0

    busy, op_ns, coll_ns, unions = [], {}, 0.0, []
    for p in dev_planes:
        ops = []
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for text, s, e in _events(line):
                s, e = max(s, w0), min(e, w1)
                if e > s:
                    ops.append((s, e))
                    name = op_name(text)
                    op_ns[name] = op_ns.get(name, 0.0) + (e - s)
                    if is_collective(text):
                        coll_ns += e - s
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged))
        unions.append(merged)
    first_union = unions[0]

    # idle gaps of the first device, labelled by the host span overlapping most
    gaps = []
    edges = [w0] + [x for iv in first_union for x in iv] + [w1]
    label_spans = sorted((s, e, n) for n, s, e in spans if n != WINDOW)
    starts = [s for s, _, _ in label_spans]
    longest = max((e - s for s, e, _ in label_spans), default=0.0)
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 - g0 < min_gap_ns:
            continue
        best, best_ov = "other", 0.0
        # only spans that start after g0 - longest can reach into the gap
        for s, e, n in label_spans[bisect.bisect_left(starts, g0 - longest): bisect.bisect_left(starts, g1)]:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = n, ov
        gaps.append((best, (g1 - g0) / 1e9))
    gaps.sort(key=lambda x: -x[1])

    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])
    n_dev = len(dev_planes)
    span_s = {}
    for name in sorted({n for n, _, _ in spans if n != WINDOW}):
        cover = _union((max(s, w0), min(e, w1)) for n, s, e in spans if n == name and min(e, w1) > max(s, w0))
        span_s[name] = {"s": sum(e - s for s, e in cover) / 1e9,
                        "busy_s": sum(_overlap(cover, u) for u in unions) / n_dev / 1e9}
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": n_dev,
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},  # summed over devices
        "collective_s": coll_ns / 1e9,
        "spans": span_s,  # {name: {"s": covered, "busy_s": device busy inside}}
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in ops_sorted[:top]],
            "idle_gaps": gaps[:top],
        },
    }


def kernel_seconds(reduced: dict, names: tuple[str, ...]) -> float | None:
    """Summed device time of every operation whose name starts with one of
    ``names``; None where the trace holds none."""
    hits = [v for k, v in reduced["op_s"].items() if any(k.startswith(n) for n in names)]
    return sum(hits) if hits else None
