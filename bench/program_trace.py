"""The program's own spans and stage scopes in a profiler trace.

    python3 bench/program_trace.py <trace dir or .xplane.pb> [--hlo <step.hlo.txt>]

``trace_reduce.reduce`` reads the device's ops and the harness's
``bench.*`` spans.  This module reads what the program writes into the
same trace, on the same clock:

* program spans: host spans named ``serve.*``, ``train*`` or ``py.*``
  (``obs.trace``), clipped to ``bench.window`` (in a launcher's profile,
  which has none, to the device's first and last op); for each name the time
  its spans cover, the device's busy time inside it (averaged over the
  chips) and the list of span durations;
* idle gaps of the first device, each labelled by the innermost span,
  harness or program, that overlaps it most; for each gap over 100 ms,
  the host events of any name that overlap it most (a GC pass, a
  dispatch, a compile), longest overlap first;
* the stage of each device op, from the ``jax.named_scope`` names on its
  name stack (``jvp(fp)``, ``transpose(jvp(na))``, ``optimizer``) in the
  compiled step's HLO ``metadata op_name``, keyed by instruction name
  (a TPU trace's ``XLA Ops`` events carry no name stack).  The NA
  kernels are counted apart from the ``na`` scope around them; time no
  scope claims is ``unscoped``.  Each instant of device busy time goes
  to the op that started last among those running, so the parts add up
  to the busy time.
"""
from __future__ import annotations

import argparse
import bisect
import heapq
import json
import os
import re
import sys

import trace_reduce

PREFIXES = ("serve.", "train", "py.")
STAGES = ("fp", "theta", "na", "fusion", "head", "optimizer")
NA_KERNELS = ("seg_gat_agg_multigraph",)
LONG_GAP_NS = 100e6
_WRAPPED = re.compile(r"^(?:[\w-]+\()*([\w.-]+)\)*$")
_META = re.compile(r'^\s*(?:ROOT )?%?([\w.-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)


def stage_of(name_stack: str | None) -> str | None:
    """The first stage scope on an op's name stack, unwrapping the
    transforms around it (``transpose(jvp(na))`` is ``na``)."""
    for part in (name_stack or "").split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in STAGES:
            return m.group(1)
    return None


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``metadata op_name`` of an optimized HLO text."""
    return dict(_META.findall(hlo_text))


def _host_events(host_planes):
    for p in host_planes:
        for line in p.lines:
            for ev in line.events:
                yield line.name, ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def _clip(spans, w0, w1):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in spans if min(e, w1) > max(s, w0)]


def _device_ops(dev_planes, w0, w1):
    """Per device: the ``XLA Ops`` events clipped to the window, as
    (start, end, instruction name)."""
    out = []
    for p in dev_planes:
        ops = []
        for line in p.lines:
            if line.name == trace_reduce.OPS_LINE:
                for ev in line.events:
                    s, e = max(float(ev.start_ns), w0), min(float(ev.start_ns + ev.duration_ns), w1)
                    if e > s:
                        ops.append((s, e, trace_reduce.op_name(ev.name)))
        out.append(sorted(ops))
    return out


def _last_started(ops):
    """Split the union of ``ops`` (sorted by start) into pieces, each
    owned by the op that started last among those running: yields
    (start, end, op)."""
    running = []  # heap of (-start, end, index): the latest start on top
    i, t = 0, 0.0
    while i < len(ops) or running:
        if not running:
            t = ops[i][0]
        while i < len(ops) and ops[i][0] <= t:
            heapq.heappush(running, (-ops[i][0], ops[i][1], i))
            i += 1
        while running and running[0][1] <= t:  # ended: dropped once on top
            heapq.heappop(running)
        if running:
            end = min(running[0][1], ops[i][0] if i < len(ops) else float("inf"))
            yield t, end, ops[running[0][2]]
            t = end


def _stage(name: str, op_names: dict[str, str]) -> str:
    if name.startswith(NA_KERNELS):
        return "na_kernel"
    return stage_of(op_names.get(name)) or "unscoped"


def stage_seconds(dev_ops, op_names: dict[str, str]) -> dict[str, float]:
    """Device seconds per stage, summed over devices, each op by its
    ``op_names`` entry (instruction name -> name stack); the NA kernels
    as ``na_kernel``, ops of no stage as ``unscoped``."""
    out: dict[str, float] = {}
    for ops in dev_ops:
        for s, e, (_, _, name) in _last_started(ops):
            key = _stage(name, op_names)
            out[key] = out.get(key, 0.0) + (e - s) / 1e9
    return out


def unscoped_ops(dev_ops, op_names: dict[str, str], top: int = 10):
    """The ops that no stage claims, by summed device seconds."""
    acc: dict[str, float] = {}
    for ops in dev_ops:
        for s, e, name in ops:
            if _stage(name, op_names) == "unscoped":
                acc[name] = acc.get(name, 0.0) + (e - s) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


def label_gaps(gaps, spans):
    """Label each (start, end) gap by the innermost span of ``spans``
    ((name, start, end)) that overlaps it most: the most overlap, then
    the shortest span; ``other`` where none does."""
    spans = sorted((s, e, n) for n, s, e in spans)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    out = []
    for g0, g1 in gaps:
        best, key = "other", (0.0, 0.0)
        for s, e, n in spans[bisect.bisect_left(starts, g0 - longest): bisect.bisect_left(starts, g1)]:
            k = (min(e, g1) - max(s, g0), -(e - s))
            if k[0] > 0 and k > key:
                best, key = n, k
        out.append(best)
    return out


def reduce(data, *, hlo_text: str | None = None, top: int = 10, min_gap_ns: float = 1e4) -> dict:
    """Program spans, labelled gaps and the stage split of one traced
    window.  ``data`` is a ``ProfileData`` or a path to an ``.xplane.pb``;
    ``hlo_text`` is the compiled step's optimized HLO.  Times are seconds."""
    if isinstance(data, (str, os.PathLike)):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(data))
    dev_planes, host_planes = trace_reduce.planes_of(data)
    if not dev_planes:
        raise ValueError("trace holds no TPU device plane")
    events = list(_host_events(host_planes))
    windows = [(s, e) for _, n, s, e in events if n == trace_reduce.WINDOW]
    if windows:
        w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    else:  # a launcher's profile: the window is the device's first to last op
        ops = [op for dev in _device_ops(dev_planes, float("-inf"), float("inf")) for op in dev]
        if not ops:
            raise ValueError("trace holds no device op and no window")
        w0, w1 = min(s for s, _, _ in ops), max(e for _, e, _ in ops)

    dev_ops = _device_ops(dev_planes, w0, w1)
    unions = [trace_reduce._union((s, e) for s, e, _ in ops) for ops in dev_ops]
    n_dev = len(dev_ops)

    program = _clip([(n, s, e) for _, n, s, e in events if n.startswith(PREFIXES)], w0, w1)
    spans = {}
    for name in sorted({n for n, _, _ in program}):
        mine = [(s, e) for n, s, e in program if n == name]
        cover = trace_reduce._union(mine)
        spans[name] = {
            "s": sum(e - s for s, e in cover) / 1e9,
            "busy_s": sum(trace_reduce._overlap(cover, u) for u in unions) / n_dev / 1e9,
            "durations": [(e - s) / 1e9 for s, e in sorted(mine)],
        }

    edges = [w0] + [x for iv in unions[0] for x in iv] + [w1]
    gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 - g0 >= min_gap_ns]
    labels = label_gaps(gaps, [(n, s, e) for _, n, s, e in events
                               if n.startswith(PREFIXES + ("bench.",)) and n != trace_reduce.WINDOW])
    labelled = sorted(((n, (g1 - g0) / 1e9) for n, (g0, g1) in zip(labels, gaps)), key=lambda x: -x[1])

    long_gaps = []
    for g0, g1 in gaps:
        if g1 - g0 > LONG_GAP_NS:
            over = sorted((max(s, g0) - min(e, g1), e - s, line, n) for line, n, s, e in events
                          if n != trace_reduce.WINDOW and min(e, g1) > max(s, g0))
            long_gaps.append({
                "gap_s": (g1 - g0) / 1e9, "at_s": (g0 - w0) / 1e9,
                # host events over the gap: the most overlap first, then the innermost
                "host_events": [{"name": n, "line": line, "s": d / 1e9, "overlap_s": -o / 1e9}
                                for o, d, line, n in over[:5]],
            })

    op_names = hlo_op_names(hlo_text or "")
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(sum(e - s for s, e in u) for u in unions) / n_dev / 1e9,
        "devices": n_dev,
        "program_spans": spans,
        "idle_gaps": labelled[:top],
        "long_gaps": long_gaps,
        "stages": stage_seconds(dev_ops, op_names),  # summed over devices
        "unscoped_ops": unscoped_ops(dev_ops, op_names),
        "steps": sum(1 for _, n, s, _ in events if n == "bench.train_step" and w0 <= s < w1),
    }


def summary(r: dict) -> dict:
    """What ``main`` prints: per-span counts, medians and device idle
    share; per-stage ms a step and chip where the window held steps."""
    from common import median, percentile

    out = {k: r[k] for k in ("window_s", "busy_s", "devices", "idle_gaps", "unscoped_ops",
                             "long_gaps")}
    out["program_spans"] = {
        n: {"count": len(v["durations"]), "s": v["s"], "busy_s": v["busy_s"],
            "idle_share": 1.0 - v["busy_s"] / v["s"] if v["s"] else None,
            "median_ms": 1e3 * median(v["durations"]),
            "p95_ms": 1e3 * percentile(v["durations"], 95)}
        for n, v in r["program_spans"].items()
    }
    per = r["steps"] * r["devices"]
    out["stages_ms_per_step"] = {k: 1e3 * v / per for k, v in r["stages"].items()} if per else None
    out["stages_sum_over_busy"] = sum(r["stages"].values()) / (r["busy_s"] * r["devices"]) if r["busy_s"] else None
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--hlo", default=None, help="the compiled step's optimized HLO text")
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".xplane.pb") else trace_reduce.find_xplane(args.trace)
    hlo = open(args.hlo).read() if args.hlo else None
    out = summary(reduce(path, hlo_text=hlo))
    for g in out["long_gaps"]:
        print(f"long gap {g['gap_s']:.6f}s at {g['at_s']:.3f}s: host events {g['host_events']}",
              file=sys.stderr)
    if out["stages_ms_per_step"]:
        print("stages ms/step/chip " + " ".join(f"{k}={v:.4f}" for k, v in sorted(out["stages_ms_per_step"].items()))
              + f" (sum/busy {out['stages_sum_over_busy']:.6f})", file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
