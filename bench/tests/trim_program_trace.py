"""Trim a recorded profiler trace to what ``bench/program_trace.py`` reads.

    python3 bench/tests/trim_program_trace.py <recorded.xplane.pb> <out.xplane.pb> [seconds]

Like ``trim_trace.py``, and besides: keeps the program's host spans
(``serve.*``, ``train*``, ``py.*``) next to the harness's ``bench.*``
ones.  With
``seconds``, keeps only the events that start in the first ``seconds``
of ``bench.window``, and the window itself cut to that length.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import program_trace  # noqa: E402
import trace_reduce  # noqa: E402
from trim_trace import _cut  # noqa: E402

HOST = program_trace.PREFIXES + ("bench.",)


def trimmed_text(data, seconds: float | None = None) -> str:
    dev, host = trace_reduce.planes_of(data)
    w0 = min((e.start_ns for p in host for ln in p.lines for e in ln.events
              if e.name == trace_reduce.WINDOW), default=None)
    end = float("inf") if seconds is None or w0 is None else w0 + seconds * 1e9
    names: dict[str, int] = {}
    planes = []
    for pid, p in enumerate(dev + host, start=1):
        lines, meta = [], {}
        for ln in p.lines:
            if (p in dev and ln.name != trace_reduce.OPS_LINE) or not ln.events:
                continue
            out = []
            for e in ln.events:
                if e.start_ns >= end or (p in host and not e.name.startswith(HOST)):
                    continue
                dur = min(e.duration_ns, end - e.start_ns) if e.name == trace_reduce.WINDOW else e.duration_ns
                name = _cut(e.name)
                mid = names.setdefault(name, len(names) + 1)
                meta[mid] = name
                out.append(f"events {{ metadata_id: {mid} offset_ps: {int(round(e.start_ns * 1000))} "
                           f"duration_ps: {int(round(dur * 1000))} }}")
            if out:
                lines.append(f"lines {{ id: {len(lines) + 1} name: {json.dumps(ln.name)} "
                             f"timestamp_ns: 0 {' '.join(out)} }}")
        if not lines:
            continue
        md = " ".join(f"event_metadata {{ key: {k} value {{ id: {k} name: {json.dumps(v)} }} }}"
                      for k, v in meta.items())
        planes.append(f"planes {{ id: {pid} name: {json.dumps(p.name)} {' '.join(lines)} {md} }}")
    return "\n".join(planes)


def main(src: str, dst: str, seconds: float | None = None) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(src)
    Path(dst).write_bytes(ProfileData.text_proto_to_serialized_xspace(trimmed_text(data, seconds)))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], float(sys.argv[3]) if len(sys.argv) > 3 else None)
