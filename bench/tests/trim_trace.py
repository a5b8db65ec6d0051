"""Trim a recorded profiler trace to what ``bench/trace_reduce.py`` reads.

    python3 bench/tests/trim_trace.py <recorded.xplane.pb> <out.xplane.pb>

Keeps every event of each device plane's ``XLA Ops`` line, with its
time, and the harness's ``bench.*`` spans on the host; an op's
instruction text is cut after its opcode (``%name = shape opcode(``),
and the other planes, lines and the event statistics are dropped.
Makes the small recorded traces under ``bench/tests/data/``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402


def _cut(text: str) -> str:
    op = trace_reduce.opcode(text)
    if op is None:
        return text
    head, rest = text.split(" = ", 1)
    return f"{head} = {rest[: rest.index(' ' + op + '(') + len(op) + 2]}...)"


def trimmed_text(data) -> str:
    names: dict[str, int] = {}
    planes = []
    dev, host = trace_reduce.planes_of(data)
    for pid, p in enumerate(dev + host, start=1):
        keep = [
            (line.name, [e for e in line.events
                         if p in dev or e.name.startswith("bench.")])
            for line in p.lines if (p in dev and line.name == trace_reduce.OPS_LINE) or p in host
        ]
        keep = [(n, evs) for n, evs in keep if evs]
        if not keep:
            continue
        lines, meta = [], {}
        for lid, (lname, evs) in enumerate(keep, start=1):
            out = []
            for e in evs:
                name = _cut(e.name)
                mid = names.setdefault(name, len(names) + 1)
                meta[mid] = name
                out.append(f"events {{ metadata_id: {mid} offset_ps: {int(round(e.start_ns * 1000))} "
                           f"duration_ps: {int(round(e.duration_ns * 1000))} }}")
            lines.append(f"lines {{ id: {lid} name: {json.dumps(lname)} timestamp_ns: 0 {' '.join(out)} }}")
        md = " ".join(f"event_metadata {{ key: {k} value {{ id: {k} name: {json.dumps(v)} }} }}"
                      for k, v in meta.items())
        planes.append(f"planes {{ id: {pid} name: {json.dumps(p.name)} {' '.join(lines)} {md} }}")
    return "\n".join(planes)


def main(src: str, dst: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(src)
    Path(dst).write_bytes(ProfileData.text_proto_to_serialized_xspace(trimmed_text(data)))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
