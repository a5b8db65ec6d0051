"""``bench/program_trace.py`` on hand-made traces and on the traces
recorded on a TPU v5e (``bench/tests/data/``)."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_trace  # noqa: E402
import trace_reduce  # noqa: E402

DATA = BENCH / "tests" / "data"
MS = 1_000_000  # ns


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Data:
    planes: list


def serving_trace() -> Data:
    """A 10 ms window: one engine step [1, 9) ms with the engine's spans
    in it, a GC pass inside the admission, the NA kernel at [5, 7) ms."""
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 10 * MS),
        Ev("bench.engine_step", 0.9 * MS, 8.2 * MS),
        Ev("serve.step", 1 * MS, 8 * MS),
        Ev("serve.admit", 1 * MS, 2 * MS),
        Ev("py.gc", 1.5 * MS, 1 * MS),
        Ev("serve.unit_tables", 3 * MS, 1.5 * MS),
        Ev("serve.na", 4.5 * MS, 0.5 * MS),
        Ev("serve.fuse", 7 * MS, 1 * MS),
        Ev("serve.fuse", 9.5 * MS, 1 * MS),   # reaches past the window: clipped
    ])])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("fusion.0", 0, 1.2 * MS),
        Ev("%seg_gat_agg_multigraph = f32[8] custom-call(f32[8] %x)", 5 * MS, 2 * MS),
        Ev("fusion.3", 7.5 * MS, 0.25 * MS),
    ])])
    return Data([host, dev])


def test_program_spans_busy_inside_and_durations():
    r = program_trace.reduce(serving_trace())
    spans = r["program_spans"]
    assert set(spans) == {"serve.step", "serve.admit", "py.gc", "serve.unit_tables",
                          "serve.na", "serve.fuse"}
    assert spans["serve.step"]["s"] == pytest.approx(0.008)
    assert spans["serve.step"]["busy_s"] == pytest.approx(0.00245)
    assert spans["serve.admit"]["busy_s"] == pytest.approx(0.0002)  # [1, 1.2) ms
    assert spans["serve.unit_tables"]["busy_s"] == 0.0
    # the second fuse span is clipped to the window's end
    assert spans["serve.fuse"]["durations"] == pytest.approx([0.001, 0.0005])
    assert spans["serve.fuse"]["busy_s"] == pytest.approx(0.00025)


def test_gaps_labelled_by_the_innermost_span_that_overlaps_most():
    r = program_trace.reduce(serving_trace())
    gaps = dict(r["idle_gaps"])
    # [1.2, 5) ms: serve.admit overlaps 1.8 ms, serve.unit_tables 1.5,
    # serve.step and bench.engine_step 3.8 each: the inner one wins
    assert r["idle_gaps"][0] == ("serve.step", pytest.approx(0.0038))
    # [7.75, 10) ms leaves the step: the harness span overlaps it most
    assert r["idle_gaps"][1] == ("bench.engine_step", pytest.approx(0.00225))
    assert gaps["serve.fuse"] == pytest.approx(0.0005)  # [7, 7.5) ms
    assert program_trace.label_gaps([(2e6, 2.4e6)], [("serve.admit", 1e6, 3e6),
                                                     ("py.gc", 1.5e6, 2.5e6)]) == ["py.gc"]
    assert program_trace.label_gaps([(0, 1)], []) == ["other"]


def test_long_gap_lists_the_host_events_over_it():
    data = serving_trace()
    host = data.planes[0].lines[0].events
    host[0].duration_ns = 400 * MS  # window [0, 400) ms: a 392 ms gap after the step
    host.append(Ev("py.gc", 100 * MS, 150 * MS))
    host.append(Ev("bench.wait_arrival", 9 * MS, 390 * MS))
    [g] = program_trace.reduce(data)["long_gaps"]
    assert g["gap_s"] == pytest.approx(0.39225)
    assert [e["name"] for e in g["host_events"]][:2] == ["bench.wait_arrival", "py.gc"]


def stage_trace() -> tuple[Data, dict]:
    """One training step on one device, the ops overlapping at [4, 5) ms
    (an async copy under the backward kernel), and a hand-made map of
    their HLO ``metadata op_name``."""
    ops = [
        ("fusion.1", 0, 1, "jit(train_step)/jvp(fp)/dot_general"),
        ("fusion.2", 1, 1.5, "jit(train_step)/jvp(theta)/nhd,ghd->gnh"),
        ("pad.1", 1.5, 2, "jit(train_step)/jvp(na)/pad"),
        ("seg_gat_agg_multigraph.1", 2, 3, "jit(train_step)/jvp(na)/jit(seg_gat_agg_multigraph)"),
        ("fusion.3", 3, 3.5, "jit(train_step)/jvp(fusion)/jit(elu)"),
        ("seg_gat_agg_multigraph_bwd.1", 3.5, 5, "jit(train_step)/transpose(jvp(na))/custom"),
        ("copy-start.1", 4, 6, None),                      # no scope: unscoped
        ("fusion.4", 6, 6.5, "jit(train_step)/transpose(jvp(head))/mul"),
        ("fusion.5", 6.5, 7, "jit(train_step)/transpose(jvp(fp))/dot_general"),
        ("fusion.6", 7, 8, "jit(train_step)/optimizer/add"),
    ]
    host = Plane("/host:CPU", [Line("python", [Ev("bench.window", 0, 10 * MS),
                                               Ev("bench.train_step", 0, 1 * MS)])])
    events = [Ev(n, s * MS, (e - s) * MS) for n, s, e, _ in ops]
    hlo = {n: st for n, _, _, st in ops if st}
    return Data([host, Plane("/device:TPU:0", [Line("XLA Ops", events)])]), hlo


def test_stage_split_adds_up_to_busy_time():
    data, hlo = stage_trace()
    text = "\n".join(f'  %{n} = f32[8] fusion(f32[8] %p), metadata={{op_name="{st}" source_line=1}}'
                     for n, st in hlo.items())
    r = program_trace.reduce(data, hlo_text=text)
    st = r["stages"]
    ms = {k: v * 1e3 for k, v in st.items()}
    # the copy started last, so [4, 5) ms is its own, not the kernel's
    assert ms == pytest.approx({"fp": 1.5, "theta": 0.5, "na": 0.5, "na_kernel": 1.5,
                                "fusion": 0.5, "unscoped": 2.0, "head": 0.5, "optimizer": 1.0})
    assert sum(st.values()) == pytest.approx(r["busy_s"]) == pytest.approx(0.008)
    assert r["unscoped_ops"] == [("copy-start.1", pytest.approx(0.002))]
    s = program_trace.summary(r)
    assert r["steps"] == 1 and s["stages_ms_per_step"]["na_kernel"] == pytest.approx(1.5)
    assert s["stages_sum_over_busy"] == pytest.approx(1.0)


@pytest.mark.parametrize("stack,stage", [
    ("jit(train_step)/jvp(fp)/dot_general", "fp"),
    ("jit(train_step)/transpose(jvp(na))/jit(seg_gat_agg_multigraph)/x", "na"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/jvp(fusion)/jit(elu)", "fusion"),
    ("psum", None),
    (None, None),
])
def test_stage_of_a_name_stack(stack, stage):
    assert program_trace.stage_of(stack) == stage


def test_hlo_op_names_keyed_by_instruction():
    text = ('HloModule m\n\nENTRY %main {\n'
            '  %fusion.8 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(step)/jvp(na)/pad" source_file="x.py"}\n'
            '  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.8)\n}\n')
    assert program_trace.hlo_op_names(text) == {"fusion.8": "jit(step)/jvp(na)/pad"}


@pytest.mark.parametrize("name", ["han-acm.train.xplane.pb", "han-acm.train-lanes4.xplane.pb"])
def test_recorded_trace_without_program_spans(name):
    """On the traces recorded before the program had spans, the gap labels
    are the harness's own, as ``trace_reduce`` gives them."""
    r = program_trace.reduce(DATA / name)
    t = trace_reduce.reduce(DATA / name)
    assert r["program_spans"] == {}
    assert r["idle_gaps"] == t["breakdown"]["idle_gaps"]
    assert r["busy_s"] == pytest.approx(t["busy_s"])
    assert sum(r["stages"].values()) == pytest.approx(r["busy_s"] * r["devices"])


def test_trimmed_to_the_first_part_of_the_window(tmp_path):
    from trim_program_trace import main as trim

    src = DATA / "han-acm.train.xplane.pb"
    out = tmp_path / "cut.xplane.pb"
    trim(str(src), str(out), 0.1)
    r, full = trace_reduce.reduce(out), trace_reduce.reduce(src)
    assert r["window_s"] == pytest.approx(0.1, rel=1e-6)
    assert 0 < r["busy_s"] <= r["window_s"] and full["window_s"] > 0.2
    assert program_trace.reduce(out)["busy_s"] == pytest.approx(r["busy_s"])


SERVE_STAGES = ("serve.admit", "serve.fp", "serve.theta", "serve.unit_tables", "serve.na",
                "serve.fuse")


def test_recorded_serving_trace():
    """3 s of han-imdb.serve on one TPU v5e, with the engine's spans:
    the harness's reduction is unchanged by them, and the idle gaps
    inside engine steps go to the engine's spans."""
    path = DATA / "han-imdb.serve.xplane.pb"
    t = trace_reduce.reduce(path, top=10**6)
    r = program_trace.reduce(path, top=10**6)
    assert t["window_s"] == pytest.approx(3.0) and t["busy_s"] == pytest.approx(0.388339117)
    assert trace_reduce.kernel_seconds(t, ("seg_gat_agg_multigraph",)) == pytest.approx(0.379983271)
    assert set(t["spans"]) == {"bench.engine_step", "bench.submit", "bench.wait_arrival",
                               "bench.wait_result"}
    spans = r["program_spans"]
    step = spans["serve.step"]
    assert len(step["durations"]) == 37 and "py.gc" in spans
    for name in SERVE_STAGES:
        assert len(spans[name]["durations"]) == 37, name
        assert spans[name]["s"] < step["s"] and spans[name]["busy_s"] <= step["busy_s"]
    engine = t["spans"]["bench.engine_step"]
    assert step["s"] <= engine["s"] and step["busy_s"] == pytest.approx(engine["busy_s"], rel=1e-2)
    harness = [n for n, _ in t["breakdown"]["idle_gaps"]]
    labels = [n for n, _ in r["idle_gaps"]]
    assert len(labels) == len(harness) and labels.count("bench.engine_step") < 0.01 * len(labels)
    assert sum(n.startswith("serve.") for n in labels) == pytest.approx(harness.count("bench.engine_step"), abs=4)


def test_recorded_training_trace_stages_add_up():
    """The han-acm.train trace of ``trace_reduce``'s tests with the step's
    ``op_name`` metadata (the ops it holds, from the optimized HLO)."""
    import json

    ops = json.loads((DATA / "han-acm.train.op_names.json").read_text())
    hlo = "\n".join(f'  %{k} = f32[] add(), metadata={{op_name="{v}"}}' for k, v in ops.items())
    r = program_trace.reduce(DATA / "han-acm.train.xplane.pb", hlo_text=hlo)
    t = trace_reduce.reduce(DATA / "han-acm.train.xplane.pb")
    st = r["stages"]
    assert set(st) == {"fp", "theta", "na", "na_kernel", "fusion", "head", "optimizer", "unscoped"}
    assert sum(st.values()) == pytest.approx(t["busy_s"], rel=1e-9)
    assert st["na_kernel"] == pytest.approx(trace_reduce.kernel_seconds(t, ("seg_gat_agg_multigraph",)))
    # ms a step: the kernels, then the NA work around them; nearly nothing unscoped
    per = {k: 1e3 * v / r["steps"] for k, v in st.items()}
    assert r["steps"] == 10 and per["na_kernel"] == pytest.approx(23.6327, abs=1e-3)
    assert per["na"] == pytest.approx(0.7511, abs=1e-3)
    assert per["unscoped"] < 0.1 * (sum(per.values()) - per["na_kernel"])
