"""``bench/trace_reduce.py`` on a hand-made trace and on one recorded on
a TPU v5e (``bench/tests/data/``)."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

DATA = BENCH / "tests" / "data"


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


def made_trace(n_dev: int = 1) -> Data:
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 1000, 10_000_000),            # window [1000, 10_001_000)
        Ev("bench.engine_step", 2_000_000, 3_000_000),   # covers the first gap
        Ev("bench.wait_result", 6_500_000, 1_000_000),
    ])])
    devs = []
    for d in range(n_dev):
        devs.append(Plane(f"/device:TPU:{d}", [
            Line("XLA Modules", [Ev("jit_step", 0, 10_000_000)]),
            Line("XLA Ops", [
                Ev("fusion.1", 0, 2_001_000),                          # clipped to 2_000_000
                Ev("%seg_gat_agg_multigraph.1 = (f32[96,64]) custom-call(s32[96] %c)",
                   5_001_000, 1_000_000),                              # after a 3 ms gap
                Ev("seg_gat_agg_multigraph_bwd", 5_501_000, 1_000_000),  # overlaps: union
                Ev("%psum.3 = f32[8]{0:T(128)} all-reduce(f32[8] %x)", 9_001_000, 300_000),
                Ev("%fusion.2 = f32[8] fusion(f32[8] %all-reduce.1)", 9_301_000, 200_000),
            ]),
        ]))
    return Data([host] + devs[::-1])


def test_busy_idle_kernel_and_collective_time():
    r = trace_reduce.reduce(made_trace())
    assert r["window_s"] == pytest.approx(0.010)
    # busy: [1000, 2_001_000) + [5_001_000, 6_501_000) + [9_001_000, 9_501_000)
    assert r["busy_s"] == pytest.approx((2_000_000 + 1_500_000 + 500_000) / 1e9)
    assert trace_reduce.kernel_seconds(r, ("seg_gat_agg_multigraph",)) == pytest.approx(0.002)
    assert trace_reduce.kernel_seconds(r, ("no_such_kernel",)) is None
    assert r["collective_s"] == pytest.approx(0.0003)  # the psum; not the fusion reading it
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["bench.engine_step"] == pytest.approx(0.003)   # [2_001_000, 5_001_000)
    assert gaps["bench.wait_result"] == pytest.approx(0.0025)  # [6_501_000, 9_001_000)
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_device_busy_inside_harness_spans():
    import common

    r = trace_reduce.reduce(made_trace(2))
    step, wait = r["spans"]["bench.engine_step"], r["spans"]["bench.wait_result"]
    assert step["s"] == pytest.approx(0.003) and wait["s"] == pytest.approx(0.001)
    # fusion.1 reaches 1 us into the step, the NA kernels 1 us into the wait
    assert step["busy_s"] == pytest.approx(1e-6) and wait["busy_s"] == pytest.approx(1e-6)
    idle = common.load_module("metrics", "idle_share.serve").read(r, {})
    assert idle == pytest.approx(100.0 * (1 - 1e-6 / 0.003))
    r["spans"].pop("bench.engine_step")
    assert common.load_module("metrics", "idle_share.serve").read(r, {}) is None


def test_busy_is_averaged_over_devices_and_ops_summed():
    one, four = trace_reduce.reduce(made_trace(1)), trace_reduce.reduce(made_trace(4))
    assert four["devices"] == 4 and four["busy_s"] == pytest.approx(one["busy_s"])
    assert four["collective_s"] == pytest.approx(4 * one["collective_s"])


def test_trace_without_window_or_device_is_refused():
    t = made_trace()
    with pytest.raises(ValueError):
        trace_reduce.reduce(Data([p for p in t.planes if not p.name.startswith("/device")]))
    t.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce(t)


# (file, window_s, busy_s, NA kernel s, collective s) as reduced when recorded:
# 0.25 s of han-acm.train on one TPU v5e, 0.10 s of han-acm.train-lanes4 on four
RECORDED = [
    ("han-acm.train.xplane.pb", 0.248601185, 0.245496445, 0.23632663, 0.0),
    ("han-acm.train-lanes4.xplane.pb", 0.101687559, 0.0963052745, 0.363856086, 0.005737789),
]


@pytest.mark.parametrize("name,window,busy,na,coll", RECORDED, ids=[r[0] for r in RECORDED])
def test_trace_recorded_on_the_chip(name, window, busy, na, coll):
    r = trace_reduce.reduce(DATA / name)
    assert r["window_s"] == pytest.approx(window) and r["busy_s"] == pytest.approx(busy)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert trace_reduce.kernel_seconds(r, ("seg_gat_agg_multigraph",)) == pytest.approx(na)
    assert r["collective_s"] == pytest.approx(coll)
    assert (r["collective_s"] > 0) == (r["devices"] == 4)
    assert all(sec >= 0 for _, sec in r["breakdown"]["idle_gaps"])
    assert r["spans"] and all(0 <= v["busy_s"] <= v["s"] * (1 + 1e-9) for v in r["spans"].values())
