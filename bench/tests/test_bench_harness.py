"""The harness finds every file by name, refuses what it cannot measure."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import run  # noqa: E402

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCHMARK["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell, "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=str(BENCH.parent), timeout=120,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cfg", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_loads_by_name(cfg):
    loaded = common.load_json("configs", cfg["name"])
    assert (BENCH.parent / cfg["file"]).resolve() == (BENCH / "configs" / f"{cfg['name']}.json").resolve()
    assert loaded["name"] == cfg["name"]
    assert sorted(loaded["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("cell", BENCHMARK["workloads"], ids=lambda c: c["name"])
def test_cell_traffic_driver_and_limits_load_by_name(cell):
    ctx = run.make_context(cell["name"], 1, 1.0, False)
    assert ctx.config["name"] == cell["config"]
    driver = common.load_module("drivers", ctx.traffic["kind"])
    assert callable(driver.run) and callable(driver.calibrate)
    assert ctx.cell["limits"] and all(v > 0 for v in ctx.cell["limits"].values())
    e2e, layer = run.metrics_for(BENCHMARK, cell["name"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2 and layer


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_loads_by_name(metric):
    assert callable(common.load_module("metrics", metric["name"]).read)
    moved = {m["name"]: m for m in BENCHMARK["end_to_end"]}[metric["moves"]]
    for w in metric.get("workloads", []):
        assert w in moved.get("workloads", [w])


def test_unknown_device_kind_is_refused():
    assert common.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        common.load_peaks("TPU v99 imaginary")


def test_unknown_names_are_refused():
    with pytest.raises(FileNotFoundError):
        common.load_json("configs", "no-such-config")
    with pytest.raises(FileNotFoundError):
        common.load_module("metrics", "no_such_metric")
    with pytest.raises(SystemExit):
        run.make_context("no-such-cell", 1, 1.0, False)


def test_serving_schedule_same_work_for_every_seed():
    drv = common.load_module("drivers", "serve")
    cfg = common.load_json("configs", "han-imdb")
    tr = common.load_json("traffic", "serve-steady")
    mps = cfg["graph"]["metapaths"]
    a = drv.schedule(tr, mps, cfg["graph"]["vertices"], 20.0, 2147483659)
    b = drv.schedule(tr, mps, cfg["graph"]["vertices"], 20.0, 7)
    key = lambda ops: sorted((k, str(v)) for _, k, v in ops)
    assert key(a) == key(b) and [o[0] for o in a] != [o[0] for o in b]
    n_req = sum(1 for _, k, _ in a if k == "request")
    assert n_req == round(tr["rate_per_s"] * 20.0)
    assert all(0.0 <= d < 20.0 for d, _, _ in a) and a == sorted(a, key=lambda o: o[0])


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}"


def test_benchmark_file_keeps_its_shape():
    import re

    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs", "workloads",
                              "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"] and BENCHMARK["command"][1].startswith("bench/")
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.fullmatch(NAME, c["name"]) and (BENCH.parent / c["file"]).is_file()
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.fullmatch(NAME, w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert re.fullmatch(NAME, m["name"]) and re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCHMARK["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
