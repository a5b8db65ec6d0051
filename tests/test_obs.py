"""Observability layer (obs/, DESIGN.md §12): profiler spans, metrics,
emitter, benchmark stats, and the serving engine's registry wiring."""
import gc
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.fusion import NABackend
from repro.graphs import dataset_target, synthetic_hetgraph
from repro.obs import Emitter, MetricsRegistry, gc_spans, profile, trace_span
from repro.serve.hgnn_engine import HGNNEngine, make_request_mix

SERVE_SPANS = ("serve.admit", "serve.fp", "serve.theta", "serve.unit_tables",
               "serve.na", "serve.fuse")


def host_spans(logdir) -> list[tuple[str, str, float, float]]:
    """(line, name, start, end) of every host event in the profile."""
    [path] = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((line.name, e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


# -- spans -------------------------------------------------------------------


def test_disabled_tracer_is_noop_identity():
    # no profiler session: spans record nothing and change nothing
    x = jnp.arange(6.0).reshape(2, 3)

    def f(a):
        return a * 2.0 + 1.0

    with trace_span("t.outer", k=1) as sp:
        y = f(x)
        sp.set_metadata(extra=2)  # absorbed while nothing records
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert np.array_equal(np.asarray(y), np.asarray(f(x)))
    n_callbacks = len(gc.callbacks)
    with profile(None):  # an empty --trace profiles nothing, hooks nothing
        assert len(gc.callbacks) == n_callbacks


def test_profile_records_spans_meta_and_gc_passes(tmp_path):
    n_callbacks = len(gc.callbacks)
    with profile(str(tmp_path)):
        with trace_span("t.step", step=3) as sp:
            sp.set_metadata(rids="1/2")
            gc.collect()
    assert len(gc.callbacks) == n_callbacks  # the gc hook is gone again
    spans = host_spans(tmp_path)
    [(line, _, s0, s1)] = [sp for sp in spans if sp[1] == "t.step"]
    gcs = [sp for sp in spans if sp[1] == "py.gc"]
    assert gcs and any(s0 <= s and e <= s1 for _, _, s, e in gcs)
    [path] = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    [meta] = [dict(e.stats) for p in ProfileData.from_file(path).planes
              for ln in p.lines for e in ln.events if e.name == "t.step"]
    assert meta == {"step": 3, "rids": "1/2"}
    assert glob.glob(f"{tmp_path}/**/perfetto_trace.json.gz", recursive=True)
    with gc_spans():  # outside a session the hook records nothing and raises nothing
        gc.collect()


# -- metrics -----------------------------------------------------------------


def test_histogram_bucket_edges_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("t.lat", base=2.0)
    for v in (1.0, 1.5, 4.0):
        h.observe(v)
    h.observe(0.0)  # underflow
    assert h.bucket_edges() == [(1.0, 1), (2.0, 1), (4.0, 1)]
    assert h.underflow == 1
    # conservative (upper-edge) percentiles
    assert h.percentile(0.5) == 1.0
    assert h.percentile(1.0) == 4.0
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["max"] == 4.0 and snap["min"] == 0.0


def test_labeled_series_and_kind_collision():
    reg = MetricsRegistry()
    reg.counter("req", route="a").inc(2)
    reg.counter("req", route="b").inc(3)
    assert reg.counter("req", route="a") is reg.counter("req", route="a")
    assert reg.value("req", route="a") == 2
    assert reg.value("req", route="b") == 3
    with pytest.raises(TypeError):
        reg.gauge("req", route="a")  # same series, different kind
    snap = reg.snapshot()
    assert {s["labels"]["route"] for s in snap["counters"]["req"]} == {"a", "b"}


def test_registry_export_json(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(3.0)
    path = tmp_path / "metrics.json"
    reg.export_json(str(path))
    doc = json.loads(path.read_text())
    assert doc["gauges"]["g"][0]["value"] == 1.5
    assert doc["histograms"]["h"][0]["value"]["count"] == 1


def test_emitter_line_and_jsonl(tmp_path):
    got = []
    path = tmp_path / "ev.jsonl"
    em = Emitter(sink=got.append, jsonl_path=str(path))
    line = em.emit("train", step=3, loss=0.123456789, tags=["a", "b"])
    em.close()
    assert line == "[train] step=3 loss=0.123457 tags=a/b" == got[0]
    rec = json.loads(path.read_text())
    assert rec == {"event": "train", "step": 3, "loss": 0.123456789, "tags": ["a", "b"]}


# -- serving engine wiring ---------------------------------------------------


def test_engine_registry_matches_metrics():
    g = synthetic_hetgraph("imdb", scale=0.05, feat_scale=0.02, seed=0)
    target, _ = dataset_target("imdb")
    eng = HGNNEngine(
        g, target_type=target, num_slots=2, cache_bytes=1 << 18,
        backend=NABackend.BLOCK,
    )
    clusters = [
        [("movie", "director", "movie"), ("movie", "actor", "movie")],
        [("movie", "keyword", "movie")],
    ]
    for req in make_request_mix(0, clusters, repeats=2):
        eng.submit(req)
    eng.run()
    m = eng.metrics()
    assert m["requests_finished"] == 4
    for k, v in m.items():
        assert abs(eng.registry.value(f"serve.{k}") - float(v)) < 1e-9, k
    # per-step latency histogram saw every step
    snap = eng.registry.snapshot()
    assert snap["histograms"]["serve.step_ms"][0]["value"]["count"] == m["steps"]
    # analytical FP-traffic replay is self-consistent on this run
    drift = eng.fp_model_drift()
    assert drift["fp_measured_fetched_bytes"] == m["fetched_bytes"]
    assert 0.0 < m["fp_model_drift"] <= 1.5


def test_engine_spans_under_tracing(tmp_path):
    g = synthetic_hetgraph("imdb", scale=0.05, feat_scale=0.02, seed=0)
    target, _ = dataset_target("imdb")
    eng = HGNNEngine(
        g, target_type=target, num_slots=2, backend=NABackend.MULTIGRAPH_INTERPRET,
        block=8, max_edges=2_000,
    )
    for req in make_request_mix(0, [[("movie", "director", "movie")]], repeats=3):
        eng.submit(req)
    with profile(str(tmp_path)):
        eng.run()
    spans = host_spans(tmp_path)
    steps = [(line, s, e) for line, n, s, e in spans if n == "serve.step"]
    assert len(steps) == eng.steps_run == 2
    for name in SERVE_SPANS:
        mine = [(line, s, e) for line, n, s, e in spans if n == name]
        assert len(mine) == eng.steps_run, name
        # each sits inside a serve.step on the same thread
        for line, s, e in mine:
            assert any(sl == line and ss <= s and e <= se for sl, ss, se in steps), name


def test_step_never_replays_the_fp_model(monkeypatch):
    import repro.serve.hgnn_engine as engine_mod

    calls = []
    real = engine_mod.fp_buffer_traffic

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "fp_buffer_traffic", counted)
    g = synthetic_hetgraph("imdb", scale=0.05, feat_scale=0.02, seed=0)
    eng = HGNNEngine(g, target_type="movie", num_slots=2, backend=NABackend.BLOCK)
    for req in make_request_mix(0, [[("movie", "keyword", "movie")]], repeats=4):
        eng.submit(req)
    eng.run()
    assert eng.steps_run == 2 and calls == []
    m = eng.metrics()
    assert len(calls) == 1
    assert eng.registry.value("serve.fp_model_drift") == m["fp_model_drift"]
    eng.registry.snapshot()  # an export computes the gauges too
    assert len(calls) == 2
