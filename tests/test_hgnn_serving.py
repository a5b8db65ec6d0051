"""HGNN serving engine + cross-request FP cache: lifecycle, capacity,
coherence, admission-policy wins, and the reuse-model regression."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NABackend, fp_buffer_traffic, stages
from repro.graphs import synthetic_hetgraph
from repro.serve import FPCache, GraphRequest, HGNNEngine, make_request_mix

MDM = ("movie", "director", "movie")
MAM = ("movie", "actor", "movie")
MKM = ("movie", "keyword", "movie")
CLUSTERS = [
    [MDM, ("movie", "director", "movie", "director", "movie")],
    [MAM, ("movie", "actor", "movie", "actor", "movie")],
    [MKM],
]
OUT_BYTES = 2 * 4 * 4  # heads * hidden * fp32


@pytest.fixture(scope="module")
def graph():
    return synthetic_hetgraph("imdb", scale=0.05, feat_scale=0.02, seed=0)


def _engine(graph, **kw):
    kw.setdefault("target_type", "movie")
    kw.setdefault("hidden", 4)
    kw.setdefault("heads", 2)
    kw.setdefault("num_slots", 2)
    kw.setdefault("cache_block_rows", 64)
    kw.setdefault("backend", NABackend.BLOCK)
    kw.setdefault("block", 8)
    kw.setdefault("max_edges", 2_000)
    kw.setdefault("seed", 0)
    return HGNNEngine(graph, **kw)


# -- FPCache unit ----------------------------------------------------------


def _xw(rng, n, din=3, dout=8):
    x = jnp.asarray(rng.standard_normal((n, din)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((din, dout)).astype(np.float32))
    return x, w, jnp.zeros((dout,))


def test_fp_cache_capacity_bound_and_hits():
    rng = np.random.default_rng(0)
    x, w, b = _xw(rng, 16)
    blk_bytes = 4 * 8 * 4  # block_rows * dout * fp32
    cache = FPCache(4 * blk_bytes, block_rows=4)

    out = cache.project("a", x, w, b)
    assert cache.stats.misses == 4 and cache.stats.hits == 0
    assert cache.resident_bytes == 4 * blk_bytes <= cache.capacity_bytes
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(stages.feature_projection(x, w, b)),
        rtol=1e-6, atol=1e-6,
    )

    again = cache.project("a", x, w, b)
    assert cache.stats.hits == 4 and cache.stats.misses == 4
    assert np.array_equal(np.asarray(out), np.asarray(again))

    # uncached recomputation (capacity 0) is bit-identical to the cached path
    nocache = FPCache(0, block_rows=4)
    assert np.array_equal(np.asarray(nocache.project("a", x, w, b)), np.asarray(out))
    assert nocache.resident_bytes == 0 and nocache.num_blocks == 0

    # capacity smaller than the table: resident set stays bounded
    small = FPCache(2 * blk_bytes, block_rows=4)
    small.project("a", x, w, b)
    assert small.resident_bytes <= small.capacity_bytes
    assert small.num_blocks == 2


def test_fp_cache_version_invalidation():
    rng = np.random.default_rng(1)
    x, w, b = _xw(rng, 8)
    cache = FPCache(1 << 16, block_rows=4)
    old = cache.project("a", x, w, b)
    assert cache.version("a") == 0 and cache.num_blocks == 2

    cache.invalidate("a")
    assert cache.version("a") == 1
    assert cache.num_blocks == 0  # stale blocks dropped eagerly
    assert cache.stats.invalidations == 1

    x2 = x + 1.0
    new = cache.project("a", x2, w, b)
    assert cache.stats.hits == 0  # old-version keys can never be served
    np.testing.assert_allclose(
        np.asarray(new), np.asarray(stages.feature_projection(x2, w, b)), rtol=1e-6
    )
    assert not np.array_equal(np.asarray(new), np.asarray(old))


def test_fp_cache_similarity_eviction_prefers_demanded_types():
    rng = np.random.default_rng(2)
    xa, w, b = _xw(rng, 4)
    xb, _, _ = _xw(rng, 4)
    xc, _, _ = _xw(rng, 4)
    blk_bytes = 4 * 8 * 4

    # LRU baseline: oldest block ("a") is the victim
    lru = FPCache(2 * blk_bytes, block_rows=4, policy="lru")
    lru.project("a", xa, w, b)
    lru.project("b", xb, w, b)
    lru.project("c", xc, w, b)
    assert lru.resident_types() == {"b", "c"}

    # similarity-weighted: "b" has zero queue demand -> evicted despite
    # being more recently used than "a"
    sim = FPCache(2 * blk_bytes, block_rows=4, policy="similarity")
    sim.project("a", xa, w, b)
    sim.project("b", xb, w, b)
    sim.set_demand({"a": 10.0, "b": 0.0, "c": 1.0})
    sim.project("c", xc, w, b)
    assert sim.resident_types() == {"a", "c"}


# -- engine lifecycle ------------------------------------------------------


def test_engine_request_lifecycle_and_slot_reuse(graph):
    eng = _engine(graph, cache_bytes=1 << 20, admission="fifo")
    r0 = GraphRequest(rid=0, metapaths=[MDM, MAM])  # 2 steps of work
    r1 = GraphRequest(rid=1, metapaths=[MKM])
    r2 = GraphRequest(rid=2, metapaths=[MKM])
    for r in (r0, r1, r2):
        eng.submit(r)
        assert r.submitted_step == 0

    # step 0: two slots -> r0 and r1 admitted in FIFO order, r2 waits
    assert eng.step() == 2
    assert r0.admitted_step == 0 and r1.admitted_step == 0
    assert r2.admitted_step == -1
    assert r1.done and r1.finished_step == 0
    assert not r0.done  # one metapath of two executed

    # step 1: r2 reuses the slot r1 freed
    assert eng.step() == 2
    assert r2.admitted_step == 1 and r2.finished_step == 1
    assert r0.finished_step == 1

    assert eng.step() == 0  # drained
    assert not eng.queue and all(s is None for s in eng.slots)
    assert {r.rid for r in eng.finished} == {0, 1, 2}
    for r in (r0, r1, r2):
        assert 0 <= r.submitted_step <= r.admitted_step <= r.finished_step
        assert r.result.shape == (eng.n_target, eng.heads * eng.hidden)
        assert r.beta.shape == (len(r.metapaths),)
        np.testing.assert_allclose(float(jnp.sum(r.beta)), 1.0, rtol=1e-5)

    m = eng.metrics()
    assert m["requests_finished"] == 3 and m["requests_waiting"] == 0
    assert m["na_launches"] == 2  # one fused launch per non-empty step
    assert eng.traffic().total == m["reused_bytes"] + m["fetched_bytes"]


def test_request_timestamps_and_queue_wait(graph):
    eng = _engine(graph, cache_bytes=1 << 20, admission="fifo", num_slots=1)
    r0 = GraphRequest(rid=0, metapaths=[MDM, MAM])  # holds the slot 2 steps
    r1 = GraphRequest(rid=1, metapaths=[MKM])
    eng.submit(r0)
    eng.submit(r1)
    assert r0.admitted_at is None and r1.submitted_at >= r0.submitted_at
    eng.step()
    after_first = time.perf_counter()
    # a slot was free: r0 waited for no step, only for the first call
    assert r0.admitted_step == r0.submitted_step == 0
    assert r0.submitted_at <= r0.admitted_at <= after_first
    assert r1.admitted_at is None and r0.finished_at is None
    eng.run()
    # r1 waited in the queue until r0 finished and freed the slot
    assert r1.admitted_step == 2 and r1.admitted_at >= r0.finished_at
    for r in (r0, r1):
        assert r.submitted_at <= r.admitted_at <= r.finished_at


def test_engine_rejects_non_target_endpoints(graph):
    eng = _engine(graph, cache_bytes=0)
    with pytest.raises(AssertionError):
        eng.submit(GraphRequest(rid=0, metapaths=[("director", "movie", "director")]))


def test_cached_results_bitwise_identical_to_uncached(graph):
    reqs = lambda: make_request_mix(0, CLUSTERS, repeats=2)
    ref_eng = _engine(graph, cache_bytes=0, admission="fifo")
    for r in reqs():
        ref_eng.submit(r)
    ref = {r.rid: np.asarray(r.result) for r in ref_eng.run()}
    assert ref_eng.metrics()["cache_hit_rate"] == 0.0

    for admission in ("fifo", "similarity"):
        eng = _engine(graph, cache_bytes=1 << 20, admission=admission)
        for r in reqs():
            eng.submit(r)
        got = {r.rid: np.asarray(r.result) for r in eng.run()}
        assert got.keys() == ref.keys()
        for rid in ref:
            assert np.array_equal(got[rid], ref[rid]), (admission, rid)
    assert eng.metrics()["cache_hit_rate"] > 0.0  # the cache actually engaged


def test_similarity_admission_beats_fifo_hit_rate(graph):
    table = {t: n * OUT_BYTES for t, n in graph.vertex_counts.items()}
    cap = table["movie"] + max(table.values()) + 64 * OUT_BYTES  # adversarial

    metrics = {}
    for admission in ("fifo", "similarity"):
        eng = _engine(graph, cache_bytes=cap, admission=admission)
        for r in make_request_mix(0, CLUSTERS, repeats=3):
            eng.submit(r)
        eng.run()
        metrics[admission] = eng.metrics()
    fifo, sim = metrics["fifo"], metrics["similarity"]
    assert fifo["requests_finished"] == sim["requests_finished"] == 9
    assert sim["cache_hit_rate"] > fifo["cache_hit_rate"]  # strictly better
    assert sim["fp_rows_computed"] < fifo["fp_rows_computed"]
    assert sim["reused_bytes"] > fifo["reused_bytes"]


def test_update_features_coherence(graph):
    run_one = lambda eng: (eng.submit(GraphRequest(rid=0, metapaths=[MDM])), eng.run())[1][-1]

    eng = _engine(graph, cache_bytes=1 << 20)
    stale = np.asarray(run_one(eng).result)

    rng = np.random.default_rng(7)
    new_x = rng.standard_normal(
        (graph.num_vertices("movie"), graph.feature_dim("movie"))
    ).astype(np.float32)
    eng.update_features("movie", new_x)
    assert eng.cache.stats.invalidations == 1
    eng.finished.clear()
    fresh = np.asarray(run_one(eng).result)
    assert not np.array_equal(fresh, stale)  # stale projections not served

    # matches an engine that never saw the old features (bitwise)
    eng2 = _engine(graph, cache_bytes=1 << 20)
    eng2.update_features("movie", new_x)
    assert np.array_equal(np.asarray(run_one(eng2).result), fresh)


# -- reuse model regression ------------------------------------------------


class _SG:
    def __init__(self, *path_types):
        self.path_types = path_types


def test_fp_buffer_traffic_partial_block_regression():
    """Pins the partial-residency byte counts: a table larger than the
    whole buffer keeps a resident prefix that is reused on re-access,
    instead of charging a full miss (matches serve/fp_cache.py)."""
    counts = {"a": 10, "b": 20, "c": 30}
    bpv = {"a": 4, "b": 4, "c": 4}  # tables: 40 / 80 / 120 bytes
    sgs = [_SG("a", "b"), _SG("b", "c"), _SG("c", "a")]

    # buffer (100) < table c (120): c keeps a 100-byte resident prefix,
    # re-accessed in g2 -> 100 reused + only 20 re-fetched
    t = fp_buffer_traffic([0, 1, 2], sgs, counts, bytes_per_vertex=bpv, fpbuf_bytes=100)
    assert (t.reused_bytes, t.fetched_bytes) == (180, 300)
    assert t.total == 480  # = bytes touched, independent of buffer size

    # everything fits: only first touches fetch
    t = fp_buffer_traffic([0, 1, 2], sgs, counts, bytes_per_vertex=bpv, fpbuf_bytes=1000)
    assert (t.reused_bytes, t.fetched_bytes) == (240, 240)

    # zero-capacity buffer: every access is a full fetch
    t = fp_buffer_traffic([0, 1, 2], sgs, counts, bytes_per_vertex=bpv, fpbuf_bytes=0)
    assert (t.reused_bytes, t.fetched_bytes) == (0, 480)
