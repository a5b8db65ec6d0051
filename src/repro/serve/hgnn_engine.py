"""HGNN serving engine: stepped graph-request execution over a resident
HetGraph with a cross-request FP cache and similarity-aware admission.

This is the paper's inter-semantic-graph data reusability (§4.3) promoted
to the serving tier.  Concurrent requests — vertex-type-tagged subgraph
queries, each a set of metapaths whose endpoints are the resident target
type — occupy a fixed-slot batch.  Each engine step executes ONE semantic
graph per occupied slot:

1. **FP** — the projected tables of every vertex type on the step's
   metapaths are materialized through the shared :class:`FPCache`
   (``serve/fp_cache.py``): blocks left behind by previous requests (or
   by co-batched slots this step) are reused, the rest computed.  This is
   ``core/reuse.py:fp_buffer_traffic``'s working-set accounting, measured
   instead of modeled.
2. **NA** — attention coefficients from the target-type table, then ONE
   fused multigraph launch for all slots' semantic graphs over the
   step's one-lane plan (``multilane.build_multilane_plan`` and
   ``multilane_na``, ``backend=MULTIGRAPH`` on TPU /
   ``MULTIGRAPH_INTERPRET`` on CPU; SEGMENT and BLOCK run a per-graph
   loop with identical semantics, ``fusion.neighbor_aggregate_multi``).
3. **LSF/GSF** — per-graph semantic importances accumulate on the slot;
   when a request's last metapath completes, global semantic fusion
   produces its embedding and the slot is freed for the queue.

Admission is similarity-aware by default: the queue is ordered by the
shortest Hamilton path over ``core/scheduling.py:similarity_matrix``
computed on the *request* mix (requests expose ``path_types`` exactly
like semantic graphs), anchored at the end that overlaps the cache's
resident types most — so co-batched and consecutive requests share FP
blocks.  ``admission="fifo"`` is the ablation baseline.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from collections import Counter
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import stages
from ..core.fusion import (
    FusedFPInputs,
    NABackend,
    SemanticGraphBatch,
    _pad_rows,
    batch_semantic_graph,
    neighbor_aggregate_multi,
)
from ..core.multilane import MultiLanePlan, build_multilane_plan, multilane_na
from ..core.reuse import FPTraffic, fp_buffer_traffic
from ..core.scheduling import shortest_hamilton_path, similarity_matrix
from ..graphs.hetgraph import HetGraph
from ..graphs.sgb import build_semantic_graph
from ..models.hgnn.common import glorot
from ..obs.metrics import MetricsRegistry
from ..obs.trace import trace_span
from .fp_cache import FPCache


@dataclasses.dataclass
class GraphRequest:
    """A vertex-type-tagged subgraph query: run the given metapaths (all
    endpoints = the engine's target type) and return the fused embedding.

    ``*_step`` are engine step numbers; ``*_at`` are ``time.perf_counter()``
    readings taken at ``submit``, at admission into a slot and when the
    step that ran its last metapath had dispatched it (the result may
    still be in flight on the device).  ``admitted_at - submitted_at`` is
    the request's wait in the admission queue.
    """

    rid: int
    metapaths: list[tuple[str, ...]]
    submitted_step: int = -1
    admitted_step: int = -1
    finished_step: int = -1
    submitted_at: float | None = None
    admitted_at: float | None = None
    finished_at: float | None = None
    result: jnp.ndarray | None = None   # [N_target, H*Dh] on finish
    beta: jnp.ndarray | None = None     # [G] semantic attention on finish
    _progress: int = 0
    _z: list = dataclasses.field(default_factory=list, repr=False)
    _w: list = dataclasses.field(default_factory=list, repr=False)

    @property
    def path_types(self) -> tuple[str, ...]:
        """Stable-unique union of vertex types across the metapaths — the
        request's FP working set (what similarity admission scores)."""
        seen: dict[str, None] = {}
        for mp in self.metapaths:
            for t in mp:
                seen.setdefault(t)
        return tuple(seen)

    @property
    def done(self) -> bool:
        return self._progress >= len(self.metapaths)


@functools.partial(jax.jit, static_argnames=("backend", "n"))
def _plan_na(plan: MultiLanePlan, th_s, th_d, hh, fp: FusedFPInputs | None, *,
             backend: NABackend, n: int) -> jnp.ndarray:
    """A step's kernel NA over its one-lane plan as one program: the
    operands padded to the plan's rows (the shared src/dst space), the
    launch and the scatter by unit.  Returns [G_active, n, H, Dh]."""
    n_pad = plan.n_dst_blocks * plan.block
    if fp is not None:
        fp = dataclasses.replace(fp, x=_pad_rows(fp.x, n_pad))
    else:
        th_s, th_d = (_pad_rows(t.swapaxes(0, 1), n_pad).swapaxes(0, 1) for t in (th_s, th_d))
        hh = _pad_rows(hh, n_pad)
    return multilane_na(plan, th_s, th_d, hh, backend=backend, fp=fp)[:, :n]


def _stable_seed(name: str) -> int:
    return int.from_bytes(hashlib.blake2b(name.encode(), digest_size=4).digest(), "big")


class HGNNEngine:
    """Fixed-slot stepped HGNN inference over a resident HetGraph."""

    def __init__(
        self,
        graph: HetGraph,
        *,
        target_type: str,
        hidden: int = 8,
        heads: int = 2,
        att_dim: int = 16,
        num_slots: int = 2,
        cache_bytes: int = 1 << 20,
        cache_block_rows: int = 128,
        cache_policy: str = "lru",
        admission: str = "similarity",
        backend: NABackend = NABackend.MULTIGRAPH,
        block: int = 16,
        max_edges: int | None = 20_000,
        seed: int = 0,
        registry: MetricsRegistry | None = None,
    ):
        assert admission in ("similarity", "fifo"), admission
        assert target_type in graph.vertex_counts, target_type
        self.graph = graph
        self.target_type = target_type
        self.hidden, self.heads, self.att_dim = hidden, heads, att_dim
        self.num_slots = num_slots
        self.admission = admission
        self.backend = backend
        self.block = block
        self.max_edges = max_edges
        self.n_target = graph.num_vertices(target_type)

        self.features = {t: jnp.asarray(x) for t, x in graph.features.items()}
        self.cache = FPCache(cache_bytes, block_rows=cache_block_rows, policy=cache_policy)
        self.params = self._init_params(jax.random.key(seed))
        self._mp_key = jax.random.key(seed + 1)
        self._mp_params: dict[tuple[str, ...], tuple[jnp.ndarray, jnp.ndarray]] = {}
        self._batches: dict[tuple[str, ...], SemanticGraphBatch] = {}

        self.queue: list[GraphRequest] = []
        self.slots: list[GraphRequest | None] = [None] * num_slots
        self.finished: list[GraphRequest] = []
        self.steps_run = 0
        self.na_launches = 0
        self.fp_rows_naive = 0  # rows a recompute-per-request FP stage would project
        self.fused_steps = 0           # steps served by the FP+NA megakernel
        self.fused_cache_bypasses = 0  # fused steps downgraded: table already cached

        # Observability (DESIGN.md §12).  Each engine owns a private
        # registry by default so two engines in one process (e.g. the
        # --compare ablation) never mix series; pass a shared registry to
        # aggregate.  Counters tick where their events happen; the gauges
        # are computed by ``metrics()``, which the registry also runs when
        # it is exported, never inside ``step()``.  ``_executed`` records,
        # per step, the stable-unique tuple of vertex types projected
        # through the cache — the input the analytical FP-traffic model
        # replays in ``fp_model_drift``.
        self.registry = registry if registry is not None else MetricsRegistry()
        self.registry.add_collector(self.metrics)
        self._executed: list[tuple[str, ...]] = []
        for k in sorted(self._COUNTER_KEYS):  # series exist from step zero
            self.registry.counter(f"serve.{k}")

    # -- parameters ---------------------------------------------------------

    def _init_params(self, rng: jax.Array) -> dict:
        keys = jax.random.split(rng, 3 + len(self.graph.vertex_counts))
        out_dim = self.heads * self.hidden
        w_fp = {}
        for i, t in enumerate(sorted(self.graph.vertex_counts)):
            w_fp[t] = glorot(keys[3 + i], (self.graph.feature_dim(t), out_dim))
        return {
            "w_fp": w_fp,
            "b_fp": {t: jnp.zeros((out_dim,)) for t in self.graph.vertex_counts},
            "w_g": glorot(keys[0], (out_dim, self.att_dim)),
            "b_g": jnp.zeros((self.att_dim,)),
            "q": glorot(keys[1], (self.att_dim, 1))[:, 0],
        }

    def _metapath_params(self, mp: tuple[str, ...]) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Per-metapath GAT vectors, deterministic in the metapath name so
        identical metapaths share parameters across requests and engines."""
        if mp not in self._mp_params:
            k = jax.random.fold_in(self._mp_key, _stable_seed("/".join(mp)))
            k1, k2 = jax.random.split(k)
            self._mp_params[mp] = (
                glorot(k1, (self.heads, self.hidden)),
                glorot(k2, (self.heads, self.hidden)),
            )
        return self._mp_params[mp]

    def _batch(self, mp: tuple[str, ...]) -> SemanticGraphBatch:
        """Device-resident semantic graph for a metapath (host-built once,
        memoized — SGB is preprocessing, as in the paper)."""
        if mp not in self._batches:
            sg = build_semantic_graph(
                self.graph, mp, max_edges=self.max_edges, seed=_stable_seed("/".join(mp))
            )
            self._batches[mp] = batch_semantic_graph(sg, block=self.block)
        return self._batches[mp]

    # -- request lifecycle --------------------------------------------------

    def submit(self, req: GraphRequest) -> None:
        assert req.metapaths, "empty request"
        for mp in req.metapaths:
            assert mp[0] == self.target_type and mp[-1] == self.target_type, (
                f"metapath {mp} endpoints must be the resident target type "
                f"{self.target_type!r} (shared dst space for the fused launch)"
            )
            for t in mp:
                assert t in self.graph.vertex_counts, t
        req.submitted_step = self.steps_run
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def _admission_order(self) -> list[int]:
        n = len(self.queue)
        if self.admission == "fifo" or n <= 1:
            return list(range(n))
        w = similarity_matrix(self.queue, self.graph.vertex_counts)
        if n <= 12:
            order, _ = shortest_hamilton_path(w)
        else:
            # greedy nearest-neighbor chain (Held-Karp is 2^n)
            order = [0]
            rest = set(range(1, n))
            while rest:
                last = order[-1]
                order.append(min(rest, key=lambda j: w[last, j]))
                rest.remove(order[-1])
        # anchor the chain at the end overlapping the resident cache most
        resident = self.cache.resident_types()

        def overlap(i: int) -> int:
            return sum(
                self.graph.vertex_counts[t]
                for t in set(self.queue[i].path_types) & resident
            )

        if overlap(order[-1]) > overlap(order[0]):
            order.reverse()
        return order

    def _admit(self) -> None:
        if self.queue:
            order = self._admission_order()
            self.queue = [self.queue[i] for i in order]
            for s in range(self.num_slots):
                if self.slots[s] is None and self.queue:
                    req = self.queue.pop(0)
                    req.admitted_step = self.steps_run
                    req.admitted_at = time.perf_counter()
                    self.slots[s] = req
        # refresh eviction demand: FP types still wanted by waiting +
        # in-flight work (similarity-weighted policy only reads this)
        demand: Counter[str] = Counter()
        for req in self.queue:
            demand.update(req.path_types)
        for req in self.slots:
            if req is not None:
                for mp in req.metapaths[req._progress :]:
                    demand.update(set(mp))
        self.cache.set_demand(demand)

    # -- execution ----------------------------------------------------------

    def _fp_tables(
        self, active: list[tuple[int, GraphRequest]], skip: set[str] = frozenset()
    ) -> dict[str, jnp.ndarray]:
        """Projected tables for the step's metapath types via the cache.
        ``skip`` types still count toward the naive-FP baseline but are
        neither projected nor admitted — the fused path projects the
        target type inside the NA launch instead."""
        tables: dict[str, jnp.ndarray] = {}
        with trace_span("serve.fp"):
            for _, req in active:
                mp = req.metapaths[req._progress]
                for t in dict.fromkeys(mp):
                    self.fp_rows_naive += self.graph.num_vertices(t)
                    if t not in tables and t not in skip:
                        tables[t] = self.cache.project(
                            t,
                            self.features[t],
                            self.params["w_fp"][t],
                            self.params["b_fp"][t],
                        )
        self._executed.append(tuple(tables))
        return tables

    def step(self) -> int:
        """One engine step: admit, then execute one semantic graph per
        occupied slot (single fused NA launch).  Returns #active slots.

        Host spans (``obs.trace``; recorded only under a profiler
        session): ``serve.step`` around the whole call, with
        ``serve.admit``, ``serve.fp``, ``serve.theta``,
        ``serve.unit_tables``, ``serve.na`` and ``serve.fuse`` inside it.
        """
        with trace_span("serve.step", step=self.steps_run) as span:
            with trace_span("serve.admit"):
                self._admit()
            active = [(s, r) for s, r in enumerate(self.slots) if r is not None]
            if not active:
                return 0
            span.set_metadata(
                slots=len(active), rids="/".join(str(r.rid) for _, r in active)
            )
            t0 = time.perf_counter()
            self._step_body(active)
            self.registry.histogram("serve.step_ms").observe(
                (time.perf_counter() - t0) * 1e3
            )
        return len(active)

    def _plan(self, batches: list[SemanticGraphBatch]) -> MultiLanePlan:
        """The step's one-lane plan: the kernels' work-unit tables."""
        with trace_span("serve.unit_tables"):
            return build_multilane_plan(batches, 1)

    def _step_body(self, active: list[tuple[int, GraphRequest]]) -> None:
        # Bound-aware dispatch for the fused-FP backend: if the cache
        # already holds the target type's whole projected table, FP is a
        # sunk cost — take the projected (multigraph) path and serve the
        # hit.  On a miss, the megakernel projects raw features on-chip
        # and h' never round-trips through HBM (nothing is admitted).
        backend = self.backend
        fused = backend in (NABackend.FUSED_FP, NABackend.FUSED_FP_INTERPRET)
        if fused and self.cache.table_coverage(self.target_type, self.n_target) >= 1.0:
            backend = NABackend(backend.value.replace("fused_fp", "multigraph"))
            fused = False
            self.fused_cache_bypasses += 1
            self.registry.counter("serve.fused_cache_bypasses").inc()

        n = self.n_target
        if fused:
            self._fp_tables(active, skip={self.target_type})
            batches, a_s, a_d = [], [], []
            for _, req in active:
                mp = req.metapaths[req._progress]
                a_src, a_dst = self._metapath_params(mp)
                batches.append(self._batch(mp))
                a_s.append(a_src)
                a_d.append(a_dst)
            fp = FusedFPInputs.shared(
                self.features[self.target_type],
                self.params["w_fp"][self.target_type],
                self.params["b_fp"][self.target_type],
                jnp.stack(a_s),
                jnp.stack(a_d),
            )
            plan = self._plan(batches)
            with trace_span("serve.na"):
                z_all = _plan_na(plan, None, None, None, fp, backend=backend, n=n)
            self.fused_steps += 1
            self.registry.counter("serve.fused_steps").inc()
        else:
            tables = self._fp_tables(active)
            hh = tables[self.target_type].reshape(n, self.heads, self.hidden)

            batches, th_s, th_d = [], [], []
            with trace_span("serve.theta"):
                for _, req in active:
                    mp = req.metapaths[req._progress]
                    a_src, a_dst = self._metapath_params(mp)
                    ts, td = stages.attention_coefficients(hh, a_src, a_dst)
                    batches.append(self._batch(mp))
                    th_s.append(ts)
                    th_d.append(td)
            if backend in (NABackend.SEGMENT, NABackend.BLOCK):
                with trace_span("serve.na"):
                    z_all = neighbor_aggregate_multi(
                        batches, jnp.stack(th_s), jnp.stack(th_d), hh, backend=backend
                    )  # [G_active, N, H, Dh]
            else:
                plan = self._plan(batches)
                with trace_span("serve.na"):
                    z_all = _plan_na(
                        plan, jnp.stack(th_s), jnp.stack(th_d), hh, None, backend=backend, n=n
                    )  # [G_active, N, H, Dh]
        self.na_launches += 1
        self.registry.counter("serve.na_launches").inc()

        valid = jnp.ones((self.n_target,), bool)
        with trace_span("serve.fuse"):
            for i, (s, req) in enumerate(active):
                z = jax.nn.elu(z_all[i].reshape(self.n_target, -1))
                w_p = stages.local_semantic_fusion(
                    z, self.params["w_g"], self.params["b_g"], self.params["q"], valid
                )
                req._z.append(z)
                req._w.append(w_p)
                req._progress += 1
                if req.done:
                    req.result, req.beta = stages.global_semantic_fusion(
                        jnp.stack(req._w), jnp.stack(req._z)
                    )
                    req._z, req._w = [], []
                    req.finished_step = self.steps_run
                    req.finished_at = time.perf_counter()
                    self.finished.append(req)
                    self.slots[s] = None
                    self.registry.counter("serve.requests_finished").inc()
        self.steps_run += 1
        self.registry.counter("serve.steps").inc()

    def run(self, max_steps: int = 10_000) -> list[GraphRequest]:
        steps = 0
        while (self.queue or any(r is not None for r in self.slots)) and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # -- coherence ----------------------------------------------------------

    def update_features(self, vtype: str, x: np.ndarray) -> None:
        """Install new raw features for ``vtype``.  Coherence rule
        (DESIGN.md §9): the cache version for the type is bumped and its
        blocks dropped, so no request ever reads a stale projection."""
        assert x.shape[0] == self.graph.num_vertices(vtype), vtype
        assert x.shape[1] == self.graph.feature_dim(vtype), vtype
        self.features[vtype] = jnp.asarray(x)
        self.cache.invalidate(vtype)

    # -- metrics ------------------------------------------------------------

    def traffic(self) -> FPTraffic:
        """Measured FP traffic in ``core/reuse.py``'s own accounting type."""
        return self.cache.stats.traffic()

    def fp_model_drift(self) -> dict:
        """Predicted-vs-measured FP traffic: replay the executed per-step
        type sets through ``core/reuse.py:fp_buffer_traffic`` (LRU buffer
        = this cache's capacity) and compare fetched bytes against what
        the block-granular cache actually fetched.  ``drift`` is
        measured/modeled fetched bytes — 1.0 means the paper's analytical
        FP-Buf model predicts the live traffic exactly; block-granular
        partial hits and similarity eviction push it below 1.0."""
        out_bytes = self.heads * self.hidden * 4  # f32 projected row

        class _Step:
            def __init__(self, pt):
                self.path_types = pt

        sgs = [_Step(pt) for pt in self._executed]
        model = fp_buffer_traffic(
            list(range(len(sgs))),
            sgs,
            self.graph.vertex_counts,
            bytes_per_vertex={t: out_bytes for t in self.graph.vertex_counts},
            fpbuf_bytes=self.cache.capacity_bytes,
        )
        measured = self.traffic()
        return dict(
            fp_model_fetched_bytes=model.fetched_bytes,
            fp_model_reused_bytes=model.reused_bytes,
            fp_measured_fetched_bytes=measured.fetched_bytes,
            fp_model_drift=measured.fetched_bytes / max(model.fetched_bytes, 1),
        )

    # counters maintained monotonically at event sites in step(); every
    # other metrics() key is mirrored into the registry as a gauge.
    _COUNTER_KEYS = frozenset(
        ("steps", "na_launches", "requests_finished", "fused_steps",
         "fused_cache_bypasses")
    )

    def metrics(self) -> dict:
        """Every engine number, the FP-model drift replay included; sets
        the registry's gauges to the non-counter ones."""
        st = self.cache.stats
        out = dict(
            steps=self.steps_run,
            na_launches=self.na_launches,
            requests_finished=len(self.finished),
            requests_waiting=len(self.queue),
            cache_hits=st.hits,
            cache_misses=st.misses,
            cache_hit_rate=st.hit_rate,
            reused_bytes=st.reused_bytes,
            fetched_bytes=st.fetched_bytes,
            reuse_fraction=st.reuse_fraction,
            evicted_bytes=st.evicted_bytes,
            fp_rows_computed=st.rows_computed,
            fp_rows_reused=st.rows_reused,
            fp_rows_naive=self.fp_rows_naive,
            fp_compute_reduction=self.fp_rows_naive / max(st.rows_computed, 1),
            fused_steps=self.fused_steps,
            fused_cache_bypasses=self.fused_cache_bypasses,
            cache_resident_bytes=self.cache.resident_bytes,
            cache_capacity_bytes=self.cache.capacity_bytes,
            **self.fp_model_drift(),
        )
        for k, v in out.items():
            if k not in self._COUNTER_KEYS:
                self.registry.gauge(f"serve.{k}").set(float(v))
        return out


def make_request_mix(
    rid_start: int,
    clusters: Sequence[Sequence[tuple[str, ...]]],
    repeats: int,
    *,
    interleave: bool = True,
) -> list[GraphRequest]:
    """Request mix builder for the launcher and the tests: ``repeats`` requests
    per metapath cluster, interleaved round-robin (the adversarial arrival
    order for FIFO admission) or grouped."""
    reqs: list[GraphRequest] = []
    rid = rid_start
    if interleave:
        for _ in range(repeats):
            for cl in clusters:
                reqs.append(GraphRequest(rid=rid, metapaths=[tuple(m) for m in cl]))
                rid += 1
    else:
        for cl in clusters:
            for _ in range(repeats):
                reqs.append(GraphRequest(rid=rid, metapaths=[tuple(m) for m in cl]))
                rid += 1
    return reqs
