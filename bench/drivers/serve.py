"""Serving driver: open-loop HGNN embedding requests through ``HGNNEngine``.

Traffic (``bench/traffic/<mix>.json``): requests arrive open loop at
``rate_per_s`` for the window.  With probability ``full_share`` a
request runs all of the target's metapaths, as HAN inference does, else
one of the proper non-empty subsets, uniformly; ``update_share`` of all
operations are ``update_features`` calls on a vertex type drawn in
proportion to its vertex count.  The requests, updates and inter-arrival
gaps (the exponential distribution's quantiles) are the same set for
every seed; ``--seed`` puts them in order, and draws the engine's
weights and the update payloads, made on the device during set-up.

Set-up builds the engine, then warms every shape the window uses: each
active-slot count with each metapath, and the fusion of each request
kind that the schedule holds.  The loop submits what is due, steps the
engine while it has work, and notes when each result is ready; a
request's latency runs from its due time to then.  Requests due in the
window that are not ready ``drain_s`` after it count as failed, with
infinite latency.

The check: every request due in the window is compared with
``bench/reference.py``, which recomputes its embedding from the target
features installed when each of its metapaths ran (a stale projection is
a wrong answer).  The number compared is the widest gap of one vertex's
embedding row, |served_v - reference_v| / |reference_v|, over the rows of
every such request (a row's norm floored at a thousandth of the largest).
"""
from __future__ import annotations

import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

import common
import graphgen
import reference
import work
from repro.core.fusion import NABackend
from repro.graphs import synthetic_hetgraph
from repro.serve.hgnn_engine import GraphRequest, HGNNEngine

POOL = 2  # payload versions kept per vertex type; consecutive updates alternate


def request_kinds(metapaths) -> list[tuple]:
    mps = [tuple(m) for m in metapaths]
    return [c for k in range(1, len(mps)) for c in itertools.combinations(mps, k)]


def schedule(traffic: dict, metapaths, counts: dict[str, int], seconds: float, seed: int):
    """[(due_s, "request", metapaths) | (due_s, "update", vtype)], sorted by due."""
    n_req = max(1, round(traffic["rate_per_s"] * seconds))
    share = traffic["update_share"]
    n_upd = round(n_req * share / (1.0 - share))
    full = [tuple(tuple(m) for m in metapaths)] * round(n_req * traffic["full_share"])
    kinds = request_kinds(metapaths)
    rest = n_req - len(full)
    reqs = full + [kinds[i % len(kinds)] for i in range(rest)]
    types = sorted(counts)
    total = sum(counts.values())
    exact = [n_upd * counts[t] / total for t in types]
    per = [int(x) for x in exact]
    for i in sorted(range(len(types)), key=lambda i: -(exact[i] - per[i]))[: n_upd - sum(per)]:
        per[i] += 1
    upds = [t for t, k in zip(types, per) for _ in range(k)]
    ops = [("request", r) for r in reqs] + [("update", t) for t in upds]
    n = len(ops)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    rng = np.random.default_rng(seed)
    ops = [ops[i] for i in rng.permutation(n)]
    gaps = gaps[rng.permutation(n)]
    due = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    return [(float(d), k, v) for d, (k, v) in zip(due, ops)]


def _payloads(key, shapes):
    return tuple(0.1 * jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32)
                 for i, s in enumerate(shapes))


_make_payloads = jax.jit(_payloads, static_argnames=("shapes",))

@jax.jit
def _gap(a, b):
    """Widest gap of a vertex's embedding row, relative to that row's norm
    in the reference (floored at a thousandth of the largest row's, for
    vertices that no metapath reaches)."""
    rn = jnp.linalg.norm(b, axis=1)
    d = jnp.linalg.norm(a.astype(jnp.float32) - b, axis=1)
    return jnp.max(d / jnp.maximum(rn, 1e-3 * jnp.max(rn)))


class Server:
    """The engine under test, its update payloads and the loop that drives it."""

    def __init__(self, ctx: common.Context, phases: common.Phases, seed: int):
        cfg, tr = ctx.config, ctx.traffic
        spec = cfg["graph"]
        self.ctx, self.spec = ctx, spec
        self.target = spec["target"]
        self.metapaths = [tuple(m) for m in spec["metapaths"]
                          if m[0] == spec["target"] and m[-1] == spec["target"]]
        backend = tr["backend"] + ("_interpret" if ctx.backend.endswith("_interpret") else "")
        with phases("graph build"):
            graph = synthetic_hetgraph(spec["dataset"], scale=ctx.scale,
                                       feat_scale=ctx.feat_scale, seed=cfg["assumed"]["graph_seed"])
        with phases("engine and weights"):
            self.eng = HGNNEngine(
                graph, target_type=self.target, hidden=cfg["hidden"], heads=cfg["heads"],
                att_dim=cfg["att_dim"], num_slots=tr["slots"], cache_bytes=tr["cache_bytes"],
                cache_block_rows=tr["cache_block_rows"], cache_policy=tr["cache_policy"],
                admission=tr["admission"], backend=NABackend(backend), block=cfg["block"],
                max_edges=cfg["max_edges"], seed=seed,
            )
            self.counts = dict(graph.vertex_counts)
            self.original = dict(self.eng.features)
            types = sorted(self.counts)
            shapes = tuple(tuple(self.original[t].shape) for t in types for _ in range(POOL))
            flat = _make_payloads(jax.random.fold_in(jax.random.key(seed), 0x5EED), shapes)
            self.pool = {t: flat[POOL * i: POOL * (i + 1)] for i, t in enumerate(types)}
            jax.block_until_ready((self.eng.params, flat))
        self.content = {t: -1 for t in types}  # -1: the graph's own features, else pool index
        self.n_updates = {t: 0 for t in types}
        self.step_content: dict[int, int] = {}  # engine step -> target content it read
        self.rid = 0

    def update(self, vtype: str) -> None:
        k = self.n_updates[vtype] % POOL
        self.n_updates[vtype] += 1
        self.eng.update_features(vtype, self.pool[vtype][k])
        self.content[vtype] = k

    def submit(self, metapaths) -> GraphRequest:
        req = GraphRequest(rid=self.rid, metapaths=list(metapaths))
        self.rid += 1
        self.eng.submit(req)
        return req

    def busy(self) -> bool:
        return bool(self.eng.queue) or any(r is not None for r in self.eng.slots)

    def step(self) -> None:
        self.step_content[self.eng.steps_run] = self.content[self.target]
        self.eng.step()

    def warm_up(self, kinds) -> None:
        """Every NA shape (active slots x metapath: g requests of all the
        metapaths, admitted together, run each metapath in g slots at
        once) and the fusion of each request kind in ``kinds``."""
        full = tuple(self.metapaths)
        reqs = []
        for g in range(1, self.eng.num_slots + 1):
            reqs += [self.submit(full) for _ in range(g)]
            while self.busy():
                self.step()
        for kind in sorted(set(kinds) - {full}):
            reqs.append(self.submit(kind))
        while self.busy():
            self.step()
        jax.block_until_ready([r.result for r in reqs])

    def loop(self, ops, seconds: float, drain_s: float, on_window_end=None):
        """Drive ``ops``; returns the record of the window."""
        eng = self.eng
        due_of: dict[int, float] = {}
        ready_at: dict[int, float] = {}
        late, step_s, pending, queued = [], [], [], []
        step_at: dict[int, float] = {}  # engine step -> its start, seconds into the window
        seen = len(eng.finished)
        i = 0
        window = jax.profiler.TraceAnnotation("bench.window")
        in_window = True
        t0 = time.perf_counter()
        window.__enter__()
        while True:
            now = time.perf_counter() - t0
            if in_window and now >= seconds:
                window.__exit__(None, None, None)
                in_window = False
                if on_window_end is not None:
                    on_window_end()
                now = time.perf_counter() - t0
            while i < len(ops) and ops[i][0] <= now:
                due, kind, val = ops[i]
                if kind == "request":
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        req = self.submit(val)
                    due_of[req.rid] = due
                else:
                    with jax.profiler.TraceAnnotation("bench.update_features"):
                        self.update(val)
                late.append(now - due)
                i += 1
            busy = self.busy()
            if busy:
                ts = time.perf_counter()
                step_at[eng.steps_run] = ts - t0
                queued.append(len(eng.queue))
                with jax.profiler.TraceAnnotation("bench.engine_step"):
                    self.step()
                step_s.append((ts - t0, time.perf_counter() - ts))
                pending += eng.finished[seen:]
                seen = len(eng.finished)
            still = []
            for r in pending:
                if r.result.is_ready():
                    ready_at[r.rid] = time.perf_counter() - t0
                else:
                    still.append(r)
            pending = still
            if not busy:
                if pending:
                    with jax.profiler.TraceAnnotation("bench.wait_result"):
                        pending[0].result.block_until_ready()
                elif i < len(ops):
                    with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                        time.sleep(max(0.0, min(ops[i][0] - (time.perf_counter() - t0), 0.05)))
                elif not in_window:
                    break
                else:
                    with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                        time.sleep(max(0.0, min(seconds - (time.perf_counter() - t0), 0.05)))
            if now > seconds + drain_s:
                break
        if in_window:
            window.__exit__(None, None, None)
            if on_window_end is not None:
                on_window_end()
        return {"t0": t0, "due": due_of, "ready": ready_at, "late": late, "steps": step_s,
                "step_at": step_at, "queued": queued,
                "requests": {r.rid: r for r in eng.finished if r.rid in due_of}}


class Reference:
    """``bench/reference.py`` over the benchmark's own graph, with each
    (metapath, target content) projected and aggregated once."""

    def __init__(self, ctx: common.Context, server: Server, seed: int, dtype=jnp.float32):
        cfg = ctx.config
        spec = cfg["graph"]
        self.cfg, self.server, self.seed, self.dtype = cfg, server, seed, dtype
        g = graphgen.hetgraph(spec, seed=cfg["assumed"]["graph_seed"], scale=ctx.scale,
                              feat_scale=ctx.feat_scale)
        self.x0 = jnp.asarray(g.features[spec["target"]])
        dims = {t: int(x.shape[1]) for t, x in g.features.items()}
        self.params = reference.init_engine(seed, dims, cfg["heads"], cfg["hidden"], cfg["att_dim"])
        self.edges = {mp: graphgen.metapath_edges(g, mp, max_edges=cfg["max_edges"],
                                                  seed=graphgen.serving_seed(mp))
                      for mp in server.metapaths}
        self._z: dict = {}

    def z(self, mp, content: int):
        key = (mp, content)
        if key not in self._z:
            t = self.server.target
            x = self.x0 if content < 0 else self.server.pool[t][content]
            a_src, a_dst = reference.init_metapath(self.seed, graphgen.serving_seed(mp),
                                                   self.cfg["heads"], self.cfg["hidden"])
            src, dst = self.edges[mp]
            p = self.params
            self._z[key] = reference.serve_metapath(
                x, p["w_fp"][t], p["b_fp"][t], a_src, a_dst, jnp.asarray(src), jnp.asarray(dst),
                p["w_g"], p["b_g"], p["q"], heads=self.cfg["heads"],
                slope=self.cfg["leaky_slope"], dtype=self.dtype)
        return self._z[key]

    def embedding(self, req: GraphRequest):
        parts = [self.z(tuple(mp), self.server.step_content[req.admitted_step + k])
                 for k, mp in enumerate(req.metapaths)]
        return reference.serve_fuse(tuple(z for z, _ in parts), tuple(w for _, w in parts))

    def beta(self, req: GraphRequest):
        ws = [self.z(tuple(mp), self.server.step_content[req.admitted_step + k])[1]
              for k, mp in enumerate(req.metapaths)]
        return jax.nn.softmax(jnp.stack(ws).astype(jnp.float32))


@jax.jit
def _gaps(a, b, beta_a, beta_b):
    """Candidate numbers for the check: the whole matrix's relative gap,
    the gap of its column means, and the widest gap of the semantic
    attention weights."""
    a = a.astype(jnp.float32)
    frob = jnp.linalg.norm(a - b) / jnp.linalg.norm(b)
    ma, mb = jnp.mean(a, axis=0), jnp.mean(b, axis=0)
    return frob, jnp.linalg.norm(ma - mb) / jnp.linalg.norm(mb), jnp.max(jnp.abs(beta_a - beta_b))


def candidate_gaps(reqs, ref: Reference, against: Reference | None = None) -> dict:
    """Worst of each candidate number over ``reqs`` (calibration only)."""
    worst = {"frob_gap": 0.0, "colmean_gap": 0.0, "beta_gap": 0.0}
    for r in reqs:
        b, beta_b = ref.embedding(r), ref.beta(r)
        a, beta_a = (r.result, r.beta) if against is None else (against.embedding(r), against.beta(r))
        got = _gaps(a, b, jnp.asarray(beta_a, jnp.float32), beta_b)
        for k, v in zip(worst, got):
            worst[k] = max(worst[k], float(v))
    return worst


def widest_gap(reqs, ref: Reference, against: Reference | None = None) -> float:
    """Widest normwise gap over ``reqs`` of the served embedding (or of
    ``against``'s) from ``ref``'s."""
    worst = 0.0
    for r in reqs:
        if r.finished_step != r.admitted_step + len(r.metapaths) - 1:
            return float("inf")  # not one metapath per step: no reference to place it
        served = r.result if against is None else against.embedding(r)
        gap = float(_gap(served, ref.embedding(r)))
        worst = max(worst, gap if gap == gap else float("inf"))  # NaN is no match
    return worst


def kinds_of(ops) -> list[tuple]:
    return [v for _, k, v in ops if k == "request"]


def run(ctx: common.Context, counter: common.CompileCounter) -> dict:
    phases = common.Phases(ctx)
    cfg, tr = ctx.config, ctx.traffic
    server = Server(ctx, phases, ctx.seed)
    ops = schedule(tr, server.metapaths, server.counts, ctx.seconds, ctx.seed)
    with phases("warm-up: SGB, unit tables, compile or cache load"):
        server.warm_up(kinds_of(ops))
    ctx.say(f"[setup] programs {counter.count('program')} (cache hits {counter.count('cache_hit')})")
    st0 = server.eng.cache.stats
    hits0, misses0 = st0.hits, st0.misses

    common.settle()
    if ctx.trace:
        common.start_trace(ctx.trace_dir)
    setup_s = time.perf_counter() - ctx.t_start
    rec = server.loop(ops, ctx.seconds, tr["drain_s"],
                      on_window_end=jax.profiler.stop_trace if ctx.trace else None)
    t0 = rec["t0"]
    in_window = counter.count("program", t0, t0 + ctx.seconds)
    st = server.eng.cache.stats
    hits, misses = st.hits - hits0, st.misses - misses0
    due, ready = rec["due"], rec["ready"]
    lat = [ready[r] - due[r] if r in ready else float("inf") for r in due]
    failed = sum(1 for r in due if r not in ready)
    late = rec["late"]
    ctx.say(f"[window] {len(due)} requests due, {len(ready)} ready, {failed} failed; "
            f"{len(rec['steps'])} engine steps, at most {max(rec['queued'], default=0)} requests "
            f"queued at a step's start; programs obtained in the window: {in_window}")
    ctx.say(f"[window] latency p50 {common.percentile(lat, 50) * 1e3:.3f} ms, "
            f"p95 {common.percentile(lat, 95) * 1e3:.3f} ms, max {max(lat) * 1e3:.3f} ms")
    ctx.say(f"[generator] late by p50 {common.median(late) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms over {len(late)} operations")
    peak = common.memory_peak(jax.devices()[:1])

    ref = Reference(ctx, server, ctx.seed)
    gap = widest_gap(rec["requests"].values(), ref)
    limit = ctx.cell["limits"]["emb_gap"]
    checks = [("emb_gap", gap, limit)]
    n, d_in = int(ref.x0.shape[0]), int(ref.x0.shape[1])
    reqs = rec["requests"]
    steps_in_window = [d for ts, d in rec["steps"] if ts < ctx.seconds]
    na_fwd, step_flops = work_in_window(cfg, ref, n, d_in, reqs.values(), rec["step_at"], ctx.seconds)
    return {
        "correct": gap <= limit and failed == 0,
        "attempted": len(due),
        "failed": failed,
        "checks": checks,
        "memory_peak_bytes": peak,
        "e2e": {"serve_p50_ms": common.percentile(lat, 50) * 1e3, "setup_s": setup_s},
        "window_s": ctx.seconds,
        "engine_step_ms": [d * 1e3 for d in steps_in_window],
        "engine_step_s": sum(steps_in_window),
        "fp_hit_rate": hits / max(hits + misses, 1),
        "step_flops": step_flops,
        "na_fwd_in_window": na_fwd,
    }


def work_in_window(cfg, ref, n, d_in, reqs, step_at, seconds):
    """Work of the engine steps that started in the window, where
    metapath k of a request ran at its admission step + k: the (FLOPs,
    bytes) of the NA forward, and the HAN-layer FLOPs, a request's
    projection counted with its first metapath."""
    f = b = model = 0.0
    for r in reqs:
        for k, mp in enumerate(r.metapaths):
            if step_at.get(r.admitted_step + k, float("inf")) < seconds:
                e = [len(ref.edges[tuple(mp)][0])]
                ff, bb = work.na_forward(e, n, cfg["heads"], cfg["hidden"])
                layer = work.han_forward_flops(e, n, d_in, cfg["heads"], cfg["hidden"],
                                               cfg["att_dim"], None)
                f, b = f + ff, b + bb
                model += layer["total"] - (layer["fp"] if k else 0.0)
    return (f, b), model


def calibrate(ctx: common.Context, seeds: list[int], control_seeds: list[int]):
    """Readings that the check's limit is set from, in one process: a
    window of ``ctx.seconds`` at the cell's load per seed, the served
    embeddings against the reference, and on ``control_seeds`` the
    control (the reference in bfloat16) against the reference."""
    for seed in seeds:
        phases = common.Phases(ctx)
        server = Server(ctx, phases, seed)
        ops = schedule(ctx.traffic, server.metapaths, server.counts, ctx.seconds, seed)
        server.warm_up(kinds_of(ops))
        rec = server.loop(ops, ctx.seconds, ctx.traffic["drain_s"])
        reqs = list(rec["requests"].values())
        ref = Reference(ctx, server, seed)
        yield {"seed": seed, "what": "program", "requests": len(reqs),
               "missing": len(rec["due"]) - len(rec["ready"]), "emb_gap": widest_gap(reqs, ref),
               **candidate_gaps(reqs, ref)}
        if seed in control_seeds:
            ctl = Reference(ctx, server, seed, dtype=jnp.bfloat16)
            yield {"seed": seed, "what": "control_bf16", "emb_gap": widest_gap(reqs, ref, ctl),
                   **candidate_gaps(reqs, ref, ctl)}
        del server, ref, reqs, rec


def sweep(ctx: common.Context, rates: list[float]):
    """Open-loop windows of ``ctx.seconds`` at each rate, a fresh engine
    each; yields what the knee is read from: completions, the backlog at
    the window's end, and latency percentiles."""
    for rate in rates:
        tr = dict(ctx.traffic, rate_per_s=rate, drain_s=5.0)  # a backlog is the reading
        server = Server(ctx, common.Phases(ctx), ctx.seed)
        ops = schedule(tr, server.metapaths, server.counts, ctx.seconds, ctx.seed)
        server.warm_up(kinds_of(ops))
        backlog = {}
        rec = server.loop(ops, ctx.seconds, tr["drain_s"],
                          on_window_end=lambda: backlog.update(
                              queue=len(server.eng.queue),
                              in_slots=sum(r is not None for r in server.eng.slots)))
        due, ready = rec["due"], rec["ready"]
        lat = sorted(ready[r] - due[r] if r in ready else float("inf") for r in due)
        done_in = sum(1 for t in ready.values() if t < ctx.seconds)
        steps = [d * 1e3 for ts, d in rec["steps"] if ts < ctx.seconds]
        yield {"rate_per_s": rate, "due": len(due), "done_in_window": done_in,
               "done_per_s": done_in / ctx.seconds, **backlog,
               "p50_ms": common.percentile(lat, 50) * 1e3, "p95_ms": common.percentile(lat, 95) * 1e3,
               "engine_step_ms_p50": common.median(steps), "engine_steps": len(steps),
               "engine_step_ms_p95": common.percentile(steps, 95),
               "engine_step_ms_max": max(steps, default=None),
               "queued_max": max(rec["queued"], default=0),
               "late_max_ms": max(rec["late"]) * 1e3}
        del server, rec
