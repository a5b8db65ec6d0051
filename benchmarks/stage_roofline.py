"""Table 3 / Fig. 3 — per-stage arithmetic intensity and execution bound.

The paper profiles HAN-on-DBLP CUDA kernels: the FP sgemm has AI
26.8 FLOP/B (compute-bound, above the T4 ridge), the NA SpMMCsr has AI
0.49 FLOP/B (memory-bound).  We reproduce the *classification* for the
TPU target by compiling each stage in isolation and reading
``cost_analysis`` (flops, bytes accessed): AI = flops/bytes, compared
with the v5e ridge point 197e12/819e9 ≈ 240 FLOP/B (bf16) or the paper's
fp32-style ridge using fp32 ops.  The NA stage lands orders of magnitude
below the FP stage — the paper's core observation, and the reason its
stage fusion pairs them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import NABackend, batch_semantic_graph, stages
from repro.core.fusion import FusedFPInputs, neighbor_aggregate_multi
from repro.graphs import build_semantic_graph, synthetic_hetgraph, to_padded_edges

from .common import timeit

RIDGE_V5E = 197e12 / 819e9  # ≈ 240 FLOP/byte (bf16 MXU)
RIDGE_T4 = 8.1e12 / 300e9    # ≈ 27 FLOP/byte (the paper's Fig. 3 ridge)


def _ai(fn, *args):
    """(flops, bytes, AI) from cost_analysis; bytes/AI are None when the
    backend omits "bytes accessed" — a fabricated default would silently
    misclassify the bound."""
    c = jax.jit(fn).lower(*args).compile()
    cost = c.cost_analysis()
    fl = float(cost.get("flops", 0.0))
    by = cost.get("bytes accessed")
    if by is None:
        return fl, None, None
    by = float(by)
    return fl, by, fl / max(by, 1.0)


def _derived(fl, ai):
    if ai is None:
        return f"AI=n/a (backend omitted bytes accessed) flops={fl:.3g}"
    return (
        f"AI={ai:.1f}FLOP/B T4bound={'compute' if ai > RIDGE_T4 else 'memory'} "
        f"v5ebound={'compute' if ai > RIDGE_V5E else 'memory'} flops={fl:.3g}"
    )


def run(report):
    g = synthetic_hetgraph("dblp", scale=0.25, feat_scale=0.5, seed=0)
    sg = build_semantic_graph(g, ("author", "paper", "author"), max_edges=300_000)
    pe = to_padded_edges(sg)
    rng = np.random.default_rng(0)
    d_in = g.feature_dim("author")
    H, Dh = 8, 64
    x = jnp.asarray(rng.standard_normal((sg.num_src, d_in)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((d_in, H * Dh)).astype(np.float32))
    b = jnp.zeros((H * Dh,))
    a_s = jnp.asarray(rng.standard_normal((H, Dh)).astype(np.float32))
    a_d = jnp.asarray(rng.standard_normal((H, Dh)).astype(np.float32))
    h = jnp.asarray(rng.standard_normal((sg.num_src, H, Dh)).astype(np.float32))
    th_s = jnp.asarray(rng.standard_normal((sg.num_src, H)).astype(np.float32))
    th_d = jnp.asarray(rng.standard_normal((sg.num_dst, H)).astype(np.float32))
    src, dst, valid = jnp.asarray(pe.src), jnp.asarray(pe.dst), jnp.asarray(pe.valid)

    # FP stage (dense GEMM — the paper's sgemm)
    fp_fn = lambda x_: stages.feature_projection(x_, w, b)
    fl, by, ai = _ai(fp_fn, x)
    t = timeit(jax.jit(fp_fn), x, iters=3)
    report("stage_roofline/FP", t, _derived(fl, ai))
    ai_fp = ai

    # NA stage (segment softmax aggregation — the paper's SpMMCsr)
    na_fn = lambda t1, t2, h_: stages.segment_softmax_aggregate(
        src, dst, valid, t1, t2, h_, sg.num_dst
    )
    fl, by, ai = _ai(na_fn, th_s, th_d, h)
    t = timeit(jax.jit(na_fn), th_s, th_d, h, iters=3)
    report("stage_roofline/NA", t, _derived(fl, ai))
    ai_na = ai

    # SF stage (semantic attention: gemm + elementwise + reduce)
    z = jnp.asarray(rng.standard_normal((3, sg.num_dst, H * Dh)).astype(np.float32))
    w_g = jnp.asarray(rng.standard_normal((H * Dh, 128)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((128,)).astype(np.float32))

    def sf(z_):
        valid_v = jnp.ones((sg.num_dst,), bool)
        w_p = jnp.stack([
            stages.local_semantic_fusion(z_[p], w_g, jnp.zeros((128,)), q, valid_v)
            for p in range(3)
        ])
        fused, _ = stages.global_semantic_fusion(w_p, z_)
        return fused

    fl, by, ai = _ai(sf, z)
    t = timeit(jax.jit(sf), z, iters=3)
    report("stage_roofline/SF", t, _derived(fl, ai))
    # the paper's headline: FP's AI is orders of magnitude above NA's
    if ai_fp is None or ai_na is None:
        report("stage_roofline/ratio", 0.0,
               "AI_FP/AI_NA=n/a (backend omitted bytes accessed)")
    else:
        report("stage_roofline/ratio", 0.0,
               f"AI_FP/AI_NA={ai_fp/max(ai_na,1e-9):.1f}x (paper: 26.8/0.49=55x)")

    # -- measured FP/NA overlap of the stage-fusion megakernel ------------
    # The analytical rows above CLASSIFY the bound; this measures how much
    # of the cheaper stage the fused launch actually hides:
    #   overlap = (t_FP + t_NA - t_fused) / min(t_FP, t_NA)
    # 1.0 = the cheaper stage fully hidden behind the other; <=0 = fusion
    # added overhead instead (expected on the CPU interpreter, which runs
    # the pipeline stages serially — the TPU path is where Alg. 2's
    # double-buffered overlap lives).
    sg_f = build_semantic_graph(g, ("author", "paper", "author"),
                                max_edges=6_000, seed=0)
    bb = batch_semantic_graph(sg_f, block=16)
    n_pad = max(((bb.num_src + 15) // 16) * 16, bb.num_dst_pad)
    din_f, hf, dhf = 64, 2, 8
    xf = jnp.asarray(rng.standard_normal((n_pad, din_f)).astype(np.float32))
    wf = jnp.asarray((rng.standard_normal((din_f, hf * dhf)) / 8).astype(np.float32))
    bf = jnp.zeros((hf * dhf,))
    asf = jnp.asarray(rng.standard_normal((1, hf, dhf)).astype(np.float32))
    adf = jnp.asarray(rng.standard_normal((1, hf, dhf)).astype(np.float32))

    def fp_stage(x_):
        hh = (x_ @ wf + bf).reshape(n_pad, hf, dhf)
        return hh, jnp.einsum("nhd,ghd->gnh", hh, asf), jnp.einsum("nhd,ghd->gnh", hh, adf)

    def na_stage(hh, ts, td):
        return neighbor_aggregate_multi(
            [bb], ts, td, hh, backend=NABackend.MULTIGRAPH_INTERPRET)

    def fused_stage(x_):
        fp = FusedFPInputs.shared(x_, wf, bf, asf, adf)
        return neighbor_aggregate_multi(
            [bb], None, None, None, backend=NABackend.FUSED_FP_INTERPRET, fp=fp)

    hh, ts, td = jax.jit(fp_stage)(x := xf)
    t_fp = timeit(jax.jit(fp_stage), x, warmup=1, iters=2)
    t_na = timeit(jax.jit(na_stage), hh, ts, td, warmup=1, iters=2)
    t_fu = timeit(jax.jit(fused_stage), x, warmup=1, iters=2)
    overlap = (t_fp + t_na - t_fu) / max(min(t_fp, t_na), 1e-9)
    report("stage_roofline/fused_overlap", t_fu,
           f"measured_overlap_frac={overlap:.2f} fp_us={t_fp:.0f} "
           f"na_us={t_na:.0f} fused_us={t_fu:.0f} "
           f"(interpret-mode: serial pipeline, not a TPU projection)")
