"""Kernel-level microbenchmarks (CPU): fused NA backends and attention.

interpret-mode Pallas timings are NOT TPU projections — they validate the
datapath; the roofline story for TPU lives in §Roofline.  What this bench
demonstrates on CPU is the *algorithmic* win of the paper's fused
online-softmax NA: the staged segment path materializes per-edge
logits/αs (3 passes over edges), the fused block path streams them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import NABackend, batch_semantic_graph, neighbor_aggregate
from repro.graphs import build_semantic_graph, synthetic_hetgraph

from .common import timeit


def run(report):
    g = synthetic_hetgraph("dblp", scale=0.12, feat_scale=0.1, seed=0)
    sg = build_semantic_graph(g, ("author", "paper", "author"), max_edges=120_000)
    batch = batch_semantic_graph(sg, block=32)
    rng = np.random.default_rng(0)
    H, Dh = 4, 16
    hs = jnp.asarray(rng.standard_normal((sg.num_src, H, Dh)).astype(np.float32))
    ths = jnp.asarray(rng.standard_normal((sg.num_src, H)).astype(np.float32))
    thd = jnp.asarray(rng.standard_normal((sg.num_dst, H)).astype(np.float32))

    for backend in (NABackend.SEGMENT, NABackend.BLOCK):
        fn = jax.jit(
            lambda a, b, c: neighbor_aggregate(batch, a, b, c, backend=backend)
        )
        t = timeit(fn, ths, thd, hs, iters=3)
        report(
            f"kernel/na/{backend.value}",
            t,
            f"edges={sg.num_edges} heads={H} dh={Dh}",
        )
    # Pallas kernel body, interpret mode (correctness-path timing only)
    fn = jax.jit(
        lambda a, b, c: neighbor_aggregate(batch, a, b, c, backend=NABackend.MULTIGRAPH_INTERPRET)
    )
    t = timeit(fn, ths, thd, hs, warmup=1, iters=1)
    report("kernel/na/pallas_interpret", t, "interpret-mode (not a TPU projection)")

    # flash attention: XLA chunked vs materialized, plus pallas interpret
    from repro.models.lm.attention import _sdpa_flash_xla, _sdpa_xla
    from repro.models.lm.config import LMConfig

    cfg = LMConfig(name="b", family="dense", num_layers=1, d_model=256, num_heads=8,
                   num_kv_heads=2, d_ff=256, vocab_size=64, head_dim=32,
                   dtype="float32", param_dtype="float32")
    B, S = 2, 1024
    q = jnp.asarray(rng.standard_normal((B, S, 8, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, 2, 32)).astype(np.float32))
    mask = jnp.tril(jnp.ones((S, S), bool))[None]
    f_mat = jax.jit(lambda q_, k_, v_: _sdpa_xla(q_, k_, v_, jnp.broadcast_to(mask, (B, S, S)), cfg))
    f_chk = jax.jit(lambda q_, k_, v_: _sdpa_flash_xla(q_, k_, v_, cfg, causal=True, window=None, q_chunk=256, k_chunk=256))
    t_mat = timeit(f_mat, q, k, v, iters=3)
    t_chk = timeit(f_chk, q, k, v, iters=3)
    report("kernel/attn/materialized", t_mat, f"S={S}")
    report("kernel/attn/chunked_online", t_chk, f"S={S} ratio={t_mat/t_chk:.2f}x")

    # FP + coefficient fusion (paper Alg. 2 lines 7-8): one pass over x vs
    # separate projection + two coefficient contractions
    from repro.core import stages

    N, Din, Hh, Dhh = 1024, 512, 8, 64
    x = jnp.asarray(rng.standard_normal((N, Din)).astype(np.float32))
    wfp = jnp.asarray(rng.standard_normal((Din, Hh * Dhh)).astype(np.float32) * 0.05)
    bfp = jnp.zeros((Hh * Dhh,))
    a_s = jnp.asarray(rng.standard_normal((Hh, Dhh)).astype(np.float32))
    a_d = jnp.asarray(rng.standard_normal((Hh, Dhh)).astype(np.float32))

    @jax.jit
    def staged_fp(x_):
        hflat = stages.feature_projection(x_, wfp, bfp)
        hh = hflat.reshape(N, Hh, Dhh)
        ts, td = stages.attention_coefficients(hh, a_s, a_d)
        return hflat, ts, td

    @jax.jit
    def fused_fp(x_):
        from repro.kernels import fused_fp_coeff
        return fused_fp_coeff(x_, wfp, bfp, a_s, a_d, block_n=256, block_k=256, interpret=True)

    t_staged = timeit(staged_fp, x, iters=3)
    t_fused = timeit(fused_fp, x, warmup=1, iters=1)
    report("kernel/fp_coeff/staged_xla", t_staged, f"N={N} Din={Din}")
    report("kernel/fp_coeff/fused_pallas_interpret", t_fused,
           "interpret-mode (datapath validation, not a TPU projection)")
