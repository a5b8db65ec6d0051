"""Fused Feature Projection + attention-coefficient kernel (Pallas TPU).

Paper §4.1.1 modification (1): the attention-coefficient computation
(Alg. 2 line 8) is fused into the FP stage — the moment a tile of h' is
produced by the MXU it is immediately contracted with a_src/a_dst, without
a round-trip to HBM.  One pass over x yields (h', theta_src, theta_dst).

Tiling: grid (N/BN, Din/BK).  The K axis is sequential with an f32 VMEM
accumulator; the N axis is parallel.  On the last K step the kernel adds
the bias, emits h', and computes both coefficient vectors per head while
the h' tile is still VMEM-resident (the accelerator's FP-Buf residency).

Working set (BN=256, BK=512, H*Dh=512, fp32): x 512 KB + w 1 MB +
acc/h' 512 KB ≈ 2 MB « 16 MB VMEM; matmul dims all 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref,      # [BN, BK]
    w_ref,      # [BK, HDh]
    b_ref,      # [1, HDh]
    asrc_ref,   # [H, Dh]
    adst_ref,   # [H, Dh]
    h_ref,      # out [BN, HDh]
    ths_ref,    # out [BN, H]
    thd_ref,    # out [BN, H]
    acc_ref,    # scratch [BN, HDh] f32
    *,
    heads: int,
    head_dim: int,
):
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finalize():
        h = acc_ref[...] + b_ref[0, :].astype(jnp.float32)  # [BN, HDh]
        h_ref[...] = h.astype(h_ref.dtype)
        # coefficients per head while h' is VMEM-resident
        for hd in range(heads):
            seg = h[:, hd * head_dim : (hd + 1) * head_dim]  # [BN, Dh]
            ths_ref[:, hd] = jnp.dot(
                seg, asrc_ref[hd, :].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).astype(ths_ref.dtype)
            thd_ref[:, hd] = jnp.dot(
                seg, adst_ref[hd, :].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).astype(thd_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret")
)
def fused_fp_coeff(
    x: jnp.ndarray,      # [N, Din]
    w: jnp.ndarray,      # [Din, H*Dh]
    b: jnp.ndarray,      # [H*Dh]
    a_src: jnp.ndarray,  # [H, Dh]
    a_dst: jnp.ndarray,  # [H, Dh]
    *,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (h' [N, H*Dh], theta_src [N, H], theta_dst [N, H])."""
    n, din = x.shape
    hdh = w.shape[1]
    heads, head_dim = a_src.shape
    assert heads * head_dim == hdh

    bn = min(block_n, n)
    bk = min(block_k, din)
    assert n % bn == 0 and din % bk == 0, (n, bn, din, bk)
    grid = (n // bn, din // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, head_dim=head_dim),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, hdh), lambda i, k: (k, 0)),
            pl.BlockSpec((1, hdh), lambda i, k: (0, 0)),
            pl.BlockSpec((heads, head_dim), lambda i, k: (0, 0)),
            pl.BlockSpec((heads, head_dim), lambda i, k: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, hdh), lambda i, k: (i, 0)),
            pl.BlockSpec((bn, heads), lambda i, k: (i, 0)),
            pl.BlockSpec((bn, heads), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, hdh), x.dtype),
            jax.ShapeDtypeStruct((n, heads), jnp.float32),
            jax.ShapeDtypeStruct((n, heads), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, hdh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_fp_coeff",
    )(x, w, b.reshape(1, -1), a_src, a_dst)
    return tuple(out)
