"""Causal/local GQA flash attention (Pallas TPU).

The same online-softmax decomposition the paper uses for the NA stage
(Fig. 6) applied to dense attention: numerator and denominator accumulate
simultaneously per query tile, so no S×S score matrix ever exists.  Used
by every attention-bearing assigned architecture; ``window`` implements
recurrentgemma's local attention.

Grid: (B, Hq, Sq/BQ, Sk/BK); the key axis is sequential (scratch carries
m/l/acc); batch, head and query-block axes are parallel.  GQA maps query
head h to kv head h // (Hq/Hkv) in the k/v index maps — kv tiles are
fetched once per group by the pipeline, the VMEM analogue of the paper's
coefficient reuse across edges sharing an endpoint.

VMEM per step (BQ=BK=512, Dh=128, bf16 in / f32 acc):
q 128 KB + k/v 256 KB + acc/m/l ~260 KB ≈ 0.7 MB « 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref,    # [1, 1, BQ, Dh]
    k_ref,    # [1, 1, BK, Dh]
    v_ref,    # [1, 1, BK, Dh]
    o_ref,    # [1, 1, BQ, Dh]
    acc_ref,  # [BQ, Dh] f32
    m_ref,    # [BQ] f32
    l_ref,    # [BQ] f32
    *,
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    q_offset: int,
):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, Dh]
    k = k_ref[0, 0].astype(jnp.float32)          # [BK, Dh]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [BQ, BK]

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)

    v = v_ref[0, 0].astype(jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-9)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Hq, Sq, Dh]
    k: jnp.ndarray,  # [B, Hkv, Sk, Dh]
    v: jnp.ndarray,  # [B, Hkv, Sk, Dh]
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0
    grid = (b, hq, sq // bq, sk // bk)
    q_offset = sk - sq  # align the last query with the last key

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            causal=causal,
            window=window,
            block_q=bq,
            block_k=bk,
            q_offset=q_offset,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, i, j: (b_, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out
