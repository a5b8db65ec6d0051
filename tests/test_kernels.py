"""Per-kernel interpret=True validation against ref.py oracles, sweeping
shapes and dtypes as the brief requires."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_fp_coeff import fused_fp_coeff
from repro.kernels.ref import ref_flash_attention, ref_fused_fp_coeff, ref_seg_gat_agg
from repro.kernels.seg_gat_agg_multigraph import seg_gat_agg_multigraph

TOL = {jnp.float32: dict(rtol=3e-5, atol=3e-5), jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def seg_gat_agg(col, masks, ths, thd, hs, *, edge_bias=None, interpret):
    """Single-graph NA: the multigraph kernel at G=1, one unit per dst row."""
    R = col.shape[0]
    return seg_gat_agg_multigraph(
        col, jnp.zeros((R,), jnp.int32), jnp.arange(R, dtype=jnp.int32), masks,
        ths[None], thd[None], hs, None if edge_bias is None else edge_bias[None],
        interpret=interpret,
    )


def _unique_cols(rng, R, W, ncols):
    """BlockCSR contract: column indices are unique within a row (-1 pad)."""
    col = np.full((R, W), -1, np.int32)
    for r in range(R):
        k = rng.integers(0, min(W, ncols) + 1)
        col[r, :k] = rng.choice(ncols, size=k, replace=False)
    return col


@pytest.mark.parametrize("B,R,W,H,Dh", [(8, 2, 1, 1, 8), (8, 3, 2, 2, 16), (16, 2, 3, 1, 32), (8, 1, 4, 4, 8)])
def test_seg_gat_agg_shapes(B, R, W, H, Dh):
    rng = np.random.default_rng(B + R + W)
    ns = 4 * B
    col = _unique_cols(rng, R, W, 4)
    masks = rng.random((R, W, B, B)) < 0.3
    ths = rng.standard_normal((ns, H)).astype(np.float32)
    thd = rng.standard_normal((R * B, H)).astype(np.float32)
    hs = rng.standard_normal((ns, H, Dh)).astype(np.float32)
    out = seg_gat_agg(
        jnp.asarray(col), jnp.asarray(masks), jnp.asarray(ths), jnp.asarray(thd),
        jnp.asarray(hs), interpret=True,
    )
    ref = ref_seg_gat_agg(
        jnp.asarray(col), jnp.asarray(masks), jnp.asarray(ths), jnp.asarray(thd), jnp.asarray(hs)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL[jnp.float32])


def test_seg_gat_agg_edge_bias_and_all_padding():
    rng = np.random.default_rng(0)
    B, R, W, H, Dh = 8, 2, 2, 2, 8
    ns = 2 * B
    col = np.array([[0, 1], [-1, -1]], np.int32)  # second row fully padded
    masks = rng.random((R, W, B, B)) < 0.4
    ths = rng.standard_normal((ns, H)).astype(np.float32)
    thd = rng.standard_normal((R * B, H)).astype(np.float32)
    hs = rng.standard_normal((ns, H, Dh)).astype(np.float32)
    bias = jnp.asarray(rng.standard_normal(H).astype(np.float32))
    out = seg_gat_agg(
        jnp.asarray(col), jnp.asarray(masks), jnp.asarray(ths), jnp.asarray(thd),
        jnp.asarray(hs), edge_bias=bias, interpret=True,
    )
    ref = ref_seg_gat_agg(
        jnp.asarray(col), jnp.asarray(masks), jnp.asarray(ths), jnp.asarray(thd),
        jnp.asarray(hs), edge_bias=bias,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)
    assert np.abs(np.asarray(out)[B:]).max() == 0.0  # padded row -> zeros


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,Din,H,Dh,bn,bk", [(64, 48, 2, 16, 32, 16), (32, 64, 1, 32, 32, 64), (128, 32, 4, 8, 64, 32)])
def test_fused_fp_coeff_sweep(dtype, N, Din, H, Dh, bn, bk):
    rng = np.random.default_rng(N + Din)
    x = rng.standard_normal((N, Din)).astype(np.float32) * 0.5
    w = rng.standard_normal((Din, H * Dh)).astype(np.float32) * 0.1
    b = rng.standard_normal(H * Dh).astype(np.float32) * 0.1
    a_s = rng.standard_normal((H, Dh)).astype(np.float32)
    a_d = rng.standard_normal((H, Dh)).astype(np.float32)
    args = [jnp.asarray(x, dtype), jnp.asarray(w, dtype), jnp.asarray(b, dtype),
            jnp.asarray(a_s, dtype), jnp.asarray(a_d, dtype)]
    h, ts, td = fused_fp_coeff(*args, block_n=bn, block_k=bk, interpret=True)
    rh, rts, rtd = ref_fused_fp_coeff(*args)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(h, np.float32), np.asarray(rh, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(ts), np.asarray(rts, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(td), np.asarray(rtd, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Sk,Dh,causal,window",
    [
        (2, 4, 2, 32, 32, 16, True, None),
        (1, 4, 4, 16, 48, 16, True, None),   # Sq != Sk (continuation)
        (1, 2, 1, 32, 32, 16, True, 8),      # MQA + local window
        (1, 2, 2, 32, 32, 16, False, None),  # bidirectional (encoder)
        (2, 8, 2, 64, 64, 32, True, None),
    ],
)
def test_flash_attention_sweep(dtype, B, Hq, Hkv, Sq, Sk, Dh, causal, window):
    rng = np.random.default_rng(Sq + Sk)
    q = jnp.asarray(rng.standard_normal((B, Hq, Sq, Dh)).astype(np.float32), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Sk, Dh)).astype(np.float32), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Sk, Dh)).astype(np.float32), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window, block_q=16, block_k=16, interpret=True)
    r = ref_flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32), **TOL[dtype]
    )


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_flash_attention_property(data):
    """Property: output rows are convex combinations of V rows."""
    rng = np.random.default_rng(data.draw(st.integers(0, 999)))
    s = data.draw(st.sampled_from([16, 32]))
    h = data.draw(st.sampled_from([1, 2]))
    q = jnp.asarray(rng.standard_normal((1, h, s, 8)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, h, s, 8)).astype(np.float32))
    v = jnp.ones((1, h, s, 8), jnp.float32)
    o = flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(o), 1.0, rtol=1e-5)


def _multigraph_case(seed=7, B=8, U=4, W=3, G=3, H=2, Dh=8, nblk=4, dtype=np.float32):
    rng = np.random.default_rng(seed)
    ns_pad = nblk * B
    col = np.full((U, W), -1, np.int32)
    for u in range(U):
        k = rng.integers(1, W + 1)
        col[u, :k] = rng.choice(nblk, size=k, replace=False)
    gid = rng.integers(0, G, U).astype(np.int32)
    row = rng.integers(0, nblk, U).astype(np.int32)
    masks = rng.random((U, W, B, B)) < 0.3
    ths = rng.standard_normal((G, ns_pad, H)).astype(dtype)
    thd = rng.standard_normal((G, ns_pad, H)).astype(dtype)
    hs = rng.standard_normal((ns_pad, H, Dh)).astype(dtype)
    bias = rng.standard_normal((G, H)).astype(np.float32)
    return col, gid, row, masks, ths, thd, hs, bias


def test_seg_gat_agg_multigraph_invalid_units_are_exact_zeros():
    from repro.kernels import seg_gat_agg_multigraph

    col, gid, row, masks, ths, thd, hs, bias = _multigraph_case()
    col[1] = -1   # unit 1: every slot padded
    col[3] = -1
    out = seg_gat_agg_multigraph(
        jnp.asarray(col), jnp.asarray(gid), jnp.asarray(row), jnp.asarray(masks),
        jnp.asarray(ths), jnp.asarray(thd), jnp.asarray(hs), jnp.asarray(bias),
        interpret=True,
    )
    B = masks.shape[-1]
    out = np.asarray(out)
    assert np.abs(out[1 * B : 2 * B]).max() == 0.0
    assert np.abs(out[3 * B : 4 * B]).max() == 0.0
    assert np.abs(out[0:B]).max() > 0.0  # live units untouched


def test_seg_gat_agg_multigraph_bf16_matches_f32_oracle():
    from repro.core.multilane import _unit_na
    from repro.kernels import seg_gat_agg_multigraph

    col, gid, row, masks, ths, thd, hs, bias = _multigraph_case(seed=11)
    B = masks.shape[-1]
    out = seg_gat_agg_multigraph(
        jnp.asarray(col), jnp.asarray(gid), jnp.asarray(row), jnp.asarray(masks),
        jnp.asarray(ths), jnp.asarray(thd), jnp.asarray(hs, jnp.bfloat16),
        jnp.asarray(bias), interpret=True,
    )
    assert out.dtype == jnp.bfloat16
    for u in range(col.shape[0]):
        ref = _unit_na(
            jnp.asarray(col[u]), jnp.asarray(masks[u]), jnp.int32(gid[u]),
            jnp.int32(row[u]), jnp.asarray(ths), jnp.asarray(thd), jnp.asarray(hs),
            jnp.asarray(bias), 0.2,
        )
        np.testing.assert_allclose(
            np.asarray(out[u * B : (u + 1) * B], np.float32), np.asarray(ref),
            **TOL[jnp.bfloat16],
        )


def test_seg_gat_agg_multigraph_g1_reduces_to_seg_gat_agg():
    """Single-graph NA (a one-lane plan of one graph) runs the multigraph
    kernel at G=1 and matches the dense single-graph oracle."""
    from repro.core import NABackend, SemanticGraphBatch
    from repro.core.multilane import build_multilane_plan, multilane_na

    rng = np.random.default_rng(5)
    B, R, W, H, Dh, nblk = 8, 3, 2, 2, 8, 4
    ns = nblk * B
    col = _unique_cols(rng, R, W, nblk)
    masks = rng.random((R, W, B, B)) < 0.4
    ths = jnp.asarray(rng.standard_normal((ns, H)).astype(np.float32))
    thd = jnp.asarray(rng.standard_normal((R * B, H)).astype(np.float32))
    hs = jnp.asarray(rng.standard_normal((ns, H, Dh)).astype(np.float32))
    bias = jnp.asarray(rng.standard_normal((H,)).astype(np.float32))
    batch = SemanticGraphBatch(
        name="g", src_type="t", dst_type="t", num_src=ns, num_dst=R * B,
        num_edges=int(masks.sum()), path_types=("t", "t"),
        col_index=jnp.asarray(col), masks=jnp.asarray(masks), block=B,
    )
    single = multilane_na(
        build_multilane_plan([batch], 1), ths[None], thd[None], hs,
        backend=NABackend.MULTIGRAPH_INTERPRET, edge_bias=bias[None],
    )[0]
    ref = ref_seg_gat_agg(jnp.asarray(col), jnp.asarray(masks), ths, thd, hs, edge_bias=bias)
    np.testing.assert_allclose(np.asarray(single), np.asarray(ref), **TOL[jnp.float32])


def test_seg_gat_agg_multigraph_vjp_matches_block_autodiff():
    """The fused Pallas backward must agree with autodiff of the pure-jnp
    BLOCK oracle (stages.block_softmax_aggregate) for every input."""
    from repro.core.stages import block_softmax_aggregate
    from repro.kernels import seg_gat_agg_multigraph

    rng = np.random.default_rng(3)
    B, R, W, H, Dh, nblk = 8, 3, 2, 2, 8, 4
    ns = nblk * B
    col = _unique_cols(rng, R, W, nblk)
    masks = rng.random((R, W, B, B)) < 0.4
    ths = jnp.asarray(rng.standard_normal((ns, H)).astype(np.float32))
    thd = jnp.asarray(rng.standard_normal((R * B, H)).astype(np.float32))
    hs = jnp.asarray(rng.standard_normal((ns, H, Dh)).astype(np.float32))
    bias = jnp.asarray(rng.standard_normal((H,)).astype(np.float32))
    colj, masksj = jnp.asarray(col), jnp.asarray(masks)
    gid = jnp.zeros((R,), jnp.int32)
    row = jnp.arange(R, dtype=jnp.int32)

    def f_kernel(a, b, c, d):
        out = seg_gat_agg_multigraph(
            colj, gid, row, masksj, a[None], b[None], c, d[None], interpret=True
        )
        return jnp.sum(jnp.sin(out))

    def f_ref(a, b, c, d):
        out = block_softmax_aggregate(colj, masksj, a, b, c, edge_bias=d)
        return jnp.sum(jnp.sin(out))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(ths, thd, hs, bias)
    gr = jax.grad(f_ref, argnums=(0, 1, 2, 3))(ths, thd, hs, bias)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_seg_gat_agg_multigraph_matches_multilane_oracle():
    """The multi-lane kernel (§4.2 at Pallas level): mixed-graph work units
    in one launch must match the per-unit jnp online-softmax oracle."""
    from repro.core.multilane import _unit_na
    from repro.kernels import seg_gat_agg_multigraph

    rng = np.random.default_rng(7)
    B, U, W, G, H, Dh = 8, 4, 3, 3, 2, 8
    nblk = 4
    ns_pad = nblk * B
    col = np.full((U, W), -1, np.int32)
    for u in range(U):
        k = rng.integers(1, W + 1)
        col[u, :k] = rng.choice(nblk, size=k, replace=False)
    gid = rng.integers(0, G, U).astype(np.int32)
    row = rng.integers(0, nblk, U).astype(np.int32)
    masks = rng.random((U, W, B, B)) < 0.3
    ths = rng.standard_normal((G, ns_pad, H)).astype(np.float32)
    thd = rng.standard_normal((G, ns_pad, H)).astype(np.float32)
    hs = rng.standard_normal((ns_pad, H, Dh)).astype(np.float32)
    bias = rng.standard_normal((G, H)).astype(np.float32)
    out = seg_gat_agg_multigraph(
        jnp.asarray(col), jnp.asarray(gid), jnp.asarray(row), jnp.asarray(masks),
        jnp.asarray(ths), jnp.asarray(thd), jnp.asarray(hs), jnp.asarray(bias),
        interpret=True,
    )
    for u in range(U):
        ref = _unit_na(
            jnp.asarray(col[u]), jnp.asarray(masks[u]), jnp.int32(gid[u]),
            jnp.int32(row[u]), jnp.asarray(ths), jnp.asarray(thd), jnp.asarray(hs),
            jnp.asarray(bias), 0.2,
        )
        np.testing.assert_allclose(
            np.asarray(out[u * B : (u + 1) * B]), np.asarray(ref), rtol=3e-5, atol=3e-5
        )
