"""The main-path Pallas kernels compile for a TPU v5e, without a chip.

The TPU compiler is installed alongside JAX; it compiles for a described
``v5e:2x2`` topology that is not attached.  Interpret mode accepts block
shapes that Mosaic refuses (the last two block dims must be multiples of
(8, 128) or span the whole array dim), so these compiles are what guards
the kernels' TPU layouts.  Widths are HAN's (8 heads x 8, block 128) on
ACM's raw feature width (1902), and S-HGN's typed launches on the union
graph of ACM (8 heads x 64 with the attention residual; the output
layer's 1 head, its classes padded to 128 lanes; 8 edge types).

The topology is described only inside a fixture: the TPU library may be
loaded by one process at a time, and pytest-xdist workers import every
test file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.seg_gat_agg_fused_fp import seg_gat_agg_fused_fp
from repro.kernels.seg_gat_agg_multigraph import Attention, seg_gat_agg_multigraph

H, DH, B, G, U, W = 8, 8, 128, 2, 24, 8
NS = 32 * B        # src vertex space (padded), 32 blocks
DIN = 1902         # ACM paper features


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _multigraph_args(sh):
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    i32 = jnp.int32
    return (
        s((U, W), i32), s((U,), i32), s((U,), i32), s((U, W, B, B), jnp.bool_),
        s((G, NS, H)), s((G, (U // G) * B, H)), s((NS, H, DH)), s((G, H)),
    )


def _fused_fp_args(sh, tables):
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    i32 = jnp.int32
    return (
        s((U, W), i32), s((U,), i32), s((U,), i32), s((G,), i32),
        s((U, W, B, B), jnp.bool_), s((NS, DIN)), s((tables, DIN, H * DH)),
        s((tables, H * DH)), s((G, H, DH)), s((G, H, DH)), s((G, H)),
    )


def test_multigraph_forward_compiles(one_chip):
    txt = _compiled_text(seg_gat_agg_multigraph, *_multigraph_args(one_chip))
    assert "tpu_custom_call" in txt


def test_multigraph_backward_compiles(one_chip):
    def loss_grad(col, gid, row, masks, ths, thd, hs, bias):
        f = lambda *p: seg_gat_agg_multigraph(col, gid, row, masks, *p).sum()
        return jax.grad(f, argnums=(0, 1, 2, 3))(ths, thd, hs, bias)

    txt = _compiled_text(loss_grad, *_multigraph_args(one_chip))
    assert "tpu_custom_call" in txt
    assert "seg_gat_agg_multigraph_bwd" in txt


@pytest.mark.parametrize("tables", [1, 2])
def test_fused_fp_forward_compiles(one_chip, tables):
    txt = _compiled_text(seg_gat_agg_fused_fp, *_fused_fp_args(one_chip, tables))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tables", [1, 2])
def test_fused_fp_backward_compiles(one_chip, tables):
    def loss_grad(col, gid, row, wsel, masks, x, w, b, a_src, a_dst, bias):
        f = lambda *p: seg_gat_agg_fused_fp(col, gid, row, wsel, masks, *p).sum()
        return jax.grad(f, argnums=(0, 1, 2, 3, 4, 5))(x, w, b, a_src, a_dst, bias)

    txt = _compiled_text(loss_grad, *_fused_fp_args(one_chip, tables))
    assert "tpu_custom_call" in txt
    assert "seg_gat_agg_fused_fp_bwd" in txt


@pytest.mark.parametrize("heads,dh,residual", [(8, 64, True), (1, 128, False)],
                         ids=["hidden-residual", "output"])
def test_multigraph_typed_tiles_compile(one_chip, heads, dh, residual):
    """S-HGN's launches: int8 type tiles, a [T, H] bias table, the
    residual's rebuilt attention, float32 dots; forward and backward in
    one program."""
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    i32, t_n, u_n, w_n = jnp.int32, 8, 86, 8
    n = u_n * B
    args = (s((u_n, w_n), i32), s((u_n,), i32), s((u_n,), i32), s((u_n, w_n, B, B), jnp.int8),
            s((1, n, heads)), s((1, n, heads)), s((n, heads, dh)), s((t_n, heads)))
    prev = Attention(s((1, n, heads)), s((1, n, heads)), s((t_n, heads)), s((n, heads))) if residual else None

    def loss_grad(col, gid, row, tiles, ths, thd, hs, bias, prev):
        f = lambda *p: seg_gat_agg_multigraph(
            col, gid, row, tiles, *p, prev, leaky_slope=0.05,
            beta=0.05 if residual else None, precision=jax.lax.Precision.HIGHEST).sum()
        return jax.grad(f, argnums=(0, 1, 2, 3))(ths, thd, hs, bias)

    txt = _compiled_text(loss_grad, *args, prev)
    assert "tpu_custom_call" in txt
    assert "seg_gat_agg_multigraph_bwd" in txt
