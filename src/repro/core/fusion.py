"""Bound-aware stage fusion — NA backends and the formats they read (paper §4.1).

Interchangeable NA backends with identical semantics:

* ``SEGMENT``  — two-pass segment softmax over a padded edge list.  This is
  the *staged baseline*: it mirrors the GPU framework's SpMM-style pass
  structure (materialize per-edge logits, reduce max, exponentiate, reduce
  sum, weighted SpMM).
* ``BLOCK``    — pure-jnp block-CSR online softmax (numerator/denominator
  accumulated simultaneously — the paper's softmax decomposition, Fig. 6).
* ``MULTIGRAPH`` — the Pallas TPU kernel
  (kernels/seg_gat_agg_multigraph): the fused online-softmax NA datapath
  expressed as VMEM-tiled MXU work, all semantic graphs of a layer in one
  launch (one graph is the G=1 case).
* ``FUSED_FP`` — the same launch with the FP stage pulled inside.

SEGMENT and BLOCK run here, one semantic graph at a time.  The kernel
backends launch only through a work-unit plan (core/multilane.py:
``build_multilane_plan`` and ``multilane_na``), which is where an
``NABackend`` names its kernel.

A compiled Pallas backend runs only on a TPU; its ``*_INTERPRET`` twin
runs the same kernel body under the Pallas interpreter (CPU validation).
Nothing swaps one for the other: :func:`require_tpu` refuses a compiled
backend on a host without a TPU.

Stage fusion proper — running FP, theta, NA, LSF inside *one* compiled
program instead of one program per stage — is expressed at the model level
(models/hgnn): `fused=True` jits the whole layer, `fused=False` runs each
stage as its own jitted program with host barriers between them, mirroring
Fig. 4(a) vs 4(b).
"""
from __future__ import annotations

import dataclasses
import enum

import jax
import jax.numpy as jnp

from ..graphs.formats import to_block_csr, to_padded_edges
from ..graphs.hetgraph import SemanticGraph
from . import stages


class NABackend(enum.Enum):
    SEGMENT = "segment"
    BLOCK = "block"
    # fused multigraph kernel (kernels/seg_gat_agg_multigraph): ALL semantic
    # graphs of a layer in one Pallas launch — the paper's multi-lane
    # datapath.  Differentiable (custom VJP with a fused backward launch).
    MULTIGRAPH = "multigraph"
    MULTIGRAPH_INTERPRET = "multigraph_interpret"
    # stage-fusion megakernel (kernels/seg_gat_agg_fused_fp): the
    # multigraph launch with the FP stage pulled INSIDE — raw features
    # stream from HBM and are projected on-chip against per-graph weight
    # tables; h' never materializes (paper Alg. 2, DESIGN.md §10).
    # Requires fp=FusedFPInputs instead of theta/h operands.
    FUSED_FP = "fused_fp"
    FUSED_FP_INTERPRET = "fused_fp_interpret"


# compiled Pallas backend name (NABackend value or multilane backend
# string) -> the interpret-mode name that runs the same kernel body
_INTERPRET_TWIN = {
    "multigraph": "multigraph_interpret",
    "fused_fp": "fused_fp_interpret",
    "kernel": "kernel_interpret",
}


def require_tpu(backend: str) -> None:
    """Refuse a compiled Pallas backend on a host whose JAX has no TPU.

    Interpret mode runs only when the caller names it; a compiled backend
    never degrades to it, so a run that reports a kernel backend ran the
    kernel on the chip.
    """
    twin = _INTERPRET_TWIN.get(backend)
    if twin is not None and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"NA backend {backend!r} compiles a Pallas TPU kernel, but JAX "
            f"found no TPU (default backend: {jax.default_backend()!r}); "
            f"ask for {twin!r} to run the same kernel in interpret mode"
        )


@dataclasses.dataclass
class SemanticGraphBatch:
    """Device-resident formats for one semantic graph."""

    name: str
    src_type: str
    dst_type: str
    num_src: int
    num_dst: int
    num_edges: int
    path_types: tuple[str, ...]
    # padded edge list (SEGMENT backend)
    src: jnp.ndarray | None = None
    dst: jnp.ndarray | None = None
    valid: jnp.ndarray | None = None
    # block CSR (BLOCK / MULTIGRAPH / FUSED_FP backends); a typed graph's
    # masks are int8 type tiles (graphs/formats.py)
    col_index: jnp.ndarray | None = None
    masks: jnp.ndarray | None = None
    block: int = 128
    # typed graphs (the union view): each padded edge's type, and the names
    edge_type: jnp.ndarray | None = None
    edge_type_names: tuple[str, ...] = ()

    @property
    def num_dst_pad(self) -> int:
        if self.col_index is None:
            return self.num_dst
        return int(self.col_index.shape[0]) * self.block


_SGB_ARRAY_FIELDS = ("src", "dst", "valid", "col_index", "masks", "edge_type")
_SGB_META_FIELDS = (
    "name", "src_type", "dst_type", "num_src", "num_dst", "num_edges", "path_types", "block",
    "edge_type_names",
)


def _sgb_flatten(b: "SemanticGraphBatch"):
    children = tuple(getattr(b, f) for f in _SGB_ARRAY_FIELDS)
    aux = tuple(getattr(b, f) for f in _SGB_META_FIELDS)
    return children, aux


def _sgb_unflatten(aux, children):
    kw = dict(zip(_SGB_META_FIELDS, aux))
    kw.update(dict(zip(_SGB_ARRAY_FIELDS, children)))
    return SemanticGraphBatch(**kw)


jax.tree_util.register_pytree_node(SemanticGraphBatch, _sgb_flatten, _sgb_unflatten)


def batch_semantic_graph(
    sg: SemanticGraph,
    *,
    block: int = 128,
    with_edges: bool = True,
    with_blocks: bool = True,
    edge_pad: int | None = None,
) -> SemanticGraphBatch:
    kw: dict = {}
    if with_edges:
        pe = to_padded_edges(sg, pad_to=edge_pad)
        kw.update(
            src=jnp.asarray(pe.src), dst=jnp.asarray(pe.dst), valid=jnp.asarray(pe.valid)
        )
        if pe.edge_type is not None:
            kw.update(edge_type=jnp.asarray(pe.edge_type))
    if with_blocks:
        bc = to_block_csr(sg, block=block)
        kw.update(col_index=jnp.asarray(bc.col_index), masks=jnp.asarray(bc.masks), block=block)
    return SemanticGraphBatch(
        name=sg.name,
        src_type=sg.src_type,
        dst_type=sg.dst_type,
        num_src=sg.num_src,
        num_dst=sg.num_dst,
        num_edges=sg.num_edges,
        path_types=sg.path_types,
        edge_type_names=sg.edge_type_names,
        **kw,
    )


@dataclasses.dataclass
class FusedFPInputs:
    """Operands of the FUSED_FP backends: raw features plus the projection
    and attention parameters the megakernel applies on-chip (in place of
    the materialized theta_src/theta_dst/h_src of the other backends).

    ``w``/``b`` are stacked per weight *table* and ``wsel`` maps each
    semantic graph to its table — graphs sharing a projection (HAN: all of
    them) share one table instead of carrying G copies through HBM.
    """

    x: jnp.ndarray       # [N, Din]      raw features (shared src/dst space)
    w: jnp.ndarray       # [T, Din, H*Dh] per-table projection weights
    b: jnp.ndarray       # [T, H*Dh]
    a_src: jnp.ndarray   # [G, H, Dh]
    a_dst: jnp.ndarray   # [G, H, Dh]
    wsel: jnp.ndarray    # int32 [G]     graph -> weight-table row

    @classmethod
    def shared(cls, x, w, b, a_src, a_dst) -> "FusedFPInputs":
        """All graphs project through ONE weight table (HAN's layout)."""
        g_n = a_src.shape[0]
        return cls(
            x=x,
            w=w[None] if w.ndim == 2 else w,
            b=b[None] if b.ndim == 1 else b,
            a_src=a_src,
            a_dst=a_dst,
            wsel=jnp.zeros((g_n,), jnp.int32),
        )


_FP_FIELDS = ("x", "w", "b", "a_src", "a_dst", "wsel")
jax.tree_util.register_pytree_node(
    FusedFPInputs,
    lambda fp: (tuple(getattr(fp, f) for f in _FP_FIELDS), None),
    lambda _, ch: FusedFPInputs(**dict(zip(_FP_FIELDS, ch))),
)


def _pad_rows(x: jnp.ndarray, n: int) -> jnp.ndarray:
    if x.shape[0] == n:
        return x
    assert x.shape[0] < n
    pad = [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def neighbor_aggregate(
    batch: SemanticGraphBatch,
    theta_src: jnp.ndarray,  # [Ns, H]
    theta_dst: jnp.ndarray,  # [Nd, H]
    h_src: jnp.ndarray,      # [Ns, H, Dh]
    *,
    backend: NABackend = NABackend.SEGMENT,
    leaky_slope: float = 0.2,
    edge_bias: jnp.ndarray | float = 0.0,
) -> jnp.ndarray:
    """Attention NA of one semantic graph, SEGMENT or BLOCK.  Returns
    [num_dst, H, Dh].  The kernel backends run through a plan
    (core/multilane.py)."""
    if backend is NABackend.SEGMENT:
        assert batch.src is not None, "batch built without edge list"
        return stages.segment_softmax_aggregate(
            batch.src, batch.dst, batch.valid, theta_src, theta_dst, h_src,
            batch.num_dst, leaky_slope=leaky_slope, edge_bias=edge_bias,
        )

    assert backend is NABackend.BLOCK, f"{backend} runs through core.multilane"
    assert batch.col_index is not None, "batch built without block CSR"
    ns_pad = ((batch.num_src + batch.block - 1) // batch.block) * batch.block
    out = stages.block_softmax_aggregate(
        batch.col_index, batch.masks, _pad_rows(theta_src, ns_pad),
        _pad_rows(theta_dst, batch.num_dst_pad), _pad_rows(h_src, ns_pad),
        leaky_slope=leaky_slope, edge_bias=edge_bias,
    )
    return out[: batch.num_dst]


def neighbor_aggregate_multi(
    batches: list[SemanticGraphBatch],
    theta_src: jnp.ndarray,  # [G, Ns, H]
    theta_dst: jnp.ndarray,  # [G, Nd, H]
    h_src: jnp.ndarray,      # [Ns, H, Dh]
    *,
    backend: NABackend = NABackend.SEGMENT,
    leaky_slope: float = 0.2,
    edge_bias: jnp.ndarray | None = None,  # [G, H]
) -> jnp.ndarray:
    """NA for all semantic graphs of a layer, one ``neighbor_aggregate``
    per graph (SEGMENT or BLOCK).  Returns [G, num_dst, H, Dh]."""
    return jnp.stack([
        neighbor_aggregate(
            bb, theta_src[i], theta_dst[i], h_src[: bb.num_src],
            backend=backend, leaky_slope=leaky_slope,
            edge_bias=0.0 if edge_bias is None else edge_bias[i],
        )
        for i, bb in enumerate(batches)
    ])


def mean_aggregate(
    batch: SemanticGraphBatch, h_src: jnp.ndarray
) -> jnp.ndarray:
    """Mean NA (R-GCN).  Returns [num_dst, ...]."""
    assert batch.src is not None
    return stages.segment_mean_aggregate(batch.src, batch.dst, batch.valid, h_src, batch.num_dst)
