"""Bound-aware stage fusion — execution paths for the NA stage (paper §4.1).

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
import numpy as np

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


_MULTIGRAPH_BACKENDS = (NABackend.MULTIGRAPH, NABackend.MULTIGRAPH_INTERPRET)
_FUSED_FP_BACKENDS = (NABackend.FUSED_FP, NABackend.FUSED_FP_INTERPRET)
# materialized-path equivalent of each fused backend (e.g. for serving's
# FP-cache-hit bypass: the projected table already exists, so re-projecting
# inside the kernel would waste the cache)
_FUSED_TO_MULTIGRAPH = {
    NABackend.FUSED_FP: NABackend.MULTIGRAPH,
    NABackend.FUSED_FP_INTERPRET: NABackend.MULTIGRAPH_INTERPRET,
}

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

    def row_edge_counts(self) -> np.ndarray:
        """#edges per dst-block row (workload units for lane scheduling)."""
        assert self.masks is not None
        return np.asarray((self.masks != 0).sum(axis=(1, 2, 3)), np.int64)


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
    """Attention NA with the chosen backend.  Returns [num_dst, H, Dh]."""
    if backend in _MULTIGRAPH_BACKENDS:
        bias = edge_bias
        if not (hasattr(bias, "ndim") and bias.ndim == 2):
            bias = jnp.broadcast_to(jnp.asarray(bias, jnp.float32), (1, theta_src.shape[-1]))
        return neighbor_aggregate_multi(
            [batch], theta_src[None], theta_dst[None], h_src,
            backend=backend, leaky_slope=leaky_slope, edge_bias=bias,
        )[0]
    if backend is NABackend.SEGMENT:
        assert batch.src is not None, "batch built without edge list"
        return stages.segment_softmax_aggregate(
            batch.src, batch.dst, batch.valid, theta_src, theta_dst, h_src,
            batch.num_dst, leaky_slope=leaky_slope, edge_bias=edge_bias,
        )

    assert backend is NABackend.BLOCK, f"{backend} needs neighbor_aggregate_multi"
    assert batch.col_index is not None, "batch built without block CSR"
    ns_pad = ((batch.num_src + batch.block - 1) // batch.block) * batch.block
    out = stages.block_softmax_aggregate(
        batch.col_index, batch.masks, _pad_rows(theta_src, ns_pad),
        _pad_rows(theta_dst, batch.num_dst_pad), _pad_rows(h_src, ns_pad),
        leaky_slope=leaky_slope, edge_bias=edge_bias,
    )
    return out[: batch.num_dst]


def build_unit_tables(batches: list[SemanticGraphBatch]):
    """Stack the block-CSR rows of several semantic graphs into the flat
    (col_index, graph_id, dst_row, masks) work-unit layout of
    kernels/seg_gat_agg_multigraph: one unit per (graph, dst-block row),
    col widths padded to the max across graphs.

    Requires all graphs to share the dst vertex space and block size
    (HAN's metapath graphs do).  Host-side; build once per layer.
    """
    assert batches, "no semantic graphs"
    b = batches[0].block
    n_rows = int(batches[0].col_index.shape[0])
    for bb in batches:
        assert bb.col_index is not None, "batch built without block CSR"
        assert bb.block == b and int(bb.col_index.shape[0]) == n_rows

    w_max = max(int(bb.col_index.shape[1]) for bb in batches)
    g_n = len(batches)
    col = np.full((g_n, n_rows, w_max), -1, np.int32)
    masks = np.zeros((g_n, n_rows, w_max, b, b), bool)
    for i, bb in enumerate(batches):
        wg = int(bb.col_index.shape[1])
        col[i, :, :wg] = np.asarray(bb.col_index)
        masks[i, :, :wg] = np.asarray(bb.masks)
    gid = np.repeat(np.arange(g_n, dtype=np.int32), n_rows)
    row = np.tile(np.arange(n_rows, dtype=np.int32), g_n)
    return (
        jnp.asarray(col.reshape(g_n * n_rows, w_max)),
        jnp.asarray(gid),
        jnp.asarray(row),
        jnp.asarray(masks.reshape(g_n * n_rows, w_max, b, b)),
    )


def neighbor_aggregate_multi(
    batches: list[SemanticGraphBatch],
    theta_src: jnp.ndarray | None,  # [G, Ns, H]   (None with FUSED_FP)
    theta_dst: jnp.ndarray | None,  # [G, Nd, H]   (None with FUSED_FP)
    h_src: jnp.ndarray | None,      # [Ns, H, Dh]  (None with FUSED_FP)
    *,
    backend: NABackend = NABackend.MULTIGRAPH_INTERPRET,
    leaky_slope: float = 0.2,
    edge_bias: jnp.ndarray | None = None,  # [G, H]
    unit_tables: tuple | None = None,
    fp: FusedFPInputs | None = None,
) -> jnp.ndarray:
    """NA for ALL semantic graphs of a layer at once.  Returns
    [G, num_dst, H, Dh].

    With a MULTIGRAPH backend this is a single fused Pallas launch (one
    forward and, under autodiff, one backward kernel for the whole layer);
    any other backend falls back to a per-graph loop of
    ``neighbor_aggregate`` — same semantics, G separate dispatches.
    ``unit_tables`` (from :func:`build_unit_tables`) may be passed to skip
    the host-side stacking inside jitted callers.

    With a FUSED_FP backend the FP stage runs *inside* the launch: pass
    ``fp=FusedFPInputs(...)`` (raw features + projection/attention params)
    and leave theta_src/theta_dst/h_src as None — no projected tensor is
    ever materialized in HBM (DESIGN.md §10).
    """
    require_tpu(backend.value)
    if backend in _FUSED_FP_BACKENDS:
        if fp is None:
            raise ValueError(
                "FUSED_FP backends take fp=FusedFPInputs (raw features + "
                "weight tables) in place of theta_src/theta_dst/h_src"
            )
        from ..kernels.seg_gat_agg_fused_fp import seg_gat_agg_fused_fp

        b0 = batches[0]
        assert b0.num_src == b0.num_dst, (
            "fused FP+NA streams ONE raw-feature table for both src and dst "
            "tiles; src and dst must share the vertex space (HAN's "
            "target-type metapath graphs do)"
        )
        b = b0.block
        nd = b0.num_dst
        nd_pad = b0.num_dst_pad
        ns_pad = ((b0.num_src + b - 1) // b) * b
        if unit_tables is None:
            unit_tables = build_unit_tables(batches)
        col, gid, row, masks = unit_tables
        x_pad = _pad_rows(fp.x, max(ns_pad, nd_pad))
        g_n = len(batches)
        out = seg_gat_agg_fused_fp(
            col, gid, row, fp.wsel, masks, x_pad, fp.w, fp.b,
            fp.a_src, fp.a_dst, edge_bias,
            leaky_slope=leaky_slope,
            interpret=backend is NABackend.FUSED_FP_INTERPRET,
        )  # [G*R*B, H, Dh] — units are g-major, rows in order
        return out.reshape(g_n, nd_pad, *out.shape[1:])[:, :nd]

    if backend not in _MULTIGRAPH_BACKENDS:
        return jnp.stack([
            neighbor_aggregate(
                bb, theta_src[i], theta_dst[i], h_src[: bb.num_src],
                backend=backend, leaky_slope=leaky_slope,
                edge_bias=0.0 if edge_bias is None else edge_bias[i],
            )
            for i, bb in enumerate(batches)
        ])

    from ..kernels.seg_gat_agg_multigraph import seg_gat_agg_multigraph

    b = batches[0].block
    nd = batches[0].num_dst
    nd_pad = batches[0].num_dst_pad
    ns_pad = ((batches[0].num_src + b - 1) // b) * b
    if unit_tables is None:
        unit_tables = build_unit_tables(batches)
    col, gid, row, masks = unit_tables

    th_s = _pad_rows(theta_src.swapaxes(0, 1), ns_pad).swapaxes(0, 1)
    th_d = _pad_rows(theta_dst.swapaxes(0, 1), nd_pad).swapaxes(0, 1)
    hs = _pad_rows(h_src, ns_pad)
    g_n = len(batches)
    out = seg_gat_agg_multigraph(
        col, gid, row, masks, th_s, th_d, hs, edge_bias,
        leaky_slope=leaky_slope,
        interpret=backend is NABackend.MULTIGRAPH_INTERPRET,
    )  # [G*R*B, H, Dh] — units are g-major, rows in order
    return out.reshape(g_n, nd_pad, *out.shape[1:])[:, :nd]


def mean_aggregate(
    batch: SemanticGraphBatch, h_src: jnp.ndarray
) -> jnp.ndarray:
    """Mean NA (R-GCN).  Returns [num_dst, ...]."""
    assert batch.src is not None
    return stages.segment_mean_aggregate(batch.src, batch.dst, batch.valid, h_src, batch.num_dst)
