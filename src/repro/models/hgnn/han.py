"""HAN — Heterogeneous graph Attention Network (Wang et al., WWW'19).

Table 2 semantics: type-specific FP, GAT neighbor attention per metapath
semantic graph, semantic attention fusion (LSF+GSF split per Alg. 2).
Metapath endpoints are all the target type, so FP projects the target
features exactly once and every semantic graph gathers from it — the
functional RAB (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import stages
from ...core.fusion import FusedFPInputs, NABackend, _pad_rows, neighbor_aggregate
from ...core.multilane import (
    MultiLanePlan,
    build_multilane_plan,
    multilane_na,
    multilane_na_sharded,
)
from ...dist.sharding import shard
from .common import HGNNData, HGNNModel, glorot, split_keys


def init_han(
    rng: jax.Array,
    data: HGNNData,
    *,
    hidden: int = 64,
    heads: int = 8,
    att_dim: int = 128,
) -> dict:
    d_in = data.feature_dims[data.target_type]
    n_graphs = len(data.graphs)
    keys = split_keys(rng, 5 + 2 * n_graphs)
    params = {
        "w_fp": glorot(keys[0], (d_in, heads * hidden)),
        "b_fp": jnp.zeros((heads * hidden,)),
        "a_src": jnp.stack([glorot(keys[5 + 2 * i], (heads, hidden)) for i in range(n_graphs)]),
        "a_dst": jnp.stack([glorot(keys[6 + 2 * i], (heads, hidden)) for i in range(n_graphs)]),
        "w_g": glorot(keys[1], (heads * hidden, att_dim)),
        "b_g": jnp.zeros((att_dim,)),
        "q": glorot(keys[2], (att_dim, 1))[:, 0],
        "w_out": glorot(keys[3], (heads * hidden, data.num_classes)),
        "b_out": jnp.zeros((data.num_classes,)),
    }
    return params


def _fuse(params, z_all, n: int):
    """ELU, then LSF per semantic graph and GSF over them (scope
    ``fusion``); ``z_all`` holds one [N, H, Dh] NA output per graph."""
    with jax.named_scope("fusion"):
        z_list, w_list = [], []
        valid_dst = jnp.ones((n,), bool)
        for i in range(len(z_all)):
            z = jax.nn.elu(z_all[i].reshape(n, -1))
            z = shard(z, "act_vertex", "act_feat")
            w_p = stages.local_semantic_fusion(
                z, params["w_g"], params["b_g"], params["q"], valid_dst
            )
            z_list.append(z)
            w_list.append(w_p)
        fused, beta = stages.global_semantic_fusion(jnp.stack(w_list), jnp.stack(z_list))
        return shard(fused, "act_vertex", "act_feat"), beta


def _project(params, x):
    """FP (scope ``fp``): the target features projected once, [N, H, Dh]."""
    heads = params["a_src"].shape[1]
    with jax.named_scope("fp"):
        h = stages.feature_projection(x, params["w_fp"], params["b_fp"])
        h = shard(h, "act_vertex", "act_feat")  # projected-once FP output (RAB)
        return h.reshape(x.shape[0], heads, -1)


def _han_embed(params, data: HGNNData, backend: NABackend):
    """FP -> per-graph (theta, NA, LSF) -> GSF.  Pure (fusable).

    Stages carry ``jax.named_scope`` names (``fp``, ``theta``, ``na``,
    ``fusion``); the backward pass inherits them, so a profile can put
    each device op down to its stage.  A kernel backend runs the layer
    over a one-lane plan (``_han_embed_multilane``).
    """
    x = data.features[data.target_type]
    n = x.shape[0]

    if backend not in (NABackend.SEGMENT, NABackend.BLOCK):
        plan = build_multilane_plan(data.graphs, 1)
        if backend not in (NABackend.FUSED_FP, NABackend.FUSED_FP_INTERPRET):
            return _han_embed_multilane(params, data, plan, backend=backend)
        # Megakernel path (DESIGN.md §10): FP happens INSIDE the NA launch
        # — raw x streams through the fused kernel, h' never materializes
        # in HBM.  One forward (and, training, one backward) launch for
        # the whole layer.
        with jax.named_scope("na"):
            fp = FusedFPInputs.shared(
                _pad_rows(x, plan.n_dst_blocks * plan.block), params["w_fp"],
                params["b_fp"], params["a_src"], params["a_dst"],
            )
            z_all = multilane_na(plan, None, None, None, backend=backend, fp=fp)[:, :n]
        return _fuse(params, z_all, n)

    hh = _project(params, x)
    z_list = []
    for i, batch in enumerate(data.graphs):
        with jax.named_scope("theta"):
            th_s, th_d = stages.attention_coefficients(hh, params["a_src"][i], params["a_dst"][i])
        with jax.named_scope("na"):
            z_list.append(neighbor_aggregate(batch, th_s, th_d, hh, backend=backend))  # [N, H, Dh]
    return _fuse(params, z_list, n)


def _head(params, fused):
    """The classifier (scope ``head``)."""
    with jax.named_scope("head"):
        return fused @ params["w_out"] + params["b_out"]


def han_forward(params, data: HGNNData, *, backend: NABackend = NABackend.SEGMENT):
    fused, _ = _han_embed(params, data, backend)
    return _head(params, fused)


def _han_embed_multilane(
    params,
    data: HGNNData,
    plan: MultiLanePlan,
    *,
    mesh=None,
    lane_axes: tuple[str, ...] = ("lane",),
    backend: str | NABackend = "reference",
):
    """The consolidated HAN layer over a lane-partitioned work-unit plan.

    One theta einsum for all relations, all NA units in one fused
    dispatch, executed through ``core.multilane``: vmapped lanes on one chip
    (``mesh=None``) or ``shard_map``ped over the mesh's lane axis (paper
    §4.2.1).  ``backend="kernel"`` runs one fused multigraph Pallas launch
    per lane shard, forward AND backward (custom VJP) — the training path
    of the mesh-scale launcher.

    Equivalence contract (pinned by tests/test_multilane): the FORWARD is
    bit-identical across lane counts and backends — units are (graph,
    dst-block-row) disjoint, so lane assignment only moves exact zeros
    through the scatter/psum.  The BACKWARD's cross-unit reduction
    (d_h_src over all units sharing the src space) is grouped by lane,
    so gradients agree to f32 tolerance (~1e-9) across lane counts and
    are bit-deterministic for a fixed topology.
    """
    x = data.features[data.target_type]
    n = x.shape[0]
    n_pad = plan.n_dst_blocks * plan.block  # shared src/dst vertex space

    hh = _project(params, x)
    with jax.named_scope("theta"):
        th_s = jnp.einsum("nhd,ghd->gnh", hh, params["a_src"])
        th_d = jnp.einsum("nhd,ghd->gnh", hh, params["a_dst"])
        th_s = _pad_rows(th_s.swapaxes(0, 1), n_pad).swapaxes(0, 1)
        th_d = _pad_rows(th_d.swapaxes(0, 1), n_pad).swapaxes(0, 1)
    with jax.named_scope("na"):
        hh_p = _pad_rows(hh, n_pad)
        if mesh is None:
            z_all = multilane_na(plan, th_s, th_d, hh_p, backend=backend)
        else:
            z_all = multilane_na_sharded(
                plan, th_s, th_d, hh_p, mesh=mesh, lane_axes=lane_axes, backend=backend
            )
        z_all = z_all[:, :n]  # [G, N, H, Dh]
    return _fuse(params, z_all, n)


def han_forward_multilane(
    params,
    data: HGNNData,
    plan: MultiLanePlan,
    *,
    mesh=None,
    lane_axes: tuple[str, ...] = ("lane",),
    backend: str = "reference",
):
    """HAN logits with NA dispatched through a multi-lane plan (see
    ``_han_embed_multilane``)."""
    fused, _ = _han_embed_multilane(
        params, data, plan, mesh=mesh, lane_axes=lane_axes, backend=backend
    )
    return _head(params, fused)


# --- staged execution (Fig. 4(a) baseline): one jitted program per stage ---

@functools.partial(jax.jit, static_argnames=())
def _fp_stage(w, b, x):
    return stages.feature_projection(x, w, b)


@jax.jit
def _coeff_stage(h, a_src, a_dst):
    return stages.attention_coefficients(h, a_src, a_dst)


@functools.partial(jax.jit, static_argnames=("num_dst",))
def _na_stage(src, dst, valid, th_s, th_d, h, num_dst):
    z = stages.segment_softmax_aggregate(src, dst, valid, th_s, th_d, h, num_dst)
    return jax.nn.elu(z.reshape(num_dst, -1))


@jax.jit
def _sf_stage(z_stack, w_g, b_g, q, w_out, b_out):
    n = z_stack.shape[1]
    valid = jnp.ones((n,), bool)
    w_list = [
        stages.local_semantic_fusion(z_stack[p], w_g, b_g, q, valid)
        for p in range(z_stack.shape[0])
    ]
    fused, _ = stages.global_semantic_fusion(jnp.stack(w_list), z_stack)
    return fused @ w_out + b_out


def han_forward_staged(params, data: HGNNData):
    """Traditional staged execution: each stage its own program with a host
    barrier after it (`block_until_ready`), mirroring DGL-on-GPU."""
    x = data.features[data.target_type]
    heads = params["a_src"].shape[1]
    h = _fp_stage(params["w_fp"], params["b_fp"], x)
    h.block_until_ready()
    hh = h.reshape(x.shape[0], heads, -1)
    z_list = []
    for i, batch in enumerate(data.graphs):
        th_s, th_d = _coeff_stage(hh, params["a_src"][i], params["a_dst"][i])
        th_s.block_until_ready()
        z = _na_stage(batch.src, batch.dst, batch.valid, th_s, th_d, hh, batch.num_dst)
        z.block_until_ready()
        z_list.append(z)
    out = _sf_stage(
        jnp.stack(z_list), params["w_g"], params["b_g"], params["q"],
        params["w_out"], params["b_out"],
    )
    out.block_until_ready()
    return out


HAN = HGNNModel(name="HAN", init=init_han, forward=han_forward)
