"""S-HGN / Simple-HGN (Lv et al., KDD'21, arXiv:2112.14936; the HGB
benchmark's ``myGAT`` / ``myGATConv``).

GAT over the union ("homogeneous") view of the HetG (``graphs.union_graph``):
one vertex table of every type, every relation's edges typed by their
relation, and a self-loop on every vertex as one more type.  Per head:

  h0_v  = W_in[type(v)] x_v + b_in[type(v)]           (scope ``fp``)
  g_v   = W^l h_v                                      (``fp``)
  s_uv  = LeakyReLU_0.05(a_dst.g_v + a_src.g_u + a_e.(W_r e_psi(u,v)))
  alpha_uv = softmax of s_uv over EVERY in-edge of v, of any type
  alpha~^l = (1 - beta) alpha^l + beta stopgrad(alpha~^(l-1))   (l > 1)
  z_v   = sum_u alpha~_uv g_u + res_v,  h_v = ELU(z_v)  (``na``)

with ``theta`` scoping the coefficients a.g and the per-type bias table
a_e.(W_r e_psi).  Layer 1 has no residual, later hidden layers add h
itself; the output layer (1 head of C) has no attention residual, adds
``W_res h`` and no activation, and the logits are L2-normalised
(``head``).  Published widths: hidden 64, 8 heads, edge embeddings 64,
beta 0.05, slope 0.05.  Departures: no dropout (HGB: 0.5 on features and
attention), glorot initialisation throughout, and edges as the HetG's
relations give them (HGB also symmetrises the adjacency).

The system runs each layer's NA as one typed multigraph launch over a
typed plan (``core.multilane.typed_na``): one unit per dst block whose
slots span every edge type, so the softmax is joint and exact.  Layer 2's
launch rebuilds layer 1's attention in-tile from per-vertex quantities.
The plain reference (:func:`shgn_reference`, the ``SEGMENT`` backend)
runs the same equations on the edge list.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import stages
from ...core.fusion import NABackend, _pad_rows
from ...core.multilane import MultiLanePlan, typed_na
from .common import HGNNData, HGNNModel, glorot, split_keys

BETA = 0.05   # attention residual
SLOPE = 0.05  # LeakyReLU negative slope
LANES = 128   # the output layer's C is padded to a whole lane tile for the kernel
# The model computes in float32: its matmuls run at HIGHEST precision, as
# the typed NA kernel's do (on a TPU, DEFAULT is one bfloat16 pass, and
# three layers compound it).
PRECISION = jax.lax.Precision.HIGHEST


def _hidden(params) -> list[str]:
    names, i = [], 1
    while f"layer{i}.w" in params:
        names.append(f"layer{i}")
        i += 1
    return names


def init_shgn(
    rng: jax.Array,
    data: HGNNData,
    *,
    hidden: int = 64,
    heads: int = 8,
    layers: int = 2,
    edge_dim: int = 64,
) -> dict:
    """Flat params: ``<type>.w_in``/``<type>.b_in``, then per layer
    (``layer1`` .. ``layer<layers>``, ``out``) ``w``, ``attn_src``,
    ``attn_dst``, ``edge_emb``, ``w_edge``, ``attn_edge``, and ``out.w_res``;
    keys are drawn in that order."""
    union = data.graphs[0]
    n_types = len(union.edge_type_names)
    dims = data.feature_dims
    ncls = data.num_classes
    keys = iter(split_keys(rng, len(union.path_types) + 6 * (layers + 1) + 1))
    params = {}
    for t in union.path_types:
        params[f"{t}.w_in"] = glorot(next(keys), (dims[t], hidden))
        params[f"{t}.b_in"] = jnp.zeros((hidden,))
    shapes = [(f"layer{i + 1}", hidden if i == 0 else heads * hidden, heads, hidden)
              for i in range(layers)] + [("out", heads * hidden, 1, ncls)]
    for name, d_in, h, dh in shapes:
        params[f"{name}.w"] = glorot(next(keys), (d_in, h * dh))
        params[f"{name}.attn_src"] = glorot(next(keys), (h, dh))
        params[f"{name}.attn_dst"] = glorot(next(keys), (h, dh))
        params[f"{name}.edge_emb"] = glorot(next(keys), (n_types, edge_dim))
        params[f"{name}.w_edge"] = glorot(next(keys), (edge_dim, h * edge_dim))
        params[f"{name}.attn_edge"] = glorot(next(keys), (h, edge_dim))
    params["out.w_res"] = glorot(next(keys), (heads * hidden, ncls))
    return params


def _input(params, data: HGNNData) -> jnp.ndarray:
    """h0 [N, hidden] over the union's vertex table (scope ``fp``)."""
    with jax.named_scope("fp"):
        return jnp.concatenate([
            jnp.matmul(data.features[t], params[f"{t}.w_in"], precision=PRECISION)
            + params[f"{t}.b_in"]
            for t in data.graphs[0].path_types
        ])


def _layer(params, name: str, h):
    """g [N, H, Dh] (scope ``fp``), theta_src / theta_dst [N, H] and the
    per-type bias table [T, H] (scope ``theta``)."""
    heads = params[f"{name}.attn_src"].shape[0]
    with jax.named_scope("fp"):
        g = jnp.matmul(h, params[f"{name}.w"], precision=PRECISION).reshape(h.shape[0], heads, -1)
    with jax.named_scope("theta"):
        th_s = jnp.einsum("nhd,hd->nh", g, params[f"{name}.attn_src"], precision=PRECISION)
        th_d = jnp.einsum("nhd,hd->nh", g, params[f"{name}.attn_dst"], precision=PRECISION)
        r = jnp.matmul(params[f"{name}.edge_emb"], params[f"{name}.w_edge"], precision=PRECISION)
        bias = jnp.einsum("thk,hk->th", r.reshape(r.shape[0], heads, -1),
                          params[f"{name}.attn_edge"], precision=PRECISION)
    return g, th_s, th_d, bias


def _head(z, data: HGNNData):
    """The target type's rows, L2-normalised (scope ``head``)."""
    with jax.named_scope("head"):
        off = 0
        for t in data.graphs[0].path_types:
            if t == data.target_type:
                break
            off += data.features[t].shape[0]
        logits = z[off : off + data.features[data.target_type].shape[0]]
        return logits / jnp.maximum(jnp.linalg.norm(logits, axis=-1, keepdims=True), 1e-12)


def shgn_forward_plan(
    params,
    data: HGNNData,
    plan: MultiLanePlan,
    *,
    backend: str = "kernel",
    beta: float = BETA,
):
    """S-HGN logits [N_target, C] with every layer's NA one typed
    multigraph launch over ``plan`` (the union graph's typed plan, one
    lane), forward and backward."""
    hidden = _hidden(params)
    if len(hidden) > 2:
        raise ValueError("the kernel rebuilds one previous layer's attention: at most 2 hidden layers")
    n = data.graphs[0].num_dst
    n_pad = plan.n_dst_blocks * plan.block
    pad = lambda x: _pad_rows(x, n_pad)

    def na(g, th_s, th_d, bias, attn_prev=None):
        return typed_na(plan, pad(th_s)[None], pad(th_d)[None], pad(g), bias,
                        attn_prev=attn_prev, beta=None if attn_prev is None else beta,
                        leaky_slope=SLOPE, backend=backend)

    h = _input(params, data)
    attn = None
    for i, name in enumerate(hidden):
        g, th_s, th_d, bias = _layer(params, name, h)
        with jax.named_scope("na"):
            z, this = na(g, th_s, th_d, bias, attn)
            z = z[:n].reshape(n, -1)
            if i:
                z = z + h
            h = jax.nn.elu(z)
        attn = this
    g, th_s, th_d, bias = _layer(params, "out", h)  # g [N, 1, C]
    with jax.named_scope("na"):
        c = g.shape[-1]
        g = jnp.pad(g, ((0, 0), (0, 0), (0, -c % LANES)))
        z, _ = na(g, th_s, th_d, bias)
        z = z[:n, 0, :c] + jnp.matmul(h, params["out.w_res"], precision=PRECISION)
    return _head(z, data)


def shgn_reference(params, data: HGNNData, *, beta: float = BETA):
    """The plain reference: the equations on the union's edge list in
    jax.numpy, matmuls at ``highest`` precision, the joint softmax by
    ``stages.segment_softmax_aggregate`` with a per-edge bias.  The
    attention residual is applied to the aggregates, which is the same
    sum: (1 - beta) sum alpha g + beta sum alpha_prev g."""
    union = data.graphs[0]
    n = union.num_dst

    def na(th_s, th_d, bias, g):
        return stages.segment_softmax_aggregate(
            union.src, union.dst, union.valid, th_s, th_d, g, n,
            leaky_slope=SLOPE, edge_bias=bias[union.edge_type],
        )

    with jax.default_matmul_precision("highest"):
        h = _input(params, data)
        prev = None
        for i, name in enumerate(_hidden(params)):
            g, th_s, th_d, bias = _layer(params, name, h)
            z = na(th_s, th_d, bias, g)
            if prev is not None:
                z = (1 - beta) * z + beta * na(*prev, g)
            z = z.reshape(n, -1)
            h = jax.nn.elu(z + h if i else z)
            prev = jax.lax.stop_gradient((th_s, th_d, bias))
        g, th_s, th_d, bias = _layer(params, "out", h)
        z = na(th_s, th_d, bias, g)[:, 0] + jnp.matmul(h, params["out.w_res"], precision=PRECISION)
        return _head(z, data)


def shgn_forward(params, data: HGNNData, *, backend: NABackend = NABackend.SEGMENT):
    """The model's forward without a plan: the plain reference
    (``SEGMENT``).  The kernel path takes the union graph's typed plan:
    :func:`shgn_forward_plan`."""
    if backend is not NABackend.SEGMENT:
        raise ValueError(f"S-HGN's kernel path is shgn_forward_plan, not backend={backend}")
    return shgn_reference(params, data)


SHGN = HGNNModel(name="S-HGN", init=init_shgn, forward=shgn_forward)
