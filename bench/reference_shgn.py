"""Plain S-HGN reference: the layer equations on the union edge list.

No kernels, blocks, plans or tiles, and nothing imported from the
program.  The union graph is built here from ``bench/graphgen.py``;
weights are drawn from the seed the way ``models/hgnn/shgn.py:init_shgn``
draws them.  Every matmul runs at ``highest`` precision in the stated
dtype; the control runs the same code in bfloat16.

Semantics (Simple-HGN, Lv et al. 2021; HGB myGAT), per head:
  h0 = W_in[type] x + b_in[type], over one table of every vertex type
  per layer l: g = W^l h;  s_uv = LeakyReLU(a_dst.g_v + a_src.g_u + a_e.(W_r e_type(u,v)))
    alpha = softmax of s over every in-edge of v (self-loop and all types)
    hidden layers after the first: alpha~ = (1 - beta) alpha + beta stopgrad(alpha~ of l-1)
    z_v = sum_u alpha~_uv g_u (+ h_v after the first layer), h = ELU(z)
  output layer (1 head of C): z = sum_u alpha_uv g_u + W_res h; logits = z / max(|z|_2, 1e-12)
The attention is computed per edge and mixed per edge, as written.
Departures, as in the program: no dropout, glorot initialisation.

Planted faults for the calibration: ``per_type`` softmaxes over each
(dst, edge type) group and sums the groups (a per-relation softmax in
place of the joint one); ``beta = 0`` leaves the residual out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import PRECISION, adamw, cast, glorot, leaf_norms

SELF_LOOP = "self"


def union_edges(g, spec: dict):
    """(types, offsets, n, src, dst, edge type, type names) of the union
    view: vertex types in the spec's order, relations in the spec's order
    as types 0.., a self-loop on every vertex as the last type; a
    same-type (v, v) relation pair is dropped for its self-loop."""
    types = list(spec["vertices"])
    offsets, n = {}, 0
    for t in types:
        offsets[t] = n
        n += g.counts[t]
    src, dst, et = [], [], []
    for i, name in enumerate(spec["relations"]):
        st, dt, s, d = g.relations[name]
        keep = (s != d) | (st != dt)
        src.append(s[keep] + offsets[st])
        dst.append(d[keep] + offsets[dt])
        et.append(np.full(int(keep.sum()), i, np.int32))
    loops = np.arange(n, dtype=np.int32)
    names = list(spec["relations"]) + [SELF_LOOP]
    return (types, offsets, n, np.concatenate(src + [loops]).astype(np.int32),
            np.concatenate(dst + [loops]).astype(np.int32),
            np.concatenate(et + [np.full(n, len(names) - 1, np.int32)]), names)


def init_shgn(seed: int, dims: dict[str, int], n_types: int, *, hidden: int, heads: int,
              layers: int, edge_dim: int, n_classes: int) -> dict:
    """The program's weights for ``seed``; ``dims`` in vertex-table order."""
    keys = iter(jax.random.split(jax.random.key(seed), len(dims) + 6 * (layers + 1) + 1))
    p = {}
    for t, d in dims.items():
        p[f"{t}.w_in"] = glorot(next(keys), (d, hidden))
        p[f"{t}.b_in"] = jnp.zeros((hidden,))
    shapes = [(f"layer{i + 1}", hidden if i == 0 else heads * hidden, heads, hidden)
              for i in range(layers)] + [("out", heads * hidden, 1, n_classes)]
    for name, d_in, h, dh in shapes:
        p[f"{name}.w"] = glorot(next(keys), (d_in, h * dh))
        p[f"{name}.attn_src"] = glorot(next(keys), (h, dh))
        p[f"{name}.attn_dst"] = glorot(next(keys), (h, dh))
        p[f"{name}.edge_emb"] = glorot(next(keys), (n_types, edge_dim))
        p[f"{name}.w_edge"] = glorot(next(keys), (edge_dim, h * edge_dim))
        p[f"{name}.attn_edge"] = glorot(next(keys), (h, edge_dim))
    p["out.w_res"] = glorot(next(keys), (heads * hidden, n_classes))
    return p


def attention(p, name, g, src, dst, et, n: int, n_types: int, slope: float, per_type: bool):
    """Per-edge attention [E, H] of layer ``name`` over projected g [N, H, Dh]."""
    heads = g.shape[1]
    th_s = jnp.einsum("nhd,hd->nh", g, p[f"{name}.attn_src"])
    th_d = jnp.einsum("nhd,hd->nh", g, p[f"{name}.attn_dst"])
    r = (p[f"{name}.edge_emb"] @ p[f"{name}.w_edge"]).reshape(n_types, heads, -1)
    bias = jnp.einsum("thk,hk->th", r, p[f"{name}.attn_edge"])
    s = jax.nn.leaky_relu(th_d[dst] + th_s[src] + bias[et], slope)
    seg, n_seg = (dst * n_types + et, n * n_types) if per_type else (dst, n)
    m = jax.ops.segment_max(s, seg, num_segments=n_seg)
    e = jnp.exp(s - m[seg])
    den = jax.ops.segment_sum(e, seg, num_segments=n_seg)
    return e / den[seg]


@functools.partial(jax.checkpoint, static_argnums=(4,))
def aggregate(alpha, g, src, dst, n: int):
    """sum_u alpha_uv g_u -> [N, H, Dh]; recomputed in the backward, so the
    [E, H, Dh] gather is never kept."""
    return jax.ops.segment_sum(alpha[:, :, None] * g[src], dst, num_segments=n)


def shgn_logits(p, feats, graph, cfg: dict, *, beta: float, per_type: bool = False):
    """Logits [N_target, C]: ``feats`` per type, ``graph`` from ``union_edges``."""
    types, offsets, n, src, dst, et, names = graph
    n_types, slope, heads = len(names), cfg["leaky_slope"], cfg["heads"]
    h = jnp.concatenate([feats[t] @ p[f"{t}.w_in"] + p[f"{t}.b_in"] for t in types])
    prev = None
    for i in range(cfg["layers"]):
        name = f"layer{i + 1}"
        g = (h @ p[f"{name}.w"]).reshape(n, heads, -1)
        alpha = attention(p, name, g, src, dst, et, n, n_types, slope, per_type)
        if prev is not None:
            alpha = (1 - beta) * alpha + beta * jax.lax.stop_gradient(prev)
        z = aggregate(alpha, g, src, dst, n).reshape(n, -1)
        h = jax.nn.elu(z + h if i else z)
        prev = alpha
    g = (h @ p["out.w"]).reshape(n, 1, -1)
    alpha = attention(p, "out", g, src, dst, et, n, n_types, slope, per_type)
    z = aggregate(alpha, g, src, dst, n)[:, 0] + h @ p["out.w_res"]
    target = cfg["graph"]["target"]
    z = z[offsets[target] : offsets[target] + feats[target].shape[0]]
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-12)


def _masked_loss(p, feats, graph, labels, rows, cfg, beta, per_type):
    lp = jax.nn.log_softmax(shgn_logits(p, feats, graph, cfg, beta=beta, per_type=per_type), axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(rows, nll, 0)) / jnp.sum(rows).astype(nll.dtype)


def train_readings(params, feats, graph, labels, cfg: dict, *, steps: int, dtype=jnp.float32,
                   keep_rows=None, beta: float | None = None, per_type: bool = False) -> dict:
    """Run ``steps`` full-batch steps from ``params``; returns each step's
    loss, the per-leaf norms of the first clipped gradient, and of the
    parameters' change after the last step.  ``keep_rows``, ``beta`` and
    ``per_type`` plant the calibration's faults."""
    beta = cfg["beta"] if beta is None else beta
    types, offsets, n, src, dst, et, names = graph
    graph = (types, offsets, n, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(et), names)
    loss_and_grad = jax.jit(
        jax.value_and_grad(lambda p, f, l, r: _masked_loss(p, f, graph, l, r, cfg, beta, per_type)))
    with jax.default_matmul_precision(PRECISION):
        p = cast(params, dtype)
        ff = cast(feats, dtype)
        rows = jnp.ones(labels.shape, bool) if keep_rows is None else keep_rows
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        p0 = p
        losses, first = [], None
        for k in range(1, steps + 1):
            loss, grads = loss_and_grad(p, ff, labels, rows)
            g, p, m, v = adamw(p, grads, m, v, k, cfg["optimizer"])
            losses.append(float(loss))
            if first is None:
                first = leaf_norms(g)
        delta = leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))
    return {"losses": losses, "grad": first, "delta": delta}
