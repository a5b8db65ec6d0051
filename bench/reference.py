"""Plain HAN reference: edge-list segment softmax in jax.numpy.

No kernels, no blocks, no lanes, no cache, and nothing imported from the
program.  Weights are drawn from the seed the way the program's
initialisers draw them (``models/hgnn/han.py:init_han``,
``serve/hgnn_engine.py:HGNNEngine._init_params``); graphs come from
``bench/graphgen.py``.  Every matmul runs at ``highest`` precision in the
stated dtype; the control runs the same code in bfloat16.

Semantics (HAN, Wang et al. 2019, one layer):
  h = x W_fp + b_fp, split into H heads of Dh
  per metapath graph P: theta_s = <h_u, a_src^P>, theta_d = <h_v, a_dst^P>,
    z_v^P = ELU(sum_{u -> v} softmax_u(LeakyReLU(theta_d[v] + theta_s[u])) h_u)
    w^P = mean_v q . tanh(W_g z_v^P + b_g)
  beta = softmax_P(w^P), fused = sum_P beta^P z^P, logits = fused W_out + b_out
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"


def glorot(key, shape):
    lim = float(np.sqrt(6.0 / (shape[0] + shape[-1])))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_han(seed: int, d_in: int, n_graphs: int, heads: int, hidden: int,
             att_dim: int, n_classes: int) -> dict:
    keys = list(jax.random.split(jax.random.key(seed), 5 + 2 * n_graphs))
    f = heads * hidden
    return {
        "w_fp": glorot(keys[0], (d_in, f)),
        "b_fp": jnp.zeros((f,)),
        "a_src": jnp.stack([glorot(keys[5 + 2 * i], (heads, hidden)) for i in range(n_graphs)]),
        "a_dst": jnp.stack([glorot(keys[6 + 2 * i], (heads, hidden)) for i in range(n_graphs)]),
        "w_g": glorot(keys[1], (f, att_dim)),
        "b_g": jnp.zeros((att_dim,)),
        "q": glorot(keys[2], (att_dim, 1))[:, 0],
        "w_out": glorot(keys[3], (f, n_classes)),
        "b_out": jnp.zeros((n_classes,)),
    }


def init_engine(seed: int, feature_dims: dict[str, int], heads: int, hidden: int,
                att_dim: int) -> dict:
    """The serving engine's shared weights for ``seed``."""
    keys = jax.random.split(jax.random.key(seed), 3 + len(feature_dims))
    f = heads * hidden
    return {
        "w_fp": {t: glorot(keys[3 + i], (feature_dims[t], f))
                 for i, t in enumerate(sorted(feature_dims))},
        "b_fp": {t: jnp.zeros((f,)) for t in feature_dims},
        "w_g": glorot(keys[0], (f, att_dim)),
        "b_g": jnp.zeros((att_dim,)),
        "q": glorot(keys[1], (att_dim, 1))[:, 0],
    }


def init_metapath(seed: int, mp_seed: int, heads: int, hidden: int):
    """The serving engine's attention vectors of one metapath."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.key(seed + 1), mp_seed))
    return glorot(k1, (heads, hidden)), glorot(k2, (heads, hidden))


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def attend(src, dst, th_s, th_d, h, n: int, slope: float):
    """GAT attention over an edge list: [E] ids, [N, H] coefficients,
    [N, H, Dh] features -> [N, H, Dh]; a vertex with no in-edge gets 0."""
    logits = jax.nn.leaky_relu(th_d[dst] + th_s[src], slope)
    m = jax.ops.segment_max(logits, dst, num_segments=n)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m[dst])
    den = jax.ops.segment_sum(p, dst, num_segments=n)
    num = jax.ops.segment_sum(p[:, :, None] * h[src], dst, num_segments=n)
    return num / jnp.maximum(den, 1e-9).astype(p.dtype)[:, :, None]


def semantic(z, w_g, b_g, q):
    return jnp.mean(jnp.tanh(z @ w_g + b_g) @ q)


def metapath_z(h, a_src, a_dst, src, dst, heads: int, slope: float, w_g, b_g, q):
    """One metapath's (z [N, H*Dh], w^P) from projected features h [N, H*Dh]."""
    n = h.shape[0]
    hh = h.reshape(n, heads, -1)
    th_s = jnp.einsum("nhd,hd->nh", hh, a_src)
    th_d = jnp.einsum("nhd,hd->nh", hh, a_dst)
    z = jax.nn.elu(attend(src, dst, th_s, th_d, hh, n, slope).reshape(n, -1))
    return z, semantic(z, w_g, b_g, q)


def fuse(zs, ws):
    beta = jax.nn.softmax(jnp.stack(ws))
    return jnp.einsum("p,pnd->nd", beta, jnp.stack(zs))


def han_logits(params, x, graphs, heads: int, slope: float):
    h = x @ params["w_fp"] + params["b_fp"]
    zs, ws = [], []
    for i, (src, dst) in enumerate(graphs):
        z, w = metapath_z(h, params["a_src"][i], params["a_dst"][i], src, dst, heads,
                          slope, params["w_g"], params["b_g"], params["q"])
        zs.append(z)
        ws.append(w)
    return fuse(zs, ws) @ params["w_out"] + params["b_out"]


def adamw(params, grads, m, v, count, opt):
    """One AdamW step with global-norm clipping; returns the clipped
    gradient the moments took, and the new params and moments."""
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9)).astype(dtype)
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    c1 = 1.0 - opt["b1"] ** count
    c2 = 1.0 - opt["b2"] ** count
    m = jax.tree_util.tree_map(lambda a, b: opt["b1"] * a + (1 - opt["b1"]) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: opt["b2"] * a + (1 - opt["b2"]) * b * b, v, g)

    def upd(p, mm, vv):
        step = (mm / c1) / (jnp.sqrt(vv / c2) + opt["eps"]) + opt["weight_decay"] * p
        return (p - opt["lr"] * step).astype(p.dtype)

    return g, jax.tree_util.tree_map(upd, params, m, v), m, v


def leaf_norms(tree) -> dict[str, float]:
    return {k: float(jnp.linalg.norm(v.astype(jnp.float32).ravel())) for k, v in tree.items()}


def train_readings(params, x, graphs, labels, *, heads: int, slope: float, opt: dict,
                   steps: int, dtype=jnp.float32, keep_rows=None) -> dict:
    """Run ``steps`` full-batch steps from ``params``; returns each step's
    loss, the per-leaf norms of the first clipped gradient, and of the
    parameters' change after the last step.  ``keep_rows`` restricts the
    loss to those target rows (a planted fault: part of the batch left out)."""
    with jax.default_matmul_precision(PRECISION):
        p = cast(params, dtype)
        xx = x.astype(dtype)
        rows = jnp.ones(labels.shape, bool) if keep_rows is None else keep_rows
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        p0 = p
        losses, first = [], None
        for k in range(1, steps + 1):
            loss, grads = _loss_and_grad(p, xx, graphs, labels, rows, heads=heads, slope=slope)
            g, p, m, v = adamw(p, grads, m, v, k, opt)
            losses.append(float(loss))
            if first is None:
                first = leaf_norms(g)
        delta = leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))
    return {"losses": losses, "grad": first, "delta": delta}


def _masked_loss(params, x, graphs, labels, rows, *, heads, slope):
    lp = jax.nn.log_softmax(han_logits(params, x, graphs, heads, slope), axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(rows, nll, 0)) / jnp.sum(rows).astype(nll.dtype)


_loss_and_grad = jax.jit(jax.value_and_grad(_masked_loss), static_argnames=("heads", "slope"))


@functools.partial(jax.jit, static_argnames=("heads", "slope"))
def _serve_metapath(x, w_fp, b_fp, a_src, a_dst, src, dst, w_g, b_g, q, *, heads, slope):
    h = x @ w_fp + b_fp
    return metapath_z(h, a_src, a_dst, src, dst, heads, slope, w_g, b_g, q)


def serve_metapath(x, w_fp, b_fp, a_src, a_dst, src, dst, w_g, b_g, q, *, heads: int,
                   slope: float, dtype=jnp.float32):
    """(z, w^P) of one metapath over target features ``x``."""
    args = cast((x, w_fp, b_fp, a_src, a_dst, w_g, b_g, q), dtype)
    x, w_fp, b_fp, a_src, a_dst, w_g, b_g, q = args
    with jax.default_matmul_precision(PRECISION):
        return _serve_metapath(x, w_fp, b_fp, a_src, a_dst, src, dst, w_g, b_g, q,
                               heads=heads, slope=slope)


@jax.jit
def serve_fuse(zs, ws):
    with jax.default_matmul_precision(PRECISION):
        return fuse(list(zs), list(ws)).astype(jnp.float32)
