"""Fig. 2 — execution-time breakdown of FP / NA / SF per HGNN model.

Each stage group is timed as its own jitted program with host barriers
(the staged execution GPU frameworks exhibit), on synthetic Table-5
datasets scaled for CPU.  The paper's finding to reproduce: NA dominates
(71.5% avg on GPU), FP second, SF small.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stages
from repro.graphs import (
    build_semantic_graphs,
    dataset_metapaths,
    dataset_target,
    relation_semantic_graphs,
    synthetic_hetgraph,
    union_graph,
)
from repro.models.hgnn import MODELS, prepare_data

from .common import timeit


SCALE = 0.15
HEADS = {"HAN": 8, "R-GAT": 4, "S-HGN": 8}


def _stage_fns(name, model, params, data):
    """(fp_fn, na_fn, sf_fn) per model, mirroring its forward exactly."""
    feats = data.features
    if name == "HAN":
        heads = params["a_src"].shape[1]

        @jax.jit
        def fp():
            x = feats[data.target_type]
            h = stages.feature_projection(x, params["w_fp"], params["b_fp"])
            return h.reshape(x.shape[0], heads, -1)

        hh = fp()

        @jax.jit
        def na():
            outs = []
            for i, b in enumerate(data.graphs):
                th_s, th_d = stages.attention_coefficients(hh, params["a_src"][i], params["a_dst"][i])
                z = stages.segment_softmax_aggregate(
                    b.src, b.dst, b.valid, th_s, th_d, hh, b.num_dst
                )
                outs.append(jax.nn.elu(z.reshape(b.num_dst, -1)))
            return jnp.stack(outs)

        zs = na()

        @jax.jit
        def sf():
            valid = jnp.ones((zs.shape[1],), bool)
            w_p = jnp.stack([
                stages.local_semantic_fusion(zs[p], params["w_g"], params["b_g"], params["q"], valid)
                for p in range(zs.shape[0])
            ])
            fused, _ = stages.global_semantic_fusion(w_p, zs)
            return fused @ params["w_out"] + params["b_out"]

        return fp, na, sf

    if name == "R-GCN":
        lp = params["layers"][0]

        @jax.jit
        def fp():
            return [feats[b.src_type] @ lp["rel"][f"g{i}"] for i, b in enumerate(data.graphs)]

        hr = fp()

        @jax.jit
        def na():
            return [
                stages.segment_mean_aggregate(b.src, b.dst, b.valid, hr[i], b.num_dst)
                for i, b in enumerate(data.graphs)
            ]

        zs = na()

        @jax.jit
        def sf():
            out = {}
            for t in feats:
                s = feats[t] @ lp["self"][t]
                for i, b in enumerate(data.graphs):
                    if b.dst_type == t:
                        s = s + zs[i]
                out[t] = jax.nn.relu(s)
            return out

        return fp, na, sf

    heads = HEADS[name]
    if name == "R-GAT":  # relation-wise GAT
        lp = params["layers"][0]

        @jax.jit
        def fp():
            hs, hd = [], []
            for i, b in enumerate(data.graphs):
                rp = lp["rel"][f"g{i}"]
                hs.append((feats[b.src_type] @ rp["w_src"]).reshape(b.num_src, heads, -1))
                hd.append((feats[b.dst_type] @ rp["w_dst"]).reshape(b.num_dst, heads, -1))
            return hs, hd

        hs, hd = fp()

        @jax.jit
        def na():
            outs = []
            for i, b in enumerate(data.graphs):
                rp = lp["rel"][f"g{i}"]
                th_s, _ = stages.attention_coefficients(hs[i], rp["a_src"], rp["a_dst"])
                _, th_d = stages.attention_coefficients(hd[i], rp["a_src"], rp["a_dst"])
                z = stages.segment_softmax_aggregate(b.src, b.dst, b.valid, th_s, th_d, hs[i], b.num_dst)
                outs.append(z.reshape(b.num_dst, -1))
            return outs

        zs = na()

        @jax.jit
        def sf():
            out = {}
            for t in feats:
                zl = [zs[i] for i, b in enumerate(data.graphs) if b.dst_type == t]
                out[t] = jax.nn.elu(jnp.mean(jnp.stack(zl), 0)) if zl else feats[t]
            return out

        return fp, na, sf

    # S-HGN: the first hidden layer over the union graph (joint typed softmax)
    u = data.graphs[0]

    @jax.jit
    def fp():
        h = jnp.concatenate([feats[t] @ params[f"{t}.w_in"] + params[f"{t}.b_in"]
                             for t in u.path_types])
        return (h @ params["layer1.w"]).reshape(h.shape[0], heads, -1)

    hproj = fp()

    @jax.jit
    def na():
        th_s, th_d = stages.attention_coefficients(
            hproj, params["layer1.attn_src"], params["layer1.attn_dst"])
        r = (params["layer1.edge_emb"] @ params["layer1.w_edge"]).reshape(-1, heads, params["layer1.attn_edge"].shape[1])
        bias = jnp.einsum("thk,hk->th", r, params["layer1.attn_edge"])
        z = stages.segment_softmax_aggregate(
            u.src, u.dst, u.valid, th_s, th_d, hproj, u.num_dst,
            leaky_slope=0.05, edge_bias=bias[u.edge_type],
        )
        return z.reshape(u.num_dst, -1)

    z = na()

    @jax.jit
    def sf():
        return jax.nn.elu(z)

    return fp, na, sf


def run(report):
    for ds in ("imdb", "acm", "dblp"):
        g = synthetic_hetgraph(ds, scale=SCALE, feat_scale=0.25, seed=0)
        target, ncls = dataset_target(ds)
        mp = build_semantic_graphs(g, dataset_metapaths(ds), max_edges=60_000)
        rel = relation_semantic_graphs(g)
        views = {"HAN": mp, "S-HGN": [union_graph(g)]}
        for name in ("HAN", "R-GCN", "R-GAT", "S-HGN"):
            data = prepare_data(g, views.get(name, rel), target, ncls, with_blocks=False)
            model = MODELS[name]
            params = model.init(jax.random.key(0), data)
            fp, na, sf = _stage_fns(name, model, params, data)
            t_fp = timeit(fp, iters=3)
            t_na = timeit(na, iters=3)
            t_sf = timeit(sf, iters=3)
            tot = t_fp + t_na + t_sf
            report(
                f"breakdown/{ds}/{name}",
                tot,
                f"FP={t_fp/tot:.0%} NA={t_na/tot:.0%} SF={t_sf/tot:.0%}",
            )
