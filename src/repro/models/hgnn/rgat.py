"""R-GAT — relational GAT (Wang et al., ACL'20).

Table 2 semantics: relation-specific FP h^r = W^r x, GAT attention NA per
relation graph, SF h_v = (1/|P|) mean over relations of z^P_v.  Source and
destination endpoints are projected with relation-specific weights (they
may have different raw dims at layer 0), and the GAT logits use the
decomposed theta_src/theta_dst form that the RAB reuses per vertex.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import stages
from ...core.fusion import NABackend, _pad_rows, neighbor_aggregate
from ...core.multilane import build_multilane_plan, multilane_na
from ...dist.sharding import shard
from .common import HGNNData, HGNNModel, glorot, split_keys


def init_rgat(
    rng: jax.Array,
    data: HGNNData,
    *,
    hidden: int = 64,
    heads: int = 4,
    layers: int = 3,
) -> dict:
    dims = data.feature_dims
    keys = iter(split_keys(rng, 2 + layers * (4 * len(data.graphs) + len(dims))))
    layer_params = []
    for layer in range(layers):
        rel = {}
        for i, g in enumerate(data.graphs):
            d_src = dims[g.src_type] if layer == 0 else heads * hidden
            d_dst = dims[g.dst_type] if layer == 0 else heads * hidden
            rel[f"g{i}"] = {
                "w_src": glorot(next(keys), (d_src, heads * hidden)),
                "w_dst": glorot(next(keys), (d_dst, heads * hidden)),
                "a_src": glorot(next(keys), (heads, hidden)),
                "a_dst": glorot(next(keys), (heads, hidden)),
            }
        self_w = {}
        for t, d in dims.items():
            d_t = d if layer == 0 else heads * hidden
            self_w[t] = glorot(next(keys), (d_t, heads * hidden))
        layer_params.append({"rel": rel, "self": self_w})
    return {
        "layers": layer_params,
        "w_out": glorot(next(keys), (heads * hidden, data.num_classes)),
        "b_out": jnp.zeros((data.num_classes,)),
    }


def _relation_na(batch, th_s, th_d, hs, backend: NABackend | str):
    """One relation's NA: SEGMENT and BLOCK per graph, a kernel over the
    relation's one-lane plan.  Source and destination types may differ,
    so each side is padded to its own block rows."""
    if backend in (NABackend.SEGMENT, NABackend.BLOCK):
        return neighbor_aggregate(batch, th_s, th_d, hs, backend=backend)
    ns_pad = -(-batch.num_src // batch.block) * batch.block
    z = multilane_na(
        build_multilane_plan([batch], 1), _pad_rows(th_s, ns_pad)[None],
        _pad_rows(th_d, batch.num_dst_pad)[None], _pad_rows(hs, ns_pad), backend=backend,
    )
    return z[0, : batch.num_dst]


def rgat_forward(params, data: HGNNData, *, backend: NABackend | str = NABackend.SEGMENT):
    h = dict(data.features)
    heads = params["layers"][0]["rel"]["g0"]["a_src"].shape[0]
    for lp in params["layers"]:
        agg: dict[str, list[jnp.ndarray]] = {}
        for i, batch in enumerate(data.graphs):
            rp = lp["rel"][f"g{i}"]
            # FP (relation-specific) fused with coefficient computation
            hs = shard(h[batch.src_type] @ rp["w_src"], "act_vertex", "act_feat")
            hs = hs.reshape(batch.num_src, heads, -1)
            hd = shard(h[batch.dst_type] @ rp["w_dst"], "act_vertex", "act_feat")
            hd = hd.reshape(batch.num_dst, heads, -1)
            th_s, _ = stages.attention_coefficients(hs, rp["a_src"], rp["a_dst"])
            _, th_d = stages.attention_coefficients(hd, rp["a_src"], rp["a_dst"])
            z = _relation_na(batch, th_s, th_d, hs, backend)
            agg.setdefault(batch.dst_type, []).append(z.reshape(batch.num_dst, -1))
        h_new = {}
        for t in h:
            if t in agg:
                s = jnp.mean(jnp.stack(agg[t]), axis=0)  # SF: mean over relations
            else:
                s = h[t] @ lp["self"][t]
            h_new[t] = shard(jax.nn.elu(s), "act_vertex", "act_feat")
        h = h_new
    return h[data.target_type] @ params["w_out"] + params["b_out"]


RGAT = HGNNModel(name="R-GAT", init=init_rgat, forward=rgat_forward)
