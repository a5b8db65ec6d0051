"""Quickstart: the HiHGNN pipeline end to end on synthetic DBLP.

    PYTHONPATH=src python examples/quickstart.py

Builds semantic graphs from metapaths (SGB), orders them by the shortest
Hamilton path over the similarity graph, balances block-row workloads
across lanes, and runs the fused HAN layer — every HiHGNN mechanism in
~60 lines.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    NABackend,
    batch_semantic_graph,
    count_reuse,
    similarity_schedule,
)
from repro.core.multilane import build_multilane_plan, multilane_na
from repro.graphs import build_semantic_graphs, dataset_metapaths, dataset_target, synthetic_hetgraph, synthetic_labels
from repro.models.hgnn import MODELS, prepare_data


def main():
    # 1. Semantic Graph Build (host preprocessing, like the paper)
    g = synthetic_hetgraph("dblp", scale=0.1, feat_scale=0.1, seed=0)
    sgs = build_semantic_graphs(g, dataset_metapaths("dblp"), max_edges=100_000)
    print("semantic graphs:", [(s.name, s.num_edges) for s in sgs])

    # 2. Similarity-aware execution scheduling (shortest Hamilton path)
    order, w = similarity_schedule(sgs, g.vertex_counts)
    print("execution order:", [sgs[i].name for i in order])

    # 3. RAB-style reuse accounting
    c = count_reuse(sgs, g.vertex_counts)
    print(f"FP work saved by dedup: {c.fp_saved:.0%}; theta work saved: {c.theta_saved:.0%}")

    # 4. Workload-aware lane balancing (independency-aware parallelism)
    batches = [batch_semantic_graph(s, block=32) for s in sgs]
    plan = build_multilane_plan(batches, num_lanes=4)
    print("lane loads (live slots):", plan.lane_plan.lane_load.astype(int).tolist(),
          f"imbalance={plan.lane_plan.imbalance():.2f}")

    # 5. Fused HAN forward + a few training steps
    target, ncls = dataset_target("dblp")
    labels = synthetic_labels(g, "dblp")
    data = prepare_data(g, [sgs[i] for i in order], target, ncls, labels, block=32)
    model = MODELS["HAN"]
    params = model.init(jax.random.key(0), data)

    from repro.models.hgnn import cross_entropy

    @jax.jit
    def step(p):
        loss, grads = jax.value_and_grad(
            lambda p_: cross_entropy(model.forward(p_, data, backend=NABackend.SEGMENT), data.labels)
        )(p)
        return jax.tree_util.tree_map(lambda a, g_: a - 0.05 * g_, p, grads), loss

    for i in range(10):
        params, loss = step(params)
        if i % 3 == 0:
            print(f"step {i}: loss {float(loss):.4f}")
    print("done — fused HGNN pipeline runs end to end.")


if __name__ == "__main__":
    main()
