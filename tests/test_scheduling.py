"""Scheduling properties: Hamilton path optimality, workload balance."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.scheduling import (
    brute_force_hamilton_path,
    lane_assignment,
    naive_lane_assignment,
    shortest_hamilton_path,
    similarity_matrix,
)
from repro.graphs import build_semantic_graphs, dataset_metapaths, synthetic_hetgraph


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 6), st.integers(0, 10_000))
def test_held_karp_equals_brute_force(n, seed):
    rng = np.random.default_rng(seed)
    w = rng.random((n, n))
    w = (w + w.T) / 2
    np.fill_diagonal(w, 0)
    order_hk, cost_hk = shortest_hamilton_path(w)
    _, cost_bf = brute_force_hamilton_path(w)
    assert sorted(order_hk) == list(range(n))  # visits every vertex once
    assert abs(cost_hk - cost_bf) < 1e-9
    # reported cost is consistent with the path itself
    path_cost = sum(w[order_hk[i], order_hk[i + 1]] for i in range(n - 1))
    assert abs(path_cost - cost_hk) < 1e-9


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_lane_assignment_balances(data):
    n_graphs = data.draw(st.integers(1, 5))
    num_lanes = data.draw(st.integers(1, 8))
    rng = np.random.default_rng(data.draw(st.integers(0, 999)))
    row_costs = [
        rng.integers(0, 100, size=rng.integers(1, 20)).astype(float)
        for _ in range(n_graphs)
    ]
    plan = lane_assignment(row_costs, num_lanes)
    naive = naive_lane_assignment(row_costs, num_lanes)
    # every unit assigned to exactly one lane
    assert (plan.unit_lane >= 0).all() and (plan.unit_lane < num_lanes).all()
    assert plan.unit_cost.sum() == naive.unit_cost.sum()
    # balanced assignment never worse than naive (max lane load)
    assert plan.lane_load.max() <= naive.lane_load.max() + 1e-9
    # no lane exceeds threshold by more than the largest single unit
    total = plan.unit_cost.sum()
    thresh = np.ceil(total / num_lanes)
    biggest = plan.unit_cost.max() if plan.unit_cost.size else 0
    assert plan.lane_load.max() <= thresh + biggest + 1e-9


def _loop_lane_assignment(row_costs, num_lanes, threshold=None):
    """The lane assignment as a plain loop over numpy arrays: the
    reference the host-float implementation must match bit for bit."""
    units = [(g, r, float(c)) for g, rc in enumerate(row_costs) for r, c in enumerate(rc)]
    unit_graph = np.asarray([u[0] for u in units], np.int32)
    unit_cost = np.asarray([u[2] for u in units])
    if threshold is None:
        threshold = float(np.ceil(unit_cost.sum() / max(num_lanes, 1)))
    lane_load = np.zeros(num_lanes)
    unit_lane = np.full(len(units), -1, np.int32)
    overflow = []
    for u in range(len(units)):
        home = int(unit_graph[u]) % num_lanes
        if lane_load[home] + unit_cost[u] <= threshold:
            unit_lane[u] = home
            lane_load[home] += unit_cost[u]
        else:
            overflow.append(u)
    for u in sorted(overflow, key=lambda i: -unit_cost[i]):
        l = int(np.argmin(lane_load))
        unit_lane[u] = l
        lane_load[l] += unit_cost[u]
    unit_row = np.asarray([u[1] for u in units], np.int32)
    return unit_graph, unit_row, unit_cost, unit_lane, lane_load


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_lane_assignment_matches_loop_reference(data):
    """Every field of the LanePlan equals the plain loop's, bytes and
    dtype, over empty graphs, ties and given thresholds."""
    num_lanes = data.draw(st.integers(1, 8))
    rng = np.random.default_rng(data.draw(st.integers(0, 9_999)))
    row_costs = [
        rng.integers(0, data.draw(st.sampled_from([2, 5, 1000])), size=rng.integers(0, 30))
        for _ in range(data.draw(st.integers(0, 5)))
    ]
    threshold = data.draw(st.sampled_from([None, 0.0, 7.0, 500.0]))
    plan = lane_assignment(row_costs, num_lanes, threshold=threshold)
    fields = (plan.unit_graph, plan.unit_row, plan.unit_cost, plan.unit_lane, plan.lane_load)
    for got, want in zip(fields, _loop_lane_assignment(row_costs, num_lanes, threshold)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


def test_similarity_matrix_paper_formula():
    g = synthetic_hetgraph("acm", scale=0.05, feat_scale=0.1)
    sgs = build_semantic_graphs(g, dataset_metapaths("acm"), max_edges=2000)
    w = similarity_matrix(sgs, g.vertex_counts)
    assert w.shape == (4, 4)
    assert np.allclose(w, w.T) and np.allclose(np.diag(w), 0)
    assert (w >= 0).all() and (w <= 1).all()
    # PAP vs PPAP share {paper, author}; PAP vs PSP share only {paper}
    i_pap = [s.name for s in sgs].index("PAP")
    i_ppap = [s.name for s in sgs].index("PPAP")
    i_psp = [s.name for s in sgs].index("PSP")
    assert w[i_pap, i_ppap] < w[i_pap, i_psp]  # more shared types => lower weight
