"""The benchmark's own copy of the synthetic Table-5 graph generator.

A copy of ``src/repro/graphs/datasets.py`` (``_rand_edges``,
``synthetic_hetgraph``, ``synthetic_labels``) and of the metapath
composition in ``src/repro/graphs/sgb.py``, as plain numpy over a
configuration's ``graph`` entry.  The reference (``bench/reference.py``)
builds its inputs from here, so it takes no graph the program has made;
``bench/tests/test_bench_work.py`` checks that both builders give the
same edges.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np


@dataclasses.dataclass
class Graph:
    counts: dict[str, int]
    features: dict[str, np.ndarray]
    relations: dict[str, tuple[str, str, np.ndarray, np.ndarray]]  # name -> (src_t, dst_t, src, dst)


def _rand_edges(rng, n_src, n_dst, n_edges):
    n_edges = min(n_edges, n_src * n_dst)
    m = int(n_edges * 1.3) + 8
    src = rng.integers(0, n_src, size=m).astype(np.int32)
    hot = max(1, n_dst // 16)
    pick_hot = rng.random(m) < 0.35
    dst = np.where(
        pick_hot, rng.integers(0, hot, size=m), rng.integers(0, n_dst, size=m)
    ).astype(np.int32)
    key = src.astype(np.int64) * n_dst + dst
    _, idx = np.unique(key, return_index=True)
    idx = idx[:n_edges]
    return src[idx], dst[idx]


def hetgraph(spec: dict, *, seed: int, scale: float = 1.0, feat_scale: float = 1.0) -> Graph:
    rng = np.random.default_rng(seed)
    counts = {t: max(4, int(round(n * scale))) for t, n in spec["vertices"].items()}
    feats = {
        t: rng.standard_normal((counts[t], max(8, int(round(d * feat_scale))))).astype(np.float32) * 0.1
        for t, d in spec["features"].items()
    }
    rels: dict = {}
    for name, (st, dt, ne) in spec["relations"].items():
        ne_s = max(4, int(round(ne * scale * scale))) if scale < 1.0 else ne
        if name[::-1] in rels and name != name[::-1]:
            s_t, d_t, s, d = rels[name[::-1]]
            rels[name] = (d_t, s_t, d, s)
            continue
        s, d = _rand_edges(rng, counts[st], counts[dt], ne_s)
        rels[name] = (st, dt, s, d)
    return Graph(counts, feats, rels)


def labels(g: Graph, spec: dict, *, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    x = g.features[spec["target"]]
    w = rng.standard_normal((x.shape[1], spec["num_classes"])).astype(np.float32)
    logits = x @ w + 0.1 * rng.standard_normal((x.shape[0], spec["num_classes"])).astype(np.float32)
    return logits.argmax(-1).astype(np.int32)


def _compose(src_a, mid_a, mid_b, dst_b, *, max_edges, rng):
    if src_a.size == 0 or mid_b.size == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    order_a = np.argsort(mid_a, kind="stable")
    order_b = np.argsort(mid_b, kind="stable")
    mid_a_s, src_a_s = mid_a[order_a], src_a[order_a]
    mid_b_s, dst_b_s = mid_b[order_b], dst_b[order_b]
    n_mid = int(max(mid_a_s[-1], mid_b_s[-1])) + 1
    cnt_a = np.bincount(mid_a_s, minlength=n_mid).astype(np.int64)
    cnt_b = np.bincount(mid_b_s, minlength=n_mid).astype(np.int64)
    start_a = np.concatenate([[0], np.cumsum(cnt_a)])
    start_b = np.concatenate([[0], np.cumsum(cnt_b)])
    pair_counts = cnt_a * cnt_b
    total = int(pair_counts.sum())
    if total == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    src_out = np.empty(total, np.int32)
    dst_out = np.empty(total, np.int32)
    pos = 0
    for m in np.nonzero(pair_counts)[0]:
        ca, cb = int(cnt_a[m]), int(cnt_b[m])
        s = src_a_s[start_a[m] : start_a[m] + ca]
        d = dst_b_s[start_b[m] : start_b[m] + cb]
        src_out[pos : pos + ca * cb] = np.repeat(s, cb)
        dst_out[pos : pos + ca * cb] = np.tile(d, ca)
        pos += ca * cb
    key = src_out.astype(np.int64) * np.int64(2**31) + dst_out.astype(np.int64)
    _, idx = np.unique(key, return_index=True)
    src_out, dst_out = src_out[idx], dst_out[idx]
    if max_edges is not None and src_out.size > max_edges:
        pick = rng.choice(src_out.size, size=max_edges, replace=False)
        pick.sort()
        src_out, dst_out = src_out[pick], dst_out[pick]
    return src_out, dst_out


def _relation(g: Graph, st: str, dt: str):
    for s_t, d_t, s, d in g.relations.values():
        if s_t == st and d_t == dt:
            return s, d
    for s_t, d_t, s, d in g.relations.values():
        if s_t == dt and d_t == st:
            return d, s
    raise KeyError(f"no relation {st}->{dt}")


def metapath_edges(g: Graph, metapath, *, max_edges: int | None, seed: int):
    """(src, dst) of one metapath's semantic graph, edges src -> dst."""
    rng = np.random.default_rng(seed)
    src, dst = _relation(g, metapath[0], metapath[1])
    for hop in range(1, len(metapath) - 1):
        ns, nd = _relation(g, metapath[hop], metapath[hop + 1])
        src, dst = _compose(src, dst, ns, nd, max_edges=max_edges, rng=rng)
    return src, dst


def metapath_name(metapath) -> str:
    return "".join(t[0].upper() for t in metapath)


def serving_seed(metapath) -> int:
    """The serving engine's SGB seed for a metapath (hgnn_engine._stable_seed)."""
    name = "/".join(metapath)
    return int.from_bytes(hashlib.blake2b(name.encode(), digest_size=4).digest(), "big")


def training_graphs(g: Graph, spec: dict, max_edges: int) -> dict[str, tuple[np.ndarray, np.ndarray]]:
    """Every metapath's edges as the training launcher builds them (SGB
    seed = the metapath's index in the dataset's list), keyed by name."""
    return {
        metapath_name(mp): metapath_edges(g, mp, max_edges=max_edges, seed=i)
        for i, mp in enumerate(spec["metapaths"])
    }
