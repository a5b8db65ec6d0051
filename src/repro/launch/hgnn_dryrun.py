import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# Production-scale dry-run of the paper's OWN technique: multi-lane HGNN
# NA+GSF with lanes sharded over a dedicated `lane` mesh axis (one lane
# group per chip column — the accelerator's scale-up §4.2 mapped onto a
# pod).  Layout comes from the "lanes" sharding rules (DESIGN.md §5),
# consumed exactly the way the LM launch path consumes its rules.

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.fusion import FusedFPInputs
from ..core.multilane import MultiLanePlan, multilane_na, multilane_na_sharded
from ..core.scheduling import LanePlan
from ..core import stages
from ..dist.sharding import make_rules, use_rules
from ..obs import profile, trace_span
from .hlostats import analyze, span_attrs
from .mesh import make_lane_mesh

PEAK_FLOPS = 197e12
ICI_BW = 50e9


def abstract_plan(lanes: int, units: int, w: int, block: int, graphs: int, rows: int):
    dummy = LanePlan(
        unit_graph=np.zeros(1, np.int32), unit_row=np.zeros(1, np.int32),
        unit_cost=np.zeros(1), unit_lane=np.zeros(1, np.int32), lane_load=np.ones(lanes),
    )
    return MultiLanePlan(
        col_index=jax.ShapeDtypeStruct((lanes, units, w), jnp.int32),
        masks=jax.ShapeDtypeStruct((lanes, units, w, block, block), jnp.bool_),
        graph_id=jax.ShapeDtypeStruct((lanes, units), jnp.int32),
        dst_row=jax.ShapeDtypeStruct((lanes, units), jnp.int32),
        valid=jax.ShapeDtypeStruct((lanes, units), jnp.bool_),
        block=block,
        num_graphs=graphs,
        n_dst_blocks=rows,
        lane_plan=dummy,
    )


def aligned_lane_step_builder(g, rows_per_lane, block, h_dim, dh, ns_pad):
    """Beyond-paper scheduling (§Perf HC-paper): co-locate the SAME dst
    rows of all semantic graphs on one lane.  The GSF combine across
    graphs becomes lane-LOCAL (a reshape, not the paper's crossbar
    transfer); only the LSF scalars cross lanes (psum of [G])."""

    def unit_row(cols, mrow, row_idx, th_s, th_d, h_src, bias):
        # cols [G, W], mrow [G, W, B, B] — all graphs of one dst row
        def per_graph(c, m, gi):
            from ..core.multilane import _unit_na

            return _unit_na(c, m, gi, row_idx, th_s, th_d, h_src, bias, 0.2)

        return jax.vmap(per_graph)(cols, mrow, jnp.arange(g))  # [G, B, H, Dh]

    def lane_step(col_index, masks, row_ids, th_s, th_d, h_src, w_g, q):
        bias = jnp.zeros((g, h_dim), jnp.float32)
        hs = h_src.astype(jnp.float32)
        z = jax.vmap(jax.vmap(unit_row, in_axes=(0, 0, 0, None, None, None, None)),
                     in_axes=(0, 0, 0, None, None, None, None))(
            col_index, masks, row_ids, th_s, th_d, hs, bias
        )  # [L, U_r, G, B, H, Dh]
        lanes, ur = z.shape[0], z.shape[1]
        zf = z.reshape(lanes, ur, g, block, h_dim * dh)
        # LSF: per-lane partial semantic importances; psum is implicit in
        # the global mean over the lane-sharded axis
        s = jnp.tanh(zf @ w_g) @ q  # [L, U_r, G, B]
        w_p = s.mean(axis=(0, 1, 3)) * (lanes * ur * block) / ns_pad  # [G]
        beta = jax.nn.softmax(w_p)
        fused = jnp.einsum("g,lugbd->lubd", beta, zf)  # lane-local GSF
        return fused, beta

    return lane_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=1_048_576)
    ap.add_argument("--graphs", type=int, default=3)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dh", type=int, default=64)
    ap.add_argument("--width", type=int, default=16, help="blocks per row")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--schedule", choices=("balanced", "aligned"), default="balanced")
    ap.add_argument(
        "--executor", choices=("spmd", "shard_map"), default="spmd",
        help="balanced schedule only: partitioner-placed (jit in_shardings) "
        "or explicit shard_map over the lane axis",
    )
    ap.add_argument(
        "--na-backend",
        choices=("reference", "kernel", "kernel_interpret", "fused_fp", "fused_fp_interpret"),
        default="reference",
        help="balanced schedule only: per-unit NA executor for multilane_na "
        "('kernel' = one fused Pallas launch per chip; needs TPU lowering, "
        "'kernel_interpret' validates the same kernel on CPU; 'fused_fp' = "
        "the FP+NA stage-fusion megakernel streaming RAW features, "
        "'fused_fp_interpret' its CPU validator)",
    )
    ap.add_argument(
        "--din", type=int, default=256,
        help="fused_fp backends only: raw feature width streamed into the megakernel",
    )
    ap.add_argument("--out", default="artifacts/dryrun/hgnn_multilane.json")
    ap.add_argument(
        "--trace", default=None, metavar="DIR",
        help="profile the dry-run into DIR (.xplane.pb + perfetto_trace.json.gz): "
             "dryrun.compile and dryrun.hlostats spans, the latter carrying "
             "hlostats collective-bytes and dot-FLOP meta",
    )
    args = ap.parse_args()
    with profile(args.trace):
        run(ap, args)
    if args.trace:
        print(f"wrote a profile under {args.trace}")


def run(ap, args):
    if args.schedule == "aligned" and args.executor != "spmd":
        ap.error("--executor shard_map only applies to --schedule balanced")
    if args.schedule == "aligned" and args.na_backend != "reference":
        ap.error("--na-backend only applies to --schedule balanced")

    block = 128
    rows = args.vertices // block
    mesh = make_lane_mesh(multi_pod=args.multi_pod)
    rules = make_rules(multi_pod=args.multi_pod, parallelism="lanes")
    lanes = 32 * 16 if args.multi_pod else 16 * 16  # one lane per chip
    units = rows * args.graphs // lanes
    g, h_dim, dh = args.graphs, args.heads, args.dh

    plan = abstract_plan(lanes, units, args.width, block, g, rows)
    ns_pad = rows * block
    th_s = jax.ShapeDtypeStruct((g, ns_pad, h_dim), jnp.float32)
    th_d = jax.ShapeDtypeStruct((g, rows * block, h_dim), jnp.float32)
    h_src = jax.ShapeDtypeStruct((ns_pad, h_dim, dh), jnp.bfloat16)
    # HAN semantic-attention params (LSF/GSF fused after NA)
    w_g = jax.ShapeDtypeStruct((h_dim * dh, 128), jnp.float32)
    q = jax.ShapeDtypeStruct((128,), jnp.float32)

    lane_axis = rules.mesh_axes("act_lane")

    def _sf_tail(z, w_g, q):
        zf = z.reshape(g, ns_pad, h_dim * dh)
        valid = jnp.ones((ns_pad,), bool)
        w_p = jnp.stack([
            stages.local_semantic_fusion(zf[p], w_g, jnp.zeros((128,)), q, valid)
            for p in range(g)
        ])
        fused, beta = stages.global_semantic_fusion(w_p, zf)
        return fused, beta

    def lane_step(plan, th_s, th_d, h_src, w_g, q):
        na = (
            (lambda p, a, b, c: multilane_na_sharded(
                p, a, b, c, mesh=mesh, lane_axes=lane_axis, backend=args.na_backend))
            if args.executor == "shard_map"
            else (lambda p, a, b, c: multilane_na(p, a, b, c, backend=args.na_backend))
        )
        z = na(plan, th_s, th_d, h_src.astype(jnp.float32))  # [G, N, H, Dh]
        return _sf_tail(z, w_g, q)

    def lane_step_fp(plan, fp, w_g, q):
        # Megakernel path: theta/h' never exist as program inputs — the
        # kernel streams RAW features and projects on-chip (DESIGN.md §10).
        if args.executor == "shard_map":
            z = multilane_na_sharded(
                plan, None, None, None,
                mesh=mesh, lane_axes=lane_axis, backend=args.na_backend, fp=fp,
            )
        else:
            z = multilane_na(plan, None, None, None, backend=args.na_backend, fp=fp)
        return _sf_tail(z, w_g, q)

    lane_sh = lambda *rest: NamedSharding(mesh, rules.spec(("act_lane",) + rest))
    feat_sh = NamedSharding(mesh, rules.spec((None, None, "act_feat")))
    rep = NamedSharding(mesh, P())
    with mesh, use_rules(rules):
        if args.schedule == "aligned":
            u_r = rows // lanes
            col_abs = jax.ShapeDtypeStruct((lanes, u_r, g, args.width), jnp.int32)
            mask_abs = jax.ShapeDtypeStruct((lanes, u_r, g, args.width, block, block), jnp.bool_)
            rowid_abs = jax.ShapeDtypeStruct((lanes, u_r), jnp.int32)
            step = aligned_lane_step_builder(g, u_r, block, h_dim, dh, ns_pad)
            lowered = jax.jit(
                step,
                in_shardings=(
                    lane_sh(None, None, None), lane_sh(None, None, None, None, None),
                    lane_sh(None), rep, rep,
                    feat_sh, rep, rep,
                ),
            ).lower(col_abs, mask_abs, rowid_abs, th_s, th_d, h_src, w_g, q)
            units = u_r
        else:
            plan_sh = MultiLanePlan(
                col_index=lane_sh(None, None),
                masks=lane_sh(None, None, None, None),
                graph_id=lane_sh(None),
                dst_row=lane_sh(None),
                valid=lane_sh(None),
                block=block, num_graphs=g, n_dst_blocks=rows, lane_plan=plan.lane_plan,
            )
            if args.na_backend.startswith("fused_fp"):
                fp_abs = FusedFPInputs(
                    x=jax.ShapeDtypeStruct((ns_pad, args.din), jnp.float32),
                    w=jax.ShapeDtypeStruct((1, args.din, h_dim * dh), jnp.float32),
                    b=jax.ShapeDtypeStruct((1, h_dim * dh), jnp.float32),
                    a_src=jax.ShapeDtypeStruct((g, h_dim, dh), jnp.float32),
                    a_dst=jax.ShapeDtypeStruct((g, h_dim, dh), jnp.float32),
                    wsel=jax.ShapeDtypeStruct((g,), jnp.int32),
                )
                x_sh = NamedSharding(mesh, rules.spec((None, "act_feat")))
                fp_sh = FusedFPInputs(x=x_sh, w=rep, b=rep, a_src=rep, a_dst=rep, wsel=rep)
                lowered = jax.jit(
                    lane_step_fp,
                    in_shardings=(plan_sh, fp_sh, rep, rep),
                ).lower(plan, fp_abs, w_g, q)
            else:
                lowered = jax.jit(
                    lane_step,
                    in_shardings=(plan_sh, rep, rep, feat_sh, rep, rep),
                ).lower(plan, th_s, th_d, h_src, w_g, q)
        try:
            with trace_span(
                "dryrun.compile", schedule=args.schedule, executor=args.executor,
                backend=args.na_backend, lanes=lanes,
            ):
                compiled = lowered.compile()
        except Exception as e:
            if args.na_backend in ("kernel", "fused_fp") and jax.default_backend() != "tpu":
                raise SystemExit(
                    f"--na-backend {args.na_backend} needs a TPU to compile the "
                    f"Pallas kernel (host backend: {jax.default_backend()}); "
                    f"use --na-backend {args.na_backend}_interpret to validate "
                    f"on this host.  Compile error: {e}"
                ) from e
            raise
    mem = compiled.memory_analysis()
    with trace_span("dryrun.hlostats") as sp:
        stats = analyze(compiled.as_text())
        # the compiled program's communication/compute footprint rides on
        # its span in the profile
        sp.set_metadata(**span_attrs(stats, schedule=args.schedule))
    edges_equiv = lanes * units * args.width * block * block  # masked-dense positions
    flops = stats.dot_flops
    result = dict(
        status="ok",
        schedule=args.schedule,
        executor=args.executor,
        mesh="pod2x16x16" if args.multi_pod else "pod16x16",
        lanes=lanes, units_per_lane=units, vertices=args.vertices, graphs=g,
        mem_per_device_gib=(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                            + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30,
        dot_flops_per_device=flops,
        collective_bytes=stats.collective_bytes,
        compute_s=flops / PEAK_FLOPS,
        collective_s=sum(stats.collective_bytes.values()) / ICI_BW,
        dense_block_positions=edges_equiv,
    )
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result, indent=1))


if __name__ == "__main__":
    main()
