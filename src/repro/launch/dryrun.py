import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax-importing module: jax locks
# the device count at first init.  512 placeholder host devices back the
# production meshes (16×16 single-pod, 2×16×16 multi-pod).  Tests and
# benches never import this module, so they see 1 device.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.environ['REPRO_DRYRUN_DEVICES']}"
    )

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..configs import ARCH_IDS, SHAPES, Shape, cell_supported, get_config
from ..dist.sharding import Rules, make_rules, param_shardings, use_rules
from ..models.lm.api import LMApi, build
from ..models.lm.config import LMConfig
from ..optim import AdamWConfig
from ..serve.engine import ServeState, init_serve_state, make_serve_step
from ..train.step import init_train_state, make_train_step, train_state_axes
from .hlostats import analyze
from .mesh import make_production_mesh

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link


def opt_config(cfg: LMConfig) -> AdamWConfig:
    big = cfg.param_count() > 5e10
    if big:
        # >100B: factored second moment, no master (pure-bf16 posture with
        # TPU stochastic rounding) — required to fit 16 GB/chip (DESIGN §7)
        return AdamWConfig(factored=True, master_fp32=False)
    return AdamWConfig()


def pick_microbatches(cfg: LMConfig, default: int | None = None) -> int:
    """None -> heuristic (16 for >50B models, else 8); explicit values honored."""
    if default is None:
        return 16 if cfg.param_count() > 5e10 else 8
    return default


def input_specs(cfg: LMConfig, shape: Shape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32)}
        if cfg.frontend == "audio":
            batch["frames"] = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        if cfg.frontend == "vision":
            batch["visual_embeds"] = jax.ShapeDtypeStruct((b, 256, cfg.d_model), jnp.bfloat16)
            batch["positions"] = jax.ShapeDtypeStruct((b, s, 3), jnp.int32)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.frontend == "audio":
            batch["frames"] = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        if cfg.frontend == "vision":
            batch["visual_embeds"] = jax.ShapeDtypeStruct((b, 256, cfg.d_model), jnp.bfloat16)
            batch["positions"] = jax.ShapeDtypeStruct((b, s, 3), jnp.int32)
        return batch
    # decode: one new token against a cache of seq_len
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def _dim_heuristic_spec(
    leaf, *, batch: int, lens: tuple[int, ...], data_axes
) -> PartitionSpec:
    """Shard cache-like tensors: first dim == batch -> data axes, first
    dim matching a cache length -> model (sequence-sharded KV)."""
    model_axes = ("model",)
    used_data = used_model = False
    parts = []
    data_sz = 1
    if data_axes:
        for a in data_axes:
            data_sz *= {"pod": 2, "data": 16, "model": 16}.get(a, 1)
    for d in leaf.shape:
        if not used_data and data_axes and d == batch and d % data_sz == 0 and d > 1:
            parts.append(tuple(data_axes) if len(data_axes) > 1 else data_axes[0])
            used_data = True
        elif not used_model and d in lens and d % 16 == 0:
            parts.append("model")
            used_model = True
        else:
            parts.append(None)
    return PartitionSpec(*parts)


def serve_state_shardings(
    mesh, rules: Rules, state_abs: ServeState, batch: int, cache_len: int,
    cfg: LMConfig, data_axes=None,
):
    lens = (cache_len,)
    if cfg.window:
        lens = (cache_len, min(cache_len, cfg.window))
    if data_axes is None:
        data_axes = rules.table.get("act_batch")

    def leaf_sh(x):
        return NamedSharding(mesh, _dim_heuristic_spec(x, batch=batch, lens=lens, data_axes=data_axes))

    caches = jax.tree_util.tree_map(leaf_sh, state_abs.caches)
    cross = jax.tree_util.tree_map(leaf_sh, state_abs.cross_kv)
    return ServeState(
        caches=caches,
        cache_pos=NamedSharding(mesh, PartitionSpec()),
        cross_kv=cross,
    )


def _tokens_sharding(mesh, rules: Rules, b: int):
    data_axes = rules.table.get("act_batch")
    spec = PartitionSpec(data_axes if data_axes and len(data_axes) > 1 else (data_axes[0] if data_axes else None))
    return NamedSharding(mesh, spec)


def model_flops(cfg: LMConfig, shape: Shape) -> float:
    """Analytic MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    microbatches: int | None = None,
    seq_shard: bool = False,
    save_hlo: str | None = None,
    remat: str | None = None,
    parallelism: str = "tp",
    grad_dtype: str | None = None,
) -> dict:
    cfg = get_config(arch)
    if remat is not None:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, remat=remat)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "params_b": cfg.param_count() / 1e9,
        "active_params_b": cfg.active_param_count() / 1e9,
    }
    ok, why = cell_supported(cfg, shape)
    if not ok:
        result.update(status="skipped", reason=why)
        return result

    chips = 512 if multi_pod else 256
    data_size = (2 * 16) if multi_pod else 16
    batch_shard = shape.global_batch % data_size == 0 and shape.global_batch >= data_size
    rules = make_rules(
        multi_pod=multi_pod, fsdp=cfg.fsdp, seq_shard=seq_shard,
        batch_shard=batch_shard, parallelism=parallelism,
    )
    result["parallelism"] = parallelism
    mesh = make_production_mesh(multi_pod=multi_pod)
    api = build(cfg)
    t0 = time.time()
    try:
        with mesh, use_rules(rules):
            if shape.kind == "train":
                mb = pick_microbatches(cfg, microbatches)
                while shape.global_batch % mb or (shape.global_batch // mb) % data_size:
                    mb //= 2  # keep each microbatch shardable over data
                mb = max(mb, 1)
                result["microbatches"] = mb
                opt = opt_config(cfg)
                state_abs = jax.eval_shape(
                    lambda k: init_train_state(api, k, opt), jax.random.key(0)
                )
                axes = train_state_axes(api, opt, state_abs.params)
                state_sh = param_shardings(mesh, rules, axes)
                batch_abs = input_specs(cfg, shape)
                batch_sh = {
                    k: NamedSharding(
                        mesh,
                        rules.spec(("act_batch",) + (None,) * (v.ndim - 1)),
                    )
                    for k, v in batch_abs.items()
                }
                step = make_train_step(api, opt, microbatches=mb, grad_dtype=grad_dtype)
                lowered = jax.jit(
                    step,
                    in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None),
                    donate_argnums=(0,),
                ).lower(state_abs, batch_abs)
            elif shape.kind == "prefill":
                params_abs = jax.eval_shape(api.init, jax.random.key(0))
                p_sh = param_shardings(mesh, rules, api.axes())
                batch_abs = input_specs(cfg, shape)
                batch_sh = {
                    k: NamedSharding(
                        mesh, rules.spec(("act_batch",) + (None,) * (v.ndim - 1))
                    )
                    for k, v in batch_abs.items()
                }

                def prefill_forward(params, batch):
                    toks = batch.pop("tokens")
                    logits, _ = api.forward(params, toks, **batch)
                    return logits

                lowered = jax.jit(
                    prefill_forward, in_shardings=(p_sh, batch_sh)
                ).lower(params_abs, batch_abs)
            else:  # decode
                params_abs = jax.eval_shape(api.init, jax.random.key(0))
                p_sh = param_shardings(mesh, rules, api.axes())
                b, s = shape.global_batch, shape.seq_len
                state_abs = jax.eval_shape(
                    lambda: init_serve_state(api, b, s, dtype=jnp.bfloat16, filled=s - 1)
                )
                cache_data_axes = ("pod", "data") if multi_pod else ("data",)
                if not (b % data_size == 0 and b >= data_size):
                    cache_data_axes = None
                st_sh = serve_state_shardings(
                    mesh, rules, state_abs, b, s, cfg, data_axes=cache_data_axes
                )
                tok_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)
                tok_sh = _tokens_sharding(mesh, rules, b)
                serve_step = make_serve_step(api)
                lowered = jax.jit(
                    serve_step,
                    in_shardings=(p_sh, st_sh, tok_sh),
                    out_shardings=(None, st_sh),
                    donate_argnums=(1,),
                ).lower(params_abs, state_abs, tok_abs)

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    except Exception as e:  # a failure here is a bug in the system
        result.update(status="failed", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        return result

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = analyze(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    mf = model_flops(cfg, shape)
    hlo_flops_dev = stats.dot_flops  # per device, loop-corrected
    coll_dev = stats.total_collective_bytes
    bytes_dev = float(cost.get("bytes accessed", 0.0))

    result.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            per_device_total=mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        ),
        cost_analysis=dict(
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=bytes_dev,
        ),
        hlo_stats=dict(
            dot_flops_per_device=hlo_flops_dev,
            dot_flops_static=stats.dot_flops_static,
            collective_bytes=stats.collective_bytes,
            collective_bytes_static=stats.collective_bytes_static,
            collective_count=stats.collective_count,
            while_trips=stats.while_trips[:32],
        ),
        model_flops=mf,
        chips=chips,
        roofline=dict(
            compute_s=hlo_flops_dev / PEAK_FLOPS,
            # memory term: loop-corrected HLO byte traffic is not separable
            # from cost_analysis; use bytes_accessed (static) as the floor
            memory_s_floor=bytes_dev / HBM_BW,
            collective_s=coll_dev / ICI_BW,
            model_flops_utilization=mf / max(hlo_flops_dev * chips, 1.0),
        ),
    )
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'pod2x16x16' if mp else 'pod16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[skip-cached] {tag}")
                        continue
                t0 = time.time()
                res = lower_cell(
                    arch, shape, multi_pod=mp,
                    microbatches=args.microbatches, seq_shard=args.seq_shard,
                )
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                status = res["status"]
                if status == "failed":
                    n_fail += 1
                    print(f"[FAIL] {tag}: {res['error']}")
                else:
                    extra = ""
                    if status == "ok":
                        gb = res["memory"]["per_device_total"] / 2**30
                        extra = f" mem/dev={gb:.2f}GiB compile={res['compile_s']}s"
                    print(f"[{status}] {tag}{extra} ({time.time()-t0:.1f}s)")
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
