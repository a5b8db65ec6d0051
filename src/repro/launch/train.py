"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke --steps 20

On real hardware: builds the production mesh, applies the logical-axis
sharding rules, and runs the fault-tolerant loop with sharded state.  On
this CPU container, --smoke runs the reduced config on a 1×1 mesh —
exactly the same code path (mesh, rules, jit-with-shardings) at toy size;
the full configs are exercised by launch/dryrun.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config, smoke_config
from ..data import SyntheticLMData
from ..dist.sharding import make_rules, param_shardings, use_rules
from ..models.lm.api import build
from ..optim import AdamWConfig
from ..train import make_train_step, train_loop
from ..train.step import init_train_state, train_state_axes
from .compile_cache import enable_compile_cache
from .mesh import make_mesh, make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = build(cfg)
    opt = AdamWConfig(lr=1e-2 if args.smoke else 3e-4, weight_decay=0.0 if args.smoke else 0.1)

    n_dev = len(jax.devices())
    if args.smoke or n_dev < 256:
        mesh = make_mesh((1, 1), ("data", "model")) if n_dev == 1 else make_mesh(
            (n_dev, 1), ("data", "model")
        )
        rules = make_rules(batch_shard=n_dev > 1, fsdp=False)
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rules = make_rules(multi_pod=args.multi_pod, fsdp=cfg.fsdp)

    data = SyntheticLMData(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.global_batch,
        seed=0, with_frames=cfg.frontend == "audio",
        frame_len=cfg.encoder_seq, d_model=cfg.d_model,
    )
    with mesh, use_rules(rules):
        state = init_train_state(api, jax.random.key(0), opt)
        axes = train_state_axes(api, opt, state.params)
        state_sh = param_shardings(mesh, rules, axes)
        state = jax.device_put(state, state_sh)
        step = make_train_step(
            api, opt, microbatches=args.microbatches,
            lr_schedule=(lambda s: jnp.asarray(1e-2)) if args.smoke else None,
        )
        state, hist = train_loop(
            state=state, train_step=step, data=data, steps=args.steps,
            ckpt_dir=args.ckpt, log_every=5,
        )
    print(f"final loss {hist[-1]['loss']:.4f} (start {hist[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
