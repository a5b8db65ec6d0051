"""Production serving launcher: batched prefill + decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke

Same engine the decode_* dry-run cells lower; --smoke executes the
reduced config on CPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, smoke_config
from ..models.lm.api import build
from ..serve.engine import greedy_generate
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = build(cfg)
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    t0 = time.time()
    out = greedy_generate(
        api, params, prompts, steps=args.steps,
        cache_len=args.prompt_len + args.steps + 1,
    )
    dt = time.time() - t0
    print(f"{cfg.name}: {args.batch * args.steps} tokens in {dt:.2f}s")
    print(np.asarray(out))


if __name__ == "__main__":
    main()
