"""End-to-end driver: train HAN (~100M-param config) for a few hundred
steps on synthetic ACM with checkpoint/resume.

    PYTHONPATH=src python examples/train_hgnn_han.py [--steps 300]

A thin veneer over the mesh-scale launcher (``repro.launch.hgnn_train``):
the model is widened (hidden 128 × 8 heads, att_dim 256, full-scale ACM
features) to ~100M parameters, trained full-batch (transductive node
classification, as HAN trains) through the consolidated multilane NA path
with the fault-tolerant train_loop — atomic checkpoints, counter-based
data state, elastic lane restarts.  Add ``--lanes 2`` to shard the NA
work units over a lane mesh; the loss trajectory does not change.  The
default ``--backend kernel`` needs a TPU; on a CPU host pass
``--backend kernel_interpret`` (and, for lanes, set
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
import argparse

from repro.launch.hgnn_train import run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--lanes", type=int, default=1)
    ap.add_argument(
        "--backend", default="kernel",
        choices=("reference", "kernel", "kernel_interpret"),
    )
    ap.add_argument("--ckpt", default="artifacts/han_ckpt")
    args = ap.parse_args()

    state, history, meta = run_training(
        dataset="acm",
        model_name="HAN",
        steps=args.steps,
        lanes=args.lanes,
        backend=args.backend,
        hidden=128,
        heads=8,
        scale=args.scale,
        feat_scale=1.0,
        ckpt_dir=args.ckpt,
        ckpt_every=100,
        log_every=20,
    )
    print(
        f"training complete: loss {history[0]['loss']:.4f} -> "
        f"{history[-1]['loss']:.4f}  acc {history[-1]['acc']:.3f}  "
        f"({meta['n_params']/1e6:.1f}M params, backend={meta['backend']})"
    )


if __name__ == "__main__":
    main()
