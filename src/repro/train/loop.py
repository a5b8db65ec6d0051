"""Fault-tolerant training loop: checkpoint/restart with exact replay.

The loop owns nothing it cannot reconstruct: model state comes from the
latest checkpoint (atomic manifest dirs), data comes from a counter-based
pipeline whose state rides in the checkpoint aux — so a crash at any step
resumes bit-identically (tests/test_train::test_crash_resume).  On a real
cluster this loop runs per-host under a supervisor that re-launches failed
workers; elastic restarts go through checkpoint.reshard_to with the new
mesh (straggler posture: synchronous steps + restart-on-failure, DESIGN §4).
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

from ..checkpoint import latest_step, restore_checkpoint, save_checkpoint
from ..data.pipeline import SyntheticLMData
from ..obs.emit import Emitter
from ..obs.metrics import MetricsRegistry, get_registry
from .step import TrainState


def train_loop(
    *,
    state: TrainState,
    train_step: Callable,
    data: SyntheticLMData,
    steps: int,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    resume: bool = True,
    crash_at: int | None = None,  # fault-injection hook for tests
    log_every: int = 10,
    log: Callable[[str], None] = print,
    log_jsonl: str | None = None,  # mirror structured records to a JSONL file
    registry: MetricsRegistry | None = None,
    state_shardings=None,  # elastic restart: place restored leaves on THIS mesh
) -> tuple[TrainState, list[dict]]:
    """Run ``steps`` train steps with checkpointing and structured logging.

    Observability (DESIGN.md §12): every step increments ``train.steps``
    and lands its wall time in the ``train.step_ms`` histogram; logged
    steps additionally set the ``train.loss``/``train.grad_norm`` gauges
    and emit a structured ``[train] step=… loss=… sec=…`` record through
    :class:`Emitter` (``log=`` stays the injectable sink).  Per-step
    ``sec`` on logged steps includes the device sync the host-side metric
    conversion forces; between log points it is dispatch wall time.  Each
    step's dispatch is a ``train`` span (meta ``step_num``) in a profiler
    trace (``StepTraceAnnotation``, no sync), beside the device's ops.
    """
    reg = registry if registry is not None else get_registry()
    em = Emitter(sink=log, jsonl_path=log_jsonl)
    step_ms = reg.histogram("train.step_ms")
    steps_c = reg.counter("train.steps")

    start = 0
    if ckpt_dir and resume:
        last = latest_step(ckpt_dir)
        if last is not None:
            # state_shardings belongs to the CURRENT run's mesh, which may
            # differ from the mesh that wrote the checkpoint (elastic lane
            # restart) — the leaves on disk are logical arrays either way.
            state, aux = restore_checkpoint(
                ckpt_dir, last, state, shardings=state_shardings
            )
            data.restore(aux["data"])
            start = last
            em.emit("resume", step=last)

    history: list[dict] = []
    jitted = jax.jit(train_step)
    try:
        for step in range(start, steps):
            if crash_at is not None and step == crash_at:
                raise RuntimeError(f"injected failure at step {step}")
            t0 = time.perf_counter()
            batch = data.next()
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                state, metrics = jitted(state, batch)
            dt = time.perf_counter() - t0
            if step % log_every == 0 or step == steps - 1:
                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                m["step"] = step
                dt = m["sec"] = time.perf_counter() - t0  # includes the sync above
                history.append(m)
                reg.gauge("train.loss").set(m["loss"])
                reg.gauge("train.grad_norm").set(m["grad_norm"])
                em.emit(
                    "train",
                    step=step,
                    loss=m["loss"],
                    gnorm=m["grad_norm"],
                    sec=dt,
                )
            step_ms.observe(dt * 1e3)
            steps_c.inc()
            if ckpt_dir and (step + 1) % ckpt_every == 0:
                save_checkpoint(ckpt_dir, step + 1, state, aux={"data": data.state()})
        if ckpt_dir:
            save_checkpoint(ckpt_dir, steps, state, aux={"data": data.state()})
    finally:
        em.close()
    return state, history
