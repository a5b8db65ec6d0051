"""Train-step builder for the HGNN models (HAN, R-GAT, ...).

The HGNN trainer reuses the LM substrate wholesale: the generic
:class:`~repro.train.step.TrainState` (params/opt/step), the AdamW
optimizer, and the fault-tolerant ``train_loop`` — only the loss changes.
HGNNs here train transductively: the forward runs over the whole resident
graph every step (the semantic-graph batches are closed over as device
constants, like the serving engine holds them resident), and the step's
minibatch is a counter-based set of labeled target vertices
(data/pipeline.py:SyntheticHGNNData) whose cross-entropy is optimized.

``make_hgnn_train_step`` takes the *forward function*, not the model: the
mesh-scale launcher passes ``han_forward_multilane`` closed over a
MultiLanePlan + lane mesh (NA through the fused multigraph kernel per
lane shard, DESIGN.md §11); tests pass plain ``model.forward`` with any
NABackend.  Both produce the identical train step because every NA
backend and lane count is numerically equivalent (the backend-equivalence
contract, tests/test_multilane).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..models.hgnn.common import HGNNData, HGNNModel
from ..optim import AdamWConfig, apply_updates, init_opt_state, opt_state_axes
from .step import TrainState

# Logical parameter axes by leaf name (model code stays mesh-free; the
# lanes rules map "mlp"/"heads" onto the model axis and replicate the
# rest across lanes — every lane gathers from the full projected table,
# the functional RAB).  Unknown names replicate, so new params are safe.
_HGNN_PARAM_AXES: dict[str, tuple[str | None, ...]] = {
    "w_fp": ("embed", "mlp"),
    "b_fp": ("mlp",),
    "a_src": ("act_graph", "heads", None),
    "a_dst": ("act_graph", "heads", None),
    "w_src": ("embed", "mlp"),
    "w_dst": ("embed", "mlp"),
    "w_g": ("mlp", None),
    "w_out": ("mlp", None),
    # S-HGN (flat "<layer or type>.<leaf>" names; keyed by the leaf part)
    "w_in": ("embed", None),
    "w": (None, "mlp"),
    "w_res": ("mlp", None),
}


def hgnn_param_axes(params) -> Any:
    """Logical-axes pytree for an HGNN params tree (same structure).

    Leaves are keyed by their last tree-path component, and a dotted
    name (S-HGN's ``layer1.w``) by its part after the last dot; anything
    not in the table replicates (``(None,) * ndim``).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    axes = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1])).rsplit(".", 1)[-1]
        ax = _HGNN_PARAM_AXES.get(name)
        if ax is None or len(ax) != leaf.ndim:
            ax = (None,) * leaf.ndim
        axes.append(tuple(ax))
    return jax.tree_util.tree_unflatten(treedef, axes)


def init_hgnn_train_state(
    model: HGNNModel, rng: jax.Array, data: HGNNData, opt_cfg: AdamWConfig, **init_kw
) -> TrainState:
    params = model.init(rng, data, **init_kw)
    return TrainState(
        params=params, opt=init_opt_state(params, opt_cfg), step=jnp.zeros((), jnp.int32)
    )


def hgnn_train_state_axes(state: TrainState, opt_cfg: AdamWConfig) -> TrainState:
    """Logical-axes TrainState for ``dist.param_shardings`` (elastic
    restarts re-derive shardings from THIS, against whatever lane mesh the
    new run has — checkpoint bits are mesh-free)."""
    pax = hgnn_param_axes(state.params)
    return TrainState(params=pax, opt=opt_state_axes(pax, opt_cfg, state.params), step=())


def make_hgnn_train_step(
    forward_fn: Callable[[Any], jnp.ndarray],
    data: HGNNData,
    opt_cfg: AdamWConfig,
    *,
    lr_schedule: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Build the (jit-able) HGNN train step.

    ``forward_fn(params) -> logits [N_target, C]`` runs the full-graph
    forward; ``batch["idx"]`` selects the step's labeled minibatch.
    Metrics carry ``loss``/``grad_norm`` (the train_loop contract) plus
    minibatch accuracy.  The loss runs in the ``head`` scope and the clip
    and AdamW update in ``optimizer`` (the model names its own stages), so
    a profile can put each device op of the step down to its stage.
    """
    assert data.labels is not None, "training needs labels in HGNNData"
    sched = lr_schedule or (lambda s: jnp.asarray(opt_cfg.lr))

    def loss_fn(params, idx):
        logits = forward_fn(params)
        with jax.named_scope("head"):
            lp = jax.nn.log_softmax(logits[idx].astype(jnp.float32), axis=-1)
            y = data.labels[idx]
            loss = -jnp.take_along_axis(lp, y[:, None], axis=-1)[:, 0].mean()
            acc = (jnp.argmax(lp, axis=-1) == y).mean()
        return loss, {"loss": loss, "acc": acc}

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch["idx"]
        )
        with jax.named_scope("optimizer"):
            lr = sched(state.step)
            new_params, new_opt, gnorm = apply_updates(
                state.params, grads, state.opt, opt_cfg, lr
            )
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return TrainState(params=new_params, opt=new_opt, step=state.step + 1), metrics

    return train_step
