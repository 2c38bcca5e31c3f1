"""Sharded training-step construction (GSPMD).

Replaces the reference's wrapper-based DDP/FSDP (train/torch/
train_loop_utils.py:158 prepare_model + NCCL process groups): here the
*same* jitted step serves dp/fsdp/tp/sp — parameters and data are
placed per the logical-axis rules and XLA inserts the gradient
reduce-scatters/all-gathers over ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import (
    infer_param_logical_axes,
    named_sharding,
    tree_shardings,
)


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      warmup_steps: int = 100,
                      total_steps: int = 10000,
                      max_grad_norm: float = 1.0) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clip — the Llama SFT recipe."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(max_grad_norm),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


class TrainState:
    """Minimal functional train state (params + opt state + step)."""

    __slots__ = ("params", "opt_state", "step")

    def __init__(self, params, opt_state, step):
        self.params = params
        self.opt_state = opt_state
        self.step = step

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def create_train_state(params: Any, optimizer: optax.GradientTransformation,
                       mesh: Mesh | None = None,
                       logical_axes: Any | None = None) -> TrainState:
    """Build a TrainState; with a mesh, params (and hence the optimizer
    moments, which are derived from them) are placed per the rules.

    ``params`` is a pytree of arrays, or a zero-argument function that
    builds one. The function is jitted with the rule shardings as its
    ``out_shardings``, so the parameters are sharded from birth: a model
    that does not fit one device never exists unsharded on the default
    device, and no second copy is made. A pytree that already exists is
    copied (see below), which holds the model twice while it lasts.
    """
    if callable(params):
        init = params
        if mesh is None:
            params = jax.jit(init)()
        else:
            if logical_axes is None:
                logical_axes = infer_param_logical_axes(jax.eval_shape(init))
            params = jax.jit(
                init, out_shardings=tree_shardings(mesh, logical_axes))()
    elif mesh is not None:
        if logical_axes is None:
            logical_axes = infer_param_logical_axes(params)
        shardings = tree_shardings(mesh, logical_axes)

        def place(x, s):
            # Copy before placing: the train step donates the state, and
            # device_put can alias the caller's buffers — donation would
            # then delete the caller's original arrays.
            return jax.device_put(jnp.array(x, copy=True), s)

        params = jax.tree.map(place, params, shardings)
    opt_state = optimizer.init(params)
    return TrainState(params, opt_state, jnp.zeros((), dtype=jnp.int32))


def build_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: optax.GradientTransformation,
    donate: bool = True,
) -> Callable:
    """Return jitted ``step(state, batch) -> (state, metrics)``.

    ``loss_fn(params, batch) -> scalar``. Sharding propagates from the
    inputs (GSPMD), so data placed with batch sharding + params placed
    per rules is all the setup needed; gradients come out with the same
    sharding as params (XLA inserts reduce-scatter over dp/fsdp).
    """

    def step(state: TrainState, batch: Any):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm, "step": state.step}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    jit_kwargs: dict = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    return jax.jit(step, **jit_kwargs)


def shard_batch(batch: Any, mesh: Mesh, seq_axes: bool = True) -> Any:
    """Place a host batch on the mesh: leading dim over (dp, fsdp),
    second dim (sequence) over sp when present."""

    def place(x):
        if x.ndim >= 2 and seq_axes:
            spec = P(("dp", "fsdp"), "sp")
        elif x.ndim >= 1:
            spec = P(("dp", "fsdp"))
        else:
            spec = P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(place, batch)
