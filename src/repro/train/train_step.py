"""Distributed train step: loss + grads + optimizer, microbatching, and the
optional cross-pod compressed gradient reduction (DESIGN.md Sec 4).

The step is a plain jit-able function over (state, batch); parallelism comes
from the in/out shardings applied by the launcher (GSPMD), with optional
``shard_map`` manual control of the 'pod' axis when gradient compression is
enabled.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.overlap import compression
from repro.models import layers
from repro.models.model import Model
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    microbatches: int = 1              # grad accumulation steps
    compress_pod_grads: bool = False   # int8 error-feedback across 'pod'


def make_train_state(model: Model, opt_cfg: adamw.AdamWConfig, key,
                     settings: TrainSettings | None = None) -> dict:
    params = model.init(key)
    state = {"params": params,
             "opt": adamw.init_state(opt_cfg, params),
             "step": jnp.zeros((), jnp.int32)}
    if settings and settings.compress_pod_grads:
        state["grad_err"] = compression.init_error_state(params)
    return state


def _split_microbatches(batch: dict, n: int) -> dict:
    return jax.tree.map(
        lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch)


def _loss_and_grads(model: Model, params, batch, n_micro: int):
    if n_micro == 1:
        return jax.value_and_grad(model.train_loss)(params, batch)

    micro = _split_microbatches(batch, n_micro)

    def acc_fn(carry, mb):
        loss_acc, grad_acc = carry
        loss, grads = jax.value_and_grad(model.train_loss)(params, mb)
        return (loss_acc + loss,
                jax.tree.map(jnp.add, grad_acc, grads)), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(acc_fn, (jnp.zeros(()), zeros), micro)
    inv = 1.0 / n_micro
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def make_train_step(model: Model, opt_cfg: adamw.AdamWConfig,
                    settings: TrainSettings = TrainSettings(),
                    mesh: Mesh | None = None):
    """Build the jit-able train step.

    With ``compress_pod_grads`` (requires a mesh with a 'pod' axis), the step
    body runs under a shard_map that is manual over 'pod' and auto over
    data/model: gradients are reduced per-pod by GSPMD, then exchanged across
    pods as int8 codes with error feedback — 4x fewer bytes on the slowest
    links of a multi-pod fabric.
    """
    def step(state, batch):
        loss, grads = _loss_and_grads(model, state["params"], batch,
                                      settings.microbatches)
        new_state = dict(state)
        if settings.compress_pod_grads:
            loss = jax.lax.pmean(loss, "pod")
            grads, new_err = compression.tree_psum_compressed(
                grads, state["grad_err"], "pod")
            new_state["grad_err"] = new_err
        with jax.named_scope(layers.OPTIMIZER):
            params, opt, metrics = adamw.apply_updates(
                opt_cfg, state["params"], grads, state["opt"])
        new_state.update(params=params, opt=opt, step=state["step"] + 1)
        return new_state, {"loss": loss, **metrics}

    if not settings.compress_pod_grads:
        return step

    if mesh is None or "pod" not in mesh.axis_names:
        raise ValueError("compress_pod_grads requires a mesh with a 'pod' "
                         "axis")

    def podded(state, batch):
        return jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P("pod")), out_specs=(P(), P()),
            axis_names={"pod"}, check_vma=False)(state, batch)

    return podded
