"""The one module that reaches into the program under test.

It builds the serving engine and the training loop through the program's
normal path (``models.model.build``, ``serve.engine.Engine``,
``train.trainer.Trainer`` over the donated, jitted step built as
``launch/train.py`` builds it), from a configuration file's sizes and
weights drawn by ``weights.py``.  The program's own registry entry gives
every setting the file does not state.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmarks.chip import weights


def model_config(cell):
    from repro.configs import registry

    s = cell.sizes
    return dataclasses.replace(
        registry.get(cell.config["program"]), name=cell.config["name"],
        n_layers=s.layers, d_model=s.d, n_heads=s.heads,
        n_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.ff,
        vocab_size=s.vocab, tie_embeddings=s.tied, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, dtype="bfloat16")


def draw_weights(cell, seed: int, shardings=None):
    """The whole parameter tree, drawn on the device in one jitted call."""
    fn = functools.partial(weights.serving_weights, s=cell.sizes,
                           embed_std=cell.config["init"]["embed_std"])
    return jax.jit(fn, out_shardings=shardings)(weights.root_key(seed))


def engine(cell, seed: int):
    from repro.models import model as model_lib
    from repro.serve.engine import Engine, ServeConfig

    mix = cell.mix
    model = model_lib.build(model_config(cell))
    return Engine(model, draw_weights(cell, seed),
                  ServeConfig(max_batch=mix["batch"], max_len=mix["max_len"],
                              temperature=0.0, eos_token=mix["eos_token"]))


def trainer(cell, seed: int, wrap):
    """(trainer, mesh): the program's Trainer over its jitted, donated train
    step, on a host mesh of the cell's chips, with the step passed through
    ``wrap`` (the benchmark's span and window bookkeeping)."""
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib
    from repro.optim import adamw
    from repro.sharding import partition
    from repro.train import train_step as ts
    from repro.train.trainer import Trainer, TrainerConfig

    mix = cell.mix
    model = model_lib.build(model_config(cell))
    opt_cfg = adamw.AdamWConfig(**{k: v for k, v in mix["optimizer"].items()
                                   if k in adamw.AdamWConfig.__annotations__})
    mesh = make_host_mesh(cell.chips)
    key = weights.root_key(seed)
    embed_std = cell.config["init"]["embed_std"]

    def init_state(key):
        params = weights.serving_weights(key, cell.sizes, embed_std)
        return {"params": params, "opt": adamw.init_state(opt_cfg, params),
                "step": jnp.zeros((), jnp.int32)}

    shardings = partition.param_shardings(jax.eval_shape(init_state, key),
                                          mesh)
    state = jax.jit(init_state, out_shardings=shardings)(key)
    step = jax.jit(ts.make_train_step(model, opt_cfg, ts.TrainSettings()),
                   out_shardings=(shardings, None), donate_argnums=(0,))
    data = DataConfig(vocab_size=cell.sizes.vocab, seq_len=mix["seq"],
                      global_batch=mix["batch"], seed=seed)
    loop = Trainer(wrap(step), state, data, None,
                   TrainerConfig(total_steps=0, log_every=1,
                                 checkpoint_every=2 ** 62))
    return loop, mesh
