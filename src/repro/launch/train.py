"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --steps 50 --batch 8 --seq 128 --smoke

``--smoke`` swaps in the reduced config so the run fits a laptop/CI CPU;
``--layers N`` keeps the published widths and cuts only the depth, so a
model too deep for one chip still trains at full width there.  The state is
built already sharded over the host mesh and donated to every step; the run
reports how many programs each step compiled (after the first: none).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import registry
from repro.data.pipeline import DataConfig
from repro.launch import compiles
from repro.launch.mesh import make_host_mesh
from repro.models import model as model_lib
from repro.optim import adamw
from repro.sharding import partition
from repro.sharding.context import use_mesh
from repro.train import train_step as ts
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=list(registry.ARCHS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--layers", type=int, default=None,
                    help="keep N layers at published widths (depth cut)")
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh over the first N devices (default: all)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint to and resume from this directory "
                         "(default: no checkpoints)")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)

    compiles.enable_cache()
    cfg = registry.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.layers is not None:
        print(f"depth cut: {args.layers} of {cfg.n_layers} layers "
              f"(widths unchanged)")
        cfg = cfg.with_depth(args.layers)
    model = model_lib.build(cfg)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(1, args.steps // 10))
    settings = ts.TrainSettings(microbatches=args.microbatches)

    def init_state(key):
        return ts.make_train_state(model, opt_cfg, key, settings)

    mesh = make_host_mesh(args.devices)
    key = jax.random.key(0)
    state_shardings = partition.param_shardings(
        jax.eval_shape(init_state, key), mesh)
    # created already placed, so the donated state aliases the step's output
    state = jax.jit(init_state, out_shardings=state_shardings)(key)
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"{cfg.name}: {n_params / 1e6:.1f}M params, mesh "
          f"{dict(mesh.shape)}, batch {args.batch}x{args.seq}")
    step = jax.jit(ts.make_train_step(model, opt_cfg, settings),
                   out_shardings=(state_shardings, None),
                   donate_argnums=(0,))

    log = compiles.CompileLog()
    compiles_per_step: list[int] = []

    def counted_step(state, batch):
        before = log.count
        out = step(state, batch)
        compiles_per_step.append(log.count - before)
        return out

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch,
                          n_media_tokens=cfg.n_media_tokens,
                          media_embed_dim=cfg.media_embed_dim)
    trainer = Trainer(counted_step, state, data_cfg, args.ckpt_dir,
                      TrainerConfig(total_steps=args.steps,
                                    checkpoint_every=args.ckpt_every,
                                    log_every=max(1, args.steps // 10)))
    if trainer.start_step:
        print(f"resumed from the step-{trainer.start_step} checkpoint in "
              f"{args.ckpt_dir}")
    with log, use_mesh(mesh):
        result = trainer.run()
    for m in result["metrics"]:
        print(f"step {m['step']:6d}  loss {m['loss']:.4f}  "
              f"{m['sec_per_step']*1e3:.0f} ms/step")
    print(f"finished at step {result['final_step']}; "
          f"straggler breaches: {result['straggler_breaches']}; "
          f"programs compiled per step: {compiles_per_step}")
    return {**result, "compiles_per_step": compiles_per_step, "config": cfg}


if __name__ == "__main__":
    main()
