"""Serving launcher: batched generation with the KV-cache engine.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b

Serves ``--batch`` requests of ``--prompt-len`` random tokens each and
``--max-new`` new tokens, from weights drawn from a fixed seed.  The default
architecture fits one 16 GB chip at full size; ``--smoke`` swaps in the
reduced config for a CPU.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import registry
from repro.launch import compiles
from repro.models import model as model_lib
from repro.serve.engine import Engine, ServeConfig


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=list(registry.ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=1024,
                    help="KV-cache length per request")
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    if args.prompt_len + args.max_new > args.max_len:
        ap.error("--prompt-len + --max-new exceeds --max-len")

    compiles.enable_cache()
    cfg = registry.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = model_lib.build(cfg)
    # initialised under jit: the float32 draws never land on the device
    params = jax.jit(model.init)(jax.random.key(0))
    engine = Engine(model, params,
                    ServeConfig(max_batch=args.batch, max_len=args.max_len,
                                temperature=args.temperature))
    rng = np.random.default_rng(0)
    prompts = rng.integers(2, cfg.vocab_size,
                           size=(args.batch, args.prompt_len)).tolist()
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new=args.max_new)
    seconds = time.perf_counter() - t0
    n_new = sum(len(o) - len(p) for p, o in zip(prompts, outs))
    for i, (p, o) in enumerate(zip(prompts, outs)):
        print(f"req{i}: {len(p)} prompt tokens -> generated={o[len(p):]}")
    print(f"{cfg.name}: answered {len(outs)} requests with {n_new} new "
          f"tokens in {seconds:.3f} s (compilation included)")
    return {"engine": engine, "prompts": prompts, "outputs": outs,
            "seconds": seconds}


if __name__ == "__main__":
    main()
