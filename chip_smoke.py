"""Run the LM stack's main path once on a TPU chip, through the launchers.

    python chip_smoke.py               # one chip: serve, then train
    python chip_smoke.py --four-chips  # four chips: sharded train step only

One chip: granite-3-2b is served at full size (all 40 layers, published
widths, weights drawn from a seed) through ``repro.launch.serve.main``; the
logits of the cached decode path are then checked against ``Model.forward``
over the same tokens.  It is then trained at published widths with a depth
cut through ``repro.launch.train.main``: the losses must be finite and no
step after the first may compile.

Four chips: the same train step runs on the host mesh over four chips
(tensor-parallel) and on a one-device mesh, from the same config, seed and
batch; their first losses must agree.

Every measurement is printed as a "chip reading".  The last line of
standard output is one JSON object naming the device.  Without a TPU, or
when a check fails, the script exits non-zero and prints no such line.
Everything runs in this one process: the chip belongs to one process.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
OUT = ROOT / "chiprun_out" / "chip_smoke"

SERVE_ARGV = ["--arch", "granite-3-2b", "--batch", "4", "--prompt-len",
              "256", "--max-new", "32", "--max-len", "1024"]
# 4 of 40 layers at batch 8x2048: 3.2 GiB of state and 10.4 GiB of
# temporaries by the v5e compiler's memory analysis, inside 15.75 GiB
TRAIN_ARGV = ["--arch", "granite-3-2b", "--layers", "4", "--batch", "8",
              "--seq", "2048", "--steps", "5"]
FOUR_CHIP_ARGV = ["--arch", "granite-3-2b", "--layers", "4", "--batch", "8",
                  "--seq", "2048", "--steps", "3"]

# Cached decode and the full forward are different XLA programs over bf16
# weights and activations.  Each layer rounds its bf16 outputs at different
# points (bf16 keeps 8 significant bits, a relative step of 2**-8), and the
# differences add up over 40 layers, as a random walk: sqrt(40) * 2**-8 is
# 2.5% per rounding point.  The bound is on the root-mean-square logit
# difference over the root-mean-square reference logit.  On a CPU, a
# narrow 40-layer copy of the model gives 1.9%, and the same copy with the
# decode position off by one gives 13%.
LOGIT_TOL = 0.06
# The four-chip step reduces its tensor-parallel partial sums in bf16 in
# another order than one chip does; the first loss (about ln(49155) = 10.8
# at random init) may move by a few bf16 steps of the logits, no more.
LOSS_TOL = 0.02


def reading(name: str, value) -> None:
    print(f"chip reading: {name}: {value}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def report_compiles(log) -> None:
    for name, sec in sorted(log.seconds.items(), key=lambda kv: -kv[1]):
        if sec >= 0.5:
            reading(f"compile seconds of {name}", f"{sec:.2f}")
    reading("compile seconds, all programs",
            f"{sum(log.seconds.values()):.2f}")


def report_memory(jax, phase: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    reading(f"peak_bytes_in_use after {phase}",
            stats.get("peak_bytes_in_use", "not reported"))


def cached_logit_gap(engine, prompts, outputs) -> tuple[float, float]:
    """Replay the served tokens through the engine's own prefill and decode
    programs, and compare those logits with one ``Model.forward`` pass over
    the same tokens.  The replay's greedy choices must be the served
    tokens.  Returns the relative RMS logit difference over all positions,
    and over the prefill's position alone (the two programs' bf16 noise
    floor, with no cache read yet)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params = engine.model, engine.params
    n_prompt = len(prompts[0])
    n = min(len(o) for o in outputs)
    tokens = jnp.asarray(np.asarray([o[:n] for o in outputs], np.int32))
    cache = model.init_cache(tokens.shape[0], engine.cfg.max_len)
    logits, cache = engine.prefill(params, cache, tokens[:, :n_prompt], None)
    cached = [logits[:, -1]]
    for t in range(n_prompt, n - 1):
        logits, cache = engine.decode(params, cache, tokens[:, t:t + 1], None)
        cached.append(logits[:, -1])
    cached = np.asarray(jnp.stack(cached, 1), np.float32)
    full = jax.jit(model.forward)(params, {"tokens": tokens})
    full = np.asarray(full[:, n_prompt - 1:n - 1], np.float32)
    check(bool(np.isfinite(cached).all()), "cached-decode logits are finite")
    check(np.array_equal(cached.argmax(-1), np.asarray(tokens[:, n_prompt:])),
          "replayed greedy tokens equal the served tokens")

    def rel_rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    return rel_rms(cached, full), rel_rms(cached[:, 0], full[:, 0])


def serve_phase(argv: list[str]) -> None:
    import jax
    from repro.launch import compiles, serve

    with compiles.CompileLog() as log:
        res = serve.main(argv)
        outputs, prompts = res["outputs"], res["prompts"]
        n_new = sum(len(o) - len(p) for p, o in zip(prompts, outputs))
        reading("requests answered", len(outputs))
        reading("tokens generated", n_new)
        reading("serve seconds, compilation included", f"{res['seconds']:.3f}")
        check(len(outputs) >= 4 and n_new >= 16 * len(outputs),
              "at least 4 requests with 16 new tokens each")
        gap, gap_prefill = cached_logit_gap(res["engine"], prompts, outputs)
    reading("cached-decode vs forward logits, relative RMS difference",
            f"{gap:.5f} (limit {LOGIT_TOL}; prefill position alone "
            f"{gap_prefill:.5f})")
    check(gap <= LOGIT_TOL, "cached-decode logits match Model.forward")
    report_compiles(log)
    report_memory(jax, "serving")


def train_phase(argv: list[str]) -> dict:
    import math

    import jax
    from repro.launch import compiles, train

    ckpt = OUT / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    with compiles.CompileLog() as log:
        res = train.main([*argv, "--ckpt-dir", str(ckpt)])
    losses = [m["loss"] for m in res["metrics"]]
    reading("training losses", losses)
    reading("programs compiled per step", res["compiles_per_step"])
    warm = [m["sec_per_step"] for m in res["metrics"][1:]]
    if warm:
        reading("seconds per step after warm-up",
                f"{sum(warm) / len(warm):.4f}")
    check(len(losses) >= 3 and all(math.isfinite(x) for x in losses),
          "at least 3 steps with finite losses")
    check(res["compiles_per_step"][0] == 1
          and not any(res["compiles_per_step"][1:]),
          "the step compiles once, at step 1")
    report_compiles(log)
    report_memory(jax, "training")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips, "
                         "against the same step on one")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 1
    from repro.launch import compiles
    OUT.mkdir(parents=True, exist_ok=True)
    reading("device", f"{devices[0].device_kind} x {len(devices)}")
    reading("compilation cache", compiles.enable_cache())
    t0 = time.perf_counter()
    if args.four_chips:
        check(len(devices) == 4, "four chips")
        four = train_phase([*FOUR_CHIP_ARGV, "--devices", "4"])
        one = train_phase([*FOUR_CHIP_ARGV, "--devices", "1"])
        four, one = four["metrics"][0]["loss"], one["metrics"][0]["loss"]
        reading("first loss, 4 chips vs 1 chip", f"{four} vs {one}")
        check(abs(four - one) <= LOSS_TOL,
              f"4-chip and 1-chip first losses within {LOSS_TOL}")
    else:
        serve_phase(SERVE_ARGV)
        train_phase(TRAIN_ARGV)
    reading("total seconds", f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
