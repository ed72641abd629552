"""Fault-tolerant training loop.

Production behaviours implemented (and covered by tests):

* **checkpoint/restart**: periodic async atomic checkpoints; on construction
  the trainer auto-resumes from the newest valid checkpoint, and the data
  pipeline replays deterministically from the restored step.
* **straggler mitigation**: a wall-clock SLO per step (rolling median x
  ``straggler_factor``); breaching steps are counted and surfaced so an
  orchestrator can evict the slow host.  (On real fleets the same watchdog
  triggers the pre-emption path; here it is fully testable logic.)
* **failure retry**: transient step failures (injectable for tests) retry up
  to ``max_retries`` from the last good state — the state update is
  transactional (functional state, no in-place mutation).  A step that
  donates its state and fails after consuming it leaves nothing to retry
  from, so its error surfaces at once.
* **elastic restart**: checkpoints restore onto a different mesh/device
  count via ``Checkpointer.restore(shardings=...)``.

What an operator can see: run ``Trainer.run`` under ``jax.profiler.trace``
and its thread shows, per step and each with ``step=<k>``, the spans
``trainer.data_wait`` (taking the batch from the prefetch queue, and once
more after the last step), ``trainer.step`` (the step call through
``block_until_ready``, retries included), ``trainer.log`` (reading the loss
into the log) and ``trainer.checkpoint`` (starting an async save).
"""

from __future__ import annotations

import dataclasses
import itertools
import statistics
import time
from typing import Callable

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticCorpus


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    log_every: int = 10
    straggler_factor: float = 3.0
    max_retries: int = 2


class StragglerWatchdog:
    def __init__(self, factor: float):
        self.factor = factor
        self.history: list[float] = []
        self.breaches = 0

    def observe(self, dt: float) -> bool:
        slow = False
        if len(self.history) >= 5:
            slo = statistics.median(self.history) * self.factor
            slow = dt > slo
            if slow:
                self.breaches += 1
        self.history.append(dt)
        if len(self.history) > 50:
            self.history.pop(0)
        return slow


def _consumed(state) -> bool:
    """True once a donating step has deleted the state's buffers."""
    return any(isinstance(x, jax.Array) and x.is_deleted()
               for x in jax.tree.leaves(state))


class Trainer:
    """``ckpt_dir=None`` trains without checkpoints (and never resumes)."""

    def __init__(self, step_fn: Callable, state, data_cfg: DataConfig,
                 ckpt_dir: str | None, cfg: TrainerConfig = TrainerConfig(),
                 fail_hook: Callable[[int], None] | None = None):
        self.step_fn = step_fn
        self.cfg = cfg
        self.ckpt = Checkpointer(ckpt_dir) if ckpt_dir is not None else None
        self.watchdog = StragglerWatchdog(cfg.straggler_factor)
        self.fail_hook = fail_hook          # test hook: raise to simulate
        self.metrics_log: list[dict] = []

        latest = self.ckpt.latest_step() if self.ckpt else None
        if latest is not None:
            state, _ = self.ckpt.restore(state, latest)
            self.start_step = latest
        else:
            self.start_step = 0
        self.state = state
        self.corpus = SyntheticCorpus(data_cfg)

    def run(self) -> dict:
        it = PrefetchIterator(self.corpus, start_step=self.start_step)
        try:
            for step in itertools.count(self.start_step):
                # the queue yields consecutive steps from start_step;
                # taking the batch after the last step too spares close()
                # waiting out the prefetch thread's blocked put
                with jax.profiler.TraceAnnotation("trainer.data_wait",
                                                  step=step):
                    _, batch = next(it)
                if step >= self.cfg.total_steps:
                    break
                t0 = time.perf_counter()
                # retry THIS step from the last good state until the retry
                # budget is exhausted (transient node failures)
                with jax.profiler.TraceAnnotation("trainer.step", step=step):
                    for attempt in range(self.cfg.max_retries + 1):
                        try:
                            if self.fail_hook is not None:
                                self.fail_hook(step)
                            new_state, metrics = self.step_fn(self.state,
                                                              batch)
                            jax.block_until_ready(
                                jax.tree.leaves(metrics)[0])
                            break
                        except Exception:
                            if (attempt == self.cfg.max_retries
                                    or _consumed(self.state)):
                                raise
                self.state = new_state
                dt = time.perf_counter() - t0
                self.watchdog.observe(dt)
                if (step + 1) % self.cfg.log_every == 0:
                    with jax.profiler.TraceAnnotation("trainer.log",
                                                      step=step):
                        self.metrics_log.append(
                            {"step": step + 1,
                             "loss": float(metrics["loss"]),
                             "sec_per_step": dt})
                if self.ckpt and (step + 1) % self.cfg.checkpoint_every == 0:
                    with jax.profiler.TraceAnnotation("trainer.checkpoint",
                                                      step=step):
                        self.ckpt.save_async(self.state, step + 1)
        finally:
            it.close()
            if self.ckpt:
                self.ckpt.wait()
        return {"final_step": min(self.cfg.total_steps, step + 1),
                "straggler_breaches": self.watchdog.breaches,
                "metrics": self.metrics_log}
