"""Training infrastructure: optimizer, data, checkpointing, fault tolerance."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import registry
from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticCorpus
from repro.models import model as model_lib
from repro.optim import adamw
from repro.train import train_step as ts
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = registry.get("granite-3-2b").reduced()
    model = model_lib.build(cfg)
    opt = adamw.AdamWConfig(lr=1e-2, total_steps=50, warmup_steps=2)
    state = ts.make_train_state(model, opt, jax.random.key(0))
    step = jax.jit(ts.make_train_step(model, opt))
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    return cfg, model, opt, state, step, data


class TestOptimizer:
    def test_loss_decreases(self, tiny):
        cfg, model, opt, state, step, data = tiny
        corpus = SyntheticCorpus(data)
        batch = {k: jnp.asarray(v) for k, v in corpus.batch_at(0).items()}
        losses = []
        for _ in range(8):
            state, m = step(state, batch)      # overfit one batch
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.2, losses

    def test_schedule_warmup_and_decay(self):
        cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
        assert float(adamw.schedule(cfg, jnp.asarray(5))) == pytest.approx(0.5)
        assert float(adamw.schedule(cfg, jnp.asarray(10))) == pytest.approx(1.0)
        end = float(adamw.schedule(cfg, jnp.asarray(100)))
        assert end == pytest.approx(cfg.min_lr_ratio, abs=1e-3)

    def test_8bit_state_tracks_fp32(self):
        """8-bit AdamW reaches the same optimum as fp32 on a quadratic."""
        p0 = {"w": jnp.asarray(np.linspace(-2, 2, 512), jnp.float32)}
        cfgs = {b: adamw.AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=0,
                                     warmup_steps=0, total_steps=100,
                                     min_lr_ratio=1.0, state_bits=b)
                for b in (32, 8)}
        outs = {}
        for bits, cfg in cfgs.items():
            params = dict(p0)
            state = adamw.init_state(cfg, params)
            for _ in range(30):
                grads = {"w": params["w"]}      # d/dw (w^2/2)
                params, state, _ = adamw.apply_updates(cfg, params, grads,
                                                       state)
            outs[bits] = np.asarray(params["w"])
        # both descend |w| from mean 1.0 toward zero at the same rate
        # (Adam's effective step shrinks near the optimum; 30 steps at
        # lr=0.1 lands around 0.15) and agree in aggregate
        assert np.abs(outs[32]).mean() < 0.2
        assert np.abs(outs[8]).mean() < 0.25
        assert np.abs(outs[8] - outs[32]).mean() < 0.06

    def test_microbatching_equivalent(self, tiny):
        cfg, model, opt, state, _, data = tiny
        corpus = SyntheticCorpus(data)
        batch = {k: jnp.asarray(v) for k, v in corpus.batch_at(1).items()}
        s1 = jax.jit(ts.make_train_step(model, opt, ts.TrainSettings(1)))
        s2 = jax.jit(ts.make_train_step(model, opt, ts.TrainSettings(2)))
        st1, m1 = s1(state, batch)
        st2, m2 = s2(state, batch)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                                  rel=1e-3)
        for a, b in zip(jax.tree.leaves(st1["params"]),
                        jax.tree.leaves(st2["params"])):
            # bf16 grad reassociation passes through Adam's normalizer, so
            # near-zero entries see amplified relative error
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=3e-2, atol=6e-3)


class TestData:
    def test_deterministic_per_step(self):
        data = DataConfig(vocab_size=100, seq_len=16, global_batch=4, seed=7)
        c = SyntheticCorpus(data)
        np.testing.assert_array_equal(c.batch_at(3)["tokens"],
                                      c.batch_at(3)["tokens"])
        assert not np.array_equal(c.batch_at(3)["tokens"],
                                  c.batch_at(4)["tokens"])

    def test_prefetch_resumes_at_step(self):
        data = DataConfig(vocab_size=100, seq_len=16, global_batch=4)
        c = SyntheticCorpus(data)
        it = PrefetchIterator(c, start_step=5)
        step, batch = next(it)
        it.close()
        assert step == 5
        np.testing.assert_array_equal(batch["tokens"],
                                      c.batch_at(5)["tokens"])


class TestCheckpointer:
    def test_roundtrip_and_latest(self, tiny, tmp_path):
        _, _, _, state, _, _ = tiny
        ck = Checkpointer(tmp_path)
        ck.save(state, 10)
        ck.save(state, 20)
        assert ck.latest_step() == 20
        restored, step = ck.restore(jax.eval_shape(lambda: state))
        assert step == 20
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_atomic_no_partial_checkpoint(self, tiny, tmp_path):
        """A .tmp directory must never be considered a valid checkpoint."""
        _, _, _, state, _, _ = tiny
        ck = Checkpointer(tmp_path)
        (tmp_path / "step_00000099.tmp").mkdir()
        assert ck.latest_step() is None
        ck.save(state, 5)
        assert ck.latest_step() == 5

    def test_structure_mismatch_rejected(self, tiny, tmp_path):
        _, _, _, state, _, _ = tiny
        ck = Checkpointer(tmp_path)
        ck.save(state, 1)
        with pytest.raises(ValueError):
            ck.restore({"just": jnp.zeros(3)})

    def test_async_save(self, tiny, tmp_path):
        _, _, _, state, _, _ = tiny
        ck = Checkpointer(tmp_path)
        ck.save_async(state, 42)
        ck.wait()
        assert ck.latest_step() == 42


class TestTrainerFaultTolerance:
    def _mk(self, tiny, tmp_path, fail_hook=None, total=12):
        cfg, model, opt, state, step, data = tiny
        state = ts.make_train_state(model, opt, jax.random.key(1))
        return Trainer(step, state, data, str(tmp_path),
                       TrainerConfig(total_steps=total, checkpoint_every=5,
                                     log_every=4, max_retries=2),
                       fail_hook=fail_hook)

    def test_runs_and_checkpoints(self, tiny, tmp_path):
        tr = self._mk(tiny, tmp_path)
        out = tr.run()
        assert out["final_step"] == 12
        assert tr.ckpt.latest_step() == 10

    def test_transient_failure_retried(self, tiny, tmp_path):
        boom = {"left": 2}

        def hook(step):
            if step == 3 and boom["left"] > 0:
                boom["left"] -= 1
                raise RuntimeError("injected node failure")

        tr = self._mk(tiny, tmp_path, fail_hook=hook)
        out = tr.run()
        assert out["final_step"] == 12       # survived the injected failures
        assert boom["left"] == 0

    def test_permanent_failure_raises(self, tiny, tmp_path):
        def hook(step):
            if step == 3:
                raise RuntimeError("persistent failure")

        tr = self._mk(tiny, tmp_path, fail_hook=hook)
        with pytest.raises(RuntimeError):
            tr.run()

    def test_failure_after_donation_surfaces_first_error(self, tiny,
                                                         tmp_path):
        """A step that consumed its donated state is not retried on the
        deleted buffers: the error it raised is the one that surfaces."""
        cfg, model, opt, _, _, data = tiny
        donating = jax.jit(ts.make_train_step(model, opt),
                           donate_argnums=(0,))
        calls = []

        def step(state, batch):
            calls.append(state)
            donating(state, batch)
            raise RuntimeError("device fault after donation")

        state = ts.make_train_state(model, opt, jax.random.key(1))
        tr = Trainer(step, state, data, str(tmp_path),
                     TrainerConfig(total_steps=3, max_retries=2))
        with pytest.raises(RuntimeError, match="device fault after donation"):
            tr.run()
        assert len(calls) == 1
        assert jax.tree.leaves(state)[0].is_deleted()

    def test_runs_without_checkpoint_dir(self, tiny):
        cfg, model, opt, _, step, data = tiny
        state = ts.make_train_state(model, opt, jax.random.key(1))
        tr = Trainer(step, state, data, None,
                     TrainerConfig(total_steps=3, checkpoint_every=1,
                                   log_every=1))
        out = tr.run()
        assert tr.ckpt is None and tr.start_step == 0
        assert [m["step"] for m in out["metrics"]] == [1, 2, 3]

    def test_resume_from_checkpoint(self, tiny, tmp_path):
        tr = self._mk(tiny, tmp_path, total=7)
        tr.run()
        assert tr.ckpt.latest_step() == 5
        # new trainer in same dir resumes at step 5, not 0
        tr2 = self._mk(tiny, tmp_path, total=7)
        assert tr2.start_step == 5

    def test_elastic_restore_different_sharding(self, tiny, tmp_path):
        """Checkpoint saved unsharded restores onto an explicit sharding
        (the degenerate-elastic case runnable on 1 device)."""
        _, _, _, state, _, _ = tiny
        ck = Checkpointer(tmp_path)
        ck.save(state, 3)
        mesh = jax.make_mesh((1,), ("data",))
        from repro.sharding import partition
        shardings = partition.param_shardings(
            jax.eval_shape(lambda: state), mesh)
        restored, _ = ck.restore(jax.eval_shape(lambda: state),
                                 shardings=shardings)
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.shape == {"data": 1}


@pytest.mark.parametrize("start,total", [(0, 3), (2, 4)])
def test_run_emits_one_data_wait_and_step_span_per_step(tiny, tmp_path,
                                                        start, total):
    """Per step: the batch taken from the queue, the step, the log line;
    a save where a checkpoint falls due; and last the batch taken after
    the last step, which ends the loop."""
    from _profile import spans

    cfg, model, opt, _, step, data = tiny
    tr = Trainer(step, ts.make_train_state(model, opt, jax.random.key(1)),
                 data, str(tmp_path),
                 TrainerConfig(total_steps=start + 1, checkpoint_every=2,
                               log_every=1))
    tr.start_step = start
    tr.run()                                  # compile outside the trace
    tr.start_step, tr.cfg.total_steps = start, total
    found = spans(tr.run, "trainer.")
    per_step = []
    for k in range(start, total):
        per_step += ["trainer.data_wait", "trainer.step", "trainer.log"]
        if (k + 1) % 2 == 0:
            per_step.append("trainer.checkpoint")
    assert [n for n, _ in found] == per_step + ["trainer.data_wait"]
    assert [s["step"] for n, s in found if n == "trainer.step"] == list(
        range(start, total))
    assert [s["step"] for n, s in found if n == "trainer.data_wait"] == list(
        range(start, total + 1))
