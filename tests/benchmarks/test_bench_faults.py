"""What decides ``correct`` has been shown to fail, part two: a run of the
harness (its look for a chip skipped) with the timed path broken underneath
comes out not correct, once for each fault a one-chip training cell can have:
a step that returns its state unchanged; half of the batch left out, the mean
taken over the rest. A one-chip cell exchanges nothing between chips and
produces no tokens."""
import jax.numpy as jnp
import pytest

from benchmarks import harness


def _unchanged_state(monkeypatch):
    from perceiver_io_tpu.training import trainer as trainer_module

    real = trainer_module.make_train_step

    def broken(*args, **kwargs):
        kwargs["donate"] = False
        step = real(*args, **kwargs)
        return lambda state, batch, rng: (state, step(state, batch, rng)[1])

    monkeypatch.setattr(trainer_module, "make_train_step", broken)


def _half_batch(monkeypatch):
    from benchmarks.adapters import common

    real = common.build_trainer

    def broken(*args, **kwargs):
        trainer, optimizer = real(*args, **kwargs)
        loss_fn = trainer.loss_fn

        def half(params, batch, rng):
            n = batch["labels"].shape[0] // 2
            keep = (jnp.arange(batch["labels"].shape[0]) < n)[:, None]
            return loss_fn(params, {**batch, "labels": jnp.where(keep, batch["labels"], -100)}, rng)

        trainer.loss_fn = half
        return trainer, optimizer

    monkeypatch.setattr(common, "build_trainer", broken)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch], ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", ["toy-ar-train", "toy-mlm-train"])
def test_broken_timed_path_comes_out_not_correct(toy_root, monkeypatch, cell, fault):
    root, files = toy_root
    fault(monkeypatch)
    result = harness.run_cell(root, cell, 2**31 + 9, 0.2, False, files_dir=files, need_tpu=False)
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("cell", ["toy-ar-train", "toy-mlm-train"])
def test_sound_timed_path_comes_out_correct(toy_root, cell):
    root, files = toy_root
    result = harness.run_cell(root, cell, 2**31 + 9, 0.2, False, files_dir=files, need_tpu=False)
    assert result["correct"] is True, result["compared"]
