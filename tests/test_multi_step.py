"""Multi-step-in-jit execution (``multi_steps`` / ``steps_per_execution``).

Oracle: a block of N scanned optimizer steps must reproduce the N-sequential-
single-steps trajectory exactly — same per-step rng (fold_in-derived), same
data order, same final params. The reference has no equivalent (torch runs a
Python loop per step); this is the TPU-native amortization of host dispatch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.parallel import (
    MeshConfig,
    create_train_state,
    make_mesh,
    make_train_step,
    shard_batch,
)
from perceiver_io_tpu.training.tasks import clm_loss_fn
from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

VOCAB, SEQ, LATENTS, CH, HEADS = 32, 16, 8, 32, 4


def tiny_clm():
    cfg = CausalLanguageModelConfig(
        vocab_size=VOCAB,
        max_seq_len=SEQ,
        max_latents=LATENTS,
        num_channels=CH,
        num_heads=HEADS,
        num_self_attention_layers=2,
        cross_attention_dropout=0.0,
    )
    return CausalLanguageModel(cfg), cfg


def _batches(n, batch_size=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, VOCAB, size=(batch_size, SEQ + 1), dtype=np.int32)
        out.append({"input_ids": ids[:, :-1], "labels": ids[:, 1:]})
    return out


def test_multi_step_matches_sequential():
    model, cfg = tiny_clm()
    mesh = make_mesh(MeshConfig(data=2))
    prefix_len = SEQ - LATENTS

    def init():
        return model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32), prefix_len
        )["params"]

    loss_fn = clm_loss_fn(model, LATENTS)
    tx = optax.adam(1e-2)
    n, k = 6, 3
    batches = _batches(n)
    keys = [jax.random.fold_in(jax.random.PRNGKey(7), i) for i in range(n)]

    # sequential single steps
    state, shardings = create_train_state(init, tx, mesh)
    step = make_train_step(loss_fn, mesh, shardings, grad_clip_norm=1.0)
    seq_losses = []
    with mesh:
        for i in range(n):
            state, m = step(state, shard_batch(batches[i], mesh), keys[i])
            seq_losses.append(float(m["loss"]))
    seq_params = jax.device_get(state.params)

    # two scanned blocks of k steps each
    state, shardings = create_train_state(init, tx, mesh)
    multi = make_train_step(
        loss_fn, mesh, shardings, grad_clip_norm=1.0, multi_steps=k
    )
    blk_losses = []
    with mesh:
        for b0 in range(0, n, k):
            stacked = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *batches[b0:b0 + k]
            )
            stacked = shard_batch(stacked, mesh, stacked_steps=True)
            state, m = multi(state, stacked, jnp.stack(keys[b0:b0 + k]))
            blk_losses.extend(float(x) for x in m["loss"])
    blk_params = jax.device_get(state.params)

    # a scan over k steps and k calls are the same float32 sums in another
    # association (XLA fuses across the scan's body), so equal to rounding:
    # Adam at 1e-2 moves a weight by about 1e-2 a step, and 1e-6 is a
    # ten-thousandth of one step
    np.testing.assert_allclose(blk_losses, seq_losses, rtol=1e-5, atol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        blk_params, seq_params,
    )


def test_trainer_steps_per_execution_matches_single(tmp_path):
    model, cfg = tiny_clm()
    prefix_len = SEQ - LATENTS

    def init():
        return model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32), prefix_len
        )["params"]

    val = _batches(2, seed=99)

    finals = {}
    log_steps = {}
    for k_exec in (1, 4):
        mesh = make_mesh(MeshConfig(data=2))
        root = tmp_path / f"k{k_exec}"
        trainer = Trainer(
            TrainerConfig(
                max_steps=10,
                steps_per_execution=k_exec,
                # val at 5 and 10: blocks run at [1-4] and [6-9], while steps
                # 5 and 10 are forced single by _block_ok — both the fused
                # path and the boundary rejection are exercised
                val_check_interval=5,
                log_every_n_steps=2,
                enable_checkpointing=False,
                enable_tensorboard=False,
                default_root_dir=str(root),
            ),
            mesh,
            clm_loss_fn(model, LATENTS),
            optax.adam(1e-2),
        )
        state = trainer.fit(init, iter(_batches(10)), val_data=lambda: iter(val))
        assert int(jax.device_get(state.step)) == 10
        finals[k_exec] = jax.device_get(state.params)
        import json

        rows = [json.loads(l) for l in open(root / "metrics.jsonl")]
        log_steps[k_exec] = [r["step"] for r in rows if "train/loss" in r]

    # the flush signature proves blocks actually executed: single-step runs
    # flush on every multiple of 2, the blocked run flushes at block ends
    assert log_steps[1] == [2, 4, 5, 6, 8, 10], log_steps[1]
    assert log_steps[4] == [4, 5, 9, 10], log_steps[4]
    # equal to rounding, as in test_multi_step_matches_sequential
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        finals[1], finals[4],
    )


def test_multi_step_composes_with_grad_accum():
    """grad_accum_steps × multi_steps in one jitted program equals the
    sequential accumulated steps (the flagship clm.sh config uses both)."""
    model, cfg = tiny_clm()
    mesh = make_mesh(MeshConfig(data=2))
    prefix_len = SEQ - LATENTS

    def init():
        return model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32), prefix_len
        )["params"]

    loss_fn = clm_loss_fn(model, LATENTS)
    batches = _batches(2)
    keys = [jax.random.fold_in(jax.random.PRNGKey(5), i) for i in range(2)]

    state, sh = create_train_state(init, optax.adam(1e-2), mesh)
    step = make_train_step(loss_fn, mesh, sh, grad_accum_steps=2)
    with mesh:
        for i, b in enumerate(batches):
            state, _ = step(state, shard_batch(b, mesh), keys[i])
    ref_params = jax.device_get(state.params)

    state, sh = create_train_state(init, optax.adam(1e-2), mesh)
    both = make_train_step(loss_fn, mesh, sh, grad_accum_steps=2, multi_steps=2)
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
    with mesh:
        state, _ = both(
            state, shard_batch(stacked, mesh, stacked_steps=True), jnp.stack(keys)
        )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
        jax.device_get(state.params), ref_params,
    )


def test_ragged_block_raises_clear_error(tmp_path):
    """A user iterable yielding a short last batch under
    ``steps_per_execution>1`` must fail with the actual ``k_exec`` integer and
    both shape lists in the message (not an opaque np.stack broadcast error,
    and not a jit tracer repr — the check is host-side Python)."""
    model, cfg = tiny_clm()
    prefix_len = SEQ - LATENTS

    def init():
        return model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32), prefix_len
        )["params"]

    good = _batches(1, batch_size=8)[0]
    short = _batches(1, batch_size=5, seed=1)[0]  # ragged: 5 != 8

    mesh = make_mesh(MeshConfig(data=1))
    trainer = Trainer(
        TrainerConfig(
            max_steps=2,
            steps_per_execution=2,
            enable_checkpointing=False,
            enable_tensorboard=False,
            default_root_dir=str(tmp_path),
        ),
        mesh,
        clm_loss_fn(model, LATENTS),
        optax.adam(1e-2),
    )
    with pytest.raises(ValueError) as excinfo:
        trainer.fit(init, iter([good, short]))
    msg = str(excinfo.value)
    assert "steps_per_execution=2" in msg, msg  # the integer, not a tracer repr
    assert str([(8, SEQ), (8, SEQ)]) in msg, msg
    assert str([(5, SEQ), (5, SEQ)]) in msg, msg
