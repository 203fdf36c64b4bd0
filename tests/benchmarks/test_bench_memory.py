"""The benchmark holds the parameters once on each side. The train driver
keeps no tree of its own beside the trainer's state (the tree it hands
``fit`` is deleted once the state holds its copy, the start is made again
for the one reading that needs it), and the reference's AdamW and its
gradient sum update in place."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.drivers import train
from benchmarks.reference import training
from conftest import TOY_AR, TOY_LFM2, TOY_MLM, build_toy_lfm2_root


def _buffers(arrays) -> set:
    return {s.data.unsafe_buffer_pointer() for a in arrays for s in a.addressable_shards}


@pytest.mark.parametrize("cell", ["toy-ar-train", "toy-lfm2-train"])
def test_driver_holds_no_tree_beside_the_trainers_state(tmp_path, monkeypatch, cell):
    """Whenever the loop is handed one of batches 1 to 4 (before step 1, and
    after each checked step), what lives on the device outside the trainer's
    state and what was there before the run is less than the largest leaf;
    and the change of each leaf reads as it does from a kept copy of the
    start (the form the driver had)."""
    root, files = build_toy_lfm2_root(tmp_path)
    before = _buffers(jax.live_arrays())
    seen = {}
    real_next = train.Stream.__next__

    def watched_next(stream):
        batch = real_next(stream)  # batch k is handed out, step k not yet dispatched
        k = stream.k
        if k <= train.CHECK_STEPS + 1:
            state = stream.trainer.state
            held = before | _buffers(jax.tree_util.tree_leaves(state))
            outside = [(s.data.nbytes, a.shape) for a in jax.live_arrays() for s in a.addressable_shards
                       if s.data.unsafe_buffer_pointer() not in held]
            leaf = max(s.data.nbytes for a in jax.tree_util.tree_leaves(state.params)
                       for s in a.addressable_shards)
            seen.setdefault("outside", []).append((k, sum(n for n, _ in outside), leaf, outside))
            now = stream.leaves(state.params)
            if k == 1:
                # a copy: a view of the device's buffer would keep it alive
                seen["start"] = {n: np.array(v, copy=True) for n, v in now.items()}
            if k == train.CHECK_STEPS + 1:
                kept = {n: jnp.asarray(v) for n, v in seen["start"].items()}
                seen["delta_norms"] = jax.jit(
                    lambda a, b: train._norms({n: a[n] - b[n] for n in a}))(now, kept)
                seen["stream"] = stream
        return batch

    monkeypatch.setattr(train.Stream, "__next__", watched_next)
    result = harness.run_cell(root, cell, 2**31 + 21, 0.2, False, files_dir=files, need_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert [k for k, *_ in seen["outside"]] == [1, 2, 3, 4]
    for k, outside, leaf, what in seen["outside"]:
        assert outside < leaf, (k, outside, leaf, sorted(what)[-8:])
    got = seen["stream"].delta_norms
    assert sorted(got) == sorted(seen["delta_norms"]) and len(got) > 10
    for name, want in seen["delta_norms"].items():
        assert float(got[name]) == float(want), name


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _undonated(params, grads, mu, nu, b1, b2, eps, wd, lr, count):
    """``training._adamw`` without its donation: the old trees stay."""
    return training._adamw.__wrapped__(params, grads, mu, nu, b1, b2, eps, wd, lr, count)


def _undonated_adamw(opt, params, grads, state):
    """``adamw_step`` as it was before it updated in place: one tree of
    zeros for both moments, nothing donated, nothing deleted."""
    if state is None:
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        state = (zeros, zeros, 0)
    mu, nu, count = state
    params, mu, nu = _undonated(
        params, grads, mu, nu, opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
        jnp.float32(training.lr_at(opt, count)), jnp.float32(count + 1))
    return params, (mu, nu, count + 1)


def test_adamw_updates_in_place_and_to_the_bit():
    opt = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01,
           "schedule": "cosine", "warmup_steps": 2, "training_steps": 10, "min_fraction": 0.1}
    make = lambda: {"w": jnp.linspace(-1.0, 1.0, 35).reshape(5, 7), "b": jnp.ones(3)}
    p, q, state, plain = make(), make(), None, None
    for i in range(3):
        grads = [jax.tree_util.tree_map(lambda x: jnp.sin(x * (i + 1.5)), q) for _ in range(2)]
        given = [x for x in jax.tree_util.tree_leaves((p, grads[0], state)) if isinstance(x, jax.Array)]
        assert len(given) == (4 if state is None else 8)
        p, state = training.adamw_step(opt, p, grads[0], state)
        assert all(x.is_deleted() for x in given), i
        q, plain = _undonated_adamw(opt, q, grads[1], plain)
        assert state[2] == plain[2] == i + 1 and not any(x.is_deleted() for x in jax.tree_util.tree_leaves(grads[1]))
        for name in q:
            assert (np.asarray(p[name]) == np.asarray(q[name])).all(), (i, name)
            for mine, theirs in zip(state[:2], plain[:2]):
                assert (np.asarray(mine[name]) == np.asarray(theirs[name])).all(), (i, name)


def test_gradient_sum_over_blocks_is_made_in_place():
    """Each block after the first is added into the sum's buffers, and the
    sum is what the one-pass mean's gradient is."""
    params = {"w": jnp.arange(6.0).reshape(2, 3) / 7}
    batch = {"input_ids": np.arange(8, dtype=np.int32).reshape(8, 1)}
    made = []

    def block(p, blk, aux):
        x = jnp.asarray(blk["input_ids"], jnp.float32)
        g = {"w": jnp.full((2, 3), x.sum()) * p["w"]}
        made.append(g["w"])
        return x.sum(), x.shape[0], g

    loss, grads = training.loss_and_grads(block, params, batch, None, 2)
    assert len(made) == 4 and made[0].is_deleted() and not made[1].is_deleted()
    assert float(loss) == 28.0 / 8
    np.testing.assert_allclose(grads["w"], 28.0 / 8 * np.asarray(params["w"]), rtol=1e-6)
    assert not params["w"].is_deleted()


@pytest.mark.parametrize("config", [TOY_AR, TOY_MLM, TOY_LFM2], ids=["clm", "mlm", "lm"])
def test_state_keeps_a_copy_of_its_own_of_the_tree_it_was_handed(config):
    """What lets the driver delete the tree it handed ``fit``:
    ``create_train_state(..., initial_params=tree)`` copies (it donates
    nothing and forwards nothing), so with the handed tree deleted the
    state's parameters are still the seeded tree, to the bit."""
    import importlib

    import optax

    from perceiver_io_tpu.parallel import MeshConfig, make_mesh
    from perceiver_io_tpu.parallel.train_step import create_train_state

    ref = importlib.import_module(f"benchmarks.reference.{config['reference']}")
    adapter = importlib.import_module(f"benchmarks.adapters.{config['program']}")
    seeded_tree = lambda: adapter.common.seeded_tree(ref, config, adapter.path_of, 2**31 + 3)
    handed = seeded_tree()
    state, _ = create_train_state(None, optax.adamw(1e-3), make_mesh(MeshConfig()), initial_params=handed)
    for leaf in jax.tree_util.tree_leaves(handed):
        leaf.delete()
    again = jax.tree_util.tree_leaves_with_path(seeded_tree())
    kept = jax.tree_util.tree_leaves_with_path(state.params)
    assert len(kept) == len(again) == len(ref.param_shapes(config))
    for (path, a), (path_b, b) in zip(kept, again):
        assert path == path_b and not a.is_deleted()
        assert (np.asarray(a) == np.asarray(b)).all(), path
