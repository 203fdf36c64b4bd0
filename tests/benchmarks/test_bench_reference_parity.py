"""The two plain references against the program's models on seeded weights,
at a tiny size on the CPU in float32: logits, loss and every leaf's
gradient (for Perceiver AR with the training step's own prefix dropout), and
for Perceiver AR the program's prefill and cached decode against the
reference's one full forward."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.adapters import common
from benchmarks.reference import training
from benchmarks.traffic.train_batches import TrainBatches
from conftest import TOY_AR, TOY_FEEDS, TOY_MLM

CASES = [(TOY_AR, "toy-fit-ar"), (TOY_MLM, "toy-fit-mlm")]


def _both(cfg, traffic, seed=11):
    ref = importlib.import_module(f"benchmarks.reference.{cfg['reference']}")
    adapter = importlib.import_module(f"benchmarks.adapters.{cfg['program']}")
    family = importlib.import_module(f"perceiver_io_tpu.scripts.text.{cfg['program']}").FAMILY
    model_cfg = adapter.model_config(cfg)
    model = type(family.build_model(model_cfg, None))(model_cfg, dtype=jnp.float32)
    tree = common.seeded_tree(ref, cfg, adapter.path_of, seed)
    flat = training.seeded_params(ref, cfg, seed)
    batch = TrainBatches(TOY_FEEDS[traffic], seed).next_batch()
    return ref, adapter, family, model_cfg, model, tree, flat, batch


@pytest.mark.parametrize("cfg,traffic", CASES, ids=["perceiver_ar", "perceiver_io_mlm"])
def test_adapter_covers_the_programs_tree(cfg, traffic):
    ref, adapter, family, model_cfg, model, tree, flat, batch = _both(cfg, traffic)
    args, kwargs = family.init_args(model_cfg, batch)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), *args, **kwargs))["params"]
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) == \
        jax.tree_util.tree_map(lambda x: x.shape, tree)
    names = sorted(flat)
    back = common.leaves_by_name(tree, names, adapter.path_of)
    assert all(np.array_equal(back[n], flat[n]) for n in names)


@pytest.mark.parametrize("cfg,traffic", CASES, ids=["perceiver_ar", "perceiver_io_mlm"])
def test_logits_agree(cfg, traffic):
    ref, adapter, family, model_cfg, model, tree, flat, batch = _both(cfg, traffic)
    ids, pad = batch["input_ids"], batch["pad_mask"].copy()
    if cfg["program"] == "clm":
        pad[:, :5] = True  # left padding shifts positions and masks keys
        prefix_len = ids.shape[1] - cfg["max_latents"]
        got = model.apply({"params": tree}, ids, prefix_len, pad_mask=pad)
        want = ref.logits(flat, cfg, ids, prefix_len, pad)
    else:
        pad[:, -5:] = True
        got = model.apply({"params": tree}, ids, pad_mask=pad)
        want = ref.logits(flat, cfg, ids, pad)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("cfg,traffic", CASES, ids=["perceiver_ar", "perceiver_io_mlm"])
def test_loss_and_gradients_agree(cfg, traffic):
    ref, adapter, family, model_cfg, model, tree, flat, batch = _both(cfg, traffic)
    step_rng = jax.random.fold_in(jax.random.PRNGKey(0), 1)  # the trainer's key of step 1
    (loss, _), grads = jax.value_and_grad(family.make_loss(model, model_cfg), has_aux=True)(
        tree, batch, step_rng)
    aux = ref.train_aux(cfg, 0, 1, batch)
    want_loss, want = training.loss_and_grads(training.make_block(ref, cfg), flat, batch, aux, 2)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    got = common.leaves_by_name(grads, sorted(flat), adapter.path_of)
    scale = np.median([float(jnp.abs(g).max()) for g in want.values()])
    for name in want:  # key biases have no gradient under softmax: absolute floor
        assert float(jnp.abs(got[name] - want[name]).max()) < 1e-4 * max(
            float(jnp.abs(want[name]).max()), scale), name


def test_adamw_follows_optax_under_the_clis_schedule():
    import optax

    from perceiver_io_tpu.training.lrs import cosine_with_warmup

    opt = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01,
           "schedule": "cosine", "warmup_steps": 3, "training_steps": 10, "min_fraction": 0.1}
    tx = optax.adamw(cosine_with_warmup(1e-3, warmup_steps=3, training_steps=10),
                     weight_decay=0.01)
    p = {"w": jnp.linspace(-1, 1, 7), "b": jnp.ones(3)}
    q, state, ref_state = p, tx.init(p), None
    for i in range(6):
        g = jax.tree_util.tree_map(lambda x: jnp.sin(x * (i + 1)), p)
        updates, state = tx.update(g, state, q)
        q = optax.apply_updates(q, updates)
        p, ref_state = training.adamw_step(opt, p, g, ref_state)
        for k in p:
            np.testing.assert_allclose(p[k], q[k], rtol=2e-6, atol=1e-9)


def test_prefill_then_cached_decode_agrees_with_the_full_forward():
    """Greedy tokens from the program's prefill and cached decode (latents
    growing, as the serving cells will run it): under the reference's one
    full forward over prompt and tokens, each served token's logit lies at
    the reference's best, to float32 rounding."""
    from perceiver_io_tpu.inference.generate import GenerationConfig, generate

    ref, adapter, family, model_cfg, model, tree, flat, batch = _both(TOY_AR, "toy-fit-ar")
    prompt_len, num_latents, new = 96, 32, 16
    prompt = batch["input_ids"][:2, :prompt_len]
    tokens = generate(model, tree, jnp.asarray(prompt),
                      GenerationConfig(max_new_tokens=new, num_latents=num_latents),
                      use_cache=True, decode_strategy="cached")
    full = np.concatenate([prompt, np.asarray(tokens)], axis=1)
    logits = ref.logits(flat, TOY_AR, full, prompt_len - num_latents)
    at = logits[:, num_latents - 1:num_latents - 1 + new]  # predictions of the new tokens
    served = np.take_along_axis(np.asarray(at), np.asarray(tokens)[..., None], axis=-1)[..., 0]
    gap = np.asarray(at).max(-1) - served
    assert gap.max() < 1e-4, gap.max()
