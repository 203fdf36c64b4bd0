"""Sharded train-step factory.

One jitted function replaces the reference's whole strategy stack: Lightning
``training_step`` + DDP gradient allreduce + FSDP gather/scatter + fairscale
checkpointing (reference ``perceiver/model/core/lightning.py:44-58``,
``perceiver/scripts/text/clm_fsdp.py:40-83``). Sharding annotations on the
state and batch make XLA emit every collective; the same compiled step runs
on a single chip (degenerate mesh) or a pod.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding

from perceiver_io_tpu.parallel.partition import infer_param_specs


class TrainState(struct.PyTreeNode):
    """Step counter + params + optimizer state. The optimizer transformation
    itself is static (not a pytree leaf), mirroring optax convention."""

    step: jnp.ndarray
    params: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads: Any) -> "TrainState":
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt_state,
        )

    @classmethod
    def create(cls, params: Any, tx: optax.GradientTransformation) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            tx=tx,
        )


def state_shardings(
    state_or_shapes: TrainState, mesh: Mesh, *, min_fsdp_size: int = 2**14
) -> TrainState:
    """Shardings for a TrainState (or its ``jax.eval_shape``): parameter rules
    apply equally to optimizer moments because optax state mirrors the param
    tree — an Adam ``mu`` leaf for ``.../q_proj/kernel`` carries that path
    suffix and picks up the same spec, giving ZeRO-style sharded optimizer
    state for free."""
    specs = infer_param_specs(state_or_shapes, mesh, min_fsdp_size=min_fsdp_size)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)


def create_train_state(
    init_params_fn: Callable[[], Any],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    min_fsdp_size: int = 2**14,
    initial_params: Any = None,
) -> Tuple[TrainState, Any]:
    """Initialize a TrainState *directly sharded* on the mesh: params and
    optimizer state are materialized shard-by-shard under jit, so a model too
    big for one chip never exists unsharded (torch FSDP needs
    ``sync_module_states`` + meta-device tricks for the same effect).

    :param initial_params: concrete warm-start params. These are device_put
        onto the mesh and passed as a jit *argument* — closing over them would
        bake the whole parameter set into the executable as constants.
    :return: (sharded TrainState, matching sharding pytree).
    """
    if initial_params is not None:
        shapes = jax.eval_shape(lambda p: TrainState.create(p, tx), initial_params)
        shardings = state_shardings(shapes, mesh, min_fsdp_size=min_fsdp_size)
        params = jax.device_put(initial_params, shardings.params)
        with mesh:
            state = jax.jit(
                lambda p: TrainState.create(p, tx),
                in_shardings=(shardings.params,),
                out_shardings=shardings,
            )(params)
        return state, shardings

    def init_fn():
        return TrainState.create(init_params_fn(), tx)

    shapes = jax.eval_shape(init_fn)
    shardings = state_shardings(shapes, mesh, min_fsdp_size=min_fsdp_size)
    with mesh:
        state = jax.jit(init_fn, out_shardings=shardings)()
    return state, shardings


LossFn = Callable[..., Tuple[jnp.ndarray, dict]]


def make_train_step(
    loss_fn: LossFn,
    mesh: Mesh,
    shardings: TrainState,
    *,
    grad_clip_norm: Optional[float] = None,
    donate: bool = True,
    grad_accum_steps: int = 1,
    multi_steps: int = 1,
):
    """Build the jitted SPMD training step.

    :param loss_fn: ``(params, batch, rng) -> (loss, metrics)``; must average
        the loss over the *local* batch shard — sharding makes XLA produce the
        global mean's allreduce.
    :param grad_clip_norm: optional global-norm clipping *after* the gradient
        allreduce (matching the FSDP script's manual ``clip_grad_norm_``,
        reference ``clm_fsdp.py:59-67``); also logs the pre-clip grad norm.
    :param grad_accum_steps: gradient accumulation (the role of Lightning's
        ``accumulate_grad_batches``, which the reference's CLM/SAM runs use,
        reference ``examples/training/clm/train.py:50``) — the batch is split
        into this many equal microbatches along dim 0 and a ``lax.scan``
        inside the step averages their gradients before the single optimizer
        update; peak activation memory is one microbatch's. NOTE the batch
        semantics differ from Lightning: Lightning accumulates across N
        loader batches (multiplying the effective batch), this DIVIDES the
        given batch — pass the full effective batch. Averaging is
        mean-of-microbatch-means, the same semantics DDP+accumulation gives
        the reference (per-microbatch masked means weight microbatches
        equally even if their mask counts differ).
    :param multi_steps: with N > 1, the returned function instead runs N
        optimizer steps in ONE device program (``lax.scan`` over a stacked
        batch) — signature ``(state, batches, rngs) -> (state, metrics)``
        where every batch leaf has an extra leading N dim (shard with
        ``shard_batch(..., stacked_steps=True)``), ``rngs`` is N stacked
        keys, and every metric comes back stacked ``(N,)``. Amortizes the
        per-call host dispatch+fetch overhead over N steps; the TPU-native
        replacement for torch's per-step Python training loop.
    :return: jitted ``(state, batch, rng) -> (state, metrics)``. Batches must
        be placed with :func:`~perceiver_io_tpu.parallel.shard_batch` (their
        committed sharding propagates; ``in_shardings`` pins only the state so
        heterogeneous batch pytrees — 2-D tokens, 4-D images — all work).
    """
    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    if multi_steps < 1:
        raise ValueError(f"multi_steps must be >= 1, got {multi_steps}")

    def value_and_grads(params, batch, rng):
        if grad_accum_steps == 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch, rng)

        def to_micro(x):
            if x.shape[0] % grad_accum_steps:
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by "
                    f"grad_accum_steps={grad_accum_steps}"
                )
            return x.reshape(grad_accum_steps, x.shape[0] // grad_accum_steps, *x.shape[1:])

        micro = jax.tree_util.tree_map(to_micro, batch)
        keys = None if rng is None else jax.random.split(rng, grad_accum_steps)

        def body(g_sum, xs):
            mb, r = xs if keys is not None else (xs, None)
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb, r
            )
            return jax.tree_util.tree_map(jnp.add, g_sum, grads), (loss, metrics)

        g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        xs = (micro, keys) if keys is not None else micro
        g_sum, (losses, metrics) = jax.lax.scan(body, g0, xs)
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum_steps, g_sum)
        metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0), metrics)
        return (jnp.mean(losses), metrics), grads

    def step(state: TrainState, batch, rng):
        # published while tracing: the flash kernel shard_maps itself over
        # the ambient mesh (ops/attention.py)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            (loss, metrics), grads = value_and_grads(state.params, batch, rng)
        # scopes for what is no Flax module (those name themselves): they end
        # up in the compiled instructions' op_name, which
        # observability.ledger.op_scopes reads
        if grad_clip_norm is not None:
            with jax.named_scope("grad_clip"):
                gnorm = optax.global_norm(grads)
                scale = jnp.minimum(1.0, grad_clip_norm / (gnorm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            metrics = {**metrics, "grad_norm": gnorm}
        with jax.named_scope("optimizer"):
            state = state.apply_gradients(grads)
        return state, {"loss": loss, **metrics}

    if multi_steps == 1:
        return jax.jit(
            step,
            in_shardings=(shardings, None, None),
            out_shardings=(shardings, None),
            donate_argnums=(0,) if donate else (),
        )

    def multi(state: TrainState, batches, rngs):
        # One device program for `multi_steps` optimizer steps: the host
        # dispatches once per block, not per step.
        return jax.lax.scan(lambda st, xs: step(st, *xs), state, (batches, rngs))

    return jax.jit(
        multi,
        in_shardings=(shardings, None, None),
        out_shardings=(shardings, None),
        donate_argnums=(0,) if donate else (),
    )


def make_eval_step(loss_fn: LossFn, mesh: Mesh, shardings: TrainState):
    """Jitted ``(state, batch) -> metrics`` with deterministic loss."""

    def step(state: TrainState, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            loss, metrics = loss_fn(state.params, batch, None)
        return {"loss": loss, **metrics}

    return jax.jit(step, in_shardings=(shardings, None), out_shardings=None)
