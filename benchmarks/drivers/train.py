"""The training window: the program's ``Trainer.fit`` loop, as the family's
``fit`` subcommand builds it, fed by the benchmark's batch stream.

The stream is the benchmark's only handle on the loop, and everything is
done from inside it, between two steps of one ``fit`` call: the first three
steps are checked (their batches kept for the reference), the steps after
them warm up, then the window opens; when its seconds are over the stream
fences the device, takes the time and ends the loop by raising. So the
compiled step and the state that were checked are the ones that are timed.
"""
from __future__ import annotations

import importlib
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import blocks
from ..reference import training as ref_training
from ..traffic.train_batches import TrainBatches

CHECK_STEPS = 3


class _WindowClosed(Exception):
    """Raised by the stream to leave ``fit`` once the window is over."""


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


class Stream:
    """Re-iterable of host batches that also runs the run's schedule. Batch
    ``k`` (from 1) feeds step ``k``; when it is asked for, steps ``< k``
    have been dispatched and ``trainer.state`` is the state after them."""

    def __init__(self, run, trainer, batches, leaves, seeded_tree, handed, b1):
        self.run, self.trainer, self.batches = run, trainer, batches
        self.leaves, self.seeded_tree, self.handed, self.b1 = leaves, seeded_tree, handed, b1
        self.k = 0
        self.check_batches, self.losses = [], []
        self.grad_norms = self.delta_norms = None
        self.cadence = trainer.config.log_every_n_steps
        self.warmup = int(run.mix.get("warmup_steps", 2))
        self.first_window_step = CHECK_STEPS + self.warmup + 1
        self.t_open = self.t_close = None
        self.steps = 0
        self.data_wait_s = 0.0
        self.trace_left = None
        self.traced = None

    def __iter__(self):
        return self

    def _fence(self):
        state = self.trainer.state
        jax.block_until_ready(state.params)
        return int(state.step)  # a host fetch of the last step's output

    def _loss_of_last_step(self):
        gauges = self.trainer.registry.snapshot()["gauges"]
        return float(gauges["trainer_loss"])

    def _mu(self):
        parts = jax.tree_util.tree_leaves(
            self.trainer.state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        return next(s for s in parts if hasattr(s, "mu")).mu

    def __next__(self):
        self.k += 1
        k, cfg = self.k, self.trainer.config
        if k <= self.first_window_step:
            self.run.stamp(f"batch_{k}_asked")
        if k == 1:
            # the state exists and holds a copy of its own of the tree ``fit``
            # was handed: from here on the state's parameters are the only ones
            for leaf in jax.tree_util.tree_leaves(self.handed):
                leaf.delete()
            self.handed = None
        if k <= CHECK_STEPS + 1:
            # the trainer logs a mean loss per cadence; one step per flush
            # while the checked steps run gives each step's own loss
            cfg.log_every_n_steps = 1
            if k > 1:
                self.losses.append(self._loss_of_last_step())
            if k == 2:
                mu = self.leaves(self._mu())
                scale = 1.0 / (1.0 - self.b1)
                self.grad_norms = jax.jit(
                    lambda t: _norms({n: v * scale for n, v in t.items()}))(mu)
            if k == CHECK_STEPS + 1:
                # the start is made again from the seed, for this one
                # reading: step 3's loss has been fetched, so no step is in
                # flight, and the device holds the state and this tree
                now = self.leaves(self.trainer.state.params)
                start = self.leaves(self.seeded_tree())
                self.delta_norms = jax.jit(
                    lambda a, b: _norms({n: a[n] - b[n] for n in a}))(now, start)
                cfg.log_every_n_steps = self.cadence
            batch = self.batches.next_batch()
            if k <= CHECK_STEPS:
                self.check_batches.append(batch)
            return batch
        if k < self.first_window_step:
            return self.batches.next_batch()
        if k == self.first_window_step:
            self._fence()
            self.run.open_window()
            self.t_open = time.perf_counter()
        elif self.t_close is None and time.perf_counter() - self.t_open >= self.run.seconds:
            self._fence()
            self.t_close = time.perf_counter()
            self.steps = k - self.first_window_step
            self.run.close_window()
            if not self.run.trace:
                raise _WindowClosed
            self.trace_left = int(self.run.mix.get("trace_steps", 6))
            self.run.start_trace()
        if self.trace_left is not None:
            if self.trace_left == 0:
                self._fence()
                self.traced = self.run.stop_trace()
                raise _WindowClosed
            self.trace_left -= 1
        t0 = time.perf_counter()
        with self.run.span("batch_handout"):
            batch = self.batches.next_batch()
        if self.t_close is None:
            self.data_wait_s += time.perf_counter() - t0
        return batch


def _gaps(prog: dict, ref: dict, floor: float, skip=()) -> dict:
    """By leaf, |program's norm - reference's norm| over the larger of the
    reference's norm of that leaf and ``floor``."""
    out = {}
    for name, r in ref.items():
        if name in skip:
            continue
        g = abs(float(prog[name]) - r) / max(r, floor)
        out[name] = g if np.isfinite(g) else float("inf")
    return out


def reference_readings(ref, config, optimizer, trainer_seed, seed, check_batches, rows,
                       precision="float32"):
    """The reference through the checked steps: each step's loss, the first
    gradient's norm by leaf, and the norm of each leaf's change after them."""
    with blocks.precision(precision):
        params = ref_training.seeded_params(ref, config, seed)
        block = ref_training.make_block(ref, config)
        losses, grad_norms, state = [], None, None
        for i, batch in enumerate(check_batches, start=1):
            aux = ref.train_aux(config, trainer_seed, i, batch)
            loss, grads = ref_training.loss_and_grads(block, params, batch, aux, rows)
            losses.append(float(loss))
            if i == 1:
                grad_norms = ref_training.leaf_norms(grads)
            params, state = ref_training.adamw_step(optimizer, params, grads, state)
        del state  # the update was made in place: the start is made again
        start = ref_training.seeded_params(ref, config, seed)
        delta = ref_training.leaf_norms({k: params[k] - start[k] for k in params})
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def _settled(reference: dict) -> tuple:
    """The median leaf's gradient norm, the leaves left out of the change (a
    leaf whose gradient is nought to rounding moves under Adam by round-off
    alone: under a thousandth of the median leaf's, by the reference), and
    the median norm of the other leaves' change."""
    g_ref, d_ref = reference["grad_norms"], reference["delta_norms"]
    g_med = statistics.median(g_ref.values())
    still = {n for n, g in g_ref.items() if g < 1e-3 * g_med}
    return g_med, still, statistics.median(v for n, v in d_ref.items() if n not in still)


def compare(program: dict, reference: dict) -> dict:
    """The numbers ``correct`` is decided on, by name, with the leaf at
    which each worst reading stands."""
    out = {}
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"]), start=1):
        out[f"loss{i}"] = abs(p - r) / abs(r)
    g_ref, d_ref = reference["grad_norms"], reference["delta_norms"]
    g_med, still, d_med = _settled(reference)
    for key, gaps in (("grad", _gaps(program["grad_norms"], g_ref, g_med)),
                      ("delta", _gaps(program["delta_norms"], d_ref, d_med, still))):
        at = max(gaps, key=gaps.get)
        out[f"{key}_leaf"], out[f"{key}_leaf_at"] = gaps[at], at
        out[f"{key}_median"] = statistics.median(gaps.values())
    return out


def leaf_table(program: dict, reference: dict, top: int = 6) -> list:
    """The ``top`` leaves by gap of the change, with both sides' norms of the
    change and of the first gradient: what a look at a wide reading starts from."""
    g_ref, d_ref = reference["grad_norms"], reference["delta_norms"]
    _, still, d_med = _settled(reference)
    gaps = _gaps(program["delta_norms"], d_ref, d_med, still)
    rows = sorted(gaps, key=gaps.get, reverse=True)[:top]
    return [{"leaf": n, "delta_gap": gaps[n], "delta_program": program["delta_norms"][n],
             "delta_reference": d_ref[n], "grad_program": program["grad_norms"][n],
             "grad_reference": g_ref[n]} for n in rows]


def run(run):
    """Drive one training cell; returns the driver's part of the result."""
    config, mix = run.config, run.mix
    ref = importlib.import_module(f"benchmarks.reference.{config['reference']}")
    adapter = importlib.import_module(f"benchmarks.adapters.{config['program']}")
    run.stamp("driver_imported")
    with run.span("build"):
        trainer, optimizer = adapter.build_fit(config, mix["fit"], run.work_dir("fit"))
        names = sorted(ref.param_shapes(config))
        leaves = lambda tree: adapter.common.leaves_by_name(tree, names, adapter.path_of)
        seeded_tree = lambda: adapter.common.seeded_tree(ref, config, adapter.path_of, run.seed)
        params = seeded_tree()
        run.stamp("trainer_and_weights")
        batches = TrainBatches(mix["feed"], run.seed)
    run.stamp("corpus")
    stream = Stream(run, trainer, batches, leaves, seeded_tree, params, optimizer["b1"])
    try:
        # ``fit`` copies this tree into its state; the stream deletes it as
        # the first batch is asked for (an init function handed to ``fit``
        # would make the seed a constant of the state's program: a compile a
        # seed)
        trainer.fit(lambda: params, stream, val_data=None, initial_params=params)
        raise RuntimeError("fit returned before the window closed: max_steps too low")
    except _WindowClosed:
        pass
    finally:
        trainer.close()
    window_s = stream.t_close - stream.t_open
    tokens = stream.steps * batches.tokens_per_batch
    run.note_memory_peak()
    run.counters["attention_einsum_fallback_total"] = adapter.common.registry_counter(
        "attention_einsum_fallback_total")
    program = {
        "losses": stream.losses,
        "grad_norms": {k: float(v) for k, v in stream.grad_norms.items()},
        "delta_norms": {k: float(v) for k, v in stream.delta_norms.items()},
    }
    trainer_seed = trainer.config.seed
    trainer.state = None
    del trainer, params
    with run.span("reference"):
        reference = reference_readings(
            ref, config, optimizer, trainer_seed, run.seed, stream.check_batches,
            int(mix.get("reference_rows", 4)),
        )
    compared = compare(program, reference)
    return {
        "attempted": stream.steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "window": {
            "window_s": window_s, "steps": stream.steps, "tokens": tokens,
            "batch": batches.batch, "seq_len": batches.seq_len,
            "data_wait_s": stream.data_wait_s, "losses": stream.losses,
        },
        "compared": compared,
        "leaf_table": leaf_table(program, reference),
        "optimizer": optimizer,
        "traced": stream.traced,
    }
