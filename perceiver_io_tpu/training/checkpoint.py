"""Checkpointing — orbax-backed, with the reference's three interoperable
forms (SURVEY.md §5.4):

- **trainer checkpoints**: best-``val_loss``-monitored, weights-only by
  default (reference ``perceiver/scripts/trainer.yaml:7-12``), with the model
  config embedded as metadata so a checkpoint alone rebuilds the model
  (``save_hyperparameters()`` parity);
- **pretrained dirs**: ``save_pretrained``/``load_pretrained`` — params +
  config, the HF-dir equivalent consumed by the inference pipelines;
- **warm-start graph**: ``load_subtree`` pulls a sub-pytree (e.g. just the
  encoder) out of any checkpoint into a fresh model — the two-stage
  classifier flow (reference ``classifier/lightning.py:30-37``).

Sharded ``jax.Array`` trees save and restore natively (each host writes its
shards); restore takes an abstract target so a checkpoint written on one mesh
reloads onto another — something torch FSDP checkpoints cannot do without
consolidation.
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from perceiver_io_tpu.models.core.config import config_from_dict, config_to_dict

CONFIG_FILE = "config.json"
PARAMS_DIR = "params"


def save_pretrained(path: str, params: Any, config: Any, *, extra: Optional[dict] = None) -> None:
    """Write a self-describing model dir: orbax params + JSON config."""
    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)
    meta = {"model_config": config_to_dict(config) if config is not None else None}
    if extra:
        meta.update(extra)
    with open(os.path.join(path, CONFIG_FILE), "w") as f:
        json.dump(meta, f, indent=2, default=str)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(path, PARAMS_DIR), params, force=True)
    ckptr.wait_until_finished()


def _restore_tree(directory: str, target: Any):
    """Restore an orbax tree: onto ``target``'s shardings when given, else
    through host memory onto the default device, uncommitted. Restoring
    without a target must not inherit the mesh the tree was saved from: a
    checkpoint written by a four-chip ``fit`` would come back committed to
    all four devices (and fail outright on a host that lacks them)."""
    if target is not None:
        return ocp.StandardCheckpointer().restore(directory, target)
    ckptr = ocp.PyTreeCheckpointer()
    saved = ckptr.metadata(directory).item_metadata.tree
    to_host = jax.tree_util.tree_map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), saved
    )
    host = ckptr.restore(directory, args=ocp.args.PyTreeRestore(restore_args=to_host))
    return jax.device_put(host)


def load_config(path: str) -> Any:
    with open(os.path.join(os.path.abspath(path), CONFIG_FILE)) as f:
        meta = json.load(f)
    d = meta.get("model_config")
    return config_from_dict(None, d) if d is not None else None


def _trainer_checkpoint_root(path: str) -> Optional[str]:
    """If ``path`` is (or is ``<root>/best`` of) a trainer checkpoint dir —
    an orbax CheckpointManager root with numeric step dirs — return the
    root, else None."""
    if os.path.basename(path) == "best" and not os.path.exists(path):
        path = os.path.dirname(path)
    if not os.path.isdir(path) or os.path.exists(os.path.join(path, PARAMS_DIR)):
        return None
    has_steps = any(name.isdigit() for name in os.listdir(path))
    return path if has_steps else None


def load_pretrained(path: str, *, target: Any = None):
    """:return: (params, config). ``target`` — an abstract pytree (e.g. from
    ``jax.eval_shape``) with shardings for direct-to-mesh restore; omit it
    and the params land on the default device, whatever mesh saved them.

    Accepts either a ``save_pretrained`` dir or a trainer checkpoint dir
    (``<root>/checkpoints`` or the ``<root>/checkpoints/best`` alias), which
    restores the best-``val_loss`` step."""
    path = os.path.abspath(path)
    ckpt_root = _trainer_checkpoint_root(path)
    if ckpt_root is not None:
        manager = BestCheckpointManager(ckpt_root)
        try:
            return manager.restore_best(target=target)
        finally:
            manager.close()
    config = load_config(path)
    return _restore_tree(os.path.join(path, PARAMS_DIR), target), config


def load_subtree(path: str, subtree: str, *, target: Any = None):
    """Load one sub-pytree (``'encoder'``, ``'perceiver_ar'`` …) from a saved
    model — partial/pretrained-subtree warm start."""
    params, _ = load_pretrained(path, target=None)
    node = params
    for key in subtree.split("/"):
        node = node[key]
    if target is not None:
        node = jax.tree_util.tree_map(lambda t, x: jax.device_put(x, t.sharding), target, node)
    return node


class ResumeCheckpointManager:
    """Periodic full-``TrainState`` snapshots (step + params + optimizer
    state, which embeds the LR-schedule position) for mid-training resume —
    the Lightning ``Trainer.fit(ckpt_path=...)`` capability the reference
    inherits. Restore takes the live sharded state as template, so snapshots
    reload directly onto the mesh (and onto a *different* mesh, which torch
    optimizer checkpoints cannot do without consolidation)."""

    def __init__(self, directory: str, *, max_to_keep: int = 2, create: bool = True):
        """:param create: make the directory (save side). Pass False for a
        pure-read restore so a mistyped path fails cleanly instead of
        leaving an empty directory tree behind."""
        self.directory = os.path.abspath(directory)
        if create:
            os.makedirs(self.directory, exist_ok=True)
        elif not os.path.isdir(self.directory):
            raise FileNotFoundError(f"no resume snapshots in {self.directory}")
        self._manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=False,
                create=create,
            ),
        )

    @staticmethod
    def _tree(state) -> dict:
        return {"step": state.step, "params": state.params, "opt_state": state.opt_state}

    def save(self, step: int, state) -> None:
        self._manager.save(step, args=ocp.args.StandardSave(self._tree(state)))
        self._manager.wait_until_finished()

    @property
    def latest_step(self) -> Optional[int]:
        return self._manager.latest_step()

    def restore_latest(self, state):
        """:param state: the freshly initialized sharded TrainState (shape,
        dtype, and sharding template). :return: TrainState at the snapshot."""
        step = self.latest_step
        if step is None:
            raise FileNotFoundError(f"no resume snapshots in {self.directory}")
        target = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            self._tree(state),
        )
        restored = self._manager.restore(step, args=ocp.args.StandardRestore(target))
        return state.replace(
            step=restored["step"],
            params=restored["params"],
            opt_state=restored["opt_state"],
        )

    def close(self):
        """Idempotent: crash-path cleanup (trainer ``finally`` blocks) may
        race a normal close — the second call is a no-op."""
        if self._manager is not None:
            self._manager.close()
            self._manager = None


class BestCheckpointManager:
    """Keeps the k best checkpoints by ``val_loss`` — the reference's
    ``ModelCheckpoint(monitor="val_loss", save_weights_only=True)``
    (``trainer.yaml:7-12``). Checkpoint dirs are named
    ``step=<n>-val_loss=<v>`` like the reference's ``.ckpt`` files."""

    def __init__(self, directory: str, *, max_to_keep: int = 1):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                best_fn=lambda metrics: metrics["val_loss"],
                best_mode="min",
                enable_async_checkpointing=False,
            ),
        )

    def save(self, step: int, params: Any, config: Any, val_loss: float) -> None:
        with open(os.path.join(self.directory, CONFIG_FILE), "w") as f:
            json.dump(
                {"model_config": config_to_dict(config) if config is not None else None},
                f,
                indent=2,
                default=str,
            )
        self._manager.save(
            step,
            args=ocp.args.StandardSave(params),
            metrics={"val_loss": float(val_loss)},
        )
        self._manager.wait_until_finished()

    @property
    def best_step(self) -> Optional[int]:
        return self._manager.best_step()

    def restore_best(self, *, target: Any = None):
        step = self.best_step
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        # the manager's one unnamed item lives under <step>/<default item name>
        item = os.path.join(
            self.directory, str(step), ocp.checkpoint_manager.DEFAULT_ITEM_NAME
        )
        params = _restore_tree(item, target)
        with open(os.path.join(self.directory, CONFIG_FILE)) as f:
            d = json.load(f).get("model_config")
        return params, (config_from_dict(None, d) if d is not None else None)

    def close(self):
        """Idempotent — see :meth:`ResumeCheckpointManager.close`."""
        if self._manager is not None:
            self._manager.close()
            self._manager = None
