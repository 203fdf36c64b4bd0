"""Shared by the adapters: the layout of a layer's parameters in the
program's tree, and the program's fit loop as its CLI builds it."""
from __future__ import annotations

import jax

_LEAF = {"w": "kernel", "bias": "bias", "g": "scale", "b": "bias"}


def layer_path(rest: str, attn: str) -> tuple:
    """Program path below a layer for the reference name below a layer:
    ``attn.q.w`` -> ``(<attn>, attention, q_proj, kernel)``."""
    parts = rest.split(".")
    leaf = _LEAF[parts[-1]]
    if parts[0] == "attn":
        return (attn, "attention", parts[1] + "_proj", leaf)
    if parts[0] == "mlp":
        return ("mlp", parts[1], leaf)
    return (attn, parts[0], leaf)  # q_norm / kv_norm / norm


def to_tree(flat: dict, path_of) -> dict:
    """Nested parameter dict from the reference's flat one."""
    tree: dict = {}
    for name, value in flat.items():
        node = tree
        *parents, leaf = path_of(name)
        for key in parents:
            node = node.setdefault(key, {})
        node[leaf] = value
    return tree


def leaves_by_name(tree, names, path_of) -> dict:
    """The program tree's leaves under the reference's names."""
    out = {}
    for name in names:
        node = tree
        for key in path_of(name):
            node = node[key]
        out[name] = node
    return out


def seeded_tree(ref, config: dict, path_of, seed: int):
    """The program's parameter tree from ``seed``, made on the device in one
    jitted call: the reference's init laid out as the program's tree."""
    return jax.jit(lambda key: to_tree(ref.init_params(key, config), path_of))(
        jax.random.PRNGKey(seed % (2**31))
    )


def build_trainer(family, model_cfg, fit: dict, root_dir: str):
    """The ``Trainer`` and its loss as ``CLI.run`` builds them for ``fit``:
    the family's model and loss, a mesh over every device with the CLI's
    default axes, AdamW under the family's default schedule, the CLI's
    default trainer settings but for what ``fit`` names."""
    from perceiver_io_tpu.parallel import MeshConfig, make_mesh
    from perceiver_io_tpu.scripts.cli import LRSchedulerArgs, OptimizerArgs, build_dataclass
    from perceiver_io_tpu.training.lrs import constant_with_warmup, cosine_with_warmup
    from perceiver_io_tpu.training.optim import make_optimizer
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    values = {**family.defaults, **{f"trainer.{k}": v for k, v in fit.get("trainer", {}).items()}}
    values["trainer.default_root_dir"] = root_dir
    trainer_cfg = build_dataclass(TrainerConfig, values, "trainer")
    opt = build_dataclass(OptimizerArgs, values, "optimizer")
    lrs = build_dataclass(LRSchedulerArgs, values, "lr_scheduler")
    steps = lrs.training_steps or trainer_cfg.max_steps
    if lrs.name == "cosine":
        schedule = cosine_with_warmup(
            opt.lr, warmup_steps=lrs.warmup_steps, training_steps=steps,
            min_fraction=lrs.min_fraction,
        )
    else:
        schedule = constant_with_warmup(opt.lr, warmup_steps=lrs.warmup_steps)
    tx = make_optimizer(
        schedule, optimizer=opt.optimizer, weight_decay=opt.weight_decay, b1=opt.b1, b2=opt.b2
    )
    model = family.build_model(model_cfg, None)
    trainer = Trainer(
        trainer_cfg, make_mesh(MeshConfig()), family.make_loss(model, model_cfg), tx,
        model_config=model_cfg, lr_schedule=schedule,
    )
    optimizer = {
        "lr": opt.lr, "b1": opt.b1, "b2": opt.b2, "eps": 1e-8, "weight_decay": opt.weight_decay,
        "schedule": lrs.name, "warmup_steps": lrs.warmup_steps, "training_steps": steps,
        "min_fraction": lrs.min_fraction,
    }
    return trainer, optimizer


def registry_counter(name: str) -> float:
    """A counter of the program's default registry (0 if never touched)."""
    from perceiver_io_tpu.observability import default_registry

    snap = default_registry().snapshot()
    return float(snap.get("counters", {}).get(name, 0.0))
