"""Task step functions — the training semantics of the reference's Lightning
wrappers, as pure ``(params, batch, rng) -> (loss, metrics)`` functions for
:func:`perceiver_io_tpu.parallel.make_train_step`.

Batches are dicts with the reference's collator fields (``labels``,
``input_ids``, ``pad_mask``; reference ``perceiver/data/text/collator.py:16-22``
uses a tuple — a dict is the pytree-friendly equivalent).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100  # torch cross_entropy ignore_index, used throughout the reference


def masked_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Token-mean CE ignoring ``IGNORE_INDEX`` labels — semantics of torch
    ``F.cross_entropy(logits, labels)`` with default mean reduction."""
    with jax.named_scope("loss"):  # read by observability.ledger.op_scopes
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        valid = labels != IGNORE_INDEX
        safe = jnp.where(valid, labels, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, nll, 0.0)
        return nll.sum() / jnp.maximum(1, valid.sum())


def _rngs(rng) -> Optional[dict]:
    if rng is None:
        return None
    d, p = jax.random.split(rng)
    return {"dropout": d, "prefix": p}


def clm_loss_fn(model, max_latents: int) -> Callable:
    """Perceiver AR causal-LM step: ``prefix_len = seq_len - max_latents``,
    pad labels ignored, loss on the last ``max_latents`` positions only
    (reference ``perceiver/model/text/clm/lightning.py:86-102``)."""

    def loss_fn(params, batch, rng):
        input_ids = batch["input_ids"]
        labels = batch["labels"]
        pad_mask = batch.get("pad_mask")
        prefix_len = input_ids.shape[1] - max_latents
        if pad_mask is not None:
            labels = jnp.where(pad_mask, IGNORE_INDEX, labels)
        logits = model.apply(
            {"params": params},
            input_ids,
            prefix_len,
            pad_mask=pad_mask,
            deterministic=rng is None,
            rngs=_rngs(rng),
        )
        loss = masked_cross_entropy(logits, labels[:, prefix_len:])
        return loss, {}

    return loss_fn


def lm_loss_fn(model) -> Callable:
    """Decoder-only LM step (``models/text/lm.py``): next-token CE over every
    position, pad labels ignored. With expert layers the step's metrics carry
    ``moe_assignments_held``, ``moe_expert_load_max_over_mean`` and
    ``moe_layers_bounded``, which the trainer logs and sets as gauges
    ``trainer_moe_*`` (docs/observability.md).

    A model with the prediction module (``num_nextn_predict_layers``) adds a
    second term: the module reads the labels as the next tokens and predicts
    the token after them, so its labels are the labels shifted by one more; a
    row's last position has none, nor has a position whose own label is
    ignored. ``loss = lm_loss + mtp_loss_weight * mtp_loss``, each a mean over
    its own labels, and the metrics carry both terms.

    A model with ``sparse_attention`` layers adds their indexers' loss
    (``indexer_loss``, the layers' KL terms summed) with weight 1, and the
    metrics carry ``lm_loss`` and ``indexer_loss``: the trainer's gauge
    ``trainer_indexer_loss``. No gradient of the one reaches the other's
    leaves (``models/text/lm.py``)."""
    cfg = model.config

    def loss_fn(params, batch, rng):
        labels = batch["labels"]
        pad_mask = batch.get("pad_mask")
        if pad_mask is not None:
            labels = jnp.where(pad_mask, IGNORE_INDEX, labels)
        if not cfg.num_nextn_predict_layers:
            logits, stats = model.apply(
                {"params": params}, batch["input_ids"], pad_mask=pad_mask, return_stats=True
            )
            loss = masked_cross_entropy(logits, labels)
            if cfg.has_indexer:
                return loss + stats["indexer_loss"], {**stats, "lm_loss": loss}
            return loss, stats if cfg.has_experts else {}
        (logits, mtp_logits), stats = model.apply(
            {"params": params}, batch["input_ids"], pad_mask=pad_mask, return_stats=True,
            next_ids=jnp.maximum(labels, 0),
        )
        lm_loss = masked_cross_entropy(logits, labels)
        after = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)), constant_values=IGNORE_INDEX)
        with jax.named_scope("mtp"):
            mtp_loss = masked_cross_entropy(mtp_logits, jnp.where(labels == IGNORE_INDEX, IGNORE_INDEX, after))
        loss = lm_loss + cfg.mtp_loss_weight * mtp_loss
        return loss, {**stats, "lm_loss": lm_loss, "mtp_loss": mtp_loss}

    return loss_fn


def mlm_loss_fn(model) -> Callable:
    """Masked-LM step: CE over all positions, unmasked labels = -100
    (reference ``perceiver/model/text/mlm/lightning.py:57-62``)."""

    def loss_fn(params, batch, rng):
        logits = model.apply(
            {"params": params},
            batch["input_ids"],
            pad_mask=batch.get("pad_mask"),
            deterministic=rng is None,
            rngs=_rngs(rng),
        )
        loss = masked_cross_entropy(logits, batch["labels"])
        return loss, {}

    return loss_fn


def image_classifier_loss_fn(model) -> Callable:
    """Image classifier step over ``{"image", "label"}`` batches (the vision
    datamodule contract; reference ``image_classifier/lightning.py:12-41``)."""

    def loss_fn(params, batch, rng):
        logits = model.apply(
            {"params": params}, batch["image"], deterministic=rng is None
        )
        labels = batch["label"]
        loss = masked_cross_entropy(logits, labels)
        acc = jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
        return loss, {"accuracy": acc}

    return loss_fn


def classifier_loss_fn(model) -> Callable:
    """Classifier step: CE + accuracy (reference
    ``perceiver/model/core/lightning.py:50-76``; accuracy reduction across
    devices comes from sharding, the ``sync_dist=True`` equivalent)."""

    def loss_fn(params, batch, rng):
        logits = model.apply(
            {"params": params},
            batch["input_ids"],
            pad_mask=batch.get("pad_mask"),
            deterministic=rng is None,
            rngs=_rngs(rng),
        )
        labels = batch["labels"]
        loss = masked_cross_entropy(logits, labels)
        acc = jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
        return loss, {"accuracy": acc}

    return loss_fn
