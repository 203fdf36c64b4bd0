"""Decoder-only language model CLI: layers declared one by one (gated short
convolutions, grouped-query attention over every earlier position, a sliding
window or the keys a learned indexer selects, latent attention, sparse
experts with shared ones beside them, a multi-token-prediction module;
docs/lm.md):

    python -m perceiver_io_tpu.scripts.text.lm fit --data=wikitext \
        --data.dataset_dir=.cache/wikitext --trainer.max_steps=10000 \
        --model.layer_types=conv,conv,full_attention,conv

A ``sparse_attention`` layer takes its indexer from ``--model.index_n_heads``,
``--model.index_head_dim`` and ``--model.index_topk``; the indexer's loss is
the second term of the loss (``trainer_indexer_loss``).

``serve`` does not take this family yet and says so.
"""
from __future__ import annotations

import jax.numpy as jnp

from perceiver_io_tpu.models.text.lm import DecoderLM, DecoderLMConfig
from perceiver_io_tpu.scripts.cli import CLI, ModelFamily
from perceiver_io_tpu.scripts.text.clm import DATA, _link
from perceiver_io_tpu.training.tasks import lm_loss_fn

FAMILY = ModelFamily(
    name="perceiver_io_tpu.scripts.text.lm",
    config_class=DecoderLMConfig,
    data_registry=DATA,
    build_model=lambda cfg, dm: DecoderLM(cfg, dtype=jnp.bfloat16),
    make_loss=lambda model, cfg: lm_loss_fn(model),
    init_args=lambda cfg, batch: (
        (jnp.asarray(batch["input_ids"][:1]),),
        # the prediction module's parameters exist once it has been called
        {"next_ids": jnp.asarray(batch["input_ids"][:1])} if cfg.num_nextn_predict_layers else {},
    ),
    link=_link,
    defaults={
        "data.task": "clm",
        "data.padding_side": "left",
        "lr_scheduler.name": "cosine",
        "lr_scheduler.warmup_steps": 200,
    },
)


def main(argv=None):
    return CLI(FAMILY).main(argv)


if __name__ == "__main__":
    main()
