"""Model layer: flax Perceiver / Perceiver IO / Perceiver AR runtime plus
task backends (SURVEY.md §2.1-2.2).

:func:`model_for_config` resolves a config dataclass to its task model — the
glue that lets a checkpoint dir rebuild its model (the reference embeds the
backend config in checkpoints the same way, ``clm/huggingface.py:15-23``).
"""
from __future__ import annotations

from typing import Any


def import_task_modules() -> None:
    """Import every task-model module — the canonical registration point.
    Importing a module registers its config dataclasses (``register_config``),
    so this is what makes bare checkpoint loading (``load_pretrained`` before
    any model import) able to rebuild configs. ``model_for_config`` routes
    through here too; a new task model only needs adding to this list (its
    dispatch entry below will then fail loudly in tests if forgotten)."""
    import perceiver_io_tpu.models.audio.symbolic  # noqa: F401
    import perceiver_io_tpu.models.text.classifier  # noqa: F401
    import perceiver_io_tpu.models.text.clm  # noqa: F401
    import perceiver_io_tpu.models.text.lm  # noqa: F401
    import perceiver_io_tpu.models.text.mlm  # noqa: F401
    import perceiver_io_tpu.models.vision.image_classifier  # noqa: F401
    import perceiver_io_tpu.models.vision.optical_flow  # noqa: F401


def model_for_config(config: Any, *, dtype=None, attention_impl: str = "auto"):
    """Instantiate the task model matching a (nested) config dataclass."""
    import jax.numpy as jnp

    import_task_modules()

    from perceiver_io_tpu.models.audio.symbolic import SymbolicAudioModel, SymbolicAudioModelConfig
    from perceiver_io_tpu.models.core.config import (
        ClassificationDecoderConfig,
        PerceiverIOConfig,
    )
    from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
    from perceiver_io_tpu.models.text.classifier import TextClassifier
    from perceiver_io_tpu.models.text.common import TextEncoderConfig
    from perceiver_io_tpu.models.text.lm import DecoderLM, DecoderLMConfig
    from perceiver_io_tpu.models.text.mlm import MaskedLanguageModel, TextDecoderConfig
    from perceiver_io_tpu.models.vision.image_classifier import ImageClassifier, ImageEncoderConfig
    from perceiver_io_tpu.models.vision.optical_flow import OpticalFlow, OpticalFlowEncoderConfig

    dtype = dtype or jnp.float32
    kwargs = {"dtype": dtype, "attention_impl": attention_impl}

    if isinstance(config, CausalLanguageModelConfig):
        return CausalLanguageModel(config, **kwargs)
    if isinstance(config, SymbolicAudioModelConfig):
        return SymbolicAudioModel(config, **kwargs)
    if isinstance(config, DecoderLMConfig):
        return DecoderLM(config, **kwargs)
    if isinstance(config, PerceiverIOConfig):
        enc, dec = config.encoder, config.decoder
        if isinstance(enc, ImageEncoderConfig):
            return ImageClassifier(config, **kwargs)
        if isinstance(enc, OpticalFlowEncoderConfig):
            return OpticalFlow(config, **kwargs)
        if isinstance(enc, TextEncoderConfig) and isinstance(dec, TextDecoderConfig):
            return MaskedLanguageModel(config, **kwargs)
        if isinstance(enc, TextEncoderConfig) and isinstance(dec, ClassificationDecoderConfig):
            return TextClassifier(config, **kwargs)
    raise ValueError(f"no model registered for config {type(config).__name__}")


__all__ = ["import_task_modules", "model_for_config"]
