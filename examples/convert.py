"""Checkpoint conversion CLI — parity with the reference's
``examples/convert.py`` (which drives 3 official HF models + 5 hosted
training checkpoints through one entrypoint). This environment is
zero-egress, so sources are local files instead of hub downloads:

Official DeepMind HF models (``pytorch_model.bin`` + ``config.json`` from
the hub):

    python examples/convert.py mlm pytorch_model.bin out_dir --hf-config config.json
    python examples/convert.py img-clf pytorch_model.bin out_dir --hf-config config.json
    python examples/convert.py flow pytorch_model.bin out_dir --hf-config config.json

Reference training checkpoints (Lightning ``.ckpt`` or bare state dicts,
reference-backend layout):

    python examples/convert.py clm epoch=000-val_loss=2.820.ckpt out_dir \
        --vocab-size 32000 --max-seq-len 1024 --max-latents 512 --num-channels 896
    python examples/convert.py sam epoch=027-val_loss=1.944.ckpt out_dir \
        --max-seq-len 6144 --max-latents 2048 --num-channels 768
    python examples/convert.py mlm mlm.ckpt out_dir            # 201M default shape
    python examples/convert.py txt-clf txt_clf.ckpt out_dir --num-classes 2

Export (the reverse direction — reference ``examples/convert.py:14-89``
produces the same artifact from Lightning checkpoints): a model trained in
this framework (``save_pretrained`` dir or trainer checkpoint dir) → a
reference-format ``save_pretrained`` directory (``config.json`` +
``backend_model.``-prefixed ``pytorch_model.bin``) the reference library
loads with ``Perceiver<Task>.from_pretrained``:

    python examples/convert.py export clm trained_model_dir out_dir
    python examples/convert.py export mlm trained_model_dir out_dir
    python examples/convert.py export clm trained_model_dir out_dir \
        --push_to_hub --repo-id user/model   # needs network + HF token

Key mappings live in ``perceiver_io_tpu/convert/`` (``torch_import`` for the
reference layout, ``hf_import`` for transformers state dicts, ``export`` for
the reverse direction), each parity-tested in ``tests/test_torch_parity.py``
/ ``tests/test_hf_convert.py`` / ``tests/test_export.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# runnable without `pip install -e .`: python examples/convert.py ...
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force_cpu() -> None:
    """Conversion is a host-side param transform — never claim an
    accelerator for it, whatever ``JAX_PLATFORMS`` says. Must run after
    importing jax, before its first use."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def _load_state_dict(path: str):
    import torch

    if path.endswith(".safetensors"):
        from safetensors.torch import load_file

        return load_file(path)
    sd = torch.load(path, map_location="cpu", weights_only=True)
    if "state_dict" in sd:  # Lightning checkpoint wrapper
        sd = sd["state_dict"]
    # Reference Lit* wrappers hold the backend as ``self.model`` (reference
    # ``clm/lightning.py:41``), so real .ckpt keys carry a uniform "model."
    # prefix the backend importers don't expect — strip it.
    if sd and all(k.startswith("model.") for k in sd):
        sd = {k[len("model."):]: v for k, v in sd.items()}
    return sd


def _d(value, fallback):
    return fallback if value is None else value


def _mlm_config(args):
    """Reference-layout MLM config; unset flags fall back to the 201M model
    the reference trains/fine-tunes (docs/training-examples.md:90-118):
    d_model 768, 26 layers, ctx 2048, 256x1280 latents."""
    from perceiver_io_tpu.models.core.config import PerceiverIOConfig
    from perceiver_io_tpu.models.text.common import TextEncoderConfig
    from perceiver_io_tpu.models.text.mlm import TextDecoderConfig

    vocab = _d(args.vocab_size, 262)
    seq = _d(args.max_seq_len, 2048)
    encoder = TextEncoderConfig(
        vocab_size=vocab,
        max_seq_len=seq,
        num_input_channels=_d(args.num_channels, 768),
        num_cross_attention_heads=8,
        num_self_attention_heads=8,
        num_self_attention_layers_per_block=_d(args.num_layers, 26),
        num_self_attention_blocks=1,
    )
    decoder = TextDecoderConfig(vocab_size=vocab, max_seq_len=seq)
    return PerceiverIOConfig(
        encoder, decoder, num_latents=_d(args.num_latents, 256),
        num_latent_channels=_d(args.num_latent_channels, 1280),
    )


def export_main(argv) -> None:
    parser = argparse.ArgumentParser(
        prog="convert.py export",
        description="Export a trained model to the reference (torch) "
        "save_pretrained format.",
    )
    parser.add_argument("task", choices=["clm", "sam", "mlm", "img-clf", "flow", "txt-clf"])
    parser.add_argument("model_dir", help="save_pretrained dir or trainer checkpoint dir")
    parser.add_argument("out_dir")
    # hub-publication surface, parity with the reference converter's
    # ``--push_to_hub``/``--commit_message`` (reference examples/convert.py:70-89,
    # which pushes each save_dir as a hub repo named after its basename)
    parser.add_argument(
        "--push_to_hub", "--push-to-hub", action="store_true",
        help="after writing out_dir, upload it to the HF hub",
    )
    parser.add_argument(
        "--repo-id", "--repo_id", default=None,
        help="hub repo id for --push_to_hub (default: basename of out_dir, "
        "matching the reference's save_dir-as-repo-name convention)",
    )
    parser.add_argument("--commit_message", "--commit-message", default=None)
    args = parser.parse_args(argv)

    import perceiver_io_tpu.convert as convert
    from perceiver_io_tpu.training.checkpoint import load_pretrained

    params, cfg = load_pretrained(args.model_dir)
    if cfg is None:
        raise SystemExit(f"{args.model_dir} carries no model config; cannot export")
    convert.save_reference_checkpoint(params, cfg, args.out_dir, args.task)
    print(f"exported {args.task} model to reference format at {args.out_dir}")
    if args.push_to_hub:
        _push_to_hub(args.out_dir, args.repo_id, args.commit_message)


def _push_to_hub(out_dir: str, repo_id, commit_message) -> None:
    """Upload an exported artifact dir to the HF hub. Fails with a clear
    message when huggingface_hub is unavailable, no token is configured, or
    the network is unreachable (e.g. a zero-egress sandbox)."""
    if repo_id is None:
        repo_id = os.path.basename(os.path.normpath(out_dir))
    try:
        from huggingface_hub import HfApi
    except ImportError:
        raise SystemExit(
            "--push_to_hub requires the huggingface_hub package "
            "(pip install huggingface_hub)"
        )
    api = HfApi()
    try:
        api.create_repo(repo_id, exist_ok=True)
        api.upload_folder(
            repo_id=repo_id,
            folder_path=out_dir,
            commit_message=commit_message or f"Upload {repo_id}",
        )
    except Exception as e:  # hub/network/auth errors all surface identically
        raise SystemExit(
            f"--push_to_hub failed for repo '{repo_id}': {e}\n"
            f"The exported artifact is intact at {out_dir}; push it later with "
            "huggingface-cli upload, or re-run with network + HF_TOKEN available."
        )
    print(f"pushed {out_dir} to hub repo {repo_id}")


def main() -> None:
    _force_cpu()
    if len(sys.argv) > 1 and sys.argv[1] == "export":
        export_main(sys.argv[2:])
        return
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    parser.add_argument("task", choices=["clm", "sam", "mlm", "img-clf", "flow", "txt-clf"])
    parser.add_argument("state_dict", help="torch .pt/.ckpt/.bin/.safetensors file")
    parser.add_argument("out_dir")
    parser.add_argument(
        "--hf-config",
        help="transformers config.json — switches mlm/img-clf/flow to the "
        "official-HF-model key layout (deepmind/* checkpoints)",
    )
    # shape flags default per task: clm/sam fall back to the reference AR
    # shape (4096 ctx, 512 latents/channels, 8 layers); mlm/txt-clf to the
    # 201M language-perceiver shape (2048 ctx, 768 ch, 26 layers, 256x1280)
    parser.add_argument("--vocab-size", type=int, default=None)
    parser.add_argument("--max-seq-len", type=int, default=None)
    parser.add_argument("--max-latents", type=int, default=None)
    parser.add_argument("--num-channels", type=int, default=None)
    parser.add_argument("--num-layers", type=int, default=None)
    parser.add_argument("--num-latents", type=int, default=None)
    parser.add_argument("--num-latent-channels", type=int, default=None)
    parser.add_argument("--num-classes", type=int, default=2)
    args = parser.parse_args()

    import perceiver_io_tpu.convert as convert
    from perceiver_io_tpu.training.checkpoint import save_pretrained

    sd = _load_state_dict(args.state_dict)

    if args.hf_config:
        import transformers

        with open(args.hf_config) as f:
            hf_cfg = transformers.PerceiverConfig(**json.load(f))
        from perceiver_io_tpu.convert import hf_import

        if args.task == "mlm":
            cfg = hf_import.mlm_config_from_hf(hf_cfg)
            params = hf_import.import_hf_masked_language_model(sd, cfg)
        elif args.task == "img-clf":
            cfg = hf_import.image_classifier_config_from_hf(hf_cfg)
            params = hf_import.import_hf_image_classifier(sd, cfg)
        elif args.task == "flow":
            cfg = hf_import.optical_flow_config_from_hf(hf_cfg)
            params = hf_import.import_hf_optical_flow(sd, cfg)
        else:
            raise SystemExit(f"--hf-config applies to mlm/img-clf/flow, not {args.task}")
    elif args.task in ("clm", "sam"):
        if args.task == "clm":
            from perceiver_io_tpu.models.text.clm import CausalLanguageModelConfig as Cfg

            importer = convert.import_causal_language_model
        else:
            from perceiver_io_tpu.models.audio.symbolic import SymbolicAudioModelConfig as Cfg

            importer = convert.import_symbolic_audio_model
        cfg = Cfg(
            vocab_size=_d(args.vocab_size, 262),
            max_seq_len=_d(args.max_seq_len, 4096),
            max_latents=_d(args.max_latents, 512),
            num_channels=_d(args.num_channels, 512),
            num_self_attention_layers=_d(args.num_layers, 8),
        )
        params = importer(sd, cfg)
    elif args.task == "mlm":
        cfg = _mlm_config(args)
        params = convert.import_masked_language_model(sd, cfg)
    elif args.task == "txt-clf":
        from perceiver_io_tpu.models.core.config import (
            ClassificationDecoderConfig,
            PerceiverIOConfig,
        )

        mlm_cfg = _mlm_config(args)
        cfg = PerceiverIOConfig(
            mlm_cfg.encoder,
            ClassificationDecoderConfig(num_classes=args.num_classes),
            num_latents=mlm_cfg.num_latents,
            num_latent_channels=mlm_cfg.num_latent_channels,
        )
        params = convert.import_text_classifier(sd, cfg)
    else:
        raise SystemExit(f"{args.task} requires --hf-config (official HF layout)")

    save_pretrained(args.out_dir, params, cfg)
    print(f"saved {args.task} model to {args.out_dir}")


if __name__ == "__main__":
    main()
