"""Beam search decoding for Perceiver AR sequence models.

Semantics follow HF ``GenerationMixin`` beam search (the decoding surface the
reference exposes and tests — reference
``tests/causal_language_model_pipeline_test.py:37-38``,
``tests/symbolic_audio_model_pipeline_test.py:95-96``), re-formulated as one
jittable ``lax.scan`` over the same right-aligned static window as
:mod:`perceiver_io_tpu.inference.generate`:

- beam scores start ``[0, -1e9, ...]`` so step 1 fans out of beam 0;
- per step: ``log_softmax`` over next-token logits, cumulative scores,
  top-``2k`` candidates over the flattened ``(k·V)`` score matrix;
- candidates ending in EOS are moved into a per-batch hypothesis buffer
  (score length-normalized at insertion, ``score / gen_len**length_penalty``
  with ``gen_len`` counting *generated* tokens only, matching the vectorized
  ``_beam_search`` in transformers >= 4.50 — older HF ``BeamHypotheses.add``
  normalized by prompt + generated); the first ``k`` non-EOS candidates
  continue as live beams;
- termination is by ``max_new_tokens`` (``early_stopping=False`` semantics:
  the search runs to max length, then live beams are finalized against the
  hypothesis buffer).

``min_new_tokens`` masks EOS to ``-inf`` until that many tokens exist
(HF ``MinNewTokensLengthLogitsProcessor``); driving it equal to
``max_new_tokens`` gives the deterministic full-length search the reference
parity tests use.

All shapes are static: beams ride the batch axis (``b·k`` windows), beam
reindexing is a gather, and per-beam token histories live in a carried
``(b, k, max_new)`` buffer that is reindexed alongside the beams.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from perceiver_io_tpu.inference.generate import (
    GenerationConfig,
    _decode_forward,
    _pad_positions,
)
from perceiver_io_tpu.inference.samplers import (
    apply_min_new_tokens,
    apply_repetition_penalty,
)

NEG_INF = -1e9


def beam_search(
    model,
    params,
    input_ids: jnp.ndarray,
    config: GenerationConfig,
    *,
    num_beams: int = 3,
    length_penalty: float = 1.0,
    prompt_pad_count: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Beam-search ``config.max_new_tokens`` tokens after ``input_ids``.

    :param input_ids: ``(b, prompt_len)`` prompt, left-padded if ragged.
    :return: ``(b, max_new_tokens)`` ids of the best beam (pad after EOS).
    """
    b, prompt_len = input_ids.shape
    n = model.max_seq_len
    max_latents = model.max_latents
    if not 0 < prompt_len <= n:
        raise ValueError(f"prompt length out of valid range [1..{n}]")
    if not 0 < config.num_latents <= max_latents:
        raise ValueError(
            f"num_latents={config.num_latents} out of valid range [1..{max_latents}]"
        )
    num_latents = min(prompt_len, config.num_latents)
    prefix_len = prompt_len - num_latents
    if prefix_len > model.max_prefix_len:
        raise ValueError(
            f"for sequence length {prompt_len}, num_latents must be >= "
            f"{num_latents + prefix_len - model.max_prefix_len}"
        )
    if prompt_pad_count is None:
        prompt_pad_count = jnp.zeros((b,), jnp.int32)
    executor = _beam_executor(
        model, config, b, prompt_len, num_latents, num_beams,
        float(length_penalty), str(input_ids.dtype),
    )
    return executor(params, input_ids, prompt_pad_count)


_EXECUTOR_CACHE: dict = {}


def _beam_executor(
    model, config, b: int, prompt_len: int, num_latents: int,
    num_beams: int, length_penalty: float, ids_dtype: str,
):
    """Compile-once beam program per static plan (same rationale and keying
    as ``generate._generation_executor`` — the eager body re-traced the
    whole scan on every call)."""
    from perceiver_io_tpu.inference.generate import (
        cached_executor,
        ledger_model_id,
        model_fingerprint,
    )
    from perceiver_io_tpu.ops.ragged_attention import trace_env

    key = (
        type(model).__qualname__, model_fingerprint(model), config,
        b, prompt_len, num_latents, num_beams, length_penalty, ids_dtype,
        trace_env(),
    )
    return cached_executor(
        _EXECUTOR_CACHE, key,
        lambda: _build_beam_executor(
            model, config, b, prompt_len, num_latents, num_beams,
            length_penalty, ids_dtype,
        ),
        max_entries=32,
        ledger_site="beam",
        ledger_components=lambda: {
            "model": ledger_model_id(model),
            # max_new_tokens is routine per-request variation — it belongs
            # to beam_plan (the compiled scan length), not the `config`
            # retrace reason (sampling/eos/latents; docs/observability.md)
            "config": dataclasses.replace(config, max_new_tokens=0),
            "bucket_shape": f"{b}x{prompt_len}",
            "num_latents": num_latents,
            "beam_plan": (
                f"k={num_beams},lp={length_penalty},"
                f"steps={config.max_new_tokens}"
            ),
            "ids_dtype": ids_dtype,
            "trace_env": trace_env(),
        },
    )


def _build_beam_executor(
    model, config, b: int, prompt_len: int, num_latents: int,
    num_beams: int, length_penalty: float, ids_dtype: str,
):
    n = model.max_seq_len
    max_latents = model.max_latents
    k = num_beams
    t_max = config.max_new_tokens
    vocab = model.config.vocab_size
    eos = config.eos_token_id
    min_new = min(config.min_new_tokens, t_max) if eos is not None else t_max
    rep_penalty = config.sampling.repetition_penalty

    def run(params, input_ids, prompt_pad_count):
        # Beams ride the batch axis: (b, k, ...) flattened to (b*k, ...).
        window = jnp.full((b, n), config.pad_token_id, input_ids.dtype)
        window = window.at[:, n - prompt_len :].set(input_ids)
        window = jnp.repeat(window, k, axis=0)
        pad_count = jnp.repeat(
            prompt_pad_count.astype(jnp.int32) + (n - prompt_len), k, axis=0
        )
        beam_scores = jnp.full((b, k), NEG_INF, jnp.float32).at[:, 0].set(0.0)

        rows = jnp.arange(b)[:, None]  # (b, 1) batch index for beam gathers

        def step(carry, t):
            window, pad_count, m, beam_scores, tok_buf, hyp_scores, hyp_tokens = carry

            logits = model.apply(
                {"params": params}, window, pad_count, m, method=_decode_forward
            )  # (b*k, V)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            if rep_penalty != 1.0:
                # HF beam order: processors run on the log-probs
                # (modeling _beam_search: log_softmax then logits_processor)
                logp = apply_repetition_penalty(
                    logp, window, rep_penalty, _pad_positions(pad_count, n)
                )
            if eos is not None:
                logp = apply_min_new_tokens(logp, t, min_new, eos)
            scores = (beam_scores.reshape(b * k, 1) + logp).reshape(b, k * vocab)

            # Top-2k candidates (sorted descending, as HF), then the first k
            # non-EOS candidates continue as live beams.
            cand_scores, cand_idx = jax.lax.top_k(scores, 2 * k)
            cand_beam = cand_idx // vocab  # (b, 2k)
            cand_tok = (cand_idx % vocab).astype(jnp.int32)

            if eos is not None:
                is_eos = cand_tok == eos
                # EOS candidates ranked among the first k enter the hypothesis
                # buffer, length-normalized at insertion (HF BeamHypotheses.add:
                # keep the k best, displacing the worst). Up to k candidates can
                # finish in one step — statically unrolled best-first inserts.
                in_first_k = jnp.arange(2 * k)[None, :] < k
                hyp_cand_score = jnp.where(
                    is_eos & in_first_k,
                    cand_scores / ((t + 1.0) ** length_penalty),
                    -jnp.inf,
                )
                for _ in range(k):
                    best_e = jnp.argmax(hyp_cand_score, axis=1)  # (b,)
                    best_score = jnp.take_along_axis(
                        hyp_cand_score, best_e[:, None], 1
                    )[:, 0]
                    src_beam = jnp.take_along_axis(cand_beam, best_e[:, None], 1)[:, 0]
                    hist = tok_buf[rows[:, 0], src_beam]  # (b, t_max)
                    hist = jnp.where(jnp.arange(t_max)[None, :] == t, eos, hist)
                    worst = jnp.argmin(hyp_scores, axis=1)  # (b,)
                    worst_score = jnp.take_along_axis(hyp_scores, worst[:, None], 1)[:, 0]
                    replace = best_score > worst_score
                    hyp_scores = hyp_scores.at[rows[:, 0], worst].set(
                        jnp.where(replace, best_score, worst_score)
                    )
                    old_rows = hyp_tokens[rows[:, 0], worst]
                    hyp_tokens = hyp_tokens.at[rows[:, 0], worst].set(
                        jnp.where(replace[:, None], hist, old_rows)
                    )
                    # consume this candidate
                    hyp_cand_score = hyp_cand_score.at[rows[:, 0], best_e].set(-jnp.inf)
                # Live beams: first k non-EOS candidates, in candidate order
                # (stable sort on the EOS flag preserves score order).
                order = jnp.argsort(is_eos.astype(jnp.int32), axis=1, stable=True)
                live = order[:, :k]
            else:
                live = jnp.broadcast_to(jnp.arange(k)[None, :], (b, k))

            new_scores = jnp.take_along_axis(cand_scores, live, 1)  # (b, k)
            new_beam = jnp.take_along_axis(cand_beam, live, 1)
            new_tok = jnp.take_along_axis(cand_tok, live, 1)

            # Reindex beam state, then advance the windows with the new tokens.
            window = window.reshape(b, k, n)[rows, new_beam].reshape(b * k, n)
            pad_count = pad_count.reshape(b, k)[rows, new_beam].reshape(b * k)
            tok_buf = tok_buf[rows, new_beam]
            tok_buf = jnp.where(
                (jnp.arange(t_max) == t)[None, None, :], new_tok[..., None], tok_buf
            )
            window = jnp.concatenate(
                [window[:, 1:], new_tok.reshape(b * k, 1).astype(window.dtype)], axis=1
            )
            pad_count = jnp.maximum(pad_count - 1, 0)
            m = jnp.minimum(m + 1, max_latents)

            carry = (window, pad_count, m, new_scores, tok_buf, hyp_scores, hyp_tokens)
            return carry, None

        # pad-filled, not zeros: a finished hypothesis's history is copied
        # into the pool wholesale, so post-EOS slots must already hold pad.
        tok_buf = jnp.full((b, k, t_max), config.pad_token_id, jnp.int32)
        hyp_scores = jnp.full((b, k), -jnp.inf, jnp.float32)
        hyp_tokens = jnp.full((b, k, t_max), config.pad_token_id, jnp.int32)
        carry = (
            window,
            pad_count,
            jnp.asarray(num_latents, jnp.int32),
            beam_scores,
            tok_buf,
            hyp_scores,
            hyp_tokens,
        )
        carry, _ = jax.lax.scan(step, carry, jnp.arange(t_max))
        _, _, _, beam_scores, tok_buf, hyp_scores, hyp_tokens = carry

        # Finalize (HF with early_stopping=False at max length): live beams join
        # the hypothesis pool, length-normalized at generated length.
        live_final = beam_scores / (float(t_max) ** length_penalty)
        all_scores = jnp.concatenate([hyp_scores, live_final], axis=1)  # (b, 2k)
        all_tokens = jnp.concatenate([hyp_tokens, tok_buf], axis=1)  # (b, 2k, t_max)
        best = jnp.argmax(all_scores, axis=1)
        return all_tokens[jnp.arange(b), best].astype(input_ids.dtype)

    return jax.jit(run)
