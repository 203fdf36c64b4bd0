"""Can a served-token check tell Perceiver AR's float32 serving apart from
the next precision below it? Reads, on the chip and with the plain reference
alone, the widest gap by which the token a precision puts first lies below
the float32-``highest`` reference's best logit, over the latent positions of
a few seeded prompts, for: float32 at the TPU's default matmul precision
(what ``clm serve`` computes in), bfloat16 inputs under float32
accumulation, and float8 inputs with bfloat16 products.

    python benchmarks/tools/probe_serve_precision.py [--cpu-witness]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness
    from benchmarks.reference import blocks, perceiver_ar, training
    from benchmarks.traffic.corpus import markov_bytes

    if "--cpu-witness" not in sys.argv:  # on the CPU float32 is float32: the second witness
        harness.require_chips(1)
    with open(os.path.join(ROOT, "benchmarks", "configs", "perceiver-ar-8k.json")) as f:
        cfg = json.load(f)
    prompt_len, latents = 2048 + 384, 512 + 384
    out = []
    for seed in (2_200_000_001, 2_200_000_002, 2_200_000_003):
        params = training.seeded_params(perceiver_ar, cfg, seed)
        ids = markov_bytes(np.random.default_rng(seed), 4 * prompt_len).reshape(4, prompt_len)
        fwd = jax.jit(lambda p, x: perceiver_ar.logits(p, cfg, x, prompt_len - latents))
        exact = fwd(params, ids)
        best = exact.max(-1)
        row = {"seed": seed}

        def widest(logits):
            first = jnp.argmax(logits, -1)
            return float((best - jnp.take_along_axis(exact, first[..., None], -1)[..., 0]).max())

        # float32_default: float32 operands at the device's default precision,
        # what jnp.dot does to float32 inputs when nothing asks for more
        for name in ("float32_default", "bfloat16", "fp8"):
            with blocks.precision(name):
                low = jax.jit(lambda p, x: perceiver_ar.logits(p, cfg, x, prompt_len - latents))
                row[name] = widest(low(params, ids))
        row["logit_spread"] = float(jnp.std(exact))
        row["top2_margin_median"] = float(jnp.median(best - jnp.sort(exact, -1)[..., -2]))
        out.append(row)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
