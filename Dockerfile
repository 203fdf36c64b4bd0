# TPU-host image for perceiver_io_tpu — the role the reference's Dockerfile
# plays for its CUDA/torch stack (reference Dockerfile:1), re-based on the
# JAX TPU wheel. On a Cloud TPU VM the libtpu runtime is injected by the
# `jax[tpu]` extra; the same image runs CPU-only for tests.
FROM python:3.12-slim

WORKDIR /app

RUN apt-get update \
    && apt-get install -y --no-install-recommends build-essential \
    && rm -rf /var/lib/apt/lists/*

COPY pyproject.toml README.md ./
COPY perceiver_io_tpu ./perceiver_io_tpu

# TPU runtime: jax[tpu] pulls libtpu from the Google releases index.
RUN pip install --no-cache-dir \
    --find-links https://storage.googleapis.com/jax-releases/libtpu_releases.html \
    "jax[tpu]==0.9.0" \
    && pip install --no-cache-dir ".[text,vision,audio]"

COPY tests ./tests
COPY examples ./examples
COPY Makefile ./

CMD ["python", "-c", "import jax, perceiver_io_tpu; print(jax.devices())"]
