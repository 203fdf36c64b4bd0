"""Plain float32 ``jax.numpy`` building blocks of the Perceiver references.

Nothing here imports the program. Every matrix product goes through
:func:`mm`, whose precision is switched by :func:`precision`: ``float32``
(six-pass ``highest``, the reference proper), ``float32_default`` (float32
operands at the device's default matmul precision) or one of the lower
precisions the controls use (``bfloat16`` inputs under float32 accumulation;
``fp8``, inputs rounded to float8_e4m3 under a per-tensor scale, accumulated
in float32, the product kept in bfloat16).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

LAYER_NORM_EPS = 1e-5
_PRECISION = ["float32"]
#: a stack of layers as a Python loop instead of a scan: the same
#: arithmetic, but XLA's cost analysis counts a scan's body once
UNROLL_STACKS = False


@contextlib.contextmanager
def precision(name: str):
    """Compute every :func:`mm` inside the block in ``name``."""
    if name not in ("float32", "float32_default", "bfloat16", "fp8"):
        raise ValueError(f"unknown precision {name!r}")
    _PRECISION.append(name)
    try:
        yield
    finally:
        _PRECISION.pop()


def _fp8(x):
    """``x`` rounded to float8_e4m3 under a per-tensor scale; the gradient
    passes straight through (a cast's own transpose would round the
    cotangent to float8 unscaled, and flush most of it to zero)."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def mm(spec: str, a, b):
    """``einsum(spec, a, b)`` in the current precision, float32 out."""
    mode = _PRECISION[-1]
    if mode == "float32_default":  # float32 operands, whatever the device does by default
        return jnp.einsum(spec, a, b)
    if mode == "bfloat16":
        return jnp.einsum(
            spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    if mode == "fp8":
        # float8 inputs under float32 accumulation, the product kept in
        # bfloat16: a float8 path of a program that computes in bfloat16
        out = jnp.einsum(spec, _fp8(a), _fp8(b), precision=jax.lax.Precision.HIGHEST)
        return out.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, p, name):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * p[name + ".g"] + p[name + ".b"]


def dense(x, p, name):
    y = mm("...i,io->...o", x, p[name + ".w"])
    bias = p.get(name + ".bias")
    return y if bias is None else y + bias


def rotary_angles(pos, rotate_dim: int):
    """``(b, n)`` positions -> ``(b, n, rotate_dim)`` angles, each frequency
    repeated for a channel pair."""
    inv = 1.0 / (10000 ** (jnp.arange(0, rotate_dim, 2, dtype=jnp.float32) / rotate_dim))
    return jnp.repeat(pos.astype(jnp.float32)[..., None] * inv, 2, axis=-1)


def rotate(t, angles):
    """Rotate the leading channels of ``t`` ``(b, h, m, c)`` by ``angles``
    ``(b, n, rd)``, right-aligned: the last ``m`` positions."""
    m, rd = t.shape[-2], angles.shape[-1]
    ang = angles[:, None, angles.shape[1] - m:, :]
    rot, rest = t[..., :rd], t[..., rd:]
    pairs = rot.reshape(*rot.shape[:-1], rd // 2, 2)
    half = jnp.stack((-pairs[..., 1], pairs[..., 0]), axis=-1).reshape(rot.shape)
    return jnp.concatenate((rot * jnp.cos(ang) + half * jnp.sin(ang), rest), axis=-1)


def _heads(x, h):
    b, n, c = x.shape
    return x.reshape(b, n, h, c // h).transpose(0, 2, 1, 3)


def attention(x_q, x_kv, p, name, heads, *, causal=False, key_pad=None,
              rot_q=None, rot_k=None):
    """Multi-head attention over already normalised inputs, with the output
    projection. ``key_pad`` is ``(b, j)``, True at padding; ``causal`` is
    right-aligned (query ``i`` sees keys up to ``i + j - i_len``)."""
    q = _heads(dense(x_q, p, name + ".q"), heads)
    k = _heads(dense(x_kv, p, name + ".k"), heads)
    v = _heads(dense(x_kv, p, name + ".v"), heads)
    q = q * (q.shape[-1] ** -0.5)
    if rot_q is not None:
        q = rotate(q, rot_q)
    if rot_k is not None:
        k = rotate(k, rot_k)
    logits = mm("bhic,bhjc->bhij", q, k)
    neg = jnp.finfo(jnp.float32).min
    i, j = logits.shape[-2:]
    if key_pad is not None:
        logits = jnp.where(key_pad[:, None, None, :], neg, logits)
    if causal:
        allowed = jnp.arange(j)[None, :] <= jnp.arange(i)[:, None] + (j - i)
        logits = jnp.where(allowed, logits, neg)
    o = mm("bhij,bhjc->bhic", jax.nn.softmax(logits, axis=-1), v)
    b, h, n, c = o.shape
    return dense(o.transpose(0, 2, 1, 3).reshape(b, n, h * c), p, name + ".o")


def mlp(x, p, name):
    x = layer_norm(x, p, name + ".norm")
    x = jax.nn.gelu(dense(x, p, name + ".hidden"), approximate=False)
    return dense(x, p, name + ".out")


def self_attention_layer(x, lp, heads, *, causal=False, rot=None):
    """One pre-norm self-attention layer with its MLP; ``lp`` holds the
    layer's parameters under their names within the layer."""
    h = layer_norm(x, lp, "norm")
    x = x + attention(h, h, lp, "attn", heads, causal=causal, rot_q=rot, rot_k=rot)
    return x + mlp(x, lp, "mlp")


def layer_params(p: dict, prefix: str) -> dict:
    """The parameters under ``prefix.``, by their names within the layer."""
    return {k[len(prefix) + 1:]: v for k, v in p.items() if k.startswith(prefix + ".")}


def self_attention_stack(x, p: dict, prefix: str, layers, heads, *, causal=False):
    """Layers ``prefix.<i>`` for ``i`` in ``layers``, one after another: a
    scan over their stacked parameters, so that a deep stack compiles one
    layer's program."""
    layers = list(layers)
    if not layers:
        return x
    each = [layer_params(p, f"{prefix}.{i}") for i in layers]
    if UNROLL_STACKS:
        for lp in each:
            x = self_attention_layer(x, lp, heads, causal=causal)
        return x
    stacked = {k: jnp.stack([lp[k] for lp in each]) for k in each[0]}
    body = lambda h, lp: (self_attention_layer(h, lp, heads, causal=causal), None)
    return jax.lax.scan(body, x, stacked)[0]


def token_nll(logits, labels, ignore=-100):
    """Sum of negative log-likelihoods over labels that are not ``ignore``,
    and their count."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels != ignore
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(valid, nll, 0.0).sum(), valid.sum()


def normal_params(key, shapes: dict, scale: float) -> dict:
    """One array per name from ``key``: normal with ``scale``, layer-norm
    gains around one. Biases are not zero, so that a bias the program drops
    shows in its output."""
    out = {}
    for idx, (name, shape) in enumerate(sorted(shapes.items())):
        x = scale * jax.random.normal(jax.random.fold_in(key, idx), shape, jnp.float32)
        out[name] = 1.0 + x if name.endswith(".g") else x
    return out
