"""Shared pieces of the plain references: precision modes, init, norms, loss.

Everything here is straight ``jax.numpy`` in float32.  A reference runs in
one of two precisions:

- ``"fp32"``: every matrix product at ``Precision.HIGHEST`` (callers also
  trace under ``jax.default_matmul_precision("highest")``), so on a TPU no
  product passes through bfloat16.
- ``"fp8"``: the control.  The operands of every weight product (the
  projections, the MLP, the head) are rounded to float8-e4m3 with one
  scale per tensor (its absolute maximum onto 448) before the product; the
  gradient passes straight through the rounding.  This is the precision
  one step below the bfloat16 the configurations state, the step a later
  change would be tempted to take.

The initialisation draws the same random numbers as the system under test
(the same ``jax.random`` key tree and shapes), so both start from one set of
weights without the reference taking any array from the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def fake_fp8(x: jnp.ndarray) -> jnp.ndarray:
    """Round ``x`` to float8-e4m3 against a per-tensor scale; the gradient
    passes straight through."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(x: jnp.ndarray, w: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``x @ w`` for a weight product, in the reference's precision."""
    if precision == "fp8":
        x, w = fake_fp8(x), fake_fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def normal(key, shape, scale: float, dtype) -> jnp.ndarray:
    """A weight drawn N(0, scale^2) in float32, stored in ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rmsnorm(scale: jnp.ndarray, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm whose gain is applied as ``1 + scale``."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def silu(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.nn.sigmoid(x)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: jnp.ndarray) -> jnp.ndarray:
    """Masked token-mean next-token cross-entropy (natural log)."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def embed_scale(d_model: int) -> float:
    """Tied embeddings are scaled by sqrt(d_model) on the way in."""
    return math.sqrt(d_model)
