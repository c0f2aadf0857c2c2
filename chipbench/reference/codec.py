"""Plain block top-k int8 codec with error feedback, as a WAN message format.

A pod's message is one flat float32 vector (its parameter-shaped tree,
leaves concatenated in tree order).  It is cut into blocks of ``block``
elements (the last one zero-padded).  In every block:

- the ``k`` elements of largest magnitude are kept, ranking magnitudes by
  the top 16 bits of their float32 pattern (8 exponent bits and the top 8
  mantissa bits); among equal keys the lower index wins;
- kept values are sent as int8 ``round(x / s)`` clipped to [-127, 127],
  with ``s = max|block| / 127`` (1 for an all-zero block), and their
  block-local indices as 16-bit integers, kept in ascending order.

Decoding scatters ``q * s`` back into a zero block.  With error feedback the
sender keeps ``message - decode(encode(message))`` and adds it to its next
message.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

KEY_BITS_DROPPED = 15


def k_per_block(block: int, frac: float) -> int:
    return max(1, min(block, int(round(block * frac))))


def encode(x: jnp.ndarray, k: int, block: int
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x (n,) float32 -> (q int8 (nb, k), idx int32 (nb, k), s (nb,))."""
    n = x.shape[0]
    block = min(block, n)
    xb = jnp.pad(x, (0, (-n) % block)).reshape(-1, block)
    mag = jnp.abs(xb)
    key = jax.lax.bitcast_convert_type(mag, jnp.int32) >> KEY_BITS_DROPPED
    _, idx = jax.lax.top_k(key, min(k, block))
    idx = jnp.sort(idx, axis=1)
    vals = jnp.take_along_axis(xb, idx, axis=1)
    amax = jnp.max(mag, axis=1)
    s = jnp.where(amax > 0, amax * jnp.float32(1.0 / 127.0), 1.0)
    q = jnp.clip(jnp.round(vals / s[:, None]), -127, 127).astype(jnp.int8)
    return q, idx, s


def decode(q: jnp.ndarray, idx: jnp.ndarray, s: jnp.ndarray, n: int,
           block: int) -> jnp.ndarray:
    block = min(block, n)
    nb = q.shape[0]
    dense = jnp.zeros((nb, block), jnp.float32)
    dense = dense.at[jnp.arange(nb)[:, None], idx].set(
        q.astype(jnp.float32) * s[:, None])
    return dense.reshape(-1)[:n]


def wire_bytes(n: int, k: int, block: int) -> int:
    """Bytes one message puts on the wire: int8 values, u16 indices and
    one float32 scale per block."""
    nb = -(-n // min(block, n))
    return nb * (k * (1 + 2) + 4)


def round_trip(flat: jnp.ndarray, k: int, block: int) -> jnp.ndarray:
    """decode(encode(flat)) for one pod's flat message."""
    return decode(*encode(flat, k, block), flat.shape[0], block)

