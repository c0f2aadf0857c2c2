"""Fused WAN payload codec: single-pass block-local top-k + int8 quantization.

This is the production encode/decode pair for compressed inter-pod gradient
shipping (``repro.core.sync``).  It supersedes the iterative-argmax kernel in
``topk_compress.py`` (kept there as the benchmark baseline), whose inner
``fori_loop`` serializes O(k_block) argmax+scatter rounds per block — the
exact anti-pattern for the 8x128 VPU once k grows with the block.

Selection algorithm (threshold refinement, no O(k) serialization):

1. Bitcast ``|x|`` to int32.  Non-negative IEEE-754 floats order identically
   to their bit patterns, so magnitude rank == integer rank.
2. Truncate to the top 16 of the 31 magnitude bits (8 exponent + 8 mantissa).
   Under int8 payload quantization a finer sort key is pure waste: the
   truncation perturbs selection only among elements whose magnitudes agree
   to ~2^-8 relative — far below the quantizer's own resolution of 1/127 —
   and error feedback re-injects whatever the coarser boundary drops.
3. Build the k-th-largest key threshold bit-by-bit: 16 branch-free rounds of
   ``count(keys >= candidate)``, each a fully vectorized compare+reduce over
   the whole tile.  Work is O(16 * block) independent of k.
4. Select ``keys > T`` plus the first (by index) ties at ``T``; exact ranks
   come from a prefix sum done as log-step lane rotations and adds —
   again vectorized, never serialized.
5. Compact the winners with a one-hot dot product (the TPU-native scatter:
   MXU contraction instead of unsupported vector scatters).  Each one-hot
   column has exactly one nonzero and the dot runs at ``HIGHEST``
   precision, so the gathered fp32 values and the local indices
   (< block <= 2^16) come out exact.
6. Quantize the selected values to int8 against a per-block scale
   ``max|x| / 127`` — fused into the same kernel, so the fp32 payload never
   round-trips through HBM.

Tile geometry: each grid step processes ``ROWS`` = 8 independent blocks as
a 2D (8, block) tile — one fp32 sublane tile, the VPU-natural layout; a
block row is padded to whole 128-lane vregs.  The selection math batches
over the rows; the one-hot gather then walks the tile one block row (and,
at high k, one lane chunk) at a time so its tile stays within VMEM.

Wire format per block of ``block`` elements: ``k_block`` encoded values +
``k_block`` block-local indices (< 2^16, i.e. u16 on the wire; int32 in
device memory) + one fp32 scale.  The **value encoding** is a precision
ladder (``value_dtype``):

- ``"int8"``  — 1 byte/value, ``q = clip(round(x / (max|x|/127)))``.
- ``"fp8"``   — 1 byte/value, IEEE fp8-e4m3 (4 exponent + 3 mantissa bits,
  finite-only, max 448): the block is scaled so ``max|x|`` lands on 448,
  then cast to ``float8_e4m3fn`` and shipped as the raw bit pattern.  Same
  bytes as int8 but relative (not absolute) rounding error — robust to
  heavy-tailed blocks where one outlier crushes int8's uniform step.
- ``"int4"``  — 0.5 byte/value, ``q = clip(round(x / (max|x|/7)))`` packed
  two to a byte (low nibble first, two's complement).  Odd ``k_block``
  pads one zero nibble per block.

At k/n = 1% and block 4096 int8 is ~0.77% of the dense fp32 bytes and int4
~0.65% — the ``SyncConfig.payload_mb`` math.

``ref.wan_encode`` / ``ref.wan_decode`` are the pure-jnp oracles with
bit-identical semantics (same truncated sort key, same tie-breaking, same
quantizers), so round-trip tests assert exact equality, not allclose.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# keep the top 16 of the 31 magnitude bits (sign bit of |x| is always 0):
# bits 30..23 exponent, 22..15 top mantissa byte
KEY_MASK = ~((1 << 15) - 1)
_N_KEY_BITS = 16                       # threshold-refinement rounds (bits 30..15)

# scale = maxabs * fl32(1/Q), NOT maxabs / Q: XLA rewrites constant
# divides to reciprocal multiplies in some fusion contexts but not others,
# which costs 1 ulp of kernel-vs-oracle exactness; an explicit multiply is
# never transformed, so both sides round identically
INV_127 = 1.0 / 127.0                  # int8 tier: q in [-127, 127]
INV_7 = 1.0 / 7.0                      # int4 tier: q in [-7, 7]
FP8_MAX = 448.0                        # fp8-e4m3 largest finite value
INV_FP8_MAX = 1.0 / 448.0

VALUE_DTYPES = ("int8", "fp8", "int4")  # the codec's precision ladder

DEFAULT_BLOCK = 4096
ROWS = 8                               # blocks per grid step: one f32 sublane tile

# the (k_block, chunk) fp32 one-hot tile of one block row is the kernels'
# VMEM high-water mark; the gather walks the block in lane chunks small
# enough to keep it under budget at ANY compress fraction (the rows per
# grid step stay at one sublane tile — chunking is semantics-free)
_ONEHOT_BUDGET_BYTES = 2 << 20
_LANES = 128


def k_per_block(block: int, frac: float) -> int:
    """Per-block winner count for a target compression fraction."""
    return max(1, min(block, int(round(block * frac))))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _onehot_chunk(lanes: int, k_block: int) -> int:
    """Lane width of one gather step over a ``lanes``-wide block row: the
    whole row, halved (staying a multiple of 128) while the one-hot tile
    exceeds the budget."""
    chunk = lanes
    while chunk * _round_up(k_block, _LANES) * 4 > _ONEHOT_BUDGET_BYTES \
            and chunk % (2 * _LANES) == 0:
        chunk //= 2
    return chunk


def _cumsum_lanes(v: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the last axis: log-step roll-and-add
    (Mosaic has no cumsum lowering; a lane rotate is native)."""
    n = v.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    shift = 1
    while shift < n:
        v = v + jnp.where(lane >= shift, pltpu.roll(v, shift, v.ndim - 1), 0)
        shift *= 2
    return v


def _select_slots(x: jnp.ndarray, k_block: int):
    """Exact block-local top-k selection over a (rows, block) tile.

    Returns (slot int32 (rows, block): the winner's output slot, in index
    order, or -1 for a loser; maxabs f32 (rows, 1)).  Selection key: |x|
    truncated to KEY_MASK bits; ties broken by lowest index (matching
    ``jax.lax.top_k``'s stable ordering in the oracle).
    """
    mag = jnp.abs(x)
    bits = jax.lax.bitcast_convert_type(mag, jnp.int32) & KEY_MASK

    # threshold refinement: per row, largest T with count(bits >= T) >=
    # k_block, built bit-by-bit over the 16 key bits — branch-free
    # compare+reduce on the full tile each round
    def refine(i, t):
        cand = t | (jnp.int32(1) << (30 - i))
        cnt = jnp.sum((bits >= cand).astype(jnp.int32), axis=1, keepdims=True)
        return jnp.where(cnt >= k_block, cand, t)

    thresh = jax.lax.fori_loop(
        0, _N_KEY_BITS, refine, jnp.zeros((x.shape[0], 1), jnp.int32))

    above = bits > thresh
    n_above = jnp.sum(above.astype(jnp.int32), axis=1, keepdims=True)
    at = bits == thresh
    # first (k_block - n_above) ties by index, exactly filling k_block
    tie_rank = _cumsum_lanes(at.astype(jnp.int32)) - 1
    mask = above | (at & (tie_rank < k_block - n_above))
    pos = _cumsum_lanes(mask.astype(jnp.int32)) - 1       # slot, by index
    return jnp.where(mask, pos, -1), jnp.max(mag, axis=1, keepdims=True)


def _quantize(vals: jnp.ndarray, maxabs: jnp.ndarray, value_dtype: str
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tier value encoding of a (rows, k_block) tile of selected values
    against the (rows, 1) block maxima.

    Returns (q int8, scale f32 (rows, 1)).  ``q`` is always an int8
    *container*: the int4 tier's [-7, 7] codes are nibble-packed by the
    wrapper (packing is a pure bit shuffle, not kernel work), the fp8 tier
    ships the e4m3 bit pattern bitcast to int8.  All three run identically
    in the oracle — the expressions below are the bit-level spec.
    """
    if value_dtype == "int8":
        scale = jnp.where(maxabs > 0, maxabs * jnp.float32(INV_127), 1.0)
        q = jnp.clip(jnp.round(vals / scale), -127.0, 127.0)
        return q.astype(jnp.int8), scale
    if value_dtype == "int4":
        scale = jnp.where(maxabs > 0, maxabs * jnp.float32(INV_7), 1.0)
        q = jnp.clip(jnp.round(vals / scale), -7.0, 7.0)
        return q.astype(jnp.int8), scale
    if value_dtype == "fp8":
        # map the block max onto e4m3's largest finite value, clip the 1-ulp
        # overshoot the fp32 reciprocal can introduce, ship the bit pattern
        scale = jnp.where(maxabs > 0, maxabs * jnp.float32(INV_FP8_MAX), 1.0)
        f8 = jnp.clip(vals / scale, -FP8_MAX, FP8_MAX
                      ).astype(jnp.float8_e4m3fn)
        return jax.lax.bitcast_convert_type(f8, jnp.int8), scale
    raise ValueError(f"unknown value_dtype {value_dtype!r} "
                     f"(expected one of {VALUE_DTYPES})")


def _dequantize(q: jnp.ndarray, scales: jnp.ndarray, value_dtype: str
                ) -> jnp.ndarray:
    """Inverse of :func:`_quantize` ((rows, k) int8 container, (rows, 1)
    scales -> f32)."""
    if value_dtype == "fp8":
        v = jax.lax.bitcast_convert_type(q, jnp.float8_e4m3fn
                                         ).astype(jnp.float32)
    else:                                   # int8 / (unpacked) int4 codes
        v = q.astype(jnp.float32)
    return v * scales


def pack_nibbles(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 codes (.., k) int8 in [-7, 7] -> (.., ceil(k/2)) uint8.

    Low nibble first, two's complement; odd ``k`` pads one zero nibble."""
    k = q.shape[-1]
    if k % 2:
        q = jnp.concatenate(
            [q, jnp.zeros(q.shape[:-1] + (1,), q.dtype)], axis=-1)
    lo = q[..., 0::2].astype(jnp.uint8) & 0xF
    hi = q[..., 1::2].astype(jnp.uint8) & 0xF
    return lo | (hi << 4)


def unpack_nibbles(p: jnp.ndarray, k: int) -> jnp.ndarray:
    """Inverse of :func:`pack_nibbles`: (.., ceil(k/2)) uint8 -> (.., k) int8."""
    lo = (p & 0xF).astype(jnp.int32)
    hi = ((p >> 4) & 0xF).astype(jnp.int32)
    pairs = jnp.stack([lo, hi], axis=-1).reshape(p.shape[:-1] + (-1,))
    signed = jnp.where(pairs < 8, pairs, pairs - 16)
    return signed[..., :k].astype(jnp.int8)


# the one-hot gathers are exact only if the MXU keeps every fp32 bit of
# the gathered values and of the indices: at the TPU's default precision
# fp32 operands may pass through bf16, which holds integers only to 256
_EXACT = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))                          # a @ b.T


def _encode_kernel(x_ref, q_ref, idx_ref, scale_ref, slot_ref, vals_ref, *,
                   k_block: int, chunk: int, value_dtype: str):
    rows, lanes = x_ref.shape
    slot, maxabs = _select_slots(x_ref[...].astype(jnp.float32), k_block)
    slot_ref[...] = slot

    # one-hot compaction, one block row and one lane chunk at a time: the
    # (k_block, chunk) one-hot has exactly one 1 per winner, so the dot is
    # an exact gather on the MXU.  Row 0 of the left operand carries the
    # values, row 1 the lane indices
    sub = jax.lax.broadcasted_iota(jnp.int32, (ROWS, chunk), 0)
    slots = jax.lax.broadcasted_iota(jnp.int32, (k_block, chunk), 0)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, k_block), 0)
    for r in range(rows):
        acc = jnp.zeros((ROWS, k_block), jnp.float32)
        for c in range(0, lanes, chunk):
            onehot = (slots == slot_ref[r:r + 1, c:c + chunk]
                      ).astype(jnp.float32)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) + c
            lhs = jnp.where(sub == 0, x_ref[r:r + 1, c:c + chunk]
                            .astype(jnp.float32), lane.astype(jnp.float32))
            acc = acc + jax.lax.dot_general(
                lhs, onehot, _NT, precision=_EXACT,
                preferred_element_type=jnp.float32)
        vals_ref[r:r + 1, :] = jnp.sum(jnp.where(out_row == 0, acc, 0.0),
                                       axis=0, keepdims=True)
        idx_ref[r:r + 1, :] = jnp.sum(jnp.where(out_row == 1, acc, 0.0),
                                      axis=0, keepdims=True
                                      ).astype(jnp.int32)

    q, scale = _quantize(vals_ref[...], maxabs, value_dtype)
    q_ref[...] = q
    scale_ref[...] = scale


def _decode_kernel(q_ref, idx_ref, scale_ref, out_ref, *, chunk: int,
                   value_dtype: str):
    rows, lanes = out_ref.shape
    k_block = idx_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, k_block), 0)
    # transpose of the encode compaction: one nonzero per column -> exact
    for r in range(rows):
        v = _dequantize(q_ref[r:r + 1, :], scale_ref[r:r + 1, :], value_dtype)
        idx = idx_ref[r:r + 1, :]
        for c in range(0, lanes, chunk):
            onehot = (cols + c == idx).astype(jnp.float32)
            out_ref[r:r + 1, c:c + chunk] = jax.lax.dot_general(
                v, onehot, _NT, precision=_EXACT,
                preferred_element_type=jnp.float32)


def _geometry(n: int, block: int, k_block: int
              ) -> Tuple[int, int, int, int, int]:
    """(block, k_block, lanes, nb_real, nb_padded): pad n up to whole
    (ROWS x block) tiles and each block row up to whole 128-lane vregs.
    Padded blocks and lanes are all-zero and sliced off the outputs; a
    zero pad lane never displaces a real element (ties go to the lowest
    index, and a real block always has at least ``k_block`` elements)."""
    block = min(block, n)
    nb = -(-n // block)
    return (block, min(k_block, block), _round_up(block, _LANES), nb,
            _round_up(nb, ROWS))


@functools.partial(jax.jit,
                   static_argnames=("k_block", "block", "value_dtype",
                                    "interpret"))
def wan_encode_pallas(
    x: jnp.ndarray, k_block: int, *, block: int = DEFAULT_BLOCK,
    value_dtype: str = "int8", interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: flat (n,) -> (payload, local idx int32 (nb*k_block,), scales f32
    (nb,)); nb = ceil(n / block).  Payload: int8 (nb*k_block,) for
    int8/fp8 (fp8 ships its bit pattern), uint8 (nb*ceil(k_block/2),)
    nibble-packed for int4."""
    n = x.shape[0]
    block, k_block, lanes, nb, nb_pad = _geometry(n, block, k_block)
    xp = jnp.pad(x, (0, nb_pad * block - n)).reshape(nb_pad, block)
    xp = jnp.pad(xp, ((0, 0), (0, lanes - block)))
    row = lambda b: (b, 0)                              # noqa: E731

    q, idx, scales = pl.pallas_call(
        functools.partial(_encode_kernel, k_block=k_block,
                          chunk=_onehot_chunk(lanes, k_block),
                          value_dtype=value_dtype),
        grid=(nb_pad // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, lanes), row)],
        out_specs=[pl.BlockSpec((ROWS, k_block), row),
                   pl.BlockSpec((ROWS, k_block), row),
                   pl.BlockSpec((ROWS, 1), row)],
        out_shape=[jax.ShapeDtypeStruct((nb_pad, k_block), jnp.int8),
                   jax.ShapeDtypeStruct((nb_pad, k_block), jnp.int32),
                   jax.ShapeDtypeStruct((nb_pad, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ROWS, lanes), jnp.int32),
                        pltpu.VMEM((ROWS, k_block), jnp.float32)],
        interpret=interpret,
    )(xp)
    q, idx, scales = q[:nb], idx[:nb].reshape(-1), scales[:nb, 0]
    if value_dtype == "int4":
        q = pack_nibbles(q)          # per-block rows -> wire bytes
    return q.reshape(-1), idx, scales


@functools.partial(jax.jit,
                   static_argnames=("n", "block", "value_dtype", "interpret"))
def wan_decode_pallas(
    q: jnp.ndarray, idx: jnp.ndarray, scales: jnp.ndarray, n: int, *,
    block: int = DEFAULT_BLOCK, value_dtype: str = "int8",
    interpret: bool = False,
) -> jnp.ndarray:
    """Inverse of :func:`wan_encode_pallas` -> dense (n,) fp32."""
    # k_block from the index array — the int4 payload is nibble-packed, so
    # q's length is not k_block-shaped for every tier
    k_block = idx.shape[0] // (-(-n // min(block, n)))
    block, k_block, lanes, nb, nb_pad = _geometry(n, block, k_block)
    if value_dtype == "int4":
        q = unpack_nibbles(q.reshape(nb, -1), k_block)

    def pad_rows(a):
        return jnp.pad(a.reshape(nb, -1), ((0, nb_pad - nb), (0, 0)))

    row = lambda b: (b, 0)                              # noqa: E731
    dense = pl.pallas_call(
        functools.partial(_decode_kernel,
                          chunk=_onehot_chunk(lanes, k_block),
                          value_dtype=value_dtype),
        grid=(nb_pad // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, k_block), row),
                  pl.BlockSpec((ROWS, k_block), row),
                  pl.BlockSpec((ROWS, 1), row)],
        out_specs=pl.BlockSpec((ROWS, lanes), row),
        out_shape=jax.ShapeDtypeStruct((nb_pad, lanes), jnp.float32),
        interpret=interpret,
    )(pad_rows(q), pad_rows(idx), pad_rows(scales))
    return dense[:nb, :block].reshape(-1)[:n]
