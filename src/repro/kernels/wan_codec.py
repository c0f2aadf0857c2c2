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
4. Select ``keys > T`` plus the first (by index) ties at ``T``.  Ranks in
   index order come from prefix sums done on the MXU: a 0/1 tile times a
   triangular 0/1 matrix counts along each 128-lane row, times a ones
   matrix gives the row totals, and a strictly lower-triangular matrix
   sums the totals of the rows above.  Each operand is 0, 1 or a row total
   <= 128, so the bf16 products and their f32 sums are exact.
5. Quantize the whole tile against the per-block scale (``max|x| / 127``
   for int8): the same expression on the same value as quantizing only the
   winners, so the codes are bit-identical, and the fp32 payload never
   round-trips through HBM.
6. Gather the winners with a one-hot factored into row and lane.  A
   block's winners fill the slots in index order, so slot ``j`` lies in
   row ``r`` iff ``start_r <= j < start_r + count_r`` (the row's winner
   count and the count before it).  That (rows, k) row one-hot fetches
   each slot's whole row of codes and slot keys (slot mod 256, or 256 for
   a loser) in one matmul; within a row the winners hold fewer than 256
   consecutive slots, so the one lane whose key is ``j mod 256`` gives the
   slot's code and lane: ``idx_j = 128 * row_j + lane_j``.  Work per block
   is O(block + 128 * k), not O(block * k).

Decoding is the same factorization in transpose: ``(row one-hot x code) @
lane one-hot`` puts each code at its (row, lane) in one matmul, and the
scale is applied after on the VPU, as the oracle's ``code * scale``.  The
output matches the oracle's bits, signed zeros included: a zero sum (a
product with a zero operand keeps the other's sign) becomes the oracle's
``+0`` fill, and fp8's ``-0`` code is put back by a second 0/1 matmul.

Every MXU product in both kernels is exact at the default precision: the
operands are 0 or 1, int8 container codes (integers of magnitude <= 128),
e4m3 values (3 mantissa bits, exponents well inside bf16's) or slot keys
in [0, 256], all of which bf16 holds; and each output sums at most one
nonzero product, because a block's winners sit at distinct positions and
fill distinct slots.

Tile geometry: a block of ``block`` elements is a (rows, 128) tile, rows =
ceil(block / 128), its tail padded with zeros; for a block that is a
multiple of 128 this is the flat message's own tiled order, so the
wrapper's (nb, rows, 128) view needs no transpose.  Each grid step takes
a group of whole blocks (``_ENCODE_GROUP``, ``_DECODE_GROUP``).  The
threshold search reads them block-major (one block per sublane row, so
each round's counts are lane reductions shared by the group); everything
after works on the (group, rows, 128) tiles, and the gather and scatter
walk the slots 128 at a time, so no value of a step grows with k.

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
_LANES = 128                           # a block row: one vreg's lanes
# blocks per grid step, from a sweep of 8, 16 and 32 on a TPU v5e (block
# 4096, k 82): encode was fastest at 32, decode at 8
_ENCODE_GROUP = 32
_DECODE_GROUP = 8


def k_per_block(block: int, frac: float) -> int:
    """Per-block winner count for a target compression fraction."""
    return max(1, min(block, int(round(block * frac))))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _block_threshold(x_ref, k_block: int):
    """Per-block selection threshold over a (G, rows, 128) tile of G blocks.

    Returns (thresh int32 (G, 1): the k-th largest truncated key,
    n_above int32 (G, 1): how many keys exceed it, maxabs f32 (G, 1)).
    Works on the block-major view, each block one sublane row of
    ``rows * 128`` lanes, so each refinement round's per-block counts are
    lane reductions shared by G blocks.
    """
    x = jnp.concatenate([x_ref[:, r, :] for r in range(x_ref.shape[1])],
                        axis=1).astype(jnp.float32)
    mag = jnp.abs(x)
    bits = jax.lax.bitcast_convert_type(mag, jnp.int32) & KEY_MASK

    # threshold refinement: per block, largest T with count(bits >= T) >=
    # k_block, built bit-by-bit over the 16 key bits — branch-free
    # compare+reduce on the full tile each round
    def refine(i, t):
        cand = t | (jnp.int32(1) << (30 - i))
        cnt = jnp.sum((bits >= cand).astype(jnp.int32), axis=1, keepdims=True)
        return jnp.where(cnt >= k_block, cand, t)

    thresh = jax.lax.fori_loop(
        0, _N_KEY_BITS, refine, jnp.zeros((x.shape[0], 1), jnp.int32))
    n_above = jnp.sum((bits > thresh).astype(jnp.int32), axis=1,
                      keepdims=True)
    return thresh, n_above, jnp.max(mag, axis=1, keepdims=True)


def _row_scan(v: jnp.ndarray):
    """Prefix sums in index order of a 0/1 f32 (G, rows, 128) tile.

    Returns (incl: inclusive prefix along each row, tot: the row's total,
    before: the totals of the rows above it), each f32 (G, rows, 128), the
    last two constant along lanes.  All three are MXU products whose
    operands are 0, 1 or a row total <= 128, so bf16 holds every operand
    exactly and the f32 accumulation is exact."""
    g, rows, lanes = v.shape
    li = jax.lax.broadcasted_iota(jnp.int32, (lanes, 2 * lanes), 0)
    lo = jax.lax.broadcasted_iota(jnp.int32, (lanes, 2 * lanes), 1)
    scan = ((li <= lo) | (lo >= lanes)).astype(jnp.bfloat16)  # [tri | ones]
    cu = jnp.einsum("grl,lm->grm", v.astype(jnp.bfloat16), scan,
                    preferred_element_type=jnp.float32)
    incl, tot = cu[..., :lanes], cu[..., lanes:]
    if rows == 1:
        return incl, tot, jnp.zeros_like(tot)
    ri = jax.lax.broadcasted_iota(jnp.int32, (g, rows, rows), 1)
    si = jax.lax.broadcasted_iota(jnp.int32, (g, rows, rows), 2)
    before = jnp.einsum("grs,gsl->grl", (si < ri).astype(jnp.bfloat16),
                        tot.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    return incl, tot, before


def _scale(maxabs: jnp.ndarray, value_dtype: str) -> jnp.ndarray:
    """Per-block scale of the tier: ``max|x|`` over the largest code."""
    inv = {"int8": INV_127, "int4": INV_7, "fp8": INV_FP8_MAX}
    if value_dtype not in inv:
        raise ValueError(f"unknown value_dtype {value_dtype!r} "
                         f"(expected one of {VALUE_DTYPES})")
    return jnp.where(maxabs > 0, maxabs * jnp.float32(inv[value_dtype]), 1.0)


def _codes(x: jnp.ndarray, scale: jnp.ndarray, value_dtype: str
           ) -> jnp.ndarray:
    """Per-tier value encoding of ``x`` against its block's ``scale``, as
    the f32 value of the int8 *container* (an integer in [-128, 127]): the
    int4 tier's [-7, 7] codes are nibble-packed by the wrapper (packing is a
    pure bit shuffle, not kernel work), the fp8 tier ships the e4m3 bit
    pattern bitcast to int8.  The oracle runs the same expressions — they
    are the bit-level spec."""
    if value_dtype == "int8":
        return jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    if value_dtype == "int4":
        return jnp.clip(jnp.round(x / scale), -7.0, 7.0)
    # fp8: map the block max onto e4m3's largest finite value, clip the
    # 1-ulp overshoot the fp32 reciprocal can introduce, ship the bits
    f8 = jnp.clip(x / scale, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)
    return jax.lax.bitcast_convert_type(f8, jnp.int8).astype(jnp.float32)


def _code_values(q: jnp.ndarray, value_dtype: str) -> jnp.ndarray:
    """The value each int8 container code stands for, before its scale:
    the integer itself (int8, unpacked int4) or the e4m3 number (fp8)."""
    if value_dtype == "fp8":
        return jax.lax.bitcast_convert_type(q, jnp.float8_e4m3fn
                                            ).astype(jnp.float32)
    return q.astype(jnp.float32)


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


# Each gather and scatter below is one bf16 MXU product with f32
# accumulation at the default precision, exact because bf16 holds every
# operand and each output sums at most one nonzero product (module
# docstring).


def _encode_kernel(x_ref, q_ref, idx_ref, scale_ref, *, k_block: int,
                   value_dtype: str):
    g, rows, lanes = x_ref.shape
    thresh, n_above, maxabs = _block_threshold(x_ref, k_block)
    x = x_ref[...].astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32) & KEY_MASK
    t = thresh.reshape(g, 1, 1)

    # winners: keys above T plus the first (k_block - n_above) ties by index
    at = bits == t
    t_incl, _, t_before = _row_scan(at.astype(jnp.float32))
    need = (k_block - n_above).astype(jnp.float32).reshape(g, 1, 1)
    win = (bits > t) | (at & (t_before + t_incl <= need))
    w_incl, w_tot, w_before = _row_scan(win.astype(jnp.float32))

    scale = _scale(maxabs, value_dtype)
    codes = _codes(x, scale.reshape(g, 1, 1), value_dtype)
    # per position: its slot mod 256 (256 for a loser) and its code; a
    # row's winners fill consecutive slots, fewer than 256 of them, so the
    # key names one lane of the row
    slot = (w_before + w_incl).astype(jnp.int32) - 1
    key = jnp.where(win, (slot & 255).astype(jnp.float32), 256.0)
    rhs = jnp.concatenate([key, codes], axis=2).astype(jnp.bfloat16)
    start, count = w_before[..., :1], w_tot[..., :1]

    # factored gather, 128 slots at a time: row one-hot a_t[r, j] (slot j
    # lies in row r) fetches the row's keys and codes, then the lane whose
    # key is j mod 256 gives slot j's lane and code
    for c0 in range(0, k_block, lanes):
        kc = min(lanes, k_block - c0)
        j = jax.lax.broadcasted_iota(jnp.int32, (g, rows, kc), 2) + c0
        a_t = (j >= start) & (j < start + count)
        got = jnp.einsum("grc,grk->gck", rhs, a_t.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)  # (g, 2L, kc)
        jj = jax.lax.broadcasted_iota(jnp.int32, (g, lanes, kc), 2) + c0
        hit = got[:, :lanes, :] == (jj & 255).astype(jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (g, lanes, kc), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (g, rows, kc), 1)
        code = jnp.sum(jnp.where(hit, got[:, lanes:, :], 0.0), axis=1)
        col = jnp.sum(jnp.where(hit, lane, 0), axis=1)
        r_j = jnp.sum(jnp.where(a_t, row, 0), axis=1)
        q_ref[:, c0:c0 + kc] = code.astype(jnp.int8)
        idx_ref[:, c0:c0 + kc] = r_j * lanes + col
    scale_ref[...] = scale


def _decode_kernel(q_ref, idx_ref, scale_ref, out_ref, *, value_dtype: str):
    g, rows, lanes = out_ref.shape
    k_block = idx_ref.shape[1]
    code = _code_values(q_ref[...], value_dtype)
    if value_dtype == "fp8":
        minus_zero = (q_ref[...].astype(jnp.int32) == -128
                      ).astype(jnp.float32)
    idx = idx_ref[...]
    # factored scatter, 128 slots at a time: (row one-hot x code) @ lane
    # one-hot puts each code at its (row, lane); the scale is applied after,
    # on the VPU, as the oracle's code * scale.  A product with a zero
    # operand keeps the other's sign, so a zero sum is made the oracle's +0
    # fill, and an fp8 -0 code (bits 0x80) is put back where it lands
    dense = jnp.zeros((g, rows, lanes), jnp.float32)
    neg_zero = dense
    for c0 in range(0, k_block, lanes):
        kc = min(lanes, k_block - c0)
        i = idx[:, c0:c0 + kc].reshape(g, 1, kc)
        v = code[:, c0:c0 + kc].reshape(g, 1, kc)
        row = jax.lax.broadcasted_iota(jnp.int32, (g, rows, kc), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (g, lanes, kc), 1)
        in_row = row == (i >> 7)
        a = jnp.where(in_row, v, 0.0).astype(jnp.bfloat16)
        b_t = (lane == (i & (lanes - 1))).astype(jnp.bfloat16)
        dense = dense + jnp.einsum("grk,glk->grl", a, b_t,
                                   preferred_element_type=jnp.float32)
        if value_dtype == "fp8":
            mz = minus_zero[:, c0:c0 + kc].reshape(g, 1, kc)
            z = jnp.where(in_row, mz, 0.0).astype(jnp.bfloat16)
            neg_zero = neg_zero + jnp.einsum(
                "grk,glk->grl", z, b_t, preferred_element_type=jnp.float32)
    out = jnp.where(dense == 0.0, 0.0, dense) * scale_ref[...].reshape(g, 1, 1)
    if value_dtype == "fp8":
        out = jnp.where(neg_zero > 0.0, -0.0, out)
    out_ref[...] = out


def _geometry(n: int, block: int, k_block: int, group: int
              ) -> Tuple[int, int, int, int, int]:
    """(block, k_block, rows, nb_real, nb_padded): each block a (rows, 128)
    tile, its tail padded to whole lane rows, and n padded to whole groups
    of ``group`` blocks.  Padded blocks and lanes are all-zero and sliced
    off the outputs; a zero pad lane never displaces a real element (ties
    go to the lowest index, and a real block always has at least
    ``k_block`` elements)."""
    block = min(block, n)
    nb = -(-n // block)
    return (block, min(k_block, block), -(-block // _LANES), nb,
            _round_up(nb, group))


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
    g = _ENCODE_GROUP
    block, k_block, rows, nb, nb_pad = _geometry(n, block, k_block, g)
    xp = jnp.pad(x, (0, nb_pad * block - n)).reshape(nb_pad, block)
    xp = jnp.pad(xp, ((0, 0), (0, rows * _LANES - block)))
    row = lambda b: (b, 0)                              # noqa: E731

    q, idx, scales = pl.pallas_call(
        functools.partial(_encode_kernel, k_block=k_block,
                          value_dtype=value_dtype),
        grid=(nb_pad // g,),
        in_specs=[pl.BlockSpec((g, rows, _LANES), lambda b: (b, 0, 0))],
        out_specs=[pl.BlockSpec((g, k_block), row),
                   pl.BlockSpec((g, k_block), row),
                   pl.BlockSpec((g, 1), row)],
        out_shape=[jax.ShapeDtypeStruct((nb_pad, k_block), jnp.int8),
                   jax.ShapeDtypeStruct((nb_pad, k_block), jnp.int32),
                   jax.ShapeDtypeStruct((nb_pad, 1), jnp.float32)],
        interpret=interpret,
    )(xp.reshape(nb_pad, rows, _LANES))
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
    g = _DECODE_GROUP
    block, k_block, rows, nb, nb_pad = _geometry(n, block, k_block, g)
    if value_dtype == "int4":
        q = unpack_nibbles(q.reshape(nb, -1), k_block)

    def pad_rows(a):
        return jnp.pad(a.reshape(nb, -1), ((0, nb_pad - nb), (0, 0)))

    row = lambda b: (b, 0)                              # noqa: E731
    dense = pl.pallas_call(
        functools.partial(_decode_kernel, value_dtype=value_dtype),
        grid=(nb_pad // g,),
        in_specs=[pl.BlockSpec((g, k_block), row),
                  pl.BlockSpec((g, k_block), row),
                  pl.BlockSpec((g, 1), row)],
        out_specs=pl.BlockSpec((g, rows, _LANES), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb_pad, rows, _LANES), jnp.float32),
        interpret=interpret,
    )(pad_rows(q), pad_rows(idx), pad_rows(scales))
    return dense.reshape(nb_pad, -1)[:nb, :block].reshape(-1)[:n]
