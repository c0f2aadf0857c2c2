"""Fused WAN payload codec: kernel-vs-oracle exactness, bucketed sync-layer
round trip, error-feedback semantics + convergence parity, chunked-overlap
equivalence, payload accounting.

Kernel tests run the Pallas kernels in interpret mode and assert EXACT
equality against the ``ref.py`` oracles — the codec's selection key,
tie-breaking and quantizer are specified to the bit (see
``repro.kernels.wan_codec``), so allclose would hide real drift.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sync import (SyncConfig, apply_sync, init_sync_state,
                             on_step_gradients, resize_sync_state)
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.wan_codec import (k_per_block, wan_decode_pallas,
                                     wan_encode_pallas)

RNG = np.random.default_rng(0)


def _rand(n):
    return jnp.asarray(RNG.normal(size=(n,)), jnp.float32)


# ------------------------------------------------------- kernel vs oracle


def _codec_input(n, block, layout):
    """A codec input whose winners fall in the named layout over each
    block's (block / 128, 128) tile of rows and lanes."""
    x = np.asarray(_rand(n))
    if layout is None:
        return jnp.asarray(x)
    x = x * 0.01
    starts = range(0, n - block + 1, block)
    if layout == "one_row":           # row 5's magnitudes dominate, all
        for b in starts:              # negative: its other lanes sum -0s
            x[b + 640:b + 768] = -10.0 - np.abs(x[b + 640:b + 768])
    elif layout == "row_each":        # one winner per 128-lane row
        for b in starts:
            for r in range(block // 128):
                x[b + 128 * r + (37 * r) % 128] = 10.0 + r
    elif layout == "ties_cross_row":  # the tie cut falls past lane 127,
        for b in starts:              # with winners above T after the ties
            x[b + 100:b + 161] = 0.5
            x[b + 200:b + 210] = 5.0
    elif layout == "signed_zeros":    # -0 winners after the negative ones
        x[:] = -0.0
        x[5::97] = -1.0 - np.abs(x[5::97])
    return jnp.asarray(x, jnp.float32)


def _assert_same_bits(a, b):
    """Decoded outputs agree to the bit, signed zeros included."""
    np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                  np.asarray(b).view(np.uint32))


# (n, k_block, block, layout): random normal input unless a layout is named
_KERNEL_CASES = [
    pytest.param(8192, 82, 4096, "one_row", id="winners-in-one-row"),
    pytest.param(8192, 32, 4096, "row_each", id="one-winner-per-row"),
    pytest.param(16384, k_per_block(4096, 0.05), 4096, None,
                 id="k-above-128"),
    pytest.param(1000, 6, 64, None, id="block-64"),
    pytest.param(5000, 20, 1000, None, id="block-1000"),
    pytest.param(512, 40, 256, "ties_cross_row", id="ties-span-two-rows"),
    pytest.param(512, 40, 256, "signed_zeros", id="signed-zeros"),
    pytest.param(394, 1, 100, "signed_zeros", id="one-negative-winner"),
]


@pytest.mark.parametrize("n,k_block,block,layout", [
    pytest.param(4096, 41, 1024, None, id="4096-41-1024"),
    pytest.param(8192, 82, 4096, None, id="8192-82-4096"),
    # non-multiple of block
    pytest.param(1000, 16, 256, None, id="1000-16-256"),
    # single short block
    pytest.param(300, 8, 512, None, id="300-8-512"),
    # padded tail block
    pytest.param(5000, 12, 1024, None, id="5000-12-1024"),
    # padded tail + partial group of blocks
    pytest.param(9000, 50, 4096, None, id="9000-50-4096"),
] + _KERNEL_CASES)
def test_encode_kernel_matches_oracle_exactly(n, k_block, block, layout):
    x = _codec_input(n, block, layout)
    q1, i1, s1 = wan_encode_pallas(x, k_block, block=block, interpret=True)
    q2, i2, s2 = ref.wan_encode(x, k_block, block=block)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    d1 = wan_decode_pallas(q1, i1, s1, n, block=block, interpret=True)
    d2 = ref.wan_decode(q2, i2, s2, n, block=block)
    _assert_same_bits(d1, d2)


def test_encode_handles_ties_and_zero_blocks():
    x = _rand(777).at[:64].set(0.25).at[400:].set(0.0)
    q1, i1, s1 = wan_encode_pallas(x, 16, block=128, interpret=True)
    q2, i2, s2 = ref.wan_encode(x, 16, block=128)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    # all-zero input: scale must fall back to 1, payload to exact zeros
    z = jnp.zeros((512,), jnp.float32)
    q, i, s = wan_encode_pallas(z, 8, block=256, interpret=True)
    assert float(jnp.max(jnp.abs(q))) == 0.0
    np.testing.assert_array_equal(np.asarray(s), np.ones(2, np.float32))
    d = wan_decode_pallas(q, i, s, 512, block=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(d), np.zeros(512, np.float32))


def test_quantization_error_bounded_by_half_scale():
    """Every reconstructed winner is within scale/2 of its fp32 value."""
    n, block, k_block = 4096, 1024, 64
    x = _rand(n)
    q, idx, scales = ref.wan_encode(x, k_block, block=block)
    dense = np.asarray(ref.wan_decode(q, idx, scales, n, block=block))
    xb = np.asarray(x).reshape(-1, block)
    db = dense.reshape(-1, block)
    il = np.asarray(idx).reshape(-1, k_block)
    for b in range(xb.shape[0]):
        err = np.abs(db[b, il[b]] - xb[b, il[b]])
        assert err.max() <= float(scales[b]) * 0.5 + 1e-7


def test_selection_energy_close_to_exact_topk():
    """The 16-bit truncated sort key costs (almost) no selection quality."""
    n, k = 8192, 256
    x = _rand(n)
    q, idx, scales = ref.wan_encode(x, k // 8, block=1024)
    d_codec = np.asarray(ref.wan_decode(q, idx, scales, n, block=1024))
    d_exact = np.asarray(
        ref.topk_decompress(*ref.topk_exact(x, k), n))
    assert np.sum(d_codec ** 2) >= 0.9 * np.sum(d_exact ** 2)


def _largest_kernel_value_bytes(fn, *args):
    """Bytes of the largest value computed inside any Pallas kernel that
    ``fn`` calls: what one grid step holds in VMEM at its high-water mark."""
    from jax.extend import core

    sizes = [0]

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside:
                sizes.extend(v.aval.size * v.aval.dtype.itemsize
                             for v in eqn.outvars if hasattr(v.aval, "shape"))
            for p in eqn.params.values():
                sub = p.jaxpr if isinstance(p, core.ClosedJaxpr) else p
                if isinstance(sub, core.Jaxpr):
                    walk(sub, inside or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return max(sizes)


def test_high_k_auto_caps_onehot_tile_and_stays_exact():
    """At aggressive fractions (205 winners a block) the factored gather
    and scatter walk the slots 128 at a time, so no value of a grid step
    grows with k: the largest is one pass's gathered (keys | codes) tile,
    (blocks per step, 2 x 128, 128) f32.  Chunking is semantics-free.
    """
    from repro.kernels import wan_codec as wc

    block = 4096
    kb = k_per_block(block, 0.05)            # 205 winners/block
    assert kb > 128
    x = _rand(1 << 16)
    tile = 2 * 128 * 128 * 4                 # one block's (keys | codes)
    enc = functools.partial(wan_encode_pallas, k_block=kb, block=block,
                            interpret=True)
    assert _largest_kernel_value_bytes(enc, x) <= wc._ENCODE_GROUP * tile
    q1, i1, s1 = enc(x)
    q2, i2, s2 = ref.wan_encode(x, kb, block=block)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    dec = functools.partial(wan_decode_pallas, n=1 << 16, block=block,
                            interpret=True)
    assert (_largest_kernel_value_bytes(dec, q1, i1, s1)
            <= wc._DECODE_GROUP * tile)
    d1 = dec(q1, i1, s1)
    d2 = ref.wan_decode(q2, i2, s2, 1 << 16, block=block)
    _assert_same_bits(d1, d2)


def test_ops_dispatch_oracle_equals_kernel():
    x = _rand(6000)
    kb = k_per_block(1024, 0.05)
    out_k = kops.wan_encode(x, kb, block=1024, interpret=True)
    out_o = kops.wan_encode(x, kb, block=1024, use_kernel=False)
    for a, b in zip(out_k, out_o):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    d_k = kops.wan_decode(*out_k, 6000, block=1024, interpret=True)
    d_o = kops.wan_decode(*out_o, 6000, block=1024, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_o))


# --------------------------------------------- precision tiers (int4 / fp8)


@pytest.mark.parametrize("value_dtype", ["int8", "fp8", "int4"])
@pytest.mark.parametrize("n,k_block,block,layout", [
    # odd k_block: int4 pads one zero nibble per block
    pytest.param(4096, 41, 1024, None, id="4096-41-1024"),
    # padded tail block
    pytest.param(5000, 12, 1024, None, id="5000-12-1024"),
    # single short block
    pytest.param(300, 8, 512, None, id="300-8-512"),
] + _KERNEL_CASES)
def test_tier_kernel_matches_oracle_exactly(value_dtype, n, k_block, block,
                                            layout):
    x = _codec_input(n, block, layout)
    q1, i1, s1 = wan_encode_pallas(x, k_block, block=block,
                                   value_dtype=value_dtype, interpret=True)
    q2, i2, s2 = ref.wan_encode(x, k_block, block=block,
                                value_dtype=value_dtype)
    assert q1.dtype == q2.dtype
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    d1 = wan_decode_pallas(q1, i1, s1, n, block=block,
                           value_dtype=value_dtype, interpret=True)
    d2 = ref.wan_decode(q2, i2, s2, n, block=block, value_dtype=value_dtype)
    _assert_same_bits(d1, d2)


def test_int4_payload_is_nibble_packed():
    """int4 wire bytes: uint8, ceil(k_block/2) per block — half of int8."""
    n, block = 4096, 1024
    for kb in (16, 17):                       # even and odd winner counts
        q8, _, _ = ref.wan_encode(_rand(n), kb, block=block,
                                  value_dtype="int8")
        q4, _, _ = ref.wan_encode(_rand(n), kb, block=block,
                                  value_dtype="int4")
        nb = n // block
        assert q8.shape[0] == nb * kb and q8.dtype == jnp.int8
        assert q4.shape[0] == nb * ((kb + 1) // 2) and q4.dtype == jnp.uint8


def test_pack_unpack_nibbles_round_trip():
    from repro.kernels.wan_codec import pack_nibbles, unpack_nibbles

    for k in (6, 7):                          # even / odd
        q = jnp.asarray(RNG.integers(-7, 8, size=(5, k)), jnp.int8)
        p = pack_nibbles(q)
        assert p.shape == (5, (k + 1) // 2) and p.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(unpack_nibbles(p, k)),
                                      np.asarray(q))


@pytest.mark.parametrize("value_dtype", ["fp8", "int4"])
def test_tier_ties_and_zero_blocks(value_dtype):
    x = _rand(777).at[:64].set(0.25).at[400:].set(0.0)
    q1, i1, s1 = wan_encode_pallas(x, 16, block=128,
                                   value_dtype=value_dtype, interpret=True)
    q2, i2, s2 = ref.wan_encode(x, 16, block=128, value_dtype=value_dtype)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    # all-zero input: scale falls back to 1, payload decodes to exact zeros
    z = jnp.zeros((512,), jnp.float32)
    q, i, s = wan_encode_pallas(z, 8, block=256, value_dtype=value_dtype,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(s), np.ones(2, np.float32))
    d = wan_decode_pallas(q, i, s, 512, block=256, value_dtype=value_dtype,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(d), np.zeros(512, np.float32))


def test_int4_round_trip_error_bounded_by_half_scale():
    """Every reconstructed winner is within scale/2 = max|x|/14 of its
    fp32 value — the int4 analogue of the int8 half-step bound."""
    n, block, k_block = 4096, 1024, 64
    x = _rand(n)
    q, idx, scales = ref.wan_encode(x, k_block, block=block,
                                    value_dtype="int4")
    dense = np.asarray(ref.wan_decode(q, idx, scales, n, block=block,
                                      value_dtype="int4"))
    xb = np.asarray(x).reshape(-1, block)
    db = dense.reshape(-1, block)
    il = np.asarray(idx).reshape(-1, k_block)
    for b in range(xb.shape[0]):
        err = np.abs(db[b, il[b]] - xb[b, il[b]])
        assert err.max() <= float(scales[b]) * 0.5 + 1e-7


def test_fp8_round_trip_error_is_relative():
    """fp8-e4m3 rounds to 3 mantissa bits: every reconstructed winner is
    within half an ulp — 2^-4 relative — of its fp32 value (plus the
    subnormal floor scale * 2^-10)."""
    n, block, k_block = 4096, 1024, 64
    x = _rand(n)
    q, idx, scales = ref.wan_encode(x, k_block, block=block,
                                    value_dtype="fp8")
    dense = np.asarray(ref.wan_decode(q, idx, scales, n, block=block,
                                      value_dtype="fp8"))
    xs = np.asarray(x)
    sel = dense != 0
    err = np.abs(dense[sel] - xs[sel])
    bound = np.abs(xs[sel]) * 2.0 ** -4 + float(scales.max()) * 2.0 ** -10
    assert (err <= bound).all()


def test_fp8_beats_int8_on_heavy_tailed_blocks():
    """The fp8 tier's reason to exist: int8's uniform step is set by the
    block max, so one huge outlier crushes every small value to zero; fp8's
    relative rounding keeps them.  Reconstruction error (on the selected
    entries) must be strictly better for fp8 here."""
    block = 256
    x = np.asarray(RNG.normal(size=(1024,)) * 1e-3, np.float32)
    x[::block] = 50.0                          # one outlier per block
    xj = jnp.asarray(x)
    errs = {}
    for dt in ("int8", "fp8"):
        q, idx, s = ref.wan_encode(xj, 32, block=block, value_dtype=dt)
        d = np.asarray(ref.wan_decode(q, idx, s, 1024, block=block,
                                      value_dtype=dt))
        sel = np.zeros_like(x, bool)
        il = np.asarray(idx).reshape(-1, 32)
        for b in range(il.shape[0]):
            sel[b * block + il[b]] = True
        errs[dt] = np.abs(d - x)[sel].sum()
    assert errs["fp8"] < errs["int8"]


# ------------------------------------------------- sync-layer integration


def _grads(n_pods=2, seed=1):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(n_pods, 300, 40)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n_pods, 77)), jnp.float32)}


def _one_sync(cfg, g):
    p = jax.tree.map(jnp.zeros_like, g)
    st = init_sync_state(cfg, p)
    _, st = on_step_gradients(cfg, g, st)
    return apply_sync(cfg, p, st, lr=1.0)


def test_codec_ship_round_trips_bucketed_pytree():
    """Bucket -> encode -> ring -> decode reproduces the legacy per-leaf
    ring semantics up to the codec's lossiness: what arrives is the ring
    peer's compressed message (energy bounded, correct peer)."""
    from repro.core.sync import _pack_stacked

    g = _grads()
    cfg = SyncConfig("asgd_ga", 1, compress_topk=0.25, quantize_int8=True,
                     codec_block=512)
    dense, _ = _one_sync(SyncConfig("asgd_ga", 1), g)
    comp, _ = _one_sync(cfg, g)
    # params went DOWN by the (rolled) peer message: recover it, in the
    # same bucket order the codec compressed (blocks span leaf boundaries)
    m_dense = -np.asarray(_pack_stacked(dense))
    m_comp = -np.asarray(_pack_stacked(comp))
    # compressed message keeps the top-magnitude mass of the dense one
    e = np.sum(m_comp ** 2) / np.sum(m_dense ** 2)
    assert 0.4 < e <= 1.0
    # and every shipped entry matches the dense message to within the int8
    # step of its 512-element block (scale = blockmax/127)
    for pod in range(m_dense.shape[0]):
        db = np.pad(m_dense[pod], (0, (-m_dense.shape[1]) % 512)
                    ).reshape(-1, 512)
        cb = np.pad(m_comp[pod], (0, (-m_comp.shape[1]) % 512)
                    ).reshape(-1, 512)
        step = np.abs(db).max(axis=1, keepdims=True) / 127.0
        nz = cb != 0
        assert (np.abs(cb - db)[nz] <=
                (np.broadcast_to(step * 0.5 + 1e-7, cb.shape))[nz]).all()


@pytest.mark.parametrize("chunks", [2, 3, 8])
def test_chunked_overlap_equals_unchunked(chunks):
    g = _grads()
    base = dict(compress_topk=0.25, quantize_int8=True, error_feedback=True,
                codec_block=512)
    p1, s1 = _one_sync(SyncConfig("asgd_ga", 1, overlap_chunks=1, **base), g)
    pc, sc = _one_sync(
        SyncConfig("asgd_ga", 1, overlap_chunks=chunks, **base), g)
    for k in g:
        np.testing.assert_array_equal(np.asarray(p1[k]), np.asarray(pc[k]))
    np.testing.assert_array_equal(np.asarray(s1.ef_residual),
                                  np.asarray(sc.ef_residual))


def test_ef_residual_is_exact_compression_error():
    """residual == message - decode(encode(message)), and re-injection
    makes two syncs ship more mass than two independent ones."""
    g = _grads()
    cfg = SyncConfig("asgd_ga", 1, compress_topk=0.1, quantize_int8=True,
                     error_feedback=True, codec_block=512)
    p = jax.tree.map(jnp.zeros_like, g)
    st = init_sync_state(cfg, p)
    _, st = on_step_gradients(cfg, g, st)
    out, st2 = apply_sync(cfg, p, st, lr=1.0)
    # reconstruct: message (bucket order) minus what the peer received
    from repro.core.sync import _pack_stacked
    msg = np.asarray(_pack_stacked(jax.tree.map(
        lambda b: b, st.ga_buffer)))
    received = -np.asarray(_pack_stacked(out))   # rolled peer message
    local = np.roll(received, -cfg.peer_shift, axis=0)   # undo the ring
    np.testing.assert_allclose(np.asarray(st2.ef_residual), msg - local,
                               atol=1e-6)
    # EF off -> residual stays empty
    cfg0 = SyncConfig("asgd_ga", 1, compress_topk=0.1, quantize_int8=True)
    _, st0 = _one_sync(cfg0, g)
    assert st0.ef_residual.shape[1] == 0


def test_ef_residual_reinjected_next_sync():
    """A second sync with zero fresh gradient still ships the residual."""
    g = _grads()
    cfg = SyncConfig("asgd_ga", 1, compress_topk=0.1, quantize_int8=True,
                     error_feedback=True, codec_block=512)
    p = jax.tree.map(jnp.zeros_like, g)
    st = init_sync_state(cfg, p)
    _, st = on_step_gradients(cfg, g, st)
    p1, st = apply_sync(cfg, p, st, lr=1.0)
    assert float(jnp.linalg.norm(st.ef_residual)) > 0
    # no new gradients: the next sync ships purely from the residual
    zero_g = jax.tree.map(jnp.zeros_like, g)
    _, st = on_step_gradients(cfg, zero_g, st)
    p2, st = apply_sync(cfg, p1, st, lr=1.0)
    moved = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p1)))
    assert moved > 0, "EF residual was not re-injected"


def test_resize_preserves_ef_residual_total():
    g = _grads(n_pods=3)
    cfg = SyncConfig("asgd_ga", 1, compress_topk=0.1, quantize_int8=True,
                     error_feedback=True, codec_block=512)
    p = jax.tree.map(jnp.zeros_like, g)
    st = init_sync_state(cfg, p)
    _, st = on_step_gradients(cfg, g, st)
    _, st = apply_sync(cfg, p, st, lr=1.0)
    total = np.asarray(jnp.sum(st.ef_residual, axis=0))
    p2 = jax.tree.map(lambda x: x[:2], p)
    shrunk = resize_sync_state(cfg, st, p2, keep=(0, 1))
    assert shrunk.ef_residual.shape[0] == 2
    np.testing.assert_allclose(
        np.asarray(jnp.sum(shrunk.ef_residual, axis=0)), total, atol=1e-5)
    grown = resize_sync_state(cfg, shrunk._replace(), g, keep=None)
    assert grown.ef_residual.shape[0] == 3
    np.testing.assert_allclose(
        np.asarray(grown.ef_residual[2]), 0.0, atol=0.0)


# --------------------------------------------------------- payload math


def test_payload_math_int8():
    dense = SyncConfig("asgd_ga", 8)
    sparse = SyncConfig("asgd_ga", 8, compress_topk=0.01)
    codec = SyncConfig("asgd_ga", 8, compress_topk=0.01, quantize_int8=True,
                       codec_block=4096)
    assert dense.payload_mb(100.0) == 100.0
    assert sparse.payload_mb(100.0) == pytest.approx(2.0)
    # int8 value + u16 index per kept element + fp32 scale per block
    assert codec.payload_mb(100.0) == pytest.approx(
        100.0 * (0.01 * 0.75 + 1.0 / 4096))
    # >= 8x below dense fp32 at equal sync interval
    assert dense.payload_mb(100.0) / codec.payload_mb(100.0) >= 8.0


def test_payload_math_tiers():
    """fp8 costs int8 bytes (1 B + u16 idx); int4 nibble-packs to 0.5 B."""
    base = dict(compress_topk=0.01, quantize_int8=True, codec_block=4096)
    int8 = SyncConfig("asgd_ga", 8, **base)
    fp8 = SyncConfig("asgd_ga", 8, value_dtype="fp8", **base)
    int4 = SyncConfig("asgd_ga", 8, value_dtype="int4", **base)
    assert fp8.payload_mb(100.0) == int8.payload_mb(100.0)
    assert int4.payload_mb(100.0) == pytest.approx(
        100.0 * (0.01 * 0.625 + 1.0 / 4096))
    assert int4.payload_mb(100.0) < int8.payload_mb(100.0)
    # tier indices follow the CODEC_TIERS ladder; codec-off is tier 0
    from repro.core.sync import CODEC_TIERS
    assert CODEC_TIERS == ("fp32", "int8", "fp8", "int4")
    assert SyncConfig("asgd_ga", 8).tier == 0
    assert (int8.tier, fp8.tier, int4.tier) == (1, 2, 3)


def test_config_validation():
    with pytest.raises(ValueError):
        SyncConfig("asgd_ga", 1, error_feedback=True)   # EF needs the codec
    with pytest.raises(ValueError):
        SyncConfig("asgd_ga", 1, overlap_chunks=0)
    with pytest.raises(ValueError):
        SyncConfig("asgd_ga", 1, codec_block=1 << 20)   # idx must fit u16
    # silently-inert codec flags are refused: int8 without a top-k
    # fraction (or on a non-gradient strategy) would train dense while the
    # run summary claims the codec was on
    with pytest.raises(ValueError):
        SyncConfig("asgd_ga", 1, quantize_int8=True)
    with pytest.raises(ValueError):
        SyncConfig("ama", 1, compress_topk=0.1, quantize_int8=True)


def test_config_validation_precise_errors():
    """Each mis-coupling gets its own actionable message (not one blanket
    error), and the new tiers validate their own knob."""
    with pytest.raises(ValueError, match="value_dtype"):
        SyncConfig("asgd_ga", 1, compress_topk=0.1, quantize_int8=True,
                   value_dtype="int2")
    with pytest.raises(ValueError, match="strategy='asgd_ga'"):
        SyncConfig("sma", 1, compress_topk=0.1, quantize_int8=True)
    with pytest.raises(ValueError, match="compress_topk"):
        SyncConfig("asgd_ga", 1, quantize_int8=True, value_dtype="int4")
    with pytest.raises(ValueError, match="error_feedback"):
        SyncConfig("asgd_ga", 1, error_feedback=True)
    with pytest.raises(ValueError, match="overlap_chunks"):
        SyncConfig("asgd_ga", 1, overlap_chunks=4)
    # a non-default tier without the codec would be silently inert: the
    # run ships fp32 while the summary claims fp8/int4
    with pytest.raises(ValueError, match="inert"):
        SyncConfig("asgd_ga", 1, compress_topk=0.01, value_dtype="fp8")
    with pytest.raises(ValueError, match="inert"):
        SyncConfig("asgd_ga", 1, value_dtype="int4")
    # valid tier configs construct fine
    for dt in ("int8", "fp8", "int4"):
        cfg = SyncConfig("asgd_ga", 4, compress_topk=0.05,
                         quantize_int8=True, value_dtype=dt,
                         error_feedback=True)
        assert cfg.uses_codec and cfg.value_dtype == dt


@pytest.mark.parametrize("value_dtype", ["fp8", "int4"])
def test_codec_tier_sync_round_trip(value_dtype):
    """The sync layer ships each tier end to end: peer message bounded by
    the tier's quantization step, EF residual exact, tier recorded in
    SyncState."""
    g = _grads()
    cfg = SyncConfig("asgd_ga", 1, compress_topk=0.25, quantize_int8=True,
                     value_dtype=value_dtype, error_feedback=True,
                     codec_block=512)
    p = jax.tree.map(jnp.zeros_like, g)
    st = init_sync_state(cfg, p)
    assert int(st.tier[0]) == cfg.tier     # one bucket under "single"
    _, st = on_step_gradients(cfg, g, st)
    out, st2 = apply_sync(cfg, p, st, lr=1.0)
    from repro.core.sync import _pack_stacked
    msg = np.asarray(_pack_stacked(st.ga_buffer))
    received = -np.asarray(_pack_stacked(out))
    local = np.roll(received, -cfg.peer_shift, axis=0)
    np.testing.assert_allclose(np.asarray(st2.ef_residual), msg - local,
                               atol=1e-6)
    assert int(st2.tier[0]) == cfg.tier
    # the sync round recorded the controller's signals
    assert (np.asarray(st2.msg_norm) > 0).all()
    assert (np.asarray(st2.resid_norm) > 0).all()
    ratio = np.asarray(st2.resid_norm) / np.asarray(st2.msg_norm)
    assert (ratio < 1.0).all()        # structurally sqrt(1 - capture)


# ------------------------------------------------- convergence parity


def test_compressed_ef_convergence_matches_dense():
    """Acceptance: compressed-with-EF ASGD-GA reaches >=95% of the dense
    run's loss reduction on the emulated 2-pod mesh (the EF residual is what
    makes aggressive compression converge; without it dropped mass is simply
    lost).  Measured as loss *reduction* from the common initial loss —
    both runs converge to near-zero, where a ratio of finals is noise."""
    from repro.data.pipeline import GeoDataset, synthetic_classification
    from repro.models.reference import PAPER_MODELS
    from repro.training.trainer import Trainer, TrainerConfig, \
        stack_pod_batches

    m = PAPER_MODELS["lenet"]
    data = synthetic_classification(1500, m["input_shape"], m["n_classes"],
                                    seed=0)

    def run(sync):
        geo = GeoDataset.partition(data, ["sh", "cq"], [2, 1])
        loaders = [geo.loader("sh", 32, seed=0), geo.loader("cq", 32, seed=1)]
        tr = Trainer(lambda p, b: (m["loss"](p, b), {}), m["init"],
                     TrainerConfig(n_pods=2, optimizer="sgd", lr=0.05,
                                   sync=sync))
        st = tr.init_state(jax.random.key(0))
        st, hist = tr.fit(
            st, lambda s: stack_pod_batches([next(l) for l in loaders]), 120)
        return hist["loss"][0], float(np.mean(hist["loss"][-10:]))

    first, dense = run(SyncConfig("asgd_ga", 4))
    _, comp = run(SyncConfig("asgd_ga", 4, compress_topk=0.05,
                             quantize_int8=True, error_feedback=True,
                             codec_block=1024, overlap_chunks=2))
    assert (first - comp) >= 0.95 * (first - dense), (first, comp, dense)
