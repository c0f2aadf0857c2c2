"""The plain references against the program at smoke sizes (float32)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, run
from chipbench.reference import codec, gqa_swiglu, mamba2
from chipbench.reference import train as reference
from chipbench.tests import cells

CONFIGS = {"mamba2": cells.MAMBA, "gqa_swiglu": cells.GRANITE}


def program_model(conf):
    from repro.configs import get_arch
    from repro.models.registry import get_model_fns
    from chipbench.program import overrides_for

    arch = get_arch(conf["arch"])
    cfg = arch.config.replace(**overrides_for(arch.config, conf["overrides"]))
    return cfg, get_model_fns(arch.module)


@pytest.mark.parametrize("arch", sorted(CONFIGS))
@pytest.mark.parametrize("f32", [False, True])
def test_init_draws_the_programs_weights(arch, f32):
    conf = cells.float32(CONFIGS[arch]) if f32 else CONFIGS[arch]
    cfg, fns = program_model(conf)
    key = run.key_for(2**31 + 5)
    mine = reference.ARCHITECTURES[arch].init(key, conf["model"])
    theirs = fns.init_params(key, cfg)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_loss_and_gradients_match_the_program(arch):
    conf = cells.float32(CONFIGS[arch])
    cfg, fns = program_model(conf)
    mod = reference.ARCHITECTURES[arch]
    params = mod.init(run.key_for(7), conf["model"])
    b = data.token_rows(3, 0, 0, 2, 32, conf["model"]["vocab_size"])
    b = {k: jnp.asarray(v) for k, v in b.items()}
    with jax.default_matmul_precision("highest"):
        ref_l, ref_g = jax.value_and_grad(
            lambda p: mod.loss(p, conf["model"], b, "fp32"))(params)
        (got_l, _), got_g = jax.value_and_grad(
            lambda p: fns.loss_fn(p, cfg, b), has_aux=True)(params)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=2e-5)
    for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-3, atol=2e-4 * float(
                                       jnp.max(jnp.abs(r))) + 1e-7)


def test_chunked_ssd_meets_the_recurrence():
    rng = np.random.default_rng(0)
    b, S, h, p, n = 2, 64, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(b, S, h, p)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.01, 0.5, size=(b, S, h)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, S, h, n)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b, S, h, n)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = mamba2.ssd(x, a, Bm, Cm, chunk=16)
        want = mamba2.ssd_recurrent(x, a, Bm, Cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("n,block,frac", [(100_000, 4096, 0.02),
                                          (3000, 4096, 0.02),
                                          (8192, 512, 0.1)])
def test_codec_matches_the_programs_oracle(n, block, frac):
    from repro.kernels import ref

    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_t(3, size=n) * 1e-3, jnp.float32)
    x = x.at[17].set(0.0)
    blk = min(block, n)
    k = codec.k_per_block(blk, frac)
    q, idx, s = codec.encode(x, k, block)
    pq, pidx, ps = ref.wan_encode(x, k, block=block)
    np.testing.assert_array_equal(np.asarray(q).reshape(-1), np.asarray(pq))
    np.testing.assert_array_equal(np.asarray(idx).reshape(-1),
                                  np.asarray(pidx))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ps))
    np.testing.assert_array_equal(
        np.asarray(codec.decode(q, idx, s, n, block)),
        np.asarray(ref.wan_decode(pq, pidx, ps, n, block=block)))
    wire = codec.wire_bytes(n, k, block)
    assert wire == pq.size * 1 + pidx.size * 2 + ps.size * 4


def _program_run(conf, traffic, n_steps, seed):
    """The program's trainer driven like the harness: per-pod losses, the
    parameters before and after, and the residual the last round left."""
    from chipbench.program import Program

    prog = Program(conf, traffic, jax.devices()[:1])
    pool = data.batch_pool(seed, n_steps, prog.pods, prog.rows, prog.seq,
                           conf["model"]["vocab_size"])
    state = prog.init_state(run.key_for(seed))
    p0 = jax.device_get(state.params)
    losses = []
    for step in range(n_steps):
        state, m = prog.trainer.train_step(state, prog.put(pool[step]))
        state = prog.trainer.maybe_sync(state, step)
        losses.append(np.asarray(m["loss_per_pod"]))
    resid = (prog.residual_tree(jax.device_get(state.sync_state.ef_residual))
             if run.has_ef(traffic["sync"]) else None)
    return np.stack(losses), p0, jax.device_get(state.params), pool, resid


@pytest.mark.parametrize("arch,traffic", [("mamba2", cells.GA),
                                          ("gqa_swiglu", cells.MA),
                                          ("mamba2", cells.MA)])
def test_training_and_sync_match_the_program(arch, traffic):
    """SGD, the codec round with error feedback and the ring average: the
    reference's losses, weight changes and residual follow the program's
    through two sync rounds and past them, in float32."""
    conf = cells.float32(CONFIGS[arch])
    n = 2 * traffic["sync"]["interval"] + 1
    losses, p0, pn, pool, resid = _program_run(conf, traffic, n, 11)
    ref = reference.train(conf["model"], arch, traffic["sync"], conf["lr"],
                          run.key_for(11), pool)
    np.testing.assert_allclose(losses, ref["losses"], rtol=5e-5)
    change = run.leaf_norms(pn, minus=p0)
    np.testing.assert_allclose(change, ref["change"], rtol=2e-3,
                               atol=1e-6)
    if resid is not None:
        np.testing.assert_allclose(run.leaf_norms(resid), ref["resid"],
                                   rtol=2e-3, atol=1e-6)
