"""Multi-device integration tests, run in SUBPROCESSES so the fake-device
XLA flag never leaks into the main test process (smoke tests must see the
1 real CPU device).

Verifies on an 8-device (2 pods x 2 data x 2 model) debug mesh that:
- the stacked-pod train step lowers, compiles AND EXECUTES with the real
  sharding rules;
- the sync step emits pod-axis collectives (collective-permute for the
  one-peer ring / all-reduce for SMA) — the paper's WAN round on ICI;
- executed multi-device training is numerically identical to the
  single-device pod emulation.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_PRELUDE = """
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch
from repro.core.sync import SyncConfig
from repro.launch import context as C
from repro.launch.mesh import make_debug_mesh
from repro.launch.shapes import InputShape, train_batch_specs
from repro.sharding.rules import axis_rules
mesh = make_debug_mesh(n_pods=2, data=2, model=2)
"""


@pytest.mark.parametrize("arch_name,strategy", [
    ("granite-8b", "ama"),
    ("qwen3-moe-30b-a3b", "asgd_ga"),
    ("mamba2-1.3b", "sma"),
])
def test_debug_mesh_train_and_sync_execute(arch_name, strategy):
    code = _PRELUDE + textwrap.dedent(f"""
    import dataclasses
    from repro.launch import shapes as S
    arch = get_arch("{arch_name}")
    setup = C.make_train_setup(arch, mesh, sync=SyncConfig("{strategy}", 2),
                               optimizer="sgd", smoke=True)
    shape = InputShape("dbg", 32, 8, "train")
    smoke_arch = dataclasses.replace(arch, config=setup.cfg)
    bspecs = S.train_batch_specs(smoke_arch, shape, 2)
    bshard = C.batch_sharding(bspecs, mesh, setup.rules, stacked=True)

    with axis_rules(setup.rules, mesh):
        jf = jax.jit(setup.trainer._train_step_impl,
                     in_shardings=(setup.state_sharding, bshard),
                     out_shardings=(setup.state_sharding, None))
        js = jax.jit(setup.trainer._sync_step_impl,
                     in_shardings=(setup.state_sharding,),
                     out_shardings=setup.state_sharding)
        with mesh:
            state = jax.jit(setup.trainer.init_state,
                            out_shardings=setup.state_sharding
                            )(jax.random.key(0))
            batch = {{k: jax.device_put(
                jax.random.randint(jax.random.key(1), v.shape, 0, 64)
                if v.dtype == jnp.int32 else
                jax.random.normal(jax.random.key(1), v.shape) * 0.1,
                bshard[k]) for k, v in bspecs.items()}}
            state2, metrics = jf(state, batch)
            hlo = js.lower(state2).compile().as_text()
            state3 = js(state2)
    loss = float(metrics["loss"])
    print(json.dumps({{
        "loss_finite": bool(np.isfinite(loss)),
        "step": int(state2.step),
        "permutes": hlo.count("collective-permute"),
        "all_reduces": hlo.count("all-reduce"),
        "params_finite": all(bool(jnp.isfinite(x).all())
                             for x in jax.tree.leaves(state3.params)),
    }}))
    """)
    res = _run(code)
    assert res["loss_finite"] and res["params_finite"]
    assert res["step"] == 1
    if strategy in ("ama", "asgd_ga"):
        assert res["permutes"] > 0, "ring send must lower to collective-permute"
    else:
        assert res["all_reduces"] > 0, "SMA must lower to all-reduce"


def test_multi_device_matches_single_device_emulation():
    """The 8-device sharded execution computes the same training trajectory
    as the single-device stacked emulation (same seeds, same batches)."""
    code = _PRELUDE + textwrap.dedent("""
    import dataclasses
    from repro.launch import shapes as S
    arch = get_arch("granite-8b")
    setup = C.make_train_setup(arch, mesh, sync=SyncConfig("ama", 2),
                               optimizer="sgd", lr=0.05, smoke=True)
    smoke_arch = dataclasses.replace(arch, config=setup.cfg)
    shape = InputShape("dbg", 16, 8, "train")
    bspecs = S.train_batch_specs(smoke_arch, shape, 2)
    bshard = C.batch_sharding(bspecs, mesh, setup.rules, stacked=True)

    def batches(step):
        k = jax.random.key(100 + step)
        return {"tokens": jax.random.randint(k, bspecs["tokens"].shape, 0,
                                             setup.cfg.vocab_size),
                "labels": jax.random.randint(jax.random.fold_in(k, 1),
                                             bspecs["labels"].shape, 0,
                                             setup.cfg.vocab_size)}

    # sharded run
    with axis_rules(setup.rules, mesh):
        jf = jax.jit(setup.trainer._train_step_impl,
                     in_shardings=(setup.state_sharding, bshard),
                     out_shardings=(setup.state_sharding, None))
        js = jax.jit(setup.trainer._sync_step_impl,
                     in_shardings=(setup.state_sharding,),
                     out_shardings=setup.state_sharding)
        with mesh:
            st = jax.jit(setup.trainer.init_state,
                         out_shardings=setup.state_sharding)(jax.random.key(0))
            sharded_losses = []
            for step in range(4):
                st, m = jf(st, batches(step))
                sharded_losses.append(float(m["loss"]))
                if (step + 1) % 2 == 0:
                    st = js(st)

    # plain single-device emulation (same Trainer impl, no shardings)
    st2 = setup.trainer.init_state(jax.random.key(0))
    plain_losses = []
    for step in range(4):
        st2, m = setup.trainer._train_step_impl(st2, batches(step))
        plain_losses.append(float(m["loss"]))
        if (step + 1) % 2 == 0:
            st2 = setup.trainer._sync_step_impl(st2)

    import numpy as np
    print(json.dumps({
        "sharded": sharded_losses, "plain": plain_losses,
        "max_diff": float(np.max(np.abs(np.array(sharded_losses)
                                        - np.array(plain_losses)))),
    }))
    """)
    res = _run(code)
    assert res["max_diff"] < 5e-4, res
