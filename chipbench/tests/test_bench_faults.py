"""A run with the timed path broken underneath comes out not correct.

Each cell's smoke-size stand-in, in float32 (where the sound program
meets the reference to rounding), runs the whole harness on the CPU (its
look for a chip skipped) under the real cell's limits, once sound and once
with each fault a training cell can have planted in the program:

- a step that returns its state unchanged;
- half of each pod's batch left out, the mean taken over the rest;
- the exchange between pods left out of the sync round;
- under error feedback, the residual of one round left out of the next.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.spec import ROOT
from chipbench.tests import cells

CELLS = {"mamba2-1.3b.ga-int8-every2": (cells.float32(cells.MAMBA),
                                        cells.GA),
         "granite-8b.ma-every4": (cells.float32(cells.GRANITE), cells.MA)}


def unchanged(prog):
    step = prog.trainer._train_step

    def fn(state, batch):
        _, metrics = step(state, batch)
        return state, metrics

    prog.trainer._train_step = fn


def half_batch(prog):
    step = prog.trainer._train_step

    def fn(state, batch):
        rows = batch["tokens"].shape[1] // 2
        return step(state, jax.tree.map(lambda v: v[:, :rows], batch))

    prog.trainer._train_step = fn


def no_exchange(prog):
    sync = prog.trainer._sync_step

    def fn(state):
        return sync(state)._replace(params=state.params)

    prog.trainer._sync_step = fn


def ef_dropped(prog):
    sync = prog.trainer._sync_step

    def fn(state):
        ss = state.sync_state
        return sync(state._replace(sync_state=ss._replace(
            ef_residual=jnp.zeros_like(ss.ef_residual))))

    prog.trainer._sync_step = fn


FAULTS = {"sound": None, "unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "ef_dropped": ef_dropped}
CASES = [(cell, fault) for cell in sorted(CELLS) for fault in sorted(FAULTS)
         if fault != "ef_dropped" or run.has_ef(CELLS[cell][1]["sync"])]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f}" for c, f in CASES])
def test_fault_is_caught(tmp_path, cell, fault):
    config, traffic = CELLS[cell]
    limits = json.loads((ROOT / "chipbench" / "limits" /
                         f"{cell}.json").read_text())
    root = cells.make_root(tmp_path, [dict(
        name=cell, config=config, traffic_name="t", traffic=traffic)],
        limits={cell: limits})
    res = run.run_cell(cell, 2**31 + 3, 0.0, False, jax.devices()[:1],
                       root=root, fault=FAULTS[fault])
    assert res["correct"] is (fault == "sound"), res["checks"]
