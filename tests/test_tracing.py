"""Device scopes and host spans of the trainer.

The train step and the sync round name their phases with
``jax.named_scope`` (``train_forward``, ``train_update``, ``sync_encode``,
``sync_ef``, ``sync_ring``, ``sync_apply``), which reach the compiled
programs' ``op_name`` metadata, and the trainer's calls write
``repro.train_step``, ``repro.maybe_sync`` and ``repro.sync_round`` host
spans into a profiler trace on the same clock as the programs' ops.
"""
import re
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.sync import SyncConfig
from repro.launch.context import wrap_loss
from repro.models.registry import get_model_fns
from repro.training.trainer import Trainer, TrainerConfig

GA_EF = SyncConfig("asgd_ga", 2, compress_topk=0.02, quantize_int8=True,
                   error_feedback=True, codec_block=512)
AMA = SyncConfig("ama", 2)


def _trainer(sync):
    arch = get_arch("mamba2-1.3b")
    cfg = arch.smoke.replace(remat="full")
    fns = get_model_fns(arch.module)
    tr = Trainer(wrap_loss(fns, cfg), lambda k: fns.init_params(k, cfg),
                 TrainerConfig(n_pods=2, optimizer="sgd", lr=0.01,
                               sync=sync))
    state = tr.init_state(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 2, 33), 0,
                                cfg.vocab_size)
    return tr, state, {"tokens": tokens[..., :-1],
                       "labels": tokens[..., 1:]}


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _scoped(names, scope):
    return any(re.search(rf"(?<![\w.]){scope}(?!\w)", n) for n in names)


@pytest.mark.parametrize("sync,scopes,absent", [
    (GA_EF, ("sync_encode", "sync_ef", "sync_ring", "sync_apply"), ()),
    (AMA, ("sync_ring", "sync_apply"), ("sync_encode", "sync_ef")),
], ids=["asgd_ga-ef-codec", "ama"])
def test_programs_carry_every_scope(sync, scopes, absent):
    tr, state, batch = _trainer(sync)
    hlo = tr.program_hlo(state, batch)
    assert sorted(hlo) == ["jit__sync_step_impl", "jit__train_step_impl"]
    train = _op_names("".join(hlo["jit__train_step_impl"]))
    for scope in ("train_forward", "train_update"):
        assert _scoped(train, scope), scope
    # the backward pass, remat recomputation in it, is the forward scope
    # under a transpose
    assert any("transpose(jvp(train_forward))" in n for n in train)
    assert any("train_forward" in n and "transpose(" not in n
               for n in train)
    sync_names = _op_names("".join(hlo["jit__sync_step_impl"]))
    for scope in scopes:
        assert _scoped(sync_names, scope), scope
    for scope in absent:
        assert not _scoped(sync_names, scope), scope


def test_train_step_outputs():
    tr, state, batch = _trainer(AMA)
    _, metrics = tr.train_step(state, batch)
    assert {"loss", "loss_per_pod", "lr"} <= set(metrics)
    assert "grad_norm" not in metrics
    assert np.isfinite(float(metrics["loss"]))


def test_host_spans_share_the_programs_clock(tmp_path):
    from jax.profiler import ProfileData

    tr, state, batch = _trainer(GA_EF)
    # compile outside the trace
    state = tr.maybe_sync(tr.train_step(state, batch)[0], 0)
    state = tr.maybe_sync(tr.train_step(state, batch)[0], 1)
    jax.block_until_ready(state)
    with jax.profiler.trace(str(tmp_path)):
        for step in (2, 3):
            state, m = tr.train_step(state, batch)
            state = tr.maybe_sync(state, step)
            float(m["loss"])
        jax.block_until_ready(state)
    path, = tmp_path.glob("**/*.xplane.pb")
    spans = defaultdict(list)
    runs = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans[e.name].append(e.start_ns)
                    continue
                st = dict(e.stats)
                if st.get("hlo_module") == "jit__train_step_impl":
                    runs[st.get("run_id")].append(e.start_ns)
    assert len(spans["repro.train_step"]) == 2
    assert len(spans["repro.maybe_sync"]) == 2
    assert len(spans["repro.sync_round"]) == 1
    starts = sorted(min(v) for v in runs.values())
    assert len(starts) == 2
    for span, run in zip(sorted(spans["repro.train_step"]), starts):
        assert span < run
