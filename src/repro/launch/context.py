"""Shared launch context: rule sets, abstract state, sharding trees.

Rule sets (logical axis -> mesh axes) per step kind:

- **train**: training state is *stacked* over pods (leading ``pod_stack``
  dim -> ``"pod"``); the in-pod batch shards over ``"data"``; parameters are
  FSDP-sharded over ``"data"`` and tensor-parallel over ``"model"``.
- **decode/prefill**: serving is per-pod-replica, so the request batch
  shards over ``("pod", "data")`` and full KV caches shard their sequence
  dim over ``"model"`` (flash-decoding style).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import Arch
from repro.core.sync import SyncConfig, SyncState
from repro.models.registry import ModelFns, get_model_fns
from repro.optim.optimizers import AdamState
from repro.sharding.rules import (DEFAULT_RULES, LA, is_la, logical_to_spec,
                                  spec_tree_for_params)
from repro.training.trainer import Trainer, TrainerConfig, TrainState

Pytree = Any


def train_rules() -> Dict:
    r = dict(DEFAULT_RULES)
    r.update({
        "pod_stack": "pod",
        "batch": "data",          # in-pod batch (the stacked dim carries pods)
        "fsdp": "data",
        "cache_seq": None,
    })
    return r


def serve_rules() -> Dict:
    r = dict(DEFAULT_RULES)
    r.update({
        "batch": ("pod", "data"),
        "cache_seq": "model",
        "fsdp": "data",
    })
    return r


# ---------------------------------------------------------------------------
# logical axes for composite state
# ---------------------------------------------------------------------------


def stacked_param_axes(fns: ModelFns, cfg) -> Pytree:
    axes = fns.param_logical_axes(cfg)
    return jax.tree.map(lambda la: LA(("pod_stack",) + la.names), axes,
                        is_leaf=is_la)


def opt_state_axes(optimizer: str, param_axes: Pytree) -> Pytree:
    if optimizer == "sgd":
        return ()
    if optimizer == "momentum":
        return param_axes
    if optimizer == "adamw":
        return AdamState(mu=param_axes, nu=param_axes, count=LA(()))
    raise KeyError(optimizer)


def sync_state_axes(sync: SyncConfig, param_axes: Pytree) -> SyncState:
    if sync.strategy in ("asgd_ga", "asp"):
        buf = param_axes
    else:
        buf = jax.tree.map(lambda la: LA((None,)), param_axes, is_leaf=is_la)
    return SyncState(ga_buffer=buf, steps_since_sync=LA(()),
                     significant_frac=LA(()),
                     ef_residual=LA(("pod_stack", None)),
                     tier=LA((None,)),              # (n_buckets,) vector
                     msg_norm=LA(("pod_stack", None)),
                     resid_norm=LA(("pod_stack", None)))


def train_state_axes(fns: ModelFns, cfg, tcfg: TrainerConfig) -> TrainState:
    p = stacked_param_axes(fns, cfg)
    return TrainState(
        params=p,
        opt_state=opt_state_axes(tcfg.optimizer, p),
        sync_state=sync_state_axes(tcfg.sync, p),
        step=LA(()),
    )


def batch_axes(batch: Dict, *, stacked: bool) -> Dict:
    """Logical axes for a flat batch dict (dims: [pod_stack,] batch, ...).

    ``positions`` leads with the M-RoPE component dim (3, B, S); scalars
    (``cache_pos``) are unsharded.
    """
    out = {}
    for k, v in batch.items():
        rank = len(v.shape)
        inner_rank = rank - (1 if stacked else 0)
        if inner_rank == 0:
            base: Tuple = ()
        elif k == "positions":
            base = (None, "batch") + (None,) * (inner_rank - 2)
        else:
            base = ("batch",) + (None,) * (inner_rank - 1)
        out[k] = LA((("pod_stack",) if stacked else ()) + base)
    return out


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


@dataclass
class TrainSetup:
    arch: Arch
    cfg: Any
    fns: ModelFns
    trainer: Trainer
    abstract_state: Pytree
    state_sharding: Pytree
    rules: Dict


def wrap_loss(fns: ModelFns, cfg) -> Callable:
    def loss(params, batch):
        return fns.loss_fn(params, cfg, batch)
    return loss


def make_train_setup(arch: Arch, mesh: Mesh, *,
                     sync: SyncConfig = SyncConfig(),
                     optimizer: str = "sgd", lr: float = 0.01,
                     smoke: bool = False,
                     config_overrides: Optional[dict] = None) -> TrainSetup:
    cfg = arch.smoke if smoke else arch.config
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    fns = get_model_fns(arch.module)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_pods = sizes.get("pod", 1)
    tcfg = TrainerConfig(n_pods=n_pods, optimizer=optimizer, lr=lr, sync=sync)
    trainer = Trainer(wrap_loss(fns, cfg), lambda k: fns.init_params(k, cfg),
                      tcfg)
    abstract_state = jax.eval_shape(trainer.init_state, jax.random.key(0))
    rules = train_rules()
    axes = train_state_axes(fns, cfg, tcfg)
    specs = spec_tree_for_params(axes, abstract_state, rules, mesh)
    sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
    return TrainSetup(arch=arch, cfg=cfg, fns=fns, trainer=trainer,
                      abstract_state=abstract_state, state_sharding=sharding,
                      rules=rules)


def batch_sharding(batch_specs: Dict, mesh: Mesh, rules: Dict, *,
                   stacked: bool) -> Dict:
    axes = batch_axes(batch_specs, stacked=stacked)
    specs = spec_tree_for_params(axes, batch_specs, rules, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
