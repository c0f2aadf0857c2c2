"""The system under test, built the way its own launchers build it.

This is the one module of the benchmark that imports the program
(``repro``, under ``src/``).  It builds the trainer of a cell from the
configuration's overrides and the traffic's sync settings, places the state
and the batches, and answers shape questions: what one sync round ships,
how many parameters a pod holds.

Layouts:

- ``stacked``: all pods on one chip, stacked on the state's leading pod
  axis, as ``repro.launch.train`` runs them (``Trainer`` with
  ``n_pods = pods``; no mesh, no sharding rules);
- ``pod_mesh``: one pod per chip on a ``(pod, 1, 1)`` mesh from
  ``repro.launch.mesh.make_pod_mesh``, built by
  ``repro.launch.context.make_train_setup`` with the state on its
  ``state_sharding`` and the train-rule axis table installed, as the
  program's four-chip path does.
"""
from __future__ import annotations

import contextlib
import sys
from pathlib import Path
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

LAYOUTS = ("stacked", "pod_mesh")


def import_program(root: Path) -> None:
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (fails here, not mid-run, if it is absent)


def overrides_for(cfg, overrides: Dict) -> Dict:
    """The configuration file's overrides as ``ModelConfig.replace``
    arguments: a JSON object for a nested config group (``ssm``, ``moe``)
    replaces fields of that group."""
    import dataclasses

    return {k: (dataclasses.replace(getattr(cfg, k), **v)
                if isinstance(v, dict) else v)
            for k, v in overrides.items()}


def _attr(obj, path: str):
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


class Program:
    def __init__(self, config: Dict, traffic: Dict, devices: Sequence):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.sharding import SingleDeviceSharding

        from repro.configs import get_arch
        from repro.core.sync import SyncConfig
        from repro.launch.context import make_train_setup, wrap_loss
        from repro.models.registry import get_model_fns
        from repro.sharding.rules import axis_rules
        from repro.training.trainer import Trainer, TrainerConfig

        layout = traffic["layout"]
        if layout not in LAYOUTS:
            raise ValueError(f"unknown layout {layout!r}; known: {LAYOUTS}")
        self.config, self.traffic = config, traffic
        self.pods = int(traffic["pods"])
        self.rows, self.seq = int(traffic["rows_per_pod"]), int(traffic["seq"])
        self.sync = SyncConfig(**traffic["sync"])
        arch = get_arch(config["arch"])
        opt, lr = config["optimizer"], float(config["lr"])
        if layout == "pod_mesh":
            from repro.launch.mesh import make_pod_mesh

            if len(devices) != self.pods:
                raise ValueError(f"pod_mesh puts one pod on each chip: "
                                 f"{self.pods} pods on {len(devices)} chips")
            mesh = make_pod_mesh(list(devices))
            setup = make_train_setup(
                arch, mesh, sync=self.sync, optimizer=opt, lr=lr,
                config_overrides=overrides_for(arch.config,
                                               config["overrides"]))
            self.trainer, self.cfg = setup.trainer, setup.cfg
            self.state_sharding = setup.state_sharding
            self.batch_sharding = NamedSharding(mesh, P("pod"))
            self._rules = (setup.rules, mesh)
        else:
            if len(devices) != 1:
                raise ValueError("the stacked layout runs on one chip")
            cfg = arch.config.replace(**overrides_for(arch.config,
                                                      config["overrides"]))
            fns = get_model_fns(arch.module)
            self.trainer = Trainer(
                wrap_loss(fns, cfg), lambda k: fns.init_params(k, cfg),
                TrainerConfig(n_pods=self.pods, optimizer=opt, lr=lr,
                              sync=self.sync))
            self.cfg = cfg
            self.state_sharding = None
            self.batch_sharding = SingleDeviceSharding(devices[0])
            self._rules = None
        self._axis_rules = axis_rules
        self._init = None
        self.abstract_state = jax.eval_shape(self.trainer.init_state,
                                             jax.random.key(0))
        self.check_sizes()

    def check_sizes(self) -> None:
        """The configuration file's sizes are what the program runs."""
        bad = {k: (v, _attr(self.cfg, k))
               for k, v in self.config["model"].items()
               if _attr(self.cfg, k) != v}
        if bad:
            raise ValueError(f"configuration and program disagree "
                             f"(file, program): {bad}")

    def context(self):
        if self._rules is None:
            return contextlib.nullcontext()
        return self._axis_rules(*self._rules)

    def init_state(self, key):
        if self._init is None:
            self._init = jax.jit(self.trainer.init_state,
                                 out_shardings=self.state_sharding)
        with self.context():
            return self._init(key)

    def put(self, batch: Dict[str, np.ndarray]):
        return jax.device_put(batch, self.batch_sharding)

    @property
    def params_per_pod(self) -> int:
        return sum(x.size for x in jax.tree.leaves(
            self.abstract_state.params)) // self.pods

    def residual_tree(self, resid: np.ndarray):
        """The error-feedback residual, ``(pods, n)`` in the codec's bucket
        order, cut into a tree shaped like the pod-stacked parameters."""
        from repro.core.sync import _unpack_stacked, bucket_layout

        like = self.abstract_state.sync_state.ga_buffer
        return _unpack_stacked(resid, like, bucket_layout(self.sync, like))

    def round_payload(self) -> List[jax.ShapeDtypeStruct]:
        """The arrays one sync round hands to the ring, for all pods, from
        the shapes the program traces: the codec's wire chunks as they pass
        its transport seam, or the parameter tree under model averaging."""
        from repro.core.sync import apply_sync

        if self.sync.uses_codec:
            shipped: List = []

            class Recorder:
                in_graph = True

                def ship_bucket(self, name, chunks, shift, payload_mb=0.0):
                    for c in chunks:
                        shipped.extend(jax.ShapeDtypeStruct(a.shape, a.dtype)
                                       for a in c)
                    return tuple(type(c)(*(jnp.roll(a, shift, axis=0)
                                           for a in c)) for c in chunks)

            st = self.abstract_state
            jax.eval_shape(lambda s: apply_sync(
                self.sync, s.params, s.sync_state, 1.0,
                transport=Recorder()), st)
            return shipped
        if self.sync.strategy == "ama":
            return [jax.ShapeDtypeStruct(x.shape, x.dtype)
                    for x in jax.tree.leaves(self.abstract_state.params)]
        raise ValueError(f"no payload count for sync strategy "
                         f"{self.sync.strategy!r}")
