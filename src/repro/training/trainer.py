"""Geo-distributed trainer: per-pod vmapped step + sync-strategy integration.

The trainer is generic over a ``loss_fn(params, batch) -> (loss, metrics)``:
the LLM path wraps ``repro.models.transformer.loss_fn`` with its ModelConfig,
and the paper-reproduction path passes the reference models' losses directly.

State layout: every leaf of ``params`` / ``opt_state`` / ``ga_buffer`` (and
the WAN codec's flat ``ef_residual`` error-feedback buffer) has a leading
**pod** dimension (size ``n_pods`` — the number of cloud partitions).
On a multi-pod mesh that dimension is sharded over the ``"pod"`` axis; on a
single CPU device it emulates the clouds faithfully (same numerics).  The
per-pod step is ``vmap``-ed over it; the sync strategies act on it with
roll/mean (-> collective-permute / all-reduce on TPU).

Host loop responsibilities (the physical-training-plane workflow of the
paper): feed per-pod batches (possibly uneven via masking — the elastic
scheduler's batch split), call the jitted ``train_step`` every iteration and
the jitted ``sync_step`` at the strategy's sync points, account WAN traffic,
and terminate (scale-to-zero) when the local stop condition fires.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sync import (SyncConfig, SyncState, _chunk_widths,
                             apply_sync, bucket_chunk_mb, bucket_layout,
                             bucket_weights_of, bucket_wire_mb,
                             finish_codec_sync, finish_codec_sync_split,
                             grow_pods, init_sync_state, is_sync_step,
                             on_step_gradients, prepare_codec_sync,
                             reencode_unsent, resize_sync_state,
                             retune_sync_state, ship_sync_payloads,
                             shrink_pods, traffic_per_step_mb)
from repro.optim.optimizers import (Optimizer, clip_by_global_norm,
                                    constant_schedule, get_optimizer)

Pytree = Any


class TrainState(NamedTuple):
    params: Pytree
    opt_state: Pytree
    sync_state: SyncState
    step: jnp.ndarray


@dataclass(frozen=True)
class TrainerConfig:
    n_pods: int = 1
    optimizer: str = "sgd"
    optimizer_kwargs: tuple = ()
    lr: float = 0.05
    lr_schedule: Optional[Callable] = None
    clip_norm: float = 0.0
    sync: SyncConfig = field(default_factory=SyncConfig)

    def make_optimizer(self) -> Optimizer:
        return get_optimizer(self.optimizer, **dict(self.optimizer_kwargs))

    def make_schedule(self):
        return self.lr_schedule or constant_schedule(self.lr)


class Trainer:
    def __init__(self, loss_fn: Callable, init_fn: Callable,
                 cfg: TrainerConfig, transport=None, stream=None):
        """loss_fn(params, batch) -> (loss, metrics dict);
        init_fn(key) -> params (single-pod, unstacked).

        ``transport`` selects who ships sync payloads
        (:mod:`repro.core.transport`): ``None`` keeps the legacy inline
        ring traced into the jitted sync step (bit-exact).  An in-graph
        transport (``SimTransport``) also ships inside that one jit and is
        billed host-side at the round barrier; a host-seam transport
        (``MeshTransport``) switches the codec sync to the split path —
        jitted prepare, host-timed per-bucket ship, jitted finish — so
        each bucket's transfer time is measured on-host.

        ``stream`` (a :class:`repro.core.autotune.StreamingShipController`)
        turns sync rounds chunk-granular on streaming-capable transports:
        jitted prepare, then per-chunk host-seam ship with the chunk's
        measured transfer observed AS IT LANDS — and, on a mid-round
        bandwidth cliff, a one-shot re-encode of the round's unsent
        segments at a cheaper ladder rung (``sync.reencode_unsent`` /
        ``finish_codec_sync_split``; the EF residual carries the fidelity
        delta exactly).  A round with zero retunes is bit-identical to
        the non-streaming path."""
        self.loss_fn = loss_fn
        self.init_fn = init_fn
        self.cfg = cfg
        self.transport = transport
        self.stream = stream
        self._host_seam = (transport is not None
                           and not getattr(transport, "in_graph", True))
        self.optimizer = cfg.make_optimizer()
        self.schedule = cfg.make_schedule()
        self._train_step = jax.jit(self._train_step_impl)
        self._sync_step = jax.jit(self._sync_step_impl)
        self._prepare_sync = jax.jit(self._prepare_sync_impl)
        self._finish_sync = jax.jit(self._finish_sync_impl)
        self._finish_sync_masked = jax.jit(self._finish_sync_masked_impl)
        # compiled-sync-step cache across retunes, keyed by the codec
        # shape of the config (interval is host-side scheduling only and
        # never forces a re-jit); carried from trainer to trainer so an
        # adaptive controller revisiting a rung reuses the old executable.
        # The host-seam split path caches its (prepare, finish, masked
        # finish) triple under the same key discipline.
        self._sync_cache: Dict[SyncConfig, Any] = {self._sync_key(cfg.sync):
                                                   self._sync_step}
        self._split_cache: Dict[SyncConfig, Any] = {
            self._sync_key(cfg.sync): (self._prepare_sync,
                                       self._finish_sync,
                                       self._finish_sync_masked)}
        # streaming retune path: (from-key, to-key, sent-signature) ->
        # (jitted tail re-encode, jitted split finish).  The partial-round
        # split point is part of the key — a re-encode that aborts after a
        # different chunk is a different program
        self._stream_cache: Dict[Tuple, Any] = {}
        self._bucket_weights: Optional[Dict[str, float]] = None
        self._wire_mb: Optional[Dict[str, float]] = None
        self._chunk_mb: Optional[Dict[str, Tuple[float, ...]]] = None
        self.traffic_mb = 0.0
        self.stream_retunes = 0

    @staticmethod
    def _sync_key(sync: SyncConfig) -> SyncConfig:
        """Cache key: the jitted sync step depends on every codec knob —
        per-bucket tiers/fractions included — but NOT on the interval."""
        import dataclasses
        return dataclasses.replace(sync, interval=1)

    def bucket_weights(self, state: "TrainState") -> Optional[Dict[str, float]]:
        """Per-bucket model-element fractions (memoized; shape-only), for
        exact layer-class traffic accounting."""
        if self.cfg.sync.bucket_policy == "single":
            return None
        if self._bucket_weights is None:
            self._bucket_weights = bucket_weights_of(self.cfg.sync,
                                                     state.params)
        return self._bucket_weights

    # ------------------------------------------------------------- state
    def init_state(self, key, same_init: bool = True) -> TrainState:
        """Stacked initial state.  ``same_init=True`` gives all pods identical
        initial parameters (the paper's setup: one model replicated)."""
        n = self.cfg.n_pods
        if same_init:
            p0 = self.init_fn(key)
            params = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), p0)
        else:
            keys = jax.random.split(key, n)
            params = jax.vmap(self.init_fn)(keys)
        opt_state = jax.vmap(self.optimizer.init)(params)
        return TrainState(
            params=params,
            opt_state=opt_state,
            sync_state=init_sync_state(self.cfg.sync, params),
            step=jnp.zeros((), jnp.int32),
        )

    # -------------------------------------------------------------- steps
    def _train_step_impl(self, state: TrainState, batch: Pytree
                         ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        lr = self.schedule(state.step)

        def forward(params, batch):
            # inside the differentiated function, so the backward pass's
            # ops carry ``transpose(jvp(train_forward))`` in their names
            with jax.named_scope("train_forward"):
                return self.loss_fn(params, batch)

        grad_fn = jax.value_and_grad(forward, has_aux=True)
        (loss, metrics), grads = jax.vmap(grad_fn)(state.params, batch)

        with jax.named_scope("train_update"):
            if self.cfg.clip_norm > 0:
                grads = jax.vmap(lambda g: clip_by_global_norm(
                    g, self.cfg.clip_norm))(grads)

            grads, sync_state = on_step_gradients(self.cfg.sync, grads,
                                                  state.sync_state)

            new_params, new_opt = jax.vmap(
                self.optimizer.update, in_axes=(0, 0, 0, None)
            )(grads, state.opt_state, state.params, lr)

        out_metrics = {"loss": jnp.mean(loss), "loss_per_pod": loss,
                       "lr": lr}
        for k, v in metrics.items():
            if k not in ("loss",):
                out_metrics[k] = jnp.mean(v)
        return TrainState(new_params, new_opt, sync_state,
                          state.step + 1), out_metrics

    def _sync_step_impl(self, state: TrainState) -> TrainState:
        lr = self.schedule(state.step)
        transport = (self.transport if (self.transport is not None
                                        and self.transport.in_graph)
                     else None)
        params, sync_state = apply_sync(self.cfg.sync, state.params,
                                        state.sync_state, lr,
                                        transport=transport)
        return state._replace(params=params, sync_state=sync_state)

    # ------------------------------------------ host-seam (split) sync path
    def _prepare_sync_impl(self, state: TrainState):
        return prepare_codec_sync(self.cfg.sync, state.sync_state)

    def _finish_sync_impl(self, state: TrainState, payloads, shipped
                          ) -> TrainState:
        lr = self.schedule(state.step)
        params, sync_state = finish_codec_sync(
            self.cfg.sync, state.params, state.sync_state, payloads,
            shipped, lr)
        return state._replace(params=params, sync_state=sync_state)

    def _finish_sync_masked_impl(self, state: TrainState, payloads, shipped,
                                 alive) -> TrainState:
        """Degraded-round finish: complete the round over the surviving
        membership mask (``alive`` is a traced argument, so one compile
        covers every crash pattern).  See ``finish_codec_sync``'s mask
        semantics: undelivered messages stay whole in the EF residual and
        the dead rows' telemetry zeroes out."""
        lr = self.schedule(state.step)
        params, sync_state = finish_codec_sync(
            self.cfg.sync, state.params, state.sync_state, payloads,
            shipped, lr, alive=alive)
        return state._replace(params=params, sync_state=sync_state)

    def wire_mb(self, state: TrainState) -> Dict[str, float]:
        """Per-bucket per-pod wire MB of one sync round (memoized per
        config; shape-only host arithmetic) — what transports bill."""
        if self._wire_mb is None:
            layout = bucket_layout(self.cfg.sync,
                                   state.sync_state.ga_buffer)
            self._wire_mb = bucket_wire_mb(self.cfg.sync, layout)
        return self._wire_mb

    def chunk_mb(self, state: TrainState) -> Dict[str, Tuple[float, ...]]:
        """Per-chunk wire MB of each bucket (memoized per config) — the
        streaming ship's chunk schedule."""
        if self._chunk_mb is None:
            layout = bucket_layout(self.cfg.sync,
                                   state.sync_state.ga_buffer)
            self._chunk_mb = bucket_chunk_mb(self.cfg.sync, layout)
        return self._chunk_mb

    # ------------------------------------------------ streaming sync path
    def _can_stream(self) -> bool:
        return (self.stream is not None
                and self.cfg.sync.uses_codec
                and self.transport is not None
                and getattr(self.transport, "supports_streaming", False))

    def _stream_fns(self, state: TrainState, cfg_to: SyncConfig,
                    sent: Dict[str, int]):
        """Jitted (tail re-encode, split finish) pair for one retune shape,
        cached under the split-path key: (from config, to config, where
        each bucket's schedule was cut)."""
        sent_key = tuple(sorted(sent.items()))
        key = (self._sync_key(self.cfg.sync), self._sync_key(cfg_to),
               sent_key)
        fns = self._stream_cache.get(key)
        if fns is None:
            cfg = self.cfg.sync
            layout = bucket_layout(cfg, state.sync_state.ga_buffer)
            sent_d = dict(sent)

            def reenc(flat):
                return reencode_unsent(cfg, cfg_to, flat, layout, sent_d)

            def fin(st, payloads, shipped, tail_shipped, tail_local):
                lr = self.schedule(st.step)
                params, sync_state = finish_codec_sync_split(
                    cfg, cfg_to, st.params, st.sync_state, payloads,
                    shipped, tail_shipped, tail_local, sent_d, lr)
                return st._replace(params=params, sync_state=sync_state)

            fns = (jax.jit(reenc), jax.jit(fin))
            self._stream_cache[key] = fns
        return fns

    def _stream_sync(self, state: TrainState,
                     host_step: int) -> Optional[TrainState]:
        """One chunk-granular sync round.  Returns None when the transport
        declines the streaming protocol for this round (e.g. a chaos plan
        armed a fault — the classic retry/degrade path must run instead).

        The round: jitted prepare at the live config; per-chunk host-seam
        ship, each landed chunk observed by the StreamingShipController
        against the pre-round bandwidth belief; on a cliff, ONE transient
        retune — the unsent segments re-encode at the cheaper rung, the
        transport re-prices the tail at the current bandwidth, and the
        split finish splices prefix + tail so the EF residual carries the
        tail's fidelity delta exactly.  ``end_stream_round`` then emits
        the same records/probe fold ``on_sync`` would — bit-identical when
        no retune fired."""
        from repro.core.autotune import BucketStats

        cfg = self.cfg.sync
        wire = self.wire_mb(state)
        if not self.transport.begin_stream_round(wire, step=host_step):
            return None
        self.stream.note_stats(BucketStats.from_sync_state(state.sync_state))
        self.stream.begin_round(host_step, cfg)
        payloads = self._prepare_sync(state)
        chunk_mb = self.chunk_mb(state)
        shipped: Dict[str, List] = {}
        # every bucket starts at 0 sent chunks: when a retune aborts the
        # schedule, buckets not yet reached re-encode whole
        sent: Dict[str, int] = {name: 0 for name in payloads.chunks}
        cfg_to: Optional[SyncConfig] = None
        for name, bchunks in payloads.chunks.items():
            for i, chunk in enumerate(bchunks):
                out, secs = self.transport.stream_ship_chunk(
                    name, chunk, cfg.peer_shift, chunk_mb[name][i])
                shipped.setdefault(name, []).append(out)
                sent[name] = i + 1
                cfg_to = self.stream.observe_chunk(name, chunk_mb[name][i],
                                                   secs)
                if cfg_to is not None:
                    break
            if cfg_to is not None:
                break
        shipped_t = {n: tuple(c) for n, c in shipped.items()}
        tails = {}
        if cfg_to is not None:
            reenc, fin = self._stream_fns(state, cfg_to, sent)
            tails, tail_local = reenc(payloads.flat)
        if tails:
            # price the re-encoded tail as one fresh transfer at the
            # *current* bandwidth, then stream it out chunk by chunk
            layout = bucket_layout(cfg, state.sync_state.ga_buffer)
            tail_schedule: Dict[str, Tuple[float, ...]] = {}
            for g, name in enumerate(layout.names):
                if name not in tails:
                    continue
                size = layout.sizes[g]
                widths = _chunk_widths(cfg.for_bucket(name), size)
                sw = int(sum(widths[:sent.get(name, 0)]))
                tcfg = cfg_to.for_bucket(name)
                tail_schedule[name] = tuple(
                    tcfg.payload_mb(m * 4 / 1e6)
                    for m in _chunk_widths(tcfg, size - sw))
            self.transport.retune_stream(
                sum(mb for t in tail_schedule.values() for mb in t))
            self.stream_retunes += 1
            tail_shipped: Dict[str, List] = {}
            for name, tchunks in tails.items():
                for i, chunk in enumerate(tchunks):
                    out, secs = self.transport.stream_ship_chunk(
                        name, chunk, cfg.peer_shift,
                        tail_schedule[name][i])
                    tail_shipped.setdefault(name, []).append(out)
                    self.stream.observe_chunk(name,
                                              tail_schedule[name][i], secs)
            state = fin(state, payloads, shipped_t,
                        {n: tuple(c) for n, c in tail_shipped.items()},
                        tail_local)
        else:
            state = self._finish_sync(state, payloads, shipped_t)
        self.transport.end_stream_round()
        self.stream.end_round()
        return state

    def _host_sync(self, state: TrainState) -> TrainState:
        """Codec sync as three dispatches with the transport at the seam:
        the ship runs host-side so the transport can execute and time each
        bucket's transfer (the measured feedback MeshTransport reports).
        Numerically identical to the monolithic jitted sync step — the
        three stages are the same functions apply_sync composes."""
        payloads = self._prepare_sync(state)
        shipped = ship_sync_payloads(self.cfg.sync, payloads.chunks,
                                     self.transport, self.wire_mb(state))
        failed = tuple(getattr(self.transport, "round_failed_pods", ()) or ())
        if failed:
            alive = np.ones((self.cfg.n_pods,), np.float32)
            for p in failed:
                if 0 <= p < self.cfg.n_pods:
                    alive[p] = 0.0
            return self._finish_sync_masked(state, payloads, shipped,
                                            jnp.asarray(alive))
        return self._finish_sync(state, payloads, shipped)

    def train_step(self, state, batch):
        with jax.profiler.TraceAnnotation("repro.train_step"):
            return self._train_step(state, batch)

    def sync_step_hlo(self, state: TrainState) -> str:
        """Optimized HLO of the in-graph sync step compiled for ``state``:
        what one sync round runs (its Pallas kernels appear as
        ``tpu_custom_call``s)."""
        return self._sync_step.lower(state).compile().as_text()

    def program_hlo(self, state: TrainState, batch: Pytree
                    ) -> Dict[str, Tuple[str, ...]]:
        """Optimized HLO texts of the train-step and in-graph sync-step
        programs the step loop runs from ``state`` on batches like
        ``batch``, keyed by HLO module name.  Each instruction's ``op_name``
        metadata carries the ``jax.named_scope`` it was traced under
        (``train_forward``, ``train_update``, ``sync_encode``, ``sync_ef``,
        ``sync_ring``, ``sync_apply``), which is how a profiler trace's op
        events are read by phase.

        A program compiled for more than one input signature in the loop
        has one text per variant: on a pod mesh the train step after a
        sync round is another executable than the one after a train step.
        The variants are found from the compiled programs' output shapes
        and shardings; nothing runs."""
        texts: Dict[str, List[str]] = {}

        def compiled(fn, *args):
            c = fn.lower(*args).compile()
            text = c.as_text()
            got = texts.setdefault(text.split(None, 2)[1].rstrip(","), [])
            if text not in got:
                got.append(text)
            return c.out_info

        seen, todo = set(), [state]
        while todo:
            st = todo.pop()
            sig = tuple((x.shape, str(x.dtype), str(x.sharding),
                         bool(getattr(x, "weak_type", False)))
                        for x in jax.tree.leaves(st))
            if sig in seen:
                continue
            seen.add(sig)
            after_train = compiled(self._train_step, st, batch)[0]
            todo += [after_train, compiled(self._sync_step, after_train)]
        return {name: tuple(t) for name, t in texts.items()}

    # ------------------------------------------------------ elasticity
    def reconfigure(self, state: TrainState, n_pods: int,
                    keep: Optional[Tuple[int, ...]] = None,
                    sync: Optional[SyncConfig] = None
                    ) -> Tuple["Trainer", TrainState]:
        """Apply a reconfiguration at a sync barrier: re-stack the leading pod
        dimension of the whole train state (grow: mean-seeded joiners; shrink:
        departed pods re-averaged into survivors, gradient accumulators
        replay-accumulated) and return a fresh ``Trainer`` bound to the new
        pod count / sync config, with WAN-traffic accounting carried over."""
        import dataclasses
        new_cfg = dataclasses.replace(self.cfg, n_pods=n_pods,
                                      sync=sync or self.cfg.sync)
        new_state = resize_train_state(new_cfg.sync, state, n_pods, keep=keep)
        trainer = Trainer(self.loss_fn, self.init_fn, new_cfg,
                          transport=self.transport, stream=self.stream)
        trainer.traffic_mb = self.traffic_mb
        trainer.stream_retunes = self.stream_retunes
        return trainer, new_state

    def retune(self, state: TrainState, sync: SyncConfig
               ) -> Tuple["Trainer", TrainState]:
        """Apply an adaptive-sync retune (``SyncPlanUpdate.sync``) at a sync
        barrier: same strategy and pod count, different codec tier / top-k /
        interval.  Unlike :meth:`reconfigure` nothing is re-stacked — params
        and optimizer state pass through untouched, and the EF residual
        carries over (it lives in dense bucket coordinates, so its meaning
        is tier-independent); only the jitted sync step re-compiles."""
        import dataclasses
        new_cfg = dataclasses.replace(self.cfg, sync=sync)
        sync_state = retune_sync_state(sync, self.cfg.sync, state.sync_state,
                                       state.params)
        trainer = Trainer(self.loss_fn, self.init_fn, new_cfg,
                          transport=self.transport, stream=self.stream)
        # the per-step path depends on the sync *strategy* (which a retune
        # cannot change), not the codec knobs — reuse the compiled train
        # step so a retune recompiles only the sync step.  And only when a
        # bucket's tier/top-k actually changed: the shared sync-step cache
        # (keyed on the interval-normalized config) means an interval-only
        # retune, or a return to a previously compiled rung combination,
        # re-jits nothing at all.  The host-seam (prepare, finish) pair
        # follows the same cache discipline.
        trainer._train_step = self._train_step
        trainer._sync_cache = self._sync_cache
        trainer._split_cache = self._split_cache
        trainer._stream_cache = self._stream_cache
        trainer.stream_retunes = self.stream_retunes
        key = self._sync_key(sync)
        cached = self._sync_cache.get(key)
        if cached is not None:
            trainer._sync_step = cached
        else:
            self._sync_cache[key] = trainer._sync_step
        split_cached = self._split_cache.get(key)
        if split_cached is not None:
            (trainer._prepare_sync, trainer._finish_sync,
             trainer._finish_sync_masked) = split_cached
        else:
            self._split_cache[key] = (trainer._prepare_sync,
                                      trainer._finish_sync,
                                      trainer._finish_sync_masked)
        if sync.bucket_policy == self.cfg.sync.bucket_policy:
            trainer._bucket_weights = self._bucket_weights
        trainer.traffic_mb = self.traffic_mb
        return trainer, state._replace(sync_state=sync_state)

    def maybe_sync(self, state: TrainState, host_step: int,
                   model_mb: float = 0.0) -> TrainState:
        with jax.profiler.TraceAnnotation("repro.maybe_sync"):
            if self.cfg.n_pods > 1:
                # WAN transfers per sync round: the flat ring's count is one
                # per pod; a hierarchical transport exposes its compiled
                # schedule's count (tree over R regions: 2(R-1); auxiliary
                # routes pay both hops) — same multiplier
                # cost.adaptive_traffic_mb bills and the DES charges
                legs = getattr(self.transport, "wan_transfers_per_round",
                               None)
                self.traffic_mb += traffic_per_step_mb(
                    self.cfg.sync, model_mb,
                    bucket_weights=self.bucket_weights(state)) * (
                        legs if legs is not None else self.cfg.n_pods)
            if is_sync_step(self.cfg.sync, host_step) and self.cfg.n_pods > 1:
                # fault-aware transports arm their plan per round (which pods
                # are dead, which transfers will need retries) before shipping
                begin = getattr(self.transport, "begin_round", None)
                if begin is not None:
                    begin(host_step)
                with jax.profiler.TraceAnnotation("repro.sync_round"):
                    if self._can_stream():
                        streamed = self._stream_sync(state, host_step)
                        if streamed is not None:
                            # the streaming round already billed itself
                            # (end_stream_round IS this round's barrier)
                            return streamed
                    if self._host_seam and self.cfg.sync.uses_codec:
                        state = self._host_sync(state)
                    else:
                        state = self._sync_step(state)
                if self.transport is not None:
                    # round barrier: bill (sim) or flush (mesh) this round's
                    # transfers into the transport's records + measured probe
                    self.transport.on_sync(self.wire_mb(state), step=host_step)
            return state

    # --------------------------------------------------------------- loop
    def fit(self, state: TrainState, batches: Callable[[int], Pytree],
            n_steps: int, *, eval_fn: Optional[Callable] = None,
            eval_every: int = 0, model_mb: float = 0.0,
            log_every: int = 0) -> Tuple[TrainState, Dict[str, List]]:
        """batches(step) -> stacked per-pod batch pytree (n_pods leading)."""
        history: Dict[str, List] = {"step": [], "loss": [], "loss_per_pod": [],
                                    "eval": []}
        for step in range(n_steps):
            batch = batches(step)
            state, metrics = self.train_step(state, batch)
            state = self.maybe_sync(state, step, model_mb)
            history["step"].append(step)
            history["loss"].append(float(metrics["loss"]))
            history["loss_per_pod"].append(
                np.asarray(metrics["loss_per_pod"]).tolist())
            if eval_fn and eval_every and (step + 1) % eval_every == 0:
                history["eval"].append((step, eval_fn(state)))
            if log_every and (step + 1) % log_every == 0:
                print(f"step {step + 1}: loss={history['loss'][-1]:.4f}")
        return state, history


# ---------------------------------------------------------------------------
# elasticity: pod re-stacking of the train state
# ---------------------------------------------------------------------------


def resize_train_state(sync_cfg: SyncConfig, state: TrainState, n_new: int,
                       keep: Optional[Tuple[int, ...]] = None) -> TrainState:
    """Grow/shrink the leading pod dimension of a :class:`TrainState`.

    ``keep`` names the surviving old pod indices in their new order (defaults
    to the first ``min(old, new)`` pods).  Parameters use mean-preserving
    transforms; optimizer moments are mean-seeded on grow but plainly kept on
    shrink (no shift — Adam's second moment must stay non-negative); the sync
    state follows its strategy's semantics — the ASGD-GA gradient buffer and
    the codec's error-feedback residual both replay-accumulate on shrink
    (sum-preserving) and zero-seed joiners
    (see ``repro.core.sync.resize_sync_state``).
    """
    n_old = jax.tree.leaves(state.params)[0].shape[0]
    if keep is None:
        keep = tuple(range(min(n_old, n_new)))
    if len(keep) > n_new:
        raise ValueError(f"keep={keep} longer than n_new={n_new}")
    shrunk = len(keep) < n_old
    params, opt = state.params, state.opt_state
    if shrunk:
        params = shrink_pods(params, keep, how="mean")
        # survivors keep their own optimizer moments untouched: a mean shift
        # could push sign-constrained leaves (Adam's second moment) negative
        opt = shrink_pods(opt, keep, how="drop")
    if n_new > len(keep):
        params = grow_pods(params, n_new, how="mean")
        opt = grow_pods(opt, n_new, how="mean")
    sync_state = resize_sync_state(sync_cfg, state.sync_state, params,
                                   keep=keep if shrunk else None)
    return TrainState(params=params, opt_state=opt, sync_state=sync_state,
                      step=state.step)


def apply_reconfig(trainer: Trainer, state: TrainState, reconfig
                   ) -> Tuple[Trainer, TrainState, bool]:
    """Bridge a control-plane :class:`~repro.core.control_plane.ReconfigPlan`
    onto a live trainer.  Returns ``(trainer, state, applied)`` — an empty
    plan diff is a structural no-op and leaves both untouched."""
    if reconfig.is_noop:
        return trainer, state, False
    keep, n_new = reconfig.pod_transition()
    new_trainer, new_state = trainer.reconfigure(
        state, n_new, keep=keep, sync=reconfig.new.request.sync)
    return new_trainer, new_state, True


# ---------------------------------------------------------------------------
# elasticity: live pod migration off the step path
# ---------------------------------------------------------------------------


def _resized_like(tree: Pytree, n_old: int, n_new: int) -> Pytree:
    """Shape/dtype skeleton of ``tree`` with every pod-stacked leaf's
    leading dimension re-sized ``n_old -> n_new`` (scalar bookkeeping
    leaves pass through)."""
    def f(x):
        shape = tuple(getattr(x, "shape", ()))
        if len(shape) >= 1 and shape[0] == n_old:
            shape = (n_new,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype)
    return jax.tree.map(f, tree)


class LiveMigrator:
    """Live pod migration: a grow/shrink staged off the training step.

    On a ``PlanDiff`` the surviving pods keep stepping.  :meth:`stage`
    materializes the target-pod-count state skeleton from the async
    engine's last durable snapshot on a background thread, via the
    checkpoint layer's ``pod_resize`` transforms — in a real deployment
    this is the bulk WAN shipment of the migration (the
    ``migration_wire_mb`` bytes the DES bills as overlapped background
    traffic).  At the next sync barrier :meth:`reconcile` applies the same
    pod-resize transforms to the *live* state (``apply_reconfig`` /
    ``resize_train_state`` — EF residuals and optimizer moments carried
    under exactly the invariants ``retune_sync_state`` guarantees), so the
    reconciled state is bit-identical to a pause-and-restore taken at the
    barrier; the staged restore validates the target structure and stands
    by as the recovery base if the barrier never comes (pod crash
    mid-migration).  The reconfiguration's only stall is the one barrier
    it reconciles at."""

    def __init__(self, engine):
        import threading
        self.engine = engine
        self._threading = threading
        self._pending: Optional[Tuple[Any, Dict[str, Any]]] = None
        self.migrations = 0
        self.restaged = 0
        self.staged_mb = 0.0
        self.errors: List[Exception] = []
        self.last_staged: Optional[Dict[str, Any]] = None

    @property
    def pending(self) -> bool:
        return self._pending is not None

    def stage(self, state: TrainState, n_new: int,
              keep: Optional[Tuple[int, ...]] = None) -> None:
        """Start materializing the ``n_new``-pod state from the last
        durable snapshot in the background.  Supersedes any earlier
        un-reconciled stage (the launcher composes events between
        barriers — only the barrier-time plan is reconciled)."""
        from repro.checkpoint import checkpoint as _ckpt

        if self._pending is not None:
            self._join_pending(superseded=True)
        n_old = jax.tree.leaves(state.params)[0].shape[0]
        like = _resized_like(state, n_old, n_new)
        holder: Dict[str, Any] = {"n_new": n_new,
                                  "keep": tuple(keep) if keep else None}

        def work():
            try:
                self.engine.wait()
                durable = self.engine.last_durable()
                if durable is None:
                    return
                snap_step, path = durable
                staged, ckpt_step = _ckpt.restore(path, like=like,
                                                  pod_resize="mean")
                holder.update(
                    state=staged, snapshot_step=snap_step,
                    ckpt_step=ckpt_step,
                    mb=sum(np.asarray(x).nbytes
                           for x in jax.tree.leaves(staged.params)) / 1e6)
            except Exception as e:   # noqa: BLE001 — surfaced at reconcile
                holder["error"] = e

        t = self._threading.Thread(target=work, daemon=True,
                                   name="live-migrator")
        t.start()
        self._pending = (t, holder)

    def _join_pending(self, superseded: bool = False) -> Optional[Dict]:
        t, holder = self._pending
        t.join()
        self._pending = None
        err = holder.get("error")
        if err is not None:
            # a failed stage degrades to a plain barrier re-stack — the
            # reconcile math never depended on the staged bytes
            self.errors.append(err)
            return None
        if superseded:
            self.restaged += 1
            return None
        if "state" not in holder:
            return None   # no durable snapshot yet: nothing was staged
        return holder

    def reconcile(self, trainer: Trainer, state: TrainState, reconfig
                  ) -> Tuple[Trainer, TrainState, bool]:
        """At the sync barrier: reconcile the migration against the live
        state.  Same signature and semantics as :func:`apply_reconfig` —
        and bit-identical results: the staged snapshot never enters the
        numerics, it only pre-moved the bytes a joining/leaving pod needs
        and pre-validated the target structure."""
        staged = self._join_pending() if self._pending is not None else None
        new_trainer, new_state, applied = apply_reconfig(trainer, state,
                                                         reconfig)
        if not applied:
            return new_trainer, new_state, applied
        self.migrations += 1
        if staged is not None:
            if staged["n_new"] != new_trainer.cfg.n_pods:
                # the plan evolved between stage and barrier: the staged
                # skeleton is stale — the barrier re-stack covered it
                self.restaged += 1
            else:
                ref = jax.tree.leaves(new_state.params)
                got = jax.tree.leaves(staged["state"].params)
                if [(tuple(a.shape), a.dtype) for a in got] != \
                        [(tuple(a.shape), a.dtype) for a in ref]:
                    raise RuntimeError(
                        "staged migration skeleton does not match the "
                        "reconciled state — snapshot/plan divergence")
                self.staged_mb += staged["mb"]
                self.last_staged = staged
        return new_trainer, new_state, applied


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def stack_pod_batches(batches: List[Dict[str, np.ndarray]]) -> Dict[str, jnp.ndarray]:
    """Stack per-cloud host batches (padding uneven batch sizes with masked
    examples so the elastic scheduler's uneven splits fit the stacked shape)."""
    max_b = max(len(next(iter(b.values()))) for b in batches)
    out: Dict[str, List[np.ndarray]] = {}
    for b in batches:
        n = len(next(iter(b.values())))
        pad = max_b - n
        mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
        for k, v in b.items():
            if pad:
                v = np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
            out.setdefault(k, []).append(v)
        out.setdefault("example_mask", []).append(mask)
    return {k: jnp.asarray(np.stack(v)) for k, v in out.items()}


def accuracy_eval(apply_fn, data: Dict[str, np.ndarray], batch: int = 512):
    """Eval callback: mean accuracy of pod-0's model on held-out data."""

    @jax.jit
    def acc(params, x, y):
        logits = apply_fn(params, x)
        if logits.ndim == 1:   # binary (DeepFM)
            return jnp.mean((logits > 0).astype(jnp.int32) == y)
        return jnp.mean(jnp.argmax(logits, -1) == y)

    def fn(state: TrainState) -> float:
        p0 = jax.tree.map(lambda x: x[0], state.params)
        n = len(data["y"])
        accs = []
        for i in range(0, n, batch):
            accs.append(float(acc(p0, data["x"][i:i + batch],
                                  data["y"][i:i + batch])))
        return float(np.mean(accs))

    return fn
