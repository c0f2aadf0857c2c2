"""Adaptive WAN sync autotuner benchmark: adaptive vs best-static codec
config on a fluctuating-bandwidth WAN trace.

The measurement couples two timelines:

- **Convergence** is real: the emulated 2-pod LeNet run from the codec
  benches (same numerics as multi-pod TPU), so compression aggressiveness
  has its true effect on the loss trajectory — an over-compressed run
  needs more steps to a target loss, exactly the failure mode a controller
  must not buy bandwidth with.
- **Wall-clock** is emulated: each step costs ``COMPUTE_STEP_S``; each sync
  round blocks for ``payload * 8 / bw(t) * (1 - overlap)`` at the trace's
  bandwidth (paper-calibrated overlap 0.55; deterministic — the trace IS
  the fluctuation, so regression CI can band-check the numbers).  Payload
  uses the paper's Table III ResNet18 gradient size, scaled by each
  config's ``payload_mb`` math.

Headline metric: **time-to-target-loss** — emulated seconds until the
5-step running-mean loss first reaches the target.  The adaptive controller
must beat the best *static* configuration, with its EF-residual guard never
violated (``max_ef_ratio <= ef_guard`` over the whole run).

A second scenario measures **per-bucket vs single-bucket control** on the
same fluctuating trace: DeepFM (the paper's CTR workload — its embedding
table is ~27% of the payload and norm-class vectors ~2%, so the layer-class
partition has real byte mass to trade) trained once under the single-bucket
``AdaptiveSyncController`` and once under the ``BucketedSyncController``.
Acceptance: the bucketed run reaches the target **no later** at **no more
wire bytes**, with neither run's EF guard violated on any bucket.

The per-sync signal stream (sim time, bandwidth, EF norms — per bucket for
the multi-controller run) and the decision lists land in
``BENCH_autotune.json`` so ``benchmarks/check_regression.py`` can replay
both control laws deterministically without re-training.

Run:  PYTHONPATH=src python -m benchmarks.autotune
      PYTHONPATH=src python -m benchmarks.autotune --compare A.json B.json
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "..", "experiments", "bench")
OUT_PATH = os.path.join(OUT_DIR, "BENCH_autotune.json")

MODEL_MB = 44.6           # ResNet18 gradients, paper Table III ballpark
COMPUTE_STEP_S = 0.3      # emulated local compute per step
OVERLAP = 0.55            # async blocking share = 1 - overlap (paper-calib)
STEPS = 220
TARGET_LOSS = 0.01        # 5-step running mean target (from init ~2.38)
EF_GUARD = 0.98           # above the bottom rung's intrinsic steady-state
#   ratio (~0.95 at int4@0.01 on this task): a guard below that would pin
#   the controller off its own ladder floor

# the controller's constructor knobs, recorded into BENCH_autotune.json so
# check_regression.py replays EXACTLY this controller (a bench retune that
# forgets to refresh baselines fails the gate loudly, not confusingly)
TUNER_KW = dict(ef_guard=EF_GUARD, topk_ladder=(0.05, 0.02, 0.01),
                hysteresis=2, interval_budget=8, max_interval=12)
BASE_SYNC = dict(strategy="asgd_ga", interval=4, compress_topk=0.05)
SEED = 0

# per-bucket scenario: DeepFM, same trace, same emulated payload scale.
# Both deepfm runs (single AND bucketed) use the same knobs; the wider
# escalate_margin reflects that per-bucket EF ratios are structurally
# higher than the pooled single-bucket ratio (a bucket's own ratio is not
# diluted by easier buckets' energy — on deepfm the dense tower reads
# ~0.96 where the pooled ratio reads ~0.88), so the escalation threshold
# scales accordingly; the hard ef_guard is identical for both.
BUCKETED_MODEL = "deepfm"
BUCKETED_TARGET_LOSS = 0.04      # bce from ~0.69; reached ~step 140
BUCKETED_TUNER_KW = {**TUNER_KW, "escalate_margin": 0.99}
FEATURE_VOCAB = 5400             # Frappe-scale feature space (reference.py)

# the fluctuating link: calm 100 Mbps, a deep 0.5 Mbps trough, partial
# recovery, a second trough — the regime the paper measures ("low bandwidth
# and high fluctuations") where no static config is right twice: fidelity
# tiers die in the troughs, aggressive tiers waste the calm stretches, and
# only spending staleness *when the link demands it* threads both
TRACE_SEGMENTS = ((0.0, 100.0), (12.0, 0.5), (60.0, 60.0),
                  (90.0, 2.0), (130.0, 80.0))

# measured-feedback scenario (the PR-5 transport seam): a SimTransport
# bills each sync round with the simulator's transfer law on the SAME
# trace, and the controller's ONLY bandwidth input is those billed times
# (MeasuredWanProbe -> injected probe_est).  latency 0 keeps achieved ==
# trace bandwidth at calm (a 50 ms latency would dominate the small
# compressed payloads and bias the belief toward single-digit Mbps);
# sigma 0.15 exercises the estimator's smoothing while inflating the
# timeline by ~1% mean — the decision band absorbs it.
MEASURED_WAN = dict(fluctuation=0.15, latency_s=0.0, seed=SEED)
MEASURED_PROBE = dict(alpha=0.5, cliff_snap=4.0)   # MeasuredWanProbe knobs,
#   recorded into the baseline so check_regression replays EXACTLY this
#   probe (same discipline as the controller knobs)
MEASURED_BAND = 0.15   # time-to-target band vs the trace-driven run

# mesh overlap measurement: 4 virtual devices, 8 chunks, a 1 Mbps emulated
# WAN hop sized so per-chunk transfer and per-chunk encode are comparable
# (that is the regime where pipelining pays; see
# MeshTransport.measure_overlap)
MESH_OVERLAP = dict(n_pods=4, n_elems=1 << 21, emulate_mbps=1.0, chunks=8)

# hierarchical-topology scenario (the third actuator): 3 pods, one per
# region, all links calm at 100 Mbps except gz<->sh, which collapses to
# 2 Mbps at t=10s and stays down.  This is the asymmetric regime where the
# shape matters: a 3-region ring crosses EVERY link every round (no
# reordering can dodge the bad one), while a tree re-roots at cq and
# aggregates over the two healthy links — one slow round to discover the
# cliff, then fast forever.  Shipping is bit-exact either way
# (HierarchicalTransport delegates to the inline ring), so the fixed
# ``ring`` and ``tree`` variants — static codec config — share ONE loss
# trajectory step for step (acceptance-flagged), and their time-to-target
# difference is purely what each shape pays the collapsed link.  ``auto``
# is the full composition: the measured-feedback adaptive codec
# controller with a TopologyPlanner wired in as the third actuator,
# starting on the ring and switching shapes from measured link beliefs.
TOPOLOGY_REGIONS = ("sh", "cq", "gz")
TOPOLOGY_CALM_MBPS = 100.0
TOPOLOGY_BAD_LINK = ("gz", "sh")
TOPOLOGY_BAD_SEGMENTS = ((0.0, 100.0), (10.0, 2.0))
TOPOLOGY_PLANNER = dict(hysteresis=2, switch_margin=0.85)   # recorded into
#   the baseline so check_regression replays EXACTLY this planner (same
#   discipline as the controller/probe knobs)

# streaming (chunk-granular) scenario: repeated MID-ROUND cliffs — the
# link collapses between one sync's fold and the next round's transfer,
# i.e. inside the exact window the round-level controllers cannot see
# (they decide at the top of the step from the previous round's
# measurements).  The once-per-round autotuner pays each surprise as one
# full stale transfer at the old tier; the streaming controller reads the
# cliff off the FIRST chunk and re-encodes the round's unsent tail at a
# cheaper rung, so it pays ~one chunk plus a cheap tail.  Calm stretches
# between cliffs let the belief recover (and the round controller
# re-escalate), so every collapse is a fresh surprise for both variants —
# the measured difference is purely the in-flight round's reaction.
STREAM_TRACE_SEGMENTS = ((0.0, 100.0), (6.0, 0.5), (26.0, 100.0),
                         (46.0, 0.5), (66.0, 100.0), (86.0, 0.5),
                         (106.0, 100.0))
STREAM_CHUNKS = 8          # overlap_chunks: first-chunk feedback at 1/8 of
#   the round's payload
STREAM_KNOBS = dict(cliff_ratio=4.0, hysteresis=1)   # recorded into the
#   baseline so check_regression replays EXACTLY this chunk-level law
#   (the --stream-cliff / --stream-hysteresis production defaults)
STREAM_SPEEDUP_MIN = 1.2   # acceptance: streaming >= 1.2x faster to the
#   target loss than the once-per-round autotuner on the same cliffs


def _trace():
    from repro.core.wan import BandwidthTrace

    return BandwidthTrace(times_s=tuple(t for t, _ in TRACE_SEGMENTS),
                          mbps=tuple(b for _, b in TRACE_SEGMENTS))


def _make_trainer(sync, model: str = "lenet", transport=None, stream=None):
    from repro.data.pipeline import GeoDataset, synthetic_classification
    from repro.models.reference import PAPER_MODELS
    from repro.training.trainer import Trainer, TrainerConfig

    m = PAPER_MODELS[model]
    data = synthetic_classification(
        1500, m["input_shape"], m["n_classes"], seed=SEED,
        feature_vocab=FEATURE_VOCAB if model == "deepfm" else None)
    geo = GeoDataset.partition(data, ["sh", "cq"], [2, 1])
    loaders = [geo.loader("sh", 32, seed=0), geo.loader("cq", 32, seed=1)]
    tr = Trainer(lambda p, b: (m["loss"](p, b), {}), m["init"],
                 TrainerConfig(n_pods=2, optimizer="sgd", lr=0.05, sync=sync),
                 transport=transport, stream=stream)
    return tr, loaders


def run_variant(sync, *, adaptive: bool = False, bucketed: bool = False,
                measured: bool = False,
                model: str = "lenet", target_loss: float = TARGET_LOSS,
                tuner_kw: Optional[Dict] = None) -> Dict:
    """One emulated-timeline training run; returns the measured trajectory.

    ``adaptive=True`` attaches an AdaptiveSyncController that observes the
    trace bandwidth + each sync's EF stats and retunes through
    ``Trainer.retune`` — the exact production path of ``launch.train
    --adaptive-sync``.  ``bucketed=True`` attaches the per-bucket
    BucketedSyncController instead (``--bucket-policy layer-class``) and
    records per-bucket signals/decisions for the replay gate.

    ``measured=True`` (the transport-seam scenario, implies the adaptive
    controller): the SAME fluctuating trace drives a ``SimTransport`` —
    and **nothing else**.  The controller never sees the trace: its only
    bandwidth input is the transport-billed transfer time of each sync
    round, folded through ``MeasuredWanProbe`` into the injected
    ``probe_est`` (the exact production path of ``launch.train
    --transport sim --adaptive-sync``).  Observability is therefore
    sync-cadence-bound — a link crash is discovered by *paying one
    transfer on it* — which is the honest cost of measured feedback that
    the trace-driven variant's every-step probing hides."""
    from repro.core.autotune import (AdaptiveSyncController, BucketStats,
                                     BucketedSyncController,
                                     bucket_stats_from_sync_state)
    from repro.core.sync import bucket_weights_of, is_sync_step
    from repro.core.transport import MeasuredWanProbe, SimTransport
    from repro.core.wan import WANConfig
    from repro.training.trainer import stack_pod_batches

    trace = _trace()
    trainer, loaders = _make_trainer(sync, model=model)
    state = trainer.init_state(jax.random.key(SEED))
    weights = (bucket_weights_of(sync, state.params)
               if sync.bucket_policy != "single" else None)
    tuner = None
    transport = None
    kw = tuner_kw if tuner_kw is not None else TUNER_KW
    if measured:
        transport = SimTransport(
            trace, WANConfig(bandwidth_mbps=trace.mbps[0], **MEASURED_WAN),
            probe=MeasuredWanProbe(**MEASURED_PROBE))
        tuner = AdaptiveSyncController(
            sync, MODEL_MB, COMPUTE_STEP_S,
            probe_est=transport.probe.estimator, **kw)
        # NO observe_wan: the belief starts empty and fills from billed
        # transfers only
    elif bucketed:
        bucket_mb = {n: w * MODEL_MB for n, w in weights.items()}
        tuner = BucketedSyncController(sync, bucket_mb, COMPUTE_STEP_S, **kw)
        tuner.observe_wan(trace.at(0.0))
    elif adaptive:
        tuner = AdaptiveSyncController(sync, MODEL_MB, COMPUTE_STEP_S, **kw)
        tuner.observe_wan(trace.at(0.0))

    sim_t = 0.0
    losses: List[float] = []
    signals: List[list] = []   # [sim_t, bw, <stats...>] per step
    decisions: List[Dict] = []
    traffic_mb = 0.0
    max_ratio = 0.0
    time_to_target: Optional[float] = None
    stats = BucketStats(0.0, 0.0)       # no reading before the first sync
    bstats: Dict[str, BucketStats] = {}
    pending_transfer: Optional[List[float]] = None   # [mb, s] since last step

    for step in range(STEPS):
        # the WAN monitor probes every step (out-of-band, like the bus's
        # bandwidth_changed events) and the controller decides at the TOP
        # of the step — reaction latency must NOT be coupled to the sync
        # cadence, or a crashed link is discovered only by paying one full
        # transfer at the stale config.  (In measured mode there IS no
        # out-of-band monitor: the probe advanced when the last sync's
        # transfer was billed, below.)
        bw = trace.at(sim_t)
        if tuner is not None:
            # full-precision norms, NOT a rounded ratio: the replay gate
            # reconstructs BucketStats from these, and both the
            # "no reading yet" state (msg_norm 0) and the controllers'
            # consume-once staleness check (value equality of consecutive
            # readings) must survive the JSON round trip exactly
            if measured:
                signals.append([round(sim_t, 3), pending_transfer,
                                stats.msg_norm, stats.resid_norm])
                pending_transfer = None
                upd = tuner.update(step, stats)
            elif bucketed:
                tuner.observe_wan(bw)
                signals.append([round(sim_t, 3), bw,
                                {n: [s.msg_norm, s.resid_norm]
                                 for n, s in bstats.items()}])
                upd = tuner.update(step, bstats)
            else:
                tuner.observe_wan(bw)
                signals.append([round(sim_t, 3), bw,
                                stats.msg_norm, stats.resid_norm])
                upd = tuner.update(step, stats)
            if upd is not None:
                trainer, state = trainer.retune(state, upd.sync)
                if bucketed:
                    decisions.append({
                        "step": step, "sim_t": round(sim_t, 2),
                        "rungs": {n: r for n, r, _ in upd.rungs},
                        "tiers": {n: t for n, _, t in upd.rungs},
                        "interval": upd.sync.interval,
                        "reasons": list(upd.reasons)})
                else:
                    decisions.append({
                        "step": step, "sim_t": round(sim_t, 2),
                        "rung": upd.rung, "tier": upd.tier,
                        "value_dtype": upd.sync.value_dtype,
                        "compress_topk": upd.sync.compress_topk,
                        "interval": upd.sync.interval,
                        "reason": upd.reason})

        state, metrics = trainer.train_step(
            state, stack_pod_batches([next(ld) for ld in loaders]))
        losses.append(float(metrics["loss"]))
        sim_t += COMPUTE_STEP_S

        if is_sync_step(trainer.cfg.sync, step):
            payload = trainer.cfg.sync.payload_mb(MODEL_MB,
                                                  bucket_weights=weights)
            if measured:
                # the transport bills this round at its sim clock (the
                # same trace), records the transfer, and feeds the probe —
                # the ONLY bandwidth signal the controller ever gets
                transport.clock_s = sim_t
                t = transport.on_sync({"all": payload}, step=step)
                pending_transfer = [payload, t]
                sim_t += t * (1.0 - OVERLAP)
            else:
                bw = trace.at(sim_t)        # achieved bandwidth this round
                sim_t += payload * 8.0 / bw * (1.0 - OVERLAP)
            traffic_mb += payload * trainer.cfg.n_pods
            state = trainer._sync_step(state)
            stats = BucketStats.from_sync_state(state.sync_state)
            max_ratio = max(max_ratio, stats.ef_ratio)
            if bucketed:
                bstats = bucket_stats_from_sync_state(
                    state.sync_state, trainer.cfg.sync.bucket_names)

        if (time_to_target is None and len(losses) >= 5
                and float(np.mean(losses[-5:])) <= target_loss):
            time_to_target = round(sim_t, 2)

    out = {
        "time_to_target_s": time_to_target,
        "final_loss": round(float(np.mean(losses[-5:])), 6),
        "total_sim_s": round(sim_t, 2),
        "traffic_mb": round(traffic_mb, 2),
        "max_ef_ratio": round(max_ratio, 6),
    }
    if tuner is not None:
        out.update({
            "n_retunes": len(decisions),
            "ef_guard": EF_GUARD,
            "decisions": decisions,
            "signals": signals,
        })
        if bucketed:
            out.update({
                "final_rungs": {n: b.rung for n, b in tuner.buckets.items()},
                "final_config": {
                    n: {"value_dtype": b.cfg.value_dtype,
                        "compress_topk": b.cfg.compress_topk}
                    for n, b in tuner.buckets.items()},
                "final_interval": trainer.cfg.sync.interval,
                "max_ef_ratio_by_bucket": {
                    n: round(r, 6)
                    for n, r in tuner.max_ef_ratio_by_bucket.items()},
            })
        else:
            out.update({
                "final_rung": tuner.rung,
                "final_config": {
                    "value_dtype": trainer.cfg.sync.value_dtype,
                    "compress_topk": trainer.cfg.sync.compress_topk,
                    "interval": trainer.cfg.sync.interval},
            })
    return out


def static_variants() -> Dict[str, "object"]:
    from repro.core.sync import SyncConfig

    base = dict(quantize_int8=True, error_feedback=True)
    return {
        "dense@4": SyncConfig("asgd_ga", 4),
        "int8_topk0.05@4": SyncConfig("asgd_ga", 4, compress_topk=0.05,
                                      **base),
        "fp8_topk0.02@4": SyncConfig("asgd_ga", 4, compress_topk=0.02,
                                     value_dtype="fp8", **base),
        "int4_topk0.01@4": SyncConfig("asgd_ga", 4, compress_topk=0.01,
                                      value_dtype="int4", **base),
    }


def bench_bucketed() -> Dict:
    """Per-bucket vs single-bucket adaptive control, same trace, DeepFM."""
    import jax as _jax
    from repro.core.sync import SyncConfig, bucket_weights_of
    from repro.models.reference import PAPER_MODELS

    base_kw = dict(compress_topk=BASE_SYNC["compress_topk"],
                   quantize_int8=True, error_feedback=True)
    single = SyncConfig(BASE_SYNC["strategy"], BASE_SYNC["interval"],
                        **base_kw)
    multi = SyncConfig(BASE_SYNC["strategy"], BASE_SYNC["interval"],
                       bucket_policy="layer-class", **base_kw)
    p0 = PAPER_MODELS[BUCKETED_MODEL]["init"](_jax.random.key(SEED))
    stacked = _jax.tree.map(lambda x: x[None], p0)
    weights = bucket_weights_of(multi, stacked)
    out = {
        "model": BUCKETED_MODEL,
        "target_loss": BUCKETED_TARGET_LOSS,
        "tuner": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in BUCKETED_TUNER_KW.items()},
        # full precision, NOT rounded: check_regression rebuilds the
        # controller from these, and _fit_interval's ceil / the interval
        # deadband are discontinuous — a rounded weight could replay a
        # different decision stream than the live run produced
        "bucket_mb": {n: w * MODEL_MB for n, w in weights.items()},
        "variants": {
            "single": run_variant(single, adaptive=True,
                                  model=BUCKETED_MODEL,
                                  target_loss=BUCKETED_TARGET_LOSS,
                                  tuner_kw=BUCKETED_TUNER_KW),
            "bucketed": run_variant(multi, bucketed=True,
                                    model=BUCKETED_MODEL,
                                    target_loss=BUCKETED_TARGET_LOSS,
                                    tuner_kw=BUCKETED_TUNER_KW),
        },
    }
    t_single = out["variants"]["single"]["time_to_target_s"]
    t_bucket = out["variants"]["bucketed"]["time_to_target_s"]
    out["single_s"], out["bucketed_s"] = t_single, t_bucket
    out["speedup_vs_single"] = (round(t_single / t_bucket, 3)
                                if t_single and t_bucket else None)
    return out


def run_streaming_variant(streaming: bool) -> Dict:
    """One measured-feedback run on the mid-round-cliff trace.

    Both variants are the SAME measured-feedback adaptive setup as the
    transport-seam scenario — a SimTransport bills every round on the
    cliff trace, and the round-level controller's only bandwidth input is
    the probe belief those billed transfers feed — and the same sync
    config (``overlap_chunks`` set either way, so the chunked codec's
    numerics are shared).  ``streaming=True`` additionally hands the
    trainer the transport and a ``StreamingShipController`` sharing the
    SAME belief, so every sync round runs the chunk-granular protocol
    (``Trainer._stream_sync``): zero-retune rounds are bit-identical to
    the classic path (property-tested), and on a mid-round cliff the
    unsent tail re-encodes at a cheaper rung.  The recorded streams — the
    per-step (billed transfer, EF stats) signals, the per-round chunk
    observation lists and the controller's per-chunk decision dicts — are
    exactly what ``check_regression.check_streaming_replay`` re-runs."""
    from repro.core.autotune import (AdaptiveSyncController, BucketStats,
                                     StreamingShipController)
    from repro.core.sync import SyncConfig, is_sync_step
    from repro.core.transport import MeasuredWanProbe, SimTransport
    from repro.core.wan import BandwidthTrace, WANConfig
    from repro.training.trainer import stack_pod_batches

    trace = BandwidthTrace(times_s=tuple(t for t, _ in STREAM_TRACE_SEGMENTS),
                           mbps=tuple(b for _, b in STREAM_TRACE_SEGMENTS))
    transport = SimTransport(
        trace, WANConfig(bandwidth_mbps=trace.mbps[0], **MEASURED_WAN),
        probe=MeasuredWanProbe(**MEASURED_PROBE))
    sync = SyncConfig(BASE_SYNC["strategy"], BASE_SYNC["interval"],
                      compress_topk=BASE_SYNC["compress_topk"],
                      quantize_int8=True, error_feedback=True,
                      overlap_chunks=STREAM_CHUNKS)
    stream = (StreamingShipController(
                  sync, MODEL_MB, ef_guard=EF_GUARD,
                  probe_est=transport.probe.estimator, **STREAM_KNOBS)
              if streaming else None)
    trainer, loaders = _make_trainer(sync, transport=transport,
                                     stream=stream)
    tuner = AdaptiveSyncController(
        sync, MODEL_MB, COMPUTE_STEP_S,
        probe_est=transport.probe.estimator, **TUNER_KW)
    state = trainer.init_state(jax.random.key(SEED))
    # the trainer ships the REAL (small) model, so the transport bills and
    # the probe observes real-scale transfers; the emulated timeline
    # re-scales those seconds to the paper's ResNet18 payload.  With
    # latency 0 the transfer law is linear in MB, so one dense-size ratio
    # scales every chunk and every round uniformly — and achieved/believed
    # bandwidth (every decision input) is scale-free, so the decision
    # stream is exactly what a 44.6 MB model would have produced
    n_elems = sum(int(np.prod(x.shape[1:]))
                  for x in jax.tree.leaves(state.params))
    em_scale = MODEL_MB / (n_elems * 4 / 1e6)

    sim_t = 0.0
    losses: List[float] = []
    signals: List[list] = []
    decisions: List[Dict] = []
    traffic_mb = 0.0
    max_ratio = 0.0
    time_to_target: Optional[float] = None
    stats = BucketStats(0.0, 0.0)
    pending_transfer: Optional[List[float]] = None
    for step in range(STEPS):
        signals.append([round(sim_t, 3), pending_transfer,
                        stats.msg_norm, stats.resid_norm])
        pending_transfer = None
        upd = tuner.update(step, stats)
        if upd is not None:
            trainer, state = trainer.retune(state, upd.sync)
            decisions.append({
                "step": step, "sim_t": round(sim_t, 2),
                "rung": upd.rung, "tier": upd.tier,
                "value_dtype": upd.sync.value_dtype,
                "compress_topk": upd.sync.compress_topk,
                "interval": upd.sync.interval,
                "reason": upd.reason})
        state, metrics = trainer.train_step(
            state, stack_pod_batches([next(ld) for ld in loaders]))
        losses.append(float(metrics["loss"]))
        sim_t += COMPUTE_STEP_S
        if is_sync_step(trainer.cfg.sync, step):
            transport.clock_s = sim_t
            wire = trainer.wire_mb(state)
            streamed = (trainer._stream_sync(state, step)
                        if streaming else None)
            if streamed is not None:
                state = streamed
                rr = transport.stream_rounds[-1]
                t = rr["t_s"]
                # what the probe observed at the fold: the clean round
                # total, or — after a retune — what actually shipped
                mb_obs = (rr["total_mb"] if not rr["retuned"]
                          else rr["shipped_mb"])
                traffic_mb += rr["shipped_mb"] * em_scale \
                    * trainer.cfg.n_pods
            else:
                state = trainer._sync_step(state)
                t = transport.on_sync(wire, step=step)
                mb_obs = sum(wire.values())
                traffic_mb += mb_obs * em_scale * trainer.cfg.n_pods
            # real-scale observation (exactly what the probe folded —
            # the replay gate re-feeds it verbatim); emulated-scale bill
            pending_transfer = [mb_obs, t]
            sim_t += t * em_scale * (1.0 - OVERLAP)
            stats = BucketStats.from_sync_state(state.sync_state)
            max_ratio = max(max_ratio, stats.ef_ratio)
        if (time_to_target is None and len(losses) >= 5
                and float(np.mean(losses[-5:])) <= TARGET_LOSS):
            time_to_target = round(sim_t, 2)

    out = {
        "time_to_target_s": time_to_target,
        "final_loss": round(float(np.mean(losses[-5:])), 6),
        "total_sim_s": round(sim_t, 2),
        "traffic_mb": round(traffic_mb, 2),
        "max_ef_ratio": round(max_ratio, 6),
        "n_retunes": len(decisions),
        "ef_guard": EF_GUARD,
        "emulation_scale": em_scale,
        "decisions": decisions,
        "signals": signals,
        "final_config": {
            "value_dtype": trainer.cfg.sync.value_dtype,
            "compress_topk": trainer.cfg.sync.compress_topk,
            "interval": trainer.cfg.sync.interval},
    }
    if streaming:
        out.update({
            # full precision everywhere: check_streaming_replay re-bills
            # every chunk (stream_chunk_time over t_round/t_tail) and
            # re-runs the decision law (achieved = mb*8/s vs the
            # estimator belief) float-for-float off these records
            "n_stream_retunes": trainer.stream_retunes,
            "n_stream_rounds": stream.n_rounds,
            "stream_rounds": [
                {**r, "chunks": [list(c) for c in r["chunks"]]}
                for r in transport.stream_rounds],
            "stream_decisions": stream.decisions,
        })
    return out


def bench_streaming() -> Dict:
    """Once-per-round autotuner vs chunk-granular streaming retune on the
    mid-round-cliff trace — the first-chunk-feedback scenario."""
    out: Dict = {
        "trace": [list(seg) for seg in STREAM_TRACE_SEGMENTS],
        "wan": dict(MEASURED_WAN),
        "probe": dict(MEASURED_PROBE),
        "chunks": STREAM_CHUNKS,
        "stream": {**STREAM_KNOBS, "ef_guard": EF_GUARD},
        "speedup_min": STREAM_SPEEDUP_MIN,
        "variants": {
            "round_adaptive": run_streaming_variant(False),
            "streaming": run_streaming_variant(True),
        },
    }
    t_round = out["variants"]["round_adaptive"]["time_to_target_s"]
    t_stream = out["variants"]["streaming"]["time_to_target_s"]
    out["round_adaptive_s"], out["streaming_s"] = t_round, t_stream
    out["speedup_vs_round_adaptive"] = (round(t_round / t_stream, 3)
                                        if t_round and t_stream else None)
    return out


def run_topology_variant(kind: str) -> Dict:
    """One topology-scenario run: 3 pods / 3 regions aggregating through a
    ``HierarchicalTransport`` whose gz<->sh link collapses mid-run.

    ``ring`` / ``tree`` fix the shape AND the codec config for the whole
    run: shipping is bit-exact across shapes, so these two share one loss
    trajectory step for step (an acceptance flag pins it) and their
    time-to-target difference is *purely* what each shape pays the
    collapsed link — the clean ablation.  ``auto`` is the full
    three-actuator composition: the measured-feedback adaptive controller
    (probe fed by billed round times, as in the transport-seam scenario)
    with a ``TopologyPlanner`` wired in (``topology=``), switching shapes
    from the measured link beliefs — the production path of
    ``launch.train --topology auto --adaptive-sync``.  The ``auto`` run
    additionally records the exact interleaved (link observation, planner
    decide) event stream so ``check_regression`` can replay the topology
    control law deterministically."""
    from repro.core.autotune import AdaptiveSyncController, BucketStats
    from repro.core.sync import SyncConfig, is_sync_step
    from repro.core.topology import (HierarchicalTransport, LinkBeliefs,
                                     TopologyPlanner, TopologySpec, link_key)
    from repro.core.transport import MeasuredWanProbe
    from repro.core.wan import BandwidthTrace, WANConfig
    from repro.data.pipeline import GeoDataset, synthetic_classification
    from repro.models.reference import PAPER_MODELS
    from repro.training.trainer import (Trainer, TrainerConfig,
                                        stack_pod_batches)

    events: List[list] = []   # interleaved, in exact occurrence order

    class RecordingBeliefs(LinkBeliefs):
        def observe(self, a, b, mbps):
            events.append(["obs", a, b, float(mbps)])
            super().observe(a, b, mbps)

    class RecordingPlanner(TopologyPlanner):
        def decide(self, step, payload_mb):
            events.append(["decide", step, float(payload_mb)])
            return super().decide(step, payload_mb)

    spec = TopologySpec.from_regions(
        list(TOPOLOGY_REGIONS), kind=("ring" if kind == "auto" else kind))
    # the link beliefs reuse the measured probe's estimator knobs: same
    # cliff-snap scale, per link instead of pooled
    beliefs = RecordingBeliefs(default_mbps=TOPOLOGY_CALM_MBPS,
                               **MEASURED_PROBE)
    transport = HierarchicalTransport(
        spec, BandwidthTrace((0.0,), (TOPOLOGY_CALM_MBPS,)),
        wan=WANConfig(bandwidth_mbps=TOPOLOGY_CALM_MBPS, **MEASURED_WAN),
        link_traces={link_key(*TOPOLOGY_BAD_LINK): BandwidthTrace(
            times_s=tuple(t for t, _ in TOPOLOGY_BAD_SEGMENTS),
            mbps=tuple(b for _, b in TOPOLOGY_BAD_SEGMENTS))},
        probe=MeasuredWanProbe(**MEASURED_PROBE), beliefs=beliefs)
    planner = (RecordingPlanner(transport.spec, beliefs,
                                apply=transport.set_kind, **TOPOLOGY_PLANNER)
               if kind == "auto" else None)

    m = PAPER_MODELS["lenet"]
    data = synthetic_classification(1500, m["input_shape"], m["n_classes"],
                                    seed=SEED)
    geo = GeoDataset.partition(data, list(TOPOLOGY_REGIONS), [1, 1, 1])
    loaders = [geo.loader(r, 32, seed=i)
               for i, r in enumerate(TOPOLOGY_REGIONS)]
    sync = SyncConfig(BASE_SYNC["strategy"], BASE_SYNC["interval"],
                      compress_topk=BASE_SYNC["compress_topk"],
                      quantize_int8=True, error_feedback=True)
    trainer = Trainer(lambda p, b: (m["loss"](p, b), {}), m["init"],
                      TrainerConfig(n_pods=len(TOPOLOGY_REGIONS),
                                    optimizer="sgd", lr=0.05, sync=sync),
                      transport=transport)
    tuner = (AdaptiveSyncController(
                 sync, MODEL_MB, COMPUTE_STEP_S,
                 probe_est=transport.probe.estimator, topology=planner,
                 **TUNER_KW)
             if kind == "auto" else None)
    state = trainer.init_state(jax.random.key(SEED))

    sim_t = 0.0
    losses: List[float] = []
    decisions: List[Dict] = []
    traffic_mb = 0.0
    max_ratio = 0.0
    time_to_target: Optional[float] = None
    stats = BucketStats(0.0, 0.0)
    for step in range(STEPS):
        if tuner is not None:
            upd = tuner.update(step, stats)
            if upd is not None:
                trainer, state = trainer.retune(state, upd.sync)
                decisions.append({
                    "step": step, "sim_t": round(sim_t, 2),
                    "rung": upd.rung, "tier": upd.tier,
                    "interval": upd.sync.interval, "reason": upd.reason,
                    "topology": upd.topology})
        state, metrics = trainer.train_step(
            state, stack_pod_batches([next(ld) for ld in loaders]))
        losses.append(float(metrics["loss"]))
        sim_t += COMPUTE_STEP_S
        if is_sync_step(trainer.cfg.sync, step):
            payload = trainer.cfg.sync.payload_mb(MODEL_MB)
            # this round ships under the schedule compiled BEFORE billing
            # (on_sync recompiles at the end) — bill traffic at its count
            legs = transport.wan_transfers_per_round
            transport.clock_s = sim_t
            t = transport.on_sync({"all": payload}, step=step)
            sim_t += t * (1.0 - OVERLAP)
            traffic_mb += payload * legs
            state = trainer._sync_step(state)
            stats = BucketStats.from_sync_state(state.sync_state)
            max_ratio = max(max_ratio, stats.ef_ratio)
        if (time_to_target is None and len(losses) >= 5
                and float(np.mean(losses[-5:])) <= TARGET_LOSS):
            time_to_target = round(sim_t, 2)

    out = {
        "time_to_target_s": time_to_target,
        "final_loss": round(float(np.mean(losses[-5:])), 6),
        "total_sim_s": round(sim_t, 2),
        "traffic_mb": round(traffic_mb, 2),
        "max_ef_ratio": round(max_ratio, 6),
        "n_retunes": len(decisions),
        "decisions": decisions,
        "final_kind": transport.spec.kind,
        "wan_transfers_per_round": transport.wan_transfers_per_round,
        "switches": [list(s) for s in transport.switches],
        "reroutes": [list(r) for r in transport.reroutes],
        "final_beliefs": transport.beliefs.snapshot(),
        "final_config": {
            "value_dtype": trainer.cfg.sync.value_dtype,
            "compress_topk": trainer.cfg.sync.compress_topk,
            "interval": trainer.cfg.sync.interval},
    }
    if planner is not None:
        # full precision (observations AND decide payloads): the replay
        # gate feeds these verbatim into fresh LinkBeliefs/TopologyPlanner
        # and the estimator EMA + estimate comparison are both
        # discontinuous in them
        out["events"] = events
        out["planner_decisions"] = [list(d) for d in planner.decisions]
    return out


def bench_topology() -> Dict:
    """Fixed-ring vs fixed-tree vs planner-driven shape on the collapsing
    asymmetric link — the third-actuator scenario."""
    from repro.core.topology import LinkBeliefs, TopologySpec

    out: Dict = {
        "regions": list(TOPOLOGY_REGIONS),
        "initial_kind": "ring",
        "default_mbps": TOPOLOGY_CALM_MBPS,
        "bad_link": list(TOPOLOGY_BAD_LINK),
        "bad_link_trace": [list(seg) for seg in TOPOLOGY_BAD_SEGMENTS],
        "beliefs": dict(MEASURED_PROBE),
        "planner": dict(TOPOLOGY_PLANNER),
        "wan": dict(MEASURED_WAN),
        "variants": {k: run_topology_variant(k)
                     for k in ("ring", "tree", "auto")},
    }
    # the schedule-shape arithmetic the traffic accounting bills
    # (check_regression recomputes these against a fresh compile)
    spec = TopologySpec.from_regions(list(TOPOLOGY_REGIONS), kind="ring")
    fresh = LinkBeliefs(default_mbps=TOPOLOGY_CALM_MBPS)
    out["wan_transfers"] = {
        k: spec.with_kind(k).compile(fresh).wan_transfers
        for k in ("ring", "tree")}
    for k in ("ring", "tree", "auto"):
        out[f"{k}_s"] = out["variants"][k]["time_to_target_s"]
    out["tree_speedup_vs_ring"] = (
        round(out["ring_s"] / out["tree_s"], 3)
        if out["ring_s"] and out["tree_s"] else None)
    return out


def _mesh_overlap_here() -> Dict:
    """The measurement itself — requires >= 4 devices in THIS process."""
    from repro.core.sync import SyncConfig
    from repro.core.transport import MeshTransport

    cfg = SyncConfig("asgd_ga", 4, compress_topk=0.05, quantize_int8=True,
                     error_feedback=True,
                     overlap_chunks=MESH_OVERLAP["chunks"])
    mesh = MeshTransport(emulate_mbps=MESH_OVERLAP["emulate_mbps"])
    return mesh.measure_overlap(cfg, n_pods=MESH_OVERLAP["n_pods"],
                                n_elems=MESH_OVERLAP["n_elems"], reps=2)


def bench_mesh_overlap() -> Dict:
    """Measured overlap_chunks pipelining on a >= 4-virtual-device mesh.

    Multi-device CPU needs ``XLA_FLAGS=--xla_force_host_platform_device_
    count=4`` *before jax initializes* — and forcing it on the whole bench
    would perturb the training numerics every other scenario's baseline
    was recorded under (multi-device XLA compiles the same program
    slightly differently).  So when this process has one device, the
    measurement runs in a CPU subprocess with the flag appended; the rest
    of the bench stays on the single-device numerics CI replays.  A failed
    subprocess fails the bench."""
    import jax

    if jax.device_count() >= 4:
        return _mesh_overlap_here()
    import subprocess
    import sys
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ,
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4"
               .strip(),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(HERE, "..", "src"),
                    os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.autotune", "--mesh-overlap"],
        env=env, cwd=os.path.join(HERE, ".."), capture_output=True,
        text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"4-device mesh-overlap subprocess exited "
                           f"{out.returncode}:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_autotune() -> Dict:
    from repro.core.sync import SyncConfig

    report: Dict = {
        "scenario": {
            "model_mb": MODEL_MB, "compute_step_s": COMPUTE_STEP_S,
            "overlap": OVERLAP, "steps": STEPS,
            "target_loss": TARGET_LOSS, "ef_guard": EF_GUARD,
            "trace": [list(seg) for seg in TRACE_SEGMENTS],
            "tuner": {**{k: list(v) if isinstance(v, tuple) else v
                         for k, v in TUNER_KW.items()},
                      "base_sync": dict(BASE_SYNC)},
        },
        "variants": {},
    }
    for name, sync in static_variants().items():
        report["variants"][name] = run_variant(sync)
    base = SyncConfig(BASE_SYNC["strategy"], BASE_SYNC["interval"],
                      compress_topk=BASE_SYNC["compress_topk"],
                      quantize_int8=True, error_feedback=True)
    report["variants"]["adaptive"] = run_variant(base, adaptive=True)

    statics = {k: v["time_to_target_s"] for k, v in
               report["variants"].items() if k != "adaptive"}
    reached = {k: v for k, v in statics.items() if v is not None}
    best_static = min(reached, key=reached.get) if reached else None
    t_adapt = report["variants"]["adaptive"]["time_to_target_s"]
    report["best_static"] = best_static
    report["best_static_s"] = reached.get(best_static)
    report["adaptive_s"] = t_adapt
    report["speedup_vs_best_static"] = (
        round(reached[best_static] / t_adapt, 3)
        if best_static and t_adapt else None)

    # measured-feedback scenario: same controller knobs, same link, but
    # the ONLY bandwidth input is transport-billed transfer times
    report["measured"] = {
        "wan": dict(MEASURED_WAN),
        "probe": dict(MEASURED_PROBE),
        "band": MEASURED_BAND,
        "variant": run_variant(base, measured=True),
    }
    m = report["measured"]["variant"]
    report["measured"]["trace_adaptive_s"] = t_adapt
    report["measured"]["measured_s"] = m["time_to_target_s"]
    # measured feedback is sync-cadence-bound: a bandwidth cliff is
    # discovered only by PAYING one transfer on it (the trace-driven
    # baseline probes every step and reacts before paying).  The decision
    # band therefore grants exactly that structural cost — one worst-case
    # stale transfer: the base config's payload at the trace's trough —
    # on top of the ordinary percentage band.  Anything beyond it would
    # mean the control law (not the observability) degraded.
    trough = min(bw for _, bw in TRACE_SEGMENTS)
    allowance = base.payload_mb(MODEL_MB) * 8.0 / trough * (1.0 - OVERLAP)
    report["measured"]["stale_transfer_allowance_s"] = round(allowance, 2)
    report["measured"]["bound_s"] = (
        round((1.0 + MEASURED_BAND) * t_adapt + allowance, 2)
        if t_adapt is not None else None)
    report["mesh_overlap"] = bench_mesh_overlap()
    report["streaming"] = bench_streaming()
    report["topology"] = bench_topology()

    report["bucketed"] = bench_bucketed()
    b = report["bucketed"]
    sv, bv = b["variants"]["single"], b["variants"]["bucketed"]
    report["acceptance"] = {
        "adaptive_beats_best_static":
            bool(t_adapt is not None and best_static is not None
                 and t_adapt < reached[best_static]),
        "ef_guard_never_violated":
            report["variants"]["adaptive"]["max_ef_ratio"] <= EF_GUARD,
        "bucketed_time_not_worse":
            bool(b["single_s"] is not None and b["bucketed_s"] is not None
                 and b["bucketed_s"] <= b["single_s"]),
        "bucketed_wire_bytes_not_worse":
            bv["traffic_mb"] <= sv["traffic_mb"],
        "bucketed_ef_guard_never_violated":
            bv["max_ef_ratio"] <= EF_GUARD
            and sv["max_ef_ratio"] <= EF_GUARD,
        # the transport-seam acceptance: measured transfer times alone
        # land the autotuner within the decision band of the trace-driven
        # run on the same fluctuating link, guard clean
        "measured_converges_within_band":
            bool(m["time_to_target_s"] is not None
                 and report["measured"]["bound_s"] is not None
                 and m["time_to_target_s"]
                 <= report["measured"]["bound_s"]),
        "measured_ef_guard_never_violated":
            m["max_ef_ratio"] <= EF_GUARD,
    }
    st = report["streaming"]
    sv = st["variants"]
    report["acceptance"].update({
        # the chunk-granular headline: on cliffs that land mid-round, the
        # streaming retune (first-chunk feedback + tail re-encode) reaches
        # the target loss >= STREAM_SPEEDUP_MIN x sooner than the
        # once-per-round autotuner paying each cliff as one stale transfer
        "streaming_beats_round_adaptive":
            bool(st["speedup_vs_round_adaptive"] is not None
                 and st["speedup_vs_round_adaptive"] >= STREAM_SPEEDUP_MIN),
        # the mechanism actually fired — at least one mid-round retune
        # (and every round ran the streaming protocol, none declined)
        "streaming_retuned_mid_round":
            sv["streaming"]["n_stream_retunes"] >= 1
            and sv["streaming"]["n_stream_rounds"]
            == len(sv["streaming"]["stream_rounds"]),
        # the convergence contract: the EF residual absorbed every
        # mid-round fidelity drop without the guard ever tripping
        "streaming_ef_guard_never_violated":
            sv["streaming"]["max_ef_ratio"] <= EF_GUARD
            and sv["round_adaptive"]["max_ef_ratio"] <= EF_GUARD,
    })
    topo = report["topology"]
    tv = topo["variants"]
    report["acceptance"].update({
        # the third-actuator headline: on the asymmetric collapsing link,
        # the tree's shape (re-rooted around the dead link) reaches the
        # target loss sooner than the flat 3-region ring, which crosses
        # every link every round
        "topology_tree_beats_ring":
            bool(topo["tree_s"] is not None and topo["ring_s"] is not None
                 and topo["tree_s"] < topo["ring_s"]),
        # the planner discovers the same answer from measured beliefs:
        # starts on the ring, ends on the tree, and pays no more than
        # staying on the ring would have
        "topology_auto_switches_to_tree":
            tv["auto"]["final_kind"] == "tree"
            and len(tv["auto"]["switches"]) >= 1,
        "topology_auto_not_worse_than_ring":
            bool(topo["auto_s"] is not None and topo["ring_s"] is not None
                 and topo["auto_s"] <= topo["ring_s"]),
        "topology_ef_guard_never_violated":
            all(v["max_ef_ratio"] <= EF_GUARD for v in tv.values()),
        # the parity guarantee, visible in the bench itself: shape changes
        # billing only, never bytes — the fixed-shape variants (identical
        # static codec config) must end at the exact same loss
        "topology_shapes_share_numerics":
            tv["ring"]["final_loss"] == tv["tree"]["final_loss"],
    })
    report["acceptance"]["mesh_overlap_speedup_measured"] = \
        report["mesh_overlap"]["overlap_speedup"] > 1.0
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=1)
    return report


def _print_report(r: Dict) -> None:
    print(f"{'variant':22s} {'t_target_s':>10s} {'final_loss':>10s} "
          f"{'traffic_mb':>10s}")
    for name, v in r["variants"].items():
        t = v["time_to_target_s"]
        print(f"{name:22s} {t if t is not None else '--':>10} "
              f"{v['final_loss']:>10} {v['traffic_mb']:>10}")
    a = r["variants"]["adaptive"]
    print(f"adaptive: {a['n_retunes']} retunes, max_ef_ratio "
          f"{a['max_ef_ratio']} (guard {a['ef_guard']}), final "
          f"{a['final_config']}")
    print(f"speedup vs best static ({r['best_static']}): "
          f"{r['speedup_vs_best_static']}x")
    m = r["measured"]["variant"]
    print(f"\nmeasured-feedback (no trace wired to the controller): "
          f"t_target {m['time_to_target_s']}s vs trace-driven "
          f"{r['measured']['trace_adaptive_s']}s, bound "
          f"{r['measured']['bound_s']}s (band {r['measured']['band']:.0%} "
          f"+ one stale transfer "
          f"{r['measured']['stale_transfer_allowance_s']}s), "
          f"{m['n_retunes']} retunes, max_ef {m['max_ef_ratio']}, "
          f"final {m['final_config']}")
    mo = r["mesh_overlap"]
    print(f"mesh overlap ({mo['n_devices']} devices, {mo['chunks']} "
          f"chunks @ {mo['emulate_mbps']} Mbps emulated): "
          f"{mo['overlap_speedup']}x (serial {mo['t_serialized_s']}s "
          f"-> pipelined {mo['t_pipelined_s']}s)")
    st = r["streaming"]
    sv = st["variants"]["streaming"]
    rv = st["variants"]["round_adaptive"]
    print(f"\nstreaming scenario ({st['chunks']} chunks, cliffs "
          f"{[seg for seg in st['trace'] if seg[1] < 10]}):")
    print(f"  round-adaptive t_target {rv['time_to_target_s']}s  traffic "
          f"{rv['traffic_mb']} MB  retunes {rv['n_retunes']}  max_ef "
          f"{rv['max_ef_ratio']}")
    print(f"  streaming      t_target {sv['time_to_target_s']}s  traffic "
          f"{sv['traffic_mb']} MB  retunes {sv['n_retunes']}  max_ef "
          f"{sv['max_ef_ratio']}  mid-round retunes "
          f"{sv['n_stream_retunes']}/{sv['n_stream_rounds']} rounds  "
          f"chunk decisions {len(sv['stream_decisions'])}")
    print(f"  speedup vs once-per-round: {st['speedup_vs_round_adaptive']}x"
          f" (min {st['speedup_min']}x)")
    topo = r["topology"]
    print(f"\ntopology scenario ({'/'.join(topo['regions'])}, "
          f"{topo['bad_link'][0]}<->{topo['bad_link'][1]} collapses "
          f"{topo['bad_link_trace'][0][1]} -> "
          f"{topo['bad_link_trace'][-1][1]} Mbps):")
    for name in ("ring", "tree", "auto"):
        v = topo["variants"][name]
        print(f"  {name:5s} t_target {v['time_to_target_s']}s  traffic "
              f"{v['traffic_mb']} MB  final {v['final_kind']} "
              f"(legs {v['wan_transfers_per_round']})  retunes "
              f"{v['n_retunes']}  max_ef {v['max_ef_ratio']}  "
              f"switches {v['switches']}")
    print(f"  tree speedup vs ring: {topo['tree_speedup_vs_ring']}x")
    b = r["bucketed"]
    print(f"\nbucketed scenario ({b['model']}, target "
          f"{b['target_loss']}): bucket_mb "
          f"{ {n: round(v, 4) for n, v in b['bucket_mb'].items()} }")
    for name in ("single", "bucketed"):
        v = b["variants"][name]
        print(f"  {name:9s} t_target {v['time_to_target_s']}s  traffic "
              f"{v['traffic_mb']} MB  retunes {v['n_retunes']}  "
              f"max_ef {v['max_ef_ratio']}")
    bv = b["variants"]["bucketed"]
    print(f"  bucketed final rungs {bv['final_rungs']}, per-bucket max_ef "
          f"{bv['max_ef_ratio_by_bucket']}")
    print(f"  speedup vs single-bucket: {b['speedup_vs_single']}x")
    print(f"acceptance: {r['acceptance']}")


def _compare(a_path: str, b_path: str) -> None:
    with open(a_path) as f:
        a = json.load(f)
    with open(b_path) as f:
        b = json.load(f)
    print(f"{'metric':38s} {'A':>12s} {'B':>12s}")
    for key in ("best_static_s", "adaptive_s", "speedup_vs_best_static"):
        print(f"{key:38s} {a[key]!s:>12s} {b[key]!s:>12s}")
    for name in a["variants"]:
        ta = a["variants"][name]["time_to_target_s"]
        tb = b["variants"].get(name, {}).get("time_to_target_s")
        print(f"{'t_target[' + name + ']':38s} {ta!s:>12s} {tb!s:>12s}")


def main(argv: Sequence[str] = None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"),
                    help="diff two BENCH_autotune.json files instead")
    ap.add_argument("--mesh-overlap", action="store_true",
                    help="run ONLY the mesh overlap measurement and print "
                         "its JSON (used by the 4-device subprocess hop)")
    args = ap.parse_args(argv)
    if args.mesh_overlap:
        import jax
        if jax.device_count() < 4:
            raise SystemExit(f"--mesh-overlap needs >= 4 devices, have "
                             f"{jax.device_count()}")
        rep = _mesh_overlap_here()
        print(json.dumps(rep))
        return rep
    if args.compare:
        _compare(*args.compare)
        return {}
    report = bench_autotune()               # writes BENCH_autotune.json
    _print_report(report)
    print(f"wrote {os.path.relpath(OUT_PATH, os.path.join(HERE, '..'))}")
    return report


if __name__ == "__main__":
    main()
