"""Serving launcher: geo-routed continuous batching over replica slot pools.

The serving counterpart of ``repro.launch.train``: builds one slot-pool
engine per regional replica (all replicas share the same parameters), a
:class:`~repro.serving.router.GeoRouter` that places each request by
measured link beliefs + catalog cost/latency, and — with ``--autoscale``
— a :class:`~repro.core.control_plane.ServingElasticityController` that
sizes the replica count from the offered load before the engines are
built (on TPU the serving control plane, like the training one, runs at
plan time).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
      --scheduler continuous --slots 4 --prompt-len 32 --new-tokens 16
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b \
      --replicas 3 --router balanced --requests 12
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_arch
from repro.core.control_plane import CloudEvent, ServingElasticityController
from repro.launch.cache import enable_compile_cache
from repro.models.registry import get_model_fns
from repro.serving.engine import (BatchScheduler, ContinuousEngine,
                                  ContinuousScheduler, ServingEngine)
from repro.serving.router import GeoRouter, ReplicaSpec, ROUTER_MODES

# replica regions are assigned from this palette in order
REGIONS = ("us-east", "eu-west", "ap-south", "us-west", "eu-north",
           "ap-north", "sa-east", "af-south")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--scheduler", default="continuous",
                    choices=["batch", "continuous"],
                    help="'continuous': slot-pool engine with per-slot "
                         "insert/evict (prefill->insert->generate); "
                         "'batch': run-to-completion baseline — a group "
                         "decodes until every member finishes before the "
                         "next group is admitted")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool width per replica (continuous): max "
                         "requests decoding concurrently in one engine")
    ap.add_argument("--batch", type=int, default=4,
                    help="group size for the run-to-completion baseline "
                         "(--scheduler batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--router", default="balanced", choices=ROUTER_MODES,
                    help="placement objective: 'nearest' (network seconds "
                         "on measured link beliefs), 'cheapest' (catalog "
                         "$/token), 'balanced' (network + queue + compute "
                         "seconds)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="regional replicas serving the same parameters "
                         "(with --autoscale: the replica-count ceiling)")
    ap.add_argument("--autoscale", action="store_true",
                    help="size the replica count from the offered load "
                         "via the ServingElasticityController (scale-up "
                         "immediate, scale-down after hysteresis) instead "
                         "of taking --replicas literally")
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.config
    fns = get_model_fns(arch.module)
    params = fns.init_params(jax.random.key(0), cfg)
    cache_len = args.prompt_len + args.new_tokens

    # ------------------------------------------------- replica scaling
    n_replicas, autoscale_reason = args.replicas, None
    if args.autoscale:
        ctrl = ServingElasticityController(
            replicas=1, max_replicas=max(1, args.replicas))
        # offered load: the whole request burst over one observation window
        d = ctrl.handle(CloudEvent("load_changed", time_s=0.0,
                                   rps=args.requests / 10.0))
        n_replicas, autoscale_reason = ctrl.replicas, d.reason
    regions = REGIONS[:n_replicas]

    router = GeoRouter([ReplicaSpec(region=r, n_slots=args.slots)
                        for r in regions], mode=args.router)
    if args.scheduler == "continuous":
        scheds = {r: ContinuousScheduler(ContinuousEngine(
            arch, params, n_slots=args.slots, cache_len=cache_len,
            use_smoke=args.smoke)) for r in regions}
    else:
        scheds = {r: BatchScheduler(
            ServingEngine(arch, params, cache_len=cache_len,
                          use_smoke=args.smoke),
            batch_size=args.batch) for r in regions}

    # ------------------------------------------------- route + submit
    rng = np.random.default_rng(0)
    placed = {}                      # global rid -> (region, local rid)
    for rid in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        src = regions[int(rng.integers(len(regions)))]
        region = router.route(rid, src, plen, args.new_tokens)
        placed[rid] = (region, scheds[region].submit(prompt,
                                                     args.new_tokens))

    t0 = time.time()
    by_region = {r: s.run() for r, s in scheds.items()}
    dt = time.time() - t0
    results = {}
    for rid, (region, local) in placed.items():
        results[rid] = by_region[region][local]
        router.complete(rid)

    total_new = sum(len(v) for v in results.values())
    print(json.dumps({
        "arch": args.arch, "scheduler": args.scheduler,
        "router": args.router, "replicas": list(regions),
        "autoscale": autoscale_reason,
        "requests": len(results), "new_tokens": total_new,
        "routes": {r: sum(1 for reg, _ in placed.values() if reg == r)
                   for r in regions},
        "wall_s": round(dt, 2),
        "tok_per_s": round(total_new / dt, 1),
    }, indent=1))
    for rid, toks in sorted(results.items())[:3]:
        print(f"req {rid}: {np.asarray(toks)[:12].tolist()} ...")
    return results


if __name__ == "__main__":
    main()
