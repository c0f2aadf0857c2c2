"""Performance hillclimbing driver (EXPERIMENTS.md §Perf).

Hypothesis -> change -> re-lower -> re-analyse cycles on the three chosen
(arch x shape) pairs.  Each variant is a tagged dry-run record
(``experiments/dryrun/<arch>__<shape>__<mesh>__<tag>.json``); this script
runs the variants and prints the roofline-term deltas vs the baseline.

Variants (the "change" column of the §Perf log):
  chunked   attention_impl=xla_chunked — flash-style blockwise attention in
            XLA; kills the O(S^2) fp32 score buffers  (memory/bytes term)
  onehot    embed_impl=onehot — vocab-sharded one-hot matmul embedding;
            avoids SPMD's involuntary full rematerialization of the gathered
            embedding table  (collective term)
  dots      remat=dots — keep matmul outputs, recompute elementwise only
            (compute term, at activation-memory cost)
  both      chunked + onehot
  cap10     MoE capacity_factor 1.25 -> 1.0 (drops overflow tokens;
            all-to-all and expert-compute term)
  syncN     multi-pod only: sync strategy sweep on the pod axis —
            asgd@1 (baseline per-step all-reduce) vs ama@8 vs asgd_ga@8 vs
            asgd_ga@8 + top-k 1% compression (the paper's technique + the
            beyond-paper compressor; measured on the sync_step record)

Usage:
  PYTHONPATH=src python -m benchmarks.hillclimb --pair gemma3-12b:train_4k \
      --variants chunked,onehot,both
  PYTHONPATH=src python -m benchmarks.hillclimb --sync-sweep kimi-k2-1t-a32b
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512"
                           ).strip()

import argparse
import json
from typing import Dict, Optional

from repro.models.config import MoEConfig

VARIANTS: Dict[str, Dict] = {
    "chunked": {"attention_impl": "xla_chunked"},
    "onehot": {"embed_impl": "onehot"},
    "both": {"attention_impl": "xla_chunked", "embed_impl": "onehot"},
    "dots": {"remat": "dots"},
    "chunked_dots": {"attention_impl": "xla_chunked", "remat": "dots"},
    "best": {"attention_impl": "xla_chunked", "embed_impl": "onehot",
             "remat": "dots"},
    "grouped": {"moe_dispatch": "grouped"},
    "grouped_onehot": {"moe_dispatch": "grouped", "embed_impl": "onehot"},
    "grouped_ff": {"moe_dispatch": "grouped", "moe_param_shard": "ff"},
    "moeff": {"moe_param_shard": "ff"},
    "moeff_onehot": {"moe_param_shard": "ff", "embed_impl": "onehot"},
    "all3": {"moe_param_shard": "ff", "embed_impl": "onehot",
             "attention_impl": "xla_chunked"},
}


def _term_summary(rec: Dict) -> Dict:
    from benchmarks.roofline import analyze_record
    row = analyze_record(rec)
    if row is None:
        return {"status": rec.get("status"), "error": rec.get("error", "")[:300]}
    return {"compute_s": row.compute_s, "memory_s": row.memory_s,
            "collective_s": row.collective_s, "dominant": row.dominant,
            "useful_ratio": row.useful_ratio}


def run_pair(arch: str, shape: str, variants, mesh: str = "single_pod"):
    from repro.launch.dryrun import run_one

    base_path = f"experiments/dryrun/{arch}__{shape}__{mesh}.json"
    if os.path.exists(base_path):
        base = json.load(open(base_path))
    else:
        base = run_one(arch, shape, mesh)
    print(f"baseline: {json.dumps(_term_summary(base))}")

    results = {"baseline": _term_summary(base)}
    for name in variants:
        ov = dict(VARIANTS[name])
        if name == "cap10":
            cfg_moe = None  # handled below with a real MoEConfig
        rec = run_one(arch, shape, mesh, tag=name, config_overrides=ov)
        results[name] = _term_summary(rec)
        print(f"{name}: {json.dumps(results[name])}")
    return results


def run_moe_capacity(arch: str, shape: str, mesh: str = "single_pod"):
    from repro.configs import get_arch
    from repro.launch.dryrun import run_one
    cfg = get_arch(arch).config
    ov = {"moe": MoEConfig(num_experts=cfg.moe.num_experts,
                           top_k=cfg.moe.top_k, capacity_factor=1.0)}
    rec = run_one(arch, shape, mesh, tag="cap10", config_overrides=ov)
    print(f"cap10: {json.dumps(_term_summary(rec))}")
    return rec


def run_sync_sweep(arch: str, shape: str = "train_4k"):
    """The paper's own experiment at dry-run level: inter-pod bytes per
    training step under each strategy (multi-pod mesh)."""
    from repro.launch.dryrun import run_one

    out = {}
    settings = [("asgd", 1, 0.0), ("ama", 8, 0.0), ("asgd_ga", 8, 0.0),
                ("asgd_ga", 8, 0.01)]
    for strat, k, topk in settings:
        tag = f"sync_{strat}{k}" + (f"_top{topk}" if topk else "")
        rec = run_one(arch, shape, "multi_pod", sync_strategy=strat,
                      sync_interval=k, sync_compress=topk, tag=tag,
                      extrapolate=False, config_overrides=None)
        if rec["status"] != "ok":
            out[tag] = {"status": rec["status"],
                        "error": rec.get("error", "")[:200]}
            print(tag, json.dumps(out[tag]))
            continue
        # the sync_step program touches ONLY the pod axis (roll/mean over the
        # stacked dim), so its collective total per device IS the inter-pod
        # traffic per sync round; the asgd baseline instead syncs inside
        # every train step (grads pmean over pod)
        step_total = rec["collectives"]["total_bytes"]
        sync_rec = rec.get("sync_step", {})
        sync_total = sync_rec.get("collectives", {}).get("total_bytes", 0)
        out[tag] = {"train_step_collective_B_per_dev": step_total,
                    "sync_round_B_per_dev": sync_total,
                    "amortized_sync_B_per_dev_step": sync_total / k,
                    "status": "ok"}
        print(tag, json.dumps(out[tag]))
    with open(f"experiments/bench/sync_sweep_{arch}.json", "w") as f:
        json.dump(out, f, indent=1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", help="arch:shape")
    ap.add_argument("--variants", default="chunked,onehot,both")
    ap.add_argument("--mesh", default="single_pod")
    ap.add_argument("--moe-capacity", help="arch:shape")
    ap.add_argument("--sync-sweep", help="arch")
    args = ap.parse_args()
    if args.pair:
        arch, shape = args.pair.split(":")
        run_pair(arch, shape, args.variants.split(","), args.mesh)
    if args.moe_capacity:
        arch, shape = args.moe_capacity.split(":")
        run_moe_capacity(arch, shape)
    if args.sync_sweep:
        run_sync_sweep(args.sync_sweep)


if __name__ == "__main__":
    main()
