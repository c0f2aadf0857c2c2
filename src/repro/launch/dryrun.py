import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512"
                           ).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for 2 pods x 256 v5e chips.
For each combination the step function is jit-compiled with explicit
in/out shardings; we record

  - ``compiled.memory_analysis()``   (per-device bytes — proves it fits)
  - ``compiled.cost_analysis()``     (FLOPs / bytes for the roofline)
  - collective bytes parsed from the partitioned HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute), split
    into intra-pod vs cross-pod by replica-group membership

into ``experiments/dryrun/<arch>__<shape>__<mesh>[__tag].json``, which
``benchmarks/roofline.py`` and EXPERIMENTS.md consume.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
      --shape train_4k --mesh multi_pod
  PYTHONPATH=src python -m repro.launch.dryrun --all        # full sweep
"""
import argparse
import json
import math
import re
import time
import traceback
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, Arch, get_arch
from repro.core.sync import SyncConfig
from repro.launch import context as C
from repro.launch.mesh import make_production_mesh, mesh_info
from repro.launch.shapes import (INPUT_SHAPES, InputShape, decode_specs,
                                 prefill_specs, shape_supported,
                                 train_batch_specs)
from repro.models.registry import get_model_fns
from repro.sharding.rules import spec_tree_for_params

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO result type (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _crosses_pod(line: str, pod_boundary: int) -> Optional[bool]:
    """Best-effort: does this collective's replica group span pods?
    Device ids < pod_boundary are pod 0 (mesh is row-major, pod slowest)."""
    m = re.search(r"replica_groups=\{\{([0-9,{} ]*)\}\}", line)
    if m:
        first = m.group(1).split("}")[0]
        ids = [int(x) for x in first.replace("{", "").split(",") if x.strip()]
        return any(i >= pod_boundary for i in ids) and any(
            i < pod_boundary for i in ids)
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]", line)
    if m:
        groups, per_group, total = map(int, m.groups())
        if "T(" not in line:
            # contiguous iota groups: group 0 = ids [0, per_group)
            return per_group > pod_boundary
        return None   # transposed iota: undetermined
    return None


def parse_collectives(hlo: str, n_pods: int, n_devices: int) -> Dict:
    """Sum operand/result bytes per collective kind from partitioned HLO."""
    pod_boundary = n_devices // max(n_pods, 1)
    out = {k: 0 for k in _COLLECTIVES}
    cross = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    unknown_cross = 0
    for line in hlo.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
                     r"([a-z\-]+)", ls)
        if not m:
            continue
        op = m.group(2)
        if op.endswith("-done"):
            continue   # counted at the -start (async pair)
        kind = op[:-6] if op.endswith("-start") else op
        if kind not in _COLLECTIVES:
            continue
        nbytes = _shape_bytes(m.group(1))
        out[kind] += nbytes
        counts[kind] += 1
        if n_pods > 1:
            c = _crosses_pod(ls, pod_boundary)
            if c is True:
                cross[kind] += nbytes
            elif c is None:
                unknown_cross += nbytes
    return {
        "bytes_by_kind": out,
        "counts_by_kind": counts,
        "total_bytes": sum(out.values()),
        "cross_pod_bytes": sum(cross.values()),
        "cross_pod_unknown_bytes": unknown_cross,
    }


def _memory_analysis_dict(compiled) -> Dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                                    # pragma: no cover
        return {"error": str(e)}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def _cost_analysis_dict(compiled) -> Dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:                                    # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")}


# ---------------------------------------------------------------------------
# lowering builders
# ---------------------------------------------------------------------------


def lower_train(arch: Arch, shape: InputShape, mesh: Mesh, *,
                sync: SyncConfig, optimizer: str,
                config_overrides: Optional[dict] = None):
    setup = C.make_train_setup(arch, mesh, sync=sync, optimizer=optimizer,
                               config_overrides=config_overrides)
    info = mesh_info(mesh)
    bspecs = train_batch_specs(arch, shape, info["n_pods"])
    bshard = C.batch_sharding(bspecs, mesh, setup.rules, stacked=True)

    from repro.sharding.rules import axis_rules
    step = setup.trainer._train_step_impl
    with axis_rules(setup.rules, mesh):
        jf = jax.jit(step, in_shardings=(setup.state_sharding, bshard),
                     out_shardings=(setup.state_sharding, None))
        lowered = jf.lower(setup.abstract_state, bspecs)

    # the sync step (the paper's WAN round) lowered separately
    with axis_rules(setup.rules, mesh):
        js = jax.jit(setup.trainer._sync_step_impl,
                     in_shardings=(setup.state_sharding,),
                     out_shardings=setup.state_sharding)
        sync_lowered = js.lower(setup.abstract_state)
    return lowered, sync_lowered, setup


def lower_prefill(arch: Arch, shape: InputShape, mesh: Mesh):
    cfg = arch.config
    fns = get_model_fns(arch.module)
    rules = C.serve_rules()
    from repro.sharding.rules import axis_rules

    pspecs = prefill_specs(arch, shape)
    pshard = C.batch_sharding(pspecs, mesh, rules, stacked=False)
    param_axes = fns.param_logical_axes(cfg)
    abstract_params = fns.abstract_params(cfg)
    pspec_tree = spec_tree_for_params(param_axes, abstract_params, rules, mesh)
    psharding = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                             is_leaf=lambda x: isinstance(x, P))

    if arch.module == "encdec":
        # enc-dec prefill == encode + build cross/self caches; lower forward
        def fn(params, batch):
            from repro.models import encdec
            logits, _ = encdec.forward(params, cfg, batch["tokens"],
                                       batch["audio_emb"])
            return logits
    else:
        def fn(params, batch):
            return fns.prefill(params, cfg, batch["tokens"], shape.seq_len,
                               positions=batch.get("positions"),
                               patch_emb=batch.get("patch_emb"))

    with axis_rules(rules, mesh):
        jf = jax.jit(fn, in_shardings=(psharding, pshard))
        return jf.lower(abstract_params, pspecs), None, None


def lower_decode(arch: Arch, shape: InputShape, mesh: Mesh):
    cfg = arch.config
    fns = get_model_fns(arch.module)
    rules = C.serve_rules()
    from repro.sharding.rules import axis_rules

    dspecs = decode_specs(arch, shape)
    abstract_params = fns.abstract_params(cfg)
    param_axes = fns.param_logical_axes(cfg)

    def abstract_cache():
        if arch.module == "encdec":
            from repro.models import encdec
            return jax.eval_shape(
                lambda: encdec.init_cache(cfg, shape.global_batch,
                                          shape.seq_len))
        return jax.eval_shape(
            lambda: fns.init_cache(cfg, shape.global_batch, shape.seq_len))

    cache = abstract_cache()
    cache_axes = fns.cache_logical_axes(cfg, shape.seq_len)
    cache_specs = spec_tree_for_params(cache_axes, cache, rules, mesh)
    cache_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs,
                               is_leaf=lambda x: isinstance(x, P))
    pspec_tree = spec_tree_for_params(param_axes, abstract_params, rules, mesh)
    psharding = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                             is_leaf=lambda x: isinstance(x, P))
    tshard = C.batch_sharding(dspecs, mesh, rules, stacked=False)

    def fn(params, token, cache, cache_pos):
        return fns.decode_step(params, cfg, token, cache, cache_pos)

    with axis_rules(rules, mesh):
        jf = jax.jit(fn, in_shardings=(psharding, tshard["token"],
                                       cache_shard, tshard["cache_pos"]),
                     out_shardings=(None, cache_shard))
        lowered = jf.lower(abstract_params, dspecs["token"], cache,
                           dspecs["cache_pos"])
    return lowered, None, None


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _lower_for(arch: Arch, shape: InputShape, mesh: Mesh, *,
               sync: SyncConfig, optimizer: str,
               config_overrides: Optional[dict]):
    if shape.kind == "train":
        return lower_train(arch, shape, mesh, sync=sync, optimizer=optimizer,
                           config_overrides=config_overrides)
    # serve paths read the (possibly overridden) config off a shallow copy
    if config_overrides:
        arch = Arch(name=arch.name,
                    config=arch.config.replace(**config_overrides),
                    smoke=arch.smoke, module=arch.module)
    if shape.kind == "prefill":
        return lower_prefill(arch, shape, mesh)
    return lower_decode(arch, shape, mesh)


def _extrapolate_costs(arch: Arch, shape: InputShape, mesh: Mesh, *,
                       sync: SyncConfig, optimizer: str,
                       base_overrides: Optional[dict]) -> Dict:
    """XLA-CPU cost_analysis counts while-loop (scan) bodies ONCE.  Compile
    python-unrolled 1-group and 2-group variants; per-group cost = c2 - c1,
    total = (c1 - body) + n_groups * body.  Exact because the stack is
    group-homogeneous."""
    cfg = arch.config
    if base_overrides:
        cfg = cfg.replace(**base_overrides)
    period, n_groups = cfg.period, cfg.n_groups
    info = mesh_info(mesh)

    def one(n_layers: int) -> Dict:
        ov = dict(base_overrides or {})
        ov.update({"n_layers": n_layers, "scan_layers": False})
        lowered, _, _ = _lower_for(arch, shape, mesh, sync=sync,
                                   optimizer=optimizer, config_overrides=ov)
        compiled = lowered.compile()
        cost = _cost_analysis_dict(compiled)
        coll = parse_collectives(compiled.as_text(), info["n_pods"],
                                 info["n_devices"])
        return {"flops": cost.get("flops", 0.0),
                "bytes": cost.get("bytes accessed", 0.0),
                "collective_bytes": float(coll["total_bytes"]),
                "cross_pod_bytes": float(coll["cross_pod_bytes"]),
                "bytes_by_kind": coll["bytes_by_kind"]}

    c1 = one(period)
    c2 = one(2 * period)

    def combine(k1, k2):
        body = max(k2 - k1, 0.0)
        fixed = max(k1 - body, 0.0)
        return fixed + n_groups * body

    out = {k: combine(c1[k], c2[k]) for k in
           ("flops", "bytes", "collective_bytes", "cross_pod_bytes")}
    out["bytes_by_kind"] = {
        k: combine(float(c1["bytes_by_kind"][k]), float(c2["bytes_by_kind"][k]))
        for k in c1["bytes_by_kind"]}
    out["one_group"] = c1
    out["two_group"] = c2
    out["n_groups"] = n_groups
    return out


def run_one(arch_name: str, shape_name: str, mesh_kind: str, *,
            sync_strategy: str = "ama", sync_interval: int = 8,
            sync_compress: float = 0.0,
            optimizer: str = "sgd", tag: str = "",
            config_overrides: Optional[dict] = None,
            out_dir: Optional[str] = None,
            extrapolate: bool = True) -> Dict:
    arch = get_arch(arch_name)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    info = mesh_info(mesh)

    ok, reason = shape_supported(arch, shape_name)
    rec: Dict = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
        "mesh_info": info, "tag": tag,
        "params": arch.config.param_count(),
        "active_params": arch.config.active_param_count(),
        "sync": {"strategy": sync_strategy, "interval": sync_interval,
                 "compress_topk": sync_compress},
        "optimizer": optimizer,
        "config_overrides": config_overrides or {},
        "tokens": (shape.global_batch * shape.seq_len
                   if shape.kind != "decode" else shape.global_batch),
    }
    if not ok:
        rec["status"] = "skipped"
        rec["skip_reason"] = reason
        _write(rec, out_dir)
        return rec

    t0 = time.time()
    sync = SyncConfig(sync_strategy, sync_interval,
                      compress_topk=sync_compress)
    try:
        lowered, sync_lowered, _ = _lower_for(
            arch, shape, mesh, sync=sync, optimizer=optimizer,
            config_overrides=config_overrides)
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo, info["n_pods"],
                                               info["n_devices"])
        rec["memory"] = _memory_analysis_dict(compiled)
        rec["cost"] = _cost_analysis_dict(compiled)
        rec["status"] = "ok"

        if sync_lowered is not None:
            cs = sync_lowered.compile()
            rec["sync_step"] = {
                "collectives": parse_collectives(cs.as_text(), info["n_pods"],
                                                 info["n_devices"]),
                "cost": _cost_analysis_dict(cs),
                "memory": _memory_analysis_dict(cs),
            }

        if extrapolate:
            t2 = time.time()
            rec["extrapolated"] = _extrapolate_costs(
                arch, shape, mesh, sync=sync, optimizer=optimizer,
                base_overrides=config_overrides)
            rec["extrapolate_s"] = round(time.time() - t2, 2)
    except Exception as e:                                    # pragma: no cover
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    _write(rec, out_dir)
    return rec


def _write(rec: Dict, out_dir: Optional[str] = None) -> None:
    d = os.path.abspath(out_dir or OUT_DIR)
    os.makedirs(d, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    path = os.path.join(
        d, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] {rec['arch']} {rec['shape']} {rec['mesh']} "
          f"-> {rec['status']} ({rec.get('total_s', 0)}s)", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=["single_pod", "multi_pod"],
                    default="single_pod")
    ap.add_argument("--all", action="store_true",
                    help="full sweep: every arch x shape x both meshes")
    ap.add_argument("--sync", default="ama")
    ap.add_argument("--interval", type=int, default=8)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        jobs = [(a, s, m) for a in ARCH_IDS for s in INPUT_SHAPES
                for m in ("single_pod", "multi_pod")]
    else:
        assert args.arch and args.shape
        jobs = [(args.arch, args.shape, args.mesh)]

    for a, s, m in jobs:
        if args.skip_existing:
            tag = f"__{args.tag}" if args.tag else ""
            p = os.path.join(os.path.abspath(args.out_dir or OUT_DIR),
                             f"{a}__{s}__{m}{tag}.json")
            if os.path.exists(p):
                with open(p) as f:
                    if json.load(f).get("status") in ("ok", "skipped"):
                        continue
        run_one(a, s, m, sync_strategy=args.sync,
                sync_interval=args.interval, optimizer=args.optimizer,
                tag=args.tag, out_dir=args.out_dir)


if __name__ == "__main__":
    main()
