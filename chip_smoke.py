"""Chip smoke: the geo-training main path, once, on a TPU, at full width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: pods are chips

One chip: ``repro.launch.train.main`` trains two full-width mamba2-1.3b
pods (4 of its 48 layers, published widths) for 6 steps under ASGD-GA
with the fused WAN codec (top-k 0.02, int8, error feedback) every 2 steps:
three codec sync rounds.  Then the compiled codec kernels are checked bit
for bit against the ``kernels/ref.py`` oracles at n = 2^24, every tier.

Four chips (``--four-chips``, that path only): the pod-sharded train step
and codec sync round on a (pod=4, data=1, model=1) mesh, against the
one-chip stacked emulation of the same four pods (2 layers, float32).

Exits non-zero on any platform but ``tpu`` and on any failed check.  The
last line of stdout is ``{"ok": true, "device": {...}}``.  Everything runs
in this one process: a chip belongs to one process at a time.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.sync import SyncConfig, is_sync_step  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.wan_codec import (DEFAULT_BLOCK, VALUE_DTYPES,  # noqa: E402
                                     k_per_block, wan_decode_pallas,
                                     wan_encode_pallas)
from repro.launch import train  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

SEED = 0
TOPK = 0.02
TRAIN_ARGS = ["--arch", "mamba2-1.3b", "--layers", "4", "--pods", "2",
              "--batch", "4", "--seq", "2048", "--steps", "6",
              "--sync", "asgd_ga", "--interval", "2",
              "--compress-topk", str(TOPK), "--int8", "--error-feedback",
              "--log-every", "1"]
CODEC_N = 1 << 24
# sharded vs emulated losses: the 5e-4 of
# test_multi_device_matches_single_device_emulation, set at smoke losses
# near 6; at full width the random-init loss is in the thousands, where
# fp32 values lie 2.4e-4 apart, so the bound widens to 16 ulps there
LOSS_ATOL, LOSS_RTOL = 5e-4, 2e-6


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
        sys.exit(1)


def custom_calls(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def device_info() -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"devices: platform {info['platform']}, kind {info['kind']}, "
        f"count {info['count']}")
    check(info["platform"] == "tpu",
          f"found platform {info['platform']!r}; this smoke needs a TPU")
    return info


def peak_bytes() -> int:
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    log(f"peak_bytes_in_use (device 0): {peak}")
    return peak


# ---------------------------------------------------------------- one chip


def train_phase() -> None:
    seen = {}

    def on_finish(trainer, state, losses):
        seen["losses"] = list(losses)
        seen["kernels"] = custom_calls(trainer.sync_step_hlo(state))
        seen["rounds"] = sum(is_sync_step(trainer.cfg.sync, s)
                             for s in range(len(losses)))

    summary = train.main(TRAIN_ARGS, on_finish=on_finish)
    losses = seen["losses"]
    log(f"{summary['model']} x{summary['layers']} layers, "
        f"{summary['pods']} pods, {seen['rounds']} codec sync rounds, "
        f"losses {losses}")
    check(len(losses) == 6 and bool(np.all(np.isfinite(losses))),
          f"expected 6 finite losses, got {losses}")
    check(seen["rounds"] == 3, f"expected 3 sync rounds, got {seen['rounds']}")
    log(f"compiled sync step: {seen['kernels']} tpu_custom_call(s)")
    check(seen["kernels"] > 0, "the sync step holds no Pallas kernel")


def codec_phase() -> None:
    """Compiled encode/decode vs the oracles, bit for bit, per tier."""
    kb = k_per_block(DEFAULT_BLOCK, TOPK)
    x = jax.random.normal(jax.random.key(SEED), (CODEC_N,), jnp.float32)
    ref_enc = jax.jit(ref.wan_encode,
                      static_argnames=("k_block", "block", "value_dtype"))
    ref_dec = jax.jit(ref.wan_decode,
                      static_argnames=("n", "block", "value_dtype"))
    for tier in VALUE_DTYPES:
        enc = wan_encode_pallas.lower(x, kb, value_dtype=tier).compile()
        check(custom_calls(enc.as_text()) > 0,
              f"{tier} encode compiled without its Pallas kernel")
        got = enc(x)
        want = ref_enc(x, k_block=kb, value_dtype=tier)
        dec = wan_decode_pallas.lower(*got, CODEC_N,
                                      value_dtype=tier).compile()
        check(custom_calls(dec.as_text()) > 0,
              f"{tier} decode compiled without its Pallas kernel")
        pairs = list(zip(("q", "idx", "scales"), got, want)) + [
            ("decode", dec(*got),
             ref_dec(*want, n=CODEC_N, value_dtype=tier))]
        bad = {name: int(np.sum(np.asarray(a) != np.asarray(b)))
               for name, a, b in pairs}
        log(f"codec {tier} n=2^24 block {DEFAULT_BLOCK} k {kb}: "
            f"mismatches vs oracle {bad}")
        check(all(a.shape == b.shape and a.dtype == b.dtype
                  for _, a, b in pairs), f"{tier}: shape/dtype differs")
        check(not any(bad.values()), f"{tier}: kernel != oracle")


# -------------------------------------------------------------- four chips


def four_chip_phase() -> None:
    """Pods are chips: the pod-sharded step and codec round vs the one-chip
    stacked emulation of the same four pods."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.context import make_train_setup
    from repro.launch.mesh import make_pod_mesh
    from repro.sharding.rules import axis_rules

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = make_pod_mesh(devs)
    sync = SyncConfig("asgd_ga", 2, compress_topk=TOPK, quantize_int8=True,
                      error_feedback=True)
    # float32 so that the two compilations agree to a few fp32 spacings;
    # the widths stay published, 2 layers let 4 stacked pods fit one chip
    setup = make_train_setup(
        get_arch("mamba2-1.3b"), mesh, sync=sync, optimizer="sgd", lr=0.02,
        config_overrides={"n_layers": 2, "param_dtype": "float32",
                          "compute_dtype": "float32"})
    tr, vocab = setup.trainer, setup.cfg.vocab_size
    rng = np.random.default_rng(SEED)
    batches = [{"tokens": rng.integers(0, vocab, (4, 1, 2048), np.int32),
                "labels": rng.integers(0, vocab, (4, 1, 2048), np.int32),
                "mask": np.ones((4, 1, 2048), np.float32)}
               for _ in range(4)]

    def run(step_fn, sync_fn, st):
        losses = []
        for i, b in enumerate(batches):
            st, m = step_fn(st, b)
            losses.append(float(m["loss"]))
            if is_sync_step(sync, i):
                st = sync_fn(st)
        return st, losses

    bshard = NamedSharding(mesh, P("pod"))
    with axis_rules(setup.rules, mesh):
        jf = jax.jit(tr._train_step_impl,
                     in_shardings=(setup.state_sharding, bshard),
                     out_shardings=(setup.state_sharding, None),
                     donate_argnums=0)
        js = jax.jit(tr._sync_step_impl, in_shardings=(setup.state_sharding,),
                     out_shardings=setup.state_sharding, donate_argnums=0)
        st = jax.jit(tr.init_state, out_shardings=setup.state_sharding
                     )(jax.random.key(SEED))
        hlo = js.lower(st).compile().as_text()
        st, sharded = run(jf, js, st)

    permutes = hlo.count("collective-permute")
    gathers = hlo.count("all-gather")
    log(f"sharded sync step: {permutes} collective-permute, {gathers} "
        f"all-gather, {custom_calls(hlo)} tpu_custom_call(s)")
    check(permutes > 0, "the pod ring did not lower to a collective-permute")
    check(gathers == 0, "the sync step all-gathers across pods")
    stacked = [x for x in jax.tree.leaves(st) if x.ndim and x.shape[0] == 4]
    total = sum(x.nbytes for x in stacked)
    held = {d.id: 0 for d in devs}
    for x in stacked:
        for shard in x.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    log(f"stacked state {total} B; per device {held}")
    check(all(4 * b == total for b in held.values()),
          "each device must hold a quarter of the stacked state")
    del st
    peak_bytes()

    st = jax.jit(tr.init_state)(jax.random.key(SEED))     # one chip
    _, emulated = run(jax.jit(tr._train_step_impl, donate_argnums=0),
                      jax.jit(tr._sync_step_impl, donate_argnums=0), st)
    diff = float(np.max(np.abs(np.array(sharded) - np.array(emulated))))
    bound = max(LOSS_ATOL, LOSS_RTOL * float(np.max(np.abs(emulated))))
    log(f"losses sharded {sharded}")
    log(f"losses emulated {emulated}; max |diff| {diff} (bound {bound})")
    check(bool(np.all(np.isfinite(sharded + emulated))), "non-finite loss")
    check(diff <= bound, f"sharded vs emulated losses differ by {diff}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pod-sharded path on 4 chips and the "
                         "one-chip emulation it is compared with")
    args = ap.parse_args(argv)
    info = device_info()
    enable_compile_cache()
    if args.four_chips:
        four_chip_phase()
    else:
        train_phase()
        codec_phase()
    peak_bytes()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
