"""Run one benchmark cell once on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the cell's trainer and state from the seed, drives the first
steps through the same calls the window makes (``Trainer.train_step``,
``Trainer.maybe_sync``, the loss read), keeping what the correctness check
needs, and warms up until a whole sync cycle compiles nothing.  Each step's
batch is handed to the device while the step before it runs, as an input
pipeline would.  The window then trains for ``--seconds`` (rounded up to
whole cycles) and ends in ``block_until_ready`` on the state.  Afterwards the peak device memory is
read, the program's state is freed, and the plain reference replays the
first steps from the same seed and batches; ``correct`` is the comparison
of the two (``chipbench/check.py``) against the cell's limits.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the first cycles of the window with the JAX profiler and reports its
per-layer metrics.  The last line of standard output is one JSON object.
Exits non-zero, printing no result, on any platform but a TPU, on fewer
chips than the cell asks for, and where the program is absent.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import check, data, spec  # noqa: E402
from chipbench import trace as tracing  # noqa: E402

CACHE_DIR = ROOT / "chipbench" / ".jax_cache"
TRACE_DIR = ROOT / "chipbench" / ".trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
MAX_WARM_CYCLES = 20


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so that only a cell's first run compiles.  Its
    size is left unbounded whatever the environment sets: a bounded cache
    keeps an access-time file per entry, and one entry without it makes
    every later write fail, so that each run compiles anew."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerator(chips: int):
    """The first ``chips`` TPU devices; exits where there are fewer."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: found platform {devs[0].platform!r}; the "
                 f"benchmark measures a TPU")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell asks for {chips} chips, found "
                 f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts new executables (compiled or loaded from the cache) while
    armed, from JAX's own compile event."""

    def __init__(self):
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE and self.armed:
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


def key_for(seed: int):
    """A PRNG key for any non-negative seed, 32 bits at a time."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def has_ef(sync: dict) -> bool:
    """Whether a traffic's sync settings keep an error-feedback residual."""
    return sync["strategy"] == "asgd_ga" and bool(sync.get("error_feedback"))


def check_steps(sync: dict) -> int:
    """Steps the correctness check follows: at least three, and through
    the first sync round; with error feedback through the second round and
    one step past it, so that the residual the first round leaves is
    carried into a message and decoded."""
    if has_ef(sync):
        return 2 * sync["interval"] + 1
    return max(3, sync["interval"])


def _pod_norms(a, b, scale):
    d = a.astype(jnp.float32)
    if b is not None:
        d = d - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(d).reshape(d.shape[0], -1),
                            axis=1)) * scale


_pod_norms_jit = jax.jit(_pod_norms)


def leaf_norms(tree, minus=None, scale: float = 1.0) -> np.ndarray:
    """(pods, leaves) norms of a pod-stacked host tree (or of its
    difference with ``minus``), computed on the device leaf by leaf."""
    a = jax.tree.leaves(tree)
    b = jax.tree.leaves(minus) if minus is not None else [None] * len(a)
    cols = [np.asarray(_pod_norms_jit(jnp.asarray(x), None if y is None
                                      else jnp.asarray(y), scale))
            for x, y in zip(a, b)]
    return np.stack(cols, axis=1)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices, *, root: Path = ROOT, t0: float = None,
             fault=None, program=None, keep: bool = False) -> dict:
    """One run of one cell on ``devices``.

    ``fault`` (tests only) receives the built program before the first
    step and may break it.  ``program`` reuses a built ``Program`` across
    runs in one process, and ``keep`` returns both sides' records under
    ``records``; ``chipbench/calibrate.py`` uses the two to read many seeds
    in one process."""
    from chipbench.program import Program
    from chipbench.reference import train as reference

    t0 = T0 if t0 is None else t0
    cell = spec.load_cell(workload, root)
    conf, traf = cell.config, cell.traffic
    prog = program if program is not None else Program(conf, traf, devices)
    if fault is not None:
        fault(prog)
    tr, sync = prog.trainer, prog.sync
    K = sync.interval
    ef = has_ef(traf["sync"])
    pool = data.batch_pool(seed, int(traf["pool"]), prog.pods, prog.rows,
                           prog.seq, conf["model"]["vocab_size"])
    counter = CompileCounter()

    def put(step):
        with jax.profiler.TraceAnnotation("bench.batch"):
            return prog.put(pool[step % len(pool)])

    def train(state, batch):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            return tr.train_step(state, batch)

    def sync_(state, step):
        with jax.profiler.TraceAnnotation("bench.maybe_sync"):
            return tr.maybe_sync(state, step)

    def read_loss(m):
        with jax.profiler.TraceAnnotation("bench.loss_read"):
            return float(m["loss"])

    # ------------------------------------ set-up, checked steps, warm-up
    # the first n_check steps are the ones the reference replays; the
    # warm-up goes on until a whole sync cycle has compiled nothing
    n_check = check_steps(traf["sync"])
    if len(pool) < n_check:
        raise ValueError("the batch pool is smaller than the checked steps")
    with prog.context():
        state = prog.init_state(key_for(seed))
        p0 = jax.device_get(state.params)
        losses_check, compiles = [], []
        counter.armed = True
        step = 0
        batch = put(step)
        while True:
            before = counter.count
            state, m = train(state, batch)
            if step == 0:
                g1_host = jax.device_get(
                    state.sync_state.ga_buffer if sync.strategy == "asgd_ga"
                    else state.params)
            state = sync_(state, step)
            batch = put(step + 1)
            read_loss(m)
            if step < n_check:
                losses_check.append(np.asarray(m["loss_per_pod"]))
            if step == n_check - 1:
                pn = jax.device_get(state.params)
                resid = (jax.device_get(state.sync_state.ef_residual)
                         if ef else None)
            compiles.append(counter.count - before)
            step += 1
            if step % K == 0 and step >= n_check and \
                    not any(compiles[step - K:step]):
                break
            if step > n_check + MAX_WARM_CYCLES * K:
                raise RuntimeError("the sync cycle still compiles after "
                                   f"{MAX_WARM_CYCLES} cycles")
        jax.block_until_ready(state)
        warm_steps = step
        setup_s = time.perf_counter() - t0

        # ------------------------------------------------------------ window
        trace_steps = int(traf["trace_steps"]) if trace else 0
        if trace:
            if trace_steps % K:
                raise ValueError("trace_steps must be whole sync cycles")
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            TRACE_DIR.mkdir(parents=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        counter.count = 0
        times, losses = [], []
        # the set-up's garbage is collected now, and what survives it is
        # left out of the collector's scans inside the window
        gc.collect()
        gc.freeze()
        t_start = time.perf_counter()
        while True:
            state, m = train(state, batch)
            state = sync_(state, step)
            batch = put(step + 1)
            losses.append(read_loss(m))
            times.append(time.perf_counter())
            step += 1
            n = step - warm_steps
            if trace and n == trace_steps:
                with jax.profiler.TraceAnnotation("bench.block"):
                    jax.block_until_ready(state)
                jax.profiler.stop_trace()
            if times[-1] - t_start >= seconds and n % K == 0 and \
                    n >= trace_steps:
                break
        jax.block_until_ready(state)
        t_end = time.perf_counter()
        gc.unfreeze()
        ms = np.diff([t_start] + times) * 1e3
        log(f"set-up {setup_s:.1f} s ({warm_steps} steps); window "
            f"{t_end - t_start:.1f} s, {len(ms)} steps (the step-time "
            f"sample): median {np.median(ms):.1f} ms, max {ms.max():.1f} "
            f"ms, first {np.round(ms[:6], 1).tolist()}")
        counter.close()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    del state, m, batch
    gc.collect()

    # ------------------------------------------------------------ check
    n_window = step - warm_steps
    window = SimpleNamespace(
        seconds=t_end - t_start, steps=n_window, compiles=counter.count,
        rounds=sum(1 for s in range(warm_steps, step)
                   if (s + 1) % K == 0),
        step_s=list(np.diff([t_start] + times)),
        tokens=n_window * prog.pods * prog.rows * prog.seq)
    lr = float(conf["lr"])
    prog_rec = {
        "losses": np.stack(losses_check).astype(np.float64),
        "grad1": (leaf_norms(g1_host) if sync.strategy == "asgd_ga"
                  else leaf_norms(p0, minus=g1_host, scale=1.0 / lr)),
        "change": leaf_norms(pn, minus=p0),
        "leaves": reference.leaf_names(jax.tree.map(lambda x: x[0], p0)),
    }
    if ef:
        prog_rec["resid"] = leaf_norms(prog.residual_tree(resid))
    del p0, pn, g1_host, resid
    gc.collect()
    ref_rec = reference.train(conf["model"], conf["reference"],
                              traf["sync"], lr, key_for(seed),
                              pool[:n_check], devices=devices)
    numbers = check.compare(prog_rec, ref_rec)
    log(f"check {time.perf_counter() - t_end:.1f} s after the window")
    finite = bool(np.all(np.isfinite(losses)))
    correct, checks = check.judge(numbers, cell.limits)
    correct = correct and finite

    # ------------------------------------------------------------ metrics
    kind = "per_layer" if trace else "end_to_end"
    dev = devices[0]
    ctx = SimpleNamespace(
        cell=cell, config=conf, traffic=traf, chips=len(devices),
        program=prog, setup_s=setup_s, window=window, peak_bytes=peak,
        peaks=spec.load_peaks(dev.device_kind, root), trace=None,
        traced=None)
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        ctx.trace = tracing.load(str(TRACE_DIR))
        lo, hi = ctx.trace.window()
        ctx.traced = SimpleNamespace(
            steps=trace_steps, rounds=trace_steps // K,
            tokens=trace_steps * prog.pods * prog.rows * prog.seq,
            lo=lo, hi=hi, seconds=(hi - lo) / 1e9)
        busy = [tracing.busy_ns(ctx.trace, d, lo, hi) / 1e9
                for d in ctx.trace.devices]
        info["busy_s"] = float(np.mean(busy)) if busy else 0.0
        info["window_s"] = ctx.traced.seconds
        breakdown = summarize(ctx.trace, lo, hi)
    metrics = {}
    for mt in cell.metrics_of(kind):
        value = mt.reader(ctx)
        if value is not None:
            metrics[mt.name] = {"value": float(value), "unit": mt.unit}
    result = {"correct": correct, "attempted": n_window,
              "failed": int(np.sum(~np.isfinite(losses))),
              "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["readings"] = {k: numbers[k] for k in check.NUMBERS
                          if k in numbers}
    result["where"] = numbers["where"]
    if keep:
        result["records"] = {"program": prog_rec, "reference": ref_rec}
    result["checks"] = checks
    return result


SPAN_LABELS = {"bench.batch": "batch hand-off",
               "bench.train_step": "train_step dispatch",
               "bench.maybe_sync": "maybe_sync", "bench.loss_read":
               "loss read", "bench.block": "final block"}


def summarize(tr, lo, hi, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps labelled by the harness span they fall in."""
    per_op: dict = {}
    for op in tr.ops:
        per_op[op.name] = per_op.get(op.name, 0.0) + op.dur / 1e9
    n_dev = max(1, len(tr.devices))
    ops = sorted(((k, v / n_dev) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = []
    for d in tr.devices:
        for s, t in tracing.idle_gaps(tr, d, lo, hi):
            label = SPAN_LABELS.get(tracing.span_at(tr, (s + t) / 2),
                                    "outside harness calls")
            gaps.append((f"{label} (chip {d})", (t - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        sys.exit("chipbench: --seed must be a non-negative integer")
    cell = spec.load_cell(args.workload)
    try:
        from chipbench.program import import_program
        import_program(ROOT)
    except ImportError as e:
        sys.exit(f"chipbench: the program is not in this checkout ({e})")
    devices = accelerator(cell.chips)
    enable_cache()
    res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices)
    w = res.pop("where")
    log(f"readings {res['readings']} (worst leaves {w})")
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
