"""Plain geo-distributed training: pods, SGD, and the two sync strategies.

Every pod holds its own copy of the model (one leading pod axis on every
leaf, all pods starting from the same draw) and trains on its own batches.
After step ``s`` (0-based) with ``(s + 1) % interval == 0`` the pods sync:

- ``asgd_ga`` (gradient accumulation): each pod sums its float32 gradients
  between rounds; at a round its message is that sum over the steps since
  the last round, plus its error-feedback residual; the message goes
  through the block top-k int8 codec (``codec.py``) to the next pod on the
  ring (pod ``p`` receives from pod ``p - shift``), which applies the
  decoded message as one more SGD step; the sender keeps what the codec
  lost as its new residual.
- ``ama`` (model averaging): each pod replaces its parameters by the mean
  of its own and its ring predecessor's.

An SGD step computes in float32 and stores the result in the parameter's
own dtype.  The model's loss and gradients come from the architecture
module named by the configuration, in the precision asked for.

``fault`` plants one of the faults the correctness check must catch, in
the reference put in the program's place: ``"half_batch"`` takes the mean
over the first half of each pod's rows only, ``"no_exchange"`` leaves the
peer's contribution out of every round, ``"ef_dropped"`` leaves the
residual of one round out of the next round's message.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import codec, gqa_swiglu, mamba2

ARCHITECTURES = {"mamba2": mamba2, "gqa_swiglu": gqa_swiglu}
FAULTS = ("half_batch", "no_exchange", "ef_dropped")


def faults_for(sync: Dict) -> Tuple[str, ...]:
    """The faults that a run under these sync settings can have."""
    ef = sync["strategy"] == "asgd_ga" and sync.get("error_feedback")
    return tuple(f for f in FAULTS if ef or f != "ef_dropped")


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree) -> jnp.ndarray:
    """(n_leaves,) float32 L2 norms of one pod's tree."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def diff_norms(a, b) -> jnp.ndarray:
    """(n_leaves,) norms of a - b, computed in float32."""
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def _sgd(p, g, lr):
    return jax.tree.map(
        lambda w, d: (w.astype(jnp.float32) - lr * d).astype(w.dtype), p, g)


def _flat(tree) -> jnp.ndarray:
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


def _unflat(flat: jnp.ndarray, like):
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for x in leaves:
        out.append(flat[off:off + x.size].reshape(x.shape))
        off += x.size
    return jax.tree.unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _programs(arch: str, model_items: Tuple, sync_items: Tuple, lr: float,
              precision: str, fault: Optional[str]):
    """The jitted pieces of one reference run, built once per setting."""
    mod = ARCHITECTURES[arch]
    model, sync = dict(model_items), dict(sync_items)
    ga_scale = lr * sync.get("ga_lr_scale", 1.0)

    def pod_loss(p, b):
        if fault == "half_batch":
            b = jax.tree.map(lambda v: v[:v.shape[0] // 2], b)
        pf = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return jax.value_and_grad(
            lambda q: mod.loss(q, model, b, precision))(pf)

    def encode_round(gsum, resid, steps):
        flat = _flat(gsum) / steps
        if sync["error_feedback"] and fault != "ef_dropped":
            flat = flat + resid
        block = min(sync["codec_block"], flat.shape[0])
        k = codec.k_per_block(block, sync["compress_topk"])
        local = codec.round_trip(flat, k, block)
        return local, (flat - local if sync["error_feedback"] else resid)

    def apply_peer(p, peer):
        return _sgd(p, _unflat(peer, p), ga_scale)

    def average(a, b):
        return jax.tree.map(lambda x, y: ((x.astype(jnp.float32)
                                           + y.astype(jnp.float32)) * 0.5
                                          ).astype(x.dtype), a, b)

    return (jax.jit(pod_loss), jax.jit(_sgd),
            jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b)),
            jax.jit(encode_round), jax.jit(apply_peer), jax.jit(average),
            jax.jit(lambda k: mod.init(k, model)))


def train(model: Dict, arch: str, sync: Dict, lr: float, key,
          batches: List[Dict[str, np.ndarray]], precision: str = "fp32",
          fault: Optional[str] = None, devices=None) -> Dict:
    """Run ``len(batches)`` steps from the seed's initial weights.

    ``batches[i][name]`` is ``(n_pods, rows, seq)``.  Returns per-pod
    losses of every step, per-pod per-leaf norms of the first step's
    gradient as the optimizer receives it (``grad1``: the accumulated
    float32 gradient under ``asgd_ga``, the applied update over the
    learning rate otherwise), of the float32 gradient itself
    (``grad1_f32``), of each parameter's change after the last step
    and any round that follows it (``change``), and under error feedback
    of each leaf's part of the residual the last round left (``resid``).  Pod ``p`` is computed on
    ``devices[p % len(devices)]`` (default: the first device), one pod at a
    time on each device, so only one pod's activations are live there."""
    if fault is not None and fault not in faults_for(sync):
        raise ValueError(f"fault {fault!r} does not apply; known: "
                         f"{faults_for(sync)}")
    strategy = sync["strategy"]
    if strategy not in ("asgd_ga", "ama"):
        raise ValueError(f"no reference for sync strategy {strategy!r}")
    if strategy == "asgd_ga" and not sync.get("quantize_int8"):
        raise ValueError("the asgd_ga reference ships through the codec")
    n_pods = batches[0]["tokens"].shape[0]
    shift = sync.get("peer_shift", 1)
    exchange = fault != "no_exchange"

    (grad_fn, sgd, add, encode_round, apply_peer, average,
     init) = _programs(arch, tuple(sorted(model.items())),
                       tuple(sorted(sync.items())), lr, precision, fault)
    devices = list(devices or jax.devices()[:1])
    dev = [devices[p % len(devices)] for p in range(n_pods)]

    def to(p, x):
        return jax.device_put(x, dev[p])

    with jax.default_matmul_precision("highest"):
        p0 = init(key)
        params = [to(p, p0) for p in range(n_pods)]
        gsum: List = [None] * n_pods
        resid: List = [None] * n_pods
        steps_since = 0
        losses, out = [], {}
        for step, host_batch in enumerate(batches):
            row_losses = []
            for p in range(n_pods):
                bp = to(p, {k: v[p] for k, v in host_batch.items()})
                loss, g = grad_fn(params[p], bp)
                row_losses.append(loss)
                new = sgd(params[p], g, lr)
                if step == 0:
                    out.setdefault("grad1_f32", []).append(
                        np.asarray(leaf_norms(g)))
                    out.setdefault("grad1", []).append(
                        out["grad1_f32"][-1] if strategy == "asgd_ga" else
                        np.asarray(diff_norms(params[p], new)) / lr)
                params[p] = new
                if strategy == "asgd_ga":
                    gsum[p] = g if gsum[p] is None else add(gsum[p], g)
                    if resid[p] is None:
                        resid[p] = to(p, jnp.zeros((sum(
                            x.size for x in jax.tree.leaves(new)),),
                            jnp.float32))
                del g
            losses.append([float(x) for x in row_losses])
            steps_since += 1
            if (step + 1) % sync["interval"] == 0:
                if strategy == "asgd_ga":
                    local = []
                    for p in range(n_pods):
                        lp, resid[p] = encode_round(
                            gsum[p], resid[p], jnp.float32(steps_since))
                        local.append(lp)
                        gsum[p] = None
                    if exchange:
                        # pod p receives the message of pod p - shift
                        params = [apply_peer(params[p], to(
                            p, local[(p - shift) % n_pods]))
                            for p in range(n_pods)]
                    del local
                elif exchange:
                    params = [average(params[p], to(
                        p, params[(p - shift) % n_pods]))
                        for p in range(n_pods)]
                steps_since = 0
        out["change"] = [np.asarray(diff_norms(params[p], to(p, p0)))
                         for p in range(n_pods)]
        if strategy == "asgd_ga" and sync["error_feedback"]:
            out["resid"] = [np.asarray(leaf_norms(_unflat(resid[p], p0)))
                            for p in range(n_pods)]
    out = {k: np.stack(v) for k, v in out.items()}
    out["losses"] = np.asarray(losses, np.float64)
    out["leaves"] = leaf_names(p0)
    return out
