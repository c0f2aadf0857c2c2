"""Plain float32 decoder with grouped-query attention and a SwiGLU MLP.

The Llama-style block of granite-8b (arXiv:2405.04324), untied head:

    q, k, v = rope(h W_q), rope(h W_k), h W_v      (h = rmsnorm(x))
    x      += softmax(q k^T / sqrt(d_head) + causal) v W_o
               (query head i reads key/value head i // (n_heads / n_kv))
    x      += (silu(h W_g) * (h W_u)) W_d          (h = rmsnorm(x))
    logits  = rmsnorm(x) W_head

RoPE rotates the two halves of each head, frequency theta^(-i / (d/2)).

Departures from the published model that the system under test makes, and
this reference follows: RMSNorm gains are initialised to 1 and applied as
``1 + scale`` (an effective gain of 2 at init); the token embedding is drawn
N(0, 1).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (HIGHEST, cross_entropy, matmul,
                                        normal, rmsnorm, silu)


def sizes(model: Dict) -> Dict:
    return dict(D=model["d_model"], H=model["n_heads"], K=model["n_kv_heads"],
                Dh=model["resolved_head_dim"], F=model["d_ff"],
                V=model["padded_vocab"], n_layers=model["n_layers"],
                eps=model["norm_eps"], theta=model["rope_theta"])


def flops_per_token(model: Dict, seq: int) -> float:
    """Forward FLOPs of one token through one layer (``chipbench/reference``):
    the four attention projections, causal attention over ``seq``
    positions, and the three SwiGLU products."""
    D, H, K, Dh, F = (model["d_model"], model["n_heads"],
                      model["n_kv_heads"], model["resolved_head_dim"],
                      model["d_ff"])
    proj = 2 * D * (H * Dh + 2 * K * Dh) + 2 * H * Dh * D
    attn = 2 * 2 * H * Dh * (seq / 2)          # causal QK^T and PV
    mlp = 3 * 2 * D * F
    return proj + attn + mlp


def init(key, model: Dict) -> Dict:
    """Parameters as the system under test draws them: the same key tree,
    shapes and dtypes; layer leaves stacked on a leading layer axis."""
    s = sizes(model)
    D, H, K, Dh, F = s["D"], s["H"], s["K"], s["Dh"], s["F"]
    pdt = jnp.dtype(model["param_dtype"])
    ke, kb = jax.random.split(key)

    def layer(k):
        k_pos = jax.random.split(k, 1)[0]
        k_attn, k_mlp = jax.random.split(k_pos)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        kg, ku, kd = jax.random.split(k_mlp, 3)
        return {
            "ln1": {"scale": jnp.ones((D,), pdt)},
            "attn": {"wq": normal(kq, (D, H * Dh), 1 / math.sqrt(D), pdt),
                     "wk": normal(kk, (D, K * Dh), 1 / math.sqrt(D), pdt),
                     "wv": normal(kv, (D, K * Dh), 1 / math.sqrt(D), pdt),
                     "wo": normal(ko, (H * Dh, D), 1 / math.sqrt(H * Dh),
                                  pdt)},
            "ln2": {"scale": jnp.ones((D,), pdt)},
            "mlp": {"wg": normal(kg, (D, F), 1 / math.sqrt(D), pdt),
                    "wu": normal(ku, (D, F), 1 / math.sqrt(D), pdt),
                    "wd": normal(kd, (F, D), 1 / math.sqrt(F), pdt)},
        }

    layers = [layer(k) for k in jax.random.split(kb, s["n_layers"])]
    blocks = {"pos0": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)}
    embed = {"tokens": normal(ke, (s["V"], D), 1.0, pdt),
             "lm_head": normal(jax.random.fold_in(ke, 1), (D, s["V"]),
                               1 / math.sqrt(D), pdt)}
    return {"blocks": blocks, "embed": embed,
            "final_norm": {"scale": jnp.ones((D,), pdt)}}


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (b, S, heads, d): rotate (first half, second half) pairs."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v):
    """One sequence: q (S, H, d), k and v (S, K, d) -> (S, H, d)."""
    S, H, d = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    scores = jnp.einsum("qhd,shd->hqs", q, k, precision=HIGHEST) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v,
                      precision=HIGHEST)


def block(x: jnp.ndarray, p: Dict, s: Dict, precision: str) -> jnp.ndarray:
    b, S, D = x.shape
    H, K, Dh = s["H"], s["K"], s["Dh"]
    h = rmsnorm(p["ln1"]["scale"], x, s["eps"])
    q = rope(matmul(h, p["attn"]["wq"], precision).reshape(b, S, H, Dh),
             s["theta"])
    k = rope(matmul(h, p["attn"]["wk"], precision).reshape(b, S, K, Dh),
             s["theta"])
    v = matmul(h, p["attn"]["wv"], precision).reshape(b, S, K, Dh)
    # one sequence at a time, recomputed in the backward pass: the score
    # matrices of a whole batch would not fit beside the weights
    o = jax.lax.map(jax.checkpoint(lambda t: _attend(*t)), (q, k, v))
    x = x + matmul(o.reshape(b, S, H * Dh), p["attn"]["wo"], precision)
    h = rmsnorm(p["ln2"]["scale"], x, s["eps"])
    g = silu(matmul(h, p["mlp"]["wg"], precision))
    u = matmul(h, p["mlp"]["wu"], precision)
    return x + matmul(g * u, p["mlp"]["wd"], precision)


def loss(params: Dict, model: Dict, batch: Dict, precision: str):
    """Token-mean cross-entropy of one pod's batch; params in float32."""
    s = sizes(model)
    x = params["embed"]["tokens"][batch["tokens"]]
    blk = jax.checkpoint(lambda x, p: block(x, p, s, precision))
    for i in range(s["n_layers"]):
        x = blk(x, jax.tree.map(lambda v: v[i], params["blocks"]["pos0"]))
    x = rmsnorm(params["final_norm"]["scale"], x, s["eps"])
    logits = matmul(x, params["embed"]["lm_head"], precision)
    return cross_entropy(logits, batch["labels"], batch["mask"])
