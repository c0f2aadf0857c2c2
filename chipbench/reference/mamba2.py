"""Plain float32 Mamba-2 language model (arXiv:2405.21060), SSD mixer only.

Each layer is a pre-norm residual block around the Mamba-2 mixer:

    z, x, B, C, dt = split(in_proj(rmsnorm(h)))
    x, B, C       = silu(causal depthwise conv_W([x, B, C]))
    dt            = softplus(dt + dt_bias);  A = -exp(A_log)
    y_t           = sum_{s<=t} (C_t . B_s) exp(sum_{s<k<=t} A dt_k) dt_s x_s
                    + D x_t                                   (per head)
    h            += out_proj(rmsnorm_gated(y * silu(z)))

The scan is the chunked state-space-dual form of the paper's minimal
listing (``ssd_minimal_discrete``): a masked quadratic form inside each
chunk, a carried state between chunks; in exact arithmetic it equals the
per-step recurrence, which the tests check at a small size.

Departures from the published model that the system under test makes, and
this reference follows: RMSNorm gains are initialised to 1 and applied as
``1 + scale`` (an effective gain of 2 at init); the tied token embedding is multiplied by sqrt(d_model) on
input and drawn N(0, 1); the gated norm has no group split.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (cross_entropy, embed_scale, matmul,
                                        normal, rmsnorm, silu)


def sizes(model: Dict) -> Dict:
    d_in = model["ssm.expand"] * model["d_model"]
    return dict(D=model["d_model"], d_in=d_in, N=model["ssm.state_dim"],
                P=model["ssm.head_dim"], G=model["ssm.n_groups"],
                H=d_in // model["ssm.head_dim"], W=model["ssm.conv_width"],
                L=model["ssm.chunk_size"], V=model["padded_vocab"],
                n_layers=model["n_layers"], eps=model["norm_eps"])


def init(key, model: Dict) -> Dict:
    """Parameters as the system under test draws them: the same key tree,
    shapes and dtypes; layer leaves stacked on a leading layer axis."""
    s = sizes(model)
    D, d_in, N, G, H, W = s["D"], s["d_in"], s["N"], s["G"], s["H"], s["W"]
    pdt = jnp.dtype(model["param_dtype"])
    conv_ch = d_in + 2 * G * N
    ke, kb = jax.random.split(key)

    def layer(k):
        k_pos = jax.random.split(k, 1)[0]
        k_mix, _ = jax.random.split(k_pos)
        k1, k2, k3, k4 = jax.random.split(k_mix, 4)
        dt = jnp.exp(jax.random.uniform(k3, (H,), jnp.float32,
                                        math.log(0.001), math.log(0.1)))
        return {
            "ln1": {"scale": jnp.ones((D,), pdt)},
            "ssm": {
                "in_proj": normal(k1, (D, 2 * d_in + 2 * G * N + H),
                                  1.0 / math.sqrt(D), pdt),
                "conv_w": (jax.random.normal(k2, (W, conv_ch), jnp.float32)
                           / math.sqrt(W)).astype(pdt),
                "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                "dt_bias": jnp.log(jnp.expm1(dt)),
                "D_skip": jnp.ones((H,), jnp.float32),
                "gate_norm": {"scale": jnp.ones((d_in,), pdt)},
                "out_proj": normal(k4, (d_in, D), 1.0 / math.sqrt(d_in),
                                   pdt),
            },
        }

    layers = [layer(k) for k in jax.random.split(kb, s["n_layers"])]
    blocks = {"pos0": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)}
    return {"blocks": blocks,
            "embed": {"tokens": normal(ke, (s["V"], D), 1.0, pdt)},
            "final_norm": {"scale": jnp.ones((D,), pdt)}}


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """out[..., i, j] = sum_{j < k <= i} x[..., k] below the diagonal,
    -inf above it (masked cumulative sums, no differences of cumsums)."""
    T = x.shape[-1]
    xe = jnp.broadcast_to(x[..., None], x.shape + (T,))        # [..., k, j]
    xe = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xe, 0.0)
    seg = jnp.cumsum(xe, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool), 0), seg, -jnp.inf)


def ssd(x, a, Bm, Cm, chunk: int):
    """Chunked SSD.  x (b,S,h,p) already times dt; a (b,S,h) = A dt;
    Bm, Cm (b,S,h,n).  Returns y (b,S,h,p)."""
    b, S, h, p = x.shape
    L = min(chunk, S)
    c = S // L
    X = x.reshape(b, c, L, h, p)
    A = jnp.moveaxis(a.reshape(b, c, L, h), -1, 1)              # b h c l
    Bc = Bm.reshape(b, c, L, h, -1)
    Cc = Cm.reshape(b, c, L, h, -1)
    A_cum = jnp.cumsum(A, axis=-1)
    Lmat = jnp.exp(_segsum(A))                                  # b h c l s
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", Cc, Bc, Lmat, X)
    decay = jnp.exp(A_cum[..., -1:] - A_cum)                    # b h c l
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bc, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    last = jnp.pad(A_cum[..., -1], ((0, 0), (0, 0), (1, 0)))    # b h c+1
    decay_chunk = jnp.exp(_segsum(last))                        # b h z c
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)
    states = new_states[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Cc, states, jnp.exp(A_cum))
    return (y_diag + y_off).reshape(b, S, h, p)


def ssd_recurrent(x, a, Bm, Cm):
    """The per-step recurrence s_t = exp(a_t) s_{t-1} + B_t x_t^T,
    y_t = s_t C_t: slow, and the definition the chunked form must meet."""
    b, S, h, p = x.shape
    n = Bm.shape[-1]

    def step(st, t):
        xt, at, bt, ct = t
        st = st * jnp.exp(at)[..., None, None] + bt[:, :, None, :] * \
            xt[..., None]
        return st, jnp.sum(st * ct[:, :, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, a, Bm, Cm))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1)


def _causal_conv(seq: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution over time: out[t] = sum_i w[i] *
    seq[t - (W - 1) + i], zero history."""
    W = w.shape[0]
    padded = jnp.pad(seq, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(padded[:, i:i + seq.shape[1]] * w[i] for i in range(W))


def mixer(p: Dict, s: Dict, x: jnp.ndarray, precision: str) -> jnp.ndarray:
    d_in, N, G, H, P = s["d_in"], s["N"], s["G"], s["H"], s["P"]
    b, S, _ = x.shape
    zxbcdt = matmul(x, p["in_proj"], precision)
    z, xs, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + G * N, 2 * d_in + 2 * G * N],
        axis=-1)
    conv = silu(_causal_conv(jnp.concatenate([xs, Bc, Cc], axis=-1),
                             p["conv_w"]))
    xs, Bc, Cc = jnp.split(conv, [d_in, d_in + G * N], axis=-1)
    xs = xs.reshape(b, S, H, P)
    # head h reads the B/C of group h // (H // G)
    Bh = jnp.repeat(Bc.reshape(b, S, G, N), H // G, axis=2)
    Ch = jnp.repeat(Cc.reshape(b, S, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"]) * dt
    y = ssd(xs * dt[..., None], a, Bh, Ch, s["L"])
    y = y + xs * p["D_skip"][:, None]
    y = y.reshape(b, S, d_in) * silu(z)
    y = rmsnorm(p["gate_norm"]["scale"], y, s["eps"])
    return matmul(y, p["out_proj"], precision)


def loss(params: Dict, model: Dict, batch: Dict, precision: str):
    """Token-mean cross-entropy of one pod's batch; params in float32."""
    s = sizes(model)
    h = params["embed"]["tokens"][batch["tokens"]] * embed_scale(s["D"])

    def block(h, p):
        return h + mixer(p["ssm"], s, rmsnorm(p["ln1"]["scale"], h,
                                              s["eps"]), precision)

    block = jax.checkpoint(block)
    for i in range(s["n_layers"]):
        h = block(h, jax.tree.map(lambda v: v[i], params["blocks"]["pos0"]))
    h = rmsnorm(params["final_norm"]["scale"], h, s["eps"])
    logits = matmul(h, params["embed"]["tokens"].T, precision)
    return cross_entropy(logits, batch["labels"], batch["mask"])
