"""Operations and bytes the work needs, computed from shapes.

- ``train_flops_per_token``: model FLOPs of one training token (forward and
  backward, three times the forward), no recomputation counted.  Products
  count 2 operations per multiply-add.  Attention and the SSD scan count
  the causal half of their quadratic terms.
- ``codec_round``: what one ASGD-GA codec round's kernels must read and
  write (one encode and two decodes per pod: the sender's own
  reconstruction for error feedback, and the peer's message), and the
  element operations of block top-k selection and quantisation.
"""
from __future__ import annotations

from typing import Dict


def _ssm_forward(m: Dict) -> float:
    D = m["d_model"]
    d_in = m["ssm.expand"] * D
    N, P, G = m["ssm.state_dim"], m["ssm.head_dim"], m["ssm.n_groups"]
    H, W, L = d_in // P, m["ssm.conv_width"], m["ssm.chunk_size"]
    proj = 2 * D * (2 * d_in + 2 * G * N + H) + 2 * d_in * D
    conv = 2 * W * (d_in + 2 * G * N)
    # per token: C.B scores over half a chunk per group; per head the
    # masked score-value product, the chunk-state write and its read-out
    scan = G * 2 * N * (L / 2) + H * (2 * P * (L / 2) + 4 * P * N)
    return proj + conv + scan


def _attn_mlp_forward(m: Dict, seq: int) -> float:
    D, H, K, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["resolved_head_dim"], m["d_ff"])
    proj = 2 * D * (H * Dh + 2 * K * Dh) + 2 * H * Dh * D
    attn = 2 * 2 * H * Dh * (seq / 2)          # causal QK^T and PV
    mlp = 3 * 2 * D * F
    return proj + attn + mlp


def train_flops_per_token(config: Dict, seq: int) -> float:
    m = config["model"]
    if config["reference"] == "mamba2":
        layer = _ssm_forward(m)
    elif config["reference"] == "gqa_swiglu":
        layer = _attn_mlp_forward(m, seq)
    else:
        raise KeyError(f"no FLOP count for {config['reference']!r}")
    head = 2 * m["d_model"] * m["vocab_size"]
    return 3.0 * (m["n_layers"] * layer + head)


def codec_round(n: int, pods: int, block: int, frac: float) -> Dict:
    """Bytes and operations of one round's codec kernels over ``pods`` pods
    whose flat message has ``n`` float32 elements.  Blocks are padded to
    whole sets of 8 rows, as the kernels tile them; indices are int32 in
    device memory."""
    block = min(block, n)
    k = max(1, min(block, int(round(block * frac))))
    nb = -(-n // block)
    nb_pad = -(-nb // 8) * 8
    dense = nb_pad * block * 4
    payload = nb_pad * (k * (1 + 4) + 4)
    encode_bytes = dense + payload
    decode_bytes = payload + dense
    # selection: a 16-round threshold search over the block (compare and
    # count), then value gather and quantisation of the k winners
    encode_ops = nb * block * (2 * 16 + 4) + nb * k * 4
    decode_ops = nb * k * 2
    return {"bytes": pods * (encode_bytes + 2 * decode_bytes),
            "ops": pods * (encode_ops + 2 * decode_ops)}
