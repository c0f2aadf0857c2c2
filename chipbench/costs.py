"""Operations and bytes the work needs, computed from shapes.

- ``train_flops_per_token``: model FLOPs of one training token (forward and
  backward, three times the forward), no recomputation counted.  Each
  layer's forward is counted by the configuration's reference module
  (``flops_per_token``, ``chipbench/reference``), beside the math it
  counts; the head here.  Products count 2 operations per multiply-add.
- ``codec_round``: what one ASGD-GA codec round's kernels must read and
  write (one encode and two decodes per pod: the sender's own
  reconstruction for error feedback, and the peer's message), and the
  element operations of block top-k selection and quantisation.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict


def train_flops_per_token(config: Dict, seq: int,
                          reference: ModuleType) -> float:
    """``reference``: the configuration's reference module
    (``spec.load_reference``, a cell's ``reference``)."""
    m = config["model"]
    layer = reference.flops_per_token(m, seq)
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
