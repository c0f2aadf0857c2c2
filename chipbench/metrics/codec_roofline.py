"""The WAN codec kernels' share of their roofline, in %: the least time
their bytes and operations need at the chip's peaks (``costs.codec_round``;
the bytes bound binds) over their device time, per round and chip."""
from chipbench import costs, trace

KERNELS = r"wan_(en|de)code_pallas"


def read(ctx):
    per_dev = trace.op_time_ns(ctx.trace, KERNELS)
    if not per_dev:
        return None
    sync = ctx.traffic["sync"]
    pods_per_chip = ctx.program.pods // ctx.chips
    need = costs.codec_round(ctx.program.params_per_pod, pods_per_chip,
                             sync["codec_block"], sync["compress_topk"])
    least = max(need["bytes"] / ctx.peaks["hbm_bytes_per_s"],
                need["ops"] / ctx.peaks["bf16_flops"])
    kernel_s = sum(per_dev.values()) / len(per_dev) / ctx.traced.rounds / 1e9
    return 100.0 * least / kernel_s
