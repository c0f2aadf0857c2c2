"""Model FLOPs of the tokens trained in the traced window over that
window's wall time, sync rounds and host gaps included, as a share of the
cell's chips' bf16 peak, in %: the whole cycle's share, which bounds what
any one kernel's gain can give."""
from chipbench import costs


def read(ctx):
    w = ctx.traced
    flops = costs.train_flops_per_token(ctx.config, ctx.program.seq,
                                        ctx.cell.reference)
    rate = flops * w.tokens / w.seconds
    return 100.0 * rate / (ctx.chips * ctx.peaks["bf16_flops"])
