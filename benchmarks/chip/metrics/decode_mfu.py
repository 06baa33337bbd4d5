"""Model FLOPs of every decode step in the window (``work.decode_step``)
over the window's seconds, the chips and their bf16 peak, in percent."""


def read(ctx):
    return 100.0 * ctx.work["flops"] / (
        ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
