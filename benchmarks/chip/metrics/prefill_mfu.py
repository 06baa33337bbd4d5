"""Model FLOPs of every prefill call in the window (``work.prefill``:
causal attention counted once) over the window's seconds, the chips and
their bf16 peak, in percent."""


def read(ctx):
    return 100.0 * ctx.work["flops"] / (
        ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
