"""Bytes every decode step in the window must move (``work.decode_step``:
the weights once, the live KV positions only) over the window's seconds,
the chips and their HBM bandwidth, in percent."""


def read(ctx):
    return 100.0 * ctx.work["bytes"] / (
        ctx.window_s * ctx.chips * ctx.peaks["hbm_bytes_per_s"])
