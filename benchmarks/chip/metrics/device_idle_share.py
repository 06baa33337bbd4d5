"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window, in percent."""

import trace_reduce


def read(ctx):
    idle = trace_reduce.idle_share(ctx.trace)
    return None if idle is None else 100.0 * idle
