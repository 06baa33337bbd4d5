"""Attention's share of its roofline in the decode step: the least time
the chip could take for the attention of every step in the window
(``work.decode_step``: each query against the live keys only, Q and O of
the new position and K, V of the live keys read once), over the device
self time of the ``jit_decode`` ops that the compiled HLO places under the
named scope ``attention_kernel`` (``models/layers.py``), in percent.  A
fusion belongs to the scope of its root instruction.  HBM bounds it at
these shapes."""

import trace_reduce

MODULE, SCOPE = "jit_decode", "attention_kernel"


def read(ctx):
    instrs = trace_reduce.in_scope(ctx.hlo[MODULE], SCOPE)
    seconds = sum(trace_reduce.scope_seconds(d, MODULE, instrs)
                  for d in ctx.trace.devices.values())
    if not instrs or seconds <= 0:
        return None
    least = max(ctx.work["attention_flops"] / ctx.peaks["bf16_flops_per_s"],
                ctx.work["attention_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.chips / seconds
