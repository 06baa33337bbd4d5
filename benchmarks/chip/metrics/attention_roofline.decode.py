"""Attention's share of its roofline in the decode step: the least time
the chip could take for the attention of every step in the window
(``work.decode_step``: each query against the live keys only, Q and O of
the new position and K, V of the live keys read once), over the device
self time of the ``jit_decode`` ops that the compiled HLO places under the
named scope ``attention_kernel`` (``models/layers.py``), in percent.  A
fusion belongs to the scope of its root instruction.  Under the scope lie
the fetch of each layer's K and V out of the stacked cache, its relayout
into on-chip memory, and the two dots, which then read on-chip memory, not
HBM.  The floor is the live KV's bytes at the HBM peak: at these shapes
the bytes bound the least time, not the FLOPs."""

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
