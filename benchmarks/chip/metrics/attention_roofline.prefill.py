"""Attention's share of its roofline in the prefill program: the least
time the chip could take for the causal attention of every call in the
window (``work.prefill``: each needed (query, key) pair once; Q, K, V read
and O written once), over the device time of the ops that the compiled
HLO places under the named scope ``attention_kernel``
(``models/layers.py``), in percent.  Compute bounds it at these shapes."""

import trace_reduce

MODULE, SCOPE = "jit_prefill", "attention_kernel"


def read(ctx):
    instrs = trace_reduce.in_scope(ctx.hlo[MODULE], SCOPE)
    seconds = sum(trace_reduce.scope_seconds(d, MODULE, instrs)
                  for d in ctx.trace.devices.values())
    if not instrs or seconds <= 0:
        return None
    least = max(ctx.work["attention_flops"] / ctx.peaks["bf16_flops_per_s"],
                ctx.work["attention_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.chips / seconds
