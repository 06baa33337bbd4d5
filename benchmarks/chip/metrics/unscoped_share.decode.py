"""Share of the decode step's device time that lies under none of the
model's named scopes: the device self time of the ``jit_decode`` ops whose
op_name (for a fusion, its root's) is under no scope of ``SCOPES``, over
the self time of all ``jit_decode`` ops, in percent.  In the decode step
that is the layer scan's own work: slicing each layer's weights out of the
stacked parameters and laying them out for the matmuls.  The fetch of each
layer's K and V out of the stacked cache is not in it: the scan body reads
them under ``attention_kernel``.  None for a program that carries none of
the scopes.

``SCOPES`` is this reader's own copy of the program's table
(``models/layers.py:SCOPES``), so that a scope renamed in the program
cannot move this number unnoticed (``tests/test_scopes.py``)."""

import trace_reduce

MODULE = "jit_decode"
SCOPES = ("embed", "norm", "qkv_proj", "attention_kernel", "attn_out",
          "kv_cache_write", "mlp", "lm_head", "sample")


def read(ctx):
    names = trace_reduce.op_names(ctx.hlo[MODULE])
    scoped = {i for i, op in names.items()
              if any(f"/{s}/" in f"{op}/" for s in SCOPES)}
    if not scoped:
        return None
    ops = [o for d in ctx.trace.devices.values() for o in d.ops
           if o.module == MODULE]
    total = sum(o.self_ns for o in ops)
    if total <= 0:
        return None
    return 100.0 * sum(o.self_ns for o in ops
                       if o.instr not in scoped) / total
