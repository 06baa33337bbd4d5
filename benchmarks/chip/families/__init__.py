"""Model families: one file per family, ``families/<family>.py``, named by
the ``"family"`` key of a configuration file (``configs/<config>.json``)
and loaded by path (``cell.family``).  Nothing else in the harness names a
family, so a configuration of a new architecture comes as new files:
its configuration and, where the family is new, the family's file.

Every family provides these five, where ``config`` is the configuration
file's object and ``s`` what ``sizes`` made of it:

- ``sizes(config) -> dict``: the sizes under the family's own plain names,
  every value hashable (the reference's compiled programs take the dict as
  a static argument), with ``"family"`` among them: ``config["family"]``.
- ``arch_config(config, name)``: the program's ``ArchConfig`` for the
  model at the configuration's depth, the only function here that imports
  the program.
- ``row_logits(s, key, seqs, quant=None)``: the plain reference.  For each
  ``(tokens, rows)`` of ``seqs``, the float32 logits (len(rows), vocab) at
  those rows, computed under ``jax.default_matmul_precision("highest")``;
  ``quant="fp8"`` is the control, every matrix product's operands rounded
  to float8 e4m3.  It imports nothing of the program and takes nothing the
  program made: it makes the weights from ``key`` by the program's own
  recipe (the same draws from the same splits of the key, stored in the
  configuration's type) and never reads the program's arrays.
- ``witness(params, key, s) -> float``: the largest gap between weights of
  the program's ``params`` and the ones the reference makes from ``key``
  (0 when the recipe agrees); ``control.py --witness`` reads it.
- ``prefill(s, batch, prompt)`` and ``decode_step(s, batch, pos)``: the
  work of one prefill call and one decode step, a dict of ``flops``,
  ``bytes``, ``attention_flops`` and ``attention_bytes``, counted from the
  shapes as ``work.py`` says.  Where a count depends on routing, the
  family counts the least a step could move (of an expert layer, never
  more than the weights of ``min(experts held, batch * top_k)`` experts),
  so that no roofline share or peak share reads over 100% because of
  routing.
"""
