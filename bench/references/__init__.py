"""The plain references, one module per architecture a configuration names.

A configuration file's ``"architecture"`` key names ``bench/references/
<architecture>.py``, which ``bench.registry.reference`` loads by path.  It
imports nothing of the program and takes nothing the program made, and
exports:

* ``layout(cfg)``: path -> shape of every weight the program holds, which
  ``bench/weights.py`` draws from the seed for both sides;
* ``served_gaps(cfg, seed, rounding, sequences, *, control, params,
  shape)``: the teacher-forced gaps of a served run, and with ``control``
  the fp8 control's (``bench.reference`` holds the shared pieces);
* ``logits(cfg, seed, rounding, tokens, params)``: reference logits, for tests;
* the work the architecture needs, counted from the file's sizes for the
  metric readers: ``gemms(cfg)`` (each paired weight's ``(K, N)`` and
  whether it carries the fused sublayer residual), ``flops_per_position
  (cfg)``, ``attention_work(cfg, context, rows)`` (one layer's) and
  ``head_flops(cfg)``.
"""
