"""The program adapters, one module per architecture a configuration names.

A configuration file's ``"architecture"`` key names ``bench/programs/
<architecture>.py``, which ``bench.registry.program`` loads by path.  It
maps the file onto the system under test (it may import ``repro``) and
exports:

* ``program_config(cfg)``: the program's ``ModelConfig``, raising where the
  file asks for equations the program does not run;
* ``params(pcfg, cfg, seed)``: the weights in the program's tree, drawn
  from the seed by ``bench/weights.py`` (``bench/run.py`` checks the tree
  against the reference's ``layout``);
* ``pair_counts(params)``: ``{weight: (P[L], R[L])}``, each layer's pair
  and residual lane counts of each paired weight of the served tree.
"""
