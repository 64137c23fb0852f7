"""On-chip benchmark of the serving platform.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
model configuration, one family of architectures, one traffic mix, one
cell's limits or one per-layer metric is a file of its own, found by name:

- ``bench/configs/<config>.json``: the configuration as it is run;
- ``bench/archs/<arch>.py``: the code of the family that a configuration
  file names in ``arch``: ``build(spec)`` (the served ``ModelConfig``),
  ``sizes_of(spec)`` (a hashable value the harness only hands back),
  ``param_count(sizes)``, ``make_params(cfg, seed, index=0)``,
  ``reference_logits(sizes, params, tokens, quant=None)`` and
  ``score_step(sizes, batch, seq)``;
- ``bench/traffic/<mix>.json``: the parameters the one generator reads;
- ``bench/cells/<cell>.json``: the limits of the cell's correctness check;
- ``bench/e2e/<metric>.py``, ``bench/metrics/<metric>.py``: the reader of
  an end-to-end or a per-layer metric (a name ``a.b`` is read by
  ``a.py``).
"""
