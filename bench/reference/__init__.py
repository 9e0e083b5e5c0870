"""Architecture modules: each describes one architecture to the benchmark.

A configuration file names its module under ``reference``
(``bench/reference/<module>.py``); that key is the one place the shared
code (``bench/model.py``, ``weights.py``, ``flops.py``, ``check.py``,
the harness and the metric readers) learns the architecture from.  A
module exports:

- ``shape(cfg)``: the frozen sizes, a subclass of ``bench.model.Shape``;
- ``program_config(cfg, s, base)``: the program's ``ModelConfig``, the
  file's sizes put into ``base``, the program's registered architecture;
- ``layer_leaves(s, i)``: name -> (shape, scale) of layer ``i``'s random
  leaves (the index decides the layer's kind; the two norms ``ln1`` and
  ``ln2`` are ones and are not listed);
- ``program_params(layer_weights, s)``: the layers laid out as the
  program's tree, in as many stacks as the program keeps;
  ``layer_weights(i, like)`` draws layer ``i`` with the leaves of layer
  ``like``, a static layer of the same kind (``common.stack``);
- ``matmul_params(s, i)``: parameters one token multiplies with in layer
  ``i``;
- ``attention_flops(s, ctx)``: one token attending over ``ctx`` keys in
  one layer, linear in ``ctx``;
- ``attention_call(s, seqs, itemsize)``: (FLOPs, bytes) of one
  paged-attention call of one layer;
- ``experts_lost_with(s, rank)``: the experts with no slot left once EP
  rank ``rank`` is gone;
- ``layer(w, x, positions, s, live, precision)``: the plain float32
  reference of one decoder layer (``live``: the experts that may be
  routed to; ``precision``: ``"f32"``, or ``"f8"`` for the control).

A new configuration adds files only:

- ``bench/configs/<name>.json``: the published config.json keys, the
  cut listed in ``reduced``, ``deployment``, ``reference`` and ``check``;
- ``bench/reference/<module>.py``, where no module describes its
  architecture yet (``common.py`` holds the shared pieces);
- ``bench/traffic/<mix>.json``, where its traffic is a new mix;
- ``bench/metrics/<name>.py``, one reader for each new metric, such as
  one that reads new spans;
- its entries in ``BENCHMARK.json``: the configuration, its cells, and
  any new metrics.
"""
