"""Model families: everything of the benchmark that depends on a model's
layout, one module per family.

A configuration names its family (``"family": "<family>"``) and the
harness loads ``families/<family>.py``, first under the cell's own root,
then under the benchmark's directory, as it loads a metric's reader.  A
new family is then one new file.  The module provides:

- ``MODEL_KEYS``: the keys of the configuration's ``model`` that the
  harness checks against the program's ``ArchConfig``;
- ``shapes(model)`` and ``make(model, seed, dtype)``: the seeded weight
  tree, leaf shapes keyed like the program's parameter tree (built with
  ``bench.weights.make_tree``);
- ``forward(weights, tokens, model, bits=None)`` and
  ``readings(weights, tokens, picks, model, bits=None)``: the plain float32
  reference, its logits and what ``bench.reference.picked`` reads of them;
  ``bits`` makes it the control;
- ``dense_work(model, rows, cv)``, ``matmul_params(model)`` and
  ``nominal_ops(model, tokens, context_sum)``: the counts the per-layer
  readings use (``bench/readings.py``).

Each function receives the configuration's whole ``model``, so a family
can read whatever key its layout needs; every family's ``model`` states
``vocab``, the range the traffic draws token ids from.

A family module imports nothing of the program: its weights, reference and
counts are the yardstick the program is held to, written from the model's
published description and built from ``bench/weights.py``,
``bench/reference.py`` and ``bench/counts.py``.
"""
