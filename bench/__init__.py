"""The chip benchmark: ``python bench/run.py --workload <cell> ...``.

Everything here is the yardstick: traffic generation, each model family's
seeded weights, plain float32 reference and operation and byte counts
(``families/``), the trace reduction and the chip peaks.  From the program
it takes only the serving engine and what the engine exposes publicly.
"""
