"""The chip benchmark: ``python bench/run.py --workload <cell> ...``.

Everything here is the yardstick: traffic generation, seeded weights, the
plain float32 reference, operation and byte counts, the trace reduction
and the chip peaks.  From the program it takes only the serving engine
and what the engine exposes publicly.
"""
