"""The chip benchmark: harness, traffic, references and trace reduction.

Entry point: ``python -m benchmarks.run`` (see ``BENCHMARK.json``).
"""
