"""The benchmark: one run of one cell of `BENCHMARK.json` on the chip.

Nothing in this package touches JAX when it is imported; every module
imports it inside the function that needs it.
"""
