"""chipbench — the on-chip benchmark of accelerate-tpu (see README.md).

Importing this package imports neither jax nor the program: the load
generator's child process imports it and must stay off the chip.
"""
