"""Plain float32 references of the benchmark's configurations.

They import nothing of the program: ``jax.numpy`` at
``Precision.HIGHEST``, no kernels, no cache, no batching beyond blocks of
rows that keep the reference inside one chip's memory.
"""
