"""Pallas TPU kernels: grouped matmul, packed attention, paged attention.

Each kernel has ``kernel.py`` (the Pallas program), ``ref.py`` (the plain
jnp oracle) and ``ops.py`` (the jitted wrapper).  How a kernel runs is
decided in one place, ``dispatch.pallas_interpret``.
"""
