"""Plain float32 references of the benchmarked architectures.

Written from the architectures' definitions in straightforward
``jax.numpy``; they import nothing of the program under test.  Every
matrix product goes through ``Numerics.mm``/``Numerics.einsum``, so the
same code computes in float32 at "highest" precision (the reference) or
with its operands rounded to a lower precision (the control that
``correct`` must reject).
"""
