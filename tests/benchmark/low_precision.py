"""The control of ``correct``: the plain reference with every matrix
product's operands rounded to the precision below the one the
configurations state (bfloat16 compute): a float8 e4m3's 3 bits of
mantissa, with float32's range, which is float8 under ideal scaling. The
reference so computed, put in the program's place, has to come out as
not correct; a test does that at a tiny size and ``PERF.md`` has the
chip's readings at a cell's own.

The rounding goes where every product is made, forward, tangent and
transposed alike: around ``dot_general``'s ``bind``. ``reduce_precision``
is linear to JAX's autodiff, so the rounded products differentiate.
"""

import contextlib

MANTISSA_BITS = {"float8_e4m3": 3, "bfloat16": 7}


@contextlib.contextmanager
def matmul_operands_in(precision: str):
    """While the block runs (and traces), both operands of every
    ``dot_general`` are rounded to ``precision``'s mantissa."""
    import jax

    primitive = jax.lax.dot_general_p
    bits = MANTISSA_BITS[precision]

    def rounded(x):
        return jax.lax.reduce_precision(x, exponent_bits=8,
                                        mantissa_bits=bits)

    def bind(lhs, rhs, **params):
        return type(primitive).bind(primitive, rounded(lhs), rounded(rhs),
                                    **params)

    primitive.bind = bind
    try:
        yield
    finally:
        del primitive.bind
