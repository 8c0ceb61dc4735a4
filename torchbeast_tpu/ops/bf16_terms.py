"""A float32 operand as bfloat16 terms, by the matmul precision traced
under.

On the MXU a float32 matmul is passes over bfloat16 pieces of its
operands: one at JAX's default precision, three under `high` (each
operand a head and a tail: head x head, head x tail, tail x head), six
under `highest` (three terms a side). Code that makes those passes
itself, because a kernel wants bfloat16 operands (models/moe.py
`_gmm_call`, ops/grouped_matmul.py) or because one operand is exact in
bfloat16 and needs no tail (models/transformer.py `frame_projection`:
a uint8 frame), cuts its operands here, at the count the caller's
`jax.default_matmul_precision` states.
"""

import jax
import jax.numpy as jnp

# bfloat16 terms an operand is cut into, by the matmul precision the
# caller traces under (JAX's names and their aliases); n terms a side
# make n (n + 1) / 2 passes.
_TERMS = {"high": 2, "tensorfloat32": 2, "highest": 3, "float32": 3}


def terms_traced_under():
    return _TERMS.get(jax.config.jax_default_matmul_precision, 1)


def bf16_terms(x, terms):
    """x as a sum of `terms` bfloat16 arrays, the largest first."""
    if terms == 1:
        return [x.astype(jnp.bfloat16)]
    out = []
    for _ in range(terms):
        # Not astype there and back: XLA takes that round trip for the
        # identity on the chip, and the next term comes out as zeros.
        head = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        out.append(head.astype(jnp.bfloat16))
        x = x - head
    return out
