"""A float32 operand as bfloat16 terms, by the matmul precision traced
under.

On the MXU a float32 matmul is passes over bfloat16 pieces of its
operands: one at JAX's default precision, three under `high` (each
operand a head and a tail: head x head, head x tail, tail x head), six
under `highest` (three terms a side). Code that makes those passes
itself cuts its operands here, at the count the caller's
`jax.default_matmul_precision` states: because a kernel wants bfloat16
operands (models/moe.py `_gmm_call`), because one operand is exact in
bfloat16 and needs no tail (models/transformer.py `frame_projection`:
a uint8 frame), or because it IS a kernel, which reads float32 tiles
and cuts them after they are loaded, so that no term is ever in HBM
(ops/grouped_matmul.py, ops/fused_attention.py: `cut_in_kernel`,
`product_of_terms`, the same terms by the only cast Mosaic lowers).
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


def cut_in_kernel(x, terms):
    """A float32 tile as `terms` bfloat16 tiles, the largest first:
    `bf16_terms` inside a Mosaic kernel, where `lax.reduce_precision`
    has no lowering and a cast there and back, which XLA folds to the
    identity outside one, is not folded."""
    out = []
    for term in range(terms):
        head = x.astype(jnp.bfloat16)
        out.append(head)
        if term + 1 < terms:
            x = x - head.astype(jnp.float32)
    return out


def product_of_terms(lhs, rhs, dims):
    """lhs x rhs over `dims`, each the list of its n bfloat16 terms, as
    the n (n + 1) / 2 one-pass products that a float32 matmul at that
    many terms is, the smallest first, so that they are not lost one by
    one beside the largest; float32."""
    out = None
    for order in reversed(range(len(lhs))):
        for i in range(order + 1):
            part = jax.lax.dot_general(
                lhs[i], rhs[order - i], dims,
                # Whatever the caller traces under: Mosaic refuses a
                # bfloat16 operand at a float32 contraction.
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32,
            )
            out = part if out is None else out + part
    return out


def product_in_kernel(lhs, rhs, terms, dims):
    """Two float32 tiles' product over `dims` at `terms` terms a side:
    each cut, then `product_of_terms`."""
    return product_of_terms(
        cut_in_kernel(lhs, terms), cut_in_kernel(rhs, terms), dims
    )
