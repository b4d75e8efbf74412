"""The one-hot contraction every WORp kernel runs on the MXU, exact to fp32.

Mosaic's default f32 matmul rounds both operands to bf16, which puts a
relative error of ~2^-9 on every sketch cell.  The one-hot operand is exact
in bf16, so only the value operand needs more bits: it is split into three
bf16 pieces whose f32 sum is the value exactly (8 + 8 + 8 of its 24
mantissa bits), and each piece is contracted on its own with f32
accumulation.  Three MXU passes instead of the six of ``Precision.HIGHEST``,
whose temporaries do not fit VMEM at the default tiles.  Where each output
sums one nonzero term (the query's one-hot over width blocks or over a
table row's sublane rows) the result is the value bitwise; where it sums
many (scatter, update) it is an fp32 sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def onehot_dot(x: jnp.ndarray, onehot: jnp.ndarray,
               dimension_numbers) -> jnp.ndarray:
    """``dot_general(x, onehot)`` in fp32 for f32 ``x`` and a 0/1 ``onehot``."""
    oh = onehot.astype(jnp.bfloat16)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(part):
        return jax.lax.dot_general(part, oh, dimension_numbers,
                                   preferred_element_type=jnp.float32)

    return (dot(hi) + dot(mid)) + dot(lo)
