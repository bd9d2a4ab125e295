"""The precision policy of a reference run.

The reference proper computes every contraction in float32 at matmul
precision ``highest``. A *control* is the same reference with the operands
of every contraction rounded to a lower-precision type first (fake
quantisation: round, then contract in float32) and, optionally, the master
weights kept in a lower type between steps. It stands in for the program
computed one precision step below what the configuration states.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Numerics:
    """``operand_dtype``: None = float32 operands; else operands of every
    dot/conv are rounded to it. ``master_dtype``: None = float32 masters;
    else the weights are rounded to it after every optimizer step."""

    operand_dtype: str | None = None
    master_dtype: str | None = None

    def q(self, a):
        if self.operand_dtype is None:
            return a
        return a.astype(jnp.dtype(self.operand_dtype)).astype(jnp.float32)

    def dot(self, a, b):
        return jnp.matmul(self.q(a), self.q(b),
                          precision=jax.lax.Precision.HIGHEST)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b),
                          precision=jax.lax.Precision.HIGHEST)

    def master(self, tree):
        if self.master_dtype is None:
            return tree
        dt = jnp.dtype(self.master_dtype)
        return jax.tree_util.tree_map(
            lambda a: a.astype(dt).astype(jnp.float32), tree)


FLOAT32 = Numerics()

# name -> Numerics; the ladder of "one step below" per stated compute dtype
CONTROLS = {
    "float8_operands": Numerics(operand_dtype="float8_e4m3fn"),
    "bfloat16_operands": Numerics(operand_dtype="bfloat16"),
    "bfloat16_everywhere": Numerics(operand_dtype="bfloat16",
                                    master_dtype="bfloat16"),
}

# the nearest precision below the one a configuration states
NEXT_BELOW = {
    "float32": "bfloat16_operands",
    "bfloat16": "float8_operands",
    "float16": "float8_operands",
}
