"""What a differentiated program holds, read from its jaxpr: the Pallas calls
by kernel name and the products against a matrix of a given shape. Shared by
the tests of what the remat sites keep (``core/remat.py``)."""

import jax


def eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold
    (scan bodies, remat bodies, custom-VJP rules), outside the Pallas
    kernels' own bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub)


def pallas_calls(jaxpr, kernel: str) -> int:
    """How many ``pallas_call`` equations are named ``kernel``."""
    return sum(eqn.primitive.name == "pallas_call"
               and eqn.params["name"] == kernel
               for eqn in eqns(jaxpr))


def products_with(jaxpr, matrix_shape) -> int:
    """How many ``x @ W`` there are with ``W`` of ``matrix_shape``: a
    ``dot_general`` contracting the left operand's last axis with the
    right operand's first (the backward's ``dy @ W^T`` contracts W's last
    axis, ``x^T dy`` has no operand of W's shape)."""
    def is_product(eqn):
        if eqn.primitive.name != "dot_general":
            return False
        lhs, rhs = (v.aval.shape for v in eqn.invars)
        (lc, rc), _ = eqn.params["dimension_numbers"]
        return (tuple(rhs) == tuple(matrix_shape)
                and tuple(lc) == (len(lhs) - 1,) and tuple(rc) == (0,))

    return sum(map(is_product, eqns(jaxpr)))
