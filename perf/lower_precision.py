"""A plain reference computed in bfloat16: the control of a cell's `correct`.

A configuration states a compute type (bfloat16) and, beside it, what stays
float32: the norms' statistics, the softmax, the router, every matmul's
accumulator, the loss. ``in_bf16(fn)`` evaluates ``fn`` (a family's plain
float32 reference) with NONE of that kept: the result of every primitive is
rounded to bfloat16, and a matmul accumulates in bfloat16 across
``ACCUMULATE_EVERY``-deep slices of its contraction (the depth of one pass
through the MXU). Put in the program's place, it has to read
``correct: false`` (``perf/controls.py``); the limits of a configuration's
``reference_tolerance`` lie between what the program reads and what this
reads.

The rounding is ``lax.reduce_precision``, which the compiler may not remove:
a cast to bfloat16 and back it may (excess precision is allowed), and then
nothing was lowered (my chip runs, PR 28, calls 5 and 7). The function is
evaluated equation by equation from its jaxpr, so the reference itself is
not written twice. An equation that holds a program of another kind than a
plain call (``scan``, ``while``, ``cond``) is refused: inside it nothing would
be rounded, and the references' forward passes have none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

ACCUMULATE_EVERY = 128
_CALLS = ("jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
          "custom_vjp_call", "remat2")


def _round(x):
    if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _dot(lhs, rhs, *, dimension_numbers, **params):
    """``dot_general`` whose accumulator is rounded to bfloat16 after every
    ``ACCUMULATE_EVERY`` terms of the (single) contracted dimension."""
    (lc, rc), _ = dimension_numbers
    plain = lambda a, b: jax.lax.dot_general(  # noqa: E731
        a, b, dimension_numbers=dimension_numbers, **params)
    depth = lhs.shape[lc[0]] if len(lc) == 1 else 0
    if depth <= ACCUMULATE_EVERY or depth % ACCUMULATE_EVERY:
        return plain(lhs, rhs)

    def slices(a, axis):
        shape = (a.shape[:axis] + (depth // ACCUMULATE_EVERY,
                                   ACCUMULATE_EVERY) + a.shape[axis + 1:])
        return jnp.moveaxis(a.reshape(shape), axis, 0)

    def add(acc, ab):
        return _round(acc + plain(*ab)), None

    zero = jnp.zeros(jax.eval_shape(plain, lhs, rhs).shape, jnp.float32)
    return jax.lax.scan(
        add, zero, (slices(lhs, lc[0]), slices(rhs, rc[0])))[0]


def _evaluate(jaxpr, consts, args):
    env = {}

    def read(var):
        return var.val if isinstance(var, jax_core.Literal) else env[var]

    for var, value in zip(jaxpr.constvars, consts):
        env[var] = _round(value)
    for var, value in zip(jaxpr.invars, args):
        env[var] = value
    for eqn in jaxpr.eqns:
        values = [read(v) for v in eqn.invars]
        name = eqn.primitive.name
        inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        if name in _CALLS and inner is not None:
            closed = isinstance(inner, jax_core.ClosedJaxpr)
            out = _evaluate(inner.jaxpr if closed else inner,
                            inner.consts if closed else (), values)
        elif name == "dot_general":
            out = [_dot(*values, **eqn.params)]
        elif any(isinstance(v, (jax_core.Jaxpr, jax_core.ClosedJaxpr))
                 for v in eqn.params.values()):
            raise NotImplementedError(
                f"in_bf16: `{name}` holds a program that would run unrounded")
        else:
            out = eqn.primitive.bind(*values, **eqn.params)
            if not eqn.primitive.multiple_results:
                out = [out]
        for var, value in zip(eqn.outvars, out):
            env[var] = _round(value)
    return [read(v) for v in jaxpr.outvars]


def in_bf16(fn):
    """``fn`` (arrays and trees of arrays in, the same out) with every
    floating-point input and every primitive's result rounded to bfloat16."""

    def lowered(*args):
        closed, shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        flat = [_round(a) for a in jax.tree_util.tree_leaves(args)]
        out = _evaluate(closed.jaxpr, closed.consts, flat)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shape), out)

    return lowered
