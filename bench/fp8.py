"""A function with every matmul's operands rounded to float8.

``quantized(fn)`` traces ``fn`` and evaluates its jaxpr with each
``dot_general``'s floating operands rounded to float8 e4m3 (3 mantissa
bits) under a per-tensor scale that maps the largest magnitude to the
format's largest value, and accumulated as before.  Put in the place of
a program served in bfloat16, the reference computed so is the control
of the comparison that decides ``correct``: the next precision down,
the step a later change could take to look faster.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend import core

F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)

#: primitives that call one sub-jaxpr on their operands, and the param
#: that holds it; evaluated inline (forward only)
CALLS = {"pjit": "jaxpr", "jit": "jaxpr", "custom_jvp_call": "call_jaxpr"}


def round_f8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under a per-tensor scale."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = (x.astype(jnp.float32) / scale).astype(F8).astype(jnp.float32)
    return (q * scale).astype(x.dtype)


def _closed(j):
    return (j.jaxpr, j.consts) if hasattr(j, "consts") else (j, ())


def _eval(jaxpr, consts, *args):
    env = {}

    def read(v):
        return v.val if isinstance(v, core.Literal) else env[v]

    for v, x in zip(jaxpr.constvars, consts):
        env[v] = x
    for v, x in zip(jaxpr.invars, args):
        env[v] = x
    for eqn in jaxpr.eqns:
        outs = _apply(eqn, [read(v) for v in eqn.invars])
        if not eqn.primitive.multiple_results:
            outs = [outs]
        for v, x in zip(eqn.outvars, outs):
            env[v] = x
    return [read(v) for v in jaxpr.outvars]


def _apply(eqn, invals):
    p, name = eqn.params, eqn.primitive.name
    if name == "dot_general":
        return eqn.primitive.bind(*[round_f8(x) for x in invals], **p)
    if name == "scan":
        body, bconsts = _closed(p["jaxpr"])
        nc, nk = p["num_consts"], p["num_carry"]
        consts, init, xs = invals[:nc], invals[nc:nc + nk], invals[nc + nk:]

        def f(carry, x):
            out = _eval(body, bconsts, *consts, *carry, *x)
            return out[:nk], out[nk:]

        carry, ys = lax.scan(f, list(init), list(xs), length=p["length"],
                             reverse=p["reverse"])
        return [*carry, *ys]
    if name in CALLS:
        return _eval(*_closed(p[CALLS[name]]), *invals)
    if any(isinstance(v, (core.Jaxpr, core.ClosedJaxpr))
           for v in p.values()):
        raise NotImplementedError(f"fp8: no rule for primitive {name!r}")
    return eqn.primitive.bind(*invals, **p)


def quantized(fn):
    """``fn`` (jitted) with every matmul's operands rounded to float8."""

    @jax.jit
    def wrapped(*args):
        closed, shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        out = _eval(closed.jaxpr, closed.consts, *jax.tree.leaves(args))
        return jax.tree.unflatten(jax.tree.structure(shape), out)

    return wrapped
