"""Plain reference of a checkpoint: a restore gives back, bit for bit, the
state that was saved. The saved state stays on the device, so the
comparison is a count there of the elements whose bits differ.

``lower_precision`` is the control: the saved state rounded to the
floating type below its own (bfloat16 for float32), which a later change
could be tempted to store. It rounds with ``reduce_precision``: under
``jit`` XLA drops a cast down and back up as excess precision, so a pair of
``astype`` calls may round nothing."""

import jax
import jax.numpy as jnp

UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
# (exponent bits, mantissa bits) of the type below: bfloat16, float8 e4m3
LOWER = {jnp.dtype(jnp.float32): (8, 7), jnp.dtype(jnp.bfloat16): (4, 3)}


def differing_elements(a, b):
    """Elements whose bits differ between two pytrees of one structure."""
    out = jnp.zeros((), jnp.int32)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        bx = jax.lax.bitcast_convert_type(x, UINT[x.dtype.itemsize])
        by = jax.lax.bitcast_convert_type(y, UINT[y.dtype.itemsize])
        out = out + jnp.sum(bx != by, dtype=jnp.int32)
    return out


def lower_precision(state):
    """The state rounded to the next lower floating type, in its own type."""
    return jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, *LOWER[x.dtype]) if x.dtype in LOWER else x,
        state)
