"""Weights from the seed, on the device, in one jitted call.

A program's own ``random_seed`` is a constant inside its HLO, so drawing
the weights through it would compile anew for every seed. Instead the
program initialises them once with its fixed seed, and ``redraw`` draws
every matrix again with the mean and the deviation its initializer gave
it, from a key that is an argument of the call.
"""

import numpy as np


def seed_key(seed):
    import jax
    return jax.random.key(np.uint32(seed % (1 << 32)))


def redraw(params, key):
    """``params`` (name -> matrix) drawn again, leaf by leaf in name
    order; jit it with the seed's key as an argument."""
    import jax
    import jax.numpy as jnp
    out = {}
    for i, name in enumerate(sorted(params)):
        p = params[name]
        noise = jax.random.normal(jax.random.fold_in(key, i), p.shape,
                                  jnp.float32)
        out[name] = (jnp.mean(p) + jnp.std(p) * noise).astype(p.dtype)
    return out
