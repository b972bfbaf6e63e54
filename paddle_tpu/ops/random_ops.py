"""Random ops with TPU-native stateless PRNG.

Reference: paddle/fluid/operators/{uniform_random_op,gaussian_random_op}.cc.
Each op instance folds the step key with its static op index, so runs are
reproducible under jit and across replicas without a mutable global state.
"""

import math

import jax
import jax.numpy as jnp

from ..core.registry import register


# A stacked parameter of more elements than this is drawn a slice of its
# leading axis at a time: drawn at once, the random bits and the float32
# values of 1.34 G elements asked the chip for 5 GB of scratch beside
# the model (PERF.md, PR 45), and nemotron_3_super's expert stack is
# 1.76 G. No other configuration has a parameter this large, so theirs
# are drawn as they were.
SLICED_DRAW = 1 << 30


def keep_mask(key, keep, shape):
    """A dropout site's keep-mask and the probability it really keeps.
    An element is kept where 16 random bits read under ``round(keep *
    65536)``: the draw is what a mask costs on the chip (the generator
    writes it to HBM and the mask's fusion reads it back; PERF.md, PR
    59), and 16 bits resolve a rate to 1.5e-5, so the kept probability
    ``t / 65536`` is returned for the sites that divide by it. The bits
    are 32-bit words over half the last axis, the low halves masking
    its first half and the high halves its second: the chip's generator
    makes words, and a ``uint16`` draw of the whole shape took it longer
    and the train step longer than the float draw had (same place). A
    rate within 2**-17 of either end has no such threshold and takes the
    32-bit draw of ``jax.random.bernoulli``."""
    t = int(round(keep * 65536))
    shape = tuple(shape)
    if not (0 < t < 65536 and shape):
        return jax.random.bernoulli(key, keep, shape), keep
    n = shape[-1]
    words = jax.random.bits(key, shape[:-1] + ((n + 1) // 2,), jnp.uint32)
    mask = jnp.concatenate([(words & 0xFFFF) < t, (words >> 16) < t],
                           axis=-1)
    return mask[..., :n], t / 65536.0


def _shape_from(ctx):
    return [int(s) for s in ctx.attr('shape')]


@register('uniform_random')
def _uniform_random(ctx):
    shape = _shape_from(ctx)
    lo = ctx.attr('min', -1.0)
    hi = ctx.attr('max', 1.0)
    dtype = ctx.out_dtype('Out')
    seed = ctx.attr('seed', 0)
    key = ctx.rng_key() if not seed else jax.random.PRNGKey(seed)
    ctx.set_output('Out', jax.random.uniform(
        key, shape, dtype=jnp.float32, minval=lo, maxval=hi).astype(dtype))


@register('uniform_random_batch_size_like')
def _uniform_random_bsl(ctx):
    ref = ctx.input('Input')
    shape = _shape_from(ctx)
    shape[ctx.attr('output_dim_idx', 0)] = ref.shape[ctx.attr('input_dim_idx', 0)]
    ctx.set_output('Out', jax.random.uniform(
        ctx.rng_key(), shape, dtype=jnp.float32,
        minval=ctx.attr('min', -1.0),
        maxval=ctx.attr('max', 1.0)).astype(ctx.out_dtype('Out')))


@register('gaussian_random')
def _gaussian_random(ctx):
    shape = _shape_from(ctx)
    mean = ctx.attr('mean', 0.0)
    std = ctx.attr('std', 1.0)
    seed = ctx.attr('seed', 0)
    key = ctx.rng_key() if not seed else jax.random.PRNGKey(seed)
    dtype = ctx.out_dtype('Out')

    def drawn(key, shape):
        return (mean + std * jax.random.normal(
            key, shape, dtype=jnp.float32)).astype(dtype)
    if len(shape) > 2 and math.prod(shape) > SLICED_DRAW:
        ctx.set_output('Out', jax.lax.map(
            lambda k: drawn(k, shape[1:]), jax.random.split(key, shape[0])))
        return
    ctx.set_output('Out', drawn(key, shape))


@register('truncated_gaussian_random')
def _truncated_gaussian_random(ctx):
    shape = _shape_from(ctx)
    mean = ctx.attr('mean', 0.0)
    std = ctx.attr('std', 1.0)
    out = mean + std * jax.random.truncated_normal(
        ctx.rng_key(), -2.0, 2.0, shape, dtype=jnp.float32)
    ctx.set_output('Out', out.astype(ctx.out_dtype('Out')))


@register('gaussian_random_batch_size_like')
def _gaussian_random_bsl(ctx):
    ref = ctx.input('Input')
    shape = _shape_from(ctx)
    shape[ctx.attr('output_dim_idx', 0)] = ref.shape[ctx.attr('input_dim_idx', 0)]
    out = ctx.attr('mean', 0.0) + ctx.attr('std', 1.0) * jax.random.normal(
        ctx.rng_key(), shape, dtype=jnp.float32)
    ctx.set_output('Out', out.astype(ctx.out_dtype('Out')))


@register('randint')
def _randint(ctx):
    shape = _shape_from(ctx)
    ctx.set_output('Out', jax.random.randint(
        ctx.rng_key(), shape, ctx.attr('low', 0), ctx.attr('high', 100),
        dtype=jnp.int32).astype(ctx.out_dtype('Out', 'int64')))


@register('shuffle_batch')
def _shuffle_batch(ctx):
    x = ctx.input('X')
    perm = jax.random.permutation(ctx.rng_key(), x.shape[0])
    ctx.set_output('Out', x[perm])
    ctx.set_output('ShuffleIdx', perm)
