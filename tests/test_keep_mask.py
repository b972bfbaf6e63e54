"""A dropout site's keep-mask (ops/random_ops.py::keep_mask: 16 random
bits an element against an integer threshold, two elements to a 32-bit
word; a word an element only where 16 bits have no threshold for the
rate) and the three sites that call
it: the ``dropout`` op, the stacked transformer's ``_dropout`` and the
attention's output. Under both generators the executor may hand a site
(``threefry2x32`` off the chip, ``rbg`` on it)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.ops import attention_ops, transformer_ops
from paddle_tpu.ops.random_ops import keep_mask

DRAWS = (4096, 4096)        # 2**24


def _threshold(keep):
    return int(round(keep * 65536))


def _kept_share(impl, rate):
    """The kept share of 2**24 draws lies within four standard errors
    of t / 65536, which is the probability the helper returns."""
    keep = 1.0 - rate
    mask, kept = keep_mask(jax.random.key(11, impl=impl), keep, DRAWS)
    assert mask.dtype == jnp.bool_ and mask.shape == DRAWS
    assert kept == _threshold(keep) / 65536.0
    assert abs(kept - keep) <= 2.0 ** -17
    n = DRAWS[0] * DRAWS[1]
    share = float(jnp.mean(mask, dtype=jnp.float32))
    assert abs(share - kept) < 4.0 * (kept * (1.0 - kept) / n) ** 0.5


def _no_threshold(impl, keep):
    """A rate within 2**-17 of an end is drawn from 32 bits, as
    ``jax.random.bernoulli`` draws it, and keeps what it was asked to."""
    key = jax.random.key(3, impl=impl)
    mask, kept = keep_mask(key, keep, (64, 64))
    assert kept == keep
    np.testing.assert_array_equal(
        mask, jax.random.bernoulli(key, keep, (64, 64)))
    drawn = str(jax.make_jaxpr(
        lambda k: keep_mask(k, keep, (64, 64))[0])(key))
    assert 'u32[64,64]' in drawn


def _sixteen_bits(impl):
    """What is drawn for a rate 16 bits resolve: a 32-bit word for two
    elements, over half the last axis."""
    key = jax.random.key(3, impl=impl)
    drawn = str(jax.make_jaxpr(
        lambda k: keep_mask(k, 0.7, (64, 64))[0])(key))
    assert 'u32[64,32]' in drawn and 'u32[64,64]' not in drawn


def _any_shape(impl):
    """An odd last axis drops the last word's high half; a scalar has
    no axis to halve and is drawn as it was."""
    key = jax.random.key(3, impl=impl)
    for shape in ((7,), (3, 5, 7), (4, 1), (4, 0)):
        mask, kept = keep_mask(key, 0.7, shape)
        assert mask.shape == shape and kept == _threshold(0.7) / 65536.0
    whole, _ = keep_mask(key, 0.7, (3, 5, 8))
    np.testing.assert_array_equal(keep_mask(key, 0.7, (3, 5, 7))[0],
                                  whole[..., :7])
    mask, kept = keep_mask(key, 0.7, ())
    assert mask.shape == () and kept == 0.7


def _a_function_of_key_and_shape(impl):
    """The same (key, shape) gives the same mask twice; another site's
    key gives another."""
    base = jax.random.key(5, impl=impl)
    site = [jax.random.fold_in(base, i) for i in (0, 1)]
    first, _ = keep_mask(site[0], 0.7, (128, 256))
    again, _ = keep_mask(site[0], 0.7, (128, 256))
    other, _ = keep_mask(site[1], 0.7, (128, 256))
    np.testing.assert_array_equal(first, again)
    assert 0.3 < float(jnp.mean(first != other)) < 0.55   # 2 x 0.7 x 0.3


HELPER_CASES = {
    'share_0.1': lambda impl: _kept_share(impl, 0.1),
    'share_0.3': lambda impl: _kept_share(impl, 0.3),
    'share_0.5': lambda impl: _kept_share(impl, 0.5),
    'share_0.9': lambda impl: _kept_share(impl, 0.9),
    'rate_1e-6_draws_32_bits': lambda impl: _no_threshold(impl, 1.0 - 1e-6),
    'keep_1e-6_draws_32_bits': lambda impl: _no_threshold(impl, 1e-6),
    'rate_0.3_draws_16_bits': _sixteen_bits,
    'odd_last_axis_and_scalar': _any_shape,
    'same_key_same_mask_other_site_differs': _a_function_of_key_and_shape,
}


@pytest.mark.parametrize('case', sorted(HELPER_CASES))
@pytest.mark.parametrize('impl', ['threefry2x32', 'rbg'])
def test_keep_mask(impl, case):
    HELPER_CASES[case](impl)


def _dropout_program(shape, **attrs):
    """(step function, scope values, feed values, program, the dropout
    op's index) of a program that drops an all-ones feed of ``shape``."""
    fluid.reset_default_programs()
    x = fluid.layers.data(name='x', shape=list(shape[1:]), dtype='float32')
    out = fluid.layers.dropout(x, **attrs)
    prog = fluid.default_main_program()
    index, op = [(i, op) for i, op in enumerate(prog.global_block().ops)
                 if op.type == 'dropout'][0]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    step, scope_vals, feed_vals = exe.compile_step(
        prog, feed={'x': np.ones(shape, 'float32')},
        fetch_list=[out, op.outputs['Mask'][0]])
    return step, scope_vals, feed_vals, prog, index


def _op_mask_is_the_helpers(impl):
    """The ``dropout`` op's Mask is ``keep_mask`` of the op's own key:
    the program's seed folded with the step, then with the op's index."""
    with fluid.scope_guard(fluid.Scope()):
        step, scope_vals, feed_vals, prog, index = _dropout_program(
            (8, 384), dropout_prob=0.3)
        (out, mask), _ = step(scope_vals, feed_vals, jnp.int32(7))
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(prog.random_seed or 0, impl=impl), 7), index)
    want, _ = keep_mask(key, 1.0 - 0.3, (8, 384))
    np.testing.assert_array_equal(mask, want.astype('float32'))
    np.testing.assert_array_equal(out, mask)    # ones, not upscaled


def _upscale_divides_by_the_kept_probability(impl):
    """``upscale_in_train``: what is kept is x / (t / 65536), float32's
    nearest, which is not its x / 0.7; the mean of a large all-ones
    input is 1 within four standard errors."""
    shape = (512, 2048)
    with fluid.scope_guard(fluid.Scope()):
        step, scope_vals, feed_vals, _, _ = _dropout_program(
            shape, dropout_prob=0.3,
            dropout_implementation='upscale_in_train')
        (out, mask), _ = step(scope_vals, feed_vals, jnp.int32(0))
    out, mask = np.asarray(out), np.asarray(mask)
    kept = _threshold(0.7) / 65536.0
    scale = np.float32(1.0) / np.float32(kept)
    assert scale != np.float32(1.0) / np.float32(0.7)
    np.testing.assert_array_equal(out, mask * scale)
    n = shape[0] * shape[1]
    assert abs(out.mean(dtype='float64') - 1.0) < \
        4.0 * ((1.0 - kept) / kept / n) ** 0.5


def _attention_divides_by_the_kept_probability(impl):
    """The attention's output of all-ones values is 1 in every element
    before it is dropped, so after it: 0 or 1 / (t / 65536), the mask
    ``keep_mask`` of the site's key, and 1 in the mean."""
    b, h, t, d, rate = 4, 4, 64, 64, 0.3
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, t, h * d), jnp.float32)
    key = jax.random.key(9, impl=impl)
    out = attention_ops.fused_attention(
        q, q, jnp.ones_like(q), h, dropout_rate=rate, rng=key)
    mask, kept = keep_mask(key, 1.0 - rate, (b, h, t, d))
    assert kept == _threshold(0.7) / 65536.0
    want = jnp.where(mask, 1.0 / kept, 0.0).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, want.reshape(b, t, h * d), rtol=1e-6)
    assert abs(float(jnp.mean(out)) - 1.0) < \
        4.0 * ((1.0 - kept) / kept / out.size) ** 0.5


def _stack_site_is_the_helpers(impl):
    """The stacked transformer's sites (``downgrade_in_infer``) multiply
    by the helper's mask and by nothing else."""
    key = jax.random.key(2, impl=impl)
    x = jnp.asarray(np.random.RandomState(1).randn(16, 24, 32), jnp.float32)
    got = transformer_ops._dropout(x, 0.3, key, False)
    mask, _ = keep_mask(key, 0.7, x.shape)
    np.testing.assert_array_equal(got, jnp.where(mask, x, 0.0))


def _inference_draws_nothing(impl):
    """``is_test``: x (1 - p) or x from the op, x (1 - rate) from the
    stack's sites, the attention's output as it is: bit for bit, and no
    random bits in what is traced."""
    x = np.random.RandomState(4).randn(8, 384).astype('float32')
    for attrs, want in (
            ({}, x * np.float32(1.0 - 0.3)),
            ({'dropout_implementation': 'upscale_in_train'}, x)):
        with fluid.scope_guard(fluid.Scope()):
            step, scope_vals, feed_vals, _, _ = _dropout_program(
                x.shape, dropout_prob=0.3, is_test=True, **attrs)
            feed_vals = dict(feed_vals, x=x)
            (out, mask), _ = step(scope_vals, feed_vals, jnp.int32(0))
            traced = str(jax.make_jaxpr(step)(scope_vals, feed_vals,
                                              jnp.int32(0)))
        np.testing.assert_array_equal(out, want)
        np.testing.assert_array_equal(mask, np.ones_like(x))
        assert 'random_bits' not in traced and 'rng_bit' not in traced
    key = jax.random.key(2, impl=impl)
    np.testing.assert_array_equal(
        transformer_ops._dropout(jnp.asarray(x), 0.3, key, True),
        x * np.float32(1.0 - 0.3))
    q = jnp.asarray(x.reshape(2, 4, 384))
    np.testing.assert_array_equal(
        attention_ops.fused_attention(q, q, q, 4, dropout_rate=0.3,
                                      rng=key, is_test=True),
        attention_ops.fused_attention(q, q, q, 4))


SITE_CASES = {
    'dropout_op_mask_is_keep_mask_of_its_key': _op_mask_is_the_helpers,
    'upscale_in_train_divides_by_kept': _upscale_divides_by_the_kept_probability,
    'attention_output_divides_by_kept': _attention_divides_by_the_kept_probability,
    'stack_site_multiplies_by_the_mask': _stack_site_is_the_helpers,
    'is_test_unchanged': _inference_draws_nothing,
}


@pytest.mark.parametrize('case', sorted(SITE_CASES))
@pytest.mark.parametrize('impl', ['threefry2x32', 'rbg'])
def test_a_dropout_site_draws_through_keep_mask(impl, case, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PRNG', impl)     # the executor's choice
    SITE_CASES[case](impl)
