"""Fused layer_norm as a Pallas TPU kernel.

XLA already fuses mean/var/normalize chains well; the win here is for
long rows (d_model >= 1024) where a single-pass Welford-style kernel
halves HBM traffic vs the two-pass XLA pattern by keeping the row tile
in VMEM across both statistics and normalization.

Gated by ops.pallas.pallas_enabled() like flash attention; the jnp
path matches bit-for-bit at fp32. The kernel compiles for the v5e as
written (chip_smoke.py, PR 21); no chip run has timed it yet.
"""

import functools

import jax
import jax.numpy as jnp

from . import interpret_mode

BLOCK_ROWS = 256


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = xc * inv * g_ref[...].astype(jnp.float32) + \
        b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _ln_pallas(x2, gamma, beta, eps, block_rows=None):
    from jax.experimental import pallas as pl

    n, d = x2.shape
    rows = block_rows if block_rows else BLOCK_ROWS
    while n % rows:
        rows //= 2
    grid = (n // rows,)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        interpret=interpret_mode(),
    )(x2, gamma, beta)


def _ln_reference(x2, gamma, beta, eps):
    x = x2.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps) * gamma + beta
    return y.astype(x2.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_2d(x2, gamma, beta, eps):
    from . import pallas_enabled
    n, d = x2.shape
    # Autotuned dispatch (r8): with PADDLE_TPU_AUTOTUNE=on and no
    # explicit PADDLE_TPU_USE_PALLAS the tuning table picks the impl
    # (and the Pallas row-block size) per (n, d, dtype). The decision is
    # memoized, so the forward and the vjp-fwd replay agree.
    from ... import tuning
    if tuning.autotune_mode() != 'off' and \
            not tuning.env_gate_set('PADDLE_TPU_USE_PALLAS'):
        picked = tuning.decide_layer_norm(n, d, str(x2.dtype))
        if picked is not None:
            if picked.get('impl') == 'pallas' and d % 128 == 0:
                return _ln_pallas(x2, gamma, beta, eps,
                                  block_rows=picked.get('block_rows'))
            return _ln_reference(x2, gamma, beta, eps)
    if pallas_enabled() and d % 128 == 0 and d >= 1024:
        return _ln_pallas(x2, gamma, beta, eps)
    return _ln_reference(x2, gamma, beta, eps)


def _ln_vjp_fwd(x2, gamma, beta, eps):
    return _ln_2d(x2, gamma, beta, eps), (x2, gamma, beta)


def _ln_vjp_bwd(eps, res, g):
    # Rematerializing XLA backward (Pallas kernels are not autodiffable);
    # the forward stays fused.
    x2, gamma, beta = res
    _, vjp = jax.vjp(lambda a, b, c: _ln_reference(a, b, c, eps),
                     x2, gamma, beta)
    return vjp(g)


_ln_2d.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def fused_layer_norm(x, gamma, beta, eps=1e-5, begin_norm_axis=-1):
    """Normalize over the trailing dims from begin_norm_axis; gamma/beta
    are flat over the normalized extent."""
    shape = x.shape
    if begin_norm_axis < 0:
        begin_norm_axis = x.ndim + begin_norm_axis
    d = 1
    for s in shape[begin_norm_axis:]:
        d *= s
    x2 = x.reshape(-1, d)
    y = _ln_2d(x2, gamma.reshape(d), beta.reshape(d), eps)
    return y.reshape(shape)
