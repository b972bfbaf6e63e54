"""One-pass fused batch-norm (training) as a Pallas TPU kernel.

VERDICT r4 next-#2: the ResNet forward (~44 TF/s vs ~68 bwd) pays the
conv→BN-stats serialization — XLA schedules the stats reduction and the
normalize as separate HBM passes over the conv output, with whatever
fusion the compiler chooses. This kernel pins the schedule: ONE
pallas_call computes fp32-accumulated statistics AND the bf16
elementwise normalize, reading x exactly twice and writing y once,
with the per-channel a/b folding (y = x·a + b) done in VMEM between
the phases. Semantics match reference batch_norm_op.cc training mode
(biased variance, saved mean/var outputs).

Grid layout: (C/bc, 2, R/br) over x reshaped [R, C] (NHWC rows ×
channels — channels ride the lane dimension). Phase 0 accumulates
sum / sumsq tiles into VMEM scratch ([8, bc] sublane partials, folded
at the end); phase 1 replays the same row blocks through y = x·a + b.
The phase-0 output index map pins all writes to block 0 so the unwritten
output buffer is fetched/copied back at most once before phase 1
rewrites it (revisiting semantics: the buffer only flushes when its
mapped index changes).

Backward is the standard BN gradient in jnp (custom_vjp): the backward
phase is already the efficient one on chip (SURVEY §7.16), so only the
forward schedule needed pinning.

Opt-in: PADDLE_TPU_BN_PALLAS=1 (benched as resnet50_bn_pallas A/B).
"""

import functools
import os

import jax
import jax.numpy as jnp

from . import interpret_mode

DEFAULT_BLOCK_R = 512


def _default_block_r():
    # read per call (not at import) so env changes after import — and
    # the autotuner's in-process sweeps — take effect
    return int(os.environ.get('PADDLE_TPU_BN_BLOCK_R',
                              str(DEFAULT_BLOCK_R)))


def bn_pallas_enabled():
    return os.environ.get('PADDLE_TPU_BN_PALLAS') == '1'


def _bn_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, var_ref,
               sum_scr, sq_scr, ab_scr, *, eps, rows_total, block_r,
               num_r_blocks):
    from jax.experimental import pallas as pl

    ph = pl.program_id(1)
    rb = pl.program_id(2)

    @pl.when((ph == 0) & (rb == 0))
    def _init():
        sum_scr[:] = jnp.zeros_like(sum_scr)
        sq_scr[:] = jnp.zeros_like(sq_scr)

    @pl.when(ph == 0)
    def _accumulate():
        x = x_ref[...]
        xf = x.astype(jnp.float32)
        # fold block rows onto the 8-sublane partials; full fp32 adds
        part = xf.reshape(block_r // 8, 8, xf.shape[-1])
        sum_scr[:] = sum_scr[:] + jnp.sum(part, axis=0)
        sq_scr[:] = sq_scr[:] + jnp.sum(jnp.square(part), axis=0)

    @pl.when((ph == 0) & (rb == num_r_blocks - 1))
    def _stats():
        n = jnp.float32(rows_total)
        mean = jnp.sum(sum_scr[:], axis=0, keepdims=True) / n   # [1, bc]
        var = jnp.maximum(
            jnp.sum(sq_scr[:], axis=0, keepdims=True) / n
            - jnp.square(mean), 0.0)
        mean_ref[...] = mean
        var_ref[...] = var
        inv = jax.lax.rsqrt(var + eps)
        a = scale_ref[...].astype(jnp.float32) * inv
        b = bias_ref[...].astype(jnp.float32) - mean * a
        ab_scr[0:1] = a
        ab_scr[1:2] = b

    @pl.when(ph == 1)
    def _normalize():
        x = x_ref[...]
        a = ab_scr[0:1].astype(x.dtype)
        b = ab_scr[1:2].astype(x.dtype)
        y_ref[...] = x * a + b


def _fused_bn_fwd(x2, scale, bias, eps, block_r):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, c = x2.shape
    block_r = min(block_r, r)
    while r % block_r != 0 or block_r % 8 != 0:
        block_r //= 2
        if block_r < 8:
            raise ValueError('fused BN needs rows divisible by 8; got %d'
                             % r)
    bc = min(c, 128)
    if c % bc != 0:
        raise ValueError('fused BN needs channels %% 128 == 0 or < 128; '
                         'got %d' % c)
    num_r_blocks = r // block_r
    grid = (c // bc, 2, num_r_blocks)
    kernel = functools.partial(
        _bn_kernel, eps=eps, rows_total=r, block_r=block_r,
        num_r_blocks=num_r_blocks)
    y, mean, var = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, bc), lambda cb, ph, rb: (rb, cb)),
            pl.BlockSpec((1, bc), lambda cb, ph, rb: (0, cb)),
            pl.BlockSpec((1, bc), lambda cb, ph, rb: (0, cb)),
        ],
        out_specs=[
            # phase 0 pins writes to block 0; phase 1 sweeps the rows
            pl.BlockSpec((block_r, bc),
                         lambda cb, ph, rb: (ph * rb, cb)),
            pl.BlockSpec((1, bc), lambda cb, ph, rb: (0, cb)),
            pl.BlockSpec((1, bc), lambda cb, ph, rb: (0, cb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), x2.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((8, bc), jnp.float32),
            pltpu.VMEM((8, bc), jnp.float32),
            pltpu.VMEM((2, bc), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary')),
        interpret=interpret_mode(),
    )(x2, scale.reshape(1, c), bias.reshape(1, c))
    return y, mean.reshape(c), var.reshape(c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_bn_core(x2, scale, bias, eps, block_r):
    return _fused_bn_fwd(x2, scale, bias, eps, block_r)


def _bn_vjp_fwd(x2, scale, bias, eps, block_r):
    y, mean, var = _fused_bn_fwd(x2, scale, bias, eps, block_r)
    return (y, mean, var), (x2, scale, mean, var)


def _bn_vjp_bwd(eps, block_r, res, cts):
    """Standard training-BN gradient (reference batch_norm_grad_op
    semantics), in jnp — the backward phase is the one XLA already runs
    efficiently. Cotangents of the mean/var outputs are ignored: they
    feed stop_gradient'd running stats in the lowering."""
    x2, scale, mean, var = res
    gy = cts[0]
    n = jnp.float32(x2.shape[0])
    inv = jax.lax.rsqrt(var + eps)                          # [C] f32
    xf = x2.astype(jnp.float32)
    gyf = gy.astype(jnp.float32)
    xhat = (xf - mean[None, :]) * inv[None, :]
    dbias = jnp.sum(gyf, axis=0)                            # [C]
    dscale = jnp.sum(gyf * xhat, axis=0)                    # [C]
    dx = (scale.astype(jnp.float32) * inv)[None, :] * (
        gyf - dbias[None, :] / n - xhat * dscale[None, :] / n)
    return dx.astype(x2.dtype), dscale.astype(scale.dtype), \
        dbias.astype(scale.dtype)


_fused_bn_core.defvjp(_bn_vjp_fwd, _bn_vjp_bwd)


def fused_batch_norm_train(x, scale, bias, eps, layout='NHWC',
                           block_r=None):
    """Training-mode BN via the one-pass kernel. x: [N,H,W,C] (NHWC),
    [N,C,H,W] (NCHW — transposed through the kernel's row layout), or
    [N,C]. Returns (y, batch_mean, batch_var) with y in x.dtype and
    fp32 stats."""
    if x.ndim == 4 and layout == 'NCHW':
        xt = x.transpose(0, 2, 3, 1)
        y, m, v = fused_batch_norm_train(xt, scale, bias, eps, 'NHWC',
                                         block_r)
        return y.transpose(0, 3, 1, 2), m, v
    shape = x.shape
    c = shape[-1]
    x2 = x.reshape(-1, c)
    y, mean, var = _fused_bn_core(x2, scale, bias, eps,
                                  block_r or _default_block_r())
    return y.reshape(shape), mean, var


def _bn_reference(x2, scale, bias, eps):
    """jnp reference for parity tests."""
    xf = x2.astype(jnp.float32)
    mean = jnp.mean(xf, axis=0)
    var = jnp.mean(jnp.square(xf), axis=0) - jnp.square(mean)
    inv = jax.lax.rsqrt(var + eps)
    a = scale.astype(jnp.float32) * inv
    b = bias.astype(jnp.float32) - mean * a
    y = (xf * a[None, :] + b[None, :]).astype(x2.dtype)
    return y, mean, var
