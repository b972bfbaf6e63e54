"""fp8(e4m3)-cast matmul — the training-path compute lever.

Both operands are quantized per-tensor to float8_e4m3fn (one fp32 scale
each, absmax/448 — quant.core.quantize_tensor_fp8), contracted with an
fp32 accumulator (``preferred_element_type=jnp.float32``: the MXU rule
from the Pallas guide — never let the accumulator inherit the fp8 input
dtype), and rescaled by ``sx * sy``. Off-TPU the quantized values are
upcast to fp32 before the contraction, which is numerically identical:
every e4m3 value and every pairwise product of two of them is exactly
representable in fp32, so the only difference vs TPU is which unit does
the multiply.

The quantization is a forward-only wire format: ``fp8_matmul`` carries a
custom_vjp whose backward is the exact fp32 rule (g @ y.T, x.T @ g) —
differentiating the casts naively would push cotangents through an fp8
round-trip and quantize the gradients too.

Dispatch (``maybe_fp8_matmul``, consulted by the mul/matmul lowerings
for 2D x 2D shapes): the explicit ``PADDLE_TPU_FP8_MATMUL`` gate (read
per call — repo_lint enforced) beats the ``tuning.decide_matmul_dtype``
table beats the native default, mirroring the Pallas-vs-XLA convention.
"""

import os

import jax
import jax.numpy as jnp

from .. import observe as _obs
from ..quant.core import quantize_tensor_fp8

__all__ = ['fp8_supported', 'fp8_matmul_gate', 'fp8_matmul',
           'maybe_fp8_matmul']


def fp8_supported():
    """True when this jax build has float8_e4m3fn."""
    return hasattr(jnp, 'float8_e4m3fn')


def fp8_matmul_gate():
    """Tri-state per-call resolver for ``PADDLE_TPU_FP8_MATMUL``:
    True ('1'/'on'/'true') forces the fp8 path wherever it is
    representable, False ('0'/'off'/'false') forces native, None
    (unset/empty) defers to the autotuner table."""
    raw = os.environ.get('PADDLE_TPU_FP8_MATMUL')
    if raw is None or raw.strip() == '':
        return None
    return raw.strip().lower() not in ('0', 'off', 'false')


def _on_tpu():
    try:
        return jax.devices()[0].platform == 'tpu'
    except Exception:
        return False


def _fp8_fwd_value(x, y):
    qx, sx = quantize_tensor_fp8(x)
    qy, sy = quantize_tensor_fp8(y)
    if _on_tpu():
        acc = jnp.matmul(qx, qy, preferred_element_type=jnp.float32)
    else:
        acc = jnp.matmul(qx.astype(jnp.float32),
                         qy.astype(jnp.float32))
    out = acc * (sx * sy)
    return out.astype(jnp.result_type(x.dtype, y.dtype))


@jax.custom_vjp
def fp8_matmul(x, y):
    """``x @ y`` through the fp8(e4m3) wire format, 2D x 2D only.
    Forward quantizes; backward is exact fp32 (straight-through)."""
    return _fp8_fwd_value(x, y)


def _fp8_vjp_fwd(x, y):
    return _fp8_fwd_value(x, y), (x, y)


def _fp8_vjp_bwd(res, g):
    x, y = res
    gf = g.astype(jnp.float32)
    # the contractions jax's own matmul vjp emits (no materialized
    # transpose): XLA:CPU sums a transposed operand in another order,
    # and the gradients must equal the f32 rule's bit for bit
    dx = jax.lax.dot_general(gf, y.astype(jnp.float32),
                             (((1,), (1,)), ((), ()))).astype(x.dtype)
    dy = jax.lax.dot_general(x.astype(jnp.float32), gf,
                             (((0,), (0,)), ((), ()))).astype(y.dtype)
    return dx, dy


fp8_matmul.defvjp(_fp8_vjp_fwd, _fp8_vjp_bwd)


def maybe_fp8_matmul(x, y):
    """The fp8 result for a 2D x 2D float matmul when dispatch selects
    it, else None (the caller falls back to the native contraction).
    Precedence: explicit env gate > tuner table winner > native."""
    if getattr(x, 'ndim', 0) != 2 or getattr(y, 'ndim', 0) != 2:
        return None
    if not fp8_supported():
        return None
    if not (jnp.issubdtype(x.dtype, jnp.floating) and
            jnp.issubdtype(y.dtype, jnp.floating)):
        return None
    gate = fp8_matmul_gate()
    if gate is False:
        return None
    if gate is None:
        from ..tuning import decide_matmul_dtype
        win = decide_matmul_dtype(int(x.shape[0]), int(x.shape[1]),
                                  int(y.shape[1]), str(x.dtype))
        if not (win and win.get('impl') == 'fp8'):
            return None
    _obs.inc('fp8.matmul_dispatch_total')
    return fp8_matmul(x, y)
