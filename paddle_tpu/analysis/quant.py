"""Pass 6 — quantization dtype/scale contracts.

The PTQ rewrite (quant/ptq.py) and the quantized KV arenas
(serving/decode/model.py) both pair low-precision storage with fp32
scale vars; accumulation stays fp32. A quantized weight that loses its
scale (or pairs with a wrong-shaped one) doesn't crash — it silently
produces garbage logits, the worst failure mode. This pass locks the
pairing statically:

- every ``quant_mul`` / ``quant_matmul`` / ``quant_lookup_table``
  weight must be int8, its ``Scale`` input present, fp32, persistable
  like the weight, and shaped exactly ``[weight.shape[quant_axis]]``;
  ``accum_dtype`` must be 'float32' (these ops upcast to fp32 at the
  use site — anything else breaks the weight-only contract).
- every paged decode op (``paged_prefill`` / ``paged_decode_step`` /
  ``paged_spec_verify``) whose K/V arena is int8 or fp8 must carry
  ``KScale``/``VScale`` arenas of dtype fp32 shaped ``[L, NB, bs, H]``
  (one scale per stored row) — and must both be written back
  (``KScaleOut``/``VScaleOut``), or the donation contract silently
  drops the scales of every new token.
"""

from .base import analysis_pass

# op type -> (weight slot, default per-channel axis)
_QUANT_OPS = {
    'quant_mul': ('Y', 1),
    'quant_matmul': ('Y', 1),
    'quant_lookup_table': ('W', 0),
}

_PAGED_OPS = ('paged_prefill', 'paged_decode_step', 'paged_spec_verify')
_QUANT_ARENA_DTYPES = ('int8', 'float8_e4m3fn')


@analysis_pass('quant')
def check(ctx):
    for i, op in enumerate(ctx.block.ops):
        if op.type in _QUANT_OPS:
            _check_weight_op(ctx, i, op)
        elif op.type in _PAGED_OPS:
            _check_paged_op(ctx, i, op)


def _check_weight_op(ctx, i, op):
    wslot, default_axis = _QUANT_OPS[op.type]
    wname = op.input(wslot)
    wvar = ctx.find_var(wname) if wname else None
    if wvar is None:
        return   # wellformed reports undefined inputs
    if wvar.dtype != 'int8':
        ctx.error('quant-weight-dtype',
                  'quantized op consumes %r of dtype %s — the %s slot '
                  'of a %s must be int8 (the PTQ rewrite produces the '
                  'int8 copy; do not hand it the fp32 original)'
                  % (wname, wvar.dtype, wslot, op.type),
                  op=op, op_index=i, var=wname)
    sname = op.input('Scale')
    if sname is None:
        ctx.error('quant-missing-scale',
                  'quantized weight %r has no Scale input — int8 '
                  'weights are meaningless without their per-channel '
                  'fp32 scales' % wname,
                  op=op, op_index=i, var=wname)
        return
    svar = ctx.find_var(sname)
    if svar is None:
        return
    if svar.dtype != 'float32':
        ctx.error('quant-scale-dtype',
                  'scale %r has dtype %s; per-channel scales must be '
                  'float32' % (sname, svar.dtype),
                  op=op, op_index=i, var=sname)
    axis = op.attr('quant_axis', default_axis)
    if wvar.shape is not None and svar.shape is not None:
        want = (wvar.shape[axis % len(wvar.shape)],)
        if tuple(svar.shape) != want:
            ctx.error('quant-scale-shape',
                      'scale %r has shape %s; weight %r quantized on '
                      'axis %d needs scales shaped %s'
                      % (sname, list(svar.shape), wname, axis,
                         list(want)),
                      op=op, op_index=i, var=sname)
    if wvar.persistable and not (svar.persistable or svar.is_data):
        ctx.error('quant-scale-transient',
                  'scale %r is a temporary but its weight %r is '
                  'persistable — the pair must live (and serialize) '
                  'together' % (sname, wname),
                  op=op, op_index=i, var=sname)
    if op.attr('accum_dtype', 'float32') != 'float32':
        ctx.error('quant-accum-dtype',
                  '%s declares accum_dtype=%r; weight-only int8 ops '
                  'accumulate in float32' % (op.type,
                                             op.attr('accum_dtype')),
                  op=op, op_index=i)


def _check_paged_op(ctx, i, op):
    for cache_slot, scale_slot in (('KCache', 'KScale'),
                                   ('VCache', 'VScale')):
        cname = op.input(cache_slot)
        cvar = ctx.find_var(cname) if cname else None
        if cvar is None or cvar.dtype not in _QUANT_ARENA_DTYPES:
            continue
        sname = op.input(scale_slot)
        if sname is None:
            ctx.error('kv-missing-scale',
                      '%s arena %r is %s but the op has no %s input — '
                      'quantized pages cannot be dequantized without '
                      'their per-row scales'
                      % (cache_slot, cname, cvar.dtype, scale_slot),
                      op=op, op_index=i, var=cname)
            continue
        svar = ctx.find_var(sname)
        if svar is None:
            continue
        if svar.dtype != 'float32':
            ctx.error('kv-scale-dtype',
                      'scale arena %r has dtype %s; must be float32'
                      % (sname, svar.dtype),
                      op=op, op_index=i, var=sname)
        want = None if cvar.shape is None else \
            tuple(cvar.shape[:3]) + (op.attr('n_head', 1),)
        if want is not None and svar.shape is not None and \
                tuple(svar.shape) != want:
            ctx.error('kv-scale-shape',
                      'scale arena %r has shape %s; arena %r %s needs '
                      'per-row scales shaped %s (one per [L, NB, bs, '
                      'H] row)'
                      % (sname, list(svar.shape), cname,
                         list(cvar.shape), list(want)),
                      op=op, op_index=i, var=sname)
        out_slot = scale_slot + 'Out'
        if op.output(out_slot) is None:
            ctx.error('kv-scale-not-written',
                      "%s is read but %s is missing — new tokens' "
                      'scales would be silently dropped by the '
                      'donated in-place update'
                      % (scale_slot, out_slot),
                      op=op, op_index=i, var=sname)
