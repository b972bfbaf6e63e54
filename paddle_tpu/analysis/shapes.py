"""Pass 2 — shape/dtype abstract interpretation.

Per-op contracts over the DECLARED var shapes (batch and other unbound
dims are -1 and treated as wildcards), checked before anything traces:
a mul whose flattened inner dims disagree fails here with the op type
and the Python file:line that appended it, instead of as a jnp
broadcast error three layers into `jit`. Contracts cover the
high-traffic op set — matmul/mul, conv, fused attention, norms,
elementwise, reshape/concat/transpose, and the optimizer update ops —
and are deliberately permissive: any dim that is unknown (-1 or an
undeclared shape) skips the check rather than guessing.
"""

from .base import analysis_pass

_WILD = -1


def _known(*dims):
    return all(d is not None and d >= 0 for d in dims)


def _prod(dims):
    """Product of a dim slice, or None when any dim is unknown."""
    out = 1
    for d in dims:
        if d is None or d < 0:
            return None
        out *= d
    return out


def _dims_eq(a, b):
    return a < 0 or b < 0 or a == b


_FLOATS = ('float16', 'bfloat16', 'float32', 'float64')
_INTS = ('int16', 'int32', 'int64', 'uint8', 'int8', 'bool')

# Optimizer state slots that must be param-shaped.
_STATE_SLOTS = frozenset((
    'Moment', 'Moment1', 'Moment2', 'Velocity', 'InfNorm', 'MeanSquare',
    'MeanGrad', 'AvgSquaredGrad', 'AvgSquaredUpdate',
    'SquaredAccumulator', 'LinearAccumulator'))

_OPTIMIZER_OPS = frozenset((
    'sgd', 'momentum', 'adagrad', 'adam', 'adamax', 'adadelta',
    'rmsprop', 'ftrl', 'decayed_adagrad', 'proximal_gd',
    'proximal_adagrad'))

_ELEMENTWISE_PREFIX = 'elementwise_'


def _sparse_params(block):
    """Param names whose grads flow as sparse rows (shape-exempt)."""
    for op in block.ops:
        if op.type == 'backward_marker':
            return set(op.attrs.get('sparse_grads') or ())
    return set()


@analysis_pass('shapes')
def check(ctx):
    sparse = _sparse_params(ctx.block)
    for i, op in enumerate(ctx.block.ops):
        fn = _CONTRACTS.get(op.type)
        if fn is None and op.type.startswith(_ELEMENTWISE_PREFIX):
            fn = _elementwise
        if fn is None and op.type in _OPTIMIZER_OPS:
            fn = _optimizer
        if fn is not None:
            fn(ctx, op, i, sparse)


# ------------------------------------------------------------- contracts
def _in_shape(ctx, op, slot):
    name = op.input(slot)
    return None if name is None else ctx.shape_of(name)


def _check_float(ctx, op, i, slots):
    for slot in slots:
        name = op.input(slot)
        if name is None:
            continue
        dt = ctx.dtype_of(name)
        if dt in _INTS:
            ctx.error('dtype-not-float',
                      'input %r (slot %s) has dtype %s; %s computes in '
                      'floating point' % (name, slot, dt, op.type),
                      op=op, op_index=i, var=name)


def _mul(ctx, op, i, sparse):
    x, y = _in_shape(ctx, op, 'X'), _in_shape(ctx, op, 'Y')
    _check_float(ctx, op, i, ('X', 'Y'))
    if x is None or y is None:
        return
    xd = op.attr('x_num_col_dims', 1)
    yd = op.attr('y_num_col_dims', 1)
    inner_x = _prod(x[xd:])
    inner_y = _prod(y[:yd])
    if inner_x is not None and inner_y is not None and inner_x != inner_y:
        ctx.error('matmul-mismatch',
                  'mul contracts X%s cols (%d, from dims %s) against '
                  'Y%s rows (%d, from dims %s)'
                  % (list(x), inner_x, list(x[xd:]), list(y), inner_y,
                     list(y[:yd])), op=op, op_index=i,
                  var=op.input('Y'))


def _matmul(ctx, op, i, sparse):
    x, y = _in_shape(ctx, op, 'X'), _in_shape(ctx, op, 'Y')
    _check_float(ctx, op, i, ('X', 'Y'))
    if x is None or y is None or len(x) < 1 or len(y) < 1:
        return
    xc = x[-2] if op.attr('transpose_X', False) and len(x) > 1 else x[-1]
    if len(y) == 1:
        yc = y[0]
    elif op.attr('transpose_Y', False):
        yc = y[-1]
    else:
        yc = y[-2]
    if _known(xc, yc) and xc != yc:
        ctx.error('matmul-mismatch',
                  'matmul contracting dims disagree: X%s gives %d, '
                  'Y%s gives %d' % (list(x), xc, list(y), yc),
                  op=op, op_index=i, var=op.input('Y'))


def _elementwise(ctx, op, i, sparse):
    x, y = _in_shape(ctx, op, 'X'), _in_shape(ctx, op, 'Y')
    xn, yn = op.input('X'), op.input('Y')
    dx, dy = ctx.dtype_of(xn), ctx.dtype_of(yn)
    if dx and dy and (dx in _FLOATS) != (dy in _FLOATS):
        ctx.warning('dtype-mix',
                    '%s mixes %s (%r) with %s (%r); jnp promotion '
                    'decides the result dtype' % (op.type, dx, xn, dy,
                                                  yn),
                    op=op, op_index=i, var=yn)
    if x is None or y is None:
        return
    axis = op.attr('axis', -1)
    if axis in (-1, None):
        axis = len(x) - len(y)
    if axis < 0 or axis + len(y) > len(x):
        ctx.error('broadcast-mismatch',
                  '%s cannot align Y%s into X%s at axis %d'
                  % (op.type, list(y), list(x), axis),
                  op=op, op_index=i, var=yn)
        return
    for j, yd in enumerate(y):
        xd = x[axis + j]
        if _known(xd, yd) and xd != yd and 1 not in (xd, yd):
            ctx.error('broadcast-mismatch',
                      '%s: Y%s dim %d (=%d) does not broadcast against '
                      'X%s dim %d (=%d)' % (op.type, list(y), j, yd,
                                            list(x), axis + j, xd),
                      op=op, op_index=i, var=yn)
            return


def _concat(ctx, op, i, sparse):
    shapes = [(n, ctx.shape_of(n)) for n in op.inputs.get('X', [])]
    shapes = [(n, s) for n, s in shapes if s is not None]
    if len(shapes) < 2:
        return
    axis = op.attr('axis', 0)
    rank = len(shapes[0][1])
    for n, s in shapes[1:]:
        if len(s) != rank:
            ctx.error('rank-mismatch',
                      'concat input %r has rank %d, first input has '
                      'rank %d' % (n, len(s), rank), op=op, op_index=i,
                      var=n)
            return
    ax = axis % rank if rank else 0
    base = shapes[0][1]
    for n, s in shapes[1:]:
        for d in range(rank):
            if d == ax:
                continue
            if not _dims_eq(base[d], s[d]):
                ctx.error('concat-mismatch',
                          'concat along axis %d but input %r dim %d '
                          '(=%d) != first input dim (=%d)'
                          % (ax, n, d, s[d], base[d]), op=op,
                          op_index=i, var=n)
                return


def _reshape(ctx, op, i, sparse):
    x = _in_shape(ctx, op, 'X')
    target = op.attr('shape')
    if x is None or not target:
        return
    target = list(target)
    for j, s in enumerate(target):
        if s == 0:
            target[j] = x[j] if j < len(x) else -1
    n_infer = sum(1 for s in target if s == -1)
    if n_infer > 1:
        ctx.error('reshape-mismatch',
                  'reshape target %s has %d inferred (-1) dims; at '
                  'most one is allowed' % (target, n_infer), op=op,
                  op_index=i, var=op.input('X'))
        return
    src = _prod(x)
    if src is None:
        return
    fixed = _prod([s for s in target if s != -1])
    if fixed is None or fixed == 0:
        return
    if n_infer == 0 and fixed != src:
        ctx.error('reshape-mismatch',
                  'reshape of X%s (%d elements) to %s (%d elements)'
                  % (list(x), src, target, fixed), op=op, op_index=i,
                  var=op.input('X'))
    elif n_infer == 1 and src % fixed:
        ctx.error('reshape-mismatch',
                  'reshape of X%s (%d elements) to %s: %d %% %d != 0, '
                  'the -1 dim cannot be inferred' % (list(x), src,
                                                     target, src, fixed),
                  op=op, op_index=i, var=op.input('X'))


def _transpose(ctx, op, i, sparse):
    x = _in_shape(ctx, op, 'X')
    axis = op.attr('axis')
    if x is None or axis is None:
        return
    if sorted(a % len(x) if len(x) else a for a in axis) != \
            list(range(len(x))):
        ctx.error('transpose-mismatch',
                  'transpose axis %s is not a permutation of rank %d'
                  % (list(axis), len(x)), op=op, op_index=i,
                  var=op.input('X'))


def _conv2d(ctx, op, i, sparse):
    x, w = _in_shape(ctx, op, 'Input'), _in_shape(ctx, op, 'Filter')
    _check_float(ctx, op, i, ('Input', 'Filter'))
    if x is None or w is None or len(x) != 4 or len(w) != 4:
        return
    groups = op.attr('groups', 1) or 1
    cin = x[3] if op.attr('data_format', 'NCHW') == 'NHWC' else x[1]
    if _known(cin, w[1]) and cin != w[1] * groups:
        ctx.error('channel-mismatch',
                  'conv2d input has %d channels but Filter%s expects '
                  '%d (groups=%d)' % (cin, list(w), w[1] * groups,
                                      groups), op=op, op_index=i,
                  var=op.input('Filter'))


def _fused_attention(ctx, op, i, sparse):
    """Either form of the op (ops/attention_ops.py): Q, K, V as the
    caller projected them, or X, Mem and the four weights, whose columns
    are then what the three feature dims were."""
    projected = op.input('Wq') is not None
    slots = ('Wq', 'Wk', 'Wv') if projected else ('Q', 'K', 'V')
    q, k, v = (_in_shape(ctx, op, slot) for slot in slots)
    n_head = op.attr('n_head', 1) or 1

    def mismatch(message, slot):
        ctx.error('attention-mismatch', message, op=op, op_index=i,
                  var=op.input(slot))
    for slot, s in zip(slots, (q, k, v)):
        if s is not None and _known(s[-1]) and s[-1] % n_head:
            mismatch('%s feature dim %d is not divisible by n_head=%d'
                     % (slot, s[-1], n_head), slot)
    if q is not None and k is not None and \
            not _dims_eq(q[-1], k[-1]):
        mismatch('%s%s and %s%s disagree on the key feature dim'
                 % (slots[0], list(q), slots[1], list(k)), slots[1])
    if not projected:
        if k is not None and v is not None and len(k) == len(v) and \
                len(k) >= 2 and not _dims_eq(k[-2], v[-2]):
            mismatch('K%s and V%s disagree on the source sequence dim'
                     % (list(k), list(v)), 'V')
        return
    # what each weight contracts: the model dim of the side it projects,
    # and for Wo the value features the heads hand it
    for src, slot in (('X', 'Wq'), ('Mem', 'Wk'), ('Mem', 'Wv'),
                      ('Wv', 'Wo')):
        a, w = _in_shape(ctx, op, src), _in_shape(ctx, op, slot)
        if a is not None and w is not None and len(w) == 2 and \
                not _dims_eq(a[-1], w[0]):
            mismatch('%s%s has %d rows but %s%s hands it %d features'
                     % (slot, list(w), w[0], src, list(a), a[-1]), slot)


def _layer_norm(ctx, op, i, sparse):
    x = _in_shape(ctx, op, 'X')
    if x is None:
        return
    begin = op.attr('begin_norm_axis', 1)
    norm = _prod(x[begin:])
    for slot in ('Scale', 'Bias'):
        s = _in_shape(ctx, op, slot)
        if s is None:
            continue
        n = _prod(s)
        if norm is not None and n is not None and n != norm:
            ctx.error('norm-shape-mismatch',
                      'layer_norm %s%s has %d elements but X%s '
                      'normalizes %d (begin_norm_axis=%d)'
                      % (slot, list(s), n, list(x), norm, begin),
                      op=op, op_index=i, var=op.input(slot))


def _batch_norm(ctx, op, i, sparse):
    x = _in_shape(ctx, op, 'X')
    if x is None:
        return
    layout = op.attr('data_layout', 'NCHW')
    c = x[-1] if (layout == 'NHWC' and len(x) == 4) else \
        (x[1] if len(x) >= 2 else None)
    if c is None or c < 0:
        return
    for slot in ('Scale', 'Bias', 'Mean', 'Variance'):
        s = _in_shape(ctx, op, slot)
        if s is None or not s:
            continue
        if _known(s[0]) and s[0] != c:
            ctx.error('norm-shape-mismatch',
                      'batch_norm %s has %d entries but X%s has %d '
                      'channels (%s)' % (slot, s[0], list(x), c,
                                         layout), op=op, op_index=i,
                      var=op.input(slot))


def _optimizer(ctx, op, i, sparse):
    pname = op.input('Param')
    p = None if pname is None else ctx.shape_of(pname)
    if p is None:
        return
    gname = op.input('Grad')
    if gname is not None and pname not in sparse:
        g = ctx.shape_of(gname)
        if g is not None and len(g) == len(p) and \
                not all(_dims_eq(a, b) for a, b in zip(p, g)):
            ctx.error('update-shape-mismatch',
                      '%s: Grad%s does not match Param %r %s'
                      % (op.type, list(g), pname, list(p)), op=op,
                      op_index=i, var=gname)
    for slot, names in op.inputs.items():
        if slot not in _STATE_SLOTS:
            continue
        for n in names:
            s = ctx.shape_of(n)
            if s is not None and (len(s) != len(p) or not all(
                    _dims_eq(a, b) for a, b in zip(p, s))):
                ctx.error('update-shape-mismatch',
                          '%s: state %s=%r %s does not match Param %r '
                          '%s' % (op.type, slot, n, list(s), pname,
                                  list(p)), op=op, op_index=i, var=n)


def _lookup_table(ctx, op, i, sparse):
    ids = op.input('Ids')
    if ids is not None:
        dt = ctx.dtype_of(ids)
        if dt is not None and dt not in _INTS:
            ctx.error('dtype-not-int',
                      'lookup_table Ids %r has dtype %s; embedding '
                      'indices must be integral' % (ids, dt), op=op,
                      op_index=i, var=ids)


def _cross_entropy(ctx, op, i, sparse):
    if op.attr('soft_label', False):
        return
    label = op.input('Label')
    if label is not None:
        dt = ctx.dtype_of(label)
        if dt is not None and dt in _FLOATS:
            ctx.error('dtype-not-int',
                      '%s Label %r has dtype %s; hard labels are '
                      'integral class ids (or set soft_label=True)'
                      % (op.type, label, dt), op=op, op_index=i,
                      var=label)


_CONTRACTS = {
    'mul': _mul,
    'matmul': _matmul,
    'concat': _concat,
    'reshape': _reshape,
    'transpose': _transpose,
    'conv2d': _conv2d,
    'fused_attention': _fused_attention,
    'layer_norm': _layer_norm,
    'batch_norm': _batch_norm,
    'lookup_table': _lookup_table,
    'cross_entropy': _cross_entropy,
    'softmax_with_cross_entropy': _cross_entropy,
}
