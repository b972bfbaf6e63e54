"""Convolution / pooling ops.

Reference: paddle/fluid/operators/{conv_op,conv_transpose_op,pool_op}.cc.
IR semantics stay NCHW for reference-parity; the layout knob only
changes the lax.conv dimension numbers inside the lowering (boundary
transposes cancel in XLA). On TPU the default is NHWC: channels-last
matches the (8,128) vector tiling (one layout against the other on the
chip: not measured, no cell trains a convolutional model; ROADMAP S8).
PADDLE_TPU_CONV_LAYOUT=NCHW|NHWC overrides; numerics are identical
either way (tests/test_amp.py::test_nhwc_conv_layout_matches_nchw).
"""

import os

import jax
import jax.numpy as jnp

from ..core.registry import register


def _conv_layout():
    env = os.environ.get('PADDLE_TPU_CONV_LAYOUT')
    if env:
        return env.upper()
    from ..core.platform_boot import is_tpu_backend
    return 'NHWC' if is_tpu_backend() else 'NCHW'


def _s2d_stem(x_nhwc, w_oihw):
    """Space-to-depth rewrite of the ResNet stem conv (k=7, s=2, p=3,
    small Cin): exactly equivalent to the original conv, but over a
    2x2-space-to-depth input — [B, H/2, W/2, 4*Cin] with a 4x4 stride-1
    kernel — so the contraction dim grows 4x toward the MXU's 128 lanes
    and the stride-2 pattern disappears (the MLPerf ResNet stem trick).

    Derivation: out[y,x,o] = Σ_{dy,dx,c} w[dy,dx,c,o]·in[2y+dy-3, ...].
    Write 2y+dy-3 = 2(y+uy)+py with py=(dy+1)%2, uy=(dy-3-py)//2 ∈
    [-2,1]: a 4-tap stride-1 conv over the (py,c)-stacked planes with
    asymmetric padding (2,1); kernel slot (uy,py) holds w[2uy+py+3]
    (the single out-of-range slot dy=-1 is zero)."""
    b, h, wdt, c = x_nhwc.shape
    # [B, H/2, 2, W/2, 2, C] -> [B, H/2, W/2, 2, 2, C] -> merge
    x2 = x_nhwc.reshape(b, h // 2, 2, wdt // 2, 2, c) \
        .transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, wdt // 2, 4 * c)
    o = w_oihw.shape[0]
    # build w2[uy+2, ux+2, (py,px,c), o] = w[o, c, 2uy+py+3, 2ux+px+3]
    w_hwio = w_oihw.transpose(2, 3, 1, 0)  # [7,7,C,O]
    wp = jnp.pad(w_hwio, [(1, 0), (1, 0), (0, 0), (0, 0)])  # dy=-1 slot
    # wp index = dy+1 = 2uy+py+4 = 2(uy+2)+py: reshape [4,2,4,2,C,O]
    w2 = wp.reshape(4, 2, 4, 2, c, o).transpose(0, 2, 1, 3, 4, 5) \
        .reshape(4, 4, 4 * c, o)
    return jax.lax.conv_general_dilated(
        x2, w2, window_strides=(1, 1), padding=[(2, 1), (2, 1)],
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def _s2d_applicable(x_nhwc, w, strides, pads, dilations, groups):
    if os.environ.get('PADDLE_TPU_CONV_S2D', '0') != '1':
        return False
    return (w.shape[2] == 7 and w.shape[3] == 7 and strides == (2, 2)
            and tuple(pads) in ((3, 3), (3, 3, 3, 3))
            and dilations == (1, 1) and groups == 1
            and w.shape[1] <= 4 and x_nhwc.shape[1] % 2 == 0
            and x_nhwc.shape[2] % 2 == 0)


@register('conv2d')
def _conv2d(ctx):
    x = ctx.input('Input')  # NCHW (or NHWC when data_format says so)
    w = ctx.input('Filter')  # OIHW (parameter layout is fixed either way)
    strides = tuple(ctx.attr('strides', [1, 1]))
    pads = ctx.attr('paddings', [0, 0])
    dilations = tuple(ctx.attr('dilations', [1, 1]))
    groups = ctx.attr('groups', 1)
    padding = [(pads[0], pads[0]), (pads[1], pads[1])] if len(pads) == 2 \
        else [(pads[0], pads[1]), (pads[2], pads[3])]
    pref = x.dtype if x.dtype == jnp.float32 else None
    if ctx.attr('data_format', 'NCHW') == 'NHWC':
        if _s2d_applicable(x, w, strides, pads, dilations, groups):
            ctx.set_output('Output', _s2d_stem(x, w))
            return
        # Activations are NHWC *in the IR* (layers.conv2d data_format=
        # 'NHWC'): no boundary transposes at all — the whole network
        # stays channels-last end-to-end, which is the TPU-native
        # layout ((8,128) vector tiling over W,C).
        out = jax.lax.conv_general_dilated(
            x, w.transpose(2, 3, 1, 0),
            window_strides=strides, padding=padding,
            rhs_dilation=dilations, feature_group_count=groups,
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            preferred_element_type=pref)
    elif _conv_layout() == 'NHWC':
        out = jax.lax.conv_general_dilated(
            x.transpose(0, 2, 3, 1), w.transpose(2, 3, 1, 0),
            window_strides=strides, padding=padding,
            rhs_dilation=dilations, feature_group_count=groups,
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            preferred_element_type=pref).transpose(0, 3, 1, 2)
    else:
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            rhs_dilation=dilations, feature_group_count=groups,
            dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
            preferred_element_type=pref)
    ctx.set_output('Output', out)


@register('conv2d_transpose')
def _conv2d_transpose(ctx):
    """Fractionally-strided conv: lhs_dilation=stride + flipped kernel,
    the gradient-of-conv formulation XLA lowers best on TPU.
    out = (in-1)*stride - 2*pad + dilation*(k-1) + 1 (conv_transpose_op.cc).
    """
    x = ctx.input('Input')  # NCHW
    w = ctx.input('Filter')  # paddle layout [Cin, Cout/groups, kh, kw]
    strides = tuple(ctx.attr('strides', [1, 1]))
    pads = ctx.attr('paddings', [0, 0])
    dilations = tuple(ctx.attr('dilations', [1, 1]))
    groups = ctx.attr('groups', 1)
    cin, cout_g, kh, kw = w.shape
    # -> [Cout, Cin/groups, kh, kw], spatially flipped
    w_t = w.reshape(groups, cin // groups, cout_g, kh, kw)
    w_t = w_t.swapaxes(1, 2).reshape(groups * cout_g, cin // groups, kh, kw)
    w_t = jnp.flip(w_t, axis=(2, 3))
    padding = [(dilations[i] * ([kh, kw][i] - 1) - pads[i],) * 2
               for i in range(2)]
    out = jax.lax.conv_general_dilated(
        x, w_t, window_strides=(1, 1), padding=padding,
        lhs_dilation=strides, rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    ctx.set_output('Output', out)


@register('conv3d')
def _conv3d(ctx):
    x = ctx.input('Input')  # NCDHW
    w = ctx.input('Filter')  # OIDHW
    strides = tuple(ctx.attr('strides', [1, 1, 1]))
    pads = ctx.attr('paddings', [0, 0, 0])
    dilations = tuple(ctx.attr('dilations', [1, 1, 1]))
    groups = ctx.attr('groups', 1)
    padding = [(p, p) for p in pads]
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=('NCDHW', 'OIDHW', 'NCDHW'))
    ctx.set_output('Output', out)


def _pool2d_impl(x, pooling_type, ksize, strides, pads, global_pooling,
                 ceil_mode=False, exclusive=True, adaptive=False,
                 data_format='NCHW'):
    if data_format == 'NHWC':
        n, h, w, c = x.shape
        spatial = (1, 2)
    else:
        n, c, h, w = x.shape
        spatial = (2, 3)
    if global_pooling or (adaptive and tuple(ksize) == (1, 1)):
        if pooling_type == 'max':
            return x.max(axis=spatial, keepdims=True)
        return x.mean(axis=spatial, keepdims=True)
    kh, kw = ksize
    sh, sw = strides
    ph, pw = pads
    eh = ew = 0
    if ceil_mode:
        # pad extra on the bottom/right so ceil-division windows fit
        eh = max(0, (-(h + 2 * ph - kh) % sh))
        ew = max(0, (-(w + 2 * pw - kw) % sw))
    if data_format == 'NHWC':
        window = (1, kh, kw, 1)
        stride = (1, sh, sw, 1)
        padding = ((0, 0), (ph, ph + eh), (pw, pw + ew), (0, 0))
        ones_shape = (1, h, w, 1)
    else:
        window = (1, 1, kh, kw)
        stride = (1, 1, sh, sw)
        padding = ((0, 0), (0, 0), (ph, ph + eh), (pw, pw + ew))
        ones_shape = (1, 1, h, w)
    if pooling_type == 'max':
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, stride,
                                     padding)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, stride,
                                   padding)
    if exclusive and (ph or pw or ceil_mode):
        ones = jnp.ones(ones_shape, dtype=x.dtype)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                       stride, padding)
        return summed / jnp.maximum(counts, 1.0)
    return summed / (kh * kw)


@register('pool2d')
def _pool2d(ctx):
    x = ctx.input('X')
    out = _pool2d_impl(
        x,
        ctx.attr('pooling_type', 'max'),
        ctx.attr('ksize', [2, 2]),
        ctx.attr('strides', [2, 2]) if not ctx.attr('global_pooling', False)
        else [1, 1],
        ctx.attr('paddings', [0, 0]),
        ctx.attr('global_pooling', False),
        ceil_mode=ctx.attr('ceil_mode', False),
        exclusive=ctx.attr('exclusive', True),
        data_format=ctx.attr('data_format', 'NCHW'))
    ctx.set_output('Out', out)


@register('row_conv')
def _row_conv(ctx):
    """row_conv_op.cc (lookahead conv for DeepSpeech): out[t] =
    sum_{i=0..k-1} w[i] * x[t+i], per feature."""
    x = ctx.input('X')  # [batch, seq, dim] (padded dense form)
    w = ctx.input('Filter')  # [k, dim]
    k = w.shape[0]
    pads = [(0, 0), (0, k - 1), (0, 0)]
    xp = jnp.pad(x, pads)
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1], :] * w[i]
    ctx.set_output('Out', out)


@register('conv_shift')
def _conv_shift(ctx):
    """conv_shift_op.cc: circular convolution (NTM addressing)."""
    x = ctx.input('X')  # [b, m]
    y = ctx.input('Y')  # [b, n], n odd, n <= m
    b, m = x.shape
    n = y.shape[1]
    half = n // 2
    idx = (jnp.arange(m)[:, None] + jnp.arange(-half, half + 1)[None, :]) % m
    gathered = x[:, idx]  # [b, m, n]
    ctx.set_output('Out', jnp.einsum('bmn,bn->bm', gathered, y))


@register('spp')
def _spp(ctx):
    """Spatial pyramid pooling (spp_op.cc)."""
    x = ctx.input('X')
    levels = ctx.attr('pyramid_height', 2)
    pooling_type = ctx.attr('pooling_type', 'max')
    n, c, h, w = x.shape
    outs = []
    for lv in range(levels):
        bins = 2 ** lv
        kh, kw = -(-h // bins), -(-w // bins)
        sh, sw = kh, kw
        out = _pool2d_impl(x, pooling_type, [kh, kw], [sh, sw], [0, 0], False,
                           ceil_mode=True)
        outs.append(out.reshape(n, -1))
    ctx.set_output('Out', jnp.concatenate(outs, axis=1))
