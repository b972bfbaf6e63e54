"""amp='bf16' end-to-end: the codepath the benchmark's train cell runs
(benchmark/configs/tbig_nmt.json: "amp": "bf16"). Whitelist ops (mul/conv/attention) compute in
bfloat16 on the MXU; blacklist ops (softmax/norms/losses) stay fp32;
master weights stay fp32 in the scope (registry.py AMP policy)."""

import numpy as np

import paddle_tpu as fluid
from util import rand


def _train(amp, steps=15, seed=0):
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    img = fluid.layers.data(name='img', shape=[1, 12, 12], dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    # bias_attr=False: the fp32 bias-add would promote the activation
    # back to fp32 (per-op promotion policy), which is fine for training
    # but would blur the in-graph dtype assertion below.
    conv = fluid.layers.conv2d(img, num_filters=6, filter_size=3,
                               act='relu', bias_attr=False,
                               param_attr=fluid.ParamAttr(
                                   name='amp_conv_w'))
    pool = fluid.layers.pool2d(conv, pool_size=2, pool_stride=2)
    logits = fluid.layers.fc(input=pool, size=10, act='softmax')
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=logits, label=label))
    fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
    if amp:
        fluid.default_main_program().amp = amp
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(seed)
    xs = rng.rand(32, 1, 12, 12).astype('float32')
    ys = (xs.sum((1, 2, 3), keepdims=False)[:, None] > 36).astype('int64')
    losses = []
    for _ in range(steps):
        losses.append(float(np.asarray(
            exe.run(feed={'img': xs, 'label': ys},
                    fetch_list=[loss])[0]).reshape(())))
    return losses, conv


def test_bf16_lenet_loss_decreases():
    losses, _ = _train('bf16')
    assert losses[-1] < losses[0] * 0.7, losses
    assert np.isfinite(losses).all()


def test_bf16_tracks_fp32():
    """bf16 training must land near the fp32 trajectory (not diverge)."""
    l32, _ = _train(None)
    l16, _ = _train('bf16')
    assert abs(l16[-1] - l32[-1]) < 0.15, (l32[-1], l16[-1])


def test_bf16_dtypes_in_graph_and_scope():
    """Whitelist op outputs are bfloat16 in-graph; master weights stay
    float32 in the scope."""
    import jax.numpy as jnp
    losses, conv = _train('bf16', steps=1)
    fluid_prog = fluid.default_main_program()
    assert fluid_prog.amp == 'bf16'
    # conv activation inside the jitted graph is bf16
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(1)
    xs = rng.rand(4, 1, 12, 12).astype('float32')
    ys = np.zeros((4, 1), 'int64')
    out = exe.run(program=fluid_prog, feed={'img': xs, 'label': ys},
                  fetch_list=[conv], return_numpy=False)[0]
    assert out.dtype == jnp.bfloat16, out.dtype
    # master weights in scope stay fp32
    w = fluid.global_scope().find('amp_conv_w')
    assert np.asarray(w).dtype == np.float32


def test_bf16_resnet_tiny_e2e():
    from paddle_tpu.models.resnet import resnet_cifar10
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    img = fluid.layers.data(name='image', shape=[3, 16, 16],
                            dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    net = resnet_cifar10(img, depth=8)
    logits = fluid.layers.fc(input=net, size=10, act='softmax')
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=logits, label=label))
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xs = rand(8, 3, 16, 16, seed=2)
    ys = np.arange(8).reshape(-1, 1).astype('int64') % 10
    first = last = None
    for _ in range(12):
        val = float(np.asarray(exe.run(
            feed={'image': xs, 'label': ys},
            fetch_list=[loss])[0]).reshape(()))
        first = val if first is None else first
        last = val
    assert np.isfinite(last)
    assert last < first, (first, last)


def _train_bn(steps=10, seed=3):
    """conv->bn->fc under amp; returns (losses, bn_out_var)."""
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    img = fluid.layers.data(name='img', shape=[3, 12, 12], dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    conv = fluid.layers.conv2d(img, num_filters=8, filter_size=3,
                               bias_attr=False)
    bn = fluid.layers.batch_norm(input=conv, act='relu')
    logits = fluid.layers.fc(input=bn, size=10, act='softmax')
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=logits, label=label))
    fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(seed)
    xs = rng.rand(16, 3, 12, 12).astype('float32')
    ys = (xs.sum((1, 2, 3))[:, None] > 216).astype('int64')
    losses = []
    for _ in range(steps):
        losses.append(float(np.asarray(exe.run(
            feed={'img': xs, 'label': ys},
            fetch_list=[loss])[0]).reshape(())))
    return losses, bn


def test_bn_bf16_compute_default(monkeypatch):
    """Under amp the BN elementwise path stays bf16 (the +13% on-chip
    lever, norm_ops._bn_bf16_compute): the BN activation is bfloat16
    in-graph while running statistics stay fp32 in the scope."""
    import jax.numpy as jnp
    monkeypatch.delenv('PADDLE_TPU_BN_COMPUTE', raising=False)
    losses, bn = _train_bn()
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(4)
    out = exe.run(program=fluid.default_main_program(),
                  feed={'img': rng.rand(4, 3, 12, 12).astype('float32'),
                        'label': np.zeros((4, 1), 'int64')},
                  fetch_list=[bn], return_numpy=False)[0]
    assert out.dtype == jnp.bfloat16, out.dtype
    # running statistics (persistable scope state) remain fp32
    stats = [n for n in fluid.global_scope().keys()
             if 'batch_norm' in n and ('mean' in n or 'variance' in n)]
    assert stats, 'no BN statistics vars found in scope'
    for n in stats:
        assert np.asarray(fluid.global_scope().find(n)).dtype == np.float32


def test_bn_bf16_tracks_fp32_compute(monkeypatch):
    """PADDLE_TPU_BN_COMPUTE=fp32 (the ablation knob) must follow the
    same training trajectory as the bf16 default."""
    monkeypatch.delenv('PADDLE_TPU_BN_COMPUTE', raising=False)
    l16, _ = _train_bn()
    monkeypatch.setenv('PADDLE_TPU_BN_COMPUTE', 'fp32')
    l32, _ = _train_bn()
    np.testing.assert_allclose(l16, l32, rtol=5e-2, atol=5e-3)


def test_nhwc_conv_layout_matches_nchw(monkeypatch):
    """PADDLE_TPU_CONV_LAYOUT=NHWC is numerics-identical (the
    ablation flag, SURVEY §5)."""
    l_nchw, _ = _train('bf16', steps=5)
    monkeypatch.setenv('PADDLE_TPU_CONV_LAYOUT', 'NHWC')
    l_nhwc, _ = _train('bf16', steps=5)
    np.testing.assert_allclose(l_nchw, l_nhwc, rtol=2e-2, atol=1e-3)


def _train_native_layout(fmt, steps=3):
    """Small residual conv net built natively in `fmt` (models/resnet.py
    building blocks with data_format threaded through the IR)."""
    from paddle_tpu.models.resnet import conv_bn_layer, basicblock

    fluid.reset_default_programs()
    fluid.global_scope().clear()
    fluid.default_main_program().random_seed = 7
    img = fluid.layers.data(name='image', shape=[3, 16, 16],
                            dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    x = img
    if fmt == 'NHWC':
        x = fluid.layers.transpose(x, [0, 2, 3, 1])
    x = conv_bn_layer(x, 8, 3, 1, 1, data_format=fmt)
    x = fluid.layers.pool2d(x, pool_size=3, pool_type='max', pool_stride=2,
                            pool_padding=1, data_format=fmt)
    x = basicblock(x, 8, 1, data_format=fmt)
    x = basicblock(x, 16, 2, data_format=fmt)
    x = fluid.layers.pool2d(x, pool_type='avg', global_pooling=True,
                            data_format=fmt)
    pred = fluid.layers.fc(x, size=10, act='softmax')
    cost = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(cost)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {'image': rng.rand(4, 3, 16, 16).astype('float32'),
            'label': rng.randint(0, 10, (4, 1)).astype('int64')}
    return [float(np.asarray(exe.run(feed=feed, fetch_list=[cost])[0])
                  .reshape(())) for _ in range(steps)]


def test_native_nhwc_network_matches_nchw():
    """data_format='NHWC' through the IR (conv2d/pool2d/batch_norm +
    resnet blocks — the transpose-free TPU layout) trains identically to
    the NCHW build: same seed, same feed, same loss trajectory."""
    l_nchw = _train_native_layout('NCHW')
    l_nhwc = _train_native_layout('NHWC')
    np.testing.assert_allclose(l_nchw, l_nhwc, rtol=2e-4, atol=2e-5)


def test_resnet50_data_format_arg_builds_nhwc_shapes():
    """resnet50_with_loss(data_format='NHWC') produces channels-last
    activation shapes in the IR while the feed stays NCHW."""
    from paddle_tpu.models.resnet import resnet50_with_loss

    fluid.reset_default_programs()
    _, cost, _ = resnet50_with_loss(image_shape=(3, 64, 64), class_dim=10,
                                    data_format='NHWC')
    block = fluid.default_main_program().global_block()
    # every conv output is NHWC: channel dim (last) matches the filter
    # count
    for op in block.ops:
        if op.type != 'conv2d':
            continue
        shape = block.var(op.output('Output')).shape
        n_filters = block.var(op.input('Filter')).shape[0]
        assert shape[-1] == n_filters, (shape, n_filters)
    assert any(op.type == 'transpose' for op in block.ops)


def test_mobilenet_native_nhwc_matches_nchw():
    """MobileNet's depthwise/pointwise stack threads data_format too
    (depthwise convs are the layout-sensitive case: feature_group_count
    = C with HWIO filters)."""
    from paddle_tpu.models.mobilenet import mobile_net

    def run(fmt):
        fluid.reset_default_programs()
        fluid.global_scope().clear()
        fluid.default_main_program().random_seed = 5
        img = fluid.layers.data(name='img', shape=[3, 32, 32],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        pred = mobile_net(img, class_dim=10, scale=0.25, data_format=fmt)
        cost = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(1)
        feed = {'img': rng.rand(4, 3, 32, 32).astype('f'),
                'label': rng.randint(0, 10, (4, 1)).astype('int64')}
        return [float(np.asarray(exe.run(feed=feed,
                                         fetch_list=[cost])[0]).reshape(()))
                for _ in range(3)]

    np.testing.assert_allclose(run('NCHW'), run('NHWC'),
                               rtol=2e-4, atol=2e-5)


def test_s2d_stem_matches_direct_conv(monkeypatch):
    """PADDLE_TPU_CONV_S2D=1 rewrites the ResNet stem conv (7x7 s2 p3,
    small Cin, NHWC-native) onto a space-to-depth 4x4 s1 conv — exact
    math, MXU-friendlier contraction (the MLPerf stem trick)."""
    def _stem(steps=3):
        fluid.reset_default_programs()
        fluid.global_scope().clear()
        fluid.default_main_program().random_seed = 11
        img = fluid.layers.data(name='image', shape=[3, 32, 32],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        x = fluid.layers.transpose(img, [0, 2, 3, 1])
        x = fluid.layers.conv2d(input=x, num_filters=16, filter_size=7,
                                stride=2, padding=3, bias_attr=False,
                                data_format='NHWC')
        x = fluid.layers.pool2d(x, pool_type='avg', global_pooling=True,
                                data_format='NHWC')
        pred = fluid.layers.fc(x, size=10, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9) \
            .minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(3)
        feed = {'image': rng.rand(4, 3, 32, 32).astype('float32'),
                'label': rng.randint(0, 10, (4, 1)).astype('int64')}
        return [float(np.asarray(exe.run(feed=feed,
                                         fetch_list=[loss])[0]).reshape(()))
                for _ in range(steps)]

    monkeypatch.delenv('PADDLE_TPU_CONV_S2D', raising=False)
    base = _stem()
    monkeypatch.setenv('PADDLE_TPU_CONV_S2D', '1')
    s2d = _stem()
    np.testing.assert_allclose(base, s2d, rtol=1e-4, atol=1e-5)


def test_lstm_under_bf16_amp_trains():
    """RNN ops under amp: uniform bf16 inputs (AMP_WHITELIST) and a
    dtype-pinned scan carry — regression: a fp32 weight against the
    bf16 pre-projection used to promote h mid-scan and break lax.scan's
    carry contract."""
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    x = fluid.layers.data(name='x', shape=[-1, 8], dtype='float32',
                          lod_level=1)
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    proj = fluid.layers.fc(input=x, size=24, num_flatten_dims=2,
                           bias_attr=False)
    h, _ = fluid.layers.dynamic_lstm(input=proj, size=24)
    g = fluid.layers.dynamic_gru(
        input=fluid.layers.fc(input=x, size=15, num_flatten_dims=2,
                              bias_attr=False), size=5)
    last = fluid.layers.concat([fluid.layers.sequence_last_step(h),
                                fluid.layers.sequence_last_step(g)],
                               axis=-1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(
        fluid.layers.fc(input=last, size=1), y))
    fluid.optimizer.Adam(learning_rate=5e-3).minimize(cost)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(4, 6, 8).astype('float32'),
            'y': rng.randn(4, 1).astype('float32')}
    losses = [np.asarray(exe.run(feed=feed,
                                 fetch_list=[cost])[0]).item()
              for _ in range(10)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
