"""Executor semantics: feed/fetch, compile cache, pruning, errors
(reference: fluid/tests/test_executor_and_mul.py + framework/prune.cc
tests)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from util import rand


def test_missing_feed_raises():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    with pytest.raises(ValueError):
        exe.run(feed={}, fetch_list=[out])


def test_uninitialized_scope_raises():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(RuntimeError):
        exe.run(feed={'x': rand(2, 4)}, fetch_list=[out])


def test_compile_cache_reused_across_steps():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed={'x': rand(2, 4)}, fetch_list=[out])
    n_compiled = len(exe._cache)
    for _ in range(3):
        exe.run(feed={'x': rand(2, 4)}, fetch_list=[out])
    assert len(exe._cache) == n_compiled  # same shapes: no re-compile
    exe.run(feed={'x': rand(5, 4)}, fetch_list=[out])
    assert len(exe._cache) == n_compiled + 1  # new batch size: new entry


def test_run_and_run_steps_of_one_are_one_dispatch():
    """run and run_steps(1) of one program from one state: the same
    fetches and scope, and each a miss of its own key and kind."""
    from paddle_tpu import observe
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    feed = {'x': rand(8, 4), 'y': rand(8, 1)}

    def from_fresh_state(call):
        # an executor of its own: the initializers and the step fold
        # the executor's step index
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(fluid.default_startup_program())
            before = observe.snapshot()['counters']
            loss, = call(exe)
            after = observe.snapshot()['counters']
        missed = {k: v - before.get(k, 0) for k, v in after.items()
                  if k.startswith('executor.cache_miss_total')
                  and v != before.get(k, 0)}
        return np.asarray(loss).reshape(()), \
            {n: np.asarray(scope.find(n)) for n in scope.keys()}, missed

    observe.reset()
    observe.enable()
    try:
        one, scope_one, miss_one = from_fresh_state(
            lambda exe: exe.run(feed=feed, fetch_list=[cost]))
        many, scope_many, miss_many = from_fresh_state(
            lambda exe: exe.run_steps(1, feed=feed, fetch_list=[cost]))
    finally:
        observe.disable()
        observe.reset()
    assert one == many
    assert sorted(scope_one) == sorted(scope_many)
    for name, value in scope_one.items():
        np.testing.assert_array_equal(value, scope_many[name], name)
    (k_one, n_one), = miss_one.items()
    (k_many, n_many), = miss_many.items()
    assert n_one == n_many == 1
    assert 'kind=single' in k_one and 'kind=multi' in k_many


def test_prune_skips_unrelated_branches():
    """Fetching one branch must not require feeds of the other."""
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.data(name='y', shape=[3], dtype='float32')
    out_x = fluid.layers.fc(input=x, size=2)
    out_y = fluid.layers.fc(input=y, size=2)  # noqa: F841
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res = exe.run(feed={'x': rand(2, 4)}, fetch_list=[out_x])
    assert res[0].shape == (2, 2)


def test_fetch_intermediate_and_multiple():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    h = fluid.layers.fc(input=x, size=8, act='relu')
    out = fluid.layers.fc(input=h, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res = exe.run(feed={'x': rand(2, 4)}, fetch_list=[h, out, 'x'])
    assert res[0].shape == (2, 8)
    assert res[1].shape == (2, 2)
    assert res[2].shape == (2, 4)


def test_return_numpy_false_returns_device_arrays():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res = exe.run(feed={'x': rand(2, 4)}, fetch_list=[out],
                  return_numpy=False)
    assert hasattr(res[0], 'devices') or hasattr(res[0], 'device')


def test_two_programs_independent():
    prog_a = fluid.Program()
    prog_b = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(prog_a, startup):
        xa = fluid.layers.data(name='x', shape=[4], dtype='float32')
        out_a = fluid.layers.fc(input=xa, size=2)
    with fluid.program_guard(prog_b, startup):
        xb = fluid.layers.data(name='x', shape=[4], dtype='float32')
        out_b = fluid.layers.fc(input=xb, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    ra = exe.run(program=prog_a, feed={'x': rand(2, 4)}, fetch_list=[out_a])
    rb = exe.run(program=prog_b, feed={'x': rand(2, 4)}, fetch_list=[out_b])
    assert ra[0].shape == (2, 2)
    assert rb[0].shape == (2, 3)


def test_program_random_seed_reproducible():
    prog = fluid.default_main_program()
    prog.random_seed = 42
    u = fluid.layers.uniform_random(shape=[8], min=0., max=1.)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    a = exe.run(feed={}, fetch_list=[u])[0]
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(fluid.default_startup_program())  # same step sequence as exe
    b = exe2.run(feed={}, fetch_list=[u])[0]
    np.testing.assert_allclose(a, b)  # same seed, same step index
    c = exe2.run(feed={}, fetch_list=[u])[0]
    assert not np.allclose(a, c)  # next step: different draw


def test_startup_initializers():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    fluid.layers.fc(input=x, size=3,
                    param_attr=fluid.ParamAttr(
                        name='w_const',
                        initializer=fluid.initializer.Constant(0.5)),
                    bias_attr=fluid.ParamAttr(
                        name='b_const',
                        initializer=fluid.initializer.Constant(-1.0)))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    w = np.asarray(fluid.global_scope().find('w_const'))
    b = np.asarray(fluid.global_scope().find('b_const'))
    np.testing.assert_allclose(w, np.full((4, 3), 0.5))
    np.testing.assert_allclose(b, np.full((3,), -1.0))


def test_scope_guard_isolates_state():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    my_scope = fluid.Scope()
    with fluid.scope_guard(my_scope):
        exe.run(fluid.default_startup_program())
        res = exe.run(feed={'x': rand(2, 4)}, fetch_list=[out],
                      scope=my_scope)
    assert res[0].shape == (2, 2)
    assert len(list(fluid.global_scope().keys())) == 0


def test_bogus_fetch_target_raises_keyerror():
    x = fluid.layers.data(name='x', shape=[3], dtype='float32')
    out = fluid.layers.scale(x, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    with pytest.raises(KeyError):
        exe.run(feed={'x': np.zeros((2, 3), 'f')},
                fetch_list=['no_such_var'])


def test_batch_size_change_recompiles_correctly():
    x = fluid.layers.data(name='x', shape=[3], dtype='float32')
    out = fluid.layers.reduce_sum(x, dim=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    a = exe.run(feed={'x': np.ones((2, 3), 'f')}, fetch_list=[out])[0]
    b = exe.run(feed={'x': np.ones((5, 3), 'f')}, fetch_list=[out])[0]
    assert a.shape == (2,) and b.shape == (5,)
    np.testing.assert_allclose(b, 3.0)


def test_wrong_dtype_feed_autocasts():
    x = fluid.layers.data(name='x', shape=[3], dtype='float32')
    out = fluid.layers.scale(x, scale=1.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got = exe.run(feed={'x': np.ones((2, 3), dtype='float64')},
                  fetch_list=[out], return_numpy=False)[0]
    import jax.numpy as jnp
    assert got.dtype == jnp.float32


def _mlp_with_dropout():
    x = fluid.layers.data(name='x', shape=[8], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    h = fluid.layers.fc(input=x, size=16, act='relu',
                        param_attr=fluid.ParamAttr(name='ms_w1'))
    h = fluid.layers.dropout(h, dropout_prob=0.3)
    pred = fluid.layers.fc(input=h, size=1,
                           param_attr=fluid.ParamAttr(name='ms_w2'))
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(
        cost)
    return cost


def test_run_steps_matches_per_step_trajectory():
    """Executor.run_steps (training loop compiled into the XLA program
    via lax.scan) must reproduce the per-step Executor.run trajectory
    EXACTLY — including dropout masks (the per-op PRNG folds the same
    global step index on both paths) and optimizer accumulator state."""
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(16, 8).astype('f'),
            'y': rng.randn(16, 1).astype('f')}
    s1, s2 = fluid.Scope(), fluid.Scope()
    with fluid.scope_guard(s1):
        fluid.reset_default_programs()
        cost = _mlp_with_dropout()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        single = [float(np.asarray(exe.run(
            feed=feed, fetch_list=[cost])[0]).reshape(()))
            for _ in range(5)]
        w1 = np.asarray(s1.find('ms_w1'))
    with fluid.scope_guard(s2):
        fluid.reset_default_programs()
        cost = _mlp_with_dropout()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        multi = np.asarray(exe.run_steps(
            5, feed=feed, fetch_list=[cost])[0]).reshape(-1)
        w2 = np.asarray(s2.find('ms_w1'))
    np.testing.assert_allclose(multi, single, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w2, w1, rtol=1e-5, atol=1e-6)


def test_run_steps_stacked_feed():
    """stacked_feed=True: each step consumes its own slice of a
    [steps, ...] superbatch — equal to feeding the batches one by one."""
    rng = np.random.RandomState(1)
    xs = rng.randn(4, 16, 8).astype('f')
    ys = rng.randn(4, 16, 1).astype('f')
    s1, s2 = fluid.Scope(), fluid.Scope()
    with fluid.scope_guard(s1):
        fluid.reset_default_programs()
        cost = _mlp_with_dropout()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        single = [float(np.asarray(exe.run(
            feed={'x': xs[i], 'y': ys[i]},
            fetch_list=[cost])[0]).reshape(())) for i in range(4)]
    with fluid.scope_guard(s2):
        fluid.reset_default_programs()
        cost = _mlp_with_dropout()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        multi = np.asarray(exe.run_steps(
            4, feed={'x': xs, 'y': ys}, fetch_list=[cost],
            stacked_feed=True)[0]).reshape(-1)
    np.testing.assert_allclose(multi, single, rtol=1e-5, atol=1e-6)


def test_run_steps_stacked_feed_wrong_leading_dim():
    fluid.reset_default_programs()
    cost = _mlp_with_dropout()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    with pytest.raises(ValueError, match='leading'):
        exe.run_steps(3, feed={'x': np.zeros((2, 16, 8), 'f'),
                               'y': np.zeros((2, 16, 1), 'f')},
                      fetch_list=[cost], stacked_feed=True)


def test_rbg_prng_dropout_semantics(monkeypatch):
    """PADDLE_TPU_PRNG=rbg (the TPU default, executor._default_prng —
    +62% tok/s on chip): dropout still zeroes ~p of activations,
    differs across steps, and a same-seed rerun reproduces the
    trajectory exactly on a given backend."""
    monkeypatch.setenv('PADDLE_TPU_PRNG', 'rbg')

    def run_once():
        with fluid.scope_guard(fluid.Scope()):
            fluid.reset_default_programs()
            x = fluid.layers.data(name='x', shape=[512],
                                  dtype='float32')
            out = fluid.layers.dropout(x, dropout_prob=0.4)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            ones = np.ones((16, 512), 'f')
            masks = [exe.run(feed={'x': ones}, fetch_list=[out])[0]
                     for _ in range(3)]
        return masks

    a = run_once()
    b = run_once()
    for m in a:
        frac = float((m == 0).mean())
        assert 0.3 < frac < 0.5, frac          # ~p zeroed
    assert not np.array_equal(a[0], a[1])       # per-step keys differ
    for ma, mb in zip(a, b):                    # same-seed reproducible
        np.testing.assert_array_equal(ma, mb)


def test_run_steps_advances_lr_schedule():
    """The lr-decay step counter is in-graph persistable state; inside a
    run_steps window it must advance per inner step (scan carry), giving
    the same trajectory and final counter as per-step dispatch."""
    from paddle_tpu import learning_rate_decay as lrd

    def build():
        fluid.reset_default_programs()
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1)
        cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        lr = lrd.exponential_decay(learning_rate=0.5, decay_steps=2,
                                   decay_rate=0.5, staircase=True)
        fluid.optimizer.SGD(learning_rate=lr).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        return cost, exe

    rng = np.random.RandomState(2)
    feed = {'x': rng.randn(8, 4).astype('f'),
            'y': rng.randn(8, 1).astype('f')}
    s1, s2 = fluid.Scope(), fluid.Scope()
    with fluid.scope_guard(s1):
        cost, exe = build()
        single = [float(np.asarray(exe.run(
            feed=feed, fetch_list=[cost])[0]).reshape(()))
            for _ in range(6)]
        counter1 = int(np.asarray(
            s1.find('@LR_DECAY_COUNTER@')).reshape(()))
    with fluid.scope_guard(s2):
        cost, exe = build()
        multi = np.asarray(exe.run_steps(
            6, feed=feed, fetch_list=[cost])[0]).reshape(-1)
        counter2 = int(np.asarray(
            s2.find('@LR_DECAY_COUNTER@')).reshape(()))
    # 6 runs advance the counter identically on both paths (absolute
    # value is the begin-offset convention of the counter op)
    assert counter1 == counter2, (counter1, counter2)
    assert counter1 >= 5
    np.testing.assert_allclose(multi, single, rtol=1e-5, atol=1e-6)
    # the decay actually kicked in (loss scale changes across windows)
    assert not np.allclose(single[0], single[-1])


def test_error_clip_clamps_activation_gradient():
    """var.error_clip = ErrorClipByValue(...) clamps the cotangent
    flowing back through that var (reference fluid/clip.py ErrorClip +
    backward error_clip_callback; here a custom_vjp at lowering)."""
    def build(clip):
        fluid.reset_default_programs()
        x = fluid.layers.data(name='x', shape=[3], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1, bias_attr=False,
                               param_attr=fluid.ParamAttr(name='ec_w'))
        if clip:
            pred.error_clip = fluid.clip.ErrorClipByValue(max=0.01)
        loss = fluid.layers.reduce_sum(fluid.layers.scale(pred,
                                                          scale=100.0))
        fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        return loss, exe

    xs = np.ones((4, 3), 'f')
    s1, s2 = fluid.Scope(), fluid.Scope()
    with fluid.scope_guard(s1):
        loss, exe = build(clip=False)
        w0 = np.asarray(s1.find('ec_w'))
        exe.run(feed={'x': xs}, fetch_list=[loss])
        dw_unclipped = (w0 - np.asarray(s1.find('ec_w')))  # lr=1
    with fluid.scope_guard(s2):
        loss, exe = build(clip=True)
        w0 = np.asarray(s2.find('ec_w'))
        exe.run(feed={'x': xs}, fetch_list=[loss])
        dw_clipped = (w0 - np.asarray(s2.find('ec_w')))
    # unclipped cotangent is 100 per element -> dw = sum_b x = 4 * 100
    np.testing.assert_allclose(dw_unclipped, 400.0, rtol=1e-5)
    # clipped to 0.01 per element -> dw = 4 * 0.01
    np.testing.assert_allclose(dw_clipped, 0.04, rtol=1e-5)


def test_error_clip_set_after_run_invalidates_cache():
    """Setting var.error_clip AFTER a compiled run must bump the program
    version so the warm executor cache recompiles with the clamp."""
    fluid.reset_default_programs()
    x = fluid.layers.data(name='x', shape=[3], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1, bias_attr=False,
                           param_attr=fluid.ParamAttr(name='ec2_w'))
    loss = fluid.layers.reduce_sum(fluid.layers.scale(pred, scale=100.0))
    fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xs = np.ones((4, 3), 'f')
    scope = fluid.global_scope()
    w0 = np.asarray(scope.find('ec2_w'))
    exe.run(feed={'x': xs}, fetch_list=[loss])
    w1 = np.asarray(scope.find('ec2_w'))
    np.testing.assert_allclose(w0 - w1, 400.0, rtol=1e-5)
    pred.error_clip = fluid.clip.ErrorClipByValue(max=0.01)
    exe.run(feed={'x': xs}, fetch_list=[loss])
    w2 = np.asarray(scope.find('ec2_w'))
    # fp32 ulp at |w|~400 dominates the 0.04 delta -> atol
    np.testing.assert_allclose(w1 - w2, 0.04, atol=2e-3)
