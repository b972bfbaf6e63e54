"""Training raw speed (ISSUE 19): bucketed backward/allreduce overlap
(deterministic size-targeted assignment, bit-identical exact path,
per-call PADDLE_TPU_GRAD_BUCKET_MB knob), fp8(e4m3) matmul (parity,
straight-through gradients, tuner-table dispatch with the explicit
PADDLE_TPU_FP8_MATMUL gate beating the table), ZeRO-1 sharded optimizer
state (bit-identity, analytic memory ledger + gauges, env override),
the overlap-fraction gauge, the quantized+bucketed composition bound,
and the analysis pass's zero-* contracts."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import observe, tuning
from paddle_tpu.parallel.collective import (assign_grad_buckets,
                                            grad_bucket_policy)
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.transpiler import (ParallelStrategy,
                                            optimizer_state_bytes,
                                            shard_opt_state_env,
                                            transpile)

DP = 8
IN, HID, BATCH = 16, 32, 4


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ('PADDLE_TPU_GRAD_BUCKET_MB', 'PADDLE_TPU_SHARD_OPT_STATE',
                'PADDLE_TPU_FP8_MATMUL', 'PADDLE_TPU_AUTOTUNE',
                'PADDLE_TPU_TUNING_TABLE', 'PADDLE_TPU_QUANT_ALLREDUCE'):
        monkeypatch.delenv(var, raising=False)
    yield
    tuning.set_timer(None)
    tuning.reset()
    observe.disable()
    observe.reset()


# ------------------------------------------------- bucket assignment
def test_bucket_assignment_reversed_and_size_targeted():
    # parameter order w1 b1 w2 b2; the walk is REVERSED (backward
    # production order) and greedy against the byte target
    items = [(2048, 'float32'), (128, 'float32'),
             (128, 'float32'), (4, 'float32')]
    buckets = assign_grad_buckets(items, 104)
    # every index exactly once, last params first
    assert sorted(i for b in buckets for i in b) == [0, 1, 2, 3]
    assert buckets[0][0] == 3
    assert len(buckets) == 4          # 4+128 > 104 closes immediately
    # a roomier target merges the small tail grads into one bucket
    buckets = assign_grad_buckets(items, 1024)
    assert buckets[0] == [3, 2, 1]    # 4+128+128 <= 1024
    assert buckets[1] == [0]          # 2048 alone exceeds the target
    # deterministic: identical inputs, identical assignment
    assert assign_grad_buckets(items, 1024) == \
        assign_grad_buckets(list(items), 1024)


def test_bucket_assignment_group_change_closes():
    # buckets never mix dtype groups — concatenation must not promote
    items = [(8, 'float32'), (8, 'float32'),
             (8, 'bfloat16'), (8, 'bfloat16')]
    buckets = assign_grad_buckets(items, 1 << 20)
    assert buckets == [[3, 2], [1, 0]]


def test_bucket_assignment_oversized_and_edge():
    assert assign_grad_buckets([(999, 'f4')], 10) == [[0]]
    assert assign_grad_buckets([], 10) == []


def test_grad_bucket_policy_env_beats_program(monkeypatch):
    prog = types.SimpleNamespace(grad_bucket_mb=2.0)
    assert grad_bucket_policy(prog) == ('mb', 2.0)
    assert grad_bucket_policy(types.SimpleNamespace()) is None
    monkeypatch.setenv('PADDLE_TPU_GRAD_BUCKET_MB', '4')
    assert grad_bucket_policy(prog) == ('mb', 4.0)
    assert grad_bucket_policy(None) == ('mb', 4.0)
    for off in ('0', 'off', 'false'):
        monkeypatch.setenv('PADDLE_TPU_GRAD_BUCKET_MB', off)
        assert grad_bucket_policy(prog) is None
    monkeypatch.setenv('PADDLE_TPU_GRAD_BUCKET_MB', '')
    assert grad_bucket_policy(prog) == ('mb', 2.0)   # blank = unset


# --------------------------------------------------- e2e train legs
def _train(bucket_mb=None, shard_opt=False, quant_on=False, opt='sgd',
           dp=DP, steps=8, seed=3):
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    x = fluid.layers.data(name='x', shape=[IN], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    h = fluid.layers.fc(input=x, size=HID, act='relu')
    pred = fluid.layers.fc(input=h, size=1, act=None)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    if opt == 'adam':
        fluid.optimizer.Adam(learning_rate=0.125).minimize(cost)
    else:
        fluid.optimizer.SGD(learning_rate=0.125).minimize(cost)
    prog = fluid.default_main_program()
    prog.random_seed = 7
    transpile(prog, make_mesh(dp=dp), ParallelStrategy(
        grad_bucket_mb=bucket_mb,
        shard_optimizer_state=True if shard_opt else None,
        quantized_allreduce=quant_on))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    # dyadic feed values (k/8): dp partial sums are exact in fp32 under
    # any association, so bit-identity asserts are meaningful
    rng = np.random.RandomState(seed)
    X = (rng.randint(-8, 8, (BATCH * dp, IN)) / 8.0).astype('float32')
    Y = (rng.randint(-8, 8, (BATCH * dp, 1)) / 8.0).astype('float32')
    losses = []
    for _ in range(steps):
        got = exe.run(feed={'x': X, 'y': Y}, fetch_list=[cost])
        losses.append(float(np.asarray(got[0]).reshape(())))
    weights = {p.name: np.asarray(fluid.global_scope().find(p.name))
               for p in prog.all_parameters()}
    return losses, weights, prog


def test_bucketed_bit_identical_across_bucket_sizes():
    """The exact bucketed path is a pure relayout: any bucket size must
    give the same bits as the unbucketed allreduce."""
    observe.enable()
    _, w_ref, _ = _train()
    for mb in (0.004, 1e-4):
        _, w_b, _ = _train(bucket_mb=mb)
        for k in w_ref:
            assert np.array_equal(w_ref[k], w_b[k]), (mb, k)
    g = observe.snapshot()['gauges']
    # the 1e-4MB (104-byte) leg ran last: every grad but the biases
    # exceeds the target, so the net splits into several buckets
    assert g.get('trainer.grad_bucket_count', 0) >= 2
    assert g.get('trainer.grad_bucket_target_bytes') == int(1e-4 * 2**20)
    assert g.get('trainer.grad_bucket_max_bytes', 0) >= IN * HID * 4


def test_bucketed_env_knob_per_call(monkeypatch):
    """PADDLE_TPU_GRAD_BUCKET_MB=0 disables bucketing even when the
    strategy asked for it — and the run stays bit-identical."""
    observe.enable()
    _, w_ref, _ = _train()
    monkeypatch.setenv('PADDLE_TPU_GRAD_BUCKET_MB', '0')
    _, w_off, prog = _train(bucket_mb=1e-4)
    assert grad_bucket_policy(prog) is None
    for k in w_ref:
        assert np.array_equal(w_ref[k], w_off[k])


# ------------------------------------------------------- fp8 matmul
def _skip_no_fp8():
    from paddle_tpu.ops.fp8_matmul import fp8_supported
    if not fp8_supported():
        pytest.skip('jax build has no float8_e4m3fn')


def test_fp8_matmul_parity_and_straight_through_grads():
    _skip_no_fp8()
    from paddle_tpu.ops.fp8_matmul import fp8_matmul
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(48, 32).astype('float32'))
    b = jnp.asarray(rng.randn(32, 24).astype('float32'))
    ref = np.asarray(jnp.matmul(a, b))
    got = np.asarray(fp8_matmul(a, b))
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < 0.05, rel
    assert got.dtype == ref.dtype
    # straight-through vjp: gradients are the f32 matmul vjp — fp8
    # quantization error (~4e-2) must not leak into the backward
    gx, gy = jax.grad(lambda x, y: fp8_matmul(x, y).sum(),
                      argnums=(0, 1))(a, b)
    rx, ry = jax.grad(lambda x, y: jnp.matmul(x, y).sum(),
                      argnums=(0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gy), np.asarray(ry), rtol=1e-6)


def test_fp8_dispatch_table_and_env_gate(tmp_path, monkeypatch):
    """Dispatch discipline: fp8 runs only where the tuner measured a
    win; the explicit env gate beats the table in either direction."""
    _skip_no_fp8()
    from paddle_tpu.ops.fp8_matmul import maybe_fp8_matmul
    observe.enable()
    a = jnp.ones((32, 32), jnp.float32)
    b = jnp.ones((32, 32), jnp.float32)

    def count():
        return observe.snapshot()['counters'].get(
            'fp8.matmul_dispatch_total', 0)

    # no table, no gate -> no dispatch (autotune off by default)
    assert maybe_fp8_matmul(a, b) is None
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'record')
    # fp8-winning table -> dispatched, counter moves
    monkeypatch.setenv('PADDLE_TPU_TUNING_TABLE',
                       str(tmp_path / 'fp8_wins.json'))
    tuning.reset()
    tuning.set_timer(lambda op, key, v, t:
                     0.001 if v.get('impl') == 'fp8' else 0.010)
    c0 = count()
    out = maybe_fp8_matmul(a, b)
    assert out is not None
    assert np.allclose(np.asarray(out), 32.0, rtol=0.05)
    assert count() == c0 + 1
    # gate '0' beats the fp8-winning table
    monkeypatch.setenv('PADDLE_TPU_FP8_MATMUL', '0')
    assert maybe_fp8_matmul(a, b) is None
    # native-winning table -> not dispatched, counter still
    monkeypatch.delenv('PADDLE_TPU_FP8_MATMUL')
    monkeypatch.setenv('PADDLE_TPU_TUNING_TABLE',
                       str(tmp_path / 'native_wins.json'))
    tuning.reset()
    tuning.set_timer(lambda op, key, v, t:
                     0.001 if v.get('impl') == 'native' else 0.010)
    c0 = count()
    assert maybe_fp8_matmul(a, b) is None
    assert count() == c0
    # gate '1' beats the native-winning table
    monkeypatch.setenv('PADDLE_TPU_FP8_MATMUL', '1')
    assert maybe_fp8_matmul(a, b) is not None


def test_fp8_matmul_rejects_non_2d_and_ints():
    from paddle_tpu.ops.fp8_matmul import maybe_fp8_matmul
    f = jnp.ones((4, 4), jnp.float32)
    assert maybe_fp8_matmul(jnp.ones((4,), jnp.float32), f) is None
    assert maybe_fp8_matmul(jnp.ones((2, 4, 4), jnp.float32), f) is None
    assert maybe_fp8_matmul(jnp.ones((4, 4), jnp.int32),
                            jnp.ones((4, 4), jnp.int32)) is None


# ------------------------------------------------------------ ZeRO-1
def test_zero1_bit_identical_and_memory_model():
    observe.enable()
    _, w_r, prog_r = _train(opt='adam')
    _, w_z, prog_z = _train(opt='adam', shard_opt=True)
    for k in w_r:
        assert np.array_equal(w_r[k], w_z[k]), k
    mem_r = optimizer_state_bytes(prog_r)
    mem_z = optimizer_state_bytes(prog_z)
    assert mem_r['total'] == mem_z['total']
    assert mem_r['reduction'] == pytest.approx(1.0)
    # accumulators shard ~dp x; only the [1]-shaped beta-pow scalars
    # stay replicated
    assert mem_z['reduction'] >= 0.8 * DP, mem_z
    assert mem_z['per_device'] < mem_r['per_device'] / (0.8 * DP)
    assert mem_z['n_state_vars'] == mem_r['n_state_vars']
    g = observe.snapshot()['gauges']
    assert g.get('trainer.optimizer_state_bytes_total') == mem_z['total']
    assert g.get('trainer.optimizer_state_bytes_per_device') == \
        pytest.approx(mem_z['per_device'])
    assert g.get('trainer.optimizer_state_reduction_x') >= 0.8 * DP
    # the transpiled program honors the zero-* analysis contracts
    from paddle_tpu import analysis
    diags = analysis.run_passes(prog_z)
    assert not [d for d in diags if d.code.startswith('zero-')], diags


def test_zero1_env_override(monkeypatch):
    assert shard_opt_state_env(True) is True
    assert shard_opt_state_env(False) is False
    assert shard_opt_state_env(None) is False
    monkeypatch.setenv('PADDLE_TPU_SHARD_OPT_STATE', '1')
    assert shard_opt_state_env(False) is True
    monkeypatch.setenv('PADDLE_TPU_SHARD_OPT_STATE', 'off')
    assert shard_opt_state_env(True) is False


def test_analysis_zero1_contract_warnings():
    """Structural zero-* checks fire on a hand-built program whose
    optimizer state specs disagree and whose grad stayed replicated."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import analysis
    prog = fluid.Program()
    b = prog.global_block()
    b.create_parameter('w', shape=[8, 4], dtype='float32')
    b.create_var(name='w@GRAD', shape=[8, 4], dtype='float32')
    b.create_var(name='lr', shape=[1], dtype='float32',
                 persistable=True)
    b.create_var(name='m1', shape=[8, 4], dtype='float32',
                 persistable=True)
    b.create_var(name='m2', shape=[8, 4], dtype='float32',
                 persistable=True)
    b.append_op('adam',
                inputs={'Param': ['w'], 'Grad': ['w@GRAD'],
                        'LearningRate': ['lr'],
                        'Moment1': ['m1'], 'Moment2': ['m2']},
                outputs={'ParamOut': ['w'], 'Moment1Out': ['m1'],
                         'Moment2Out': ['m2']})
    prog.mesh = make_mesh(dp=8)
    prog.var_shardings = {'w': P(), 'm1': P('dp', None), 'm2': P()}
    diags = analysis.run_passes(prog)
    codes = {d.code for d in diags}
    assert 'zero-state-spec-mismatch' in codes
    assert 'zero-grad-replicated' in codes
    mism = [d for d in diags if d.code == 'zero-state-spec-mismatch'][0]
    assert mism.severity == 'warning' and mism.var == 'w'
    repl = [d for d in diags if d.code == 'zero-grad-replicated'][0]
    assert repl.var == 'w@GRAD'


# --------------------------------------------- overlap + composition
def test_overlap_fraction_math():
    f = observe.overlap_fraction
    assert f(1.0, 1.0, 1.0) == pytest.approx(1.0)     # fully hidden
    assert f(2.0, 1.0, 1.0) == pytest.approx(0.0)     # fully serial
    assert f(1.5, 1.0, 1.0) == pytest.approx(0.5)
    assert f(0.5, 1.0, 0.2) == 1.0                    # clamped high
    assert f(9.9, 1.0, 1.0) == 0.0                    # clamped low
    assert f(0.0, 1.0, 1.0) is None                   # degenerate
    assert f(1.0, -1.0, 1.0) is None
    assert f(None, 1.0, 1.0) is None
    assert f('x', 1.0, 1.0) is None


def test_record_allreduce_overlap_gauge():
    from paddle_tpu.trainer import record_allreduce_overlap
    observe.enable()
    frac = record_allreduce_overlap(1.5, 1.0, 1.0)
    assert frac == pytest.approx(0.5)
    g = observe.snapshot()['gauges']
    assert g.get('trainer.allreduce_overlap_fraction') == \
        pytest.approx(0.5)
    # degenerate legs record nothing and return None
    assert record_allreduce_overlap(0.0, 1.0, 1.0) is None


def test_step_flops_gauge_agrees_with_the_analytic_count(monkeypatch):
    """What ``trainer.mfu`` and the benchmark's ``train.mfu`` divide:
    with PADDLE_TPU_OBSERVE_COST=1 a cache miss publishes XLA's
    cost-analysis FLOPs of the step it compiled
    (``executor.step_flops``), and the analytic matmul count the
    benchmark multiplies the token rate by
    (``benchmark/shape_fns/transformer_train_flops.py``) agrees with it
    within 3x: the analytic side counts matmuls only (x3 for the
    backward pass), XLA the whole program."""
    import os
    from benchmark import manifest
    from paddle_tpu.models import transformer as T
    shape_fn = manifest.load_module(os.path.join(
        os.path.dirname(manifest.__file__), 'shape_fns',
        'transformer_train_flops.py'))

    batch, seq, vocab = 2, 16, 512
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    avg_cost, _ = T.transformer_base(
        src_vocab_size=vocab, trg_vocab_size=vocab,
        src_seq_len=seq, trg_seq_len=seq, max_length=256)
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    observe.enable()
    feed = T.make_fake_batch(batch, seq, seq, vocab, vocab)
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '1')
    exe.run(feed=feed, fetch_list=[avg_cost])
    xla_flops = observe.snapshot()['gauges'].get('executor.step_flops')
    assert xla_flops, 'a cache miss must publish its step\'s FLOPs'
    # transformer_base: 6 layers, 8 heads of 64, d_model 512, FFN 2048
    analytic = shape_fn.step_flops(batch, seq, seq, vocab, 6, 8, 64,
                                   512, 2048)
    assert 1.0 / 3.0 <= analytic / xla_flops <= 3.0, (analytic,
                                                       xla_flops)


def test_quantized_plus_bucketed_composition():
    """EQuARX int8 gradient compression rides inside the buckets; the
    composed run must train to the same neighborhood as exact."""
    loss_f, _, _ = _train(steps=12)
    loss_qb, _, _ = _train(bucket_mb=1e-4, quant_on=True, steps=12)
    tol = max(0.05, 0.25 * abs(loss_f[-1]))
    assert abs(loss_qb[-1] - loss_f[-1]) <= tol, (loss_f[-1],
                                                  loss_qb[-1])
