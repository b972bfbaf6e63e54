"""SPMD correctness on the 8-virtual-device CPU mesh (SURVEY.md §4):
data-parallel grads == single-device, tensor-parallel == unsharded,
ring attention == full attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.transpiler import ParallelStrategy, transpile
from util import rand


def _build_mlp_loss():
    x = fluid.layers.data(name='x', shape=[6], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='int64')
    h = fluid.layers.fc(input=x, size=16, act='relu',
                        param_attr=fluid.ParamAttr(name='w1'),
                        bias_attr=fluid.ParamAttr(name='b1'))
    out = fluid.layers.fc(input=h, size=4, act='softmax',
                          param_attr=fluid.ParamAttr(name='w2'),
                          bias_attr=fluid.ParamAttr(name='b2'))
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=out, label=y))
    return loss


def _train_k_steps(mesh=None, strategy=None, steps=3, seed=0, opt='sgd'):
    """Build + train the MLP; returns (final loss, final w1)."""
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    loss = _build_mlp_loss()
    fluid.default_main_program().random_seed = 7
    {'sgd': lambda: fluid.optimizer.SGD(learning_rate=0.1),
     'momentum': lambda: fluid.optimizer.Momentum(learning_rate=0.1,
                                                  momentum=0.9),
     'adam': lambda: fluid.optimizer.Adam(learning_rate=0.05),
     }[opt]().minimize(loss)
    if mesh is not None:
        transpile(fluid.default_main_program(), mesh, strategy)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(seed)
    xs = rng.rand(16, 6).astype('float32')
    ys = rng.randint(0, 4, (16, 1)).astype('int64')
    final = None
    for _ in range(steps):
        final = exe.run(feed={'x': xs, 'y': ys}, fetch_list=[loss])
    w1 = np.asarray(fluid.global_scope().find('w1'))
    return float(np.asarray(final[0]).reshape(())), w1


def test_data_parallel_matches_single_device():
    loss_1, w1_1 = _train_k_steps(mesh=None)
    mesh = make_mesh(dp=8)
    loss_dp, w1_dp = _train_k_steps(
        mesh=mesh, strategy=ParallelStrategy(data_parallel=True))
    assert abs(loss_1 - loss_dp) < 1e-4
    np.testing.assert_allclose(w1_1, w1_dp, rtol=1e-4, atol=1e-5)


def test_tensor_parallel_matches_unsharded():
    loss_1, w1_1 = _train_k_steps(mesh=None)
    mesh = make_mesh(dp=2, tp=4)
    strategy = ParallelStrategy(
        data_parallel=True, tensor_parallel=True,
        tp_rules=[('w1', 1), ('w2', 0)])  # column then row split
    loss_tp, w1_tp = _train_k_steps(mesh=mesh, strategy=strategy)
    assert abs(loss_1 - loss_tp) < 1e-4
    np.testing.assert_allclose(w1_1, w1_tp, rtol=1e-4, atol=1e-5)


def _train_wide_deep(mesh=None, strategy=None, steps=3, vocab=64):
    """Wide&Deep (is_sparse embeddings) for the row-sharding parity check."""
    from paddle_tpu.models import wide_deep
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    predict, avg_cost, acc, feeds = wide_deep.build(
        num_slots=4, vocab_size=vocab, dense_dim=5, embed_size=8)
    fluid.default_main_program().random_seed = 11
    fluid.optimizer.Adagrad(learning_rate=0.05).minimize(avg_cost)
    if mesh is not None:
        transpile(fluid.default_main_program(), mesh, strategy)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(3)
    feed = {'C%d' % i: rng.randint(0, vocab, (16, 1)).astype('int64')
            for i in range(4)}
    feed['dense'] = rng.rand(16, 5).astype('float32')
    feed['label'] = rng.randint(0, 2, (16, 1)).astype('int64')
    final = None
    for _ in range(steps):
        final = exe.run(feed=feed, fetch_list=[avg_cost])
    emb = np.asarray(fluid.global_scope().find('emb_slot_0'))
    return float(np.asarray(final[0]).reshape(())), emb


def test_row_sharded_embedding_matches_unsharded():
    """is_sparse tables row-sharded over tp must train identically to the
    replicated run (the pserver sparse-row role via GSPMD gather)."""
    loss_1, emb_1 = _train_wide_deep(mesh=None)
    mesh = make_mesh(dp=2, tp=4)
    loss_sh, emb_sh = _train_wide_deep(
        mesh=mesh, strategy=ParallelStrategy(data_parallel=True))
    # the transpiled program must actually row-shard the tables
    sh = fluid.default_main_program().var_shardings
    assert sh['emb_slot_0'] == ('tp',) or sh['emb_slot_0'][0] == 'tp'
    assert sh['wide_slot_0'][0] == 'tp'
    assert abs(loss_1 - loss_sh) < 1e-4
    np.testing.assert_allclose(emb_1, emb_sh, rtol=1e-4, atol=1e-5)


def test_ring_attention_equals_full_attention():
    from paddle_tpu.parallel.ring_attention import ring_attention
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    b, h, t, d, n_shards = 2, 2, 32, 8, 8
    rng = np.random.RandomState(0)
    q = rng.randn(b, h, t, d).astype('float32')
    k = rng.randn(b, h, t, d).astype('float32')
    v = rng.randn(b, h, t, d).astype('float32')

    # full attention reference
    def full(q, k, v, causal):
        s = np.einsum('bhqd,bhkd->bhqk', q * d ** -0.5, k)
        if causal:
            mask = np.tril(np.ones((t, t), dtype=bool))
            s = np.where(mask[None, None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum('bhqk,bhkd->bhqd', p, v)

    mesh = Mesh(np.array(jax.devices()[:n_shards]).reshape(n_shards),
                ('sp',))
    spec = P(None, None, 'sp', None)

    for causal in (False, True):
        ring = shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name='sp',
                                           causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        got = np.asarray(jax.jit(ring)(q, k, v))
        np.testing.assert_allclose(got, full(q, k, v, causal),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg='causal=%s' % causal)


def test_collectives_roundtrip():
    from paddle_tpu.parallel import collective
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ('dp',))
    x = np.arange(8, dtype='float32').reshape(4, 2)

    f = shard_map(lambda a: collective.all_reduce(a, 'dp'),
                  mesh=mesh, in_specs=(P('dp', None),),
                  out_specs=P('dp', None))
    got = np.asarray(jax.jit(f)(x))
    expect = np.tile(x.sum(0, keepdims=True), (4, 1))
    np.testing.assert_allclose(got, expect)

    g = shard_map(
        lambda a: collective.all_gather(a, 'dp', axis=0)[None],
        mesh=mesh, in_specs=(P('dp', None),), out_specs=P('dp', None),
        check_vma=False)
    got_g = np.asarray(jax.jit(g)(x))  # each shard returns the full gather
    np.testing.assert_allclose(got_g.reshape(4, 4, 2)[0], x)


def test_transpiler_attaches_shardings():
    loss = _build_mlp_loss()
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    mesh = make_mesh(dp=4, tp=2)
    strategy = ParallelStrategy(data_parallel=True, tensor_parallel=True,
                                tp_rules=[('w1', 1), ('w2', 0)])
    prog = transpile(fluid.default_main_program(), mesh, strategy)
    sh = prog.var_shardings
    assert sh['x'][0] == 'dp'
    assert sh['w1'] == ('tp',) or sh['w1'][1] == 'tp'
    assert sh['w2'][0] == 'tp'
    # Adam moments follow the param sharding
    moment_names = [n for n in sh if 'w1' in n and 'moment' in n]
    assert moment_names
    for n in moment_names:
        assert sh[n] == sh['w1']


def test_auto_tp_matches_unsharded():
    """tensor_parallel with NO tp_rules: Megatron col/row pairing derived
    from the op graph must still train identically to unsharded."""
    loss_1, w1_1 = _train_k_steps(mesh=None)
    mesh = make_mesh(dp=2, tp=4)
    strategy = ParallelStrategy(data_parallel=True, tensor_parallel=True)
    loss_tp, w1_tp = _train_k_steps(mesh=mesh, strategy=strategy)
    sh = fluid.default_main_program().var_shardings
    assert sh['w1'][-1] == 'tp'   # first fc: column split
    assert sh['w2'][0] == 'tp'    # second fc: row split
    assert sh['b1'] == ('tp',)    # column-split layer's bias follows
    assert abs(loss_1 - loss_tp) < 1e-4
    np.testing.assert_allclose(w1_1, w1_tp, rtol=1e-4, atol=1e-5)


def test_accumulator_sharding_survives_colliding_names():
    """Params named so prefix-matching would pair accumulators with the
    WRONG param ('w' vs 'w_x', same shape, different specs): structural
    matching keys on the optimizer op, so each velocity follows its own
    param."""
    x = fluid.layers.data(name='x', shape=[16], dtype='float32')
    h = fluid.layers.fc(input=x, size=16, act='relu',
                        param_attr=fluid.ParamAttr(name='w'),
                        bias_attr=False)
    out = fluid.layers.fc(input=h, size=16,
                          param_attr=fluid.ParamAttr(name='w_x'),
                          bias_attr=False)
    loss = fluid.layers.mean(out)
    fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    # Force same shapes but different specs via explicit rules.
    mesh = make_mesh(dp=2, tp=4)
    strategy = ParallelStrategy(
        data_parallel=True, tensor_parallel=True,
        tp_rules=[('w_x', 0), ('w', 1)])
    prog = transpile(fluid.default_main_program(), mesh, strategy)
    sh = prog.var_shardings
    block = prog.global_block()
    for op in block.ops:
        if op.inputs.get('Param') and op.inputs.get('Velocity'):
            pname = op.inputs['Param'][0]
            vname = op.inputs['Velocity'][0]
            assert sh[vname] == sh[pname], (pname, vname)


def test_dryrun_multichip_entrypoint():
    import importlib
    import __graft_entry__
    importlib.reload(__graft_entry__)
    __graft_entry__.dryrun_multichip(8)


def test_pipeline_parallel_matches_sequential():
    from paddle_tpu.parallel.pipeline import pipelined_apply
    from jax.sharding import Mesh

    n_stages, batch, n_micro, d = 4, 8, 4, 16
    rng = np.random.RandomState(0)
    # 4 identical-shape linear+tanh stages
    ws = rng.randn(n_stages, d, d).astype('float32') * 0.3
    bs = rng.randn(n_stages, d).astype('float32') * 0.1
    x = rng.randn(batch, d).astype('float32')

    def stage_fn(params, h):
        w, b = params
        return jnp.tanh(h @ w + b)

    mesh = Mesh(np.array(jax.devices()[:n_stages]).reshape(n_stages),
                ('pp',))
    got = np.asarray(pipelined_apply(stage_fn, (ws, bs), x, n_micro, mesh))

    ref = x
    for s in range(n_stages):
        ref = np.tanh(ref @ ws[s] + bs[s])
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)


def test_pipeline_parallel_differentiable():
    from paddle_tpu.parallel.pipeline import pipelined_apply
    from jax.sharding import Mesh

    n_stages, batch, d = 2, 4, 8
    rng = np.random.RandomState(1)
    ws = rng.randn(n_stages, d, d).astype('float32') * 0.3
    x = rng.randn(batch, d).astype('float32')
    mesh = Mesh(np.array(jax.devices()[:n_stages]).reshape(n_stages),
                ('pp',))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    def loss(ws):
        return pipelined_apply(stage_fn, ws, x, 2, mesh).sum()

    g = jax.grad(loss)(jnp.asarray(ws))
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0

    def loss_ref(ws):
        h = x
        for s in range(n_stages):
            h = jnp.tanh(h @ ws[s])
        return h.sum()

    g_ref = jax.grad(loss_ref)(jnp.asarray(ws))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-4, atol=1e-5)


def _build_scan_transformer(mesh=None, strategy=None, dropout=0.0,
                            n_layer=4, optimizer=None):
    """Tiny scan-stacked transformer (enc+dec), minimized (Adam unless
    an optimizer factory is given), transpiled onto `mesh`, startup run.
    Returns (cost, exe) — the one copy of this build recipe."""
    from paddle_tpu.models import transformer as T
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    fluid.default_main_program().random_seed = 7
    avg_cost, _ = T.transformer_base(
        src_vocab_size=64, trg_vocab_size=64, src_seq_len=8, trg_seq_len=8,
        n_layer=n_layer, d_model=16, d_inner=32, d_key=8, d_value=8,
        n_head=2, dropout_rate=dropout, scan_layers=True)
    opt = optimizer() if optimizer is not None else \
        fluid.optimizer.Adam(learning_rate=1e-3)
    opt.minimize(avg_cost)
    if mesh is not None:
        transpile(fluid.default_main_program(), mesh, strategy)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return avg_cost, exe


def _scan_transformer_feed():
    from paddle_tpu.models import transformer as T
    return T.make_fake_batch(8, 8, 8, 64, 64, seed=3)


def _train_scan_transformer(mesh=None, strategy=None, steps=3,
                            dropout=0.0, n_layer=4, optimizer=None):
    """Build + train `steps` steps on a constant batch; returns the
    per-step losses."""
    avg_cost, exe = _build_scan_transformer(mesh, strategy, dropout,
                                            n_layer, optimizer)
    feed = _scan_transformer_feed()
    return [float(np.asarray(exe.run(
        feed=feed, fetch_list=[avg_cost])[0]).reshape(()))
        for _ in range(steps)]


def test_program_pipeline_matches_single_device():
    """Program-level pipeline parallelism: a fluid-built transformer
    (scan_layers=True) transpiled with pipeline_parallel trains through
    Executor.run on a pp mesh with the SAME loss trajectory as single
    device — encoder and decoder stacks both pipelined, cross-attention
    memory microbatched alongside."""
    base = _train_scan_transformer()
    pp4 = _train_scan_transformer(
        mesh=make_mesh(dp=1, pp=4),
        strategy=ParallelStrategy(data_parallel=False,
                                  pipeline_parallel=True))
    np.testing.assert_allclose(pp4, base, rtol=2e-4, atol=1e-5)
    # composes with dp: 2 stages x 2-way data parallel
    pp_dp = _train_scan_transformer(
        mesh=make_mesh(dp=2, pp=2),
        strategy=ParallelStrategy(data_parallel=True,
                                  pipeline_parallel=True,
                                  pipeline_microbatches=4))
    np.testing.assert_allclose(pp_dp, base, rtol=2e-4, atol=1e-5)


def test_program_pipeline_composes_with_tp():
    """pp x tp (the scaling-book large-model config): the shard_map is
    manual over pp only, so GSPMD manages the intra-stage Megatron
    column/row splits — loss trajectory must equal single device."""
    base = _train_scan_transformer()
    pp_tp = _train_scan_transformer(
        mesh=make_mesh(dp=1, pp=2, tp=4),
        strategy=ParallelStrategy(data_parallel=False,
                                  tensor_parallel=True,
                                  pipeline_parallel=True))
    np.testing.assert_allclose(pp_tp, base, rtol=2e-4, atol=1e-5)
    # the stacked qkv weights really are tp-split inside their stage
    prog = fluid.default_main_program()
    spec = prog.var_shardings['enc_stack_slf_q.w']
    assert tuple(spec) == ('pp', None, 'tp'), spec
    spec_o = prog.var_shardings['enc_stack_slf_o.w']
    assert tuple(spec_o) == ('pp', 'tp', None), spec_o


def test_program_pipeline_composes_with_sp():
    """pp x sp: the ring-attention dispatch nests as an sp-manual inner
    shard_map inheriting the pp-manual context mesh — long-context
    sequence parallelism inside a pipeline stage, loss-equal to single
    device."""
    base = _train_scan_transformer(n_layer=2)
    pp_sp = _train_scan_transformer(
        mesh=make_mesh(dp=1, pp=2, sp=4), n_layer=2,
        strategy=ParallelStrategy(
            data_parallel=False, sequence_parallel=True,
            pipeline_parallel=True,
            sp_vars=['src_word', 'trg_word', 'lbl_word', 'lbl_weight']))
    np.testing.assert_allclose(pp_sp, base, rtol=2e-4, atol=1e-5)


def test_program_pipeline_composes_with_run_steps():
    """The pipelined step under Executor.run_steps (shard_map inside the
    multi-step lax.scan): trajectory equals per-step dispatch."""
    mesh = make_mesh(dp=1, pp=2)
    strat = ParallelStrategy(data_parallel=False, pipeline_parallel=True)

    per_step = _train_scan_transformer(mesh=mesh, strategy=strat, steps=4,
                                       n_layer=2)

    avg_cost, exe = _build_scan_transformer(mesh=mesh, strategy=strat,
                                            n_layer=2)
    out = exe.run_steps(4, feed=_scan_transformer_feed(),
                        fetch_list=[avg_cost])
    windowed = np.asarray(out[0]).reshape(-1).tolist()
    np.testing.assert_allclose(windowed, per_step, rtol=2e-4, atol=1e-5)


def test_program_pipeline_composes_with_grad_accum():
    """GradientAccumulator's gated updates under a pipelined program:
    the accumulator state and phase counter live OUTSIDE the pp
    shard_map, so accumulation semantics are unchanged — trajectory
    equals single device (loss repeats in pairs: k=2)."""
    def accum():
        return fluid.optimizer.GradientAccumulator(
            fluid.optimizer.SGD(learning_rate=0.1), 2)

    base = _train_scan_transformer(steps=4, n_layer=2, optimizer=accum)
    assert base[0] == base[1] and base[2] == base[3]  # k=2 gating
    pp = _train_scan_transformer(
        steps=4, n_layer=2, optimizer=accum,
        mesh=make_mesh(dp=2, pp=2),
        strategy=ParallelStrategy(data_parallel=True,
                                  pipeline_parallel=True))
    np.testing.assert_allclose(pp, base, rtol=2e-4, atol=1e-5)


def test_program_pipeline_with_dropout_runs():
    """Dropout keys fold the microbatch index (masks per microbatch);
    trajectory differs from single-device by design — train steps must
    run and decrease."""
    losses = _train_scan_transformer(
        mesh=make_mesh(dp=1, pp=2), dropout=0.1, steps=4,
        strategy=ParallelStrategy(data_parallel=False,
                                  pipeline_parallel=True))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_program_pipeline_requires_pp_axis():
    """pipeline_parallel on a mesh without a pp axis must raise, not
    silently train unpipelined (r4 review)."""
    from paddle_tpu.models import transformer as T
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    T.transformer_base(
        src_vocab_size=64, trg_vocab_size=64, src_seq_len=8, trg_seq_len=8,
        n_layer=2, d_model=16, d_inner=32, d_key=8, d_value=8, n_head=2,
        dropout_rate=0.0, scan_layers=True)
    with pytest.raises(ValueError, match='pp axis'):
        transpile(fluid.default_main_program(), make_mesh(dp=8),
                  ParallelStrategy(pipeline_parallel=True))


def test_program_pipeline_requires_scan_stack():
    from paddle_tpu.models import transformer as T
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    avg_cost, _ = T.transformer_base(
        src_vocab_size=64, trg_vocab_size=64, src_seq_len=8, trg_seq_len=8,
        n_layer=2, d_model=16, d_inner=32, d_key=8, d_value=8, n_head=2,
        dropout_rate=0.0, scan_layers=False)   # unrolled: no stack op
    with pytest.raises(ValueError, match='scan_layers'):
        transpile(fluid.default_main_program(), make_mesh(dp=1, pp=2),
                  ParallelStrategy(pipeline_parallel=True))


def test_program_pipeline_indivisible_layers_raises():
    from paddle_tpu.models import transformer as T
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    T.transformer_base(
        src_vocab_size=64, trg_vocab_size=64, src_seq_len=8, trg_seq_len=8,
        n_layer=3, d_model=16, d_inner=32, d_key=8, d_value=8, n_head=2,
        dropout_rate=0.0, scan_layers=True)
    with pytest.raises(ValueError, match='divisible'):
        transpile(fluid.default_main_program(), make_mesh(dp=1, pp=2),
                  ParallelStrategy(pipeline_parallel=True))


def test_checkpoint_portable_across_meshes(tmp_path):
    """A checkpoint saved while training on a dp x pp x tp mesh (params
    sharded: stage-split stacks, Megatron tp splits) loads on a single
    device and continues with the same trajectory — save gathers global
    values, so checkpoints are mesh-layout-free."""
    feed = _scan_transformer_feed()
    cost, exe = _build_scan_transformer(
        make_mesh(dp=2, pp=2, tp=2),
        ParallelStrategy(data_parallel=True, tensor_parallel=True,
                         pipeline_parallel=True), n_layer=2)
    for _ in range(2):
        exe.run(feed=feed, fetch_list=[cost])
    fluid.io.save_checkpoint(exe, str(tmp_path), step=2)
    l_mesh = [float(np.asarray(exe.run(
        feed=feed, fetch_list=[cost])[0]).reshape(())) for _ in range(2)]

    cost, exe = _build_scan_transformer(n_layer=2)
    assert fluid.io.load_checkpoint(exe, str(tmp_path)) == 2
    l_single = [float(np.asarray(exe.run(
        feed=feed, fetch_list=[cost])[0]).reshape(())) for _ in range(2)]
    np.testing.assert_allclose(l_single, l_mesh, rtol=2e-4, atol=1e-5)


def test_retranspile_clears_pipeline_schedule():
    """Re-transpiling with pipeline_parallel=False must clear the old
    schedule — the stack lowerings key off program.pipeline (r4
    review)."""
    from paddle_tpu.models import transformer as T
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    T.transformer_base(
        src_vocab_size=64, trg_vocab_size=64, src_seq_len=8, trg_seq_len=8,
        n_layer=2, d_model=16, d_inner=32, d_key=8, d_value=8, n_head=2,
        dropout_rate=0.0, scan_layers=True)
    prog = fluid.default_main_program()
    transpile(prog, make_mesh(dp=1, pp=2),
              ParallelStrategy(pipeline_parallel=True,
                               pipeline_microbatches=4))
    assert prog.pipeline == {'n_micro': 4}
    transpile(prog, make_mesh(dp=1, pp=2),
              ParallelStrategy(pipeline_parallel=False))
    assert prog.pipeline is None


def test_transpile_invalidates_compiled_cache():
    """A step compiled before transpile must not be reused after: the
    old trace has no sharding constraints (and no pipeline schedule).
    transpile bumps the program version, which keys the executor
    cache."""
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    loss = _build_mlp_loss()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = fluid.default_main_program()
    v0 = prog._version
    transpile(prog, make_mesh(dp=8), ParallelStrategy(data_parallel=True))
    assert prog._version > v0


def test_multihost_autodetect_failure_warns(monkeypatch):
    """Auto-detect path (PADDLE_TRAINERS set, no coordinator): a failed
    jax.distributed init falls back single-host but WARNS — a pod with
    broken metadata must not silently train on duplicate data."""
    import warnings
    from paddle_tpu.parallel import multihost
    monkeypatch.setattr(multihost, '_initialized', False)
    monkeypatch.setenv('PADDLE_TRAINERS', '4')
    monkeypatch.delenv('PADDLE_COORDINATOR', raising=False)
    monkeypatch.delenv('PADDLE_TRAINER_ID', raising=False)

    class _FakeDist(object):
        @staticmethod
        def initialize(*a, **k):
            raise RuntimeError('no pod metadata')

    import jax
    monkeypatch.setattr(jax, 'distributed', _FakeDist)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        ok = multihost.init_distributed()
    assert ok is False
    assert any('SINGLE-HOST' in str(x.message) for x in w), \
        [str(x.message) for x in w]


def test_multihost_single_host_fallbacks():
    from paddle_tpu.parallel import multihost
    assert multihost.init_distributed() in (True, False)
    assert multihost.process_count() >= 1
    assert multihost.host_local_batch(16) == 16 // multihost.process_count()
    mesh = multihost.global_device_mesh(tp=2)
    assert mesh.shape['tp'] == 2


def _train_attention_model(mesh=None, strategy=None, steps=3, causal=True):
    """Tiny attention model via the fused_attention IR op; returns
    (loss, q-projection weights) after training."""
    from paddle_tpu.models.transformer import _multi_head_attention
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    x = fluid.layers.data(name='x', shape=[16, 32], dtype='float32')
    y = fluid.layers.data(name='y', shape=[16, 32], dtype='float32')
    attn = _multi_head_attention(x, x, d_key=8, d_value=8, n_head=4,
                                 d_model=32, dropout_rate=0.0,
                                 causal=causal, name='spattn')
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(attn, y))
    fluid.default_main_program().random_seed = 5
    fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    if mesh is not None:
        transpile(fluid.default_main_program(), mesh, strategy)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(2)
    xs = rng.randn(4, 16, 32).astype('float32')
    ys = rng.randn(4, 16, 32).astype('float32')
    final = None
    for _ in range(steps):
        final = exe.run(feed={'x': xs, 'y': ys}, fetch_list=[loss])
    w = np.asarray(fluid.global_scope().find('spattn_q.w'))
    return float(np.asarray(final[0]).reshape(())), w


def test_ring_attention_dispatch_matches_unsharded():
    """fused_attention on a mesh with sp>1 dispatches to ring attention
    (K/V rotating over ICI) and must train identically to the unsharded
    run — fwd AND bwd (long-context sequence parallelism end-to-end)."""
    for causal in (False, True):
        loss_1, w_1 = _train_attention_model(mesh=None, causal=causal)
        mesh = make_mesh(dp=2, sp=4)
        strategy = ParallelStrategy(data_parallel=True,
                                    sequence_parallel=True,
                                    sp_vars=['x', 'y'])
        loss_sp, w_sp = _train_attention_model(mesh=mesh,
                                               strategy=strategy,
                                               causal=causal)
        assert abs(loss_1 - loss_sp) < 1e-4, (causal, loss_1, loss_sp)
        np.testing.assert_allclose(w_1, w_sp, rtol=1e-4, atol=1e-5,
                                   err_msg='causal=%s' % causal)


def test_auto_tp_splits_an_attention_sublayer_by_heads():
    """tensor_parallel with no tp_rules over the one-op attention
    sublayer: q, k and v projections column-split (one head a shard at
    n_head = tp = 4), the output projection row-split, and training
    follows the unsharded run."""
    loss_1, w_1 = _train_attention_model(mesh=None)
    mesh = make_mesh(dp=2, tp=4)
    loss_tp, w_tp = _train_attention_model(
        mesh=mesh, strategy=ParallelStrategy(data_parallel=True,
                                             tensor_parallel=True))
    sh = fluid.default_main_program().var_shardings
    for name in ('spattn_q.w', 'spattn_k.w', 'spattn_v.w'):
        assert tuple(sh[name]) == (None, 'tp'), (name, sh[name])
    assert tuple(sh['spattn_out.w']) == ('tp', None)
    assert abs(loss_1 - loss_tp) < 1e-4
    np.testing.assert_allclose(w_1, w_tp, rtol=1e-4, atol=1e-5)


def test_parallel_executor_facade():
    """ParallelExecutor API over GSPMD: global batch shards over dp,
    training matches the single-device run (reference ParallelExecutor
    role, parallel/executor.py)."""
    from paddle_tpu.parallel import ParallelExecutor
    loss_1, w1_1 = _train_k_steps(mesh=None)

    fluid.reset_default_programs()
    fluid.global_scope().clear()
    loss = _build_mlp_loss()
    fluid.default_main_program().random_seed = 7
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    pe = ParallelExecutor(use_cuda=False, loss_name=loss.name,
                          place=fluid.CPUPlace())
    assert pe.device_count == 8
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    xs = rng.rand(16, 6).astype('float32')
    ys = rng.randint(0, 4, (16, 1)).astype('int64')
    final = None
    for _ in range(3):
        final = pe.run([loss], feed={'x': xs, 'y': ys})
    assert abs(float(np.asarray(final[0]).reshape(())) - loss_1) < 1e-4
    np.testing.assert_allclose(
        np.asarray(fluid.global_scope().find('w1')), w1_1,
        rtol=1e-4, atol=1e-5)
    pe.bcast_params()  # no-op, API compatibility


@pytest.mark.parametrize('mesh_kw,strat_kw', [
    (dict(dp=8), dict(data_parallel=True)),
    (dict(dp=4, tp=2), dict(data_parallel=True, tensor_parallel=True)),
], ids=['dp8', 'dp4xtp2'])
def test_run_steps_on_mesh_with_stacked_feed(mesh_kw, strat_kw):
    """run_steps(stacked_feed=True) on a mesh: the var's PartitionSpec
    describes the per-step batch, so the superbatch shards with a
    replicated leading [steps] axis (steps need not divide the mesh) and
    the trajectory equals per-step dispatch — including under dp x tp
    (auto-derived Megatron splits inside the scanned step)."""
    steps = 3  # deliberately not divisible by either mesh's dp axis
    rng = np.random.RandomState(3)
    xs = rng.rand(steps, 16, 6).astype('float32')
    ys = rng.randint(0, 4, (steps, 16, 1)).astype('int64')

    def build():
        fluid.reset_default_programs()
        fluid.global_scope().clear()
        loss = _build_mlp_loss()
        fluid.default_main_program().random_seed = 7
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        transpile(fluid.default_main_program(), make_mesh(**mesh_kw),
                  ParallelStrategy(**strat_kw))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        return loss, exe

    loss, exe = build()
    single = [float(np.asarray(exe.run(
        feed={'x': xs[i], 'y': ys[i]}, fetch_list=[loss])[0]).reshape(()))
        for i in range(steps)]
    loss, exe = build()
    multi = np.asarray(exe.run_steps(
        steps, feed={'x': xs, 'y': ys}, fetch_list=[loss],
        stacked_feed=True)[0]).reshape(-1)
    np.testing.assert_allclose(multi, single, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('opt', ['momentum', 'adam'])
def test_zero1_optimizer_state_sharding_matches_single_device(opt):
    """ParallelStrategy(shard_optimizer_states=True): accumulators get a
    'dp' axis in their spec (ZeRO-1) and training is numerically the
    single-device trajectory — GSPMD derives the reduce-scatter /
    all-gather."""
    loss_1, w1_1 = _train_k_steps(mesh=None, opt=opt)
    mesh = make_mesh(dp=8)
    loss_z, w1_z = _train_k_steps(
        mesh=mesh,
        strategy=ParallelStrategy(data_parallel=True,
                                  shard_optimizer_states=True),
        opt=opt)
    assert abs(loss_1 - loss_z) < 1e-4, (loss_1, loss_z)
    np.testing.assert_allclose(w1_1, w1_z, rtol=1e-4, atol=1e-5)
    # the state specs actually carry 'dp' (not just replicated copies)
    shardings = fluid.default_main_program().var_shardings
    acc_specs = {n: s for n, s in shardings.items() if n.endswith('_acc')}
    assert acc_specs, 'no accumulator specs recorded'
    dp_sharded = [n for n, s in acc_specs.items() if 'dp' in tuple(s)]
    assert dp_sharded, acc_specs


def test_zero1_composes_with_tensor_parallel():
    """shard_optimizer_states under dp x tp: tp axes stay, 'dp' lands on
    a free divisible axis (or not at all — divisibility-gated)."""
    loss_1, w1_1 = _train_k_steps(mesh=None, opt='adam')
    mesh = make_mesh(dp=2, tp=4)
    loss_z, w1_z = _train_k_steps(
        mesh=mesh,
        strategy=ParallelStrategy(
            data_parallel=True, tensor_parallel=True,
            tp_rules=[('w1', 1), ('w2', 0)],
            shard_optimizer_states=True),
        opt='adam')
    assert abs(loss_1 - loss_z) < 1e-4, (loss_1, loss_z)
    np.testing.assert_allclose(w1_1, w1_z, rtol=1e-4, atol=1e-5)
    shardings = fluid.default_main_program().var_shardings
    # w1's moments keep their tp split on axis 1, gain 'dp' on axis 0
    # (6 % 2 == 0 under dp=2)
    m1 = tuple(shardings['w1_moment1_acc'])
    assert 'tp' in m1 and 'dp' in m1, m1


def test_fsdp_parameter_sharding_matches_single_device():
    """ParallelStrategy(fully_shard_parameters=True): weights, grads,
    and state all take 'dp' (ZeRO-3/FSDP); XLA all-gathers weights at
    use and reduce-scatters grads. Numerics == single device."""
    loss_1, w1_1 = _train_k_steps(mesh=None, opt='adam')
    mesh = make_mesh(dp=8)
    loss_f, w1_f = _train_k_steps(
        mesh=mesh,
        strategy=ParallelStrategy(data_parallel=True,
                                  fully_shard_parameters=True,
                                  shard_optimizer_states=True),
        opt='adam')
    assert abs(loss_1 - loss_f) < 1e-4, (loss_1, loss_f)
    np.testing.assert_allclose(w1_1, w1_f, rtol=1e-4, atol=1e-5)
    shardings = fluid.default_main_program().var_shardings
    # w1 [6,16]: axis0 % 8 != 0, axis1 16 % 8 == 0 -> P(None, 'dp')
    assert 'dp' in tuple(shardings['w1']), shardings['w1']
    assert tuple(shardings['w1_moment1_acc']) == tuple(shardings['w1'])


def test_ring_attention_masked_equals_reference():
    """r5: per-example kv_len padding masks under sequence parallelism —
    ring attention over an 8-shard sp axis must equal the unsharded
    masked reference, including rows whose length falls inside an
    earlier shard's block."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.parallel.ring_attention import ring_attention
    from paddle_tpu.ops.attention_ops import reference_attention

    b, h, t, d, n_shards = 3, 2, 32, 8, 8
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
               for _ in range(3))
    lens = jnp.asarray([32, 13, 3], jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:n_shards]).reshape(n_shards),
                ('sp',))
    spec = P(None, None, 'sp', None)
    for causal in (False, True):
        ring = shard_map(
            lambda q_, k_, v_, l_: ring_attention(
                q_, k_, v_, axis_name='sp', causal=causal, kv_len=l_),
            mesh=mesh, in_specs=(spec, spec, spec, P(None)),
            out_specs=spec)
        got = np.asarray(jax.jit(ring)(q, k, v, lens))
        want = np.asarray(reference_attention(q, k, v, causal=causal,
                                              key_length=lens))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5,
                                   err_msg='causal=%s' % causal)


def test_masked_attention_dispatch_rides_ring():
    """The fused_attention sp gate no longer requires key_length=None:
    a masked batch on an sp mesh takes the ring path and matches the
    unfused reference."""
    import paddle_tpu.ops.attention_ops as ao
    mesh = make_mesh(sp=8)
    rng = np.random.RandomState(6)
    b, t, hd, nh = 2, 32, 16, 2
    q3, k3, v3 = (jnp.asarray(rng.randn(b, t, hd), jnp.float32)
                  for _ in range(3))
    lens = jnp.asarray([32, 9], jnp.int32)
    qlen = jnp.asarray([30, 32], jnp.int32)
    with mesh:
        got = jax.jit(lambda a, b_, c, l, ql: ao.fused_attention(
            a, b_, c, nh, causal=False, key_length=l, query_length=ql,
            mesh=mesh))(q3, k3, v3, lens, qlen)
    want = ao.fused_attention(q3, k3, v3, nh, causal=False,
                              key_length=lens, query_length=qlen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
