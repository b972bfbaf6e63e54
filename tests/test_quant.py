"""Quantization end-to-end (ISSUE 13): blockwise int8 numerics
(stochastic-rounding unbiasedness), the real shard_map
quantized_all_reduce vs exact psum, the O(log n) ppermute broadcast,
int8-gradient-allreduce convergence + per-call env knob on the
trainer path, the PTQ Program rewrite (parity, calibration threshold,
contract pass), and the quantized paged KV arena (concurrent ==
sequential at int8, attention parity, off-by-default bit-identity,
zero post-warmup recompiles)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import quant
from paddle_tpu.quant import core as qcore

DP = 4


def _mesh(n=DP):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ('dp',))


def _shard_map(fn, mesh, n_in=1):
    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map
    spec = P('dp', None)
    return shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                     out_specs=spec)


# ------------------------------------------------- blockwise numerics
def test_stochastic_rounding_unbiased():
    """E[dequant(quant(x))] == x under stochastic rounding; the
    deterministic rounder is biased on off-grid values (that bias is
    exactly why gradient traffic wants the stochastic mode)."""
    v = np.array([0.3, -1.7, 0.031, 100.0, -0.26, 55.5],
                 dtype='float32')
    outs = np.stack([
        np.asarray(qcore.qdq(jnp.asarray(v), block=8,
                             key=jax.random.PRNGKey(i)))
        for i in range(400)])
    # scale = 100/127 ~ 0.79; mean over 400 draws converges ~ s/sqrt(n)
    assert np.abs(outs.mean(axis=0) - v).max() < 0.12
    det = np.asarray(qcore.qdq(jnp.asarray(v), block=8))
    # deterministic: 0.3 rounds to 0 at this scale — bias ~ 0.3
    assert np.abs(det - v).max() > 0.2


def test_quantize_blockwise_round_trip_and_pad():
    x = np.random.RandomState(0).randn(3, 37).astype('float32')
    q, s = qcore.quantize_blockwise(jnp.asarray(x), block=16)
    assert np.asarray(q).dtype == np.int8
    back = np.asarray(qcore.dequantize_blockwise(q, s, shape=x.shape))
    assert back.shape == x.shape
    rel = np.abs(back - x).max() / np.abs(x).max()
    assert rel < 2.0 / 127
    # an all-zero tensor stays exactly zero (scale floor, no NaN)
    z = np.asarray(qcore.qdq(jnp.zeros((5, 5), 'float32')))
    assert np.array_equal(z, np.zeros((5, 5), 'float32'))


# ------------------------------------------ collectives (shard_map)
def test_quantized_all_reduce_matches_psum():
    from paddle_tpu.parallel import collective
    mesh = _mesh()
    x = np.random.RandomState(0).randn(DP, 500).astype('float32')
    exact = np.tile(x.sum(0, keepdims=True), (DP, 1))

    for key in (None, jax.random.PRNGKey(5)):
        f = _shard_map(
            lambda a, _k=key: collective.quantized_all_reduce(
                a.reshape(-1), 'dp', key=_k).reshape(a.shape), mesh)
        got = np.asarray(jax.jit(f)(x))
        rel = np.abs(got - exact).max() / np.abs(exact).max()
        assert rel < 0.05, rel
        # the reduced tensor must be IDENTICAL on every device — the
        # requantized-shard all_gather guarantees it by construction
        for d in range(1, DP):
            assert np.array_equal(got[0], got[d])

    # mean op + a size that is neither block- nor dp-divisible
    y = np.random.RandomState(1).randn(DP, 37).astype('float32')
    g = _shard_map(
        lambda a: collective.quantized_all_reduce(
            a.reshape(-1), 'dp', op='mean', block=16).reshape(a.shape),
        mesh)
    gm = np.asarray(jax.jit(g)(y))
    em = np.tile(y.mean(0, keepdims=True), (DP, 1))
    assert np.abs(gm - em).max() / np.abs(em).max() < 0.05


def test_broadcast_ppermute_formulation():
    """broadcast == root's value everywhere, for roots != 0 and a
    non-power-of-two axis (the recursive-doubling select covers both)."""
    from paddle_tpu.parallel import collective
    for n, root in ((4, 0), (4, 2), (3, 1)):
        mesh = _mesh(n)
        x = np.arange(2 * n, dtype='float32').reshape(n, 2)
        f = _shard_map(
            lambda a, _r=root: collective.broadcast(a, 'dp', root=_r),
            mesh)
        got = np.asarray(jax.jit(f)(x))
        np.testing.assert_array_equal(
            got, np.tile(x[root:root + 1], (n, 1)))


def test_wire_bytes_model():
    # the >=3x fewer wire bytes, straight from the model
    fp32 = qcore.allreduce_wire_bytes(1 << 20, 8)
    q = qcore.quantized_allreduce_wire_bytes(1 << 20, 8, block=256)
    assert fp32 / q >= 3.0
    assert qcore.allreduce_wire_bytes(100, 1) == 0.0


# ------------------------------------------------ trainer wiring
def _build_fit_a_line(quant_on, dp=0):
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.transpiler import (ParallelStrategy,
                                                transpile)
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1, act=None,
                           param_attr=fluid.ParamAttr(name='fw'))
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
    if dp:
        transpile(fluid.default_main_program(), make_mesh(dp=dp),
                  ParallelStrategy(data_parallel=True,
                                   quantized_allreduce=quant_on))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, cost


def _train(exe, cost, steps=120, seed=0):
    rng = np.random.RandomState(seed)
    true_w = rng.randn(13, 1).astype('float32')
    losses = []
    for _ in range(steps):
        xs = rng.randn(32, 13).astype('float32')
        ys = xs @ true_w + 0.5
        out = exe.run(feed={'x': xs, 'y': ys}, fetch_list=[cost])
        losses.append(float(np.asarray(out[0]).reshape(())))
    return losses, np.asarray(fluid.global_scope().find('fw'))


def test_int8_allreduce_convergence_fit_a_line():
    """The satellite contract: fit_a_line trains to tolerance with the
    quantized gradient allreduce on, and the off path is bit-identical
    to never having had the feature."""
    exe, cost = _build_fit_a_line(False, dp=DP)
    loss_f, w_f = _train(exe, cost)
    exe, cost = _build_fit_a_line(False, dp=DP)
    loss_f2, w_f2 = _train(exe, cost)
    assert np.array_equal(w_f, w_f2)          # off == off, bit-exact
    exe, cost = _build_fit_a_line(True, dp=DP)
    loss_q, w_q = _train(exe, cost)
    assert loss_q[-1] < 0.05, loss_q[-5:]
    assert abs(loss_q[-1] - loss_f[-1]) < 0.05
    assert not np.array_equal(w_q, w_f)       # the wire format ran


def test_executor_publishes_the_wire_model_of_the_traced_step():
    """The executor counts the gradient elements of the step it traced
    and publishes what the two-leg int8 schedule would put on the wire
    beside the fp32 ring: at 64x256 + 256x64 weights on dp=4 the model
    says 3x fewer bytes or better, and nothing is published for a
    program that did not ask for quantization."""
    from paddle_tpu import observe
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.transpiler import (ParallelStrategy,
                                                transpile)

    def gauges(quant_on):
        fluid.reset_default_programs()
        fluid.global_scope().clear()
        observe.reset()
        observe.enable()
        x = fluid.layers.data(name='x', shape=[64], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(input=x, size=256, act='relu')
        h = fluid.layers.fc(input=h, size=64, act='relu')
        pred = fluid.layers.fc(input=h, size=1, act=None)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.02).minimize(cost)
        transpile(fluid.default_main_program(), make_mesh(dp=DP),
                  ParallelStrategy(data_parallel=True,
                                   quantized_allreduce=quant_on))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        exe.run(feed={'x': rng.randn(8 * DP, 64).astype('float32'),
                      'y': rng.randn(8 * DP, 1).astype('float32')},
                fetch_list=[cost])
        try:
            return observe.snapshot()['gauges']
        finally:
            observe.disable()
            observe.reset()

    g = gauges(True)
    n_elems = 64 * 256 + 256 + 256 * 64 + 64 + 64 + 1
    assert g['quant.allreduce_grad_elements'] == n_elems
    assert g['quant.allreduce_bytes_fp32'] == \
        qcore.allreduce_wire_bytes(n_elems, DP)
    assert g['quant.allreduce_bytes_quant'] == \
        qcore.quantized_allreduce_wire_bytes(n_elems, DP, 256)
    assert g['quant.allreduce_compression'] >= 3.0
    assert not [k for k in gauges(False)
                if k.startswith('quant.allreduce')]


def test_quant_allreduce_env_knob_per_call():
    """PADDLE_TPU_QUANT_ALLREDUCE is read per executor call and folded
    into the compile-cache key: flipping it mid-process changes the
    traced step (recompile), and '0' overrides a program that asked
    for quantization."""
    from paddle_tpu import observe
    exe, cost = _build_fit_a_line(True, dp=DP)
    rng = np.random.RandomState(3)
    xs = rng.randn(32, 13).astype('float32')
    ys = (xs @ rng.randn(13, 1)).astype('float32')
    feed = {'x': xs, 'y': ys}
    prev = os.environ.pop('PADDLE_TPU_QUANT_ALLREDUCE', None)
    try:
        exe.run(feed=feed, fetch_list=[cost])        # quantized (flag)
        assert exe.last_cache_miss
        os.environ['PADDLE_TPU_QUANT_ALLREDUCE'] = '0'
        exe.run(feed=feed, fetch_list=[cost])        # override -> off
        assert exe.last_cache_miss                   # new cache key
        os.environ['PADDLE_TPU_QUANT_ALLREDUCE'] = '1'
        exe.run(feed=feed, fetch_list=[cost])
        # env '1' == the program flag's policy: SAME key, cache hit —
        # the key tracks the resolved policy, not the knob's source
        assert not exe.last_cache_miss
        os.environ['PADDLE_TPU_QUANT_BLOCK'] = '64'
        exe.run(feed=feed, fetch_list=[cost])        # block change: miss
        assert exe.last_cache_miss
        os.environ.pop('PADDLE_TPU_QUANT_BLOCK')
        os.environ['PADDLE_TPU_QUANT_ALLREDUCE'] = '0'
        exe.run(feed=feed, fetch_list=[cost])        # off again: hit
        assert not exe.last_cache_miss
    finally:
        os.environ.pop('PADDLE_TPU_QUANT_BLOCK', None)
        if prev is None:
            os.environ.pop('PADDLE_TPU_QUANT_ALLREDUCE', None)
        else:
            os.environ['PADDLE_TPU_QUANT_ALLREDUCE'] = prev


# --------------------------------------------------------------- PTQ
def _build_infer_model():
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    np.random.seed(0)
    ids = fluid.layers.data(name='ids', shape=[4], dtype='int64')
    x = fluid.layers.data(name='x', shape=[8], dtype='float32')
    emb = fluid.layers.embedding(input=ids, size=[50, 8])
    pooled = fluid.layers.reduce_sum(emb, dim=1)
    h = fluid.layers.fc(input=[x, pooled], size=16, act='relu')
    out = fluid.layers.fc(input=h, size=4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    infer = fluid.io.get_inference_program([out])
    feed = {'ids': np.random.randint(0, 50, (16, 4, 1)).astype('int64'),
            'x': np.random.rand(16, 8).astype('float32')}
    return exe, infer, out, feed


def test_ptq_parity_and_weight_drop():
    exe, infer, out, feed = _build_infer_model()
    scope = fluid.global_scope()
    ref = exe.run(program=infer, feed=feed, fetch_list=[out])[0]
    qprog, report = quant.quantize_inference_program(
        infer, scope, sample_feed=feed, executor=exe)
    assert report['quantized'] == 4       # embedding + 3 matmuls
    assert report['weight_bytes_int8'] < report['weight_bytes_fp32'] / 2
    got = exe.run(program=qprog, feed=feed, fetch_list=[out])[0]
    cos = float((ref * got).sum() /
                (np.linalg.norm(ref) * np.linalg.norm(got)))
    assert cos > 0.999
    assert np.abs(ref - got).max() < 0.02
    # every calibrated rel_err was measured and small
    assert all(o['rel_err'] is not None and o['rel_err'] < 0.05
               for o in report['ops'])
    # the fp32 originals are gone from the rewritten program; int8 +
    # scale pairs exist and live in scope
    qb = qprog.global_block()
    for o in report['ops']:
        assert not qb.has_var(o['param'])
        assert qb.var(o['param'] + quant.INT8_SUFFIX).dtype == 'int8'
        assert scope.find(o['param'] + quant.SCALE_SUFFIX) is not None
    # the ORIGINAL program still runs fp32 (never mutated)
    ref2 = exe.run(program=infer, feed=feed, fetch_list=[out])[0]
    np.testing.assert_array_equal(ref, ref2)


def test_ptq_calibration_threshold_reverts():
    """A max_rel_err below what int8 can deliver must keep ops fp32 —
    and the resulting program is bit-identical to the original."""
    exe, infer, out, feed = _build_infer_model()
    ref = exe.run(program=infer, feed=feed, fetch_list=[out])[0]
    qprog, report = quant.quantize_inference_program(
        infer, fluid.global_scope(), sample_feed=feed, executor=exe,
        max_rel_err=1e-9)
    assert report['quantized'] == 0 and report['skipped'] == 4
    got = exe.run(program=qprog, feed=feed, fetch_list=[out])[0]
    np.testing.assert_array_equal(ref, got)


def test_ptq_save_load_round_trip(tmp_path):
    """A PTQ'd program survives save_inference_model /
    create_predictor — int8 weights and scales serialize like any
    persistable."""
    exe, infer, out, feed = _build_infer_model()
    scope = fluid.global_scope()
    ref = exe.run(program=infer, feed=feed, fetch_list=[out])[0]
    qprog, _ = quant.quantize_inference_program(infer, scope)
    model_dir = str(tmp_path / 'ptq_model')
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(model_dir, ['ids', 'x'], [out],
                                      exe, main_program=qprog)
    from paddle_tpu.inference import create_predictor
    pred = create_predictor(model_dir, place=fluid.CPUPlace())
    got = pred.predict(feed)[0]
    cos = float((ref * got).sum() /
                (np.linalg.norm(ref) * np.linalg.norm(got)))
    assert cos > 0.999


def test_quant_analysis_pass_contracts():
    """The quant pass errors on every broken pairing the PTQ rewrite
    could produce if it rotted."""
    from paddle_tpu import analysis
    exe, infer, out, feed = _build_infer_model()
    qprog, _ = quant.quantize_inference_program(infer,
                                                fluid.global_scope())
    diags = analysis.run_passes(qprog, feed_names=['ids', 'x'],
                                fetch_names=[out.name],
                                passes=['quant'])
    assert [d for d in diags if d.severity == 'error'] == []

    def broken(mutate):
        p = qprog.clone()
        mutate(p.global_block())
        return [d.code for d in analysis.run_passes(
            p, feed_names=['ids', 'x'], fetch_names=[out.name],
            passes=['quant']) if d.severity == 'error']

    qops = [op for op in qprog.global_block().ops
            if op.type.startswith('quant_')]
    assert len(qops) == 4

    def drop_scale(b):
        next(o for o in b.ops if o.type == 'quant_mul') \
            .inputs.pop('Scale')
    assert 'quant-missing-scale' in broken(drop_scale)

    def wrong_accum(b):
        next(o for o in b.ops if o.type == 'quant_mul') \
            .attrs['accum_dtype'] = 'bfloat16'
    assert 'quant-accum-dtype' in broken(wrong_accum)

    def wrong_scale_shape(b):
        op = next(o for o in b.ops if o.type == 'quant_mul')
        b.vars[op.input('Scale')].shape = (3,)
    assert 'quant-scale-shape' in broken(wrong_scale_shape)

    def fp32_weight(b):
        op = next(o for o in b.ops if o.type == 'quant_lookup_table')
        b.vars[op.input('W')].dtype = 'float32'
    assert 'quant-weight-dtype' in broken(fp32_weight)


def test_quant_analysis_pass_kv_contracts():
    from paddle_tpu import analysis
    from paddle_tpu.serving.decode.model import (LMSpec,
                                                 build_lm_programs)
    progs = build_lm_programs(LMSpec(vocab_size=64), 2, 4, 8, 4,
                              kv_dtype='int8')

    def errs(p):
        return [d.code for d in analysis.run_passes(
            p, fetch_names=[progs.decode_fetch], passes=['quant'])
            if d.severity == 'error']

    assert errs(progs.decode) == []
    broken = progs.decode.clone()
    op = next(o for o in broken.global_block().ops
              if o.type == 'paged_decode_step')
    op.inputs.pop('KScale')
    assert 'kv-missing-scale' in errs(broken)
    broken2 = progs.decode.clone()
    op2 = next(o for o in broken2.global_block().ops
               if o.type == 'paged_decode_step')
    op2.outputs.pop('VScaleOut')
    assert 'kv-scale-not-written' in errs(broken2)


# --------------------------------------------------- quantized KV
from paddle_tpu.serving.decode import (DecodeEngine, LMSpec,  # noqa: E402
                                       random_weights)

KV_SPEC = LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8,
                 d_value=8, d_model=16, d_inner=32)
KV_WEIGHTS = random_weights(KV_SPEC, seed=3)


def _kv_engine(**kw):
    kw.setdefault('max_batch', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('num_blocks', 64)
    kw.setdefault('pages_per_seq', 4)
    kw.setdefault('weights', KV_WEIGHTS)
    kw.setdefault('place', fluid.CPUPlace())
    return DecodeEngine(KV_SPEC, **kw)


def _kv_requests(n=5, seed=0):
    rng = np.random.RandomState(seed)
    return [dict(prompt_ids=rng.randint(0, 60,
                                        int(rng.randint(1, 10))).tolist(),
                 max_new_tokens=int(rng.randint(3, 7)),
                 temperature=0.0 if i % 2 == 0 else 0.7,
                 seed=100 + i) for i in range(n)]


def test_kv_int8_concurrent_matches_sequential():
    """The PR 6 bit-consistency invariant SURVIVES quantization:
    int8-KV concurrent mixed-length decode == int8-KV sequential
    single-request decode, pages fully reclaimed, zero post-warmup
    executor cache misses (signatures unchanged by the scale arenas)."""
    from paddle_tpu import observe
    reqs = _kv_requests()
    seq_out = []
    for r in reqs:
        e = _kv_engine(kv_dtype='int8')
        e.start()
        seq_out.append(e.generate(timeout=120, **r))
        e.shutdown()

    observe.enable()
    try:
        eng = _kv_engine(kv_dtype='int8')
        eng.warmup()
        before = observe.snapshot()
        eng.start()
        streams = [eng.submit(**r) for r in reqs]
        conc = [s.result(120) for s in streams]
        eng.shutdown(drain=True)
        snap = observe.snapshot()
    finally:
        observe.disable()
        observe.reset()
    assert conc == seq_out
    assert eng.pool.free_blocks() == eng.num_blocks
    misses = [
        (k, v) for k, v in snap['counters'].items()
        if k.startswith('executor.cache_miss_total') and
        v > before['counters'].get(k, 0)]
    assert misses == [], misses
    assert eng.resident_seqs_peak >= 2


def test_kv_dtypes_generate_and_default_is_fp32():
    reqs = _kv_requests(n=3, seed=1)

    def run(kv_dtype):
        e = _kv_engine(kv_dtype=kv_dtype)
        e.start()
        outs = [e.generate(timeout=120, **r) for r in reqs]
        e.shutdown()
        return outs

    base = run(None)
    assert run('fp32') == base        # explicit fp32 == default, bit-exact
    for dt in ('bf16', 'int8') + \
            (('fp8',) if qcore.kv_fp8_supported() else ()):
        outs = run(dt)
        assert all(len(o) > 0 for o in outs)
        assert outs == run(dt)        # deterministic per dtype


def test_kv_dtype_env_knob_per_call():
    prev = os.environ.pop('PADDLE_TPU_KV_DTYPE', None)
    try:
        os.environ['PADDLE_TPU_KV_DTYPE'] = 'int8'
        eng = _kv_engine()
        assert eng.kv_dtype == 'int8'
        assert eng._progs.arena_names == ('lm_kcache', 'lm_vcache',
                                          'lm_kscale', 'lm_vscale')
        os.environ.pop('PADDLE_TPU_KV_DTYPE')
        eng2 = _kv_engine()
        assert eng2.kv_dtype == 'float32'
        # explicit ctor arg beats env
        os.environ['PADDLE_TPU_KV_DTYPE'] = 'int8'
        assert _kv_engine(kv_dtype='bf16').kv_dtype == 'bfloat16'
        with pytest.raises(ValueError):
            qcore.resolve_kv_dtype('int4')
    finally:
        if prev is None:
            os.environ.pop('PADDLE_TPU_KV_DTYPE', None)
        else:
            os.environ['PADDLE_TPU_KV_DTYPE'] = prev


def test_paged_attention_quantized_parity():
    """The dequantizing gather path vs fp32 on ragged mixed lengths —
    the parity bound of a quantized arena, in unit form."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_blocked, paged_attention_reference)
    rng = np.random.RandomState(7)
    nb, h, bs, d = 6, 2, 4, 8
    kf = rng.randn(1, nb, bs, h, d).astype('float32')   # per-head rows
    vf = rng.randn(1, nb, bs, h, d).astype('float32')

    def arena(x):                    # [L, NB, bs, H, D] -> [L, NB, bs, H*D]
        return np.asarray(x).reshape(1, nb, bs, h * d)
    q = rng.randn(3, h, d).astype('float32')
    tables = np.array([[0, 1, 2, 6], [3, 4, 6, 6], [5, 6, 6, 6]],
                      'int32')
    lens = np.array([11, 8, 3], 'int32')
    ref = np.asarray(paged_attention_reference(q, arena(kf), arena(vf),
                                               tables, lens))
    for dt in ('int8',) + \
            (('float8_e4m3fn',) if qcore.kv_fp8_supported() else ()):
        kq, ks = qcore.quantize_rows(jnp.asarray(kf), dt)
        vq, vs = qcore.quantize_rows(jnp.asarray(vf), dt)
        got = np.asarray(paged_attention_blocked(
            q, arena(kq), arena(vq), tables, lens,
            k_scales=np.asarray(ks), v_scales=np.asarray(vs)))
        cos = float((ref * got).sum() /
                    (np.linalg.norm(ref) * np.linalg.norm(got)))
        assert cos > 0.995, (dt, cos)
        assert np.abs(ref - got).max() < 0.1, dt


def test_kv_bytes_accounting():
    from paddle_tpu.serving.decode.model import (arena_bytes,
                                                 kv_bytes_per_token,
                                                 num_blocks_for_budget)
    # L*H*(dk+dv) = 2*2*16 = 64 elements/token
    assert kv_bytes_per_token(KV_SPEC, 'float32') == 64 * 4
    assert kv_bytes_per_token(KV_SPEC, 'bfloat16') == 64 * 2
    assert kv_bytes_per_token(KV_SPEC, 'int8') == 64 + 2 * 2 * 2 * 4
    budget = arena_bytes(KV_SPEC, 16, 4, 'float32')
    nb8 = num_blocks_for_budget(budget, KV_SPEC, 4, 'int8')
    assert nb8 / 16.0 >= 1.8     # the equal-bytes capacity headline
    assert arena_bytes(KV_SPEC, nb8, 4, 'int8') <= budget
