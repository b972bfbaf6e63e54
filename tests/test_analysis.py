"""Program IR verifier (paddle_tpu.analysis): each of the five passes
against a minimally-broken Program (asserting pass name, severity, op
index, and construction provenance file:line), the executor's
PADDLE_TPU_VERIFY integration (strict raises BEFORE any trace, warn
compiles and runs with the flight event + counters recorded, one
verification per program key), startup verification in the trainer and
decode engine, the tools/program_lint.py CLI, and the transformer
train program verified clean, once a program key."""

import inspect
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, observe
from paddle_tpu.analysis import ProgramVerifyError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ME = os.path.basename(__file__)


@pytest.fixture(autouse=True)
def _clean():
    os.environ.pop('PADDLE_TPU_VERIFY', None)
    yield
    os.environ.pop('PADDLE_TPU_VERIFY', None)
    observe._flight_armed = False
    observe._FLIGHT_DUMP.update(path=None, last_exc=None, last_path=None)
    observe.disable()
    observe.reset()


def _here():
    """'test_analysis.py:<line of the caller>'."""
    return '%s:%d' % (_ME, inspect.currentframe().f_back.f_lineno)


def _find(diags, pass_name, code):
    got = [d for d in diags if d.pass_name == pass_name and
           d.code == code]
    assert got, 'no %s/%s in %s' % (pass_name, code,
                                    [d.format() for d in diags])
    return got[0]


def _assert_provenance(diag, expect):
    assert diag.provenance is not None, diag.format()
    assert diag.provenance.endswith(expect), \
        '%r does not end with %r' % (diag.provenance, expect)


def _program_verify_events():
    return [e['data'] for e in observe.flight_recorder().events()
            if e['kind'] == 'program_verify']


# ------------------------------------------------------------ the passes
def test_wellformed_undefined_input_with_provenance():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var(name='o', shape=[2, 2], dtype='float32')
    b.append_op('relu', inputs={'X': ['nope']}, outputs={'Out': ['o']}); line = _here()  # noqa: E702
    d = _find(analysis.run_passes(prog), 'wellformed', 'undefined-input')
    assert d.severity == 'error'
    assert d.op_index == 0
    assert d.op_type == 'relu'
    assert d.var == 'nope'
    _assert_provenance(d, line)


def test_wellformed_use_before_def_and_duplicate_and_dead():
    prog = fluid.Program()
    b = prog.global_block()
    for n in ('x', 't', 'o', 'dead'):
        b.create_var(name=n, shape=[2, 2], dtype='float32',
                     is_data=(n == 'x'))
    b.append_op('relu', inputs={'X': ['t']}, outputs={'Out': ['o']}); use_line = _here()  # noqa: E702
    b.append_op('tanh', inputs={'X': ['x']}, outputs={'Out': ['t']})
    b.append_op('tanh', inputs={'X': ['x']}, outputs={'Out': ['t']}); dup_line = _here()  # noqa: E702
    b.append_op('sigmoid', inputs={'X': ['x']}, outputs={'Out': ['dead']}); dead_line = _here()  # noqa: E702
    diags = analysis.run_passes(prog, fetch_names=['o'])

    d = _find(diags, 'wellformed', 'use-before-def')
    assert (d.severity, d.op_index) == ('error', 0)
    _assert_provenance(d, use_line)

    d = _find(diags, 'wellformed', 'duplicate-writer')
    assert (d.severity, d.op_index, d.var) == ('warning', 2, 't')
    _assert_provenance(d, dup_line)

    # ops 1-2 are dead too: liveness walks in reverse, and the only
    # read of 't' (op#0) precedes both writers, so neither reaches the
    # fetch — exactly the bug the use-before-def error explains
    dead = [x for x in diags
            if x.pass_name == 'wellformed' and x.code == 'dead-op']
    assert sorted(x.op_index for x in dead) == [1, 2, 3]
    d, = (x for x in dead if x.op_index == 3)
    assert d.severity == 'info'
    _assert_provenance(d, dead_line)


def test_shapes_matmul_mismatch():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var(name='x', shape=[-1, 4], dtype='float32', is_data=True)
    b.create_parameter('w', shape=[5, 3], dtype='float32')
    b.create_var(name='o', shape=[-1, 3], dtype='float32')
    b.append_op('mul', inputs={'X': ['x'], 'Y': ['w']}, outputs={'Out': ['o']}); line = _here()  # noqa: E702
    d = _find(analysis.run_passes(prog), 'shapes', 'matmul-mismatch')
    assert d.severity == 'error'
    assert d.op_index == 0
    assert d.op_type == 'mul'
    _assert_provenance(d, line)
    assert '4' in d.message and '5' in d.message


def _attention_program(shapes, n_head=4):
    """One fused_attention op over vars of the given shapes: the
    projected form when 'Wq' is among them, else Q/K/V."""
    prog = fluid.Program()
    b = prog.global_block()
    for name, shape in shapes.items():
        if name.startswith('W'):
            b.create_parameter(name, shape=shape, dtype='float32')
        else:
            b.create_var(name=name, shape=shape, dtype='float32',
                         is_data=True)
    b.create_var(name='o', shape=[-1, 6, 16], dtype='float32')
    b.append_op('fused_attention',
                inputs={n: [n] for n in shapes}, outputs={'Out': ['o']},
                attrs={'n_head': n_head})
    return prog


_PROJECTED = {'X': [-1, 6, 16], 'Mem': [-1, 9, 16], 'Wq': [16, 32],
              'Wk': [16, 32], 'Wv': [16, 24], 'Wo': [24, 16]}
_QKV = {'Q': [-1, 6, 32], 'K': [-1, 9, 32], 'V': [-1, 9, 24]}


@pytest.mark.parametrize('form', ['projected', 'qkv'])
def test_shapes_attention_accepts_both_forms(form):
    """Tq != Tk and Dk != Dv are legal in either form."""
    prog = _attention_program(_PROJECTED if form == 'projected' else _QKV)
    assert [d for d in analysis.run_passes(prog)
            if d.code == 'attention-mismatch'] == []


@pytest.mark.parametrize('base,change,var,words', [
    (_PROJECTED, {'Wq': [16, 30], 'Wk': [16, 30]}, 'Wq',
     ('30', 'n_head=4')),
    (_PROJECTED, {'Wv': [16, 22], 'Wo': [22, 16]}, 'Wv',
     ('22', 'n_head=4')),
    (_PROJECTED, {'Wk': [16, 28]}, 'Wk', ('key feature dim',)),
    (_PROJECTED, {'Wq': [12, 32]}, 'Wq', ('12 rows', 'X', '16')),
    (_PROJECTED, {'Wk': [20, 32]}, 'Wk', ('20 rows', 'Mem', '16')),
    (_PROJECTED, {'Wv': [20, 24]}, 'Wv', ('20 rows', 'Mem', '16')),
    (_PROJECTED, {'Wo': [32, 16]}, 'Wo', ('32 rows', 'Wv', '24')),
    (_QKV, {'Q': [-1, 6, 30], 'K': [-1, 9, 30]}, 'Q', ('30', 'n_head=4')),
    (_QKV, {'K': [-1, 9, 28]}, 'K', ('key feature dim',)),
    (_QKV, {'V': [-1, 7, 24]}, 'V', ('source sequence dim',)),
], ids=['wq-heads', 'wv-heads', 'wq-wk', 'x-wq', 'mem-wk', 'mem-wv',
        'wv-wo', 'q-heads', 'q-k', 'k-v-length'])
def test_shapes_attention_mismatch(base, change, var, words):
    """Each contract of fused_attention fires from the shapes of the
    form the op carries: the weights' when it owns its projections,
    Q/K/V's when the caller projected."""
    prog = _attention_program(dict(base, **change))
    got = [d for d in analysis.run_passes(prog)
           if d.pass_name == 'shapes' and d.code == 'attention-mismatch']
    assert got, 'no attention-mismatch'
    d, = [d for d in got if d.var == var]
    assert (d.severity, d.op_index, d.op_type) == \
        ('error', 0, 'fused_attention')
    for word in words:
        assert word in d.message, d.message


def test_shapes_elementwise_and_optimizer_contracts():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var(name='x', shape=[-1, 8], dtype='float32', is_data=True)
    b.create_var(name='y', shape=[3], dtype='float32', is_data=True)
    b.create_var(name='o', shape=[-1, 8], dtype='float32')
    b.append_op('elementwise_add', inputs={'X': ['x'], 'Y': ['y']},
                outputs={'Out': ['o']})
    w = b.create_parameter('w', shape=[4, 4], dtype='float32')
    b.create_var(name='w@GRAD', shape=[4, 5], dtype='float32')
    b.create_var(name='lr', shape=[1], dtype='float32', persistable=True)
    b.append_op('sgd', inputs={'Param': ['w'], 'Grad': ['w@GRAD'],
                               'LearningRate': ['lr']},
                outputs={'ParamOut': ['w']})
    diags = analysis.run_passes(prog)
    d = _find(diags, 'shapes', 'broadcast-mismatch')
    assert (d.severity, d.op_index) == ('error', 0)
    d = _find(diags, 'shapes', 'update-shape-mismatch')
    assert (d.severity, d.op_index) == ('error', 1)
    assert w.name in d.message


def test_sharding_indivisible_and_conflict():
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.mesh import make_mesh
    prog = fluid.Program()
    b = prog.global_block()
    b.create_parameter('w', shape=[3, 4], dtype='float32')
    b.create_var(name='a', shape=[8, 8], dtype='float32', is_data=True)
    b.create_var(name='c', shape=[8, 8], dtype='float32', is_data=True)
    b.create_var(name='o', shape=[8, 8], dtype='float32')
    b.append_op('elementwise_add', inputs={'X': ['a'], 'Y': ['c']}, outputs={'Out': ['o']}); line = _here()  # noqa: E702
    prog.mesh = make_mesh(tp=8)
    prog.var_shardings = {'w': P('tp'), 'a': P('tp', None),
                          'c': P(None, 'tp')}
    diags = analysis.run_passes(prog)

    d = _find(diags, 'sharding', 'axis-indivisible')
    assert d.severity == 'error'
    assert d.var == 'w'
    assert '3 % 8' in d.message

    d = _find(diags, 'sharding', 'spec-conflict')
    assert (d.severity, d.op_index) == ('warning', 0)
    _assert_provenance(d, line)


def test_donation_double_and_read_after_donate():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_parameter('w', shape=[4], dtype='float32')
    b.create_var(name='g', shape=[4], dtype='float32', is_data=True)
    b.create_var(name='lr', shape=[1], dtype='float32', persistable=True)
    b.create_var(name='peek', shape=[4], dtype='float32')
    sgd = {'inputs': {'Param': ['w'], 'Grad': ['g'],
                      'LearningRate': ['lr']},
           'outputs': {'ParamOut': ['w']}}
    b.append_op('sgd', **sgd)
    b.append_op('sgd', **sgd); dup_line = _here()  # noqa: E702
    b.append_op('scale', inputs={'X': ['w']}, outputs={'Out': ['peek']}, attrs={'scale': 1.0}); read_line = _here()  # noqa: E702
    diags = analysis.run_passes(prog)

    d = _find(diags, 'donation', 'double-donation')
    assert (d.severity, d.op_index, d.var) == ('error', 1, 'w')
    _assert_provenance(d, dup_line)

    d = _find(diags, 'donation', 'read-after-donate')
    assert (d.severity, d.op_index, d.var) == ('warning', 2, 'w')
    _assert_provenance(d, read_line)


def test_recompile_attr_object_and_dynamic_feed():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var(name='x', shape=[-1, -1], dtype='int64', is_data=True)
    b.create_var(name='o', shape=[-1, -1], dtype='int64')
    b.append_op('scale', inputs={'X': ['x']}, outputs={'Out': ['o']}, attrs={'hook': lambda v: v}); line = _here()  # noqa: E702
    diags = analysis.run_passes(prog)

    d = _find(diags, 'recompile', 'attr-callable')
    assert (d.severity, d.op_index) == ('error', 0)
    _assert_provenance(d, line)

    # object() repr embeds a memory address
    b.append_op('scale', inputs={'X': ['x']}, outputs={'Out': ['o']},
                attrs={'thing': object()})
    diags = analysis.run_passes(prog)
    d = _find(diags, 'recompile', 'attr-object-id')
    assert (d.severity, d.op_index) == ('error', 1)

    d = _find(diags, 'recompile', 'dynamic-feed-dim')
    assert (d.severity, d.var) == ('warning', 'x')


def test_recompile_attr_object_only_when_present():
    # the lambda also repr-matches object-id; this case is the pure one
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var(name='x', shape=[-1, 2], dtype='float32', is_data=True)
    b.create_var(name='o', shape=[-1, 2], dtype='float32')
    b.append_op('scale', inputs={'X': ['x']}, outputs={'Out': ['o']},
                attrs={'scale': 2.0, 'name': 'fine', 'dims': [1, 2]})
    diags = analysis.run_passes(prog)
    assert not [d for d in diags if d.pass_name == 'recompile'
                and d.code.startswith('attr-')]


# --------------------------------------------------- executor integration
def _broken_program():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var(name='o', shape=[2, 2], dtype='float32')
    b.append_op('relu', inputs={'X': ['nope']}, outputs={'Out': ['o']})
    return prog


def test_strict_mode_raises_before_any_trace():
    os.environ['PADDLE_TPU_VERIFY'] = 'strict'
    observe.arm_flight()
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(ProgramVerifyError) as ei:
        exe.run(program=_broken_program(), feed={}, fetch_list=['o'])
    assert ei.value.diagnostics
    assert any(d.code == 'undefined-input' for d in ei.value.diagnostics)
    kinds = [e['kind'] for e in observe.flight_recorder().events()]
    # verification fired; nothing traced or compiled
    assert 'program_verify' in kinds
    assert 'compile' not in kinds


def test_warn_mode_compiles_and_records():
    os.environ['PADDLE_TPU_VERIFY'] = 'warn'
    observe.enable()
    observe.arm_flight()
    # a program with a warning-severity finding that still runs fine:
    # two writers of one temporary (last write wins in the trace)
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    h = fluid.layers.fc(input=x, size=4, act='relu')
    b = fluid.default_main_program().global_block()
    b.append_op('tanh', inputs={'X': [x.name]}, outputs={'Out': [h.name]})
    b.append_op('tanh', inputs={'X': [x.name]}, outputs={'Out': [h.name]})
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    out, = exe.run(feed={'x': np.ones((2, 4), 'float32')},
                   fetch_list=[h])
    assert np.asarray(out).shape == (2, 4)

    events = _program_verify_events()
    assert any(e['warnings'] >= 1 for e in events)
    n = observe.get_counter('analysis.diagnostics_total',
                            severity='warning', **{'pass': 'wellformed'})
    assert n >= 1

    # once per key: re-running the same signature adds no new event
    before = len(_program_verify_events())
    exe.run(feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[h])
    assert len(_program_verify_events()) == before


def test_verify_off_by_default_on_executor():
    observe.arm_flight()
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    h = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[h])
    assert not _program_verify_events()


def test_trainer_verifies_at_startup():
    observe.arm_flight()

    def net():
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1)
        return [fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))]

    def opt():
        return fluid.optimizer.SGD(learning_rate=0.1)

    def reader():
        for _ in range(2):
            yield {'x': np.ones((2, 4), 'float32'),
                   'y': np.ones((2, 1), 'float32')}

    t = fluid.Trainer(net, opt, place=fluid.CPUPlace())
    t.train(num_epochs=1, reader=reader)
    assert any(e['label'] == 'trainer'
               for e in _program_verify_events())


def test_serving_engine_verifies_at_startup(tmp_path):
    observe.arm_flight()
    from paddle_tpu.inference import create_predictor
    from paddle_tpu.serving import ServingEngine
    x = fluid.layers.data(name='x', shape=[6], dtype='float32')
    pred = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / 'm')
    fluid.io.save_inference_model(d, ['x'], [pred], exe)
    eng = ServingEngine(create_predictor(d, place=fluid.CPUPlace()),
                        max_batch_size=2)
    try:
        eng.start()
        assert any(e['label'] == 'serving'
                   for e in _program_verify_events())
    finally:
        eng.shutdown(drain=False)


def test_decode_engine_verifies_at_startup():
    observe.arm_flight()
    from paddle_tpu.serving.decode import DecodeEngine, LMSpec
    eng = DecodeEngine(LMSpec(vocab_size=64), max_batch=2, block_size=4,
                       num_blocks=8, pages_per_seq=2)
    try:
        labels = set(e['label'] for e in _program_verify_events())
        assert {'decode_startup', 'decode_prefill',
                'decode_step'} <= labels
    finally:
        eng.shutdown(drain=False)


def test_strict_engine_construction_fails_on_broken_graph():
    # strict refuses at startup_verify too: ProgramVerifyError from the
    # trainer before any compile
    os.environ['PADDLE_TPU_VERIFY'] = 'strict'

    def net():
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        # sabotage: an op reading a name nothing defines
        fluid.default_main_program().global_block().append_op(
            'relu', inputs={'X': ['ghost']}, outputs={'Out': [cost.name]})
        return [cost]

    t = fluid.Trainer(net, lambda: fluid.optimizer.SGD(learning_rate=0.1),
                      place=fluid.CPUPlace())
    with pytest.raises(ProgramVerifyError):
        t.train(num_epochs=1,
                reader=lambda: iter([{'x': np.ones((2, 4), 'float32'),
                                      'y': np.ones((2, 1), 'float32')}]))


# ------------------------------------------------------------------- CLI
def test_program_lint_cli_json_schema():
    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = tempfile.mkdtemp()
    fluid.io.save_inference_model(d, ['x'], [pred], exe)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'program_lint.py'),
         d, '--json'], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert set(rep) == {'model', 'ops', 'counts', 'diagnostics'}
    assert rep['counts'] == {'error': 0, 'warning': 0, 'info': 0}
    assert rep['ops'] >= 2


def test_program_lint_cli_flags_broken_model():
    from paddle_tpu.core.serialize import program_to_dict
    prog = _broken_program()
    d = tempfile.mkdtemp()
    with open(os.path.join(d, '__model__.json'), 'w') as f:
        json.dump({'feed_names': [], 'fetch_names': ['o'],
                   'program': program_to_dict(prog)}, f)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'program_lint.py'),
         d, '--json'], capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep['counts']['error'] >= 1
    bad = [dd for dd in rep['diagnostics']
           if dd['code'] == 'undefined-input']
    assert bad and bad[0]['pass'] == 'wellformed'
    # provenance survived serialization: this very file built the op
    assert bad[0]['provenance'] and _ME in bad[0]['provenance']


# ------------------------------------------- the train program verifies
def test_verifier_overhead_vs_cold_compile():
    """Every analysis pass over the transformer train program finds no
    error; under PADDLE_TPU_VERIFY=strict the executor walks it once a
    program key and not once a step, and the program it passed compiles
    and runs. What a walk costs beside the cold compile it precedes is
    a time: not measured here."""
    from paddle_tpu.models import transformer as T
    batch, seq, vocab = 2, 16, 512
    avg_cost, _ = T.transformer_base(
        src_vocab_size=vocab, trg_vocab_size=vocab,
        src_seq_len=seq, trg_seq_len=seq, max_length=256)
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    prog = fluid.default_main_program()
    diags = analysis.run_passes(prog, fetch_names=[avg_cost.name])
    assert analysis.summarize(diags)['error'] == 0, diags

    observe.enable()
    os.environ['PADDLE_TPU_VERIFY'] = 'strict'
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = T.make_fake_batch(batch, seq, seq, vocab, vocab)
    losses = [float(np.asarray(exe.run(
        feed=feed, fetch_list=[avg_cost])[0]).reshape(()))
        for _ in range(3)]
    hists = observe.snapshot()['histograms']
    assert np.isfinite(losses).all(), losses
    walks = [h['count'] for k, h in hists.items()
             if k.startswith('analysis.verify_seconds')]
    # two program keys (startup, train), three steps of the second
    assert walks and sum(walks) == 2, hists.keys()
