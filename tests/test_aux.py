"""Aux subsystems: errors, flags, lod, debug, memory_optimize, datasets,
profiler (reference: platform/enforce.h, fluid/debuger.py,
memory_optimization_transpiler.py, v2/dataset tests)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from util import run_startup_and, rand


def test_enforce():
    from paddle_tpu.core.errors import enforce, enforce_shape_match, \
        EnforceError
    enforce(True, 'fine')
    with pytest.raises(EnforceError):
        enforce(False, 'bad %d', 7)
    enforce_shape_match((None, 3), (8, 3))
    with pytest.raises(EnforceError):
        enforce_shape_match((2, 3), (3, 3))


def test_flags_env(monkeypatch):
    from paddle_tpu.core import flags
    monkeypatch.setenv('PADDLE_TPU_V', '3')
    got = flags.init_flags({'benchmark': True})
    assert got['v'] == 3 and got['benchmark'] is True
    with pytest.raises(KeyError):
        flags.set_flag('nope', 1)


def test_lod_pad_roundtrip():
    from paddle_tpu.core.lod import (pad_sequences, unpad_sequences,
                                     create_lod_tensor, bucket_length)
    seqs = [[1, 2, 3], [4], [5, 6]]
    padded, lengths = pad_sequences(seqs, pad_value=0)
    assert padded.shape == (3, 3)
    np.testing.assert_array_equal(lengths, [3, 1, 2])
    back = unpad_sequences(padded, lengths)
    for a, b in zip(back, seqs):
        np.testing.assert_array_equal(a, b)
    padded2, lengths2 = create_lod_tensor(
        np.arange(6), [[3, 1, 2]])
    np.testing.assert_array_equal(lengths2, [3, 1, 2])
    assert bucket_length(33) == 64


def test_debug_program_printer(tmp_path):
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    out = fluid.layers.fc(input=x, size=2)
    code = fluid.debug.program_to_code()
    assert 'mul' in code and 'x[float32' in code
    dot = fluid.debug.draw_block_graphviz(
        fluid.default_main_program().global_block(),
        path=str(tmp_path / 'g.dot'))
    assert 'digraph' in open(dot).read()


def test_memory_optimize_remat_still_correct():
    x = fluid.layers.data(name='x', shape=[8], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    h = fluid.layers.fc(input=x, size=16, act='relu')
    pred = fluid.layers.fc(input=h, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    fluid.memory_optimize(level=1)
    assert fluid.default_main_program().remat_policy == 'full'
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    xs = rng.rand(16, 8).astype('float32')
    ys = xs.sum(1, keepdims=True).astype('float32')
    losses = [float(np.asarray(exe.run(feed={'x': xs, 'y': ys},
                                       fetch_list=[loss])[0]).reshape(()))
              for _ in range(20)]
    assert losses[-1] < losses[0]


def test_new_datasets_schemas():
    from paddle_tpu.dataset import (conll05, sentiment, wmt16, flowers,
                                    voc2012, mq2007)
    item = next(iter(conll05.train()()))
    assert len(item) == 9 and len(item[0]) == len(item[8])
    toks, label = next(iter(sentiment.train()()))
    assert label in (0, 1) and len(toks) >= 8
    src, trg_in, trg_next = next(iter(wmt16.train()()))
    assert trg_in[0] == 0 and trg_next[-1] == 1
    assert len(trg_in) == len(trg_next)
    img, label = next(iter(flowers.train()()))
    assert img.shape == (3, 32, 32) and 0 <= label < flowers.CLASS_NUM
    img, seg = next(iter(voc2012.train()()))
    assert seg.shape == img.shape[1:]
    better, worse = next(iter(mq2007.train(format='pairwise')()))
    assert better.shape == (mq2007.FEATURE_DIM,)
    feats, rel = next(iter(mq2007.train(format='listwise')()))
    assert feats.shape[0] == len(rel)


def test_profiler_context():
    with fluid.profiler.profiler('CPU', 'total'):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        out = fluid.layers.fc(input=x, size=2)
        run_startup_and({'x': rand(2, 4)}, [out])
