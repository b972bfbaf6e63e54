"""Kernel autotuner (ISSUE 8).

Covers the tuning-table lifecycle (round-trip, corruption fallback,
deterministic winners under injected timings, env-gate precedence over
table entries), the per-call block-size satellite and the stdlib CLI.
"""

import json
import os
import subprocess
import sys

import pytest

from paddle_tpu import observe, tuning


@pytest.fixture(autouse=True)
def _fresh_tuning(tmp_path, monkeypatch):
    """Every test gets its own table path, a clean tuner, and no
    autotune/gate env leakage."""
    for var in ('PADDLE_TPU_AUTOTUNE', 'PADDLE_TPU_USE_PALLAS',
                'PADDLE_TPU_BN_PALLAS', 'PADDLE_TPU_PALLAS_BLOCK_K',
                'PADDLE_TPU_PALLAS_BLOCK_Q'):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv('PADDLE_TPU_TUNING_TABLE',
                       str(tmp_path / 'tuning.json'))
    tuning.reset()
    tuning.set_timer(None)
    yield
    tuning.reset()
    tuning.set_timer(None)


def _fake_timer(winner_impl_by_key):
    """Timer giving 1ms to the keyed winner impl, 10ms to the rest."""
    calls = []

    def timer(op, key, variant, thunk):
        calls.append((op, key, variant.get('impl')))
        want = None
        for frag, impl in winner_impl_by_key.items():
            if frag in key:
                want = impl
        return 0.001 if variant.get('impl') == want else 0.010

    timer.calls = calls
    return timer


# ------------------------------------------------------- table lifecycle
def test_table_roundtrip(tmp_path):
    path = str(tmp_path / 't.json')
    t = tuning.TuningTable(path)
    t.put('cpu', 'flash_attention|x|f32',
          {'impl': 'pallas', 'block_k': 256},
          {'xla': 0.01, 'pallas bk256': 0.001})
    assert t.save() == path
    back = tuning.TuningTable.load(path)
    assert back.loaded_from_disk
    ent = back.lookup('cpu', 'flash_attention|x|f32')
    assert ent['winner'] == {'impl': 'pallas', 'block_k': 256}
    assert ent['timings']['xla'] == pytest.approx(0.01)
    assert back.size() == 1
    # merge-on-save composes with another writer's entries
    other = tuning.TuningTable(path)
    other.put('cpu', 'layer_norm|y|f32', {'impl': 'xla'}, {'xla': 0.002})
    other.save()
    merged = tuning.TuningTable.load(path)
    assert merged.size() == 2


def test_corrupted_table_ignored_with_flight_event(tmp_path):
    path = str(tmp_path / 'bad.json')
    with open(path, 'w') as f:
        f.write('{"this is": "not a tuning table"')
    observe.arm_flight()
    before = len(observe.flight_recorder().events())
    t = tuning.TuningTable.load(path)
    assert t.size() == 0 and not t.loaded_from_disk
    events = observe.flight_recorder().events()[before:]
    assert any(e['kind'] == 'tuning_table_ignored' for e in events)
    # version mismatch is equally ignored
    with open(path, 'w') as f:
        json.dump({'format_version': 999, 'tables': {}}, f)
    t2 = tuning.TuningTable.load(path)
    assert t2.size() == 0 and not t2.loaded_from_disk


def test_fake_timings_deterministic_winner(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'on')
    timer = _fake_timer({'tq1024': 'xla'})
    tuning.set_timer(timer)
    d1 = tuning.decide_attention(1, 8, 1024, 1024, 64, 'float32',
                                 True, False)
    assert d1 == {'impl': 'xla'}
    n = len(timer.calls)
    assert n > 1   # every candidate was timed exactly once
    # memo hit: no re-measurement in-process
    assert tuning.decide_attention(1, 8, 1024, 1024, 64, 'float32',
                                   True, False) == d1
    assert len(timer.calls) == n
    # table replay: a fresh process (reset()) trusts the persisted entry
    tuning.reset()
    tuning.set_timer(timer)
    assert tuning.decide_attention(1, 8, 1024, 1024, 64, 'float32',
                                   True, False) == d1
    assert len(timer.calls) == n


def test_record_mode_remeasures(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'on')
    timer = _fake_timer({'tq1024': 'xla'})
    tuning.set_timer(timer)
    tuning.decide_attention(1, 8, 1024, 1024, 64, 'float32', True, False)
    n = len(timer.calls)
    # record mode re-benchmarks even though the table has the entry
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'record')
    tuning.reset()
    timer2 = _fake_timer({'tq1024': 'pallas'})
    tuning.set_timer(timer2)
    d = tuning.decide_attention(1, 8, 1024, 1024, 64, 'float32',
                                True, False)
    assert d['impl'] == 'pallas' and len(timer2.calls) == n


def test_two_shapes_record_both_winners(monkeypatch):
    """Acceptance demo: in ONE process the kernel choice differs across
    two shapes and the table records both winners."""
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'on')
    tuning.set_timer(_fake_timer({'tq1024': 'xla', 'tq4096': 'pallas'}))
    d1k = tuning.decide_attention(4, 8, 1024, 1024, 64, 'bfloat16',
                                  True, False)
    d4k = tuning.decide_attention(1, 8, 4096, 4096, 64, 'bfloat16',
                                  True, False)
    assert d1k['impl'] == 'xla'
    assert d4k['impl'] == 'pallas' and d4k['block_q'] in (256, 512)
    table = tuning.current_table()
    assert table.size() == 2
    kinds = list(table.tables)
    winners = {k: e['winner']['impl']
               for k, e in table.tables[kinds[0]].items()}
    assert sorted(winners.values()) == ['pallas', 'xla']
    # and the persisted file agrees
    back = tuning.TuningTable.load(tuning.table_path())
    assert back.size() == 2


def test_env_gate_overrides_table(monkeypatch):
    """A table entry saying 'pallas' must lose to an explicit
    PADDLE_TPU_USE_PALLAS=0 (and vice versa, the gate alone dispatches
    pallas with autotune off)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import flash_attention as fa_mod

    called = {'n': 0}

    def marker(q, k, v, causal=False, sm_scale=None, block_q=None,
               kv_len=None, block_k=None):
        called['n'] += 1
        return attention_ops.reference_attention(q, k, v, causal=causal,
                                                 key_length=kv_len)

    monkeypatch.setattr(fa_mod, 'flash_attention', marker)
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'on')
    tuning.set_timer(_fake_timer({'tq512': 'pallas'}))
    q3 = jnp.ones((1, 512, 64), jnp.float32)

    # tuner says pallas -> flash dispatches
    out = attention_ops.fused_attention(q3, q3, q3, n_head=1, causal=True)
    assert called['n'] == 1 and out.shape == (1, 512, 64)

    # explicit env off -> table overridden, no flash dispatch
    monkeypatch.setenv('PADDLE_TPU_USE_PALLAS', '0')
    attention_ops.fused_attention(q3, q3, q3, n_head=1, causal=True)
    assert called['n'] == 1

    # explicit env on + autotune off -> flash dispatches (legacy gate)
    monkeypatch.setenv('PADDLE_TPU_USE_PALLAS', '1')
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'off')
    attention_ops.fused_attention(q3, q3, q3, n_head=1, causal=True)
    assert called['n'] == 2


# --------------------------------------------------- per-call block knobs
def test_block_k_env_read_per_call(monkeypatch):
    """The import-time DEFAULT_BLOCK_K bug: env changes after import
    must take effect (the autotuner varies blocks in-process)."""
    from paddle_tpu.ops.pallas.flash_attention import resolve_blocks
    assert resolve_blocks(1024, 1024) == (512, 128)
    monkeypatch.setenv('PADDLE_TPU_PALLAS_BLOCK_K', '256')
    assert resolve_blocks(1024, 1024)[1] == 256
    monkeypatch.setenv('PADDLE_TPU_PALLAS_BLOCK_K', '192')
    # non-pow2 override degrades to a dividing block, never asserts
    assert resolve_blocks(1024, 1024)[1] == 128
    # explicit args (the tuner's winner) beat the env
    assert resolve_blocks(1024, 1024, 256, 512) == (256, 512)


def test_attention_block_variants_divide():
    from paddle_tpu.ops.pallas.flash_attention import (
        attention_block_variants)
    for tq, tk in ((1024, 1024), (4096, 4096), (512, 768), (128, 128)):
        pairs = attention_block_variants(tq, tk)
        assert pairs
        for bq, bk in pairs:
            assert tq % bq == 0 and tk % bk == 0


# ------------------------------------------------------------------- CLI
def test_tuning_inspect_cli(tmp_path, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'on')
    tuning.set_timer(_fake_timer({'tq1024': 'xla',
                                  'matmul_dtype': 'fp8'}))
    tuning.decide_attention(1, 8, 1024, 1024, 64, 'float32', True, False)
    # a linalg-family entry rides the same table (ISSUE 15)
    from paddle_tpu.parallel.mesh import make_mesh
    tuning.decide_summa_panel(64, 512, 64, 'float32',
                              make_mesh(dp=2, tp=2))
    # a matmul compute-dtype entry too (ISSUE 19)
    tuning.decide_matmul_dtype(64, 64, 64, 'float32')
    path = tuning.table_path()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, 'tools', 'tuning_inspect.py')
    r = subprocess.run([sys.executable, script, path, '--json'],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc['kind'] == 'paddle_tpu_tuning_table'
    assert doc['status'] == 'ok' and doc['n_entries'] == 3
    kind = doc['device_kinds'][0]
    attn = [e for k, e in doc['tables'][kind].items()
            if k.startswith('flash_attention')]
    assert attn[0]['winner'] == 'xla'
    assert attn[0]['timings_ms']['xla'] == pytest.approx(1.0)
    # the linalg summary section names the panel winner + margin
    (lkey, lent), = doc['linalg'][kind].items()
    assert lkey.startswith('summa_matmul|n64 k512 m64|dp2 tp2')
    assert lent['op'] == 'summa_matmul'
    assert isinstance(lent['size'], int)
    assert 'margin_over_runner_up' in lent
    # the matmul-dtype summary names the fp8-vs-native winner + shape
    (mkey, ment), = doc['matmul_dtype'][kind].items()
    assert mkey.startswith('matmul_dtype|m64 k64 n64')
    assert ment['op'] == 'matmul_dtype'
    assert ment['winner'] == 'fp8'
    assert ment['shape'] == 'm64 k64 n64'
    assert 'margin_over_runner_up' in ment
    # --linalg filters the tables to the family
    r3 = subprocess.run([sys.executable, script, path, '--json',
                         '--linalg'],
                        capture_output=True, text=True, timeout=60)
    doc3 = json.loads(r3.stdout)
    assert all(k.startswith('summa_matmul')
               for k in doc3['tables'][kind])
    # --matmul-dtype filters to the compute-dtype entries
    r4 = subprocess.run([sys.executable, script, path, '--json',
                         '--matmul-dtype'],
                        capture_output=True, text=True, timeout=60)
    doc4 = json.loads(r4.stdout)
    assert all(k.startswith('matmul_dtype')
               for k in doc4['tables'][kind])
    assert doc4['tables'][kind]
    # text mode renders without jax in the tool (stdlib-only contract)
    r2 = subprocess.run([sys.executable, script, path],
                        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 0 and 'winner' in r2.stdout
    assert 'linalg panel/block winners' in r2.stdout
    assert 'matmul dtype winners' in r2.stdout
