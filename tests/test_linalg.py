"""Distributed linear algebra at pod scale (ISSUE 15).

Covers the SUMMA / blocked-Cholesky / blocked-QR / power-iteration IR
ops end to end through the Executor on dp in {1, 2, 4} CPU meshes
(numpy parity, residuals), the dyadic-exact case proving SUMMA's
result is bit-identical across mesh widths, the O(N^2/P) memory
contract, panel/block resolution precedence (attr > env > tuner >
default), the autotuner's linalg op family under injected timings, and
the blocked-layout analysis pass.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import analysis, linalg, observe, tuning
from paddle_tpu.parallel.mesh import make_mesh


@pytest.fixture(autouse=True)
def _clean_knobs(monkeypatch, tmp_path):
    for var in ('PADDLE_TPU_AUTOTUNE', 'PADDLE_TPU_SUMMA_PANEL',
                'PADDLE_TPU_LINALG_BLOCK'):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv('PADDLE_TPU_TUNING_TABLE',
                       str(tmp_path / 'tuning.json'))
    tuning.reset()
    tuning.set_timer(None)
    yield
    tuning.reset()
    tuning.set_timer(None)


def _meshes():
    """dp in {1, 2, 4}: single device, 2x2, and 4x2 grids."""
    return [None, make_mesh(dp=2, tp=2), make_mesh(dp=4, tp=2)]


# ------------------------------------------------------------- parity
def test_summa_matches_numpy_across_meshes():
    rng = np.random.RandomState(0)
    n, k, m = 32, 64, 48
    a = rng.randn(n, k).astype('float32')
    b = rng.randn(k, m).astype('float32')
    ref = a.astype('float64') @ b.astype('float64')
    for mesh in _meshes():
        got = np.asarray(linalg.matmul(a, b, mesh=mesh, panel=8))
        rel = np.abs(got - ref).max() / np.abs(ref).max()
        assert rel < 1e-5, (mesh and dict(mesh.shape), rel)


def test_summa_dyadic_bit_identity_across_mesh_widths():
    """Mesh-width independence, bit for bit: with dyadic-rational
    inputs every partial sum is exactly representable in fp32, so the
    panel-ordered SUMMA accumulation and the single-device dot must
    agree to the LAST BIT on every mesh width and panel size."""
    rng = np.random.RandomState(1)
    n = 32
    a = (rng.randint(-4, 5, (n, n)) * 0.25).astype('float32')
    b = (rng.randint(-4, 5, (n, n)) * 0.25).astype('float32')
    results = [np.asarray(linalg.matmul(a, b))]
    for mesh in (make_mesh(dp=2, tp=2), make_mesh(dp=4, tp=2)):
        for panel in (4, 8):
            results.append(np.asarray(
                linalg.matmul(a, b, mesh=mesh, panel=panel)))
    for r in results[1:]:
        assert r.dtype == results[0].dtype
        assert np.array_equal(r, results[0]), \
            'SUMMA result not bit-identical across mesh widths'


def test_blocked_cholesky_matches_numpy():
    rng = np.random.RandomState(2)
    n = 32
    m0 = rng.randn(n, n).astype('float32')
    spd = (m0 @ m0.T + n * np.eye(n)).astype('float32')
    ref = np.linalg.cholesky(spd.astype('float64'))
    for mesh in [None, make_mesh(dp=2), make_mesh(dp=4)]:
        l = np.asarray(linalg.cholesky(spd, mesh=mesh, block=4))
        assert np.abs(np.triu(l, 1)).max() == 0.0
        rel = np.abs(l - ref).max() / np.abs(ref).max()
        assert rel < 1e-5, (mesh and dict(mesh.shape), rel)
        recon = np.abs(l @ l.T - spd).max() / np.abs(spd).max()
        assert recon < 1e-5


def test_blocked_qr_orthogonality_and_reconstruction():
    rng = np.random.RandomState(3)
    n, m = 64, 32
    a = rng.randn(n, m).astype('float32')
    for mesh in [None, make_mesh(dp=2), make_mesh(dp=4)]:
        q, r = linalg.qr(a, mesh=mesh, block=8)
        q, r = np.asarray(q), np.asarray(r)
        assert q.shape == (n, m) and r.shape == (m, m)
        assert np.abs(q.T @ q - np.eye(m)).max() < 1e-5
        assert np.abs(q @ r - a).max() / np.abs(a).max() < 1e-5
        assert np.abs(np.tril(r, -1)).max() < 1e-6


def _gapped_symmetric(n, seed=4):
    rng = np.random.RandomState(seed)
    qo, _ = np.linalg.qr(rng.randn(n, n))
    spectrum = np.concatenate([[10.0, 5.0],
                               np.linspace(1.0, 2.0, n - 2)])
    s = ((qo * spectrum) @ qo.T).astype('float32')
    return (s + s.T) / 2


def test_power_iteration_matches_numpy():
    n = 48
    s = _gapped_symmetric(n)
    w = np.linalg.eigvalsh(s)
    dom = float(w[np.abs(w).argmax()])
    for mesh in [None, make_mesh(dp=4)]:
        lam, v = linalg.power_iteration(s, iters=50, mesh=mesh)
        assert abs(lam - dom) / abs(dom) < 1e-3
        # v is the dominant eigenvector up to sign
        assert np.abs(np.asarray(s @ v) - lam * v).max() < 1e-2


def test_power_iteration_quantized_reduction():
    """The PR 13 compression/accuracy trade on a non-NN workload: the
    Rayleigh reduction through quantized_all_reduce converges to the
    same dominant eigenvalue within the quantization tolerance, and
    the wire-bytes model reports >= 3x compression."""
    n = 256
    s = _gapped_symmetric(n, seed=5)
    w = np.linalg.eigvalsh(s)
    dom = float(w[np.abs(w).argmax()])
    observe.enable()
    try:
        # qblock 64 so the wire model is padding-free at this N (the
        # honest model: a vector SMALLER than one scale block does not
        # compress)
        lam, _ = linalg.power_iteration(s, iters=50,
                                        mesh=make_mesh(dp=4),
                                        quantized=True, qblock=64)
        gauges = observe.snapshot().get('gauges', {})
    finally:
        observe.disable()
    assert abs(lam - dom) / abs(dom) < 5e-2
    comp = [v for kk, v in gauges.items()
            if kk.startswith('linalg.powit_compression')]
    assert comp and comp[0] >= 3.0, gauges


# ------------------------------------------- executor cache + memory
def _cache_misses():
    return sum(v for k, v in observe.snapshot()['counters'].items()
               if k.startswith('executor.cache_miss_total'))


def _summa_runs(exe):
    rng = np.random.RandomState(6)
    a = rng.randn(32, 32).astype('float32')
    b = rng.randn(32, 32).astype('float32')
    prog, out = linalg.build_matmul_program(
        32, 32, 32, mesh=make_mesh(dp=2, tp=2), panel=8)
    for _ in range(4):
        yield exe.run(prog, feed={'summa_x': a, 'summa_y': b},
                      fetch_list=[out])


def _power_iteration_runs(exe):
    s = _gapped_symmetric(48)
    for quantized in (False, True):
        # a whole 10-step iteration a run. A call builds its program, so
        # a call compiles once: the loop inside must hit the entry its
        # first step made
        yield linalg.power_iteration(s, iters=10, mesh=make_mesh(dp=4),
                                     quantized=quantized, qblock=16,
                                     executor=exe)


@pytest.mark.parametrize('runs,missed', [
    (_summa_runs, [True, False, False, False]),
    (_power_iteration_runs, [False, False])],   # a call's last step hit
    ids=['summa', 'power_iteration'])
def test_zero_cache_misses_after_warmup(runs, missed):
    """One compile a program (power iteration: one a call, the exact
    and the quantized reduction), by the executor's flag after each run
    and by its counter; every later dispatch hits."""
    compiles = {_summa_runs: 1, _power_iteration_runs: 2}[runs]
    observe.enable()
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        m0 = _cache_misses()
        flags = [exe.last_cache_miss for _ in runs(exe)]
        misses = _cache_misses() - m0
    finally:
        observe.disable()
    assert flags == missed
    assert misses == compiles


def test_memory_contract_model():
    mesh = make_mesh(dp=2, tp=4)
    # the default panel keeps the contract by construction
    panel = linalg.default_panel(2048, 2, 4, n=512, m=512)
    model = linalg.per_shard_peak_bytes('summa_matmul', mesh,
                                        (512, 2048, 512), panel=panel)
    assert model['participants'] == 8
    assert model['factor'] <= 1.5
    # an oversized panel at a small shape breaks it, and the assert
    # helper says so
    with pytest.raises(linalg.MemoryContractError):
        linalg.assert_memory_contract('summa_matmul', mesh,
                                      (64, 128, 32), panel=16)
    # plain-dict mesh shape works too (stdlib callers)
    model2 = linalg.per_shard_peak_bytes(
        'summa_matmul', {'dp': 2, 'tp': 4}, (512, 2048, 512),
        panel=panel)
    assert model2 == model


def test_panel_resolution_precedence(monkeypatch):
    """attr > env > default, observable through the trace-time
    linalg.summa_panel gauge."""
    rng = np.random.RandomState(7)
    a = rng.randn(32, 64).astype('float32')
    b = rng.randn(64, 32).astype('float32')
    mesh = make_mesh(dp=2, tp=2)
    ref = a @ b

    def run(panel=None):
        observe.enable()
        try:
            got = np.asarray(linalg.matmul(a, b, mesh=mesh,
                                           panel=panel))
            gauges = observe.snapshot().get('gauges', {})
        finally:
            observe.disable()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
        vals = [v for kk, v in gauges.items()
                if kk.startswith('linalg.summa_panel')]
        return vals[-1]

    # env knob, read per call; an illegal value rounds DOWN to legal
    monkeypatch.setenv('PADDLE_TPU_SUMMA_PANEL', '24')
    assert run() == 16
    # explicit attr beats the env
    assert run(panel=8) == 8
    monkeypatch.delenv('PADDLE_TPU_SUMMA_PANEL')
    # default: largest legal <= 256 under the memory contract
    assert run() == linalg.default_panel(64, 2, 2, n=32, m=32)


# ------------------------------------------------------ tuning family
def test_autotune_linalg_family_fake_timer(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'on')
    calls = []

    def timer(op, key, variant, thunk):
        calls.append((op, variant))
        size = variant.get('panel', variant.get('block'))
        return 0.001 if size == 16 else 0.010

    tuning.set_timer(timer)
    mesh = make_mesh(dp=2, tp=4)
    win = tuning.decide_summa_panel(64, 512, 64, 'float32', mesh)
    assert win == {'impl': 'summa', 'panel': 16}
    n = len(calls)
    assert n > 1
    # memoized: no re-measure in process
    assert tuning.decide_summa_panel(64, 512, 64, 'float32',
                                     mesh) == win
    assert len(calls) == n
    # cholesky + qr family keys record separately
    line = make_mesh(dp=4)
    wc = tuning.decide_linalg_block('blocked_cholesky', 128, 128,
                                    'float32', line)
    wq = tuning.decide_linalg_block('blocked_qr', 256, 128, 'float32',
                                    line)
    assert wc['block'] == 16 and wq['block'] == 16
    table = tuning.current_table()
    keys = sorted(k for t in table.tables.values() for k in t)
    assert any(k.startswith('summa_matmul|') for k in keys)
    assert any(k.startswith('blocked_cholesky|') for k in keys)
    assert any(k.startswith('blocked_qr|') for k in keys)


def test_tuned_panel_dispatches_through_lowering(monkeypatch):
    """PADDLE_TPU_AUTOTUNE=on + a table winner: the summa lowering uses
    the tuned panel (gauge-observable), and an explicitly set
    PADDLE_TPU_SUMMA_PANEL still overrides the table."""
    monkeypatch.setenv('PADDLE_TPU_AUTOTUNE', 'on')
    tuning.set_timer(lambda op, key, variant, thunk:
                     0.001 if variant.get('panel') == 16 else 0.010)
    rng = np.random.RandomState(8)
    a = rng.randn(16, 32).astype('float32')
    b = rng.randn(32, 16).astype('float32')
    mesh = make_mesh(dp=2, tp=2)

    def run():
        observe.enable()
        try:
            np.asarray(linalg.matmul(a, b, mesh=mesh))
            gauges = observe.snapshot().get('gauges', {})
        finally:
            observe.disable()
        return [v for kk, v in gauges.items()
                if kk.startswith('linalg.summa_panel')][-1]

    assert run() == 16                     # table winner
    monkeypatch.setenv('PADDLE_TPU_SUMMA_PANEL', '8')
    assert run() == 8                      # explicit gate beats table


# ------------------------------------------------------ analysis pass
def test_linalg_pass_flags_indivisible_shapes():
    prog, out = linalg.build_matmul_program(
        63, 128, 32, mesh=make_mesh(dp=2, tp=4), panel=8)
    codes = [d.code for d in analysis.run_passes(prog,
                                                 fetch_names=[out])
             if d.severity == 'error']
    assert 'block-indivisible' in codes


def test_linalg_pass_flags_unblocked_layouts():
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(dp=2, tp=4)
    prog, out = linalg.build_matmul_program(64, 128, 32, mesh=mesh,
                                            panel=8)
    del prog.var_shardings['summa_y']
    diags = analysis.run_passes(prog, fetch_names=[out],
                                passes=['linalg'])
    assert [d.code for d in diags] == ['layout-not-blocked']
    assert diags[0].var == 'summa_y'

    prog, out = linalg.build_matmul_program(64, 128, 32, mesh=mesh,
                                            panel=8)
    prog.var_shardings['summa_x'] = P(None, 'tp')
    codes = [d.code for d in analysis.run_passes(
        prog, fetch_names=[out], passes=['linalg'])]
    assert codes == ['implicit-full-gather']


def test_linalg_pass_warns_misaligned_panel():
    prog, out = linalg.build_matmul_program(
        64, 128, 32, mesh=make_mesh(dp=2, tp=4), panel=24)
    diags = analysis.run_passes(prog, fetch_names=[out],
                                passes=['linalg'])
    assert [d.code for d in diags] == ['panel-misaligned']
    assert diags[0].severity == 'warning'
    assert 'rounds it down to 16' in diags[0].message


def test_linalg_pass_checks_factorization_and_powit_layouts():
    from jax.sharding import PartitionSpec as P
    line = make_mesh(dp=4)
    prog, out = linalg.build_cholesky_program(63, mesh=line, block=4)
    codes = [d.code for d in analysis.run_passes(
        prog, fetch_names=[out], passes=['linalg'])]
    assert codes == ['block-indivisible']

    prog, (vout, lam) = linalg.build_power_iter_program(64, mesh=line)
    # row-blocked instead of the contract's column-blocked layout
    prog.var_shardings['powit_x'] = P('dp', None)
    codes = [d.code for d in analysis.run_passes(
        prog, fetch_names=[vout, lam], passes=['linalg'])]
    assert codes == ['implicit-full-gather']
