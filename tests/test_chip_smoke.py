"""chip_smoke.py's contract off the chip: the rehearsal runs every leg
at tiny size on the CPU, the real command refuses a machine without a
TPU, and TPUPlace never silently resolves to whatever device exists."""

import collections
import json
import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, 'chip_smoke.py')


def _smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('PADDLE_TPU_PALLAS_INTERPRET', None)
    return subprocess.run([sys.executable, SMOKE] + list(args), env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=600)


def test_rehearsal_runs_every_leg_on_cpu():
    r = _smoke('--rehearsal')
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    lines = r.stdout.splitlines()
    assert lines[0] == 'REHEARSAL platform=cpu'
    legs = {ln.split()[1]: json.loads(ln.split(' ', 2)[2])
            for ln in lines if ln.startswith('LEG ')}
    assert sorted(legs) == ['four_chip', 'kernels', 'serve', 'train']
    assert legs['train']['last_loss'] < legs['train']['first_loss']
    assert legs['serve']['misses_after_warmup'] == 0
    assert legs['kernels']['interpret'] is True
    assert {'layer_norm', 'batch_norm', 'paged_attention'} <= \
        set(legs['kernels']['first_call_s'])
    assert legs['four_chip']['shards']['src_word'] == [4, 8]
    last = json.loads(lines[-1])
    assert last['ok'] is True and last['rehearsal'] is True
    assert last['device']['platform'] == 'cpu'


def test_without_a_tpu_the_smoke_refuses_and_names_the_platform():
    r = _smoke()
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr
    assert 'LEG ' not in r.stdout and '"ok"' not in r.stdout


def test_tpuplace_raises_when_no_tpu_and_cpu_not_asked_for(monkeypatch):
    import jax
    Dev = collections.namedtuple('Dev', 'platform device_kind')
    asked = jax.config.jax_platforms
    monkeypatch.setattr(jax, 'devices', lambda *a: [Dev('cpu', 'cpu')])
    jax.config.update('jax_platforms', None)     # platform unset
    try:
        with pytest.raises(RuntimeError, match="no TPU.*'cpu'"):
            fluid.Executor(fluid.TPUPlace(0))
        # a TPU resolves whatever was or was not asked for
        monkeypatch.setattr(jax, 'devices',
                            lambda *a: [Dev('tpu', 'TPU v5 lite')])
        assert fluid.TPUPlace(0).jax_device().platform == 'tpu'
    finally:
        jax.config.update('jax_platforms', asked)
    # asked for by name (the suite's own setting), the CPU is TPUPlace(0)
    monkeypatch.undo()
    assert fluid.TPUPlace(0).jax_device().platform == 'cpu'
