"""The examples/ directory stays runnable: each script executes
end-to-end on CPU in a subprocess (compile-heavy ones get generous
watchdogs). The C inference example is covered by tests/test_capi.py's
compiled-client tests."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, timeout=420):
    env = dict(os.environ)
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    # the examples are written for the chip; here they run on the host
    # CPU, asked for by name before any device query
    boot = ("from paddle_tpu.core.platform_boot import force_host_cpu; "
            "force_host_cpu(); "
            "import runpy; runpy.run_path(%r, run_name='__main__')"
            % os.path.join(REPO, 'examples', name))
    r = subprocess.run([sys.executable, '-c', boot],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


def test_fit_a_line_example():
    out = _run_example('train_fit_a_line.py')
    assert 'reloaded model max abs err' in out


def test_pipelined_transformer_example():
    out = _run_example('train_transformer_pipelined.py')
    assert 'step 9' in out


def test_ctr_sparse_resume_example():
    out = _run_example('train_ctr_sparse_resume.py')
    assert 'expect 8' in out
    assert 'epoch finished' in out


def test_v1_quickstart_example():
    out = _run_example('train_v1_quickstart.py')
    final = float(out.strip().splitlines()[-1].split()[-1])
    assert final < 0.1


def test_v1_seq2seq_generate_example():
    out = _run_example('train_v1_seq2seq_generate.py')
    assert 'top-beam copy accuracy' in out
