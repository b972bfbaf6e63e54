"""Where compiled artefacts go: one rule, one place
(core/platform_boot.cache_root). With JAX_COMPILATION_CACHE_DIR set
everything lives there and the code sets no directory; unset, it is
<checkout>/.jax_cache in every process. Nothing resolves under the
system temp directory."""

import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(REPO, '.jax_cache')

# a fresh process that arms the cache the way Executor() does and says
# where jax will keep it
REPORT = ("import jax; "
          "from paddle_tpu.core import platform_boot as pb; "
          "pb.arm_compile_cache(); "
          "print('DIR', jax.config.jax_compilation_cache_dir)")
# the same, after compiling and running one small program
TRAIN = ("import numpy as np, jax, paddle_tpu as fluid; "
         "x = fluid.layers.data(name='x', shape=[4], dtype='float32'); "
         "y = fluid.layers.fc(input=x, size=2); "
         "exe = fluid.Executor(fluid.TPUPlace(0)); "
         "exe.run(fluid.default_startup_program()); "
         "exe.run(feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[y]); "
         "print('DIR', jax.config.jax_compilation_cache_dir)")


def _fresh_process(code, tmp_path, **env_overrides):
    env = dict(os.environ)
    for var in ('JAX_COMPILATION_CACHE_DIR', 'PADDLE_TPU_TUNING_TABLE'):
        env.pop(var, None)
    scratch = tmp_path / 'tmpdir'
    scratch.mkdir(exist_ok=True)
    env.update({'JAX_PLATFORMS': 'cpu', 'TMPDIR': str(scratch),
                # 'auto' arms the cache on TPU only; the suite is on CPU
                'PADDLE_TPU_COMPILE_CACHE': '1',
                'PYTHONPATH': REPO})
    env.update(env_overrides)
    r = subprocess.run([sys.executable, '-c', code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    line, = [ln for ln in r.stdout.splitlines() if ln.startswith('DIR ')]
    return line[len('DIR '):], scratch


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


def test_env_dir_is_where_the_cache_lives_and_nowhere_else(tmp_path):
    placed = tmp_path / 'placed'
    before = _listing(CHECKOUT_CACHE)
    got, scratch = _fresh_process(
        TRAIN, tmp_path, JAX_COMPILATION_CACHE_DIR=str(placed))
    assert got == str(placed)
    assert any(name.endswith('-cache') for name in os.listdir(placed)), \
        'jax wrote no entry into JAX_COMPILATION_CACHE_DIR'
    # no other path was written: not the checkout's default directory,
    # not the system temp directory
    assert _listing(CHECKOUT_CACHE) == before
    assert not [n for n in os.listdir(scratch) if 'paddle_tpu' in n]


def test_unset_every_process_names_the_checkout(tmp_path):
    first, _ = _fresh_process(REPORT, tmp_path)
    second, _ = _fresh_process(REPORT, tmp_path)
    assert first == second == CHECKOUT_CACHE


def test_nothing_resolves_under_the_temp_dir(monkeypatch, tmp_path):
    from paddle_tpu import tuning
    from paddle_tpu.core import platform_boot

    def kept():
        return [platform_boot.cache_root(), tuning.table_path()]

    for var in ('JAX_COMPILATION_CACHE_DIR', 'PADDLE_TPU_TUNING_TABLE'):
        monkeypatch.delenv(var, raising=False)
    tmp = os.path.realpath(tempfile.gettempdir())
    for path in kept():
        assert os.path.realpath(path).startswith(CHECKOUT_CACHE), path
        assert not os.path.realpath(path).startswith(tmp + os.sep), path
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'c'))
    for path in kept():
        assert path.startswith(str(tmp_path / 'c')), path


def test_compile_cache_flag_opt_out(monkeypatch):
    """compile_cache=False leaves jax's config alone; True arms it at
    cache_root() even off-TPU."""
    import jax

    from paddle_tpu.core import platform_boot as pb
    from paddle_tpu.core.flags import FLAGS, get_flag
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    get_flag('compile_cache')  # populate FLAGS before setitem
    try:
        monkeypatch.setattr(pb, '_cache_armed', False)
        monkeypatch.setitem(FLAGS, 'compile_cache', False)
        pb.arm_compile_cache()
        assert jax.config.jax_compilation_cache_dir == prev_dir
        monkeypatch.setitem(FLAGS, 'compile_cache', True)
        pb.arm_compile_cache()
        assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE
    finally:
        # jax.config state is session-global; restore it (monkeypatch
        # only unwinds env vars and attrs)
        jax.config.update('jax_compilation_cache_dir', prev_dir)
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          prev_min)
