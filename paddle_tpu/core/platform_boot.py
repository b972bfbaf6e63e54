"""Platform selection and the compile-cache location, in one place.

The backend is whatever jax finds: a TPU where there is one, and the
host CPU only when it is asked for by name (``JAX_PLATFORMS=cpu``, which
the tests' conftest sets through :func:`force_host_cpu`). Nothing here
falls back from one platform to another.
"""

import os


def force_host_cpu(n_devices=None):
    """Ask for the host CPU platform by name; optionally request
    n_devices virtual devices (the dp/tp/pp test meshes).

    Safe to call after `import jax` but must run before any device query
    (jax.devices(), first jit execution, ...): the device count is fixed
    when the backend initializes.
    """
    if n_devices is not None:
        flags = os.environ.get('XLA_FLAGS', '')
        if '--xla_force_host_platform_device_count' not in flags:
            os.environ['XLA_FLAGS'] = (
                flags + ' --xla_force_host_platform_device_count=%d'
                % n_devices).strip()
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    jax.config.update('jax_platforms', 'cpu')


def cache_root():
    """The one directory this package keeps compiled or tuned artefacts
    under: ``JAX_COMPILATION_CACHE_DIR`` where it is set, otherwise
    ``<checkout>/.jax_cache`` — the same string in every process,
    because the path is part of jax's cache key. The tuning table
    lives beneath it."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, '.jax_cache')


_cache_armed = False


def arm_compile_cache():
    """Arm jax's persistent compilation cache (idempotent; called at
    Executor construction) so a second process skips the HLO->binary
    compile of every program the first one ran. The cache is keyed by
    HLO, so a changed lowering can never be served a stale binary.

    jax reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set no
    directory is set in code. Otherwise the cache is pointed at
    :func:`cache_root`. The ``compile_cache`` flag
    (PADDLE_TPU_COMPILE_CACHE) is 'auto' (TPU only), on, or off.

    This is one of two layers — keep them apart when reading a cold
    start:

    1. **jax's compilation cache** (this function): skips the XLA
       compile; the process still traces every program.
    2. **Kernel tuning table** (``paddle_tpu/tuning``): which kernel
       variant each (op, shape, dtype) dispatches; changes what gets
       compiled, not whether.
    """
    global _cache_armed
    if _cache_armed:
        return
    from .flags import get_flag
    mode = get_flag('compile_cache')  # 'auto' | explicit on | off
    if mode in (False, '0', 'false', 'no', 'off'):
        return
    explicit_on = mode in (True, '1', 'true', 'yes', 'on')
    # 'auto': TPU only. XLA:CPU persists AOT results whose recorded
    # machine features can mismatch the loader's host detection
    # ('+prefer-no-scatter ... could lead to SIGILL', then an abort
    # materializing an array from a cache-loaded executable).
    if not explicit_on and not is_tpu_backend():
        return
    _cache_armed = True
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', cache_root())
    # jax skips entries that compiled in under a second by default; the
    # decode engine's small prefill buckets are exactly those
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)


def is_tpu_backend():
    """True when the default jax backend is the 'tpu' platform. Shared
    by the backend-dependent defaults (executor._default_prng dropout
    RNG, conv_ops._conv_layout) so the policy lives in one place."""
    import jax
    return jax.default_backend() == 'tpu'
