"""AOT serialized-executable cache: warm a fresh process from disk.

The second cache layer (see platform_boot.arm_compile_cache's list),
off unless PADDLE_TPU_AOT_CACHE=1 asks for it. jax's persistent
compilation cache skips the HLO->binary compile but a
restarted process still pays the full Python trace of every (program,
shapes) key before it can even ASK the module cache; the tuning table
skips re-benchmarking but not compilation. This layer removes both: on
an Executor cache miss the fully-compiled step executable is serialized
(``jax.experimental.serialize_executable`` — PjRT executable bytes +
the call's pytree defs) keyed by a CONTENT fingerprint of the program
plus the feed/fetch signature and a backend fingerprint; the next
process with the same program reaches its first dispatch with ZERO
traces and ZERO XLA compiles — the whole-program-compilation thesis of
PAPERS "Automatic Full Compilation ... to Cloud TPUs" applied to
restart latency (the Gemma-serving fleet scenario: a scaled-up replica
warms in seconds).

Keying: ``fingerprint()`` hashes the serialized program content (the
same dict io.save_inference_model persists), the executor cache-key
parts (kind, amp, remat, feed signature, fetches, steps), and the
backend fingerprint (jax/jaxlib versions, platform, device kind and
count) — NOT ``id(program)``, so two processes (or two Program objects)
with identical content share entries. Any mismatch — different jaxlib,
different chip, corrupted file — falls back to a live compile with an
``aot_fallback`` flight event; the cache can only ever cost a read.

The key does NOT cover the lowering code: change an ``ops/`` lowering
and the Program IR — hence the fingerprint — is unchanged, so a cache
directory shared between two commits serves the old commit's
executable to the new one. That is why this layer is opt-in on every
backend, and why a measurement must never share its directory across
commits (ROADMAP D4).

Knobs::

    PADDLE_TPU_AOT_CACHE      1 turns the layer on; anything else is off
    PADDLE_TPU_AOT_CACHE_DIR  cache directory (default: ``aot/`` under
                              platform_boot.cache_root())

Only single-device programs are cached (``program.mesh is None``) —
sharded executables embed device assignments that do not relocate. A
cached executable is loaded onto the process's first device only
(``execution_devices``), whatever the host's device count.
"""

import hashlib
import json
import os
import pickle
import tempfile

from .. import observe as _obs

FORMAT_VERSION = 1
_SUFFIX = '.jaot'


def enabled(environ=None):
    env = os.environ if environ is None else environ
    raw = (env.get('PADDLE_TPU_AOT_CACHE') or '').strip().lower()
    return raw in ('1', 'true', 'yes', 'on')


def cache_dir():
    d = os.environ.get('PADDLE_TPU_AOT_CACHE_DIR')
    if d:
        return d
    from .platform_boot import cache_root
    return os.path.join(cache_root(), 'aot')


def backend_fingerprint():
    """Everything a serialized executable is only valid under."""
    import jax
    try:
        import jaxlib
        jaxlib_ver = jaxlib.__version__
    except Exception:
        jaxlib_ver = 'unknown'
    try:
        devs = jax.devices()
        kind, n = str(devs[0].device_kind), len(devs)
    except Exception:
        kind, n = 'unknown', 0
    return {'format': FORMAT_VERSION, 'jax': jax.__version__,
            'jaxlib': jaxlib_ver, 'platform': jax.default_backend(),
            'device_kind': kind, 'n_devices': n}


def fingerprint(program, parts):
    """Content hash naming the cache entry: program structure (ops,
    vars, attrs — the save_inference_model dict), the executor key
    parts (everything in the in-memory key EXCEPT id(program)), and the
    backend fingerprint. Stable across processes by construction."""
    from .serialize import program_to_dict
    h = hashlib.sha1()
    h.update(json.dumps(program_to_dict(program), sort_keys=True,
                        default=repr).encode())
    h.update(repr(parts).encode())
    h.update(json.dumps(backend_fingerprint(), sort_keys=True).encode())
    return h.hexdigest()


def path_for(fp):
    return os.path.join(cache_dir(), fp + _SUFFIX)


def load(fp):
    """(callable, status): the deserialized-and-loaded executable for
    fingerprint *fp*, or None with status 'absent' | 'mismatch' |
    'error'. Mismatch/corruption is a flight event and a fallback,
    never a raise — a stale cache must not take the process down."""
    path = path_for(fp)
    if not os.path.exists(path):
        return None, 'absent'
    try:
        with open(path, 'rb') as f:
            blob = pickle.load(f)
        meta = blob['meta']
        want = backend_fingerprint()
        if meta != want:
            bad = sorted(k for k in want if meta.get(k) != want.get(k))
            _obs.inc('executor.aot_fallback_total', reason='mismatch')
            _obs.flight_event('aot_fallback', reason='mismatch',
                              fields=','.join(bad), path=path)
            return None, 'mismatch'
        import jax
        from jax.experimental import serialize_executable as _se
        # without execution_devices jax loads onto EVERY local device
        # and the one-device executable then fails at dispatch, where
        # the except below cannot see it
        loaded = _se.deserialize_and_load(
            blob['payload'], blob['in_tree'], blob['out_tree'],
            execution_devices=jax.devices()[:1])
        return loaded, 'loaded'
    except Exception as e:
        _obs.inc('executor.aot_fallback_total', reason='error')
        _obs.flight_event('aot_fallback', reason='error', path=path,
                          error='%s: %s' % (type(e).__name__, e))
        return None, 'error'


def save(fp, compiled_exe):
    """Serialize *compiled_exe* (a jax.stages.Compiled) under *fp*.
    Atomic (unique tmp + os.replace, the io._write_atomic contract) and
    best-effort: serialization failure — e.g. a backend whose PjRT
    executables do not serialize — records a flight event and returns
    None; the in-process executable keeps working regardless."""
    try:
        from jax.experimental import serialize_executable as _se
        payload, in_tree, out_tree = _se.serialize(compiled_exe)
        blob = {'meta': backend_fingerprint(), 'payload': payload,
                'in_tree': in_tree, 'out_tree': out_tree}
        d = cache_dir()
        os.makedirs(d, exist_ok=True)
        path = path_for(fp)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=fp + '.')
        try:
            with os.fdopen(fd, 'wb') as f:
                pickle.dump(blob, f)
            umask = os.umask(0)
            os.umask(umask)
            os.chmod(tmp, 0o666 & ~umask)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path
    except Exception as e:
        _obs.flight_event('aot_save_failed', fingerprint=fp[:12],
                          error='%s: %s' % (type(e).__name__, e))
        return None
