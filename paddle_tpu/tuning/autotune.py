"""Per-shape kernel autotuner.

On first sight of an (op, shape, dtype, device_kind) key, microbenchmark
the candidate variants — XLA vs Pallas, and a small grid of Pallas block
sizes — and record the winner in the persisted :class:`TuningTable`.
Dispatch sites (``ops/attention_ops.py`` and the layer/batch-norm
wrappers) consult ``decide()`` instead of the global
env gates when autotuning is on; the explicit env gates
(``PADDLE_TPU_USE_PALLAS`` etc.) always override the table.

Knobs::

    PADDLE_TPU_AUTOTUNE      off (default) | on | record
    PADDLE_TPU_TUNING_TABLE  table path (default: tuning.json beside the
                             compile cache, platform_boot.cache_root())

``on`` trusts existing table entries and only measures unseen keys;
``record`` re-measures every key it encounters (refreshing a stale
table — the record-vs-replay workflow: record once on the target chip,
replay everywhere with ``on``).

Measurement runs eagerly at trace time: candidates execute on synthetic
inputs of the live shape (concrete arrays, so a nested ``jax.jit``
dispatches for real even while an outer trace is active), timed to
``block_until_ready``. A candidate that fails to compile (e.g. a
real Pallas kernel on a CPU host) scores +inf and simply loses. Tests
inject deterministic timings via :func:`set_timer`.
"""

import math
import os
import time

from .. import observe as _obs
from .table import TuningTable

__all__ = ['autotune_mode', 'decide', 'reset', 'set_timer', 'table_path',
           'current_table', 'device_kind', 'env_gate_set',
           'decide_summa_panel', 'decide_linalg_block',
           'decide_matmul_dtype']

_STATE = {'table': None, 'table_path': None, 'memo': {}, 'timer': None}


# ---------------------------------------------------------------- knobs
def autotune_mode(environ=None):
    """'off' | 'on' | 'record' from PADDLE_TPU_AUTOTUNE."""
    env = os.environ if environ is None else environ
    raw = (env.get('PADDLE_TPU_AUTOTUNE') or 'off').strip().lower()
    if raw in ('on', '1', 'true', 'yes'):
        return 'on'
    if raw == 'record':
        return 'record'
    return 'off'


def table_path():
    """PADDLE_TPU_TUNING_TABLE, or ``tuning.json`` under
    platform_boot.cache_root() beside the compile cache."""
    p = os.environ.get('PADDLE_TPU_TUNING_TABLE')
    if p:
        return p
    from ..core.platform_boot import cache_root
    return os.path.join(cache_root(), 'tuning.json')


def env_gate_set(*names):
    """True when any of the named env gates is EXPLICITLY set — the
    operator pinned a kernel choice, which overrides the table."""
    return any(os.environ.get(n) is not None for n in names)


def device_kind():
    """The backend's device kind string ('cpu', 'TPU v5e', ...) — the
    table's top-level key, so one file can hold tables for several chip
    generations."""
    kind = _STATE.get('device_kind')
    if kind is None:
        try:
            import jax
            kind = str(jax.devices()[0].device_kind)
        except Exception:
            kind = 'unknown'
        _STATE['device_kind'] = kind
    return kind


def reset():
    """Drop every cached decision and the in-memory table (tests, and
    callers that re-point PADDLE_TPU_TUNING_TABLE mid-process)."""
    _STATE['table'] = None
    _STATE['table_path'] = None
    _STATE['memo'] = {}
    _STATE.pop('device_kind', None)


def set_timer(fn):
    """Inject a timing function ``fn(op, key, variant, thunk) ->
    seconds`` (None restores the real timer). Tests use this for
    deterministic winner selection without touching hardware."""
    _STATE['timer'] = fn


def current_table():
    """The table for the current PADDLE_TPU_TUNING_TABLE path, loading
    it on first access (and reloading if the path knob changed)."""
    path = table_path()
    if _STATE['table'] is None or _STATE['table_path'] != path:
        _STATE['table'] = TuningTable.load(path)
        _STATE['table_path'] = path
        if _STATE['table'].loaded_from_disk:
            _obs.flight_event('tuning_table_loaded', path=path,
                              entries=_STATE['table'].size())
        _obs.set_gauge('tuning.table_size', _STATE['table'].size())
    return _STATE['table']


# ------------------------------------------------------------ measuring
def _time_thunk(op, key, variant, thunk, warmup=1, iters=3):
    """Best-of-`iters` wall seconds for one candidate. The thunk builds
    its own synthetic inputs and returns a device array; timing ends in
    block_until_ready. +inf when the candidate cannot run here."""
    import jax
    try:
        for _ in range(max(0, warmup)):
            jax.block_until_ready(thunk())
        best = math.inf
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            jax.block_until_ready(thunk())
            best = min(best, time.perf_counter() - t0)
        return best
    except Exception as e:
        _obs.flight_event('tuning_candidate_failed', op=op, key=key,
                          variant=_label(variant),
                          error='%s: %s' % (type(e).__name__, e))
        return math.inf


def _label(variant):
    """Stable short label for a variant dict ('pallas bq512 bk256')."""
    impl = variant.get('impl', '?')
    extras = ' '.join('%s%s' % (k.replace('block_', 'b'), v)
                      for k, v in sorted(variant.items()) if k != 'impl')
    return ('%s %s' % (impl, extras)).strip()


def _measure(op, key, candidates):
    """Time every candidate; returns (winner_variant, {label: secs}).
    Falls back to the first candidate when nothing ran (all +inf)."""
    timer = _STATE['timer'] or _time_thunk
    timings = {}
    best, best_t = None, math.inf
    t0 = time.perf_counter()
    for variant, thunk in candidates:
        dt = timer(op, key, variant, thunk)
        timings[_label(variant)] = dt if math.isfinite(dt) else -1.0
        if dt < best_t:
            best, best_t = variant, dt
    if best is None:
        best = candidates[0][0]
    _obs.record('tuning.tune_seconds', time.perf_counter() - t0, op=op)
    return best, timings


# -------------------------------------------------------------- deciding
def decide(op, key, candidates):
    """The tuned variant dict for (op, key), or None when autotuning is
    off (callers then fall back to the default env-gate logic).

    ``candidates`` is ``[(variant_dict, thunk), ...]``; thunks only run
    when the key has never been measured (mode 'on') or always (mode
    'record'). Decisions are memoized per process — the hot path after
    the first trace is one dict hit — and persisted to the table file
    the moment they are measured, so a restarted process replays them
    without re-benchmarking."""
    mode = autotune_mode()
    if mode == 'off' or not candidates:
        return None
    kind = device_kind()
    memo_key = (kind, key)
    hit = _STATE['memo'].get(memo_key)
    if hit is not None:
        return hit
    table = current_table()
    if mode == 'on':
        ent = table.lookup(kind, key)
        if ent and isinstance(ent.get('winner'), dict):
            winner = dict(ent['winner'])
            _STATE['memo'][memo_key] = winner
            _obs.inc('tuning.decisions_total', op=op, source='table',
                     impl=winner.get('impl', '?'))
            return winner
    winner, timings = _measure(op, key, candidates)
    table.put(kind, key, winner, timings,
              mode='recorded' if mode == 'record' else 'measured')
    table.save()
    _STATE['memo'][memo_key] = dict(winner)
    _obs.inc('tuning.decisions_total', op=op, source='measured',
             impl=winner.get('impl', '?'))
    _obs.set_gauge('tuning.table_size', table.size())
    _obs.flight_event('tune', op=op, key=key, winner=_label(winner),
                      device_kind=kind)
    return dict(winner)


# ------------------------------------------------- per-op decision hooks
# Each hook renders the shape key, enumerates candidates with synthetic-
# input thunks, and returns decide()'s verdict. They are called from
# inside jit traces: thunks build CONCRETE arrays, so the nested
# executions run eagerly and never leak tracers into the outer program.

def decide_attention(b, h, tq, tk, d, dtype, causal, masked):
    """xla vs pallas-flash, over the (block_q, block_k) grid. `masked`
    keys variable-length batches separately (the kernel skips masked key
    blocks, so its ranking differs from the dense case)."""
    import jax
    import jax.numpy as jnp
    from ..ops.pallas.flash_attention import (attention_block_variants,
                                              flash_attention)
    from ..ops.attention_ops import reference_attention

    key = ('flash_attention|b%d h%d tq%d tk%d d%d causal%d masked%d|%s'
           % (b, h, tq, tk, d, int(bool(causal)), int(bool(masked)),
              dtype))

    def mk_inputs():
        q = jnp.ones((b, h, tq, d), dtype)
        k = jnp.ones((b, h, tk, d), dtype)
        v = jnp.ones((b, h, tk, d), dtype)
        lens = (jnp.full((b,), max(1, (3 * tk) // 4), jnp.int32)
                if masked else None)
        return q, k, v, lens

    def xla_thunk():
        q, k, v, lens = mk_inputs()
        return jax.jit(lambda q, k, v: reference_attention(
            q, k, v, causal=causal, key_length=lens))(q, k, v)

    candidates = [({'impl': 'xla'}, xla_thunk)]
    for bq, bk in attention_block_variants(tq, tk):
        def pallas_thunk(bq=bq, bk=bk):
            q, k, v, lens = mk_inputs()
            return jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, kv_len=lens,
                block_q=bq, block_k=bk))(q, k, v)
        candidates.append(
            ({'impl': 'pallas', 'block_q': bq, 'block_k': bk},
             pallas_thunk))
    return decide('flash_attention', key, candidates)


def decide_layer_norm(n, d, dtype):
    """xla vs the fused Pallas row kernel over a small block_rows grid
    (the kernel's win is long rows; the grid lets short-row shapes keep
    the XLA fusion)."""
    import jax
    import jax.numpy as jnp
    from ..ops.pallas import layer_norm as _ln

    key = 'layer_norm|n%d d%d|%s' % (n, d, dtype)

    def mk_inputs():
        return (jnp.ones((n, d), dtype), jnp.ones((d,), jnp.float32),
                jnp.zeros((d,), jnp.float32))

    def xla_thunk():
        x, g, b = mk_inputs()
        return jax.jit(lambda x, g, b: _ln._ln_reference(
            x, g, b, 1e-5))(x, g, b)

    candidates = [({'impl': 'xla'}, xla_thunk)]
    if d % 128 == 0:
        for rows in (512, 256, 128):
            if rows > n:
                continue
            def pallas_thunk(rows=rows):
                x, g, b = mk_inputs()
                return jax.jit(lambda x, g, b: _ln._ln_pallas(
                    x, g, b, 1e-5, block_rows=rows))(x, g, b)
            candidates.append(({'impl': 'pallas', 'block_rows': rows},
                               pallas_thunk))
    return decide('layer_norm', key, candidates)


def _ladder(sizes, cap=6):
    """Trim a legal-size ladder to at most `cap` candidates, keeping
    the largest (each candidate runs the real distributed kernel, so
    the sweep cost is bounded; the small end of the ladder loses on
    per-step collective latency everywhere we have measured)."""
    sizes = [s for s in sizes if s >= 8] or sizes[-1:]
    return sizes[-cap:]


def decide_summa_panel(n, k, m, dtype, mesh):
    """SUMMA k-panel size over the legal ladder (divisors of
    gcd(K/tp, K/dp)) — the `linalg` op family, keyed by
    (op, shape, dtype, mesh grid). Candidates run the REAL shard_map
    kernel on `mesh` at the live shape: coarse panels amortize the
    broadcast chain, fine panels overlap it against the local dot, and
    which wins is a property of the chip generation the table is keyed
    by."""
    import jax
    import jax.numpy as jnp
    from ..linalg import kernels

    n_dp, n_tp = kernels.axis_sizes_of(mesh, 'dp', 'tp')
    key = ('summa_matmul|n%d k%d m%d|dp%d tp%d|%s'
           % (n, k, m, n_dp, n_tp, dtype))
    panels = _ladder(kernels.legal_panels(k, n_dp, n_tp))
    candidates = []
    for p in panels:
        def thunk(p=p):
            a = jnp.ones((n, k), dtype)
            b = jnp.ones((k, m), dtype)
            return jax.jit(lambda a_, b_: kernels.summa_matmul(
                a_, b_, mesh, panel=p))(a, b)
        candidates.append(({'impl': 'summa', 'panel': p}, thunk))
    return decide('summa_matmul', key, candidates)


def decide_linalg_block(op, n, m, dtype, mesh, axis='dp'):
    """Factorization panel width for blocked_cholesky / blocked_qr
    over the legal ladder (cholesky panels must divide the per-shard
    row extent; qr panels the column count). Same linalg family key
    shape as decide_summa_panel."""
    import jax
    import jax.numpy as jnp
    from ..linalg import kernels

    (n_dp,) = kernels.axis_sizes_of(mesh, axis)
    key = '%s|n%d m%d|dp%d|%s' % (op, n, m, n_dp, dtype)
    if op == 'blocked_cholesky':
        blocks = kernels.legal_blocks(n, local=n // n_dp)
    elif op == 'blocked_qr':
        blocks = kernels.legal_blocks(m)
    else:
        raise ValueError('decide_linalg_block: unknown op %r' % op)
    candidates = []
    for blk in _ladder(blocks):
        def thunk(blk=blk):
            if op == 'blocked_cholesky':
                # synthetic SPD: diagonally dominant, full rank
                a = jnp.eye(n, dtype=dtype) * (2.0 * n) + 1.0
                return jax.jit(lambda a_: kernels.blocked_cholesky(
                    a_, mesh, block=blk))(a)
            a = (jnp.sin(jnp.arange(n * m, dtype=jnp.float32))
                 .reshape(n, m).astype(dtype))
            return jax.jit(lambda a_: kernels.blocked_qr(
                a_, mesh, block=blk))(a)[0]
        candidates.append(({'impl': 'blocked', 'block': blk}, thunk))
    return decide(op, key, candidates)


def decide_matmul_dtype(m, k, n, dtype):
    """Native (input-dtype) vs fp8(e4m3)-cast contraction for one
    2D matmul shape — the ``matmul_dtype`` family behind the
    mul/matmul lowerings' dispatch (ops/fp8_matmul.py). The fp8
    candidate only enumerates where this jax build carries
    float8_e4m3fn; the explicit ``PADDLE_TPU_FP8_MATMUL`` gate is
    checked at the dispatch site and beats this table."""
    import jax
    import jax.numpy as jnp

    key = 'matmul_dtype|m%d k%d n%d|%s' % (m, k, n, dtype)

    def mk_inputs():
        return jnp.ones((m, k), dtype), jnp.ones((k, n), dtype)

    def native_thunk():
        x, y = mk_inputs()
        return jax.jit(jnp.matmul)(x, y)

    candidates = [({'impl': 'native'}, native_thunk)]
    from ..quant.core import kv_fp8_supported
    if kv_fp8_supported():
        def fp8_thunk():
            from ..ops.fp8_matmul import fp8_matmul
            x, y = mk_inputs()
            return jax.jit(fp8_matmul)(x, y)
        candidates.append(({'impl': 'fp8'}, fp8_thunk))
    return decide('matmul_dtype', key, candidates)


def decide_batch_norm(r, c, dtype):
    """xla two-pass stats vs the one-pass fused Pallas BN kernel over a
    block_r grid (training-mode forward only — the backward is jnp on
    both paths)."""
    import jax
    import jax.numpy as jnp
    from ..ops.pallas import batch_norm as _bn

    key = 'batch_norm|r%d c%d|%s' % (r, c, dtype)

    def mk_inputs():
        return (jnp.ones((r, c), dtype), jnp.ones((c,), jnp.float32),
                jnp.zeros((c,), jnp.float32))

    def xla_thunk():
        x, s, b = mk_inputs()
        return jax.jit(lambda x, s, b: _bn._bn_reference(
            x, s, b, 1e-5)[0])(x, s, b)

    candidates = [({'impl': 'xla'}, xla_thunk)]
    if r % 8 == 0 and (c % 128 == 0 or c < 128):
        for br in (512, 256):
            if br > r:
                continue
            def pallas_thunk(br=br):
                x, s, b = mk_inputs()
                return jax.jit(lambda x, s, b: _bn._fused_bn_fwd(
                    x, s, b, 1e-5, br)[0])(x, s, b)
            candidates.append(({'impl': 'pallas', 'block_r': br},
                               pallas_thunk))
    return decide('batch_norm', key, candidates)
