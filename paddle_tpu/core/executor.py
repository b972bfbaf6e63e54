"""Executor: compiles a Program into ONE jitted XLA computation.

Reference: paddle/fluid/framework/executor.{h,cc} + python/fluid/executor.py.
The reference interprets ops one-by-one through per-device OpKernels; here a
whole block — forward, autodiff'd backward, optimizer updates — is traced
through the registered JAX lowerings and compiled once per
(program version, feed signature). Persistable state (params, optimizer
accumulators, BN statistics, learning rate) flows through the jitted step as
a donated dict argument, so parameter updates are in-place in HBM and steps
run with zero host round-trips beyond feed/fetch.
"""

import os
import threading
import time

import numpy as np

from .. import observe as _obs
from .dtypes import to_jnp_dtype
from .place import CPUPlace, TPUPlace
from .program import Variable, default_main_program
from .registry import LoweringContext, get_lowering
from .scope import global_scope


def _ensure_ops_imported():
    from .. import ops as _ops  # noqa: F401  (registers lowerings)


def collect_error_clips(block, ops):
    """{var name: (lo, hi)} for every op output carrying an error_clip
    (validated once, at compile/trace start — not per op per trace).
    Only ErrorClipByValue maps onto the cotangent-clamp lowering."""
    from ..clip import ErrorClipByValue
    clips = {}
    for op in ops:
        for n in op.output_names():
            if n in clips:
                continue
            v = block._find_var_recursive(n)
            ec = getattr(v, 'error_clip', None) if v is not None else None
            if ec is None:
                continue
            if not isinstance(ec, ErrorClipByValue):
                raise NotImplementedError(
                    'error_clip on %r: only ErrorClipByValue is '
                    'supported by the cotangent-clamp lowering (got %s)'
                    % (n, type(ec).__name__))
            clips[n] = (float(ec.min), float(ec.max))
    return clips


_ERROR_CLIP_FN = None


def _error_clip_grad(x, lo, hi):
    """Identity forward; clamps the cotangent to [lo, hi] on the way
    back (the reference's error clip semantics, fluid/clip.py
    ErrorClipByValue applied through backward.py callbacks). The
    custom_vjp is built once (module cache) — lo/hi ride as nondiff
    args, so one primitive serves every clipped var."""
    global _ERROR_CLIP_FN
    if _ERROR_CLIP_FN is None:
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
        def f(x, lo, hi):
            return x

        def fwd(x, lo, hi):
            return x, None

        def bwd(lo, hi, _res, g):
            return (jnp.clip(g, lo, hi),)

        f.defvjp(fwd, bwd)
        _ERROR_CLIP_FN = f
    return _ERROR_CLIP_FN(x, lo, hi)


def _default_prng():
    """Dropout-mask PRNG implementation. On TPU the hardware
    RngBitGenerator ('rbg') is the default: counter-based threefry
    mask generation is arithmetic the step pays for every mask (one
    against the other on the chip: one encoder layer of the train
    cell, forward, backward and Adam, takes 12.1 ms a step under rbg
    and 23.9 under threefry2x32, PERF.md section 6, PR 59; section 5
    has dropout's share of the cell's step). rbg is deterministic for a fixed
    (seed, step) on a given backend/version; threefry remains the
    default off-TPU and the cross-backend-reproducible choice
    (PADDLE_TPU_PRNG=threefry2x32|rbg overrides)."""
    import os
    env = os.environ.get('PADDLE_TPU_PRNG')
    if env:
        return env
    from .platform_boot import is_tpu_backend
    return 'rbg' if is_tpu_backend() else 'threefry2x32'


def _remat_policy(name):
    import jax
    if name in ('full', 'nothing_saveable'):
        return jax.checkpoint_policies.nothing_saveable
    if name == 'dots_saveable':
        return jax.checkpoint_policies.dots_saveable
    raise ValueError('unknown remat policy %r' % name)


class StepHandle(object):
    """One dispatched-but-unresolved step (or run_steps window).

    JAX dispatch is asynchronous: ``run(..., return_handle=True)``
    returns as soon as the computation is enqueued, with the fetches
    still device futures. ``resolve()`` blocks on them and returns the
    numpy metrics; ``ready()`` peeks without blocking. ``dispatched_at``
    timestamps the enqueue so the pipelined trainer can attribute
    host-blocked vs device-blocked wall time."""

    __slots__ = ('fetches', 'steps', 'dispatched_at', 'cache_miss',
                 '_resolved')

    def __init__(self, fetches, steps=1, cache_miss=False):
        self.fetches = fetches
        self.steps = int(steps)
        self.cache_miss = bool(cache_miss)
        self.dispatched_at = time.perf_counter()
        self._resolved = None

    def ready(self):
        """True when every fetch has landed (non-blocking peek)."""
        if self._resolved is not None:
            return True
        try:
            return all(bool(v.is_ready()) for v in self.fetches)
        except AttributeError:
            return True   # plain numpy values: nothing in flight

    def resolve(self):
        """Block until the dispatch completes; returns numpy metrics.
        Idempotent — the device references are dropped on first call."""
        if self._resolved is None:
            self._resolved = [np.asarray(v) for v in self.fetches]
            self.fetches = self._resolved
        return self._resolved


class _Compiled(object):
    __slots__ = ('fn', 'raw_fn', 'scope_in_names', 'scope_out_names',
                 'feed_names', 'fetch_names', 'flops')

    def __init__(self, fn, raw_fn, scope_in_names, scope_out_names,
                 feed_names, fetch_names):
        self.fn = fn
        self.raw_fn = raw_fn  # un-jitted step function (jittable, no donation)
        self.scope_in_names = scope_in_names
        self.scope_out_names = scope_out_names
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.flops = None  # per-step XLA cost-analysis FLOPs (observe)


_SUB_BLOCK_ATTRS = ('sub_block', 'true_block', 'false_block')


def _op_reads(op, program, cache=None):
    """All names *op* reads, including external reads made inside its
    sub-blocks (while/rnn bodies, if_else branches). A name defined by an
    earlier op within the same sub-block is internal and excluded, so the
    result is exactly the set of values the op needs from its surroundings.
    Pass a dict as *cache* to amortize the sub-block walk across passes."""
    if cache is not None and id(op) in cache:
        return cache[id(op)]
    reads = list(op.input_names())
    if program is not None:
        for attr in _SUB_BLOCK_ATTRS:
            idx = op.attrs.get(attr)
            if idx is not None:
                defined = set()
                for sub_op in program.block(idx).ops:
                    for n in _op_reads(sub_op, program, cache):
                        if n not in defined:
                            reads.append(n)
                    defined.update(sub_op.output_names())
    if cache is not None:
        cache[id(op)] = reads
    return reads


def _analyze(block, ops, feed_names, reads_cache=None):
    """Determine scope inputs (persistable/state vars read before defined)
    and scope outputs (persistable vars written)."""
    defined = set(feed_names)
    scope_in, scope_out = [], []
    for op in ops:
        if op.type == 'backward_marker':
            defined.update(op.attrs['grad_names'])
            continue
        for name in _op_reads(op, block.program, reads_cache):
            if name in defined or name in scope_in:
                continue
            scope_in.append(name)
        for name in op.output_names():
            defined.add(name)
            var = block._find_var_recursive(name)
            if var is not None and var.persistable and name not in scope_out:
                scope_out.append(name)
    return scope_in, scope_out


def _prune_ops(block, ops, fetch_names, reads_cache=None):
    """Keep ops contributing to fetches or to persistable state updates.

    Liveness walks into sub-blocks via _op_reads: a var read only inside a
    while/if_else body still keeps its producer alive (reference analog:
    Prune in paddle/fluid/framework/prune.cc descends into sub-block descs).
    """
    needed = set(fetch_names)
    kept = []
    for op in reversed(ops):
        writes_state = any(
            (lambda v: v is not None and v.persistable)(
                block._find_var_recursive(n))
            for n in op.output_names())
        if op.type == 'backward_marker' or writes_state or \
                (set(op.output_names()) & needed):
            kept.append(op)
            needed.update(_op_reads(op, block.program, reads_cache))
            if op.type == 'backward_marker':
                needed.add(op.attrs['loss_name'])
    kept.reverse()
    return kept


class Executor(object):
    def __init__(self, place=None):
        self.place = place if place is not None else TPUPlace(0)
        # resolves or raises: TPUPlace on a machine without a TPU is an
        # error unless the CPU was asked for by name (core/place.py).
        # The step itself still runs on jax's default device — `place`
        # does not select chip i (ROADMAP W1).
        self.place.jax_device()
        self._cache = {}
        # Serving runs this executor from concurrent threads: _lock
        # guards the compile cache, the per-key compile locks, and the
        # global step counter; last_cache_miss is per-thread so one
        # thread's hit can't mask another thread's miss.
        self._lock = threading.Lock()
        self._compile_locks = {}
        # Program keys already checked by the static verifier
        # (PADDLE_TPU_VERIFY): verification runs once per key, at first
        # compile, BEFORE anything traces.
        self._verified = set()
        # The step fn DONATES its scope inputs (param buffers alias
        # outputs); two concurrent dispatches on one scope would hand
        # the second a deleted buffer. Dispatch + scope write-back is
        # therefore one critical section; traces/compiles of distinct
        # keys still run concurrently.
        self._dispatch_lock = threading.Lock()
        self._tls = threading.local()
        self._step = 0
        from .platform_boot import arm_compile_cache
        arm_compile_cache()

    @property
    def last_cache_miss(self):
        """Whether THIS thread's most recent run()/run_steps() call
        missed the compile cache (thread-local: concurrent serving
        threads each see their own answer)."""
        return getattr(self._tls, 'last_cache_miss', False)

    @last_cache_miss.setter
    def last_cache_miss(self, value):
        self._tls.last_cache_miss = value

    def _next_steps(self, n):
        """Atomically claim n global step indices (dropout keys fold
        the step index; two threads must never share one)."""
        with self._lock:
            step0 = self._step
            self._step += n
        return np.int32(step0)

    def _maybe_verify(self, kind, key, program, feed_vals, fetch_names):
        """PADDLE_TPU_VERIFY=off|warn|strict: run the static verifier
        (paddle_tpu.analysis) over the program ONCE per cache key, at
        the first sight of that key and BEFORE any trace — strict mode
        raises ProgramVerifyError while the op that broke the graph is
        still one `file:line` away; warn mode records program_verify
        flight events + analysis.* counters and proceeds. 'off' (the
        default) costs one set lookup per run."""
        if key in self._verified:
            return
        from ..analysis import verify, verify_mode
        mode = verify_mode()
        if mode != 'off':
            verify(program, feed_names=sorted(feed_vals),
                   fetch_names=fetch_names, mode=mode, label=kind)
        self._verified.add(key)

    def _lookup_or_compile(self, kind, key, use_cache, compile_fn):
        """Compile-cache access, safe under concurrent serving threads:
        a hit is one locked dict read; a miss takes a per-key lock so
        two threads racing on the same (program, shapes) signature
        compile ONCE — the loser blocks, then reads the winner's entry
        as a hit. Distinct keys still compile concurrently. Returns
        (compiled, missed)."""
        if not use_cache:
            return self._observed_compile(kind, key, compile_fn), True
        with self._lock:
            compiled = self._cache.get(key)
            if compiled is not None:
                return compiled, False
            key_lock = self._compile_locks.setdefault(key,
                                                      threading.Lock())
        with key_lock:
            with self._lock:
                compiled = self._cache.get(key)
            if compiled is not None:
                return compiled, False
            compiled = self._observed_compile(kind, key, compile_fn)
            with self._lock:
                self._cache[key] = compiled
        return compiled, True

    # ------------------------------------------------------------------ run
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True,
            return_handle=False):
        with _obs.span('executor.run', record='executor.run_seconds',
                       kind='single') as run_span:
            return self._dispatch(run_span, None, False, use_program_cache,
                                  program, feed, fetch_list, scope,
                                  return_numpy, return_handle)

    def run_steps(self, steps, program=None, feed=None, fetch_list=None,
                  scope=None, return_numpy=True, stacked_feed=False,
                  return_handle=False):
        """Run `steps` training steps as ONE XLA execution: the compiled
        step function is wrapped in a lax.scan, so per-dispatch overhead
        (host->device feed, dispatch latency) is paid once per `steps`
        instead of per step. State
        (params, optimizer accumulators, BN stats) chains through the
        scan carry exactly as it chains through the scope across
        Executor.run calls; the per-op PRNG keys fold the true global
        step index, so dropout masks differ per step exactly as they do
        in the one-step path.

        feed values are constant across steps by default (microbench /
        full-batch training); with stacked_feed=True every feed array
        carries a leading [steps, ...] axis (a prefetched superbatch —
        reader.prefetch_to_device pairs with this). Fetches come back
        stacked over the steps axis.

        Reference analog: the trainer's inner batch loop
        (python/paddle/v2/trainer.py:1 train loop); TPU-first, the loop
        itself compiles into the program."""
        with _obs.span('executor.run', record='executor.run_seconds',
                       kind='multi') as run_span:
            return self._dispatch(run_span, steps, stacked_feed, True,
                                  program, feed, fetch_list, scope,
                                  return_numpy, return_handle)

    def _resolve_call(self, program, feed, fetch_list, scope):
        """What run / run_steps / compile_step each begin with: the
        defaults, the fetch names, the feed at its declared dtypes and
        the two gradient policies."""
        _ensure_ops_imported()
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]
        feed_vals = self._normalize_feed(program.global_block(), feed or {})
        # read per call and folded into the cache key: flipping the
        # PADDLE_TPU_QUANT_ALLREDUCE knob mid-process recompiles
        # instead of silently reusing the other mode's executable
        from ..parallel.collective import grad_bucket_policy
        from ..quant.core import grad_allreduce_policy
        return (program, scope, fetch_names, feed_vals,
                grad_allreduce_policy(program), grad_bucket_policy(program))

    def _dispatch(self, run_span, steps, stacked_feed, use_program_cache,
                  program, feed, fetch_list, scope, return_numpy,
                  return_handle):
        """The one body of run (``steps`` None) and run_steps: look the
        executable up or compile it, gather its inputs, enqueue it and
        write the scope back."""
        multi = steps is not None
        kind = 'multi' if multi else 'single'
        n_steps = steps if multi else 1
        # the five children below tile the call's ``executor.run`` span;
        # their histograms say which program it was
        labels = self._phase_labels(program) if _obs.enabled() else None
        with _obs.span('executor.lookup', record='executor.lookup_seconds',
                       labels=labels):
            program, scope, fetch_names, feed_vals, qpolicy, bpolicy = \
                self._resolve_call(program, feed, fetch_list, scope)
            if stacked_feed:
                for name, arr in feed_vals.items():
                    if arr.shape[0] != steps:
                        raise ValueError(
                            'run_steps(stacked_feed=True): feed %r '
                            'leading dim %d != steps %d'
                            % (name, arr.shape[0], steps))
            feed_sig = tuple(sorted(
                (n, v.shape[1:] if stacked_feed else v.shape, str(v.dtype))
                for n, v in feed_vals.items()))
            key = (id(program), program._version, program.amp,
                   program.remat_policy, qpolicy, bpolicy, feed_sig,
                   tuple(fetch_names))
            if multi:
                key = ('multi',) + key + (steps, stacked_feed)
            feed_names = sorted(feed_vals)

            def compile_fn():
                if multi:
                    return self._compile_multi(
                        program, feed_names, fetch_names, qpolicy,
                        bpolicy, steps, stacked_feed)
                return self._compile(
                    program, feed_names, fetch_names,
                    quant_allreduce=qpolicy, grad_bucket=bpolicy)

            self._maybe_verify(kind, key, program, feed_vals, fetch_names)
            compiled, missed = self._lookup_or_compile(
                kind, key, use_program_cache, compile_fn)
            self.last_cache_miss = missed
            kid = self._account_lookup(kind, key, missed, run_span)

        locked = False
        try:
            # the flag follows the acquire with nothing between: whatever
            # the span's exit raises, ``finally`` gives the lock back
            with _obs.span('executor.lock', record='executor.lock_seconds',
                           labels=labels):
                self._dispatch_lock.acquire()
                locked = True
            with _obs.span('executor.prepare',
                           record='executor.prepare_seconds', labels=labels):
                scope_vals, feed_vals = self._prepare_inputs(
                    'Executor.run_steps' if multi else 'Executor.run',
                    program, compiled, scope, feed_vals,
                    feed_stack_axis=stacked_feed)
                if _obs.enabled() and compiled.flops is None:
                    one_feed = {n: v[0] for n, v in feed_vals.items()} \
                        if stacked_feed else feed_vals
                    self._cost_account(compiled, key, scope_vals, one_feed)
                step0 = self._next_steps(n_steps)
            fetches, new_scope = self._enqueue(
                kind, kid, missed, compiled, scope_vals, feed_vals, step0,
                labels)
            with _obs.span('executor.writeback',
                           record='executor.writeback_seconds',
                           labels=labels):
                for name, value in new_scope.items():
                    scope.set(name, value)
                # a return_numpy call waits for the device in _hand_back
                # (``executor.fetch``, a child of this span): other
                # threads dispatch meanwhile
                self._dispatch_lock.release()
                locked = False
                # the step's inputs were donated and the scope now holds
                # their successors: this dict is the last holder of the
                # old arrays, and letting go of a training step's
                # thousand takes most of a millisecond. Here it has a
                # name; left to the frame's end it has none
                del scope_vals
                return self._hand_back(fetches, n_steps, return_numpy,
                                       return_handle)
        finally:
            if locked:
                self._dispatch_lock.release()

    @staticmethod
    def _phase_labels(program):
        """The one label of the five phases' histograms: the Program's
        name (``decode_step``, ``prefill_<bucket>``; the decode engine
        sets them), ``main`` for one without. Built once a call, and
        only with observe on."""
        if program is None:
            program = default_main_program()
        return {'program': program.name or 'main'}

    def _compile_multi(self, program, feed_names, fetch_names, qpolicy,
                       bpolicy, steps, stacked_feed):
        """The single-step function of ``_compile`` wrapped in a
        lax.scan over ``steps``."""
        import jax
        import jax.numpy as jnp
        base = self._compile(program, feed_names, fetch_names,
                             quant_allreduce=qpolicy,
                             grad_bucket=bpolicy)

        # state that is read each step chains through the scan carry;
        # written-only persistables (no reader) are ALSO carried —
        # seeded with zeros of their traced shape and overwritten
        # every step — so only their final value occupies memory
        # (stacking them in the ys would cost steps x size).
        written_only = [n for n in base.scope_out_names
                        if n not in set(base.scope_in_names)]

        def multi_fn(scope_vals, feeds, step0):
            f0 = {n: v[0] for n, v in feeds.items()} \
                if stacked_feed else feeds
            _, ns_shapes = jax.eval_shape(base.raw_fn, scope_vals,
                                          f0, step0)
            wo0 = {n: jnp.zeros(ns_shapes[n].shape,
                                ns_shapes[n].dtype)
                   for n in written_only if n in ns_shapes}

            def body(carry, t):
                sc, wo = carry
                f = {n: v[t] for n, v in feeds.items()} \
                    if stacked_feed else feeds
                fetches, new_scope = base.raw_fn(sc, f, step0 + t)
                return ({n: new_scope[n] for n in sc},
                        {n: new_scope[n] for n in wo}), fetches

            (final_sc, final_wo), stacked = jax.lax.scan(
                body, (scope_vals, wo0),
                jnp.arange(steps, dtype=jnp.int32))
            final_scope = dict(final_sc)
            final_scope.update(final_wo)
            return stacked, final_scope

        multi_fn.__name__ = multi_fn.__qualname__ = '%s_x%d' % (
            base.raw_fn.__name__, steps)
        jit_multi = jax.jit(multi_fn, donate_argnums=(0,))
        return _Compiled(jit_multi, base.raw_fn,
                         base.scope_in_names, base.scope_out_names,
                         base.feed_names, base.fetch_names)

    # -------------------------------------------------------------- helpers
    def _account_lookup(self, kind, key, missed, run_span):
        """With observe on: count a cache hit, put the key's id on the
        call's ``executor.run`` span, and return the id."""
        if not _obs.enabled():
            return None
        kid = _obs.key_id(key)
        if not missed:
            _obs.inc('executor.cache_hit_total', kind=kind, key=kid)
        if run_span is not None:
            run_span.attrs['key'] = kid
        return kid

    def _enqueue(self, kind, kid, missed, compiled, scope_vals, feed_vals,
                 step_i, labels):
        """Hand the step to the device. The first dispatch of a key is
        the XLA compile plus one step: a near-free compile-time signal
        even when the AOT cost probe is off (PADDLE_TPU_OBSERVE_COST=0),
        so it is recorded apart from the enqueues of a warm key. A warm
        key's call is also read on the thread's CPU clock
        (``executor.enqueue_cpu_seconds``): where that falls short of
        the wall time the thread was off the CPU (the runtime, the GIL,
        the scheduler), not computing. Only on a host whose thread clock
        is finer than a call: one that ticks at 10 ms reads 0 or a tick
        (docs/observability.md)."""
        if labels is None:       # observe is off
            return compiled.fn(scope_vals, feed_vals, step_i)
        if missed:
            with _obs.span('executor.enqueue',
                           record='executor.first_dispatch_seconds',
                           labels={'kind': kind, 'key': kid}):
                return compiled.fn(scope_vals, feed_vals, step_i)
        with _obs.span('executor.enqueue',
                       record='executor.enqueue_seconds', labels=labels):
            cpu0 = time.thread_time()
            out = compiled.fn(scope_vals, feed_vals, step_i)
            _obs.record('executor.enqueue_cpu_seconds',
                        time.thread_time() - cpu0, **labels)
            return out

    def _hand_back(self, fetches, steps, return_numpy, return_handle):
        if return_handle:
            return StepHandle(list(fetches), steps=steps,
                              cache_miss=self.last_cache_miss)
        if return_numpy:
            return self.fetch(fetches)
        return list(fetches)

    @staticmethod
    def fetch(values):
        """Block on fetches that ``run(return_numpy=False)`` handed back
        on the device and return them as numpy: what ``run`` does itself
        with ``return_numpy=True``, for a caller that has something to do
        between the enqueue and the wait."""
        with _obs.span('executor.fetch', record='executor.fetch_seconds'):
            return [np.asarray(v) for v in values]

    def _observed_compile(self, kind, key, compile_fn):
        """Trace/prune/compile with telemetry: cache-miss counter, a
        span, and per-key trace seconds. The XLA compile itself happens
        lazily at the first dispatch (and is separately accounted by
        _cost_account's AOT probe when observability is on)."""
        if not _obs.enabled():
            return compile_fn()
        kid = _obs.key_id(key)
        _obs.inc('executor.cache_miss_total', kind=kind, key=kid)
        t0 = time.perf_counter()
        with _obs.span('executor.trace', kind=kind, key=kid):
            compiled = compile_fn()
        dt = time.perf_counter() - t0
        _obs.record('executor.trace_seconds', dt, kind=kind, key=kid)
        # a mid-run compile is exactly the kind of last-seconds context a
        # postmortem needs (shape churn right before death)
        _obs.flight_event('compile', kind=kind, key=kid,
                          trace_seconds=round(dt, 6))
        return compiled

    def _cost_account(self, compiled, key, scope_vals, feed_vals):
        """Best-effort per-step FLOPs via an AOT compile of the un-donated
        step fn + XLA cost_analysis (observe-enabled runs only; one extra
        compile per cache miss — PADDLE_TPU_OBSERVE_COST=0 opts out).
        Also the honest 'executor.compile_seconds' measurement: whole-
        program XLA compile time per (program, shapes) key."""
        if os.environ.get('PADDLE_TPU_OBSERVE_COST') == '0':
            compiled.flops = 0.0
            return
        import jax
        kid = _obs.key_id(key)
        try:
            t0 = time.perf_counter()
            with _obs.span('executor.xla_compile', key=kid):
                exe = jax.jit(compiled.raw_fn).lower(
                    scope_vals, feed_vals, np.int32(0)).compile()
            dt = time.perf_counter() - t0
            _obs.record('executor.compile_seconds', dt, key=kid)
            _obs.overhead('compile', dt)
            compiled.flops = _obs.cost_analysis_flops(exe) or 0.0
        except Exception:
            compiled.flops = 0.0   # tried; never retry per key
        if compiled.flops:
            _obs.set_gauge('executor.step_flops', compiled.flops)

    def _normalize_feed(self, block, feed):
        """Normalize feed values to arrays with the declared
        (canonicalized) dtype. Values already on device (jax Arrays) are
        passed through untouched — np.asarray would round-trip them
        through host memory."""
        import jax
        feed_vals = {}
        for name, value in feed.items():
            var = block._find_var_recursive(name)
            dtype = to_jnp_dtype(var.dtype) if var is not None else None
            arr = value if isinstance(value, jax.Array) \
                else np.asarray(value)
            if dtype is not None and arr.dtype != dtype:
                arr = arr.astype(dtype)
            feed_vals[name] = arr
        return feed_vals

    def _prepare_inputs(self, who, program, compiled, scope, feed_vals,
                        feed_stack_axis=False):
        """Missing-feed check, scope gather, and mesh sharding shared by
        run / run_steps / compile_step."""
        missing = [n for n in compiled.feed_names if n not in feed_vals]
        if missing:
            raise ValueError('%s: missing feed for data vars %s'
                             % (who, missing))
        scope_vals = {}
        for name in compiled.scope_in_names:
            value = scope.find(name)
            if value is None:
                raise RuntimeError(
                    'Variable %r is not initialized in scope. Run the '
                    'startup program first.' % name)
            scope_vals[name] = value
        mesh = program.mesh
        if mesh is not None:
            scope_vals = self._shard_values(program, mesh, scope_vals)
            feed_vals = self._shard_values(program, mesh, feed_vals,
                                           stack_axis=feed_stack_axis)
        return scope_vals, feed_vals

    def _shard_values(self, program, mesh, vals, stack_axis=False):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        out = {}
        for name, value in vals.items():
            spec = program.var_shardings.get(name)
            if spec is None:
                spec = PartitionSpec()
            elif stack_axis:
                # stacked_feed superbatch: the var's spec describes the
                # per-step array; the leading [steps] axis is replicated
                spec = PartitionSpec(None, *spec)
            sharding = NamedSharding(mesh, spec)
            already = getattr(value, 'sharding', None)
            if already == sharding:
                out[name] = value
            else:
                out[name] = jax.device_put(value, sharding)
        return out

    def _compile(self, program, feed_names, fetch_names,
                 quant_allreduce=None, grad_bucket=None):
        import jax

        block = program.global_block()
        all_ops = list(block.ops)
        reads_cache = {}  # amortizes the sub-block walk across the 3 passes
        ops = _prune_ops(block, all_ops, fetch_names, reads_cache)

        # Data vars actually consumed must be fed.
        consumed = set()
        for op in ops:
            consumed.update(_op_reads(op, program, reads_cache))
        needed_feeds = sorted(
            n for n in consumed
            if (lambda v: v is not None and v.is_data)(
                block._find_var_recursive(n)))

        scope_in, scope_out = _analyze(block, ops, set(feed_names) | set(
            n for n in consumed if block._find_var_recursive(n) is None),
            reads_cache)
        # Drop anything that's actually a fed data var.
        scope_in = [n for n in scope_in if n not in set(feed_names)]
        # Donation-friendly: every scope input is also returned (pass-through
        # if not updated), so donated buffers alias outputs.
        scope_out_all = list(dict.fromkeys(scope_in + scope_out))

        marker_idxs = [i for i, op in enumerate(ops)
                       if op.type == 'backward_marker']
        if len(marker_idxs) > 1:
            raise NotImplementedError(
                'Program has %d backward sections (multiple '
                'optimizer.minimize / append_backward calls). Build each '
                'loss in its own Program (the reference GAN examples do the '
                'same) — interleaved update/grad semantics in one program '
                'are ambiguous.' % len(marker_idxs))
        marker_idx = marker_idxs[0] if marker_idxs else None
        seed = program.random_seed if program.random_seed is not None else 0
        mesh = program.mesh
        shardings = program.var_shardings
        amp = program.amp
        error_clips = collect_error_clips(block, ops)

        # Quantized dp gradient aggregation (EQuARX wire format): under
        # GSPMD the dp allreduce is inserted by XLA inside the grad
        # contraction, so the compressed schedule is modeled by passing
        # each dense dp-reduced gradient through the int8 per-block
        # quantize/dequantize with stochastic rounding (quant/core.qdq
        # — the requantized-shard leg; the explicit two-leg schedule is
        # collective.quantized_all_reduce, proven against psum in
        # tests/test_quant.py). Active only where the compressed
        # collective would exist: a training step on a dp>1 mesh.
        quant_grads = None
        if quant_allreduce is not None and marker_idx is not None and \
                mesh is not None and dict(mesh.shape).get('dp', 1) > 1:
            quant_grads = {'block': int(quant_allreduce[1])}
            if _obs.enabled():
                from ..quant import core as _quant
                n_dp = dict(mesh.shape).get('dp', 1)
                marker = ops[marker_idx]
                n_elems = 0
                for pn in marker.attrs['param_names']:
                    v = block._find_var_recursive(pn)
                    if v is not None and v.shape:
                        sz = 1
                        for d in v.shape:
                            sz *= int(d)
                        n_elems += sz
                fp32_b = _quant.allreduce_wire_bytes(n_elems, n_dp)
                q_b = _quant.quantized_allreduce_wire_bytes(
                    n_elems, n_dp, quant_grads['block'])
                _obs.set_gauge('quant.allreduce_grad_elements', n_elems)
                _obs.set_gauge('quant.allreduce_bytes_fp32', fp32_b)
                _obs.set_gauge('quant.allreduce_bytes_quant', q_b)
                _obs.set_gauge('quant.allreduce_compression',
                               fp32_b / max(q_b, 1.0))
                _obs.inc('quant.allreduce_compiles_total')

        # Bucketed asynchronous gradient allreduce (the EQuARX overlap
        # leg): instead of leaving the dp reduction as one fused
        # collective after the whole backward, dense gradients are
        # partitioned into size-targeted buckets in reverse production
        # order (assignment is static — computed here from the declared
        # shapes, so trace and re-trace agree) and each bucket gets its
        # own sharding-constraint round trip in step_fn. XLA then emits
        # one reduce-scatter/all-gather pair per bucket with dataflow
        # deps only on that bucket's gradients, which the latency-hiding
        # scheduler overlaps against the remaining backward compute.
        # Same gating as quant_grads: a training step on a dp>1 mesh.
        grad_buckets = None
        if grad_bucket is not None and marker_idx is not None and \
                mesh is not None and dict(mesh.shape).get('dp', 1) > 1:
            from ..parallel.collective import assign_grad_buckets
            marker = ops[marker_idx]
            sparse_names = set(marker.attrs.get('sparse_grads') or {})
            dense_pairs = [
                (pn, gn) for pn, gn in zip(marker.attrs['param_names'],
                                           marker.attrs['grad_names'])
                if pn not in sparse_names]
            items = []
            for pn, _ in dense_pairs:
                v = block._find_var_recursive(pn)
                shape = v.shape if v is not None and v.shape else (1,)
                numel = 1
                for d in shape:
                    numel *= int(d)
                dt = np.dtype(to_jnp_dtype(v.dtype)) if v is not None \
                    else np.dtype('float32')
                items.append((numel * dt.itemsize, str(dt)))
            target = int(grad_bucket[1] * 1024 * 1024)
            buckets = assign_grad_buckets(items, target)
            grad_buckets = {'pairs': dense_pairs, 'buckets': buckets}
            if _obs.enabled():
                per_bucket = [sum(items[i][0] for i in b)
                              for b in buckets]
                _obs.set_gauge('trainer.grad_bucket_count', len(buckets))
                _obs.set_gauge('trainer.grad_bucket_target_bytes', target)
                _obs.set_gauge('trainer.grad_bucket_max_bytes',
                               max(per_bucket) if per_bucket else 0)
                _obs.inc('trainer.grad_bucket_compiles_total')

        def run_ops(op_list, env, base_key, start_index=0):
            import jax as _jax
            import jax.numpy as _jnp
            from jax.sharding import NamedSharding, PartitionSpec
            from .registry import AMP_BF16_OUT_SLOTS
            for i, op in enumerate(op_list):
                ctx = LoweringContext(env, op, block, start_index + i,
                                      base_key,
                                      is_test=bool(op.attrs.get('is_test',
                                                                False)),
                                      amp=amp)
                try:
                    # every HLO instruction's op_name carries the Fluid
                    # op it came from, forward and (through jax's
                    # transpose(jvp(...)) wrapping) backward
                    with _jax.named_scope(op.type):
                        get_lowering(op.type)(ctx)
                except KeyError as e:
                    raise RuntimeError(
                        'While lowering op %r: missing input %s. '
                        'Feed it or run producers first.' % (op.type, e))
                if amp == 'bf16' and op.type in AMP_BF16_OUT_SLOTS:
                    # fp32-stat ops hand activations back to the bf16
                    # stream (see registry.AMP_BF16_OUT_SLOTS)
                    for slot in AMP_BF16_OUT_SLOTS[op.type]:
                        name = op.output(slot)
                        if name in env and env[name].dtype == _jnp.float32:
                            env[name] = env[name].astype(_jnp.bfloat16)
                if error_clips:
                    # reference error_clip: clamp the gradient flowing
                    # BACK through this var (fluid/clip.py ErrorClip +
                    # backward.py error_clip_callback); TPU-native, the
                    # clamp rides the var's cotangent via custom_vjp
                    for name in op.output_names():
                        if name in error_clips and name in env:
                            lo, hi = error_clips[name]
                            env[name] = _error_clip_grad(env[name],
                                                         lo, hi)
                if mesh is not None:
                    for name in op.output_names():
                        spec = shardings.get(name)
                        if spec is not None and name in env:
                            env[name] = _jax.lax.with_sharding_constraint(
                                env[name], NamedSharding(mesh, spec))
            return env

        prng_impl = _default_prng()

        def step_fn(scope_vals, feed_vals, step_i):
            # PADDLE_TPU_PRNG=rbg swaps in the TPU hardware RNG for
            # dropout-mask generation (threefry is counter-based and
            # costs real MXU-adjacent cycles per element; rbg trades
            # strict reproducibility-across-backends for speed).
            base_key = jax.random.fold_in(
                jax.random.key(seed, impl=prng_impl), step_i)
            env = {}
            env.update(feed_vals)
            env.update(scope_vals)

            if marker_idx is not None:
                import jax.numpy as _jnp
                from .backward import SPARSE_SEED_PREFIX
                pre = ops[:marker_idx]
                marker = ops[marker_idx]
                post = ops[marker_idx + 1:]
                param_names = marker.attrs['param_names']
                grad_names = marker.attrs['grad_names']
                loss_name = marker.attrs['loss_name']
                sparse_info = marker.attrs.get('sparse_grads') or {}

                # sparse-grad tables are NOT differentiated (they stay
                # in base_env; the lookup lowering detaches them) — a
                # zero row seed shaped like the lookup OUTPUT becomes
                # the leaf instead, so its grad is O(batch x dim) rows,
                # never an O(vocab) dense table grad
                dense_names = [n for n in param_names
                               if n not in sparse_info]
                base_env = {k: v for k, v in env.items()
                            if k not in set(dense_names)}
                params = {n: env[n] for n in dense_names}
                for pname, info in sparse_info.items():
                    ids = env[info['ids']]
                    ids_shape = ids.shape[:-1] \
                        if ids.ndim >= 2 and ids.shape[-1] == 1 \
                        else ids.shape
                    params[SPARSE_SEED_PREFIX + info['out']] = _jnp.zeros(
                        ids_shape + (env[pname].shape[-1],),
                        env[pname].dtype)

                # Only values consumed after the backward boundary may
                # escape the forward — anything else would be saved as a
                # checkpoint output and defeat rematerialization.
                needed_after = set(fetch_names) | set(scope_out_all)
                needed_after.add(loss_name)

                for op in post:
                    needed_after.update(_op_reads(op, program, reads_cache))

                def fwd(p):
                    e = dict(base_env)
                    e.update(p)
                    e = run_ops(pre, e, base_key)
                    loss = e[loss_name].sum()
                    keep = {k: v for k, v in e.items()
                            if k in needed_after}
                    return loss, keep

                if program.remat_policy:
                    fwd = jax.checkpoint(
                        fwd, policy=_remat_policy(program.remat_policy))

                (_, kept), grads = jax.value_and_grad(
                    fwd, has_aux=True)(params)
                env.update(kept)

                # Bucketed allreduce: each bucket is concatenated,
                # padded to a dp multiple, and pushed through a
                # P('dp') -> [optional qdq] -> P() sharding-constraint
                # round trip. The constraint pair is the per-bucket
                # collective boundary — XLA lowers it to a
                # reduce-scatter/all-gather over just this bucket's
                # gradients, with dataflow deps only on them, so the
                # scheduler overlaps it with the rest of the backward.
                # Exact path is a pure relayout (bit-identical to
                # unbucketed); the quantized path compresses per bucket
                # (key namespace 0x6b31, distinct from per-grad 0x5172).
                bucket_vals = {}
                if grad_buckets is not None:
                    from jax.sharding import NamedSharding as _NS
                    from jax.sharding import PartitionSpec as _P
                    n_dp = dict(mesh.shape)['dp']
                    pairs = grad_buckets['pairs']
                    for bi, bucket in enumerate(grad_buckets['buckets']):
                        names = [pairs[i][0] for i in bucket]
                        flats = [grads[n].reshape(-1) for n in names]
                        cat = _jnp.concatenate(flats) \
                            if len(flats) > 1 else flats[0]
                        numel = cat.shape[0]
                        pad = (-numel) % n_dp
                        if pad:
                            cat = _jnp.pad(cat, (0, pad))
                        cat = jax.lax.with_sharding_constraint(
                            cat, _NS(mesh, _P('dp')))
                        if quant_grads is not None:
                            from ..quant.core import qdq as _bqdq
                            bkey = jax.random.fold_in(
                                jax.random.fold_in(base_key, 0x6b31),
                                bi)
                            cat = _bqdq(cat,
                                        block=quant_grads['block'],
                                        key=bkey)
                        cat = jax.lax.with_sharding_constraint(
                            cat, _NS(mesh, _P()))
                        if pad:
                            cat = cat[:numel]
                        off = 0
                        for n in names:
                            g = grads[n]
                            sz = int(g.size)
                            bucket_vals[n] = cat[off:off + sz] \
                                .reshape(g.shape).astype(g.dtype)
                            off += sz

                for pi, (pn, gn) in enumerate(zip(param_names,
                                                  grad_names)):
                    if pn in sparse_info:
                        # sparse row grads scatter in place; they never
                        # ride the dense allreduce, so no wire format
                        rows = grads[SPARSE_SEED_PREFIX +
                                     sparse_info[pn]['out']]
                        env[gn] = rows.reshape(-1, rows.shape[-1])
                    elif pn in bucket_vals:
                        env[gn] = bucket_vals[pn]
                    elif quant_grads is not None:
                        from ..quant.core import qdq as _qdq
                        gkey = jax.random.fold_in(
                            jax.random.fold_in(base_key, 0x5172), pi)
                        env[gn] = _qdq(grads[pn],
                                       block=quant_grads['block'],
                                       key=gkey)
                    else:
                        env[gn] = grads[pn]
                if mesh is not None:
                    # grads are assigned here, not as op outputs, so the
                    # run_ops constraint pass never sees them; ZeRO-1's
                    # reduce-scatter (transpiler dp-extends the grad
                    # spec when shard_optimizer_states is on) is applied
                    # at the assignment boundary instead.
                    from jax.sharding import NamedSharding as _NS
                    for gn in grad_names:
                        gspec = shardings.get(gn)
                        if gspec is not None and gn in env:
                            env[gn] = jax.lax.with_sharding_constraint(
                                env[gn], _NS(mesh, gspec))
                env = run_ops(post, env, base_key,
                              start_index=marker_idx + 1)
            else:
                env = run_ops(ops, env, base_key)

            fetches = []
            for name in fetch_names:
                if name not in env:
                    raise KeyError(
                        'fetch target %r was not computed by this program'
                        % name)
                fetches.append(env[name])
            new_scope = {n: env[n] for n in scope_out_all if n in env}
            return fetches, new_scope

        # the XLA module is jit_<name>: a trace's module line and a load
        # error say which program it is
        step_fn.__name__ = step_fn.__qualname__ = program.name or (
            'train_step' if marker_idx is not None else 'infer_step')
        jit_fn = jax.jit(step_fn, donate_argnums=(0,))
        return _Compiled(jit_fn, step_fn, scope_in, scope_out_all,
                         needed_feeds, fetch_names)

    def compile_step(self, program=None, feed=None, fetch_list=None,
                     scope=None):
        """AOT path: compile a (program, feed-spec) pair and return
        ``(step_fn, scope_vals, feed_vals)`` where ``step_fn(scope_vals,
        feed_vals, step_i)`` is a pure jittable function returning
        ``(fetches, new_scope)``. Used by __graft_entry__ and the
        inference predictor; ``Executor.run`` callers never need this."""
        program, scope, fetch_names, feed_vals, qpolicy, bpolicy = \
            self._resolve_call(program, feed, fetch_list, scope)
        compiled = self._compile(
            program, sorted(feed_vals), fetch_names,
            quant_allreduce=qpolicy, grad_bucket=bpolicy)
        scope_vals, feed_vals = self._prepare_inputs(
            'Executor.compile_step', program, compiled, scope, feed_vals)
        return compiled.raw_fn, scope_vals, feed_vals
