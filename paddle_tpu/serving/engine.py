"""ServingEngine: online inference over a compiled Predictor.

The single-shot `inference.Predictor` is fast per call but serves one
request at a time and compiles a fresh XLA executable for every new
feed shape. This engine makes it a traffic-serving endpoint:

- **admission** — `submit()` (any thread) appends to a bounded queue
  and returns a `concurrent.futures.Future`; past `max_queue_depth` it
  fails fast with `QueueFullError` (backpressure the caller can see)
  instead of blocking unboundedly.
- **batcher thread** — pops requests and assembles a micro-batch until
  the top bucket fills or the `batch_timeout_ms` deadline from the
  first queued request expires, then pads it up the `BucketLadder` (so
  the executor sees one of a small, fixed set of shapes).
- **dispatch thread** — runs the padded batch through the predictor's
  compiled executable, un-pads, and resolves each request's future.
  Assembly of batch k+1 overlaps device execution of batch k through a
  small hand-off queue.
- **warmup()** — AOT-precompiles every ladder signature before traffic,
  so no live request ever pays XLA compile latency (asserted in
  tests/test_serving.py via the executor's cache-miss counters).

Reference analog: the C++ inference predictor pool + batching deploy
layer (paddle/fluid/inference); TPU-native, batching exists to bound
the compile-signature set as much as to raise throughput.
"""

import collections
import itertools
import queue as _queue
import threading
import time

from concurrent.futures import Future

import numpy as np

from .. import observe as _obs
from ..observe import reqtrace as _reqtrace
from .buckets import BucketLadder

__all__ = ['ServingEngine', 'QueueFullError', 'EngineClosedError']

_ENGINE_IDS = itertools.count(1)   # unique /readyz check name per engine


class QueueFullError(RuntimeError):
    """submit() found max_queue_depth requests already waiting — the
    engine is saturated; shed load or retry with backoff."""


class EngineClosedError(RuntimeError):
    """submit() after shutdown(), or a queued request abandoned by a
    non-draining shutdown."""


class _Request(object):
    __slots__ = ('feed', 'rows', 'future', 't_submit', 't_batched',
                 'ctx')

    def __init__(self, feed, rows, ctx=None):
        self.feed = feed
        self.rows = rows
        self.future = Future()
        self.t_submit = time.perf_counter()
        self.t_batched = None
        self.ctx = ctx      # reqtrace.RequestContext (trace correlation)


class ServingEngine(object):
    """Dynamic micro-batching server over an `inference.Predictor`.

    ::

        pred = create_predictor(model_dir)
        eng = ServingEngine(pred, max_batch_size=8, batch_timeout_ms=2)
        eng.warmup()          # compile every bucket signature AOT
        eng.start()
        fut = eng.submit({'x': batch})     # -> Future of [fetch, ...]
        outs = eng.predict({'x': batch})   # submit + wait
        eng.shutdown()        # drain, then stop the workers

    Thread-safe for any number of client threads; the predictor itself
    is only ever driven from the dispatch thread (plus warmup, which
    shares its lock).
    """

    def __init__(self, predictor, max_batch_size=8, batch_timeout_ms=2.0,
                 max_queue_depth=64, ladder=None, seq_axes=None,
                 seq_lens=None, pad='edge', mask_feed=None,
                 fetch_seq_axes=None, dispatch_depth=2, name=None):
        self._predictor = predictor
        # replica identity: the router's dispatch labels, health-check
        # names, and trace route tags all key on this
        self.name = str(name) if name else 'engine%d' % next(_ENGINE_IDS)
        self.max_batch_size = int(max_batch_size)
        self.batch_timeout_s = float(batch_timeout_ms) / 1000.0
        self.max_queue_depth = int(max_queue_depth)
        self._ladder = ladder if ladder is not None else BucketLadder(
            max_batch_size, seq_axes=seq_axes, seq_lens=seq_lens, pad=pad)
        self.max_batch_size = self._ladder.max_batch_size
        self._mask_feed = mask_feed
        self._fetch_seq_axes = dict(fetch_seq_axes or {})

        feed_names = set(predictor.feed_names)
        if mask_feed is not None and mask_feed not in feed_names:
            raise ValueError('mask_feed %r is not a model feed (feeds: '
                             '%s)' % (mask_feed, sorted(feed_names)))
        for name in self._ladder.seq_axes:
            if name not in feed_names:
                raise ValueError('seq_axes names unknown feed %r' % name)
        # feeds the CLIENT supplies (the engine generates the mask)
        self._client_feeds = [n for n in predictor.feed_names
                              if n != mask_feed]

        self._mu = threading.Condition(threading.Lock())
        self._pending = collections.deque()
        self._dispatch_q = _queue.Queue(maxsize=int(dispatch_depth))
        self._predict_mu = threading.Lock()   # dispatcher vs warmup
        self._done_cv = threading.Condition(threading.Lock())
        self._unfinished = 0
        self._closed = False
        self._draining = False
        self._started = False
        self._warmed = False
        self._threads = []
        self._health_name = None
        self.warmup_signatures = 0

    # ------------------------------------------------------------ intake
    def _validate(self, feed):
        missing = [n for n in self._client_feeds if n not in feed]
        if missing:
            raise ValueError('submit: missing feeds %s' % missing)
        unknown = sorted(n for n in feed if n not in self._client_feeds)
        if unknown:
            if self._mask_feed in unknown:
                raise ValueError(
                    'submit: feed %r is the engine-generated mask — '
                    'do not supply it' % self._mask_feed)
            raise ValueError('submit: unexpected feed names %s — this '
                             'model feeds %s' % (unknown,
                                                 self._client_feeds))
        rows = self._ladder.rows_of(feed)
        if rows > self.max_batch_size:
            raise ValueError(
                'request of %d rows exceeds max_batch_size=%d — split '
                'it client-side' % (rows, self.max_batch_size))
        if self._ladder.seq_axes:
            self._ladder.bucket_seq(self._ladder._seq_len_of(feed))
        return rows

    def queue_depth(self):
        """Requests admitted but not yet batched — the router's
        least-loaded signal (same number as the serving.queue_depth
        gauge, readable without the registry)."""
        with self._mu:
            return len(self._pending)

    def submit(self, feed, ctx=None, deadline_s=None):
        """Enqueue one request ({name: array} with a leading batch
        axis, <= max_batch_size rows). Returns a Future resolving to
        the list of fetch arrays for exactly those rows. Raises
        QueueFullError past max_queue_depth and EngineClosedError after
        shutdown; malformed feeds raise ValueError synchronously.

        ``ctx`` (a reqtrace.RequestContext) carries an upstream trace —
        the router passes its own so one trace id spans the whole hop
        chain; when absent a fresh context is created here (sampling
        per PADDLE_TPU_TRACE_SAMPLE, deadline from ``deadline_s``)."""
        t_sub0 = time.perf_counter()
        rows = self._validate(feed)
        if ctx is None:
            ctx = _reqtrace.new_context(self.name, deadline_s=deadline_s)
        req = _Request(feed, rows, ctx)
        # count the request BEFORE it becomes visible to the batcher —
        # otherwise a fast resolve could decrement past a drain()'s
        # notion of zero while this submit is still in flight
        with self._done_cv:
            self._unfinished += 1
        try:
            with self._mu:
                if self._closed:
                    raise EngineClosedError('ServingEngine is shut down')
                if len(self._pending) >= self.max_queue_depth:
                    _obs.inc('serving.rejected_total',
                             reason='queue_full')
                    _obs.flight_event('serving_rejected',
                                      reason='queue_full',
                                      queue_depth=len(self._pending))
                    raise QueueFullError(
                        'serving queue full (%d waiting >= '
                        'max_queue_depth=%d)'
                        % (len(self._pending), self.max_queue_depth))
                self._pending.append(req)
                _obs.set_gauge('serving.queue_depth', len(self._pending))
                self._mu.notify()
        except BaseException:
            self._request_done()
            raise
        if ctx.sampled:
            # the client thread's own slice of the timeline (validate +
            # enqueue) and the flow arrow the batcher/dispatcher link to
            ctx.stage('submit', t_sub0, time.perf_counter(),
                      engine=self.name, rows=rows)
            ctx.flow_begin('request')
        _obs.inc('serving.requests_total')
        return req.future

    def predict(self, feed, timeout=None):
        """submit() + wait — the drop-in replacement for
        Predictor.predict under concurrency."""
        return self.submit(feed).result(timeout)

    # ---------------------------------------------------------- lifecycle
    def ready(self):
        """Load-balancer readiness: True only once start() ran AND
        warmup() completed (every live request is a guaranteed cache
        hit), and False again the moment shutdown/drain begins — a
        balancer honoring this never routes to an engine that would
        pay an XLA compile or drop the request on the floor."""
        return bool(self._started and self._warmed
                    and not self._closed and not self._draining)

    def start(self):
        """Launch the batcher and dispatch threads (idempotent).
        Registers ready() as a /readyz check on the diagnostics server's
        health registry (observe.serve exposes it). Verifies the
        predictor's program first (paddle_tpu.analysis, default warn;
        PADDLE_TPU_VERIFY=strict refuses to serve a broken graph)."""
        program = getattr(self._predictor, 'program', None)
        if program is not None:   # duck-typed predictors have no IR
            from .. import analysis as _analysis
            _analysis.startup_verify(
                program,
                feed_names=list(self._predictor.feed_names),
                fetch_names=[getattr(f, 'name', f) for f in
                             getattr(self._predictor, 'fetch_targets',
                                     ())],
                label='serving')
        with self._mu:
            if self._closed:
                raise EngineClosedError('ServingEngine is shut down')
            if self._started:
                return self
            self._started = True
        for name, fn in (('paddle_tpu_serving_batcher', self._batcher),
                         ('paddle_tpu_serving_dispatch',
                          self._dispatcher)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        self._health_name = 'serving.%s' % self.name
        _obs.register_health_check(self._health_name, self._ready_check,
                                   readiness_only=True)
        return self

    def _ready_check(self):
        r = self.ready()
        if r:
            return True, None
        if not self._warmed:
            return False, 'not warmed up'
        if self._closed or self._draining:
            return False, 'shutting down'
        return False, 'not started'

    def warmup(self, example=None):
        """AOT-precompile EVERY ladder signature by dispatching one
        synthetic padded batch per (batch rung, seq rung) pair — after
        this returns, live traffic can only produce executor cache
        hits. `example` (one request's feed dict) binds any feed dims
        the saved program leaves symbolic beyond batch/sequence.
        Returns the number of signatures dispatched."""
        specs = self._predictor.feed_specs()
        sigs = self._ladder.signatures()
        t_all = time.perf_counter()
        for b, s in sigs:
            feed = {}
            for name, (shape, dtype) in specs.items():
                if name == self._mask_feed:
                    continue
                feed[name] = self._synthetic(name, shape, dtype, b, s,
                                             example)
            if self._mask_feed is not None:
                shape, dtype = specs[self._mask_feed]
                feed[self._mask_feed] = np.ones(
                    (b, s) if len(shape) >= 2 else (b,),
                    dtype=_np_dtype(dtype))
            t0 = time.perf_counter()
            with self._predict_mu:
                self._predictor.predict(feed)
            _obs.record('serving.warmup_seconds',
                        time.perf_counter() - t0, batch=b,
                        seq=s if s is not None else '')
        self.warmup_signatures = len(sigs)
        self._warmed = True
        _obs.set_gauge('serving.warmup_signatures', len(sigs))
        _obs.set_gauge('serving.warmup_total_seconds',
                       time.perf_counter() - t_all)
        return len(sigs)

    def _synthetic(self, name, shape, dtype, batch, seq, example):
        shape = list(shape)
        if not shape:
            raise ValueError('feed %r is scalar — cannot batch' % name)
        shape[0] = batch
        axis = self._ladder.seq_axes.get(name)
        if axis is not None:
            shape[axis] = seq
        for i, d in enumerate(shape):
            if d == -1:
                if example is not None and name in example:
                    shape[i] = np.asarray(example[name]).shape[i]
                else:
                    raise ValueError(
                        'warmup: feed %r dim %d is unbound (-1) and not '
                        'covered by the ladder — pass warmup(example='
                        '{...}) with a representative request' % (name, i))
        return np.zeros(shape, dtype=_np_dtype(dtype))

    def drain(self, timeout=None):
        """Block until every accepted request has resolved. Returns
        True when drained, False on timeout."""
        deadline = None if timeout is None else \
            time.perf_counter() + timeout
        with self._done_cv:
            while self._unfinished > 0:
                wait = None if deadline is None else \
                    deadline - time.perf_counter()
                if wait is not None and wait <= 0:
                    return False
                self._done_cv.wait(wait)
        return True

    def shutdown(self, drain=True, timeout=None):
        """Stop accepting work, then stop the workers. drain=True
        (default) completes everything already accepted first;
        drain=False fails queued-but-unbatched requests with
        EngineClosedError (batches already handed to dispatch still
        complete)."""
        with self._mu:
            if self._closed and not self._threads:
                return
            self._closed = True
            self._draining = drain
            self._mu.notify_all()
        if self._health_name is not None:
            _obs.unregister_health_check(self._health_name)
            self._health_name = None
        if not drain or not self._started:
            self._fail_pending(EngineClosedError(
                'ServingEngine shut down without draining'))
        if self._started and drain:
            self.drain(timeout)
        for t in self._threads:
            if t.name.endswith('batcher'):
                t.join(timeout)
        self._dispatch_q.put(None)
        for t in self._threads:
            if t.name.endswith('dispatch'):
                t.join(timeout)
        self._threads = []

    def close(self):
        self.shutdown(drain=True)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)
        return False

    def _fail_pending(self, exc):
        while True:
            with self._mu:
                if not self._pending:
                    break
                req = self._pending.popleft()
                _obs.set_gauge('serving.queue_depth', len(self._pending))
            if not req.future.cancelled():
                req.future.set_exception(exc)
            self._request_done()

    def _request_done(self):
        with self._done_cv:
            self._unfinished -= 1
            if self._unfinished <= 0:
                self._done_cv.notify_all()

    # ------------------------------------------------------------ workers
    def _batcher(self):
        while True:
            with self._mu:
                while not self._pending and not self._closed:
                    self._mu.wait()
                if not self._pending and self._closed:
                    return
                first = self._pending.popleft()
                _obs.set_gauge('serving.queue_depth', len(self._pending))
            batch, total = [first], first.rows
            deadline = first.t_submit + self.batch_timeout_s
            while total < self.max_batch_size:
                with self._mu:
                    if not self._pending:
                        wait = deadline - time.perf_counter()
                        if wait <= 0 or self._closed or self._draining:
                            break
                        self._mu.wait(wait)
                        if not self._pending:
                            if time.perf_counter() >= deadline or \
                                    self._closed or self._draining:
                                break
                            continue
                    if self._pending[0].rows + total > self.max_batch_size:
                        break   # head doesn't fit: dispatch what we have
                    req = self._pending.popleft()
                    _obs.set_gauge('serving.queue_depth',
                                   len(self._pending))
                batch.append(req)
                total += req.rows
            self._hand_off(batch)

    def _hand_off(self, batch):
        now = time.perf_counter()
        live = []
        for r in batch:
            # claims the future against client-side cancel(): a request
            # that reached RUNNING can no longer be cancelled
            if r.future.set_running_or_notify_cancel():
                r.t_batched = now
                _obs.record('serving.queue_seconds', now - r.t_submit,
                            exemplar=r.ctx.exemplar() if r.ctx else None)
                if r.ctx is not None and r.ctx.sampled:
                    # queue_wait started on the client thread but ends
                    # here: explicit bounds, batcher thread's track
                    r.ctx.stage('queue_wait', r.t_submit, now)
                    r.ctx.flow_step()
                live.append(r)
            else:
                self._request_done()
        if not live:
            return
        try:
            padded, info = self._ladder.assemble([r.feed for r in live])
            if self._mask_feed is not None:
                shape, dtype = self._predictor.feed_specs()[
                    self._mask_feed]
                info_mask = info.token_mask if len(shape) >= 2 and \
                    info.seq_bucket is not None else info.batch_mask
                padded[self._mask_feed] = info_mask(_np_dtype(dtype))
        except BaseException as e:
            for r in live:
                r.future.set_exception(e)
                self._request_done()
            return
        t_asm = time.perf_counter()
        for r in live:
            if r.ctx is not None and r.ctx.sampled:
                r.ctx.stage('batch_assemble', now, t_asm,
                            batch_rows=info.total)
        _obs.inc('serving.batches_total')
        _obs.record('serving.batch_size', info.total)
        _obs.record('serving.padding_waste', info.waste())
        self._dispatch_q.put((padded, info, live))

    def _dispatcher(self):
        while True:
            item = self._dispatch_q.get()
            if item is None:
                return
            padded, info, batch = item
            t0 = time.perf_counter()
            for r in batch:
                _obs.record('serving.batch_seconds', t0 - r.t_batched)
                if r.ctx is not None and r.ctx.sampled:
                    r.ctx.stage('dispatch', r.t_batched, t0)
            try:
                with self._predict_mu:
                    fetches = self._predictor.predict(padded)
                t_comp = time.perf_counter()
                _obs.record('serving.compute_seconds', t_comp - t0,
                            bucket=info.batch_bucket)
                results = self._ladder.disassemble(fetches, info,
                                                   self._fetch_seq_axes)
                now = time.perf_counter()
                for r, outs in zip(batch, results):
                    r.future.set_result(outs)
                    _obs.record('serving.request_seconds',
                                now - r.t_submit,
                                exemplar=r.ctx.exemplar() if r.ctx
                                else None)
                    if r.ctx is not None and r.ctx.sampled:
                        r.ctx.stage('compute', t0, t_comp,
                                    bucket=info.batch_bucket)
                        r.ctx.stage('unpad', t_comp, now)
                        r.ctx.flow_end()
                    self._request_done()
            except BaseException as e:
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(e)
                        if r.ctx is not None:
                            r.ctx.event('request_error',
                                        error=type(e).__name__)
                            r.ctx.flow_end()
                        self._request_done()
                _obs.inc('serving.batch_errors_total')


def _np_dtype(dtype):
    """Numpy-constructible dtype for synthetic feeds; bf16 feeds are
    synthesized f32 and cast by the executor's feed normalization."""
    name = str(dtype)
    if name == 'bfloat16':
        return np.float32
    return np.dtype(name)
