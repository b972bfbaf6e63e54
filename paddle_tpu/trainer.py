"""High-level Trainer / Inferencer (reference: the v2 trainer loop
python/paddle/v2/trainer.py SGD.train with event handlers, and the later
fluid.Trainer shape).

A reader-driven loop over the Executor: batches from a v2-style reader
(optionally prefetched to HBM), per-step/epoch events to a handler,
checkpointing via the fault.CheckpointManager (periodic mid-epoch
saves, keep-last-K retention, sha1-verified auto-resume) and bad-step
guards (fault.guards) on the fetched loss.

The loop is a bounded asynchronous pipeline (train(pipeline_depth=D)):
JAX dispatch is async, so each step is ENQUEUED without syncing and a
deque of <= D in-flight StepHandles is resolved oldest-first — the
host prepares and enqueues steps k+1..k+D while step k executes
on-device. D=1 (the default) resolves each dispatch immediately and is
bit-identical to the classic synchronous loop, params and event stream
alike. host_prefetch=N additionally moves reader iteration, _to_feed,
and window stacking onto a worker thread behind a bounded queue.
"""

import collections
import threading
import time

import numpy as np

from .core.executor import Executor
from .core.place import TPUPlace
from .core.program import (default_main_program, default_startup_program,
                           program_guard)
from . import io as _io
from . import observe as _obs
from .fault import CheckpointConfig, CheckpointManager
from .fault import inject as _inject
from .fault.guards import BadStepGuard

__all__ = ['BeginEpochEvent', 'EndEpochEvent', 'BeginStepEvent',
           'EndStepEvent', 'Trainer', 'record_allreduce_overlap']

_PREFETCH_ERR = object()


def record_allreduce_overlap(step_seconds, compute_seconds,
                             comm_seconds):
    """Publish ``trainer.allreduce_overlap_fraction`` — the fraction of
    the gradient-allreduce leg hidden behind backward compute, from
    three wall-clock measurements (the bucketed step, the compute-only
    step, and the collective-only leg; see observe.overlap_fraction).
    Sits alongside ``trainer.pipeline_overlap_fraction``. Nothing in
    the tree times the three legs: on the chip the fraction is not
    measured. Returns the fraction (or None on degenerate inputs)."""
    frac = _obs.overlap_fraction(step_seconds, compute_seconds,
                                 comm_seconds)
    if frac is not None and _obs.enabled():
        _obs.set_gauge('trainer.allreduce_overlap_fraction', frac)
    return frac


class BeginEpochEvent(object):
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class EndEpochEvent(object):
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class BeginStepEvent(object):
    def __init__(self, epoch_id, step_id):
        self.epoch = epoch_id
        self.step = step_id


class EndStepEvent(object):
    """Step result delivered to the event handler. Beyond the fetched
    `metrics`, carries `wall_time` (this step's host wall seconds —
    windowed steps report wall/window; pipelined steps report the wall
    charged to this dispatch, i.e. excluding time overlapped with older
    in-flight steps) and, when observability is on, `telemetry`: a
    small dict (steps_per_sec_ema / step_seconds_last / mfu / goodput)
    so handlers can log throughput without re-timing steps
    themselves."""

    def __init__(self, epoch_id, step_id, metrics, wall_time=None,
                 telemetry=None):
        self.epoch = epoch_id
        self.step = step_id
        self.metrics = metrics
        self.wall_time = wall_time
        self.telemetry = telemetry


class _Inflight(object):
    """One dispatched-but-unresolved unit in the trainer's pipeline."""

    __slots__ = ('epoch', 'step0', 'steps', 'items', 'handle', 't0', 't1')

    def __init__(self, epoch, step0, steps, items, handle, t0, t1):
        self.epoch = epoch
        self.step0 = step0
        self.steps = steps
        self.items = items
        self.handle = handle
        self.t0 = t0        # dispatch (enqueue) start
        self.t1 = t1        # dispatch (enqueue) end


class Trainer(object):
    """train_func builds the graph and returns the fetch vars (loss
    first); optimizer_func returns the optimizer. Mirrors the reference
    trainer's event-handler contract."""

    def __init__(self, train_func, optimizer_func, place=None,
                 checkpoint_config=None, program=None,
                 startup_program=None):
        self.place = place if place is not None else TPUPlace(0)
        self.program = program or default_main_program()
        self.startup = startup_program or default_startup_program()
        # Build into self.program/self.startup even when the caller passed
        # custom Programs (otherwise train_func appends to the defaults and
        # the custom Program trains an empty graph).
        with program_guard(self.program, self.startup):
            self.fetches = train_func()
            if not isinstance(self.fetches, (list, tuple)):
                self.fetches = [self.fetches]
            optimizer_func().minimize(self.fetches[0])
        self.exe = Executor(self.place)
        if isinstance(checkpoint_config, str):
            # legacy contract: a bare dirname = epoch-end saves only,
            # guards off — exactly the pre-fault-subsystem behavior
            checkpoint_config = CheckpointConfig(checkpoint_config,
                                                 nan_policy=None)
        self.checkpoint_config = checkpoint_config
        self._ckpt = (CheckpointManager(checkpoint_config)
                      if checkpoint_config is not None else None)
        self.checkpoint_dir = (checkpoint_config.dirname
                               if checkpoint_config is not None else None)
        self._guard = None
        if checkpoint_config is not None and checkpoint_config.nan_policy:
            self._guard = BadStepGuard(
                checkpoint_config.nan_policy,
                checkpoint_config.max_bad_steps,
                manager=self._ckpt, executor=self.exe,
                program=self.program)
        self._ckpt_reader = None
        self._last_save = time.monotonic()
        self._step = 0
        self._t_train_entry = None   # set at train() entry; cleared at
                                     # the first dispatch (startup gauge)
        self._peak_flops = None   # lazy device_peak_flops() (observe)
        # ------------------------------------------- pipeline state
        self._event_handler = lambda e: None
        self._inflight = collections.deque()
        self._group_start_step = 0     # _step at the last pipeline-empty
        self._last_resolve_end = None
        self._idle_since = None        # pipeline-empty timestamp
        self._in_ckpt_drain = False
        # pulled-vs-trained ledger (reader-yield units): _pulled moves
        # with the reader (possibly on a prefetch worker thread),
        # _trained with resolves; _reader_lock keeps a checkpoint's
        # (offset, pending) pair consistent against concurrent pulls
        self._reader_lock = threading.Lock()
        self._pulled = 0
        self._trained = 0
        self._pending = 0
        # ---------------------------------------- co-location yield
        # (serving.tenancy.colocation_yield): request_yield() asks the
        # loop to pause at the next dispatch boundary; the loop drains
        # its in-flight pipeline first — the checkpoint sync point —
        # then parks until resume_from_yield(). Pausing between
        # dispatches never changes the dispatched computation, so the
        # final params are bit-identical to an uninterrupted run at
        # the same step count.
        self._yield_requested = False
        self._yield_gate = threading.Event()
        self._yield_gate.set()
        self._parked = False

    def _to_feed(self, data, feeder, feed_order):
        if feeder is not None:
            return feeder.feed(data)
        if isinstance(data, dict):
            # dicts pass through untouched — including dicts of
            # device-resident jax Arrays from reader.prefetch_to_device
            return data
        return {name: np.asarray([d[i] for d in data])
                for i, name in enumerate(feed_order)}

    def train(self, num_epochs, event_handler=None, reader=None,
              feed_order=None, feeder=None, steps_per_dispatch=1,
              pipeline_depth=1, host_prefetch=0, stacked_windows=False):
        """Event-driven training loop (reference v2 trainer contract).

        steps_per_dispatch > 1 compiles the loop body into the XLA
        program (Executor.run_steps over stacked feed windows): one
        device dispatch per window, identical trajectory. Event order
        within a window necessarily shifts — the window's
        BeginStepEvents fire before the dispatch and its EndStepEvents
        (with true per-step metrics) after — since the steps execute as
        one program. Trailing batches that do not fill a window run
        per-step.

        pipeline_depth=D > 1 keeps up to D dispatches in flight:
        enqueue is async, so the host feeds and enqueues steps
        k+1..k+D while step k computes; fetches resolve oldest-first.
        D=1 (default) is bit-identical to the synchronous loop.
        BeginStepEvent fires at dispatch and EndStepEvent at resolve,
        so with D>1 up to D Begin events may precede a step's End.
        Checkpoint cadence points and skip_step guard snapshots drain
        the pipeline first (a save or an undo must not race in-flight
        updates), so cadence may land up to D-1 steps late and the
        skip_step undo unit widens to the whole drain group (<= D
        steps) — see fault.guards.

        host_prefetch=N > 0 runs reader iteration + _to_feed + window
        stacking on a worker thread behind a queue of <= N prepared
        feeds, overlapping host decode with both dispatch and device
        compute.

        stacked_windows=True declares that the reader yields
        device-resident [steps_per_dispatch, ...] superbatches
        (reader.staged_superbatch / recordio_superbatch): each yield is
        fed straight to Executor.run_steps(stacked_feed=True) with no
        re-normalization or host stacking."""
        event_handler = event_handler or (lambda e: None)
        self._event_handler = event_handler
        _inject.install_from_env()
        # crash forensics: PADDLE_TPU_FLIGHT_DUMP arms the flight
        # recorder (and a SIGTERM postmortem) even with metrics off, so
        # a preempted run leaves its last seconds behind
        _obs.arm_flight_from_env()
        # static IR verification before the first compile: default warn
        # (flight events + counters), PADDLE_TPU_VERIFY=strict raises
        # ProgramVerifyError here — before tracing, pointing at the
        # layers call that built the broken op
        from . import analysis as _analysis
        _analysis.startup_verify(
            self.program,
            fetch_names=[getattr(f, 'name', f) for f in self.fetches],
            label='trainer')
        _obs.run_begin()
        try:
            self._train_impl(num_epochs, event_handler, reader,
                             feed_order, feeder, steps_per_dispatch,
                             pipeline_depth, host_prefetch,
                             stacked_windows)
        except BaseException as e:
            _obs.flight_event('train_exception', error=type(e).__name__,
                              step=self._step)
            _obs.flight_dump('trainer_exception', exc=e)
            raise

    def _train_impl(self, num_epochs, event_handler, reader, feed_order,
                    feeder, steps_per_dispatch, pipeline_depth,
                    host_prefetch, stacked_windows):
        from .reader.state import CheckpointableReader
        self._t_train_entry = time.perf_counter()
        self._ckpt_reader = (reader if isinstance(reader,
                                                  CheckpointableReader)
                             else None)
        if reader is not None:
            # Multihost: each host consumes a disjoint shard of the stream
            # (parallel.multihost.shard_reader; no-op on a single host).
            from .parallel.multihost import shard_reader
            reader = shard_reader(reader)
        self.exe.run(self.startup)
        start_epoch = 0
        resume_step = 0
        if self._ckpt is not None and self.checkpoint_config.resume:
            t_restore = time.monotonic()
            meta = self._ckpt.restore(self.exe, self.program,
                                      reader=self._ckpt_reader)
            if meta is not None:
                # restart recovery is run overhead, not training time
                _obs.overhead('restore', time.monotonic() - t_restore)
                if meta.get('reader') and self._ckpt_reader is None \
                        and reader is not None:
                    import warnings
                    warnings.warn(
                        'resume: the checkpoint records a reader '
                        'position but the passed reader is not a '
                        'CheckpointableReader — the resumed stream '
                        'will REPLAY already-trained items. Wrap it in '
                        'reader.checkpointable(...) to resume '
                        'mid-epoch.')
                self._step = int(meta.get('step') or 0)
                # RNG stream continuity (dropout masks): the executor's
                # step key counter sits one ahead of the trainer's step
                # (startup consumed key 0)
                self.exe._step = self._step + 1
                tstate = meta.get('trainer') or {}
                start_epoch = int(tstate.get('epoch', 0))
                resume_step = int(tstate.get('epoch_step', 0))
        self._last_save = time.monotonic()
        w = int(steps_per_dispatch)
        depth = max(1, int(pipeline_depth))
        self._inflight = collections.deque()
        self._last_resolve_end = None
        # the device is idle until the first dispatch: that lead-in is
        # host-blocked wall, same as any later pipeline-empty gap
        self._idle_since = time.perf_counter()
        self._in_ckpt_drain = False
        self._pulled = 0
        self._trained = 0
        t_train0 = time.perf_counter()
        blocked0 = (self._blocked_seconds() if _obs.enabled() else (0, 0))
        # skip_step undoes via a host snapshot taken at pipeline-empty
        # points; bounding the undo unit to <= depth means draining the
        # whole group before refilling instead of popping one
        sync_groups = self._guard is not None and \
            self._guard.needs_snapshot
        for epoch in range(start_epoch, num_epochs):
            event_handler(BeginEpochEvent(epoch))
            # resumed mid-epoch: the CheckpointableReader replays only
            # the untrained remainder; step ids continue where they left
            step = resume_step
            resume_step = 0
            units = self._feed_units(reader, feeder, feed_order, w,
                                     stacked_windows)
            if host_prefetch and int(host_prefetch) > 0:
                units = self._prefetch_units(units, int(host_prefetch))
            for feed, n_steps, n_items in units:
                if self._yield_requested:
                    self._yield_point()
                self._dispatch(epoch, step, feed, n_steps, n_items)
                step += n_steps
                if len(self._inflight) >= depth:
                    if sync_groups:
                        while self._inflight:
                            self._resolve_oldest()
                    else:
                        self._resolve_oldest()
            while self._inflight:
                self._resolve_oldest()
            event_handler(EndEpochEvent(epoch))
            if self._ckpt is not None and self.checkpoint_config.epoch_end:
                with self._reader_lock:
                    self._pending = self._pulled - self._trained
                    self._save_checkpoint(epoch + 1, 0)
        if self._ckpt is not None:
            # completeness point: LATEST/GC of the last async save landed
            self._ckpt.wait()
        if _obs.enabled():
            wall = time.perf_counter() - t_train0
            hb, db = self._blocked_seconds()
            if wall > 0:
                # 1.0 = feed/fetch fully hidden under device compute;
                # 0.0 = the loop is serial (sync depth-1 behavior)
                _obs.set_gauge(
                    'trainer.pipeline_overlap_fraction',
                    max(0.0, 1.0 - ((hb - blocked0[0]) +
                                    (db - blocked0[1])) / wall))
            _obs.flush()   # end-of-train snapshot (no-op without a sink)

    # ------------------------------------------------------ feed stream
    @staticmethod
    def _feed_sig(feed):
        # .shape is read off device arrays directly — np.asarray here
        # would pull a prefetched batch back through host memory
        return {n: (v.shape if hasattr(v, 'shape')
                    else np.asarray(v).shape)
                for n, v in feed.items()}

    @staticmethod
    def _stack_window(window):
        """Stack w per-step feeds into [w, ...] arrays for
        run_steps(stacked_feed=True). Device-resident feeds
        (reader.prefetch_to_device) stack on-device."""
        out = {}
        for name in window[0]:
            vals = [f[name] for f in window]
            if hasattr(vals[0], 'devices'):
                import jax.numpy as jnp
                out[name] = jnp.stack(vals)
            else:
                out[name] = np.stack(vals)
        return out

    def _feed_units(self, reader, feeder, feed_order, w,
                    stacked_windows):
        """One epoch of prepared dispatch units (feed, n_steps,
        n_items): reader pull + _to_feed + window collection/stacking —
        every host-side cost the dispatch path does not need to pay
        itself, so _prefetch_units can move the whole generator onto a
        worker thread. n_items counts reader yields (the
        CheckpointableReader offset unit) for the pulled-vs-trained
        checkpoint ledger."""
        it = iter(reader())
        window = []
        while True:
            # the lock keeps a concurrent checkpoint's (offset, pending)
            # pair consistent when this generator runs on the prefetch
            # worker; uncontended cost is one atomic acquire per batch
            with self._reader_lock:
                try:
                    data = next(it)
                except StopIteration:
                    break
                self._pulled += 1
            if stacked_windows:
                # already a device-resident [w, ...] superbatch
                # (reader.staged_superbatch / recordio_superbatch):
                # no _to_feed, no re-normalization, no host stack
                yield data, w, 1
                continue
            t_feed = time.perf_counter()
            feed = self._to_feed(data, feeder, feed_order)
            if _obs.enabled():
                _obs.record('trainer.phase_seconds',
                            time.perf_counter() - t_feed, phase='feed')
            if w <= 1:
                yield feed, 1, 1
                continue
            if window and self._feed_sig(feed) != \
                    self._feed_sig(window[0]):
                # shape change mid-window (bucketed readers): the
                # collected prefix runs per-step, stacking resumes at
                # this batch
                for f in window:
                    yield f, 1, 1
                window = []
            window.append(feed)
            if len(window) == w:
                t_stack = time.perf_counter()
                stacked = self._stack_window(window)
                if _obs.enabled():
                    # per-window feed cost carries a steps=w label so
                    # phase percentiles stay comparable across
                    # dispatch modes
                    _obs.record('trainer.phase_seconds',
                                time.perf_counter() - t_stack,
                                phase='feed', steps=w)
                window = []
                yield stacked, w, w
        for f in window:    # trailing window: per-step
            yield f, 1, 1

    def _prefetch_units(self, units, depth):
        """Bounded host prefetch: iterate the _feed_units generator on
        a worker thread behind a Queue(depth). Puts are close-aware
        (timeout loop against a closed Event), so a consumer that exits
        early — break, error, GeneratorExit — never leaves the worker
        blocked on a full queue."""
        from queue import Full, Queue
        q = Queue(maxsize=max(1, int(depth)))
        done = object()
        closed = threading.Event()

        def _put(item):
            while not closed.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except Full:
                    pass
            return False

        def work():
            try:
                for unit in units:
                    if not _put(unit):
                        return
                _put(done)
            except BaseException as e:   # surfaced on the consumer side
                _put((_PREFETCH_ERR, e, None))

        t = threading.Thread(target=work, daemon=True,
                             name='paddle_tpu_trainer_prefetch')
        t.start()
        try:
            while True:
                item = q.get()
                if item is done:
                    return
                if item[0] is _PREFETCH_ERR:
                    raise item[1]
                if _obs.enabled():
                    # occupancy AFTER the pop: 0 = dispatch is starved
                    _obs.set_gauge('trainer.prefetch_queue_depth',
                                   q.qsize())
                yield item
        finally:
            closed.set()

    # ------------------------------------------------ co-location yield
    def request_yield(self):
        """Ask the training loop to pause at its next dispatch
        boundary (serving.tenancy.colocation_yield calls this when the
        co-located serving replica hits SLO pressure). Returns
        immediately; the loop drains its in-flight pipeline — the same
        sync point a due checkpoint uses — then parks with the device
        idle until :meth:`resume_from_yield`. A yield never changes
        what gets dispatched, so params stay bit-identical to an
        uninterrupted run at the same step count."""
        self._yield_gate.clear()
        self._yield_requested = True

    def resume_from_yield(self):
        """Release a :meth:`request_yield` park (idempotent)."""
        self._yield_requested = False
        self._yield_gate.set()

    def yielded(self):
        """True while the training loop is actually parked (drained
        and blocked) — the co-location scenario's observable."""
        return self._parked

    def _yield_point(self):
        # drain: every dispatched step resolves before the pause, so
        # a resume (or a checkpoint during the pause window) sees a
        # consistent param state
        while self._inflight:
            self._resolve_oldest()
        self._parked = True
        t0 = time.perf_counter()
        _obs.set_gauge('trainer.yielded', 1)
        self._yield_gate.wait()
        self._parked = False
        if self._idle_since is not None:
            # the parked window is the tenant's time, not host-blocked
            # wall — restart the idle clock so the overlap fraction
            # only bills real feed-preparation gaps
            self._idle_since = time.perf_counter()
        _obs.set_gauge('trainer.yielded', 0)
        if _obs.enabled():
            _obs.record('trainer.yield_seconds',
                        time.perf_counter() - t0)

    # ------------------------------------------------- dispatch/resolve
    def _dispatch(self, epoch, step0, feed, n_steps, n_items):
        handler = self._event_handler
        g = self._guard
        if not self._inflight:
            if g is not None and g.needs_snapshot:
                # snapshot cadence = pipeline-empty points (<= every
                # depth dispatches under sync_groups); nothing is in
                # flight here, so the device->host readback cannot
                # stall pending work
                g.snapshot()
            self._group_start_step = self._step
            if _obs.enabled() and self._idle_since is not None:
                # the device had nothing queued while the host prepared
                # this feed: that gap is host-blocked wall
                _obs.add_gauge('trainer.host_blocked_seconds',
                               time.perf_counter() - self._idle_since)
        self._idle_since = None
        for i in range(n_steps):
            handler(BeginStepEvent(epoch, step0 + i))
        t0 = time.perf_counter()
        if n_steps == 1:
            with _obs.span('trainer.step', step=self._step):
                h = self.exe.run(program=self.program, feed=feed,
                                 fetch_list=self.fetches,
                                 return_handle=True)
        else:
            with _obs.span('trainer.window', steps=n_steps,
                           step0=self._step):
                h = self.exe.run_steps(n_steps, program=self.program,
                                       feed=feed,
                                       fetch_list=self.fetches,
                                       stacked_feed=True,
                                       return_handle=True)
        t1 = time.perf_counter()
        if self._t_train_entry is not None:
            # cold-vs-warm startup headline: wall from train() entry to
            # the first dispatch ENQUEUED — startup-program run, resume,
            # and the first step's trace+compile all land in here
            _obs.set_gauge('trainer.time_to_first_dispatch_seconds',
                           t1 - self._t_train_entry)
            self._t_train_entry = None
        self._inflight.append(
            _Inflight(epoch, step0, n_steps, n_items, h, t0, t1))
        _obs.set_gauge('trainer.inflight_depth', len(self._inflight))

    def _resolve_oldest(self):
        """Resolve the oldest in-flight dispatch: sync its fetches,
        run the guard, fire EndStepEvents, count it, checkpoint if due.
        Returns (epoch, next_epoch_step) of the resolved unit."""
        handler = self._event_handler
        ent = self._inflight.popleft()
        _obs.set_gauge('trainer.inflight_depth', len(self._inflight))
        r0 = time.perf_counter()
        was_ready = ent.handle.ready() if _obs.enabled() else True
        with _obs.span('trainer.resolve', step0=ent.step0,
                       steps=ent.steps):
            metrics = ent.handle.resolve()
        r1 = time.perf_counter()
        if _obs.enabled():
            _obs.record('trainer.resolve_seconds', r1 - r0)
            if not was_ready:
                # the host sat here waiting on the device
                _obs.add_gauge('trainer.device_blocked_seconds', r1 - r0)
        self._step += ent.steps
        loss_val = None
        if _obs.enabled():
            # leading indicator: z-score the fetched loss against its
            # EWMA baseline BEFORE the guard's NaN postcondition runs
            try:
                loss_val = float(np.mean(
                    np.asarray(metrics[0], dtype=np.float64)))
            except (TypeError, ValueError):
                pass
            if loss_val is not None:
                _obs.anomaly('loss', loss_val)
        g = self._guard
        verdict = 'ok'
        if g is not None:
            from .fault.guards import is_bad
            undo = ent.steps
            if is_bad(metrics[0]) and self._inflight:
                # pipelined detection: the steps behind this one are
                # already dispatched on poisoned state — drain and
                # discard them BEFORE the guard restores anything
                # (their scope writes happened at dispatch; the
                # restore must win)
                self._drain_discard()
            if g.needs_snapshot:
                # the snapshot predates the whole drain group: undoing
                # it takes the group's earlier good steps with it
                undo = self._step - self._group_start_step
            verdict = g.handle(metrics[0], self._step, steps=undo)
            if verdict == 'skipped':
                self._step = self._group_start_step
        if self._last_resolve_end is not None:
            wall = r1 - max(ent.t0, self._last_resolve_end)
        else:
            wall = r1 - ent.t0
        self._last_resolve_end = r1
        self._record_step(wall, ent.t1 - ent.t0, r1 - r0, verdict,
                          steps=ent.steps,
                          cache_miss=ent.handle.cache_miss)
        if loss_val is not None:
            _obs.flight_event('step_end', step=self._step,
                              epoch=ent.epoch, steps=ent.steps,
                              verdict=verdict, wall=round(wall, 6),
                              loss=loss_val)
        else:
            _obs.flight_event('step_end', step=self._step,
                              epoch=ent.epoch, steps=ent.steps,
                              verdict=verdict, wall=round(wall, 6))
        telemetry = _obs.step_telemetry() if _obs.enabled() else None
        if ent.steps == 1:
            handler(EndStepEvent(ent.epoch, ent.step0, metrics,
                                 wall_time=wall, telemetry=telemetry))
        else:
            for i in range(ent.steps):
                handler(EndStepEvent(
                    ent.epoch, ent.step0 + i,
                    [np.asarray(m[i]) for m in metrics],
                    wall_time=wall / ent.steps, telemetry=telemetry))
        self._trained += ent.items
        if not self._inflight:
            self._idle_since = time.perf_counter()
        if verdict == 'ok':
            # never checkpoint a bad step's state; a skipped/rolled-back
            # step saves nothing and the next good one resumes cadence
            self._maybe_checkpoint(ent.epoch, ent.step0 + ent.steps)
        _inject.fire('step_end', step=self._step)
        return ent.epoch, ent.step0 + ent.steps

    def _drain_discard(self):
        """Bad step detected with younger dispatches in flight: resolve
        them (their updates are about to be overwritten by the guard's
        restore), fire their EndStepEvents, and count their reader
        items as consumed — the data stream continues FORWARD past a
        bad batch — but never count their steps."""
        handler = self._event_handler
        while self._inflight:
            ent = self._inflight.popleft()
            metrics = ent.handle.resolve()
            _obs.inc('trainer.pipeline_drained_steps_total', ent.steps)
            if ent.steps == 1:
                handler(EndStepEvent(ent.epoch, ent.step0, metrics))
            else:
                for i in range(ent.steps):
                    handler(EndStepEvent(
                        ent.epoch, ent.step0 + i,
                        [np.asarray(m[i]) for m in metrics]))
            self._trained += ent.items
        _obs.set_gauge('trainer.inflight_depth', 0)
        self._idle_since = None

    @staticmethod
    def _blocked_seconds():
        return (_obs.get_gauge('trainer.host_blocked_seconds') or 0.0,
                _obs.get_gauge('trainer.device_blocked_seconds') or 0.0)

    # ----------------------------------------------------- checkpoints
    def _save_checkpoint(self, epoch, epoch_step):
        """Checkpoint NOW, recording where the loop stands: resume
        restarts at (epoch, epoch_step) with the reader replaying the
        untrained remainder of that epoch."""
        t0 = time.monotonic()
        with _obs.span('fault.checkpoint_save', step=self._step):
            self._ckpt.save(self.exe, self.program, step=self._step,
                            reader=self._ckpt_reader,
                            reader_pending=getattr(self, '_pending', 0),
                            trainer_state={'epoch': int(epoch),
                                           'epoch_step': int(epoch_step)})
        _obs.overhead('checkpoint', time.monotonic() - t0)
        self._last_save = time.monotonic()

    def _ckpt_cadence_due(self):
        cfg = self.checkpoint_config
        if self._ckpt is None or (not cfg.save_every_steps and
                                  cfg.save_every_secs is None):
            return False
        due = bool(cfg.save_every_steps) and self._step > 0 and \
            self._step % cfg.save_every_steps == 0
        if not due and cfg.save_every_secs is not None:
            due = time.monotonic() - self._last_save >= cfg.save_every_secs
        return due

    def _maybe_checkpoint(self, epoch, epoch_step):
        if self._in_ckpt_drain or not self._ckpt_cadence_due():
            return
        # a due save is a sync point: younger steps are already
        # dispatched (updates applied), so resolve them first — the
        # saved params and the recorded position must agree. Cadence
        # therefore lands up to depth-1 steps late under pipelining.
        self._in_ckpt_drain = True
        try:
            while self._inflight:
                epoch, epoch_step = self._resolve_oldest()
        finally:
            self._in_ckpt_drain = False
        with self._reader_lock:
            self._pending = self._pulled - self._trained
            if self._ckpt_reader is not None and \
                    self._pending > self._ckpt_reader.offset:
                # pulled-but-untrained items span an epoch boundary
                # (offset already reset); their in-epoch positions are
                # unknowable — defer to the next cadence point instead
                # of mis-recording
                return
            self._save_checkpoint(epoch, epoch_step)

    # -------------------------------------------------------- telemetry
    def _record_step(self, wall, compute_s, fetch_s, verdict, steps=1,
                     cache_miss=False):
        """Telemetry for one dispatch: phase histograms, throughput EMA,
        MFU, and the goodput ledger. A dispatch that compiled charges its
        wall time to overhead (goodput counts recompiles against the
        run); bad steps likewise. cache_miss is captured at dispatch —
        under pipelining the executor's last_cache_miss already belongs
        to a younger step by resolve time."""
        if not _obs.enabled():
            return
        if steps > 1:
            # windows record whole-window phase seconds; the steps=w
            # label keeps them out of the per-step percentile streams
            _obs.record('trainer.phase_seconds', compute_s,
                        phase='compute', steps=steps)
            _obs.record('trainer.phase_seconds', fetch_s,
                        phase='fetch', steps=steps)
        else:
            _obs.record('trainer.phase_seconds', compute_s,
                        phase='compute')
            _obs.record('trainer.phase_seconds', fetch_s, phase='fetch')
        per_step = wall / steps
        _obs.inc('trainer.steps_total', steps)
        _obs.record('trainer.step_seconds', per_step)
        _obs.set_gauge('trainer.step_seconds_last', per_step)
        _obs.anomaly('step_time', per_step)
        rate = steps / wall if wall > 0 else 0.0
        prev = _obs.get_gauge('trainer.steps_per_sec_ema')
        _obs.set_gauge('trainer.steps_per_sec_ema',
                       rate if prev is None else 0.9 * prev + 0.1 * rate)
        if cache_miss:
            _obs.overhead('first_dispatch', wall)
        elif verdict == 'ok':
            _obs.step_done(wall, steps)
        else:
            _obs.overhead('bad_step', wall)
        flops = _obs.get_gauge('executor.step_flops')
        if flops:
            if self._peak_flops is None:
                self._peak_flops = _obs.device_peak_flops() or 0.0
            if self._peak_flops:
                _obs.set_gauge('trainer.mfu', min(
                    1.0, steps * flops / wall / self._peak_flops))
        _obs.maybe_flush()

    def save_params(self, dirname):
        _io.save_params(self.exe, dirname, main_program=self.program)

    def save_inference_model(self, dirname, feeded_var_names,
                             target_vars):
        _io.save_inference_model(dirname, feeded_var_names, target_vars,
                                 self.exe, main_program=self.program)
