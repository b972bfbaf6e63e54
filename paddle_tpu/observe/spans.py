"""Host-side span tracing exported as Chrome-trace/Perfetto JSON.

Spans are nested host wall-time intervals (compile, step, feed, fetch,
checkpoint, barrier...). Each completed span becomes one Chrome-trace
"complete" event (``ph: "X"`` with ``ts``/``dur`` in microseconds), so
the file written by export() loads directly in Perfetto
(https://ui.perfetto.dev) or chrome://tracing, with nesting recovered
from containment on the (pid, tid) track.

Bridge to device traces: when jax is already loaded, entering a span
also enters ``jax.profiler.TraceAnnotation(name)``, so the SAME span
names show up inside an XLA device trace captured with
``profiler.start_profiler(trace_dir=...)`` — host intervals and device
ops line up by name in one Perfetto view.

Cross-thread parenting: the thread-local ``begin``/``end`` stack can
only nest spans on ONE thread. A request that crosses threads (serving
submit → batcher → dispatcher, any producer→consumer handoff) links its
spans with Chrome-trace *flow events* instead: the producer calls
``flow_begin(name)`` and hands the returned ``FlowHandle`` to the
consumer, who calls ``flow_step``/``flow_end`` on *its* thread — Perfetto
draws an arrow between the enclosing slices. ``add_span`` records a
completed interval with explicit perf_counter timestamps (no stack), so
a stage measured on thread A but *observed* finishing on thread B still
lands on the observing thread's track with exact bounds, and
``add_instant`` records zero-duration marks (per-token events).

Two clocks beside the ring. ``StateClock`` keeps the seconds one thread
has spent in each of a few states, fed by the enter and exit of the
spans that are those states, so that an interval that begins in one
span or on one thread and ends in another can be split by state as the
difference of two readings. ``SpanRecorder.offset_to`` measures where
the ring's clock stands against another record of the same spans (the
profiler's trace holds a copy of every span that began and ended while
it ran), so that the ring's own events, also those the trace lost at an
edge, can be laid over that trace.
"""

import bisect
import collections
import json
import os
import sys
import threading
import time

__all__ = ['SpanRecorder', 'FlowHandle', 'StateClock', 'MAX_EVENTS']

# bound memory in unbounded runs: a ring of the newest MAX_EVENTS events
# (a long-lived server exports its last minutes, not its start-up); the
# count of those pushed out is recorded in the export metadata
MAX_EVENTS = 200000
# offset_to: how far apart two recorders' lengths of one span may lie,
# and how far from the voted offset a pair of its copies
VOTE_US = 25.0
PAIR_US = 500.0

# every event carries the process id. ``os.getpid()`` is a system call
# an event (6 us on the benchmark's sandboxed host, PERF.md section 6,
# PR 56: as much as the rest of a span), so it is read once here and
# again in a forked child
_pid = os.getpid()


def _refresh_pid():
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_refresh_pid)


class _Span(object):
    __slots__ = ('name', 'attrs', 't0', 'ann')

    def __init__(self, name, attrs):
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.ann = None


class FlowHandle(object):
    """Ticket for one producer→consumer handoff arrow. Created by
    ``SpanRecorder.flow_begin`` on the producer thread; any number of
    ``flow_step`` calls and one ``flow_end`` may follow from OTHER
    threads — the events share ``flow_id`` so Perfetto links the
    enclosing slices across tracks."""

    __slots__ = ('flow_id', 'name')

    def __init__(self, flow_id, name):
        self.flow_id = flow_id
        self.name = name


class StateClock(object):
    """Cumulative seconds one thread has spent in each of ``states``,
    readable at any instant, mid-span, from any thread.

    The thread's spans feed it: ``observe.span(..., clock=)`` hands the
    span's own two clock readings to ``enter`` and ``exit``. The time
    between one state's exit and the next one's enter (a loop's own
    microseconds) stays with the state that closed, so from the first
    enter on the totals tile the thread's wall time without a hole, and
    the parts of any interval sum to its length. What is known lives in
    one tuple that ``enter`` and ``exit`` replace whole, so a reader on
    another thread takes no lock and sees no half-made state."""

    def __init__(self, states):
        self.states = tuple(states)
        self._index = {s: i for i, s in enumerate(self.states)}
        # (seconds by state up to ``since``, the state running from then
        # on or None between two spans, since, the state that ran up to
        # ``since``)
        self._now = ((0.0,) * len(self.states), None, 0.0, None)

    @staticmethod
    def _plus(totals, i, seconds):
        return totals[:i] + (totals[i] + seconds,) + totals[i + 1:]

    def enter(self, state, t):
        totals, _, since, last = self._now
        if last is not None:
            totals = self._plus(totals, last, t - since)
        self._now = (totals, self._index[state], t, last)

    def exit(self, state, t):
        totals, _, since, _ = self._now
        i = self._index[state]
        self._now = (self._plus(totals, i, t - since), None, t, i)

    def at(self, t):
        """Seconds by state, in the order of ``states``, as they stood
        at ``time.perf_counter()`` = ``t``: now, or an instant of the
        state still running. Between two spans time runs on under the
        state that closed (a reader that holds the lock the thread is
        waiting for sees such a stretch last milliseconds), and an
        instant just before the last transition (a reader on another
        thread that lost a race with it) is taken off the state that ran
        until then: the states always sum to ``t`` less the first
        enter. After the thread's last span, read it at that span's
        end."""
        totals, running, since, last = self._now
        if t < since or running is None:
            running = last
        return totals if running is None else \
            self._plus(totals, running, t - since)


class SpanRecorder(object):
    def __init__(self):
        # re-entrant: a garbage collection can strike a thread that
        # holds it, and its ``host.gc`` span is added from inside the
        # collector (observe._on_gc)
        self._lock = threading.RLock()
        self._events = collections.deque()
        self._dropped = 0
        # observe.__init__ points this at the registry's
        # spans_dropped_total counter, so a truncated trace is visible
        # from /metrics alone (not just the trace-file metadata)
        self.on_drop = None
        self._tls = threading.local()
        # one zero point for the whole recorder: perf_counter deltas
        # anchored to an epoch timestamp so ts is meaningful across
        # threads and aligns with the jax trace clock reasonably well
        self._epoch0 = time.time() - time.perf_counter()
        self._flow_ids = 0
        self._proc_labels = set()

    # ---------------------------------------------------------- record
    def begin(self, name, attrs=None, bridge_jax=True):
        sp = _Span(name, attrs)
        if bridge_jax:
            jax = sys.modules.get('jax')
            if jax is not None:
                try:
                    # attrs become the event's stats in the profiler's
                    # trace, not part of its name
                    sp.ann = jax.profiler.TraceAnnotation(
                        name, **(attrs or {}))
                    sp.ann.__enter__()
                except Exception:
                    sp.ann = None
        stack = getattr(self._tls, 'stack', None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(sp)
        sp.t0 = time.perf_counter()
        return sp

    def end(self, sp=None):
        """Close the innermost open span of this thread (or unwind to
        ``sp``); returns its duration in seconds, None if none was
        open."""
        t1 = time.perf_counter()
        stack = getattr(self._tls, 'stack', None)
        if not stack:
            return None
        top = stack.pop()
        if sp is not None and top is not sp:
            # mismatched end (generator-based caller): unwind to sp
            while stack and top is not sp:
                top = stack.pop()
        if top.ann is not None:
            try:
                top.ann.__exit__(None, None, None)
            except Exception:
                pass
        ev = {'name': top.name, 'ph': 'X', 'pid': _pid,
              'tid': threading.get_ident(),
              'ts': (self._epoch0 + top.t0) * 1e6,
              'dur': (t1 - top.t0) * 1e6}
        if top.attrs:
            ev['args'] = top.attrs
        self._append(ev)
        return t1 - top.t0

    def _append(self, ev):
        with self._lock:
            cb = None
            if len(self._events) >= MAX_EVENTS:
                self._events.popleft()   # the oldest falls off the ring
                self._dropped += 1
                cb = self.on_drop
            self._events.append(ev)
        if cb is not None:
            try:
                cb(1)
            except Exception:
                pass

    def depth(self):
        return len(getattr(self._tls, 'stack', ()) or ())

    # ------------------------------------------- explicit-interval spans
    def add_span(self, name, t0, t1, attrs=None, tid=None):
        """Record a completed span with explicit ``time.perf_counter()``
        bounds — no thread-local stack, no jax bridge. The span lands on
        the calling thread's track (or ``tid``), so a stage whose start
        was clocked on another thread (e.g. a request's queue wait,
        started at submit() but observed ending in the batcher) still
        renders with exact bounds."""
        ev = {'name': name, 'ph': 'X', 'pid': _pid,
              'tid': threading.get_ident() if tid is None else tid,
              'ts': (self._epoch0 + t0) * 1e6,
              'dur': max(0.0, t1 - t0) * 1e6}
        if attrs:
            ev['args'] = dict(attrs)
        self._append(ev)

    def add_instant(self, name, attrs=None):
        """Record a zero-duration mark on the calling thread (scope
        't'): per-token decode events, admission decisions, kills."""
        ev = {'name': name, 'ph': 'i', 's': 't', 'pid': _pid,
              'tid': threading.get_ident(),
              'ts': (self._epoch0 + time.perf_counter()) * 1e6}
        if attrs:
            ev['args'] = dict(attrs)
        self._append(ev)

    # ------------------------------------------------ cross-thread flows
    def flow_begin(self, name, attrs=None, flow_id=None):
        """Start a flow arrow on the calling thread; returns the
        FlowHandle the consumer thread passes to flow_step/flow_end.
        ``flow_id`` defaults to a recorder-unique integer (pass a
        trace id to make the arrow greppable in the raw JSON)."""
        with self._lock:
            if flow_id is None:
                self._flow_ids += 1
                flow_id = self._flow_ids
        h = FlowHandle(flow_id, name)
        self._flow_event('s', h, attrs)
        return h

    def flow_step(self, handle, attrs=None):
        """Mark the flow passing through the calling thread."""
        self._flow_event('t', handle, attrs)

    def flow_end(self, handle, attrs=None):
        """Terminate the flow on the calling thread."""
        self._flow_event('f', handle, attrs, bind_enclosing=True)

    def _flow_event(self, ph, handle, attrs, bind_enclosing=False):
        ev = {'name': handle.name, 'cat': 'flow', 'ph': ph,
              'id': handle.flow_id, 'pid': _pid,
              'tid': threading.get_ident(),
              'ts': (self._epoch0 + time.perf_counter()) * 1e6}
        if bind_enclosing:
            ev['bp'] = 'e'   # bind the arrowhead to the enclosing slice
        if attrs:
            ev['args'] = dict(attrs)
        self._append(ev)

    # -------------------------------------------------- process metadata
    def set_process_name(self, label):
        """Record a Chrome-trace ``process_name`` metadata event so this
        process's track carries a human label ('controller', 'r0', ...)
        in a merged fleet view (tools/fleet_trace.py) instead of a bare
        pid. Idempotent per label — the heartbeat loop may call it every
        tick without flooding the ring."""
        with self._lock:
            if label in self._proc_labels:
                return
            self._proc_labels.add(label)
        self._append({'name': 'process_name', 'ph': 'M',
                      'pid': _pid, 'tid': threading.get_ident(),
                      'args': {'name': str(label)}})

    # ---------------------------------------------------------- export
    def events(self):
        with self._lock:
            return list(self._events)

    def perf_time(self, ev):
        """``time.perf_counter()`` as it read when the recorded event
        ``ev`` began (its ``ts`` is that on the recorder's epoch)."""
        return ev['ts'] / 1e6 - self._epoch0

    def offset_to(self, copies, min_matched=20):
        """Where another clock stands against this ring's, by
        measurement: ``copies`` are ``(name, start_ns, dur_ns)`` of
        spans as another recorder timed them (the profiler's trace has
        one of every span of this ring that began and ended while it
        ran). Returns ``{'matched': n, 'copies': m, 'offset_ns': o,
        'residual_us_p95': r}`` such that a ring event's ``ts * 1000 +
        o`` is its start on the other clock, ``n`` of the ``m`` copies
        whose names the ring knows having found their span; None under
        ``min_matched`` pairs.

        A few of the longest copies vote first, each with every ring
        span of its name and its length (to ``VOTE_US``: the two
        recorders read their clocks microseconds apart) and with one
        vote to share among them, so a span of a length of its own (an
        idle wait, a prefill) outweighs one of many alike; the offset
        with most votes is the guess. Every copy is then paired with
        the ring span of its name that starts nearest the guess (within
        ``PAIR_US``); the offset is the pairs' median and the residual
        how far 95% of them lie from it at most."""
        ring = {}
        for ev in self.events():
            if ev.get('ph') == 'X':
                ring.setdefault(ev['name'], []).append(
                    (ev['ts'] * 1e3, ev['dur'] * 1e3))
        copies = [c for c in copies if c[0] in ring and c[2] > 0]
        if len(copies) < min_matched:
            return None
        for spans in ring.values():
            spans.sort()
        near = VOTE_US * 1e3
        votes = []
        for name, s, d in sorted(copies, key=lambda c: -c[2])[:16]:
            alike = [s - t0 for t0, dur in ring[name] if abs(dur - d) <= near]
            votes.extend((off, 1.0 / len(alike)) for off in alike)
        if not votes:
            return None
        votes.sort()
        # the heaviest window of the votes
        best, guess, weight, lo = 0.0, None, 0.0, 0
        for hi, (off, w) in enumerate(votes):
            weight += w
            while off - votes[lo][0] > near:
                weight -= votes[lo][1]
                lo += 1
            if weight > best + 1e-9:
                best, guess = weight, votes[(lo + hi) // 2][0]
        slack = PAIR_US * 1e3
        apart = []
        for name, s, _ in copies:
            starts = ring[name]
            i = bisect.bisect_left(starts, (s - guess,))
            d = min((s - starts[j][0] for j in (i - 1, i)
                     if 0 <= j < len(starts)),
                    key=lambda d: abs(d - guess))
            if abs(d - guess) <= slack:
                apart.append(d)
        if len(apart) < min_matched:
            return None
        apart.sort()
        offset = apart[len(apart) // 2]
        off = sorted(abs(d - offset) for d in apart)
        return {'matched': len(apart), 'copies': len(copies),
                'offset_ns': offset,
                'residual_us_p95': off[int(0.95 * (len(off) - 1))] / 1e3}

    def clear(self):
        with self._lock:
            self._events.clear()
            self._dropped = 0
            self._proc_labels = set()

    def chrome_trace(self):
        """Chrome trace JSON object (dict) of all completed spans."""
        with self._lock:
            doc = {'traceEvents': list(self._events),
                   'displayTimeUnit': 'ms'}
            if self._dropped:
                doc['paddle_tpu_dropped_spans'] = self._dropped
            return doc

    def export(self, path):
        doc = self.chrome_trace()
        tmp = path + '.tmp'
        with open(tmp, 'w') as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path
