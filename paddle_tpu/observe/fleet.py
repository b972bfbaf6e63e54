"""Fleet-wide telemetry federation: one merged view over N processes.

PR 16 made replicas real subprocesses behind the RPC control plane
(serving/rpc.py); each one runs its own diagnostics server, so the
controller process can SEE every replica's registry — it just never
looked. This module is the controller-side half of that look:

- ``FleetFederation`` keeps a registry of live replica handles
  (duck-typed: ``.url`` of the replica's diagnostics server, optional
  ``.clock_offset()`` / ``.postmortem()``), scrapes each one's /varz
  over HTTP on a poll interval, re-labels every series with
  ``{replica, host}`` (registry.relabel_snapshot) and merges the
  results into one snapshot — served by diagnostics.py at ``/fleetz``
  and as Prometheus text at ``/metrics?scope=fleet``.
- ``ClockOffsetEstimator`` turns NTP-style four-timestamp exchanges
  (serving/rpc.py runs one against /clockz after each successful
  readiness probe) into an EWMA-smoothed per-replica wall-clock offset,
  so ``federated_trace`` and tools/fleet_trace.py can shift replica
  span timestamps onto the controller's clock before merging.
- ``federated_trace(trace_id)`` fans a /tracez?trace_id= query out to
  every registered replica, shifts the returned spans by that replica's
  offset, and returns one cross-process timeline (the controller's
  /tracez does this automatically; replicas are queried with
  ``&local=1`` so a replica that is ITSELF federating cannot recurse).

The poll interval knob ``PADDLE_TPU_FLEET_POLL_S`` is read PER CALL
(repo_lint-enforced), never at import. Scrapes happen on a daemon
thread or explicitly via ``poll_once()`` — deterministic tests call
the latter and never start the thread.
"""

import json
import os
import sys
import threading
import time
import urllib.request

from .registry import relabel_snapshot

__all__ = ['ClockOffsetEstimator', 'FleetFederation', 'fleet',
           'http_get_json', 'poll_interval', 'FLEET_POLL_ENV',
           'DEFAULT_POLL_S']

FLEET_POLL_ENV = 'PADDLE_TPU_FLEET_POLL_S'
DEFAULT_POLL_S = 2.0


def _obs():
    return sys.modules['paddle_tpu.observe']


def poll_interval(environ=None):
    """The fleet scrape interval in seconds — read from the environment
    PER CALL, default DEFAULT_POLL_S, floor 0.05 (a zero/malformed
    value must not spin the poll thread)."""
    env = os.environ if environ is None else environ
    raw = env.get(FLEET_POLL_ENV)
    if not raw:
        return DEFAULT_POLL_S
    try:
        return max(0.05, float(raw))
    except ValueError:
        return DEFAULT_POLL_S


def http_get_json(url, timeout=5.0):
    """GET ``url`` and parse the body as JSON (the shape every
    diagnostics GET route speaks)."""
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode('utf-8'))


class ClockOffsetEstimator(object):
    """EWMA-smoothed wall-clock offset of one remote process, fed by
    NTP-style four-timestamp exchanges:

        t0  local send    (local clock)
        t1  remote recv   (remote clock)
        t2  remote send   (remote clock)
        t3  local recv    (local clock)

    ``offset = ((t1-t0) + (t2-t3)) / 2`` estimates remote−local, so a
    remote timestamp maps onto the local clock as ``t_remote − offset``.
    Samples whose round-trip time is much worse than the best seen so
    far are down-weighted (asymmetric network delay is the dominant
    error term); the first sample seeds the EWMA directly. A seed can
    be a bad one: the first exchange with a fresh server takes tens of
    milliseconds where later ones take under one, and its error is
    bounded by its round trip. So a sample whose round trip is under a
    quarter of the seed's replaces the estimate, and is the new seed,
    instead of being averaged into an error many times its own."""

    __slots__ = ('alpha', '_offset', '_rtt', '_best_rtt', '_seed_rtt',
                 'samples')

    def __init__(self, alpha=0.25):
        self.alpha = float(alpha)
        self._offset = None
        self._rtt = None
        self._best_rtt = None
        self._seed_rtt = None
        self.samples = 0

    def update(self, t0, t1, t2, t3):
        """Fold in one exchange; returns the smoothed offset."""
        offset = ((t1 - t0) + (t2 - t3)) / 2.0
        rtt = max(0.0, (t3 - t0) - (t2 - t1))
        self.samples += 1
        self._rtt = rtt
        if self._best_rtt is None or rtt < self._best_rtt:
            self._best_rtt = rtt
        if self._offset is None or rtt < 0.25 * self._seed_rtt:
            self._offset = offset
            self._seed_rtt = rtt
        else:
            a = self.alpha
            if self._best_rtt > 0 and rtt > 4.0 * self._best_rtt:
                a *= self._best_rtt / rtt
            self._offset += a * (offset - self._offset)
        return self._offset

    def offset(self):
        """Smoothed remote−local offset in seconds (None before the
        first sample)."""
        return self._offset

    def rtt(self):
        """Round-trip time of the LAST exchange in seconds."""
        return self._rtt


class FleetFederation(object):
    """Controller-side scrape-and-merge over registered replicas."""

    def __init__(self):
        self._lock = threading.Lock()
        self._replicas = {}      # name -> replica handle (duck-typed)
        self._scrapes = {}       # name -> last successful scrape record
        self._errors = {}        # name -> consecutive scrape failures
        self._thread = None
        self._stop = None

    # -------------------------------------------------------- membership
    def register(self, replica, name=None):
        """Track ``replica`` (anything with a ``.url`` diagnostics
        address; ``.clock_offset()`` / ``.postmortem()`` picked up when
        present). Returns the registered name."""
        name = str(name if name is not None
                   else getattr(replica, 'name', None) or id(replica))
        with self._lock:
            self._replicas[name] = replica
        return name

    def unregister(self, name):
        with self._lock:
            self._replicas.pop(str(name), None)
            self._scrapes.pop(str(name), None)
            self._errors.pop(str(name), None)

    def replicas(self):
        with self._lock:
            return dict(self._replicas)

    def clear(self):
        """Drop every replica and scrape (test isolation); stops the
        poll thread first."""
        self.stop_polling()
        with self._lock:
            self._replicas = {}
            self._scrapes = {}
            self._errors = {}

    # ----------------------------------------------------------- scraping
    def poll_once(self, timeout_s=5.0):
        """Scrape every registered replica's /varz once (synchronous);
        returns the number of successful scrapes. A replica that fails
        to answer keeps its LAST successful snapshot (age visible in
        the /fleetz doc) — a dying replica's final numbers are exactly
        the ones worth reading."""
        ok = 0
        for name, rep in sorted(self.replicas().items()):
            url = getattr(rep, 'url', None)
            if not url:
                continue
            try:
                raw = http_get_json(url.rstrip('/') + '/varz',
                                    timeout=timeout_s)
            except Exception:
                with self._lock:
                    self._errors[name] = self._errors.get(name, 0) + 1
                _obs().inc('fleet.scrape_errors_total', replica=name)
                continue
            off = None
            fn = getattr(rep, 'clock_offset', None)
            if callable(fn):
                try:
                    off = fn()
                except Exception:
                    off = None
            host = str(raw.get('host', ''))
            with self._lock:
                self._errors[name] = 0
                self._scrapes[name] = {
                    'url': url, 'host': host, 'ts': time.time(),
                    'raw': raw, 'clock_offset_s': off,
                    'labeled': relabel_snapshot(raw, replica=name,
                                                host=host)}
            if off is not None:
                _obs().set_gauge('rpc.clock_offset_seconds', off,
                                 replica=name)
            ok += 1
        _obs().set_gauge('fleet.replicas_scraped', ok)
        return ok

    def scrapes(self):
        with self._lock:
            return dict(self._scrapes)

    # ------------------------------------------------------------ merging
    def merged_snapshot(self, include_local=True):
        """One Registry.snapshot()-shaped dict over the whole fleet:
        every replica's last scrape re-labeled ``{replica, host}``,
        plus (by default) the local process's own registry labeled
        ``replica=controller`` — ready for prometheus_exposition."""
        out = {'counters': {}, 'gauges': {}, 'histograms': {}}
        if include_local:
            snap = _obs().snapshot()
            local = relabel_snapshot(snap, replica='controller',
                                     host=str(snap.get('host', '')))
            for kind in out:
                out[kind].update(local.get(kind) or {})
        for name, sc in sorted(self.scrapes().items()):
            for kind in out:
                out[kind].update(sc['labeled'].get(kind) or {})
        return out

    def fleet_doc(self):
        """The /fleetz payload: per-replica scrape health (age, clock
        offset, consecutive errors), the merged snapshot, and the
        SLO module's fleet-derived panels (queue-depth skew, handoff
        bytes/s, cross-replica p99 spread)."""
        from . import slo
        now = time.time()
        with self._lock:
            reps = {}
            for name in sorted(self._replicas):
                sc = self._scrapes.get(name)
                reps[name] = {
                    'url': getattr(self._replicas[name], 'url', None),
                    'scraped': sc is not None,
                    'age_s': round(now - sc['ts'], 3) if sc else None,
                    'host': sc['host'] if sc else None,
                    'clock_offset_s':
                        sc['clock_offset_s'] if sc else None,
                    'consecutive_errors': self._errors.get(name, 0),
                }
            per_replica = {name: sc['raw']
                           for name, sc in self._scrapes.items()}
        return {'replicas': reps,
                'derived': slo.fleet_derived(per_replica),
                'merged': self.merged_snapshot()}

    # ----------------------------------------------------- trace assembly
    def federated_trace(self, trace_id, timeout_s=5.0):
        """Fan /tracez?trace_id= out to every registered replica, shift
        each replica's span timestamps onto the local clock by its
        estimated offset (``ts − offset·1e6`` µs), and return the spans
        merged with nothing dropped — the caller (diagnostics._tracez_doc)
        appends them to the local process's own matching spans. Replicas
        are queried with ``&local=1`` so a federating replica answers
        from its own recorder only."""
        merged = []
        sources = {}
        for name, rep in sorted(self.replicas().items()):
            url = getattr(rep, 'url', None)
            if not url:
                continue
            try:
                doc = http_get_json(
                    '%s/tracez?trace_id=%s&local=1'
                    % (url.rstrip('/'), trace_id), timeout=timeout_s)
            except Exception:
                sources[name] = {'ok': False, 'spans': 0}
                continue
            off = None
            fn = getattr(rep, 'clock_offset', None)
            if callable(fn):
                try:
                    off = fn()
                except Exception:
                    off = None
            spans = doc.get('spans') or []
            shift = (off or 0.0) * 1e6
            for e in spans:
                e = dict(e)
                if 'ts' in e:
                    e['ts'] = e['ts'] - shift
                args = dict(e.get('args') or {})
                args['replica'] = name
                e['args'] = args
                merged.append(e)
            sources[name] = {'ok': True, 'spans': len(spans),
                             'clock_offset_s': off}
        merged.sort(key=lambda e: e.get('ts', 0.0))
        return {'spans': merged, 'sources': sources}

    # --------------------------------------------------------- poll thread
    def start_polling(self, interval_s=None):
        """Start the background scrape thread (idempotent). The
        interval is re-read from PADDLE_TPU_FLEET_POLL_S every cycle
        when not pinned by ``interval_s``."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = threading.Event()
            stop = self._stop

        def loop():
            while not stop.wait(poll_interval() if interval_s is None
                                else interval_s):
                try:
                    self.poll_once()
                except Exception:
                    pass             # scrape trouble must not kill the loop
        t = threading.Thread(target=loop, daemon=True,
                             name='paddle_tpu_fleet_poll')
        with self._lock:
            self._thread = t
        t.start()

    def stop_polling(self):
        with self._lock:
            stop, self._stop = self._stop, None
            t, self._thread = self._thread, None
        if stop is not None:
            stop.set()
        if t is not None:
            t.join(timeout=5)


_fleet_lock = threading.Lock()
_fleet = None


def fleet():
    """The process-wide FleetFederation (created on first use)."""
    global _fleet
    with _fleet_lock:
        if _fleet is None:
            _fleet = FleetFederation()
        return _fleet
