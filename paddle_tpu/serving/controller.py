"""Fleet controller: SLO-driven replica lifecycle as a closed loop.

PR 8 made replica cold-start ~0.1s (the AOT executable cache) and the
SLO layer gave the router burn-rate / predicted-p99 / queue-depth
signals — this module closes the loop. ``FleetController`` owns the
lifecycle of every replica behind a ``Router`` and turns the static
replica list into a self-healing, autoscaling fleet:

- **scale out** — when the route's error-budget burn rate, predicted
  p99, or aggregate queue depth cross their thresholds, spawn a fresh
  replica via the pluggable ``ReplicaFactory``. The factory rides the
  AOT executable cache (a warmed cache makes ``warmup()`` deserialize
  instead of compile), so scale-up lands in ~0.1s — fast enough to
  beat a flash crowd to the error budget. The replica is registered
  with the router only after ``ready()`` is True: traffic never lands
  on a cold replica.
- **scale in** — on a sustained trough (every pressure signal low for
  ``trough_s``), pick the least-loaded replica, deregister it from the
  router (no new work from that instant), ``drain()`` every accepted
  request to completion, THEN ``shutdown()`` — zero request loss by
  construction, asserted by the trough chaos scenario
  (tests/test_autoscale.py).
- **self-heal** — a replica whose ``ready()`` flips or that dies
  mid-flight is detected on the next tick, deregistered, and replaced
  automatically. Restarts back off exponentially per lineage
  (``backoff_base_s * 2^restarts``, capped), and a **crash-loop
  circuit breaker** quarantines a lineage that keeps dying
  (``crash_loop_threshold`` deaths inside ``crash_window_s``): a
  ``controller_quarantine`` flight event + counter fire and the slot
  stays down for ``quarantine_s`` instead of thrashing the fleet with
  doomed restarts.

Each replica walks a small state machine, visible on the ``/statusz``
``fleet`` panel and as ``controller.replica_state`` gauges::

    UP ──(trough)──> DRAINING ──> retired        (scale-in, zero loss)
    UP ──(died/unready)──> DEAD ──(backoff)──> replaced (new UP)
    DEAD ──(crash loop)──> QUARANTINED ──(quarantine_s)──> replaced

The loop runs on a daemon thread (``start()``/``close()``), but every
decision lives in ``step(now=)`` so tests drive it deterministically
on a synthetic clock. All tunables are constructor arguments with
``PADDLE_TPU_AUTOSCALE*`` env overrides read PER CALL inside
``step()`` — never at import time (tools/repo_lint.py enforces this
module).
"""

import itertools
import os
import threading
import time

from .. import observe as _obs

__all__ = ['FleetController', 'ReplicaFactory', 'ttft_pressure',
           'page_pressure', 'UP', 'DRAINING', 'QUARANTINED', 'DEAD']

# replica state machine (the /statusz fleet panel renders these; the
# numeric codes are what the controller.replica_state gauge carries)
UP = 'UP'
DRAINING = 'DRAINING'
QUARANTINED = 'QUARANTINED'
DEAD = 'DEAD'
STATE_CODES = {UP: 0, DRAINING: 1, QUARANTINED: 2, DEAD: 3}
STATE_NAMES = {v: k for k, v in STATE_CODES.items()}

_CONTROLLER_IDS = itertools.count(1)


def _env_float(name, default):
    """Env override for one knob, read per call (never import time)."""
    raw = os.environ.get(name)
    if raw in (None, ''):
        return default
    try:
        return float(raw)
    except ValueError:
        return default


class ReplicaFactory(object):
    """Spawns one replica per call: anything with
    ``create(name) -> replica`` fits; a plain callable
    ``factory(name) -> replica`` is adapted automatically.

    The returned replica must quack like a ``ServingEngine``:
    ``ready()``, ``queue_depth()``, ``submit(feed, ctx=)``,
    ``drain(timeout=)``, ``shutdown(drain=)``, and optionally
    ``warmup()``/``start()`` (called by the controller when the
    replica comes back not-ready — a factory may also hand over an
    already-serving replica)."""

    def __init__(self, fn):
        self._fn = fn

    def create(self, name):
        return self._fn(name)

    @staticmethod
    def adapt(factory):
        if hasattr(factory, 'create'):
            return factory
        if callable(factory):
            return ReplicaFactory(factory)
        raise TypeError('factory must be callable or expose '
                        '.create(name), got %r' % (factory,))


def ttft_pressure(phase_router, budget_s, high=1.0, low=0.5):
    """Per-phase scaling policy for the PREFILL pool of a
    :class:`~paddle_tpu.serving.router.PhaseRouter`: pressure when the
    rolling TTFT attribution (prefill phase + handoff p95) burns past
    ``high`` x ``budget_s``, calm below ``low`` x ``budget_s``.
    Returns ``(pressure_fn, calm_fn)`` for ``FleetController(
    router=pr.pool('prefill'), pressure_fn=..., calm_fn=...)`` —
    prefill replicas are compute-bound, so the signal that matters is
    how long prompts wait for FLOPs, not page occupancy."""
    budget_s = float(budget_s)

    def pressure_fn(now):
        p95 = phase_router.prefill_phase_p95()
        signals = {'ttft_p95': p95, 'ttft_budget': budget_s,
                   'mean_queue_depth': 0.0, 'burn_rate': None}
        if p95 is not None and p95 > high * budget_s:
            return True, 'ttft_burn', signals
        return False, None, signals

    def calm_fn(signals):
        p95 = signals.get('ttft_p95')
        return p95 is None or p95 < low * budget_s

    return pressure_fn, calm_fn


def page_pressure(phase_router, free_low=0.15, free_high=0.5):
    """Per-phase scaling policy for the DECODE pool: pressure when the
    most page-starved ready decode replica's free-page fraction drops
    below ``free_low``, calm once every replica is back above
    ``free_high``. Decode replicas are HBM-bound — KV pages, not
    FLOPs, are the resource that runs out (each handoff lands a whole
    page group at once, so allocator pressure is a fleet signal, not a
    replica detail)."""

    def pressure_fn(now):
        frac = phase_router.decode_free_page_frac()
        signals = {'free_page_frac': frac, 'mean_queue_depth': 0.0,
                   'burn_rate': None}
        if frac is not None and frac < free_low:
            return True, 'page_pressure', signals
        return False, None, signals

    def calm_fn(signals):
        frac = signals.get('free_page_frac')
        return frac is None or frac > free_high

    return pressure_fn, calm_fn


class _Lineage(object):
    """Crash history of one replica slot across restarts. The fleet
    heals by lineage: replica0 dies -> replica0-r1 spawns carrying
    replica0's death ledger, so a crash LOOP (the same slot dying
    again and again) is visible no matter how often the engine object
    underneath is replaced."""

    __slots__ = ('base', 'deaths', 'restarts', 'next_restart_at',
                 'quarantined_until', 'pending_heal', 'last_postmortem')

    def __init__(self, base):
        self.base = base
        self.deaths = []            # timestamps (controller clock)
        self.restarts = 0
        self.next_restart_at = 0.0
        self.quarantined_until = None
        self.pending_heal = False
        # the dead replica's last flight-recorder dump (pulled at
        # death, attached to the heal event) — its final seconds
        self.last_postmortem = None


class _Record(object):
    """One live (or recently dead) replica the controller manages."""

    __slots__ = ('name', 'replica', 'state', 'lineage', 'spawned_at')

    def __init__(self, name, replica, lineage, spawned_at):
        self.name = name
        self.replica = replica
        self.state = UP
        self.lineage = lineage
        self.spawned_at = spawned_at


class FleetController(object):
    """Replica-lifecycle control loop over a ``Router``.

    ::

        router = Router(engines, slo=tracker, route='serve', hedge=True)
        ctl = FleetController(router, factory=make_engine, slo=tracker,
                              min_replicas=2, max_replicas=6)
        ctl.start()                      # ticks every interval_s
        ...
        ctl.close()                      # stop the loop; fleet stays up

    Scale-out pressure is ANY of: ``burn_rate > burn_high``,
    ``predicted_p99 > latency budget``, or mean ready-replica queue
    depth ``> queue_high``. Scale-in requires ALL pressure signals low
    for ``trough_s`` seconds. Both honor cooldowns so one spike never
    see-saws the fleet. Env overrides (read per step):

    - ``PADDLE_TPU_AUTOSCALE_MIN`` / ``PADDLE_TPU_AUTOSCALE_MAX``
    - ``PADDLE_TPU_AUTOSCALE_BURN_HIGH`` / ``_BURN_LOW``
    - ``PADDLE_TPU_AUTOSCALE_QUEUE_HIGH`` / ``_QUEUE_LOW``
    - ``PADDLE_TPU_AUTOSCALE_TROUGH_S``
    - ``PADDLE_TPU_AUTOSCALE_BACKOFF_BASE_S``
    - ``PADDLE_TPU_AUTOSCALE_QUARANTINE_S``
    """

    def __init__(self, router, factory, slo=None, route=None,
                 min_replicas=1, max_replicas=8, interval_s=0.25,
                 burn_high=1.0, burn_low=0.25, queue_high=6.0,
                 queue_low=1.0, scale_out_cooldown_s=1.0,
                 scale_in_cooldown_s=2.0, trough_s=3.0, scale_step=1,
                 backoff_base_s=0.25, backoff_max_s=8.0,
                 crash_loop_threshold=3, crash_window_s=10.0,
                 quarantine_s=30.0, drain_timeout_s=30.0,
                 name_prefix='auto', pressure_fn=None, calm_fn=None):
        self.router = router
        # pluggable pressure: a phase-split fleet scales each pool on
        # its own physics — ``ttft_pressure`` (prefill, compute-bound)
        # and ``page_pressure`` (decode, HBM-bound) build the
        # (pressure_fn, calm_fn) pair; None keeps the SLO/queue-depth
        # policy below
        self.pressure_fn = pressure_fn
        self.calm_fn = calm_fn
        self.factory = ReplicaFactory.adapt(factory)
        self._slo = slo if slo is not None else getattr(router, '_slo',
                                                        None)
        self.route = str(route) if route else getattr(router, 'route',
                                                      'serve')
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.interval_s = float(interval_s)
        self.burn_high = float(burn_high)
        self.burn_low = float(burn_low)
        self.queue_high = float(queue_high)
        self.queue_low = float(queue_low)
        self.scale_out_cooldown_s = float(scale_out_cooldown_s)
        self.scale_in_cooldown_s = float(scale_in_cooldown_s)
        self.trough_s = float(trough_s)
        self.scale_step = int(scale_step)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.crash_loop_threshold = int(crash_loop_threshold)
        self.crash_window_s = float(crash_window_s)
        self.quarantine_s = float(quarantine_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.name_prefix = str(name_prefix)
        self._ids = itertools.count(1)
        self._mu = threading.RLock()
        self._records = {}            # name -> _Record (managed fleet)
        self._lineages = {}           # base -> _Lineage
        self._last_scale_out = None
        self._last_scale_in = None
        self._trough_since = None
        self._stop = threading.Event()
        self._thread = None
        self._cid = next(_CONTROLLER_IDS)
        # adopt the router's current fleet: each existing replica is
        # its own lineage, healed/retired like any spawned one
        now = time.perf_counter()
        for name, replica in router.replicas():
            lin = self._lineages.setdefault(name, _Lineage(name))
            self._records[name] = _Record(name, replica, lin, now)
        self._publish(now)

    # ---------------------------------------------------------- lifecycle
    def start(self):
        """Run ``step()`` every ``interval_s`` on a daemon thread
        (idempotent)."""
        with self._mu:
            if self._thread is not None:
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name='paddle_tpu_fleet_controller%d' % self._cid)
            self._thread.start()
        return self

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.step()
            except Exception:
                # a crashing tick must never take the fleet down; the
                # counter makes the crash visible instead of silent
                _obs.inc('controller.step_errors_total',
                         route=self.route)

    def close(self, shutdown_replicas=False):
        """Stop the control loop. ``shutdown_replicas=True`` also
        drains and retires every managed replica (tests/benches)."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        self._thread = None
        if shutdown_replicas:
            for rec in list(self._records.values()):
                if rec.state in (UP, DRAINING):
                    try:
                        self.router.remove_replica(rec.name)
                    except KeyError:
                        pass
                    try:
                        rec.replica.shutdown(drain=True)
                    except Exception:
                        pass
                    rec.state = DEAD

    # -------------------------------------------------------- inspection
    def census(self):
        """{state: count} over managed replicas (quarantined lineages
        count as QUARANTINED even though no engine object exists)."""
        with self._mu:
            counts = {UP: 0, DRAINING: 0, QUARANTINED: 0, DEAD: 0}
            for rec in self._records.values():
                counts[rec.state] += 1
            return counts

    def states(self):
        """{replica_name: state} — the /statusz fleet panel's rows."""
        with self._mu:
            return {name: rec.state
                    for name, rec in self._records.items()}

    def current(self, base):
        """The live replica object of lineage ``base`` (None when the
        slot is dead or quarantined) — the crash-loop chaos harness's
        way of aiming repeated kills at one slot across restarts."""
        with self._mu:
            for rec in self._records.values():
                if rec.lineage.base == base and rec.state == UP:
                    return rec.replica
        return None

    # -------------------------------------------------------------- tick
    def step(self, now=None):
        """One control tick: census -> heal -> scale. ``now`` defaults
        to the real clock; tests pass a synthetic one (every cooldown,
        backoff, trough, and quarantine window keys off it)."""
        now = time.perf_counter() if now is None else now
        with self._mu:
            self._census_tick(now)
            self._heal_tick(now)
            self._scale_tick(now)
            self._publish(now)

    # census: notice deaths and stable survivors ------------------------
    def _census_tick(self, now):
        for rec in list(self._records.values()):
            if rec.state != UP:
                continue
            if rec.replica.ready():
                # a replica that survived a full crash window clears
                # its lineage's ledger — old deaths stop counting
                # toward the breaker and backoff resets
                lin = rec.lineage
                if lin.restarts and \
                        now - rec.spawned_at > self.crash_window_s:
                    lin.restarts = 0
                    lin.deaths = [t for t in lin.deaths
                                  if now - t <= self.crash_window_s]
                continue
            self._mark_dead(rec, now)

    def _mark_dead(self, rec, now):
        """An UP replica's ready() flipped: health-check failure, an
        external kill, or a mid-flight death. Deregister it (in-flight
        requests fail typed; the router's failover already re-ran
        them) and queue the lineage for healing."""
        rec.state = DEAD
        lin = rec.lineage
        lin.deaths.append(now)
        lin.pending_heal = True
        backoff = min(self.backoff_max_s,
                      _env_float('PADDLE_TPU_AUTOSCALE_BACKOFF_BASE_S',
                                 self.backoff_base_s)
                      * (2.0 ** lin.restarts))
        lin.next_restart_at = now + backoff
        # postmortem aggregation: pull the dead replica's last flight
        # dump NOW (a SIGTERMed worker dumped on the way down; a
        # SIGKILLed one left its last heartbeat snapshot) and stash it
        # on the lineage — the heal event carries it forward
        pm = None
        pm_fn = getattr(rec.replica, 'postmortem', None)
        if callable(pm_fn):
            try:
                pm = pm_fn()
            except Exception:
                pm = None
        if pm is not None:
            lin.last_postmortem = pm
            _obs.inc('controller.postmortems_total', route=self.route,
                     lineage=lin.base)
        _obs.inc('controller.deaths_total', route=self.route,
                 replica=rec.name)
        _obs.flight_event('controller_replica_dead', replica=rec.name,
                          lineage=lin.base, route=self.route,
                          restarts=lin.restarts,
                          backoff_s=round(backoff, 4),
                          postmortem_reason=(pm or {}).get('reason'),
                          postmortem_events=len((pm or {})
                                                .get('events') or []))
        try:
            self.router.remove_replica(rec.name)
        except KeyError:
            pass                     # already deregistered (scale-in race)
        try:
            rec.replica.shutdown(drain=False)
        except Exception:
            pass                     # a corpse that won't die politely

    # heal: replace dead slots, quarantine crash loops ------------------
    def _heal_tick(self, now):
        quarantine_s = _env_float('PADDLE_TPU_AUTOSCALE_QUARANTINE_S',
                                  self.quarantine_s)
        for lin in self._lineages.values():
            if not lin.pending_heal:
                continue
            if lin.quarantined_until is not None:
                if now < lin.quarantined_until:
                    continue
                # quarantine served: one fresh chance, clean ledger
                lin.quarantined_until = None
                lin.deaths = []
                lin.restarts = 0
                lin.next_restart_at = now
                self._drop_quarantine_marker(lin)
            recent = [t for t in lin.deaths
                      if now - t <= self.crash_window_s]
            if len(recent) >= self.crash_loop_threshold:
                self._quarantine(lin, now, quarantine_s, len(recent))
                continue
            if now < lin.next_restart_at:
                continue
            if self._ready_count() >= self._max(now):
                continue             # the fleet healed around this slot
            lin.restarts += 1
            if self._spawn(lin, now, reason='heal') is not None:
                lin.pending_heal = False
                self._drop_dead_records(lin)
                _obs.inc('controller.heals_total', route=self.route,
                         lineage=lin.base)
                # the heal event carries the dead predecessor's final
                # seconds: reason + last ring events from the pulled
                # postmortem (chaos suites assert this linkage)
                pm, lin.last_postmortem = lin.last_postmortem, None
                _obs.flight_event(
                    'controller_heal', lineage=lin.base,
                    route=self.route, restarts=lin.restarts,
                    postmortem_reason=(pm or {}).get('reason'),
                    postmortem_pid=(pm or {}).get('pid'),
                    postmortem_events=len((pm or {})
                                          .get('events') or []),
                    postmortem_last_kinds=[
                        e.get('kind') for e in
                        ((pm or {}).get('events') or [])[-5:]])

    def _drop_dead_records(self, lin):
        """Forget a lineage's dead predecessors once a replacement is
        up (or the slot is benched) — the census shows live state, the
        flight ring keeps the history."""
        for name in [n for n, rec in self._records.items()
                     if rec.lineage is lin and rec.state == DEAD]:
            del self._records[name]

    def _quarantine(self, lin, now, quarantine_s, recent_deaths):
        if lin.quarantined_until is not None:
            return                   # already benched
        lin.quarantined_until = now + quarantine_s
        self._drop_dead_records(lin)
        # a census marker so the fleet panel shows the benched slot
        marker = '%s[quarantined]' % lin.base
        rec = _Record(marker, None, lin, now)
        rec.state = QUARANTINED
        self._records[marker] = rec
        _obs.inc('controller.quarantines_total', route=self.route,
                 lineage=lin.base)
        _obs.flight_event('controller_quarantine', lineage=lin.base,
                          route=self.route, deaths=recent_deaths,
                          window_s=self.crash_window_s,
                          until_s=round(quarantine_s, 3))

    def _drop_quarantine_marker(self, lin):
        self._records.pop('%s[quarantined]' % lin.base, None)

    # scale: pressure up, sustained trough down -------------------------
    def _pressure(self, now):
        """(pressured, reason, signals) — ANY high signal pressures."""
        if self.pressure_fn is not None:
            return self.pressure_fn(now)
        burn_high = _env_float('PADDLE_TPU_AUTOSCALE_BURN_HIGH',
                               self.burn_high)
        queue_high = _env_float('PADDLE_TPU_AUTOSCALE_QUEUE_HIGH',
                                self.queue_high)
        burn = p99 = budget = None
        if self._slo is not None:
            try:
                # the tick's clock flows into the tracker so a test
                # driving step(now=synthetic) reads a consistent window
                burn = self._slo.burn_rate(self.route, now=now)
                p99 = self._slo.predicted_p99(self.route, now=now)
                budget = self._slo.objective(
                    self.route).latency_budget_s
            except KeyError:
                pass                 # route not tracked: queue-only
        depths = [rec.replica.queue_depth()
                  for rec in self._records.values()
                  if rec.state == UP and rec.replica.ready()]
        mean_depth = (sum(depths) / len(depths)) if depths else 0.0
        signals = {'burn_rate': burn, 'predicted_p99': p99,
                   'latency_budget': budget, 'mean_queue_depth':
                   round(mean_depth, 3)}
        if burn is not None and burn > burn_high:
            return True, 'burn_rate', signals
        if p99 is not None and budget is not None and p99 > budget:
            return True, 'predicted_p99', signals
        if mean_depth > queue_high:
            return True, 'queue_depth', signals
        return False, None, signals

    def _calm(self, signals):
        if self.calm_fn is not None:
            return self.calm_fn(signals)
        burn_low = _env_float('PADDLE_TPU_AUTOSCALE_BURN_LOW',
                              self.burn_low)
        queue_low = _env_float('PADDLE_TPU_AUTOSCALE_QUEUE_LOW',
                               self.queue_low)
        burn = signals['burn_rate']
        return ((burn is None or burn < burn_low)
                and signals['mean_queue_depth'] < queue_low)

    def _ready_count(self):
        return sum(1 for rec in self._records.values()
                   if rec.state == UP and rec.replica.ready())

    def _min(self, now):
        return int(_env_float('PADDLE_TPU_AUTOSCALE_MIN',
                              self.min_replicas))

    def _max(self, now):
        return int(_env_float('PADDLE_TPU_AUTOSCALE_MAX',
                              self.max_replicas))

    def _scale_tick(self, now):
        pressured, reason, signals = self._pressure(now)
        _obs.set_gauge('controller.fleet_pressure', int(pressured),
                       route=self.route)
        ready = self._ready_count()
        if pressured:
            self._trough_since = None
            in_cooldown = (self._last_scale_out is not None and
                           now - self._last_scale_out
                           < self.scale_out_cooldown_s)
            if ready >= self._max(now) or in_cooldown:
                return
            self._last_scale_out = now
            for _ in range(self.scale_step):
                if self._ready_count() >= self._max(now):
                    break
                base = '%s%d' % (self.name_prefix, next(self._ids))
                lin = self._lineages.setdefault(base, _Lineage(base))
                if self._spawn(lin, now, reason=reason) is not None:
                    _obs.inc('controller.scale_out_total',
                             route=self.route, reason=reason)
                    _obs.flight_event('controller_scale_out',
                                      route=self.route, reason=reason,
                                      **{k: v for k, v in
                                         signals.items()
                                         if v is not None})
            return
        if not self._calm(signals):
            self._trough_since = None
            return
        trough_s = _env_float('PADDLE_TPU_AUTOSCALE_TROUGH_S',
                              self.trough_s)
        if self._trough_since is None:
            self._trough_since = now
        if now - self._trough_since < trough_s:
            return
        if ready <= self._min(now):
            return
        if self._last_scale_in is not None and \
                now - self._last_scale_in < self.scale_in_cooldown_s:
            return
        self._last_scale_in = now
        self._scale_in_one(now, signals)

    def _scale_in_one(self, now, signals):
        """Retire the least-loaded UP replica: deregister from routing
        (no new work), drain every accepted request, then shut down —
        the zero-request-loss sequence the trough scenario asserts."""
        ups = [rec for rec in self._records.values()
               if rec.state == UP and rec.replica.ready()]
        if not ups:
            return
        victim = min(ups, key=lambda rec: rec.replica.queue_depth())
        victim.state = DRAINING
        self._publish(now)           # the DRAINING window is visible
        try:
            self.router.remove_replica(victim.name)
        except KeyError:
            pass
        _obs.flight_event('controller_scale_in', replica=victim.name,
                          route=self.route,
                          queue_depth=victim.replica.queue_depth())
        t0 = time.perf_counter()
        try:
            drained = victim.replica.drain(timeout=self.drain_timeout_s)
            victim.replica.shutdown(drain=True)
        except Exception:
            drained = False
        _obs.inc('controller.scale_in_total', route=self.route)
        _obs.record('controller.drain_seconds',
                    time.perf_counter() - t0, route=self.route)
        if not drained:
            _obs.inc('controller.drain_timeouts_total',
                     route=self.route)
        # the retired slot's last visible state: gauges cannot be
        # deleted, so the per-replica state pins at DEAD (= gone)
        _obs.set_gauge('controller.replica_state', STATE_CODES[DEAD],
                       replica=victim.name, route=self.route)
        del self._records[victim.name]
        self._lineages.pop(victim.lineage.base, None)

    # spawn -------------------------------------------------------------
    def _spawn(self, lin, now, reason):
        """Create, warm, start, and register one replica of lineage
        ``lin``. Returns the record, or None when the factory or
        warmup failed (counted; the lineage stays pending with its
        death ledger grown, so a broken factory crash-loops into
        quarantine instead of spinning forever)."""
        name = lin.base if lin.restarts == 0 and \
            lin.base not in self._records else \
            '%s-r%d' % (lin.base, lin.restarts)
        t0 = time.perf_counter()
        try:
            replica = self.factory.create(name)
            if not replica.ready():
                warm = getattr(replica, 'warmup', None)
                if callable(warm):
                    warm()
                st = getattr(replica, 'start', None)
                if callable(st):
                    st()
            if not replica.ready():
                raise RuntimeError('factory produced a replica that '
                                   'never became ready()')
            self.router.add_replica(replica, name=name)
        except Exception as e:
            _obs.inc('controller.spawn_failures_total',
                     route=self.route, lineage=lin.base)
            _obs.flight_event('controller_spawn_failed',
                              lineage=lin.base, route=self.route,
                              error=type(e).__name__)
            lin.deaths.append(now)
            lin.pending_heal = True
            lin.next_restart_at = now + min(
                self.backoff_max_s,
                self.backoff_base_s * (2.0 ** lin.restarts))
            return None
        spawn_s = time.perf_counter() - t0
        rec = _Record(name, replica, lin, now)
        self._records[name] = rec
        _obs.inc('controller.spawns_total', route=self.route,
                 reason=reason)
        _obs.record('controller.spawn_seconds', spawn_s,
                    route=self.route, reason=reason)
        _obs.flight_event('controller_spawn', replica=name,
                          lineage=lin.base, route=self.route,
                          reason=reason, seconds=round(spawn_s, 4))
        return rec

    # observe -----------------------------------------------------------
    def _publish(self, now):
        counts = {UP: 0, DRAINING: 0, QUARANTINED: 0, DEAD: 0}
        for rec in self._records.values():
            counts[rec.state] += 1
            _obs.set_gauge('controller.replica_state',
                           STATE_CODES[rec.state], replica=rec.name,
                           route=self.route)
        for state, n in counts.items():
            _obs.set_gauge('controller.replicas', n,
                           state=state.lower(), route=self.route)
        _obs.set_gauge('controller.replicas_ready', self._ready_count(),
                       route=self.route)
