"""Multi-replica serving router: least-loaded + session-affinity
dispatch, dynamic membership, hedged requests under a retry budget,
retry-on-replica-down, and SLO-aware admission.

One ``ServingEngine`` is a single replica; this router fronts N of
them (any objects with ``submit(feed, ctx=)``, ``ready()``,
``queue_depth()`` and a ``name`` — the decode engine's facade fits the
same shape for token workloads) and makes the fleet behave like one
endpoint:

- **placement** — requests go to the *ready* replica with the
  shallowest admission queue (each engine's ``ready()`` +
  ``queue_depth()``, the same numbers its /readyz check and
  ``serving.queue_depth`` gauge export). A ``session`` key pins a
  client to a preferred replica (rendezvous hash, so membership
  changes only reassign sessions touching the changed replica) while
  it stays ready — cache/affinity wins without giving up failover.
  Replicas
  that are not ready — including one whose drain/shutdown has begun —
  are never candidates.
- **dynamic membership** — ``add_replica``/``remove_replica`` mutate
  the fleet under the router's lock, so a fleet controller
  (``serving.controller``) can spawn and retire replicas while
  traffic flows: a removed replica takes no new work (in-flight
  requests on it still complete; its drain happens outside the
  router), a freshly added one joins the candidate ranking on the
  next submit.
- **failover** — a replica that dies mid-request fails that request
  with ``EngineClosedError``; the router catches exactly that (it
  means "replica gone", never "bad request") and resubmits to another
  replica, up to ``retries`` times, spending one retry-budget token
  per resubmission. A replica that is full at submit time is skipped
  for the next-least-loaded one. Accepted requests therefore either
  complete or fail with a typed error — never hang.
- **hedged requests** — with ``hedge=True``, a request whose elapsed
  time passes the route's rolling p95 (``slo.predicted_quantile``, or
  the explicit ``hedge_delay_s`` floor) while deadline budget remains
  gets a second dispatch to an *untried* replica; first completion
  wins, the loser is cancelled/ignored. When both complete, their
  results are compared — ``router.hedge_mismatch_total`` stays 0 for
  a deterministic model, the bit-identity contract the autoscale
  chaos scenarios assert.
- **retry budget** — hedges and failovers share one token bucket that
  refills at ``retry_budget`` tokens per accepted request (burst
  ``retry_budget_burst``), so retries are capped at a small fraction
  of traffic and can never amplify an overload: when the bucket is
  empty, hedges are suppressed and failovers surface their error
  instead of resubmitting.
- **SLO-aware admission** — with an ``observe.slo.SloTracker``
  attached, each submit compares the route's rolling predicted p99
  against the request's remaining deadline budget (or the route's
  latency budget): when the fleet is predicted to blow the budget the
  router *sheds* (``SLOShedError``, a ``QueueFullError`` subclass so
  existing backpressure handling just works) or *degrades* (admits
  but tags the request context) instead of queueing doomed work. A
  request whose deadline is already exhausted is shed synchronously
  before any dispatch or hedge token is spent.

Every decision is observable: ``router.*`` counters/gauges (dispatch
per replica, hedges/wins/mismatches, retry-budget tokens, sheds by
reason, replicas ready), flight events for failover and shedding, and
per-request trace events on sampled ``RequestContext``s. No
environment reads at import time (tools/repo_lint.py enforces this
module).
"""

import itertools
import os
import threading
import time
import zlib

from concurrent.futures import Future

from .. import observe as _obs
from ..observe import reqtrace as _reqtrace
from .engine import EngineClosedError, QueueFullError

__all__ = ['Router', 'PhaseRouter', 'NoReplicaAvailableError',
           'SLOShedError']

_ROUTER_IDS = itertools.count(1)


class NoReplicaAvailableError(RuntimeError):
    """Every replica is down or not ready — the fleet cannot accept
    this request at all (distinct from QueueFullError: full is
    transient backpressure, this is an availability incident)."""


class SLOShedError(QueueFullError):
    """Admission control shed this request: the route's predicted p99
    exceeds its remaining latency budget, or the deadline budget was
    already exhausted at submit. A QueueFullError subclass so callers'
    existing reject/backoff handling applies unchanged."""


class _RetryBudget(object):
    """Token bucket shared by hedges and failovers: each accepted
    request deposits ``ratio`` tokens (capped at ``burst``), each
    hedge or failover dispatch spends 1.0 — so retry traffic is
    bounded by ratio x accepted + burst, by construction."""

    __slots__ = ('ratio', 'burst', 'tokens', '_mu')

    def __init__(self, ratio, burst):
        self.ratio = float(ratio)
        self.burst = float(burst)
        self.tokens = float(burst)
        self._mu = threading.Lock()

    def deposit(self):
        with self._mu:
            self.tokens = min(self.burst, self.tokens + self.ratio)
            return self.tokens

    def try_spend(self):
        with self._mu:
            if self.tokens >= 1.0:
                self.tokens -= 1.0
                return True
            return False

    def refund(self):
        with self._mu:
            self.tokens = min(self.burst, self.tokens + 1.0)


class _InFlight(object):
    """Per-request dispatch state: which replicas were tried, how many
    attempts are outstanding (primary + hedge + failovers), and the
    first-completion-wins settlement. All transitions under ``mu``."""

    __slots__ = ('feed', 'session', 'ctx', 'outer', 'tried', 'mu',
                 'settled', 'outstanding', 'first_result', 'have_result',
                 'stashed_exc', 'hedged', 'attempts_left', 'timer')

    def __init__(self, feed, session, ctx, outer, attempts_left):
        self.feed = feed
        self.session = session
        self.ctx = ctx
        self.outer = outer
        self.tried = set()
        self.mu = threading.Lock()
        self.settled = False
        self.outstanding = 0
        self.first_result = None
        self.have_result = False
        self.stashed_exc = None
        self.hedged = False
        self.attempts_left = attempts_left
        self.timer = None


def _arrays_equal(x, y):
    import numpy as np
    x, y = np.asarray(x), np.asarray(y)
    if (np.issubdtype(x.dtype, np.inexact)
            and np.issubdtype(y.dtype, np.inexact)):
        # NaN == NaN for this check: identical NaN-bearing outputs
        # (a model that emits NaNs, chaos poison_nans) are not a
        # determinism mismatch. equal_nan raises on non-float dtypes,
        # hence the guard.
        return np.array_equal(x, y, equal_nan=True)
    return np.array_equal(x, y)


def _results_equal(a, b):
    """Best-effort bit-identity check between two fetch lists — the
    hedging invariant (a hedge re-runs the SAME feed through the SAME
    model, so any divergence is a real determinism bug)."""
    try:
        if type(a) is not type(b):
            return False
        seq_a = a if isinstance(a, (list, tuple)) else [a]
        seq_b = b if isinstance(b, (list, tuple)) else [b]
        if len(seq_a) != len(seq_b):
            return False
        return all(_arrays_equal(x, y) for x, y in zip(seq_a, seq_b))
    except Exception:
        return True   # uncomparable payloads never count as a mismatch


class Router(object):
    """Least-loaded / session-affinity dispatch over a dynamic fleet
    of serving replicas.

    ::

        replicas = [ServingEngine(pred_i, name='replica%d' % i)
                    for i, pred_i in enumerate(preds)]
        tracker = SloTracker([Objective('serve', latency_budget_s=0.5)])
        router = Router(replicas, slo=tracker, route='serve',
                        hedge=True)
        fut = router.submit({'x': batch}, session='user-42')
        outs = router.predict({'x': batch})
        router.add_replica(new_engine)       # fleet controller's hooks
        old = router.remove_replica('replica0')
        router.close()        # unregisters health; replicas are yours

    ``admission``: 'slo' sheds/degrades on predicted-p99 breach (needs
    ``slo``), 'none' skips the check. ``on_breach``: 'shed' raises
    SLOShedError, 'degrade' admits but tags the request context and
    counts it. ``hedge=True`` needs either ``slo`` (rolling
    ``hedge_quantile`` delay) or an explicit ``hedge_delay_s``. The
    router owns no long-lived threads; completion hooks run on the
    replicas' dispatcher threads and hedge checks on short one-shot
    timers.
    """

    def __init__(self, replicas, slo=None, route='serve',
                 session_affinity=True, retries=2, admission=None,
                 on_breach='shed', hedge=False, hedge_quantile=0.95,
                 hedge_delay_s=None, hedge_min_delay_s=0.002,
                 retry_budget=0.1, retry_budget_burst=20.0,
                 tenants=None):
        reps = list(replicas)
        if not reps:
            raise ValueError('Router needs at least one replica')
        names = [getattr(r, 'name', None) or 'replica%d' % i
                 for i, r in enumerate(reps)]
        if len(set(names)) != len(names):
            raise ValueError('replica names must be unique, got %s'
                             % names)
        self._replicas = list(zip(names, reps))
        self.route = str(route)
        self._slo = slo
        if admission is None:
            admission = 'slo' if slo is not None else 'none'
        if admission == 'slo' and slo is None:
            raise ValueError("admission='slo' needs an SloTracker")
        if on_breach not in ('shed', 'degrade'):
            raise ValueError("on_breach must be 'shed' or 'degrade'")
        if hedge and slo is None and hedge_delay_s is None:
            raise ValueError('hedge=True needs an SloTracker (rolling '
                             'p95 delay) or an explicit hedge_delay_s')
        self.admission = admission
        self.on_breach = on_breach
        self.session_affinity = bool(session_affinity)
        self.retries = int(retries)
        self.hedge = bool(hedge)
        self.hedge_quantile = float(hedge_quantile)
        self.hedge_delay_s = hedge_delay_s
        self.hedge_min_delay_s = float(hedge_min_delay_s)
        # optional multi-tenant policy (serving.tenancy.TenantRegistry):
        # admission charges the session's tenant bucket before any
        # dispatch; None keeps the single-tenant behavior exactly
        self._tenants = tenants
        self._budget = _RetryBudget(retry_budget, retry_budget_burst)
        self._mu = threading.Lock()
        self._rr = itertools.count()    # tiebreak for equal depths
        self._closed = False
        self._health_name = 'serving.router%d' % next(_ROUTER_IDS)
        _obs.register_health_check(self._health_name, self._ready_check,
                                   readiness_only=True)
        _obs.set_gauge('router.replicas_total', len(reps))
        _obs.set_gauge('router.retry_budget_tokens', self._budget.tokens)

    # --------------------------------------------------------- lifecycle
    def ready(self):
        """True while at least one replica is ready — the fleet-level
        /readyz signal."""
        return any(r.ready() for _, r in self._members())

    def _ready_check(self):
        members = self._members()
        n = sum(1 for _, r in members if r.ready())
        if n:
            return True, '%d/%d replicas ready' % (n, len(members))
        return False, '0/%d replicas ready' % len(members)

    def close(self, shutdown_replicas=False, drain=True):
        """Unregister the router's health check; optionally shut every
        replica down too."""
        self._closed = True
        _obs.unregister_health_check(self._health_name)
        if shutdown_replicas:
            for _, r in self._members():
                r.shutdown(drain=drain)

    def _members(self):
        with self._mu:
            return list(self._replicas)

    def replicas(self):
        """[(name, replica)] — live view for tests and tooling."""
        return self._members()

    # -------------------------------------------------------- membership
    def add_replica(self, replica, name=None):
        """Register one replica with the fleet (fleet-controller hook).
        The replica joins the candidate ranking on the next submit; it
        should already be ready() — the controller only registers
        replicas after warmup. Names must stay unique."""
        name = str(name) if name else (getattr(replica, 'name', None)
                                       or 'replica?')
        with self._mu:
            if any(n == name for n, _ in self._replicas):
                raise ValueError('replica name %r already in the fleet'
                                 % name)
            self._replicas.append((name, replica))
            total = len(self._replicas)
        _obs.set_gauge('router.replicas_total', total)
        _obs.inc('router.membership_changes_total', change='add',
                 route=self.route)
        return name

    def remove_replica(self, name):
        """Deregister one replica (fleet-controller hook) and return
        it. It takes no new work from this router the moment this
        returns — requests already dispatched to it still complete,
        and draining/shutdown is the caller's job (scale-in drains
        BEFORE shutdown so accepted work is never lost)."""
        with self._mu:
            for i, (n, r) in enumerate(self._replicas):
                if n == name:
                    del self._replicas[i]
                    total = len(self._replicas)
                    break
            else:
                raise KeyError('no replica named %r in the fleet'
                               % name)
        _obs.set_gauge('router.replicas_total', total)
        _obs.inc('router.membership_changes_total', change='remove',
                 route=self.route)
        return r

    # --------------------------------------------------------- placement
    def _publish_fleet(self):
        ready = 0
        for name, r in self._members():
            ok = r.ready()
            ready += bool(ok)
            _obs.set_gauge('router.replica_queue_depth',
                           r.queue_depth() if ok else -1, replica=name)
        _obs.set_gauge('router.replicas_ready', ready)

    def _candidates(self, session=None, exclude=()):
        """Ready replicas in dispatch-preference order: the session's
        pinned replica first (when affine and ready), then ascending
        queue depth. A replica whose ready() is False — not started,
        not warmed, full-stop dead, or mid-drain/shutdown — is never a
        candidate: scale-in must not route new work onto a replica
        being retired."""
        members = self._members()
        avail = [(name, r) for name, r in members
                 if name not in exclude and r.ready()]
        ranked = sorted(avail,
                        key=lambda nr: (nr[1].queue_depth(),
                                        next(self._rr)))
        if session is not None and self.session_affinity and members:
            # rendezvous (highest-random-weight) hashing: each session
            # pins to the member maximizing hash(session, name), so a
            # membership change only moves the sessions that touch the
            # added/removed replica — not the whole keyspace the way a
            # modulus over len(members) would
            key = str(session).encode()
            pin = max(members,
                      key=lambda nr: zlib.crc32(
                          nr[0].encode() + b'\x00' + key))
            if pin in ranked:
                ranked.remove(pin)
                ranked.insert(0, pin)
        return ranked

    # --------------------------------------------------------- admission
    def _admission_check(self, ctx):
        """Shed or degrade before any dispatch. An already-exhausted
        deadline sheds synchronously (no dispatch, no hedge token);
        otherwise, with SLO admission, a predicted-p99 breach sheds or
        degrades. Returns True when the request was degraded."""
        remaining = ctx.remaining()
        if remaining is not None and remaining <= 0.0:
            # the fast path: the budget is gone before any work
            # happened — shed without touching a replica or a token
            _obs.inc('router.shed_total', reason='deadline_expired',
                     route=self.route)
            ctx.event('shed', reason='deadline_expired')
            raise SLOShedError(
                'admission shed: deadline budget already exhausted '
                '(%.4fs past) on route %r' % (-remaining, self.route))
        if self.admission != 'slo':
            return False
        p99 = self._slo.predicted_p99(self.route)
        if p99 is None:
            return False
        budget = remaining if remaining is not None else \
            self._slo.objective(self.route).latency_budget_s
        if p99 <= budget:
            return False
        if self.on_breach == 'degrade':
            _obs.inc('router.degraded_total', route=self.route)
            ctx.event('degraded', predicted_p99=p99, budget=budget)
            return True
        _obs.inc('router.shed_total', reason='predicted_p99',
                 route=self.route)
        _obs.flight_event('router_shed', route=self.route,
                          predicted_p99=round(p99, 6),
                          budget=round(budget, 6))
        ctx.event('shed', predicted_p99=p99, budget=budget)
        raise SLOShedError(
            'admission shed: predicted p99 %.4fs exceeds remaining '
            'budget %.4fs on route %r' % (p99, budget, self.route))

    # ----------------------------------------------------------- intake
    def submit(self, feed, session=None, deadline_s=None, ctx=None):
        """Route one request to the fleet; returns a Future. Raises
        SLOShedError (admission: predicted breach or expired
        deadline), QueueFullError (every ready replica full),
        NoReplicaAvailableError (no ready replica); after acceptance
        the future resolves with the result or a typed error — a
        replica dying mid-request triggers transparent resubmission
        (budget permitting) up to ``retries`` times first, and with
        hedging on, a request outliving the route's p95 gets a second
        chance on an untried replica."""
        if ctx is None:
            ctx = _reqtrace.new_context(self.route,
                                        deadline_s=deadline_s)
        _obs.inc('router.requests_total', route=self.route)
        self._admission_check(ctx)
        if self._tenants is not None:
            # quota charge keyed off the same session id the rendezvous
            # pin uses; QuotaExceededError propagates synchronously and
            # the request never reaches the retry-budget deposit below
            self._tenants.admit(session, route=self.route)
        state = _InFlight(feed, session, ctx, Future(),
                          attempts_left=self.retries)
        # accepted traffic funds the retry budget (shed requests never
        # reach this line, so they cannot buy hedges)
        _obs.set_gauge('router.retry_budget_tokens',
                       self._budget.deposit())
        self._dispatch(state, hedge=False)
        self._schedule_hedge(state)
        self._publish_fleet()
        return state.outer

    def predict(self, feed, session=None, deadline_s=None, timeout=None):
        """submit() + wait."""
        return self.submit(feed, session=session,
                           deadline_s=deadline_s).result(timeout)

    # --------------------------------------------------------- dispatch
    def _dispatch(self, state, hedge):
        """One placement attempt: submit to the best untried ready
        replica and hook its completion. Raises QueueFullError /
        NoReplicaAvailableError when nothing accepts (the caller
        decides whether that is fatal — it is for the primary, it is
        not for a hedge or failover)."""
        last_full = None
        for name, replica in self._candidates(state.session,
                                              exclude=state.tried):
            try:
                inner = replica.submit(state.feed, ctx=state.ctx)
            except QueueFullError as e:
                last_full = e
                continue
            except EngineClosedError:
                continue   # lost the race with a shutdown: next replica
            with state.mu:
                state.tried.add(name)
                state.outstanding += 1
            _obs.inc('router.dispatch_total', replica=name,
                     route=self.route)
            state.ctx.event('routed', replica=name, hedge=hedge)
            inner.add_done_callback(
                lambda f, name=name: self._on_attempt_done(
                    f, name, state, hedge))
            return name
        # nothing accepted it: full everywhere vs nothing ready
        if last_full is not None:
            _obs.inc('router.shed_total', reason='queue_full',
                     route=self.route)
            raise last_full
        _obs.inc('router.no_replica_total', route=self.route)
        _obs.flight_event('router_no_replica', route=self.route)
        raise NoReplicaAvailableError(
            'no ready replica (fleet of %d) for route %r'
            % (len(self._members()), self.route))

    # ----------------------------------------------------------- hedging
    def _hedge_delay(self):
        """Seconds to wait before hedging: the route's rolling
        ``hedge_quantile`` latency (floored at hedge_min_delay_s),
        falling back to the explicit hedge_delay_s; None disables the
        hedge for this request (no latency signal yet)."""
        if self._slo is not None:
            try:
                q = self._slo.predicted_quantile(self.route,
                                                 self.hedge_quantile)
            except KeyError:
                q = None
            if q is not None:
                return max(q, self.hedge_min_delay_s)
        if self.hedge_delay_s is not None:
            return max(float(self.hedge_delay_s), self.hedge_min_delay_s)
        return None

    def _schedule_hedge(self, state):
        if not self.hedge:
            return
        delay = self._hedge_delay()
        if delay is None:
            _obs.inc('router.hedge_suppressed_total', reason='no_signal',
                     route=self.route)
            return
        remaining = state.ctx.remaining()
        if remaining is not None and remaining <= delay:
            # the deadline will expire before the hedge would fire —
            # hedging could never help this request
            _obs.inc('router.hedge_suppressed_total', reason='deadline',
                     route=self.route)
            return
        t = threading.Timer(delay, self._maybe_hedge, args=(state,))
        t.daemon = True
        state.timer = t
        t.start()

    def _maybe_hedge(self, state):
        """Timer body: the primary outlived the hedge delay — dispatch
        a second attempt to an untried replica if deadline budget
        remains and the retry budget has a token."""
        if self._closed or state.outer.done():
            return
        if state.ctx.expired():
            _obs.inc('router.hedge_suppressed_total', reason='deadline',
                     route=self.route)
            return
        if not self._budget.try_spend():
            _obs.inc('router.hedge_suppressed_total', reason='budget',
                     route=self.route)
            _obs.inc('router.retry_budget_exhausted_total', kind='hedge',
                     route=self.route)
            return
        _obs.set_gauge('router.retry_budget_tokens', self._budget.tokens)
        with state.mu:
            if state.settled:
                self._budget.refund()
                return
            state.hedged = True
        try:
            name = self._dispatch(state, hedge=True)
        except (QueueFullError, NoReplicaAvailableError):
            # nowhere to hedge to: not an error for the request (the
            # primary is still running) — refund the token
            self._budget.refund()
            with state.mu:
                state.hedged = state.outstanding > 1
            _obs.inc('router.hedge_suppressed_total', reason='no_replica',
                     route=self.route)
            return
        _obs.inc('router.hedge_total', route=self.route)
        state.ctx.event('hedge', replica=name)

    # ------------------------------------------------------- completion
    def _on_attempt_done(self, inner, name, state, hedge):
        try:
            result = inner.result()
        except EngineClosedError as e:
            # the replica died under this attempt — the ONE failure
            # class where retrying elsewhere is always safe (the
            # request never computed)
            self._attempt_died(name, state, hedge, e)
        except BaseException as e:
            self._attempt_failed(state, e)
        else:
            self._attempt_succeeded(state, name, result, hedge)

    def _attempt_died(self, name, state, hedge, exc):
        _obs.inc('router.failover_total', replica=name, route=self.route)
        _obs.flight_event('router_failover', replica=name,
                          route=self.route,
                          attempts_left=state.attempts_left)
        state.ctx.event('failover', replica=name)
        with state.mu:
            # this attempt is over for good — retire its outstanding
            # slot HERE, so a successful redispatch (which increments
            # again) leaves the count balanced and the final attempt's
            # failure can actually settle the future instead of
            # stashing the error forever
            state.outstanding -= 1
            settled = state.settled
            can_retry = state.attempts_left > 0
            if can_retry:
                state.attempts_left -= 1
        if not settled and can_retry:
            if not self._budget.try_spend():
                _obs.inc('router.retry_budget_exhausted_total',
                         kind='failover', route=self.route)
                self._settle_failure(state, exc)
                return
            _obs.set_gauge('router.retry_budget_tokens',
                           self._budget.tokens)
            try:
                self._dispatch(state, hedge=hedge)
            except NoReplicaAvailableError:
                # nowhere left to go: the request died with its
                # replica — surface THAT, not the fleet census
                self._budget.refund()
                self._settle_failure(state, exc)
            except Exception as redispatch_exc:
                self._budget.refund()
                self._settle_failure(state, redispatch_exc)
            return
        self._settle_failure(state, exc)

    def _attempt_succeeded(self, state, name, result, hedge):
        with state.mu:
            state.outstanding -= 1
            if not state.settled:
                state.settled = True
                state.first_result = result
                state.have_result = True
                won = True
            else:
                won = False
                mismatch = state.have_result and \
                    not _results_equal(state.first_result, result)
        if won:
            if state.hedged:
                _obs.inc('router.hedge_wins_total',
                         winner='hedge' if hedge else 'primary',
                         route=self.route)
                state.ctx.event('hedge_won',
                                winner='hedge' if hedge else 'primary',
                                replica=name)
            if state.timer is not None:
                state.timer.cancel()
            self._finish(state, result=result)
        elif mismatch:
            # both attempts completed and disagreed: a determinism bug
            # worth an alarm, not a silent coin flip
            _obs.inc('router.hedge_mismatch_total', route=self.route)
            _obs.flight_event('router_hedge_mismatch', route=self.route,
                              replica=name)

    def _attempt_failed(self, state, exc):
        with state.mu:
            state.outstanding -= 1
        self._settle_failure(state, exc)

    def _settle_failure(self, state, exc):
        """Settle-only half of failure handling: callers that already
        retired the attempt's outstanding slot (_attempt_died) land
        here directly, so no path can double-decrement."""
        with state.mu:
            if state.settled:
                return                      # a loser failing is noise
            if state.outstanding > 0:
                # another attempt (hedge or primary) is still running —
                # hold the error, it may yet be rescued
                if state.stashed_exc is None:
                    state.stashed_exc = exc
                return
            state.settled = True
            exc = state.stashed_exc or exc
        if state.timer is not None:
            state.timer.cancel()
        self._finish(state, exc=exc)

    def _finish(self, state, result=None, exc=None):
        ctx, outer = state.ctx, state.outer
        latency = time.perf_counter() - ctx.t_start
        ok = exc is None
        _obs.record('router.request_seconds', latency,
                    exemplar=ctx.exemplar(), route=self.route)
        if self._slo is not None:
            self._slo.record(self.route, latency, ok=ok,
                             trace_id=ctx.exemplar())
        try:
            if ok:
                outer.set_result(result)
            else:
                _obs.inc('router.request_errors_total',
                         error=type(exc).__name__, route=self.route)
                outer.set_exception(exc)
        except Exception:
            pass   # client cancelled the outer future: result dropped


# ===================================================================
# Phase-aware fleet scheduling: disaggregated prefill/decode serving
# ===================================================================

class _DeadlineExpired(Exception):
    """Internal pipeline signal: the request's deadline ran out
    between phases (converted to SLOShedError at the stream)."""


class HandoffStream(object):
    """The client's view of a disaggregated generation request: quacks
    like ``decode.GenerationStream`` (iterate for tokens, ``result()``
    for the full list, ``finish_reason``), but the tokens come from
    whichever decode replica the pipeline landed on. Until the decode
    phase starts, iteration and ``result()`` block; a pipeline failure
    (no replica, shed, handoff error) surfaces as that typed exception
    from either call — accepted requests settle, never hang."""

    __slots__ = ('request_id', '_evt', '_inner', '_exc')

    def __init__(self, request_id):
        self.request_id = request_id
        self._evt = threading.Event()
        self._inner = None
        self._exc = None

    # pipeline-side
    def _wire(self, inner):
        self._inner = inner
        self._evt.set()

    def _fail(self, exc):
        self._exc = exc
        self._evt.set()

    # client-side
    @property
    def finish_reason(self):
        if self._exc is not None:
            return 'error'
        return self._inner.finish_reason if self._inner is not None \
            else None

    def done(self):
        return self._exc is not None or \
            (self._inner is not None and self._inner.done())

    def __iter__(self):
        self._evt.wait()
        if self._exc is not None:
            raise self._exc
        return iter(self._inner)

    def result(self, timeout=None):
        t0 = time.perf_counter()
        if not self._evt.wait(timeout):
            raise TimeoutError('decode phase not reached within %ss'
                               % timeout)
        if self._exc is not None:
            raise self._exc
        left = None if timeout is None else \
            max(0.0, timeout - (time.perf_counter() - t0))
        return self._inner.result(left)


class PhaseRouter(object):
    """Fleet scheduler for a phase-split serving fleet: a **prefill
    pool** (compute-bound replicas, bucket-laddered, admission keyed
    on queue depth x predicted prefill latency) feeding a **decode
    pool** (HBM-bound replicas, paged, admission keyed on free KV
    pages and open batch slots) through the zero-copy KV handoff
    (``serving.handoff``). This is the PAPERS "Serving Gemma on Cloud
    TPU" architecture: a long compute-bound prefill never again stalls
    a resident decode step, because the two phases never share chips.

    ::

        pre  = [DecodeEngine(spec, prefix_cache=True, ...)]   # x P
        dec  = [DecodeEngine(spec, prefix_cache=True, ...)]   # x D
        pr = PhaseRouter(pre, dec, route='disagg')
        stream = pr.submit(prompt, max_new_tokens=64, session='u1')
        for tok in stream: ...
        pr.close()

    Every replica is a ``DecodeEngine`` with ``prefix_cache=True``
    (the cache is both the export staging area on the prefill side
    and the handoff registry on the decode side) and the SAME weights
    and arena ``kv_dtype`` fleet-wide. The request pipeline, run on a
    small worker pool (``handoff_workers`` /
    ``PADDLE_TPU_HANDOFF_WORKERS``):

    1. **prefill** — least-loaded prefill replica by queue depth x
       rolling per-replica prefill latency; ``max_new_tokens=1``
       (sampling is (seed, position)-keyed, so the decode replica
       regenerates the same first token bit-identically from the
       handed-off pages).
    2. **handoff** — the prompt's frozen full pages hop replica:
       export (pin chain, read through reused staging buffers),
       install (dedup against the destination cache, scatter the tail,
       publish). Shared system prompts ship ONCE per decode replica.
    3. **decode** — decode replica chosen by (open slot, most free
       pages), with rendezvous-hash session affinity so a session's
       prefixes stay hot on one replica's cache; the full request
       submits there and admission-matches the just-installed chain —
       prefill on the decode replica covers only the uncached suffix
       (< block_size tokens + the sampling position), always a warm
       small bucket. Zero new XLA signatures on either fleet.

    ``colocated=True`` degenerates to single-pool serving (each
    request prefills AND decodes on one decode-pool replica, no
    handoff) — the colocated leg ``tests/chaos.py::disagg_chaos`` runs
    beside the split one at an equal count of engines, and the right
    choice when
    prompts are short or the fleet is tiny (docs/serving.md).

    Per-phase membership is dynamic (``add_replica(r, phase=...)`` /
    ``remove_replica(name, phase=...)`` under the router lock), and
    ``pool(phase)`` exposes each pool through the Router membership
    protocol so one ``FleetController`` per phase can scale them
    independently (prefill on TTFT burn, decode on page pressure —
    ``controller.ttft_pressure`` / ``controller.page_pressure``).
    """

    PHASES = ('prefill', 'decode')

    def __init__(self, prefill_replicas, decode_replicas, slo=None,
                 route='disagg', session_affinity=True, retries=2,
                 colocated=False, handoff_workers=None,
                 max_inflight=None, via_bytes=True, lat_window=64,
                 tenants=None):
        self.route = str(route)
        self._slo = slo
        # optional multi-tenant policy: admission charges requests AND
        # decode tokens (max_new_tokens) to the session's tenant, and
        # the resolved priority class rides the request into the decode
        # scheduler/prefix cache
        self._tenants = tenants
        self.session_affinity = bool(session_affinity)
        self.retries = int(retries)
        self.colocated = bool(colocated)
        self.via_bytes = bool(via_bytes)
        self._mu = threading.Lock()
        self._rr = itertools.count()
        self._ids = itertools.count(1)
        self._closed = False
        self._inflight = 0
        self._pools = {'prefill': [], 'decode': []}
        for phase, reps in (('prefill', prefill_replicas or []),
                            ('decode', decode_replicas)):
            for i, r in enumerate(reps):
                name = getattr(r, 'name', None) or \
                    '%s%d' % (phase, i)
                self.add_replica(r, phase=phase, name=name)
        if not self._pools['decode']:
            raise ValueError('PhaseRouter needs at least one decode '
                             'replica')
        if not self.colocated and not self._pools['prefill']:
            raise ValueError('PhaseRouter needs at least one prefill '
                             'replica (or colocated=True)')
        if handoff_workers is None:
            handoff_workers = int(os.environ.get(
                'PADDLE_TPU_HANDOFF_WORKERS', '') or 4)
        self.handoff_workers = int(handoff_workers)
        self.max_inflight = int(max_inflight) if max_inflight \
            else 8 * self.handoff_workers
        # rolling prefill-phase latency per replica (EWMA) + a recent-
        # TTFT-attribution window (prefill + handoff seconds) the
        # per-phase autoscaling policy reads
        self._pf_lat = {}
        self._ttft_window = []
        self._lat_window = int(lat_window)
        from concurrent.futures import ThreadPoolExecutor
        self._pipeline = ThreadPoolExecutor(
            max_workers=self.handoff_workers,
            thread_name_prefix='paddle_tpu_handoff')
        self._publish()

    # -------------------------------------------------------- membership
    def add_replica(self, replica, phase='decode', name=None):
        if phase not in self.PHASES:
            raise ValueError('phase must be one of %s, got %r'
                             % (self.PHASES, phase))
        name = str(name) if name else (getattr(replica, 'name', None)
                                       or 'replica?')
        with self._mu:
            for ph in self.PHASES:
                if any(n == name for n, _ in self._pools[ph]):
                    raise ValueError('replica name %r already in the '
                                     '%s pool' % (name, ph))
            self._pools[phase].append((name, replica))
        _obs.inc('router.membership_changes_total', change='add',
                 route=self.route, phase=phase)
        self._publish()
        return name

    def remove_replica(self, name, phase=None):
        phases = (phase,) if phase else self.PHASES
        with self._mu:
            for ph in phases:
                for i, (n, r) in enumerate(self._pools[ph]):
                    if n == name:
                        del self._pools[ph][i]
                        _obs.inc('router.membership_changes_total',
                                 change='remove', route=self.route,
                                 phase=ph)
                        self._publish_locked()
                        return r
        raise KeyError('no replica named %r in %s' % (name, phases))

    def members(self, phase):
        with self._mu:
            return list(self._pools[phase])

    def pool(self, phase):
        """A Router-shaped view of one phase's membership
        (add_replica/remove_replica/replicas/route/ready) so a
        ``FleetController`` can own that phase's lifecycle without
        knowing about the other."""
        return _PhasePool(self, phase)

    # --------------------------------------------------------- liveness
    def ready(self):
        dec = any(r.ready() for _, r in self.members('decode'))
        if self.colocated:
            return dec
        return dec and any(r.ready()
                           for _, r in self.members('prefill'))

    def close(self, shutdown_replicas=False, drain=True):
        self._closed = True
        self._pipeline.shutdown(wait=True)
        if shutdown_replicas:
            for ph in self.PHASES:
                for _, r in self.members(ph):
                    r.shutdown(drain=drain)

    def _publish(self):
        with self._mu:
            self._publish_locked()

    def _publish_locked(self):
        if not _obs.enabled():
            return
        for ph in self.PHASES:
            members = self._pools[ph]
            _obs.set_gauge('router.phase_replicas', len(members),
                           phase=ph, route=self.route)
            _obs.set_gauge('router.phase_replicas_ready',
                           sum(1 for _, r in members if r.ready()),
                           phase=ph, route=self.route)

    # ------------------------------------------------- pressure signals
    def prefill_phase_p95(self):
        """p95 of the recent TTFT attribution window (prefill phase +
        handoff seconds per request) — what ``ttft_pressure`` scales
        the prefill pool on."""
        with self._mu:
            w = sorted(self._ttft_window)
        if not w:
            return None
        return w[min(len(w) - 1, int(0.95 * len(w)))]

    def decode_free_page_frac(self):
        """min over ready decode replicas of free_pages/num_blocks —
        what ``page_pressure`` scales the decode pool on (the fleet is
        as healthy as its most page-starved replica)."""
        fracs = [r.free_pages() / float(r.num_blocks)
                 for _, r in self.members('decode') if r.ready()]
        return min(fracs) if fracs else None

    def _note_prefill(self, replica_name, seconds):
        """Per-prefill-replica latency EWMA — the predicted-prefill-
        latency half of the prefill admission key."""
        with self._mu:
            prev = self._pf_lat.get(replica_name)
            self._pf_lat[replica_name] = seconds if prev is None \
                else 0.7 * prev + 0.3 * seconds

    def _note_ttft(self, seconds):
        with self._mu:
            self._ttft_window.append(seconds)
            if len(self._ttft_window) > self._lat_window:
                del self._ttft_window[:-self._lat_window]
        _obs.record('handoff.ttft_attributed_seconds', seconds,
                    route=self.route)

    # --------------------------------------------------------- placement
    def _prefill_candidates(self, exclude=()):
        """Ready prefill replicas, cheapest expected wait first:
        (queue_depth + 1) x rolling prefill latency — the compute-
        bound admission key (a deep queue on a slow replica is the
        worst seat in the house)."""
        with self._mu:
            members = list(self._pools['prefill'])
            lat = dict(self._pf_lat)
        avail = [(n, r) for n, r in members
                 if n not in exclude and r.ready()]
        return sorted(
            avail, key=lambda nr: ((nr[1].queue_depth() + 1)
                                   * lat.get(nr[0], 1e-3),
                                   next(self._rr)))

    def _decode_candidates(self, session=None, exclude=()):
        """Ready decode replicas, most headroom first: open batch
        slots, then free KV pages — the HBM-bound admission key. A
        session pins (rendezvous hash) to keep its prefixes hot on one
        replica's radix cache; the pin leads the ranking but never
        blocks failover."""
        members = self.members('decode')
        avail = [(n, r) for n, r in members
                 if n not in exclude and r.ready()]
        ranked = sorted(
            avail, key=lambda nr: (nr[1].free_slots() == 0,
                                   -nr[1].free_pages(),
                                   next(self._rr)))
        if session is not None and self.session_affinity and members:
            key = str(session).encode()
            pin = max(members,
                      key=lambda nr: zlib.crc32(
                          nr[0].encode() + b'\x00' + key))
            if pin in ranked:
                ranked.remove(pin)
                ranked.insert(0, pin)
        return ranked

    # ----------------------------------------------------------- intake
    def submit(self, prompt_ids, max_new_tokens=16, temperature=0.0,
               seed=0, eos_id=None, session=None, deadline_s=None,
               ctx=None):
        """Route one generation request through the phase pipeline;
        returns a :class:`HandoffStream` immediately. Raises
        QueueFullError when the pipeline is at ``max_inflight``
        (bounded like any admission queue), SLOShedError on an
        already-expired deadline, EngineClosedError after close().
        Accepted requests complete or fail typed — never hang."""
        if self._closed:
            raise EngineClosedError('PhaseRouter is closed')
        if ctx is None:
            ctx = _reqtrace.new_context(self.route,
                                        deadline_s=deadline_s)
        remaining = ctx.remaining()
        if remaining is not None and remaining <= 0.0:
            _obs.inc('router.phase_sheds_total',
                     reason='deadline_expired', route=self.route)
            raise SLOShedError('phase router shed: deadline budget '
                               'already exhausted')
        tenant = None
        if self._tenants is not None:
            # one request + max_new_tokens decode tokens, charged to
            # the session's tenant before the pipeline slot is taken
            # (QuotaExceededError propagates synchronously, same
            # contract as the deadline shed above)
            tenant = self._tenants.admit(session,
                                         tokens=int(max_new_tokens),
                                         route=self.route)
        with self._mu:
            if self._inflight >= self.max_inflight:
                _obs.inc('router.phase_sheds_total',
                         reason='pipeline_full', route=self.route)
                raise QueueFullError(
                    'handoff pipeline full (%d inflight >= '
                    'max_inflight=%d)'
                    % (self._inflight, self.max_inflight))
            self._inflight += 1
        _obs.inc('router.phase_requests_total', route=self.route)
        stream = HandoffStream(next(self._ids))
        req = dict(prompt=[int(t) for t in prompt_ids],
                   max_new_tokens=int(max_new_tokens),
                   temperature=float(temperature), seed=int(seed),
                   eos_id=eos_id, session=session, ctx=ctx,
                   tenant=tenant.name if tenant else None,
                   priority=tenant.priority if tenant else None)
        try:
            self._pipeline.submit(self._run_pipeline, req, stream)
        except RuntimeError:
            with self._mu:
                self._inflight -= 1
            raise EngineClosedError('PhaseRouter is closed')
        return stream

    def generate(self, prompt_ids, timeout=None, **kwargs):
        """submit() + wait."""
        return self.submit(prompt_ids, **kwargs).result(timeout)

    # ---------------------------------------------------------- pipeline
    def _run_pipeline(self, req, stream):
        try:
            if self.colocated:
                self._decode_phase(req, stream, src=None)
            else:
                src = self._prefill_phase(req)
                self._decode_phase(req, stream, src=src)
        except _DeadlineExpired:
            _obs.inc('router.phase_sheds_total',
                     reason='deadline_expired', route=self.route)
            stream._fail(SLOShedError(
                'deadline expired in the handoff pipeline'))
        except BaseException as e:
            _obs.inc('router.phase_errors_total',
                     error=type(e).__name__, route=self.route)
            stream._fail(e)
        finally:
            with self._mu:
                self._inflight -= 1

    def _check_deadline(self, ctx):
        remaining = ctx.remaining()
        if remaining is not None and remaining <= 0.0:
            raise _DeadlineExpired()

    def _prefill_phase(self, req):
        """Dispatch the prompt-only prefill (max_new_tokens=1) to the
        best prefill replica, failing over across the pool; returns
        the replica that now holds the prompt's frozen pages in its
        cache. The sampled token is discarded — the decode replica
        regenerates it bit-identically ((seed, position)-keyed
        sampling over identical KV bits)."""
        ctx = req['ctx']
        self._check_deadline(ctx)
        t0 = time.perf_counter()
        tried = set()
        last_exc = None
        for _ in range(self.retries + 1):
            cands = self._prefill_candidates(exclude=tried)
            if not cands:
                break
            name, eng = cands[0]
            tried.add(name)
            try:
                s = eng.submit(req['prompt'], max_new_tokens=1,
                               temperature=req['temperature'],
                               seed=req['seed'], ctx=ctx)
                _obs.inc('router.phase_dispatch_total',
                         phase='prefill', replica=name,
                         route=self.route)
                s.result()
            except QueueFullError as e:
                last_exc = e
                continue
            except EngineClosedError as e:
                # replica died under the prefill: its pages died with
                # it — retry whole-phase on the next replica
                last_exc = e
                _obs.inc('router.failover_total', replica=name,
                         route=self.route)
                continue
            dt = time.perf_counter() - t0
            self._note_prefill(name, dt)
            if ctx.sampled:
                ctx.event('prefill_phase', replica=name,
                          seconds=round(dt, 6))
            return name, eng, t0
        if last_exc is not None:
            raise last_exc
        _obs.inc('router.no_replica_total', route=self.route,
                 phase='prefill')
        raise NoReplicaAvailableError(
            'no ready prefill replica for route %r' % self.route)

    def _decode_phase(self, req, stream, src):
        """Install the handed-off pages (when disaggregated) and
        submit the full request on the chosen decode replica; wire the
        replica's GenerationStream to the client's HandoffStream.
        Failover re-installs on the next candidate — the packet
        lives on the PREFILL replica's cache until eviction, so a
        decode replica dying mid-handoff costs one re-export."""
        from . import handoff as _handoff
        ctx = req['ctx']
        tried = set()
        last_exc = None
        for _ in range(self.retries + 1):
            self._check_deadline(ctx)
            cands = self._decode_candidates(req['session'],
                                            exclude=tried)
            if not cands:
                break
            name, eng = cands[0]
            tried.add(name)
            try:
                if src is not None:
                    src_name, src_eng, t0_pf = src
                    covered = _handoff.handoff(
                        src_eng, eng, req['prompt'],
                        via_bytes=self.via_bytes, ctx=ctx)
                    # TTFT attribution: prefill + handoff is the part
                    # the PHASE SPLIT added ahead of the decode
                    # replica's (small) suffix prefill
                    self._note_ttft(time.perf_counter() - t0_pf)
                    if ctx.sampled:
                        ctx.event('kv_handoff', src=src_name,
                                  dst=name, covered_tokens=covered)
                inner = eng.submit(req['prompt'],
                                   max_new_tokens=req['max_new_tokens'],
                                   temperature=req['temperature'],
                                   seed=req['seed'],
                                   eos_id=req['eos_id'], ctx=ctx,
                                   tenant=req.get('tenant'),
                                   priority=req.get('priority'))
            except QueueFullError as e:
                last_exc = e
                continue
            except EngineClosedError as e:
                last_exc = e
                _obs.inc('router.failover_total', replica=name,
                         route=self.route)
                continue
            _obs.inc('router.phase_dispatch_total', phase='decode',
                     replica=name, route=self.route)
            stream._wire(inner)
            return
        if last_exc is not None:
            raise last_exc
        _obs.inc('router.no_replica_total', route=self.route,
                 phase='decode')
        raise NoReplicaAvailableError(
            'no ready decode replica for route %r' % self.route)


class _PhasePool(object):
    """Router-membership adapter for one phase of a PhaseRouter — the
    object a per-phase FleetController drives (same surface as
    ``Router``: add_replica / remove_replica / replicas / route)."""

    def __init__(self, router, phase):
        if phase not in PhaseRouter.PHASES:
            raise ValueError('unknown phase %r' % phase)
        self._router = router
        self.phase = phase
        self.route = '%s/%s' % (router.route, phase)
        self._slo = router._slo

    def replicas(self):
        return self._router.members(self.phase)

    def add_replica(self, replica, name=None):
        return self._router.add_replica(replica, phase=self.phase,
                                        name=name)

    def remove_replica(self, name):
        return self._router.remove_replica(name, phase=self.phase)

    def ready(self):
        return any(r.ready() for _, r in self.replicas())
