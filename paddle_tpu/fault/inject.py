"""Deterministic fault injection: the harness that PROVES the
fault-tolerance layer instead of trusting it.

Five injectable faults, each deterministic (fixed step index, no
randomness — reruns reproduce exactly):

- kill the process once the global step reaches k (os._exit — the
  abrupt end of a preemption's grace window),
- SIGTERM the process at step k (the preemption NOTICE itself: the
  flight-recorder SIGTERM handler gets to dump a postmortem before the
  default action terminates — what a real TPU preemption looks like
  from inside),
- truncate a checkpoint file right after it commits (a write torn by
  preemption, or bit-rot/partial copy that survived the atomic rename),
- poison batch k's float arrays with NaNs (corrupt input),
- make a reader raise transiently (flaky storage),
- kill one serving replica mid-load (``kill_replica`` — the fleet
  chaos scenario's replica-down event; the router's failover and the
  /readyz flip are asserted against it).

Hook points: the Trainer calls fire('step_end', step=...) after each
step, the CheckpointManager calls fire('checkpoint_saved', ...) after
each commit. Both are no-ops without an installed plan.

Env contract (for subprocess crash/resume drills — the resumed run must
NOT set these again or it re-dies at the same step; an elastic-resume
drill relaunches on a DIFFERENT mesh, see tests/fault_injection_child.py
FT_MESH_DP):

    PADDLE_TPU_FI_KILL_AT_STEP=k     os._exit(42) at global step >= k
    PADDLE_TPU_FI_PREEMPT_AT_STEP=k  SIGTERM self at global step >= k
                                     (subprocess exit code -SIGTERM)
    PADDLE_TPU_FI_CORRUPT_CKPT_AT=k  truncate params.npz of the
                                     checkpoint committed at step k
"""

import os

__all__ = ['KILL_EXIT_CODE', 'FaultPlan', 'TransientReaderError',
           'install', 'install_from_env', 'clear', 'active', 'fire',
           'truncate_file', 'poison_nans', 'flaky', 'kill_replica',
           'crash_loop', 'kill_process']

KILL_EXIT_CODE = 42
_ENV_KILL = 'PADDLE_TPU_FI_KILL_AT_STEP'
_ENV_PREEMPT = 'PADDLE_TPU_FI_PREEMPT_AT_STEP'
_ENV_CORRUPT = 'PADDLE_TPU_FI_CORRUPT_CKPT_AT'


class TransientReaderError(IOError):
    """Injected transient input failure (reader.retry's target class)."""


class FaultPlan(object):
    def __init__(self, kill_at_step=None, corrupt_checkpoint_at_step=None,
                 preempt_at_step=None):
        self.kill_at_step = kill_at_step
        self.corrupt_checkpoint_at_step = corrupt_checkpoint_at_step
        self.preempt_at_step = preempt_at_step


_active = None


def install(plan):
    global _active
    _active = plan


def clear():
    global _active
    _active = None


def active():
    return _active


def install_from_env(environ=None):
    """Install a plan from the PADDLE_TPU_FI_* vars. No-op when none are
    set or when a plan was already installed programmatically."""
    env = os.environ if environ is None else environ
    if _active is not None:
        return _active
    kill = env.get(_ENV_KILL)
    preempt = env.get(_ENV_PREEMPT)
    corrupt = env.get(_ENV_CORRUPT)
    if kill is None and corrupt is None and preempt is None:
        return None
    plan = FaultPlan(
        kill_at_step=int(kill) if kill else None,
        corrupt_checkpoint_at_step=int(corrupt) if corrupt else None,
        preempt_at_step=int(preempt) if preempt else None)
    install(plan)
    return plan


def fire(point, step=None, dirname=None):
    plan = _active
    if plan is None:
        return
    if (point == 'step_end' and plan.preempt_at_step is not None
            and step is not None and step >= plan.preempt_at_step):
        import signal
        # one-shot: if a handler absorbs the signal (a unit test, or a
        # grace-window drain), training continues instead of re-dying
        # on every subsequent step — matching a real preemption notice,
        # which is delivered once
        plan.preempt_at_step = None
        try:
            from .. import observe as _obs
            _obs.flight_event('preempt', step=step)
        except Exception:
            pass
        # SIGTERM, not a hard kill: the armed flight-recorder handler
        # (observe._install_sigterm_handler) dumps its postmortem, then
        # chains to the default action, which terminates the process —
        # exactly the shape of a cloud preemption notice
        os.kill(os.getpid(), signal.SIGTERM)
        return
    if (point == 'step_end' and plan.kill_at_step is not None
            and step is not None and step >= plan.kill_at_step):
        # The one concession before the hard kill: a flight-recorder
        # postmortem (no-op unless armed) — exactly what a real
        # preemption's SIGTERM grace window would leave behind.
        try:
            from .. import observe as _obs
            _obs.flight_event('kill', step=step,
                              kill_at_step=plan.kill_at_step)
            _obs.flight_dump('fault_injection_kill')
        except Exception:
            pass
        # os._exit: no atexit, no flushes, no thread joins — the closest
        # in-process stand-in for a preempted VM. >= (not ==) so a
        # windowed dispatch that jumps past k still dies.
        os._exit(KILL_EXIT_CODE)
    if (point == 'checkpoint_saved'
            and plan.corrupt_checkpoint_at_step is not None
            and step == plan.corrupt_checkpoint_at_step and dirname):
        truncate_file(os.path.join(dirname, 'params.npz'))


def kill_replica(engine, drain=False):
    """Chaos action for the serving fleet: abruptly take one replica
    down mid-load (``drain=False``, the default, is the preemption
    shape — queued-but-unbatched requests fail with the typed
    EngineClosedError, which the router's failover resubmits
    elsewhere; batches already handed to dispatch still complete).
    The flight event makes the kill findable in postmortems and in a
    chaos scenario's record. Returns the engine."""
    name = getattr(engine, 'name', None) or type(engine).__name__
    try:
        from .. import observe as _obs
        _obs.flight_event('replica_kill', replica=str(name),
                          drain=bool(drain))
        _obs.inc('fault.replica_kills_total', replica=str(name))
    except Exception:
        _obs = None
    engine.shutdown(drain=drain)
    # a killed replica doesn't get to tidy its own grave: graceful
    # shutdown unregisters the engine's /readyz check, but a chaos kill
    # re-registers it so the corpse shows NOT-ready (the balancer-visible
    # flip the failover tests assert) instead of silently vanishing
    check = getattr(engine, '_ready_check', None)
    if _obs is not None and callable(check):
        try:
            _obs.register_health_check('serving.%s' % name, check,
                                       readiness_only=True)
        except Exception:
            pass
    return engine


def crash_loop(engine, kills, interval_s):
    """Chaos action for the self-healing fleet: kill the same replica
    SLOT repeatedly — the scenario that must trip the fleet
    controller's crash-loop circuit breaker (quarantine) instead of
    thrashing it with doomed restarts.

    ``engine`` is either a live engine (killed once; later iterations
    find nothing new to kill) or, the interesting form, a zero-arg
    callable returning the slot's CURRENT live engine or None —
    ``lambda: controller.current('replica2')`` aims every kill at
    whatever replacement the controller just spawned. Each iteration
    waits ``interval_s`` (so heals can land in between), resolves the
    target, and ``kill_replica``s it with a ``crash_loop_kill`` flight
    event. Returns the number of kills actually performed (a
    quarantined slot stops producing victims — fewer kills than asked
    is the breaker WORKING)."""
    import time as _time
    resolve = engine if callable(engine) else (lambda: engine)
    killed = 0
    last = None
    for i in range(int(kills)):
        if i:
            _time.sleep(float(interval_s))
        victim = resolve()
        if victim is None or victim is last and not victim.ready():
            continue                 # slot is down/benched: no victim
        try:
            from .. import observe as _obs
            _obs.flight_event('crash_loop_kill', iteration=i,
                              replica=str(getattr(victim, 'name',
                                                  '?')))
        except Exception:
            pass
        kill_replica(victim, drain=False)
        last = victim
        killed += 1
    return killed


def kill_process(proc_or_resolver, sig=None):
    """Chaos action for the CROSS-HOST fleet: deliver a real signal
    (default SIGKILL) to a live replica worker PID — death the kernel
    enforces, not a flipped flag. Mirrors ``kill_replica`` /
    ``crash_loop``:

    ``proc_or_resolver`` is any of
      - a ``subprocess.Popen`` (or anything with ``.pid``),
      - a ``serving.rpc.RemoteReplica`` (its ``.proc`` is the victim),
      - a raw integer PID, or
      - the interesting form: a zero-arg callable returning any of the
        above or None — ``lambda: ctl.current('r2')`` aims every kill
        at whatever replacement the controller just spawned.

    Emits the ``process_kill`` flight event +
    ``fault.process_kills_total`` before the signal (the postmortem
    must show the kill even if this process dies next). Returns the
    PID signalled, or None when there was no victim (slot empty /
    process already reaped) — a quarantined slot producing no victims
    is the breaker WORKING, same contract as ``crash_loop``."""
    import signal
    victim = (proc_or_resolver() if callable(proc_or_resolver)
              else proc_or_resolver)
    if victim is None:
        return None
    proc = getattr(victim, 'proc', None) or victim   # RemoteReplica
    if isinstance(proc, int):
        pid, alive = proc, True
    else:
        pid = getattr(proc, 'pid', None)
        if pid is None:
            return None
        poll = getattr(proc, 'poll', None)
        alive = poll() is None if callable(poll) else True
    if not alive:
        return None                 # already a reaped corpse
    signum = int(sig) if sig is not None else signal.SIGKILL
    try:
        from .. import observe as _obs
        _obs.flight_event('process_kill', pid=int(pid), sig=signum,
                          replica=str(getattr(victim, 'name', pid)))
        _obs.inc('fault.process_kills_total',
                 replica=str(getattr(victim, 'name', pid)))
    except Exception:
        pass
    try:
        os.kill(int(pid), signum)
    except ProcessLookupError:
        return None                 # raced with its own death
    return int(pid)


def truncate_file(path, keep_fraction=0.5):
    """Cut a file to a prefix of itself — the on-disk shape of a write
    torn mid-stream."""
    size = os.path.getsize(path)
    with open(path, 'r+b') as f:
        f.truncate(int(size * keep_fraction))


def poison_nans(reader, at_step):
    """Wrap a reader: the item at stream index at_step has every float
    array replaced with NaNs (dict / tuple / list items supported)."""
    import numpy as np

    def _poison_val(v):
        arr = np.asarray(v)
        if arr.dtype.kind == 'f':
            return np.full_like(arr, np.nan)
        return v

    def _poison(item):
        if isinstance(item, dict):
            return {k: _poison_val(v) for k, v in item.items()}
        if isinstance(item, (list, tuple)):
            return type(item)(_poison_val(v) for v in item)
        return _poison_val(item)

    def wrapper():
        for i, item in enumerate(reader()):
            yield _poison(item) if i == at_step else item
    return wrapper


def flaky(reader, fail_times, fail_after=0, exc=TransientReaderError):
    """Wrap a reader factory: the first fail_times iterations raise exc
    after yielding fail_after items; later passes run clean. State is
    exposed as wrapper.state ({'fails', 'calls'}) for assertions."""
    state = {'fails': 0, 'calls': 0}

    def wrapper():
        state['calls'] += 1
        if state['fails'] < fail_times:
            state['fails'] += 1
            n = 0
            for item in reader():
                if n >= fail_after:
                    raise exc('injected transient failure %d/%d'
                              % (state['fails'], fail_times))
                yield item
                n += 1
            raise exc('injected transient failure %d/%d (at stream end)'
                      % (state['fails'], fail_times))
        for item in reader():
            yield item
    wrapper.state = state
    return wrapper
