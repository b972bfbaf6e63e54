"""Chaos scenarios for the fleet tests: a small fleet under scheduled
load with a fault thrown at it.

A scenario returns **counts and events only**: requests accepted, lost
and errored, kills performed, scale-outs and scale-ins, quarantines,
heals, handoffs, pages deduplicated, cache misses after warm-up, retry
dispatches against their bound, census peaks. It measures no latency,
percentile, rate or burn rate: these run on a CPU beside other test
workers, and a CPU run gives counts and never a speed (ROADMAP, north
star). The SLO tracker's arithmetic has its own tests at given clocks
(``tests/test_fleet.py``); here the tracker only has to be wired, so
that ``slo.*`` names land in the metrics JSONL.

A scenario takes no sizes: each has one caller, and its constants are
what that test runs.
"""

import contextlib
import os
import tempfile
import threading
import time

import numpy as np


def _fresh():
    import paddle_tpu as fluid
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    return fluid


class ChaosPredictor(object):
    """Duck-typed predictor with a fixed compute floor a batch: a
    replica's capacity (batches a second) stops depending on how fast
    THIS machine's tiny MLP runs."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay_s = delay_s

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def predict(self, feed):
        out = self._inner.predict(feed)
        if self._delay_s:
            time.sleep(self._delay_s)
        return out


def save_chaos_model(in_dim):
    """Save the tiny MLP the chaos scenarios serve; returns its dir."""
    fluid = _fresh()
    model_dir = os.path.join(tempfile.mkdtemp(prefix='fleet_chaos_'),
                             'model')
    x = fluid.layers.data(name='x', shape=[in_dim], dtype='float32')
    h = fluid.layers.fc(input=x, size=16, act='relu')
    out = fluid.layers.fc(input=h, size=4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(model_dir, ['x'], [out], exe)
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    return model_dir


def _counter_sum(snap, prefix, substr=''):
    return sum(v for k, v in snap['counters'].items()
               if k.startswith(prefix) and substr in k)


def _counters_since(snap0):
    """``delta(prefix, substr='')``: how far the counters under
    ``prefix`` moved since ``snap0``."""
    from paddle_tpu import observe
    snap1 = observe.snapshot()
    return lambda prefix, substr='': (
        _counter_sum(snap1, prefix, substr)
        - _counter_sum(snap0, prefix, substr))


@contextlib.contextmanager
def _trace_sample(rate):
    """PADDLE_TPU_TRACE_SAMPLE for the length of a scenario (the
    variable is read per call): sampled requests leave cross-thread
    trace timelines and exemplars."""
    prev = os.environ.get('PADDLE_TPU_TRACE_SAMPLE')
    os.environ['PADDLE_TPU_TRACE_SAMPLE'] = str(rate)
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop('PADDLE_TPU_TRACE_SAMPLE', None)
        else:
            os.environ['PADDLE_TPU_TRACE_SAMPLE'] = prev


def _wait_settled(ledgers, accepted, grace_s):
    """Router callbacks resolve with the inner futures; a grace covers
    the last callback chain after the replicas drained."""
    t_end = time.perf_counter() + grace_s
    while sum(s.ok + s.errors for s in ledgers) < accepted and \
            time.perf_counter() < t_end:
        time.sleep(0.01)


# ------------------------------------------------------------------ fleet
IN_DIM = 8
MAX_BATCH = 8
COMPUTE_DELAY_S = 0.010


def fleet_chaos():
    """A 3-replica router under a diurnal open-loop load with a flash
    crowd, and one replica killed mid-spike
    (``fault.inject.kill_replica``, no drain). Returns the request
    ledger (the zero-loss contract), the kill (readiness before and
    after, what the survivors were dispatched and served after it, the
    failovers it caused: none when the victim's queue was empty at that
    instant) and the sampled-trace census. ``slo.*`` / ``router.*`` metrics land in the
    metrics JSONL."""
    from paddle_tpu import observe
    from paddle_tpu.fault import inject
    from paddle_tpu.inference import create_predictor
    from paddle_tpu.observe.slo import Objective, SloTracker
    from paddle_tpu.serving import (NoReplicaAvailableError, Router,
                                    ServingEngine)
    from paddle_tpu.serving.loadgen import (Stats, diurnal, flash_crowd,
                                            heavy_tailed_rows, open_loop)

    replicas, duration = 3, 3.0
    steady_qps, spike_qps, spike_at, spike_s = 30.0, 700.0, 1.0, 1.0
    kill_at = 1.2
    latency_budget_s = 0.025

    model_dir = save_chaos_model(IN_DIM)
    engines = [ServingEngine(ChaosPredictor(create_predictor(model_dir),
                                            COMPUTE_DELAY_S),
                             max_batch_size=MAX_BATCH,
                             batch_timeout_ms=1.0, max_queue_depth=8,
                             name='replica%d' % i)
               for i in range(replicas)]
    for eng in engines:
        eng.warmup()
        eng.start()
    snap0 = observe.snapshot()

    tracker = SloTracker([Objective('fleet', latency_budget_s,
                                    availability_target=0.95,
                                    window_s=1.0)])
    router = Router(engines, slo=tracker, route='fleet', retries=3)
    schedule = flash_crowd(
        diurnal(steady_qps, 1.25 * steady_qps, period_s=2 * duration),
        spike_qps, spike_at, spike_s)

    stats = Stats()
    submitted = [0]
    no_replica = [0]

    def submit_request(rng):
        rows = heavy_tailed_rows(rng, 1, MAX_BATCH)
        feed = {'x': rng.rand(rows, IN_DIM).astype('float32')}
        try:
            fut = router.submit(feed, session=int(rng.randint(0, 64)),
                                deadline_s=latency_budget_s)
        except NoReplicaAvailableError:
            no_replica[0] += 1
            return None   # counted as a reject in the ledger
        # QueueFullError (incl. SLOShedError) propagates: the loop
        # counts it as a reject
        submitted[0] += 1
        return fut, rows

    victim = engines[-1]
    kill = {'victim': victim.name}
    t0 = time.perf_counter()

    def survivors_dispatched():
        return sum(observe.get_counter('router.dispatch_total',
                                       replica=eng.name, route='fleet')
                   for eng in engines if eng is not victim)

    def killer():
        wait = kill_at - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        kill['at_s'] = time.perf_counter() - t0
        kill['ready_before'] = victim.ready()
        inject.kill_replica(victim, drain=False)
        kill['ready_after'] = victim.ready()
        kill['survivors_dispatched_before'] = survivors_dispatched()

    thread = threading.Thread(target=killer, daemon=True)
    with _trace_sample(0.1):
        thread.start()
        open_loop(submit_request, stats, t0 + duration, schedule)
        for eng in engines:
            if eng is not victim:
                eng.shutdown(drain=True)
        _wait_settled([stats], submitted[0], 10.0)
    thread.join(timeout=5)
    router.close()
    tracker.publish()

    # sampled-trace census: distinct trace ids and the widest thread
    # spread any one of them achieved
    by_trace = {}
    for ev in observe.spans().events():
        tid = (ev.get('args') or {}).get('trace_id')
        if tid and ev.get('ph') == 'X':
            by_trace.setdefault(tid, set()).add(ev.get('tid'))
    delta = _counters_since(snap0)
    accepted = submitted[0]
    completed = stats.ok + stats.errors
    kill['kills'] = delta('fault.replica_kills_total')
    kill['survivors_dispatched_after'] = survivors_dispatched() - \
        kill.pop('survivors_dispatched_before', 0)
    kill['failovers'] = delta('router.failover_total',
                              'replica=%s' % victim.name)
    kill['ok_after'] = stats.counts_between(
        kill.get('at_s', duration), float('inf'))['ok']
    return {
        'replicas': replicas,
        'accepted': accepted,
        'completed': completed,
        'lost': accepted - completed,
        'requests_ok': stats.ok,
        'requests_rejected': stats.rejected,
        'requests_errored': stats.errors,
        'no_replica': no_replica[0],
        'kill': kill,
        'failovers': delta('router.failover_total'),
        'sheds': delta('router.shed_total'),
        'sampled_traces': len(by_trace),
        'max_trace_threads': max(
            [len(tids) for tids in by_trace.values()] or [0]),
    }


# -------------------------------------------------------------- autoscale
RETRY_BUDGET = 0.1
RETRY_BUDGET_BURST = 20.0


def _crash_loop(ctl, t0):
    """Kill ONE slot four times, each kill landing on whatever
    replacement the controller spawned for it."""
    from paddle_tpu.fault import inject
    wait = 0.6 - (time.perf_counter() - t0)
    if wait > 0:
        time.sleep(wait)
    kills = inject.crash_loop(lambda: ctl.current('crash2'),
                              kills=4, interval_s=0.45)
    return {'kills_performed': kills}


def _autoscale_scenarios():
    from paddle_tpu.serving.loadgen import flash_crowd
    return {
        # offered load jumps ~15x: the controller must scale out, with
        # zero accepted-request loss
        'flash': dict(
            qps=flash_crowd(30.0, 500.0, 1.2, 3.0 - 1.2), duration=3.0,
            n_start=2, deadline_s=0.05, chaos=None,
            ctl_kw=dict(min_replicas=2, max_replicas=6, interval_s=0.1,
                        burn_high=1.0, queue_high=3.0,
                        scale_out_cooldown_s=0.35, trough_s=1e9,
                        scale_step=2)),
        # one replica slot is killed repeatedly: the circuit breaker
        # must quarantine the flapping lineage (flight event + counter)
        # after healing it at least once, the survivors losing nothing
        'crash': dict(
            qps=40.0, duration=3.5, n_start=3, deadline_s=None,
            chaos=_crash_loop,
            ctl_kw=dict(min_replicas=2, max_replicas=4, interval_s=0.1,
                        backoff_base_s=0.05, backoff_max_s=0.4,
                        crash_loop_threshold=2, crash_window_s=10.0,
                        quarantine_s=60.0, trough_s=1e9,
                        scale_out_cooldown_s=1e9)),
        # load drops 10x: the controller must scale in by
        # drain-then-shutdown with zero loss and zero errors
        'trough': dict(
            qps=[(0.0, 40.0), (1.0, 4.0)], duration=3.5, n_start=4,
            deadline_s=None, chaos=None,
            ctl_kw=dict(min_replicas=2, max_replicas=4, interval_s=0.1,
                        burn_low=0.5, queue_low=1.5, trough_s=0.6,
                        scale_in_cooldown_s=0.5,
                        scale_out_cooldown_s=1e9, queue_high=1e9,
                        burn_high=1e9)),
    }


def autoscale_chaos(tag):
    """One of the three self-healing scenarios (``'flash'``, ``'crash'``
    or ``'trough'``) through a fresh fleet, one FleetController and a hedging Router:
    open-loop load, a census sampler that also flushes JSONL snapshots
    (so ``tools/metrics_report.py --fleet`` can rebuild the timeline),
    and the scenario's chaos thread. Returns the request ledger, the
    controller's event counters for this scenario alone, the census
    peaks, and the hedging ledger of its traffic: every dispatch past a
    request's primary (hedges and failovers) against the token budget
    ``RETRY_BUDGET x funded + RETRY_BUDGET_BURST``, and how many hedges
    disagreed with their primary. ``funded`` bounds from above the
    requests that passed admission, which is where the router deposits:
    the accepted ones and those every replica's full queue then
    refused (a hedge that found every queue full is counted there too,
    which only loosens the bound)."""
    from paddle_tpu import observe
    from paddle_tpu.inference import create_predictor
    from paddle_tpu.observe.slo import Objective, SloTracker
    from paddle_tpu.serving import (FleetController,
                                    NoReplicaAvailableError, Router,
                                    ServingEngine)
    from paddle_tpu.serving.loadgen import Stats, open_loop

    sc = _autoscale_scenarios()[tag]
    duration = sc['duration']
    # one loaded program and one executor under every replica: the
    # first warmup compiles the ladder, and a spawn's warmup (the
    # scale-up path) is served by the executor's in-memory cache
    pred = ChaosPredictor(create_predictor(save_chaos_model(IN_DIM)),
                          COMPUTE_DELAY_S)

    def make_engine(name):
        """The ReplicaFactory: an engine of its own (queue, batcher,
        thread) over the one loaded model."""
        return ServingEngine(pred, max_batch_size=MAX_BATCH,
                             batch_timeout_ms=1.0, max_queue_depth=12,
                             name=name)

    snap0 = observe.snapshot()
    engines = []
    for i in range(sc['n_start']):
        eng = make_engine('%s%d' % (tag, i))
        eng.warmup()
        eng.start()
        engines.append(eng)
    tracker = SloTracker([Objective(tag, 0.05, availability_target=0.95,
                                    window_s=1.0)])
    router = Router(engines, slo=tracker, route=tag, retries=3,
                    hedge=True, retry_budget=RETRY_BUDGET,
                    retry_budget_burst=RETRY_BUDGET_BURST)
    ctl = FleetController(router, make_engine, slo=tracker, route=tag,
                          name_prefix='%s-auto' % tag, **sc['ctl_kw'])
    ctl.start()

    stats = Stats()
    submitted = [0]
    no_replica = [0]

    def submit_request(rng):
        rows = int(rng.randint(1, MAX_BATCH // 2))
        feed = {'x': rng.rand(rows, IN_DIM).astype('float32')}
        try:
            fut = router.submit(feed, session=int(rng.randint(0, 64)),
                                deadline_s=sc['deadline_s'])
        except NoReplicaAvailableError:
            no_replica[0] += 1
            return None
        submitted[0] += 1
        return fut, rows

    census_peak = {}
    t0 = time.perf_counter()
    stop = threading.Event()

    def sampler():
        last_flush = 0.0
        while not stop.wait(0.05):
            for k, v in ctl.census().items():
                census_peak[k] = max(census_peak.get(k, 0), v)
            now = time.perf_counter()
            if now - last_flush >= 0.25:
                last_flush = now
                observe.flush(kind='snapshot')

    threads = [threading.Thread(target=sampler, daemon=True)]
    chaos_result = {}
    if sc['chaos'] is not None:
        threads.append(threading.Thread(
            target=lambda: chaos_result.update(sc['chaos'](ctl, t0)),
            daemon=True))
    with _trace_sample(0.05):
        for t in threads:
            t.start()
        open_loop(submit_request, stats, t0 + duration, sc['qps'])
        ctl.close()                    # stop ticking before teardown
        for _name, rep in router.replicas():
            rep.shutdown(drain=True)
        _wait_settled([stats], submitted[0], 15.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        ctl.close(shutdown_replicas=True)
        router.close()
    tracker.publish()
    observe.flush(kind='snapshot')

    delta = _counters_since(snap0)
    accepted = submitted[0]
    completed = stats.ok + stats.errors
    retry_dispatches = delta('router.dispatch_total') - accepted
    funded = accepted + no_replica[0] + \
        delta('router.shed_total', 'reason=queue_full')
    bound = RETRY_BUDGET * funded + RETRY_BUDGET_BURST
    return dict({
        'scenario': tag,
        'accepted': accepted,
        'completed': completed,
        'lost': accepted - completed,
        'requests_ok': stats.ok,
        'requests_rejected': stats.rejected,
        'requests_errored': stats.errors,
        'no_replica': no_replica[0],
        'census_peak': census_peak,
        'scale_outs': delta('controller.scale_out_total'),
        'scale_outs_by_burn': delta('controller.scale_out_total',
                                    'reason=burn_rate'),
        'scale_ins': delta('controller.scale_in_total'),
        'heals': delta('controller.heals_total'),
        'deaths': delta('controller.deaths_total'),
        'quarantines': delta('controller.quarantines_total'),
        'spawn_failures': delta('controller.spawn_failures_total'),
        'drain_timeouts': delta('controller.drain_timeouts_total'),
        'hedge': {
            'hedges': delta('router.hedge_total'),
            'failovers': delta('router.failover_total'),
            'retry_dispatches': retry_dispatches,
            'funded': funded,
            'bound': bound,
            'mismatches': delta('router.hedge_mismatch_total'),
        },
    }, **chaos_result)


# ----------------------------------------------------------------- disagg
def disagg_chaos():
    """Disaggregated against colocated at an equal count of engines:
    both legs run three engines, the same weights and the same mixed
    long-prompt / long-decode closed-loop traffic
    (``loadgen.phase_mix``); the disaggregated leg splits the fleet
    into one prefill and two decode engines joined by the zero-copy KV
    handoff, the colocated leg serves both phases on every replica.
    Returns, a leg: the request ledger, executor cache misses after
    warm-up (the handoff installs pages between dispatches and the
    decode side's suffix prefill rides a warmed bucket: no new XLA
    signature on either side), handoffs, pages installed and
    deduplicated, bytes moved, preemptions."""
    from paddle_tpu import observe
    from paddle_tpu.quant.core import resolve_kv_dtype
    from paddle_tpu.serving import PhaseRouter
    from paddle_tpu.serving.decode import (DecodeEngine, LMSpec,
                                           kv_page_bytes, random_weights)
    from paddle_tpu.serving.loadgen import Stats, closed_loop, phase_mix

    duration, clients = 2.5, 6
    n_prefill, n_decode = 1, 2
    vocab, block_size, pages_per_seq = 2048, 16, 32
    shared_prefix, shared_prefix_len = 0.6, 32
    spec = LMSpec(vocab_size=vocab, n_layer=2, n_head=4, d_key=16,
                  d_value=16, d_model=64, d_inner=128)
    weights = random_weights(spec, seed=11)
    # long prompts land in the TOP prefill bucket (the stall a
    # colocated replica suffers); leave room for their short decode
    long_hi = pages_per_seq * block_size - 56
    shared_ids = np.random.RandomState(1234).randint(
        0, vocab, shared_prefix_len).tolist()

    def make_engine(name):
        return DecodeEngine(spec, max_batch=8, block_size=block_size,
                            num_blocks=256, pages_per_seq=pages_per_seq,
                            max_queue_depth=8 * clients,
                            prefix_cache=True, weights=weights,
                            name=name)

    def run_leg(tag, disagg):
        n_pre = n_prefill if disagg else 0
        n_dec = n_decode if disagg else n_prefill + n_decode
        pre = [make_engine('%s-pf%d' % (tag, i)) for i in range(n_pre)]
        dec = [make_engine('%s-dc%d' % (tag, i)) for i in range(n_dec)]
        for e in pre + dec:
            e.warmup()
            e.start()
        router = PhaseRouter(pre, dec, route=tag, colocated=not disagg,
                             max_inflight=4 * clients)
        # the zero-recompile window opens AFTER warmup: anything from
        # here on is a live-traffic signature the invariant forbids
        snap0 = observe.snapshot()
        stats = Stats()
        mu = threading.Lock()
        ledger = {'accepted': 0, 'completed': 0, 'tokens': 0}

        def do_request(rng):
            plen, max_new = phase_mix(rng, long_prompt_frac=0.35,
                                      long_prompt=(long_hi - 32, long_hi))
            if rng.rand() < shared_prefix:
                tail = max(1, plen - shared_prefix_len)
                prompt = shared_ids + \
                    rng.randint(0, vocab, tail).tolist()
            else:
                prompt = rng.randint(0, vocab, plen).tolist()
            stream = router.submit(prompt, max_new_tokens=max_new,
                                   seed=int(rng.randint(1 << 20)),
                                   session=int(rng.randint(0, 16)))
            with mu:
                ledger['accepted'] += 1
            n = sum(1 for _tok in stream)
            with mu:
                ledger['completed'] += 1
                ledger['tokens'] += n
            return n

        closed_loop(do_request, stats, time.perf_counter() + duration,
                    clients)
        router.close(shutdown_replicas=True)
        delta = _counters_since(snap0)
        return dict(ledger, **{
            'fleet': tag,
            'engines': n_pre + n_dec,
            'prefill_replicas': n_pre,
            'decode_replicas': n_dec,
            'lost': ledger['accepted'] - ledger['completed'],
            'requests_ok': stats.ok,
            'requests_rejected': stats.rejected,
            'requests_errored': stats.errors,
            'post_warmup_cache_misses':
                delta('executor.cache_miss_total'),
            'handoffs': delta('handoff.count_total'),
            'handoff_pages_installed':
                delta('handoff.pages_installed_total'),
            'handoff_pages_deduped':
                delta('handoff.pages_deduped_total'),
            'handoff_bytes': delta('handoff.bytes_total'),
            'preemptions': delta('decode.preemptions_total'),
        })

    observe.flush(kind='snapshot')
    coloc = run_leg('coloc', disagg=False)
    observe.flush(kind='snapshot')
    split = run_leg('disagg', disagg=True)
    observe.flush(kind='snapshot')
    kv = resolve_kv_dtype(None)
    return {
        'colocated': coloc,
        'disaggregated': split,
        'kv_dtype': kv,
        'page_wire_bytes': kv_page_bytes(spec, block_size, kv),
        'page_wire_bytes_fp32': kv_page_bytes(spec, block_size,
                                              'float32'),
    }


# ------------------------------------------------------------ multitenant
def multitenant_chaos():
    """Four scenarios through the ``serving.tenancy`` policy layer:

    1. **noisy neighbor**: an interactive tenant alone, then beside a
       batch tenant flooding ten times its request quota: the token
       bucket sheds the flood at admission and the interactive tenant
       loses nothing.
    2. **quota exhaustion**: a tenant offered well past its quota:
       every shed is the typed ``QuotaExceededError`` (never a bare
       queue-full), and the traffic that WAS admitted loses nothing.
    3. **priority inversion**: a decode engine whose KV pool the batch
       class has saturated receives interactive arrivals: exhaustion
       preempts only batch sequences, and every interactive request
       completes.
    4. **co-location**: a background fine-tuning Trainer shares the
       host with serving; traffic that breaks the SLO drives the burn
       rate past 1 and ``colocation_yield`` yields the trainer
       (``tenant.trainer_yields_total``), calm resumes it, and the
       final params are bit-identical to an uninterrupted run.

    ``tenant.admitted/shed/preempted/evicted_pages`` land in the
    metrics JSONL; ``tools/metrics_report.py --tenants`` renders the
    panel."""
    from paddle_tpu import observe
    from paddle_tpu.inference import create_predictor
    from paddle_tpu.observe.slo import Objective, SloTracker
    from paddle_tpu.serving import (FleetController, QueueFullError,
                                    NoReplicaAvailableError,
                                    QuotaExceededError, Router,
                                    ServingEngine, TenantRegistry,
                                    colocation_yield, slo_burn_pressure)
    from paddle_tpu.serving.loadgen import Stats, open_loop

    mix_duration = quota_duration = 1.5
    batch_quota_rps, quota_rps = 10.0, 8.0
    inv_batch_new, inv_inter_new = 28, 8
    train_batches, train_split = 8, 3
    model_dir = save_chaos_model(IN_DIM)

    def make_engine(name):
        pred = ChaosPredictor(create_predictor(model_dir), 0.008)
        return ServingEngine(pred, max_batch_size=MAX_BATCH,
                             batch_timeout_ms=1.0, max_queue_depth=16,
                             name=name)

    def run_mix(tag, registry, traffic, duration, n_engines=2):
        """Open-loop pacers, one a tenant (``traffic`` is
        ``[(tenant, qps, sessions)]``), through one quota-equipped
        Router. Returns each tenant's admission ledger."""
        snap0 = observe.snapshot()
        engines = []
        for i in range(n_engines):
            eng = make_engine('%s%d' % (tag, i))
            eng.warmup()
            eng.start()
            engines.append(eng)
        router = Router(engines, route=tag, tenants=registry)
        t0 = time.perf_counter()
        per, threads = {}, []
        for seed, (name, qps, sessions) in enumerate(traffic):
            led = {'stats': Stats(t0), 'submitted': 0, 'typed': 0,
                   'untyped': 0}

            def submit_request(rng, name=name, sessions=sessions,
                               led=led):
                feed = {'x': rng.rand(1, IN_DIM).astype('float32')}
                session = '%s/s%d' % (name, int(rng.randint(sessions)))
                try:
                    fut = router.submit(feed, session=session)
                except QuotaExceededError:
                    led['typed'] += 1
                    return None
                except (QueueFullError, NoReplicaAvailableError):
                    led['untyped'] += 1
                    return None
                led['submitted'] += 1
                return fut, 1

            per[name] = led
            threads.append(threading.Thread(
                target=open_loop,
                args=(submit_request, led['stats'], t0 + duration, qps),
                kwargs=dict(seed=101 + seed), daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for eng in engines:
            eng.shutdown(drain=True)
        _wait_settled([led['stats'] for led in per.values()],
                      sum(led['submitted'] for led in per.values()), 15.0)
        router.close()
        delta = _counters_since(snap0)
        out = {'scenario': tag, 'tenants': {}}
        for name, led in per.items():
            s = led['stats']
            out['tenants'][name] = {
                'offered': led['submitted'] + s.rejected,
                'admitted': led['submitted'],
                'ok': s.ok,
                'errors': s.errors,
                'lost': led['submitted'] - (s.ok + s.errors),
                'quota_sheds': led['typed'],
                'untyped_rejects': led['untyped'],
                'shed_counter': delta('tenant.shed',
                                      'tenant=%s' % name),
            }
        return out

    # 1 — noisy neighbor: batch flood beside the interactive tenant
    def mk_registry():
        reg = TenantRegistry()
        reg.add('fg', priority='interactive')
        reg.add('bg', priority='batch', request_rate=batch_quota_rps)
        return reg

    solo = run_mix('nnsolo', mk_registry(), [('fg', 25.0, 8)],
                   mix_duration)
    mixed = run_mix('nnmix', mk_registry(),
                    [('fg', 25.0, 8), ('bg', 10 * batch_quota_rps, 8)],
                    mix_duration)

    # 2 — quota exhaustion: typed sheds, zero loss for admitted work
    reg = TenantRegistry()
    reg.add('acme', priority='standard', request_rate=quota_rps)
    quota = run_mix('quota', reg, [('acme', 40.0, 4)], quota_duration,
                    n_engines=1)

    # 3 — priority inversion: batch saturates the KV pool, then
    # interactive arrives; only batch may be preempted
    def run_inversion():
        from paddle_tpu.serving.decode import DecodeEngine, LMSpec
        spec = LMSpec(vocab_size=256, n_layer=1, n_head=2, d_key=8,
                      d_value=8, d_model=16, d_inner=32)
        # 3 batch seqs want 3*ceil((8+inv_batch_new)/4) pages >> 24:
        # exhaustion mid-decode is guaranteed while batch runs
        engine = DecodeEngine(spec, max_batch=4, block_size=4,
                              num_blocks=24, pages_per_seq=16,
                              max_queue_depth=16)
        engine.warmup()
        engine.start()
        before = observe.snapshot()
        rng = np.random.RandomState(5)
        batch_streams = [
            engine.submit(rng.randint(0, 256, 8).tolist(),
                          max_new_tokens=inv_batch_new, seed=i,
                          tenant='bulk', priority='batch')
            for i in range(3)]
        time.sleep(0.25)       # let the batch class occupy the pool
        inter_streams = [
            engine.submit(rng.randint(0, 256, 8).tolist(),
                          max_new_tokens=inv_inter_new, seed=10 + i,
                          tenant='fg', priority='interactive')
            for i in range(2)]
        inter_lens = [len(s.result(timeout=300)) for s in inter_streams]
        batch_lens = [len(s.result(timeout=300)) for s in batch_streams]
        engine.shutdown(drain=True)
        delta = _counters_since(before)
        return {
            'scenario': 'inversion',
            'preempted_batch': delta('tenant.preempted',
                                     'priority=batch'),
            'preempted_interactive': delta('tenant.preempted',
                                           'priority=interactive'),
            'interactive_tokens': inter_lens,
            'interactive_tokens_asked': inv_inter_new,
            'batch_tokens': batch_lens,
        }

    inversion = run_inversion()

    # 4 — co-location: SLO pressure yields the trainer, calm resumes
    # it, params stay bit-identical to the uninterrupted run
    def make_batches():
        rng = np.random.RandomState(3)
        w = rng.randn(4, 1).astype('float32')
        r = np.random.RandomState(4)
        out = []
        for _ in range(train_batches):
            xs = r.randn(8, 4).astype('float32')
            out.append({'x': xs, 'y': xs @ w})
        return out

    def train_run(fluid, reader, hooks=None):
        """One fresh linreg training run; ``hooks(trainer)`` runs
        between construction and train() (the colo leg wires the
        controller there). Returns the final persistables."""
        from paddle_tpu import io as _io

        def train_func():
            x = fluid.layers.data(name='x', shape=[4], dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            pred = fluid.layers.fc(input=x, size=1)
            return [fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))]

        trainer = fluid.Trainer(
            train_func=train_func,
            optimizer_func=lambda: fluid.optimizer.SGD(
                learning_rate=0.1),
            place=fluid.CPUPlace())
        done = hooks(trainer) if hooks is not None else None
        trainer.train(num_epochs=1, event_handler=lambda e: None,
                      reader=reader)
        arrays, _ = _io._snapshot_vars(trainer.program,
                                       predicate=_io._is_persistable)
        arrays = {k: np.array(v) for k, v in arrays.items()}
        if done is not None:
            done()
        return arrays

    def run_colocation():
        batches = make_batches()
        base = train_run(_fresh(), lambda: iter(batches))

        gate_hit, gate_go = threading.Event(), threading.Event()

        def gated_reader():
            for i, b in enumerate(batches):
                if i == train_split:
                    gate_hit.set()
                    gate_go.wait(timeout=120)
                yield b

        tracker = SloTracker([Objective(
            'colo', 0.002, availability_target=0.5, window_s=1.2)])
        engine = make_engine('colo0')
        engine.warmup()
        engine.start()
        # admission='none': the tracker must SEE every breach (burn is
        # the yield signal here) — SLO admission would shed the chaos
        # burst before it ever recorded a violation
        router = Router([engine], slo=tracker, route='colo',
                        admission='none')
        seen = {}
        fluid = _fresh()

        def hooks(trainer):
            pf, cf = colocation_yield(
                trainer, *slo_burn_pressure(tracker, 'colo'),
                route='colo')
            ctl = FleetController(router, make_engine, slo=tracker,
                                  route='colo', min_replicas=1,
                                  max_replicas=1, interval_s=0.05,
                                  pressure_fn=pf, calm_fn=cf)
            ctl.start()

            def chaos():
                # trainer is mid-run, parked at the reader gate with
                # the pipeline drained of steps [0, train_split)
                gate_hit.wait(timeout=120)
                # burn the budget: every request breaches the 2ms
                # deadline by construction (8ms compute floor)
                rng = np.random.RandomState(11)
                for _ in range(20):
                    feed = {'x': rng.rand(1, IN_DIM).astype('float32')}
                    router.submit(feed, session='fg/s0').result(
                        timeout=30)
                t_dead = time.perf_counter() + 5.0
                while time.perf_counter() < t_dead:
                    if observe.get_counter('tenant.trainer_yields_total',
                                           route='colo'):
                        seen['yielded'] = True
                        break
                    time.sleep(0.002)
                gate_go.set()      # loop resumes, sees the request,
                t_dead = time.perf_counter() + 10.0   # drains, parks
                while time.perf_counter() < t_dead:
                    if trainer.yielded():
                        seen['parked'] = True
                        break
                    time.sleep(0.002)
                # calm: no more traffic — the violation window slides
                # out, burn drops, the controller resumes the trainer
                # (train() returning IS the resume evidence)

            th = threading.Thread(target=chaos, daemon=True)
            th.start()

            def done():
                th.join(timeout=60)
                seen['resumed'] = not trainer.yielded()
                ctl.close(shutdown_replicas=False)
            return done

        colo_params = train_run(fluid, gated_reader, hooks=hooks)
        engine.shutdown(drain=True)
        router.close()
        bit_identical = set(colo_params) == set(base) and all(
            np.array_equal(colo_params[k], base[k]) for k in base)
        return {
            'scenario': 'colocation',
            'train_steps': len(batches),
            'bit_identical': bit_identical,
            'yielded': seen.get('yielded', False),
            'parked': seen.get('parked', False),
            'resumed': seen.get('resumed', False),
        }

    return {
        'noisy_neighbor': {'solo': solo, 'mixed': mixed},
        'quota_exhaustion': quota,
        'priority_inversion': inversion,
        'colocation': run_colocation(),
    }
