#!/usr/bin/env python3
"""One run of one benchmark cell; the last line of stdout is the result.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearsal]

The cell, its configuration, traffic, runner, per-layer metrics and
their readers are all found by name through BENCHMARK.json (see
manifest.py and README.md). This file knows no cell: it checks the
device, arms the compile cache, hands the runner a Context that keeps
the window, the spans, the compile counter and the profiler, reduces
the trace, and prints the contract's JSON line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
before building anything. ``--rehearsal`` (explicit, never automatic)
asks for the host CPU by name and takes the tiny sizes of the config's
and the traffic's ``rehearsal`` blocks: it exists to debug the harness
without a chip, stamps ``platform=cpu`` and reports every time, rate
and share as null.

A traced run on the chip is made by a child of this process, which
itself never starts jax (``supervise``): where the profiler hands back
no device line for the traced tail, or the tail was never reached, the
child leaves with ``TRACE_LOST`` as the window closes, before its
checks, and the whole run is made once more, in a new process on the
same seed. A second loss prints the line as it is, with the reason
under ``device.trace_lost``. Untraced runs and rehearsals are one
process, as they were.
"""

import time
T_PROCESS = time.perf_counter()        # set-up is clocked from here

import argparse            # noqa: E402
import contextlib          # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import shutil              # noqa: E402
import signal              # noqa: E402
import subprocess          # noqa: E402
import sys                 # noqa: E402
import tempfile            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import manifest, tracelib            # noqa: E402

TRACE_SECONDS = 3.0        # the traced tail of the window
WINDOW_SPAN = 'bench.window'
# the host spans an idle gap of the device is named after (regexes; where
# they nest the innermost owns the time under it): the train runner's
# two, the decode worker's and the executor's. Not the ring's
# ``decode.device_empty``: it is the engine's account of the device, cut
# where programs arrive and not where the worker's spans nest
GAP_LABELS = (r'^bench\.(dispatch|wait_oldest)$',
              r'^decode\.(?!device_empty$)', r'^executor\.')
COMPILE_EVENTS = ('/jax/compilation_cache/cache_hits',
                  '/jax/compilation_cache/cache_misses')
COMPILE_DURATION = '/jax/core/compile/backend_compile_duration'
TRACE_LOST = 75            # a child's exit code: make the run again
ATTEMPTS = 2               # of a traced run on the chip, at the most


class TraceLost(Exception):
    """The traced tail holds nothing of the device, and the run may be
    made again."""


class Context(object):
    """What a runner is given: the cell's data, and the window."""

    def __init__(self, resolved, args, root):
        self.cell = resolved['cell']
        self.config = resolved['config']
        self.traffic = resolved['traffic']
        self.reference = manifest.load_module(resolved['reference'])
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.rehearsal)
        # a supervised child that is not the last gives up a lost trace
        self.may_retry = 0 < getattr(args, 'attempt', 0) < ATTEMPTS
        self.trace_lost = None         # why, where the tail gave no device line
        self.root = root
        self.spans = {}
        self.samples = {}
        self.sources = {}
        self.setup_s = None
        self.t_window = None
        self.t_trace = None            # perf_counter when tracing began
        # the trace's directory is this run's alone (under TMPDIR, made as
        # the tail begins): two runs of one checkout at a time, as the
        # tests' workers make them, must not remove or read each other's
        self._trace_dir = None
        self._window_span = None
        self._compiles = 0
        self.compiles_in_window = None
        self.registry = [None, None]
        self.registry_tail = None      # the registry as the traced tail began
        self.memory = None             # memory_stats() as the window ends

    def sized(self, block):
        """A config's or traffic's block with its ``rehearsal`` overrides
        laid over it under --rehearsal."""
        out = {k: v for k, v in block.items() if k != 'rehearsal'}
        if self.rehearsal:
            out.update(block.get('rehearsal', {}))
        return out

    # -------------------------------------------------------------- spans
    @contextlib.contextmanager
    def span(self, name):
        """A host span of the benchmark's own: its duration is kept for
        the span readers, and while the profiler runs it is also written
        into the trace, where the idle gaps are named after it."""
        note = None
        if self.t_trace is not None:
            import jax
            note = jax.profiler.TraceAnnotation(name)
            note.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append(
                time.perf_counter() - t0)
            if note is not None:
                note.__exit__(None, None, None)

    # ------------------------------------------------------------- window
    def on_compile(self, event, *_, **__):
        if event in COMPILE_EVENTS or event == COMPILE_DURATION:
            self._compiles += 1

    def begin_window(self):
        """Everything before this instant is set-up."""
        self.spans.clear()
        self.samples.clear()
        self.registry[0] = self._snapshot()
        self._compiles = 0
        self.t_window = time.perf_counter()
        self.setup_s = self.t_window - T_PROCESS
        return self.t_window

    def window_left(self):
        return self.t_window + self.seconds - time.perf_counter()

    def tick(self):
        """Called by the runner inside its loop: starts the profiler when
        the traced tail of the window begins, and keeps the registry as
        it stands then: a reader that sets a counter against the trace
        takes it from there to ``end_window``'s snapshot, the interval
        that ``trace['window']`` is cut from. An untraced run returns at
        the first line."""
        if not self.trace or self.t_trace is not None:
            return
        tail = min(TRACE_SECONDS, self.seconds / 2.0)
        if self.window_left() > tail:
            return
        import jax
        if self._trace_dir is None:
            # a name only: the profiler makes the directory it writes to
            self._trace_dir = os.path.join(
                tempfile.gettempdir(), 'bench_trace_%d_%d'
                % (os.getpid(), time.perf_counter_ns()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        t0 = time.perf_counter()
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self.t_trace = time.perf_counter()
        self._trace_start_s = self.t_trace - t0
        self.registry_tail = self._snapshot()
        self._window_span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window_span.__enter__()

    def end_window(self):
        self.compiles_in_window = self._compiles
        self.registry[1] = self._snapshot()
        import jax
        self.memory = [d.memory_stats() or {}
                       for d in jax.devices()[:self.cell['chips']]]
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self._trace_stop_s = time.perf_counter() - t0
        if self.trace and not self.rehearsal:
            self.trace_lost = self._why_lost()
        if self.trace_lost:
            say('TRACE_LOST', why=self.trace_lost, may_retry=self.may_retry)
            if self.may_retry:
                shutil.rmtree(self._trace_dir or '', ignore_errors=True)
                raise TraceLost(self.trace_lost)

    def _why_lost(self):
        """None where the traced tail holds ops of every chip the cell
        runs on, else what it lacks, in words. A look at the file's
        planes and lines, not a reading: it is made as the window
        closes, on the thread that still polls the requests in flight."""
        if self.t_trace is None:
            return ('the window closed before tick() could start the '
                    'profiler: the runner\'s thread stood still through '
                    'its last %.1f s' % min(TRACE_SECONDS, self.seconds / 2))
        t0 = time.perf_counter()
        path = tracelib.find_xplane(self._trace_dir)
        chips = tracelib.chips_with_ops(path) if path else []
        say('TRACE_SESSION', start_s=self._trace_start_s,
            stop_s=self._trace_stop_s, look_s=time.perf_counter() - t0,
            file_bytes=os.path.getsize(path) if path else None,
            chips_with_ops=chips)
        if len(chips) >= self.cell['chips']:
            return None
        return ('the profiler took %.3f s to start and %.3f s to stop, and '
                '%s' % (self._trace_start_s, self._trace_stop_s,
                        'its trace holds device ops of chips %s where the '
                        'cell runs on %d' % (chips, self.cell['chips'])
                        if path else 'wrote no trace'))

    def _snapshot(self):
        if not self.trace:
            return None
        from paddle_tpu import observe
        return observe.snapshot()

    def take_trace(self):
        """The reduced trace, or None; the directory is removed."""
        if self._trace_dir is None:
            return None
        try:
            path = tracelib.find_xplane(self._trace_dir)
            return tracelib.read_xplane(path) if path else None
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


def say(tag, **fields):
    print('%s %s' % (tag, json.dumps(fields, sort_keys=True)), flush=True)


def device_stamp(chips, rehearsal):
    """jax's first device and the count, or exit 2 with no result."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.stderr.write('benchmark: jax found no device: %s\n' % e)
        sys.exit(2)
    dev = devs[0]
    stamp = {'platform': dev.platform, 'kind': dev.device_kind,
             'count': len(devs)}
    if not rehearsal and (dev.platform != 'tpu' or len(devs) < chips):
        sys.stderr.write(
            'benchmark: the cell needs %d TPU chip(s), jax found platform '
            '%r (%s x%d). Nothing was run.\n'
            % (chips, dev.platform, dev.device_kind, len(devs)))
        sys.exit(2)
    return stamp


def memory_fields(stats):
    """The line's memory keys from each used chip's ``memory_stats()``
    as the window ended (what a check allocates after the window is not
    the cell's). The allocator's ``bytes_in_use`` holds arrays only; a
    program's scratch is reserved apart, out of what the arrays leave
    free, and stays reserved between steps (the runtime said so when a
    program did not load: "Attempting to reserve 13.12G at the bottom of
    memory ... There are 12.17G free", with 4.7 GB of arrays on a
    16.9 GB chip; PERF.md, PR 24). ``memory_peak_bytes`` is the two read
    at one instant and added: what the chip holds while the cell runs.
    The allocator's own two peaks, which need not coincide (set-up's
    arrays, the window's scratch), are given beside it."""
    for i, one in enumerate(stats):
        say('MEMORY', device=i, **one)
    full = [s for s in stats if 'bytes_in_use' in s]
    if not full:
        return {'memory_peak_bytes': None}
    top = max(full, key=lambda s: s['bytes_in_use']
              + s.get('bytes_reserved', 0))
    return {'memory_peak_bytes': int(top['bytes_in_use'])
            + int(top.get('bytes_reserved', 0)),
            'memory_arrays_peak_bytes': top.get('peak_bytes_in_use'),
            'memory_reserved_peak_bytes': top.get('peak_bytes_reserved'),
            'memory_limit_bytes': top.get('bytes_limit')}


def reduce_trace(ctx, chips):
    """(device fields, breakdown, trace for the readers) of the run's
    trace; all None where no device line was recorded."""
    trace = ctx.take_trace()
    if not trace:
        return {}, None, None
    say('TRACE_LINES', **trace['lines'])
    window = tracelib.window_of(trace, WINDOW_SPAN)
    used = sorted(trace['devices'])[:chips]
    if window is None or not used:
        return {}, None, trace
    lo, hi = window
    busy = [tracelib.busy_ns(trace['devices'][i], lo, hi) for i in used]
    fields = {'busy_s': sum(busy) / len(busy) / 1e9,
              'window_s': (hi - lo) / 1e9}
    first = trace['devices'][used[0]]
    breakdown = {'device_ops': tracelib.top_ops(first, lo, hi, 10)}
    trace['window'] = (lo, hi)
    trace['first'] = first
    return fields, breakdown, trace


def named_idle_gaps(sources):
    """``breakdown.idle_gaps``: the longest idle gaps of the traced tail,
    each named after the host span that owns most of it. The spans are
    the trace's own and, where the program's ring can be laid over the
    trace (``tracelib.ring_on_trace``: the offset is measured once a run
    and kept in ``sources`` for the readers), the ring's as well: an
    idle wait that an edge of the tail cut is in the ring alone."""
    trace = sources['trace']
    ring = tracelib.ring_on_trace(sources) or []
    host = trace['host'] + [(name, s, e - s) for name, s, e in ring]
    return tracelib.idle_gaps(trace['first'], host, *trace['window'],
                              GAP_LABELS, 5)


def arguments(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--rehearsal', action='store_true',
                    help='tiny sizes on the host CPU; times are null')
    ap.add_argument('--attempt', type=int, default=0, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def supervise(argv, spawn=subprocess.Popen):
    """A traced run on the chip, made by a child and made again, once,
    where the child leaves with TRACE_LOST; the exit code of the last.
    This process never starts jax, so the chip is the child's alone; the
    child writes to this process's own stdout and stderr, and is ended
    and waited for on every way out of here."""
    def leave(signum, _):
        sys.exit(128 + signum)
    signal.signal(signal.SIGTERM, leave)
    code = TRACE_LOST
    for attempt in range(1, ATTEMPTS + 1):
        child = spawn([sys.executable, os.path.abspath(__file__)]
                      + list(argv) + ['--attempt', str(attempt)])
        try:
            code = child.wait()
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if code != TRACE_LOST:
            break
        sys.stderr.write('benchmark: attempt %d lost its trace; the run is '
                         'made again\n' % attempt)
    return code if code >= 0 else 128 - code


def die_with_parent():
    """A supervised child is not left behind where its parent is killed
    outright (prctl PR_SET_PDEATHSIG, Linux; elsewhere nothing)."""
    try:
        import ctypes
        ctypes.CDLL(None).prctl(1, int(signal.SIGKILL))
    except (OSError, AttributeError):
        pass


def main(argv=None, root=None):
    args = arguments(argv)
    if args.attempt:
        die_with_parent()

    root = root or os.path.dirname(HERE)
    m = manifest.load(root)
    resolved = manifest.resolve(m, args.workload)
    chips = resolved['cell']['chips']
    if root not in sys.path:
        sys.path.insert(0, root)

    # importing the package does not start jax's backend
    from paddle_tpu import observe
    from paddle_tpu.core.platform_boot import (arm_compile_cache,
                                               force_host_cpu)
    # the XLA cost probe would compile every program a second time
    os.environ.setdefault('PADDLE_TPU_OBSERVE_COST', '0')
    if args.rehearsal:
        force_host_cpu(8)
    import jax
    device = device_stamp(chips, args.rehearsal)
    if args.rehearsal:
        print('REHEARSAL platform=%s' % device['platform'], flush=True)
    say('DEVICE', **device)

    peaks = manifest.read_json(os.path.join(m['_dir'], 'peaks.json'))
    if device['kind'] in peaks['devices']:
        peaks = peaks['devices'][device['kind']]
    elif args.rehearsal:
        peaks = None
    else:
        sys.stderr.write('benchmark: no peaks for device kind %r in '
                         'peaks.json\n' % device['kind'])
        return 2

    arm_compile_cache()
    say('COMPILE_CACHE', dir=jax.config.jax_compilation_cache_dir)
    if args.trace:
        observe.enable()
    ctx = Context(resolved, args, root)
    jax.monitoring.register_event_listener(ctx.on_compile)
    jax.monitoring.register_event_duration_secs_listener(ctx.on_compile)

    runner = manifest.load_module(resolved['runner'])
    result = runner.run(ctx)
    if ctx.setup_s is None or ctx.compiles_in_window is None:
        raise RuntimeError('runner %s never opened or closed its window'
                           % resolved['runner'])

    measured = dict(result['end_to_end'], setup_s=ctx.setup_s)
    correct = bool(result['correct']) and ctx.compiles_in_window == 0
    say('WINDOW', setup_s=ctx.setup_s,
        compiles_in_window=ctx.compiles_in_window,
        **result.get('notes', {}))
    if not args.rehearsal:
        # everything the runner measured, also what the manifest does not
        # name for this cell: the spreads of candidates are read from it
        say('MEASURED', **measured)

    device.update(memory_fields(ctx.memory or []))
    out = {'correct': correct, 'attempted': int(result['attempted']),
           'failed': int(result['failed']), 'metrics': {},
           'device': device}
    if args.trace:
        t_reduce = time.perf_counter()
        fields, breakdown, trace = reduce_trace(ctx, chips)
        device.update(fields)
        if not fields and not args.rehearsal:
            # the check refuses such a line; it says why it is so
            device['trace_lost'] = ctx.trace_lost or 'no window in the trace'
        sources = dict(
            ctx.sources, spans=ctx.spans, samples=ctx.samples,
            registry_before=ctx.registry[0], registry_after=ctx.registry[1],
            registry_tail=ctx.registry_tail, trace=trace, measured=measured,
            config=ctx.config, traffic=ctx.traffic, cell=ctx.cell,
            peaks=peaks, bench_dir=m['_dir'])
        if breakdown:
            breakdown['idle_gaps'] = named_idle_gaps(sources)
            out['breakdown'] = breakdown
        for metric in resolved['per_layer']:
            value = manifest.load_module(metric['reader']).read(
                metric['spec'].get('args', {}), sources)
            if value is not None:
                out['metrics'][metric['entry']['name']] = {
                    'value': value, 'unit': metric['entry']['unit'],
                    'source': metric['entry']['source']}
        # what a traced run pays after its window for the per-layer line
        say('READERS', listed=len(resolved['per_layer']),
            read=len(out['metrics']),
            seconds=time.perf_counter() - t_reduce)
    else:
        for entry in resolved['end_to_end']:
            out['metrics'][entry['name']] = {
                'value': measured[entry['name']], 'unit': entry['unit'],
                'source': entry['source']}
    if args.rehearsal:
        # a CPU's clock is never written under a device metric's name
        out['rehearsal'] = True
        for metric in out['metrics'].values():
            if metric['source'] != 'program_counter':
                metric['value'] = None
    for metric in out['metrics'].values():
        del metric['source']
    print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    given = arguments()
    if given.trace and not given.rehearsal and not given.attempt:
        sys.exit(supervise(sys.argv[1:]))
    try:
        sys.exit(main())
    except TraceLost:
        # the engine's threads and the programs in flight go with the
        # process; the parent makes the run again
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(TRACE_LOST)
    except Exception:
        # a program the chip refuses to load leaves the TPU runtime unable
        # to shut down: the interpreter then hangs at exit until the
        # caller's limit (PERF.md, PR 24). Say what failed and leave.
        import traceback
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
