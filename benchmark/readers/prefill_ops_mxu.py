"""Share, in percent, of the chip's matrix peak that a family of ops
reaches inside prefill chunks: the least operations those chunks need,
which a named shape function counts, over a peak, against the device
time the ops took, both sides over the same chunks of the traced tail.

Counted by chunk, not by prefill: a first ask's prefill of 65 chunks
takes longer than the traced tail, so whichever edge it straddles, the
chunks that ran inside the tail are read and the others are not. A
chunk is one run of a prefill program on the device (an event of the
trace's ``XLA Modules`` line, which ``tracelib.read_xplane`` does not
keep: the runner reads it with ``program_runs`` before the trace is
reduced and hands it over as ``sources['prefill_program_runs']``). The
host enqueues a prefill's chunks back to back and waits for the last
only, so a chunk's host span says nothing of when it ran; what holds is
the order: the device runs the prefill programs in the order the worker
dispatched them. The runner hands over every chunk the worker
dispatched, in order (``sources['prefill_chunks']``: the prefill it is
of, that prefill's ``decode.prefill.run`` span by the recorder's clock
relative to the profiler's start, the chunk's bucket and its
``attn_pairs``). One prefill whose span lies whole inside the tail
anchors the two orders (its span in the trace, matched to the
recorder's by start and duration; its first program run is its first
chunk), and every other run is its neighbour's neighbour. Each matched
run's program has to be its chunk's bucket's, and the runs under every
whole span have to be that prefill's chunks, else nothing is read. The
anchor's first run is the first that starts at or after its span; the
profiler's alignment of the device's clock with the host's can put it
just before the span instead, so the two neighbouring shifts are tried
too and the one kept under which both conditions hold (``shift`` on the
line below: 0, or 1 where the run before was the first chunk's).

What is not read is said: a line ``PREFILL_CHUNKS`` with the runs read,
the shift, and the runs of the tail that were dropped (cut by an edge of the
window or by the profiler's stop, or with no chunk to match), and why
where nothing was read (no prefill whole inside the tail: one prefill
longer than it; a program whose spans carry no count: the parent).

Nothing is clipped: a share above 100 means the operations are counted
too high or the ops too few.
args: {"function": shape function with least_flops(pairs, config),
"match": [regex, ...], "program": regex whose group 1 is the bucket in
a program run's name, "peak": key of peaks.json}."""

import bisect
import json
import os
import re

from benchmark import manifest, tracelib

SPAN = 'decode.prefill.run'
MODULES_LINE = 'XLA Modules'
# the recorder's clock against the trace's: the profiler's window span
# opens a registry snapshot after the instant the runner took
START_SLACK_S = 0.25
DUR_SLACK_S = 0.001
# the device's clock against the host's inside one trace: a prefill's
# first program run was seen up to the 0.5-1.8 ms the host needs from
# the span's start to the dispatch *before* its span (PERF.md, PR 37)
EDGE_SLACK_NS = 2000000


def program_runs(path):
    """[(name, start_ns, dur_ns)] of the whole programs the first chip
    ran, from the ``.xplane.pb`` at ``path``; [] where there is none."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        dev = tracelib.DEVICE_PLANE.match(plane.name)
        if dev:
            planes[int(dev.group(1))] = plane
    if not planes:
        return []
    return sorted(
        ((ev.name, int(ev.start_ns), int(ev.duration_ns))
         for line in planes[min(planes)].lines if line.name == MODULES_LINE
         for ev in line.events), key=lambda ev: ev[1])


def anchor(chunks, whole, lo):
    """(index in ``chunks`` of a prefill's first chunk, that prefill's
    span in the trace) for the first span of ``whole`` that exactly one
    dispatched prefill matches by start and duration, else None."""
    firsts = {}
    for i, c in enumerate(chunks):
        firsts.setdefault(c['run'], i)
    for s, e in whole:
        near = [i for i in firsts.values()
                if abs(chunks[i]['dur'] - (e - s) / 1e9) < DUR_SLACK_S
                and abs(chunks[i]['t'] - (s - lo) / 1e9) < START_SLACK_S]
        if len(near) == 1:
            return near[0], (s, e)
    return None


def chunks_read(chunks, runs, host, window, program, last_op_start):
    """({'read', 'dropped', 'why'; 'shift' where runs were read},
    [(pairs, start_ns, end_ns)] of the program runs whole inside
    ``window`` with the chunk each is). The
    profiler cuts the run it stops in to where it stopped, inside the
    window's last millisecond: a run is whole only if the chip started
    an op after it (``last_op_start``)."""
    lo, hi = window
    hi = min(hi, last_op_start)
    rx = re.compile(program)
    runs = [(rx.search(name), s, s + d) for name, s, d in runs]
    runs = [(int(m.group(1)), s, e) for m, s, e in runs if m]
    inside = [r for r in runs if r[2] > lo and r[1] < hi]
    said = {'read': 0, 'dropped': len(inside), 'why': None}
    if not chunks or any(c['pairs'] is None for c in chunks):
        said['why'] = 'the program gave no count'
        return said, []
    whole = sorted((s, s + d) for name, s, d in host
                   if name == SPAN and s >= lo and s + d <= hi)
    found = anchor(chunks, whole, lo)
    if found is None:
        said['why'] = 'no prefill whole inside the tail'
        return said, []
    first_chunk, (s, e) = found
    # the anchor's first chunk is the first run that starts at or after
    # its span; where the profiler set the device's clock a little early
    # against the host's it is the run before that one, so the
    # neighbouring shifts are tried too and the one kept under which
    # every run is its chunk's
    guess = first_chunk - bisect.bisect_left([r[1] for r in runs], s)
    why = None
    for shift in (guess, guess + 1, guess - 1):
        out, fault = _placed(chunks, runs, whole, lo, hi, shift)
        if out is not None:
            said.update(read=len(out), dropped=len(inside) - len(out),
                        shift=shift - guess)
            return said, [c[:3] for c in out]
        why = why or fault
    said['why'] = why
    return said, []


def _placed(chunks, runs, whole, lo, hi, shift):
    """([(pairs, start_ns, end_ns, prefill)] of the runs whole inside
    [lo, hi], None) with run ``i`` taken as chunk ``i + shift``, or
    (None, why) where that cannot be: a run whose program is not its
    chunk's bucket's, or a whole span under which the runs are not one
    prefill's chunks, all of them (a run belongs to the span it starts
    under, give or take ``EDGE_SLACK_NS`` at the span's start)."""
    out = []
    for i, (bucket, s, e) in enumerate(runs):
        if e <= lo or s >= hi or not 0 <= i + shift < len(chunks):
            continue
        chunk = chunks[i + shift]
        if chunk['bucket'] != bucket:
            return None, 'run %d is prefill_%d, its chunk %d' % (
                i, bucket, chunk['bucket'])
        if s >= lo and e <= hi:
            out.append((chunk['pairs'], s, e, chunk['run']))
    for s, e in whole:
        under = [c for c in out if s - EDGE_SLACK_NS <= c[1] < e]
        if len({c[3] for c in under}) != 1 or len(under) != sum(
                1 for c in chunks if c['run'] == under[0][3]):
            return None, 'the runs under a span are not one prefill'
    return out, None


def op_ns(device, patterns, intervals):
    """Nanoseconds of matching device ops that started inside one of the
    disjoint ``intervals``."""
    intervals = sorted(intervals)
    starts = [s for s, _ in intervals]
    ns = 0
    for _, s, d in tracelib.matching(device, patterns):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < intervals[i][1]:
            ns += d
    return ns


def read(args, sources):
    trace, peaks = sources['trace'], sources['peaks']
    runs = sources.get('prefill_program_runs')
    if not trace or 'window' not in trace or peaks is None or not runs:
        return None
    said, chunks = chunks_read(
        sources.get('prefill_chunks'), runs, trace['host'],
        trace['window'], args['program'],
        max([s for _, s, _ in trace['first']] or [0]))
    print('PREFILL_CHUNKS %s' % json.dumps(said, sort_keys=True),
          flush=True)
    ns = op_ns(trace['first'], args['match'],
               [(s, e) for _, s, e in chunks])
    if not ns:
        return None
    least = manifest.load_module(os.path.join(
        sources['bench_dir'], 'shape_fns', args['function'] + '.py')
    ).least_flops(sum(pairs for pairs, _, _ in chunks), sources['config'])
    return 100.0 * (least / peaks[args['peak']]) / (ns / 1e9)
