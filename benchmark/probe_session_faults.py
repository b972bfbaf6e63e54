#!/usr/bin/env python3
"""Planted faults against the limits of a cell whose reference is
``references/kimi_k2_6.py``: what a server that got one thing of this
configuration wrong would show in the cell's comparison.

    python3 benchmark/probe_session_faults.py --workload <cell> [--seed n]
            [--lengths 9000,17000,33000] [--rows 256] [--suffix 288]
            [--faults yarn,softmax_mscale,scale_routed,offset,state,weights]
            [--rehearsal]

``benchmark/probe_faults.py`` turns a reference's boolean switches off.
This configuration's faults are not all switches, so the probe is a file
of its own beside it, printing the same ``PLANTED`` lines under the same
ladder: ``yarn`` (plain rope in place of the YaRN table),
``softmax_mscale`` (the softmax scale without m^2), ``scale_routed``
(``routed_scaling_factor`` dropped), ``offset`` (the last ``--suffix``
positions, from the page boundary below them, rotated as if counted
from 0: a prefix-cache hit whose suffix was prefilled at the wrong
positions), and the two precision controls (``state``: bfloat16 state;
``weights``, last because it rounds the engine's matrices where they
lie: float8 e4m3's three mantissa bits). The probe builds the cell's
engine for its weights alone (nothing is served), computes the
reference's float32 logits of seeded sequences, and for the last
``--rows`` positions of each sequence and each fault prints how far the
faulty model's choice lies under the sound reference's largest logit, by
the sound reference's own logits, and whether the cell's limits would
have passed it. Not part of a run.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np                                              # noqa: E402

from benchmark import manifest, run as bench                    # noqa: E402
from benchmark.probe_faults import LADDER                       # noqa: E402
from benchmark.probe_precision import to_three_mantissa_bits    # noqa: E402

SWITCHES = ('yarn', 'softmax_mscale', 'scale_routed')


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--lengths', default='9000,17000,33000')
    ap.add_argument('--rows', type=int, default=256)
    ap.add_argument('--suffix', type=int, default=288)
    ap.add_argument('--faults', default='yarn,softmax_mscale,scale_routed,'
                                        'offset,state,weights')
    ap.add_argument('--rehearsal', action='store_true')
    ap.set_defaults(seconds=0.0, trace=0)
    args = ap.parse_args(argv)

    root = os.path.dirname(HERE)
    resolved = manifest.resolve(manifest.load(root), args.workload)
    from paddle_tpu.core.platform_boot import (arm_compile_cache,
                                               force_host_cpu)
    if args.rehearsal:
        force_host_cpu(8)
    import jax
    bench.say('DEVICE', **bench.device_stamp(resolved['cell']['chips'],
                                             args.rehearsal))
    arm_compile_cache()
    ctx = bench.Context(resolved, args, root)
    runner = manifest.load_module(resolved['runner'])
    engine, config = runner.build_engine(ctx)
    spec = engine.spec
    arch, held = ctx.reference.arch_of(spec), ctx.reference.held_of(spec)
    limits = config['reference']
    pad, page = limits['pad_to'], config['engine']['block_size']
    rng = np.random.RandomState(args.seed % (1 << 32))
    sequences = [rng.randint(0, spec.vocab_size, int(n))
                 for n in args.lengths.split(',')]

    def rows_of(tokens, **lowered):
        size = -(-len(tokens) // pad) * pad
        padded = np.zeros((size,), np.int32)
        padded[:len(tokens)] = tokens
        lo = max(0, len(tokens) - args.rows)
        return np.asarray(ctx.reference.logits(
            engine.device_weights(), padded, dict(arch, **lowered), held,
            rows=(lo, len(tokens))))

    try:
        sound = [rows_of(t) for t in sequences]
        for fault in args.faults.split(','):
            if fault == 'weights':
                rounded = jax.jit(to_three_mantissa_bits, donate_argnums=0)
                for name in sorted(engine.device_weights()):
                    w = engine.device_weights()[name]
                    if w.ndim >= 2:
                        engine.load_weights({name: rounded(w)})
            elif fault not in SWITCHES + ('offset', 'state'):
                raise SystemExit('probe_session_faults: no fault %r' % fault)
            for tokens, rows in zip(sequences, sound):
                lowered = {fault: False} if fault in SWITCHES else {}
                if fault == 'state':
                    lowered = {'state_dtype': 'bfloat16'}
                if fault == 'offset':
                    lowered = {'offset_from': max(
                        0, len(tokens) - args.suffix) // page * page}
                below = rows_of(tokens, **lowered)
                choice = below.argmax(axis=1)
                gaps = rows.max(axis=1) - rows[np.arange(len(rows)), choice]
                bench.say(
                    'PLANTED', fault=fault, tokens=len(tokens),
                    rows=len(rows), gap_max=float(gaps.max()),
                    gap_mean=float(gaps.mean()),
                    not_first=int((gaps > 0).sum()),
                    share_over={str(t): float((gaps > t).mean())
                                for t in LADDER},
                    within_limits=bool(runner.within_limits(
                        gaps.tolist(), limits)),
                    random_token_gap=float(
                        (rows.max(axis=1) - rows.mean(axis=1)).mean()),
                    logit_std=float(rows.std()),
                    logits_rms_diff=float(np.sqrt(
                        np.mean(np.square(below - rows)))))
    finally:
        engine.shutdown(drain=False)
    return 0


if __name__ == '__main__':
    sys.exit(main())
