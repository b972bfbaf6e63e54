#!/usr/bin/env python3
"""Planted faults against a serving cell's limits, where the cell's plain
reference has switches for them: what a server that got one thing wrong
would show in the cell's comparison.

    python3 benchmark/probe_faults.py --workload <cell> [--seed n]
            [--lengths 3000,9000,15000] [--rows 256]
            [--faults select,gate,rescale,state,weights] [--rehearsal]

``benchmark/probe_precision.py`` lowers the two precisions a
configuration states. A reference whose ``arch`` carries switches
(``references/dots3_note.py``: ``select``, the indexer's choice;
``gate``, the headwise output gate; ``rescale``, the latents' rescale)
can also be computed with one of them off. The probe builds the cell's
engine for its weights alone (nothing is served), computes the
reference's float32 logits of seeded sequences, and for the last
``--rows`` positions of each sequence and each fault prints how far the
faulty model's choice lies under the sound reference's largest logit, by
the sound reference's own logits: the share of tokens over a ladder of
gaps, the largest, and whether the cell's limits
(``runners/serve_block.py::within_limits``) would have passed it. The
two precision controls ride along under the same ladder (``state``:
``arch['state_dtype'] = 'bfloat16'``; ``weights``, last because it
rounds the engine's matrices where they lie:
``probe_precision.to_three_mantissa_bits``). Not part of a run.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np                                              # noqa: E402

from benchmark import manifest, run as bench                    # noqa: E402
from benchmark.probe_precision import to_three_mantissa_bits    # noqa: E402

LADDER = (0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0, 1.5, 2.0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--lengths', default='3000,9000,15000')
    ap.add_argument('--rows', type=int, default=256)
    ap.add_argument('--faults', default='select,gate,rescale,state,weights')
    ap.add_argument('--rehearsal', action='store_true')
    # bench.Context reads both; nothing is served or traced here
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
    pad = limits['pad_to']
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
            lowered = {fault: False}
            if fault == 'state':
                lowered = {'state_dtype': 'bfloat16'}
            elif fault == 'weights':
                lowered = {}
                rounded = jax.jit(to_three_mantissa_bits, donate_argnums=0)
                for name in sorted(engine.device_weights()):
                    w = engine.device_weights()[name]
                    if w.ndim >= 2:
                        engine.load_weights({name: rounded(w)})
            elif fault not in arch:
                raise SystemExit('probe_faults: the reference of %s has no '
                                 'switch %r' % (args.workload, fault))
            for tokens, rows in zip(sequences, sound):
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
