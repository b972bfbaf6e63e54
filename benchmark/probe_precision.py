#!/usr/bin/env python3
"""The second reading behind a serving cell's ``logit_gap_tol``: what the
plain reference itself gives one precision below the one the
configuration states.

    python3 benchmark/probe_precision.py --workload <cell> [--seed n]
            [--lengths 700,2100,4300,5900] [--rows 256] [--rehearsal]

The configuration states two precisions, and each has its control:

- ``state``: float32 for the residual stream, the router's scores and
  weights, the attention's scores and softmax, and the logits. The
  nearest below is bfloat16: the reference is computed again with
  ``arch['state_dtype'] = 'bfloat16'``, the weights as they are.
- ``weights``: bfloat16 matrices. The nearest below is float8 (e4m3):
  every matrix is rounded to float8's three mantissa bits where it lies
  and the reference computed again at float32 state.

The probe builds the cell's engine for its weights alone (nothing is
served) and computes the reference's float32 logits of seeded sequences.
For the last ``--rows`` positions of each sequence and each control it
prints how far the lowered model's choice lies under the reference's
largest logit, by the reference's own logits: the gap a server that had
silently dropped to that precision would show in the cell's comparison.
The cell's limits (``within_limits`` in ``runners/serve_block.py``: the
share of tokens more than ``logit_gap_tol`` under, and ``logit_gap_cap``
against what a token drawn at random shows, ``random_token_gap``) have
to refuse a control in every sequence (the ``CONTROL`` lines). The first reading, the served tokens' own
largest gap, is in every run's WINDOW line. Not part of a run.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np                                              # noqa: E402

from benchmark import manifest, run as bench                    # noqa: E402


MANTISSA = {'bfloat16': (7, 'uint16'), 'float32': (23, 'uint32')}


def to_three_mantissa_bits(w):
    """``w`` rounded to nearest-even at float8 e4m3's three mantissa
    bits, by integer arithmetic on its bits (the exponent keeps its
    range). A pair of converts through ``float8_e4m3fn`` would say the
    same in one line, but the v5e has no float8 and its compiler widens
    the type, which folds the pair away: the first probe read a
    difference of exactly 0 (my chip run, PR 28)."""
    import jax
    import jax.numpy as jnp
    bits, kind = MANTISSA[str(w.dtype)]
    drop = bits - 3
    raw = jax.lax.bitcast_convert_type(w, jnp.dtype(kind))
    one = jnp.asarray(1, raw.dtype)
    half = (one << (drop - 1)) - one + ((raw >> drop) & one)
    return jax.lax.bitcast_convert_type(
        (raw + half) & ~((one << drop) - one), w.dtype)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--seconds', type=float, default=1.0)
    ap.add_argument('--trace', type=int, default=0)
    ap.add_argument('--lengths', default='700,2100,4300,5900')
    ap.add_argument('--rows', type=int, default=256)
    ap.add_argument('--rehearsal', action='store_true')
    args = ap.parse_args(argv)

    root = os.path.dirname(HERE)
    resolved = manifest.resolve(manifest.load(root), args.workload)
    from paddle_tpu.core.platform_boot import (arm_compile_cache,
                                               force_host_cpu)
    if args.rehearsal:
        force_host_cpu(8)
    import jax
    import jax.numpy as jnp
    bench.say('DEVICE', **bench.device_stamp(resolved['cell']['chips'],
                                             args.rehearsal))
    arm_compile_cache()
    ctx = bench.Context(resolved, args, root)
    runner = manifest.load_module(resolved['runner'])
    engine, config = runner.build_engine(ctx)
    spec = engine.spec
    arch, held = ctx.reference.arch_of(spec), ctx.reference.held_of(spec)
    pad = config['reference']['pad_to']
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

    def compare(control, precision, stated, **lowered):
        tol = config['reference']['logit_gap_tol']
        largest, over = [], []
        for tokens, rows in zip(sequences, stated):
            below = rows_of(tokens, **lowered)
            choice = below.argmax(axis=1)
            gaps = rows.max(axis=1) - rows[np.arange(len(rows)), choice]
            largest.append(float(gaps.max()))
            over.append(float((gaps > tol).mean()))
            bench.say('BELOW', control=control, precision=precision,
                      tokens=len(tokens), rows=len(rows),
                      gap_max=largest[-1],
                      gap_mean=float(gaps.mean()),
                      gap_p50=float(np.median(gaps)),
                      not_first=int((gaps > 0).sum()),
                      over_tol=int((gaps > tol).sum()),
                      random_token_gap=float(
                          (rows.max(axis=1) - rows.mean(axis=1)).mean()),
                      logit_std=float(rows.std()),
                      logits_rms_diff=float(np.sqrt(
                          np.mean(np.square(below - rows)))))
        bench.say('CONTROL', control=control, precision=precision,
                  smallest_gap_max=min(largest), largest_gap_max=max(largest),
                  smallest_share_over_tol=min(over),
                  largest_share_over_tol=max(over))

    try:
        stated = [rows_of(t) for t in sequences]
        compare('state', 'bfloat16 residual, router, softmax, logits',
                stated, state_dtype='bfloat16')
        rounded = jax.jit(to_three_mantissa_bits, donate_argnums=0)
        for name in sorted(engine.device_weights()):
            w = engine.device_weights()[name]
            if w.ndim >= 2:
                engine.load_weights({name: rounded(w)})
        compare('weights', '3 mantissa bits (float8 e4m3)', stated)
    finally:
        engine.shutdown(drain=False)
    return 0


if __name__ == '__main__':
    sys.exit(main())
