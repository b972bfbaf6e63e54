"""Per-shape ResNet-50 conv microbench: fwd vs dgrad vs wgrad.

The anatomy (bench.py resnet50_anatomy) says WHERE the step time goes at
phase granularity (fwd vs bwd+update); this says WHICH conv directions
are slow at op granularity, so the bwd gap (VERDICT r3 #2) can be
attacked shape by shape. Times each representative ResNet-50 conv shape
(batch 64, NHWC, bf16) three ways inside one jitted fori_loop — forward
conv, input gradient, filter gradient — chaining iterations through the
data so every iteration depends on the last; timing ends in
block_until_ready.

Run: python tools/conv_bwd_microbench.py [--inner 8] [--batch 64]
Prints one JSON line per shape with ms and achieved TFLOP/s per leg.
"""

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# (H, W, Cin, Cout, k, stride, count) — count = how many times the shape
# appears in ResNet-50 so the weighted total reconstructs the step.
SHAPES = [
    (224, 224, 3, 64, 7, 2, 1),      # conv1
    (56, 56, 64, 64, 1, 1, 1),       # stage2 reduce (first block)
    (56, 56, 64, 64, 3, 1, 3),       # stage2 3x3
    (56, 56, 64, 256, 1, 1, 3),      # stage2 expand
    (56, 56, 256, 64, 1, 1, 2),      # stage2 reduce (later blocks)
    (56, 56, 256, 512, 1, 2, 1),     # stage3 shortcut
    (56, 56, 256, 128, 1, 2, 1),     # stage3 reduce s2
    (28, 28, 128, 128, 3, 1, 4),     # stage3 3x3
    (28, 28, 128, 512, 1, 1, 4),     # stage3 expand
    (28, 28, 512, 128, 1, 1, 3),     # stage3 reduce
    (28, 28, 512, 1024, 1, 2, 1),    # stage4 shortcut
    (28, 28, 512, 256, 1, 2, 1),     # stage4 reduce s2
    (14, 14, 256, 256, 3, 1, 6),     # stage4 3x3
    (14, 14, 256, 1024, 1, 1, 6),    # stage4 expand
    (14, 14, 1024, 256, 1, 1, 5),    # stage4 reduce
    (14, 14, 1024, 2048, 1, 2, 1),   # stage5 shortcut
    (14, 14, 1024, 512, 1, 2, 1),    # stage5 reduce s2
    (7, 7, 512, 512, 3, 1, 3),       # stage5 3x3
    (7, 7, 512, 2048, 1, 1, 3),      # stage5 expand
    (7, 7, 2048, 512, 1, 1, 2),      # stage5 reduce
]


def conv(x, w, stride):
    pad = (w.shape[0] - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def time_leg(fn, args, inner, chain):
    """Run `fn` inner times inside one jit, chained via `chain`; return
    per-iteration seconds."""
    def many(args):
        def body(_, carry):
            return chain(carry, fn(*carry))
        return jax.lax.fori_loop(0, inner, body, args)

    jmany = jax.jit(many)
    out1 = jax.block_until_ready(jmany(args))   # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(jmany(out1))
    return (time.perf_counter() - t0) / inner


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--inner', type=int, default=8)
    p.add_argument('--batch', type=int, default=64)
    args = p.parse_args()
    rng = np.random.RandomState(0)
    totals = {'fwd': 0.0, 'dgrad': 0.0, 'wgrad': 0.0}
    for (h, w_, cin, cout, k, s, count) in SHAPES:
        x0 = jnp.asarray(rng.randn(args.batch, h, w_, cin) * 0.1,
                         jnp.bfloat16)
        w0 = jnp.asarray(rng.randn(k, k, cin, cout) * 0.1, jnp.bfloat16)
        fwd = functools.partial(conv, stride=s)
        ho, wo = -(-h // s), -(-w_ // s)
        flops = 2.0 * args.batch * ho * wo * cout * cin * k * k

        def dgrad(x, w):
            return jax.grad(
                lambda x: fwd(x, w).astype(jnp.float32).sum())(x)

        def wgrad(x, w):
            return jax.grad(
                lambda w: fwd(x, w).astype(jnp.float32).sum())(w)

        res = {'shape': '%dx%dx%d->%d k%d s%d x%d'
                        % (h, w_, cin, cout, k, s, count)}
        legs = {
            # fwd: chain y back into x (shapes differ; fold via mean)
            'fwd': (fwd, lambda c, y: (
                c[0] + 1e-3 * jnp.mean(y).astype(c[0].dtype), c[1])),
            'dgrad': (dgrad, lambda c, dx: (c[0] + 1e-3 * dx, c[1])),
            'wgrad': (wgrad, lambda c, dw: (c[0], c[1] + 1e-3 * dw)),
        }
        for name, (fn, chain) in legs.items():
            dt = time_leg(fn, (x0, w0), args.inner, chain)
            res[name + '_ms'] = round(dt * 1e3, 3)
            res[name + '_tflops'] = round(flops / dt / 1e12, 1)
            totals[name] += dt * count
        print(json.dumps(res), flush=True)
    print(json.dumps({'weighted_totals_ms':
                      {k: round(v * 1e3, 2) for k, v in totals.items()}}),
          flush=True)


if __name__ == '__main__':
    main()
