#!/usr/bin/env python3
"""Find a serving cell's knee once: one engine, several offered rates.

    python3 benchmark/sweep.py --workload <cell> --rates 1.5,2,2.5,3
                               --seconds 40 [--seed n] [--rehearsal]

One process builds and warms the engine once, then offers each rate in
turn for --seconds with the cell's own lengths, and prints for each the
tokens per second delivered, the waiting queue sampled through the
interval (a backlog that grows from the middle third to the last means
the rate is above what the engine sustains), and the client's TTFT.
Between rates the engine drains. The cell's ``rate_rps`` is then written
into its traffic file by hand, at four fifths of the highest rate whose
backlog stayed flat; the table goes into PERF.md. Not part of a run.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import loadgen, manifest, run as bench, stats   # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=15.0)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--trace', type=int, default=0)
    ap.add_argument('--rehearsal', action='store_true')
    args = ap.parse_args(argv)

    root = os.path.dirname(HERE)
    resolved = manifest.resolve(manifest.load(root), args.workload)
    from paddle_tpu.core.platform_boot import (arm_compile_cache,
                                               force_host_cpu)
    if args.rehearsal:
        force_host_cpu(8)
    bench.say('DEVICE', **bench.device_stamp(resolved['cell']['chips'],
                                             args.rehearsal))
    arm_compile_cache()
    ctx = bench.Context(resolved, args, root)
    serve = manifest.load_module(resolved['runner'])
    engine, config = serve.build_engine(ctx)
    model = config['model']
    try:
        engine.warmup()
        engine.start()
        for rate in [float(r) for r in args.rates.split(',')]:
            traffic = dict(ctx.sized(ctx.traffic), rate_rps=rate,
                           preroll_s=0)
            requests = loadgen.schedule(traffic, args.seed, args.seconds)
            prompts = {r.index: loadgen.prompt_tokens(r, model['vocab_size'])
                       for r in requests}
            depth = []

            def watch(now):
                if not depth or now - depth[-1][0] >= 0.1:
                    depth.append((now, engine.queue_depth()))

            t0 = time.perf_counter()
            client = loadgen.drive(
                lambda r: engine.submit(prompts[r.index],
                                        max_new_tokens=r.answer_len),
                serve.poll, requests, t0, watch)
            loadgen.wait_until(t0 + args.seconds, client.step)
            records = client.records
            tokens = sum(1 for r in records for t in r.token_at
                         if t < t0 + args.seconds)
            left = client.finish(t0 + args.seconds + 120)
            drained_s = time.perf_counter() - t0 - args.seconds
            third = max(1, len(depth) // 3)
            ttft = [r.ttft for r in records if r.ttft is not None]
            bench.say(
                'RATE', rate_rps=rate, requests=len(records),
                offered_tokens_per_s=sum(r.answer_len for r in requests)
                / args.seconds,
                delivered_tokens_per_s=tokens / args.seconds,
                queue_first_third=sum(d for _, d in depth[:third]) / third,
                queue_middle_third=sum(
                    d for _, d in depth[third:2 * third]) / third,
                queue_last_third=sum(d for _, d in depth[-third:]) / third,
                queue_max=max(d for _, d in depth),
                ttft_p50_ms=1000 * stats.percentile(ttft, 50),
                ttft_p95_ms=1000 * stats.percentile(ttft, 95),
                itl_p50_ms=1000 * stats.percentile(
                    [g for r in records for g in r.gaps], 50),
                refused=sum(1 for r in records if r.refused),
                unfinished=left, drain_s=drained_s)
    finally:
        engine.shutdown(drain=False)
    return 0


if __name__ == '__main__':
    sys.exit(main())
