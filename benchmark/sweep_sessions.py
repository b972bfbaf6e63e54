#!/usr/bin/env python3
"""Find the knee of a cell of document sessions once: one engine,
several offered rates.

    python3 benchmark/sweep_sessions.py --workload <cell>
            --rates 0.8,1.0,1.2,1.6 --seconds 60 [--seed n] [--rehearsal]

``benchmark/sweep.py`` draws each rate's requests from
``loadgen.schedule`` and cannot replay sessions. This is that tool for
a runner whose requests come from ``benchmark/sessions.py``: one
process builds and warms the engine once, then offers each rate in turn
for --seconds with the cell's own trace scaled to the rate (no
pre-roll), and prints for each the tokens per second delivered, the
waiting queue sampled through the interval by thirds, the client's
TTFT by hit and miss, and how long the interval took to drain. Between
rates the engine drains and the prefix cache is emptied, so every rate
starts cold as a run does. The knee, by the one rule of
``traffic/long_ctx_steady.json``: the highest rate up to which no swept
rate left a backlog at the end of its interval. Not part of a run.
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import (loadgen, manifest, run as bench,     # noqa: E402
                       sessions, stats)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=60.0)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--rehearsal', action='store_true')
    ap.set_defaults(trace=0)
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
    vocab = config['model']['vocab_size']
    try:
        engine.warmup()
        engine.start()
        for rate in [float(r) for r in args.rates.split(',')]:
            traffic = dict(ctx.sized(ctx.traffic), rate_rps=rate,
                           preroll_s=0)
            requests, asks = sessions.schedule(traffic, args.seed,
                                               args.seconds)
            prompts = {r.index: sessions.prompt_tokens(
                r, asks[r.index], vocab) for r in requests}
            streams, depth = {}, []

            def submit(r):
                streams[r.index] = engine.submit(
                    prompts[r.index], max_new_tokens=r.answer_len)
                return streams[r.index]

            def watch(now):
                if not depth or now - depth[-1][0] >= 0.1:
                    depth.append((now, engine.queue_depth()))

            t0 = time.perf_counter()
            client = loadgen.drive(submit, serve.poll, requests, t0, watch)
            loadgen.wait_until(t0 + args.seconds, client.step)
            records = client.records
            tokens = sum(1 for r in records for t in r.token_at
                         if t < t0 + args.seconds)
            left_at_end = len(client.live)
            left = client.finish(t0 + args.seconds + 300)
            drained_s = time.perf_counter() - t0 - args.seconds
            engine.drain(timeout=60)
            third = max(1, len(depth) // 3)

            def mean_ms(values):
                return 1000 * sum(values) / len(values) if values else None
            hit = [r.ttft for r in records if r.ttft is not None
                   and streams[r.request.index].cached_tokens]
            miss = [r.ttft for r in records if r.ttft is not None
                    and not streams[r.request.index].cached_tokens]
            ttft = hit + miss
            bench.say(
                'SWEEP', rate_rps=rate, requests=len(records),
                documents=len({asks[r.index].document for r in requests}),
                offered_tokens_per_s=sum(
                    r.answer_len for r in requests) / args.seconds,
                tokens_per_s=tokens / args.seconds,
                queue_by_third=[
                    sum(d for _, d in depth[i * third:(i + 1) * third])
                    / float(third) for i in range(3)],
                queue_max=max(d for _, d in depth),
                open_at_end=left_at_end, drained_s=drained_s,
                unfinished=left,
                refused=sum(1 for r in records if r.refused),
                ttft_mean_ms=mean_ms(ttft),
                ttft_p90_ms=1000 * stats.percentile(ttft, 90)
                if ttft else None,
                ttft_hit_ms=mean_ms(hit), ttft_miss_ms=mean_ms(miss),
                itl_mean_ms=mean_ms([g for r in records for g in r.gaps]),
                cached_share=sum(
                    streams[r.request.index].cached_tokens or 0
                    for r in records if r.request.index in streams)
                / float(sum(r.prompt_len for r in requests)),
                evictions=engine.prefix_cache.evictions,
                pages_free=engine.free_pages())
            engine.prefix_cache.clear()
    finally:
        engine.shutdown(drain=False)
    return 0


if __name__ == '__main__':
    sys.exit(main())
