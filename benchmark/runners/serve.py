"""Serving cells: the config's DecodeEngine under open-loop traffic.

A widened copy of ``chip_smoke.leg_serve`` (PR 21): build, warmup(),
start(), then the traffic file's schedule paced by ``loadgen.drive``.
Traffic runs for ``preroll_s`` before the window opens, so the batch is
at its steady occupancy when measuring begins; that is part of set-up.
Requests due inside the window are the sample; the window's tokens per
second count every token delivered in it. After the window the runner
waits ``drain_s`` for stragglers, then checks what was served, outside
the window: a few of the window's short requests served again one at a
time must give identical greedy tokens (the engine's stated invariant),
and a seeded sample of the window's requests of any length is held to
the config's plain reference (``references/<config>.py``): every served
token must be the reference's choice, or within the config's
``logit_gap_tol`` of it by the reference's own logits.
"""

import importlib
import queue
import time

import numpy as np

from benchmark import loadgen, stats, weights


def build_engine(ctx):
    config = ctx.sized(ctx.config)
    module, function = config['builder']['function'].split(':')
    engine_cls = getattr(importlib.import_module(module), function)
    from paddle_tpu.serving.decode import LMSpec
    model = config['model']
    spec = LMSpec(**model)
    engine = engine_cls(spec, **config['engine'])
    reseed_weights(engine, ctx.seed, set(config['weights']['frozen']))
    return engine, config


def reseed_weights(engine, seed, frozen):
    """Every weight matrix drawn again on the device from the seed
    (weights.redraw); vectors and the ``frozen`` tables (sinusoid
    positions) stay."""
    import jax
    old = {n: v for n, v in engine.export_weights().items()
           if v.ndim >= 2 and n not in frozen}
    engine.load_weights(jax.jit(weights.redraw)(old,
                                                weights.seed_key(seed)))


def poll(stream):
    """loadgen.Client's reader for a GenerationStream: the tokens put
    on it since the last call, whether it has ended, what failed it.
    The stream's public reader is a blocking iterator, which costs a
    thread per request; this takes from the queue underneath it
    (PERF.md section 7 asks the program for a public one)."""
    tokens = []
    while True:
        try:
            item = stream._q.get_nowait()
        except queue.Empty:
            return tokens, False, None
        if not isinstance(item, int):   # the stream's end mark
            break
        tokens.append(item)
    try:
        stream.result(5)                # re-raises what failed it
    except Exception as e:              # the client's view of a failure
        return tokens, True, repr(e)
    return tokens, True, None


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, engine, traffic, config, signatures)
    finally:
        engine.shutdown(drain=False)


def against_reference(ctx, engine, config, prompts, served):
    """The largest gap, by the reference's logits, between its choice
    and the token served, over ``served`` (records); how many tokens
    differ from its choice; the deviation of its logits."""
    import jax
    geometry = config['engine']
    capacity = geometry['pages_per_seq'] * geometry['block_size']
    weights = jax.device_put(engine.export_weights())
    gaps, deviation = [], 0.0
    for r in served:
        one, deviation = ctx.reference.token_gaps(
            weights, config['model']['n_head'], prompts[r.request.index],
            r.tokens, capacity)
        gaps.extend(one)
    return {'reference_gap_max': max(gaps) if gaps else None,
            'reference_tokens': len(gaps),
            'reference_tokens_not_first': sum(1 for g in gaps if g > 0),
            'reference_logit_std': deviation}


def serve(ctx, engine, traffic, config, signatures):
    vocab = config['model']['vocab_size']
    geometry = config['engine']
    preroll = traffic['preroll_s']
    requests = loadgen.schedule(traffic, ctx.seed, ctx.seconds)
    prompts = {r.index: loadgen.prompt_tokens(r, vocab) for r in requests}

    def submit(request):
        return engine.submit(prompts[request.index],
                             max_new_tokens=request.answer_len)

    blocks = geometry['num_blocks']
    state = {'sampled': 0.0}

    def housekeeping(now):
        if ctx.t_window is None:
            if now >= t0 + preroll:
                ctx.begin_window()
            return
        if ctx.window_left() <= 0:
            return
        ctx.tick()
        if now - state['sampled'] >= traffic['sample_every_s']:
            state['sampled'] = now
            used = blocks - engine.free_pages()
            ctx.samples.setdefault('kv_pages_used', []).append(used)
            ctx.samples.setdefault('kv_pool_used_pct', []).append(
                100.0 * used / blocks)

    t0 = time.perf_counter()
    client = loadgen.drive(submit, poll, requests, t0, housekeeping)
    lo, hi = ctx.t_window, ctx.t_window + ctx.seconds
    loadgen.wait_until(hi, client.step)
    ctx.end_window()
    unfinished = client.finish(hi + traffic['drain_s'])
    records = client.records

    sample = [r for r in records if r.request.due >= preroll]
    good = [r for r in sample if r.complete]
    refused = sum(1 for r in sample if r.refused)
    errored = sum(1 for r in sample if r.error)
    ttft = [r.ttft for r in good]
    gaps = [g for r in good for g in r.gaps]
    in_window = sum(1 for r in records for t in r.token_at if lo <= t < hi)
    late = [r.sent_at - r.due_at for r in records]

    # the engine's invariant, on the chip: the same prompts one at a time
    rng = np.random.RandomState(ctx.seed % (1 << 32))
    short = [r for r in good
             if r.request.answer_len <= traffic['recheck_max_answer']]
    again = [short[i] for i in rng.permutation(len(short))[
        :traffic['recheck_requests']]]
    same = all(engine.generate(prompts[r.request.index],
                               max_new_tokens=r.request.answer_len,
                               timeout=120) == r.tokens for r in again)
    held = [good[i] for i in rng.permutation(len(good))[
        :config['reference']['requests']]]
    reference = against_reference(ctx, engine, config, prompts, held)
    agrees = (reference['reference_gap_max'] is not None
              and reference['reference_gap_max']
              <= config['reference']['logit_gap_tol'])

    ms = 1000.0
    return {
        # a refusal fails the run: below the knee nothing is refused, and
        # the latencies below are of completed requests only
        'correct': bool(same and again and agrees
                        and len(good) == len(sample) and unfinished == 0),
        'attempted': len(sample),
        'failed': len(sample) - len(good),
        'end_to_end': {
            'ttft_mean_ms': ms * sum(ttft) / len(ttft),
            'itl_mean_ms': ms * sum(gaps) / len(gaps),
            'serve_tokens_per_s': in_window / ctx.seconds,
            'ttft_p90_ms': ms * stats.percentile(ttft, 90),
            'ttft_p95_ms': ms * stats.percentile(ttft, 95),
            'itl_p90_ms': ms * stats.percentile(gaps, 90),
            'itl_p95_ms': ms * stats.percentile(gaps, 95),
        },
        'notes': dict(reference, **{
            'signatures': signatures, 'requests_sent': len(records),
            'refused': refused, 'errored': errored,
            'unfinished': unfinished, 'rechecked': len(again),
            'same_one_at_a_time': same,
            'ttft_p50_ms': ms * stats.percentile(ttft, 50),
            'itl_p50_ms': ms * stats.percentile(gaps, 50),
            'itl_p99_ms': ms * stats.percentile(gaps, 99),
            'itl_max_ms': ms * max(gaps),
            'ttft_samples': len(ttft), 'itl_samples': len(gaps),
            'prompt_len_p50': stats.percentile(
                [r.request.prompt_len for r in sample], 50),
            'answer_len_p50': stats.percentile(
                [r.request.answer_len for r in sample], 50),
            'offered_tokens_per_s': sum(
                r.request.answer_len for r in sample) / ctx.seconds,
            'pacer_late_ms_p50': ms * stats.percentile(late, 50),
            'pacer_late_ms_p95': ms * stats.percentile(late, 95),
            'pacer_late_ms_max': ms * max(late),
        }),
    }
