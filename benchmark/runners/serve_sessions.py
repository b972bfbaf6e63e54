"""Serving cells of document sessions over the latent_moe block family
(kimi_k2_6): a DecodeEngine over ``LMSpec(block='latent_moe')`` with
the prefix cache on, under an open loop of asks that share their
documents.

``runners/serve_latent.py`` reads dots3_note's config.json keys and
serves ``loadgen.schedule``'s independent requests. This runner is that
file, loaded as it loads ``serve_block.py``, with only what differs:
``spec_of`` for ``model_type`` kimi_k2 (dense latent attention, YaRN,
a scaled routed sum, no gate, no rescale), the sessions' schedule and
prompts (``benchmark/sessions.py``), and the held sample: at least one
first ask that ran past ``long_tokens`` and at least ``shared_requests``
later asks of different documents that the engine served from shared
pages (its stream's ``cached_tokens``), so that the long chunked
prefill and the suffix after a hit are both held to the reference on
the chip. ``serve_block.serve`` draws its requests from
``loadgen.schedule`` inside itself, so the loop is written out here a
third time (PERF.md section 7 asks a benchmark issue for hooks); the
window, the pre-roll, the limits and what ``correct`` means are its own
(``within_limits``, ``against_reference``), as are the reader of a
stream (``poll``) and the drawing of the weights
(``serve_latent.draw_weights``).

The one-at-a-time check is of the same computation, as it is in the
other cells: a request served again alone has to take the programs it
took in the window, and with the prefix cache on a request served again
finds its own pages published and prefills a shorter suffix through a
smaller bucket's program, whose bfloat16 products round otherwise (the
first chip run of PR 36 read different tokens so, within the limits of
the reference both times). So the check takes requests that found
nothing cached (first asks), empties the cache before each and serves
them alone: the same chunks from position 0, in another batch. What a
hit serves is held to the reference (the sample's later asks); one hit
served again through a deeper hit is reported beside it
(``hit_again_same``), not judged.
"""

import os
import time

import numpy as np

from benchmark import loadgen, manifest, sessions, stats

_latent = manifest.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), 'serve_latent.py'))
poll = _latent.poll
within_limits = _latent.within_limits
against_reference = _latent.against_reference
draw_weights = _latent.draw_weights

FULL = 'full_attention'
# the served tokens' gaps are reported over this ladder, for the limits
LADDER = (0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0)


class _Recorder(object):
    """The cell's reference with the gaps it returned kept, so that the
    WINDOW line can say how they are spread; ``against_reference`` sees
    the reference's own functions."""

    def __init__(self, reference):
        self._reference, self.gaps = reference, []

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def token_gaps(self, *args):
        gaps, deviation = self._reference.token_gaps(*args)
        self.gaps.extend(gaps)
        return gaps, deviation


def spec_of(config):
    """The LMSpec of a kimi_k2 config.json, cut as the file says:
    ``n_routed_experts`` is what is held here of
    ``published.n_routed_experts``, ``num_hidden_layers`` the leading
    layers that are run."""
    from paddle_tpu.serving.decode import LMSpec
    if config['model_type'] != 'kimi_k2' or config['attention_bias'] or \
            config['hidden_act'] != 'silu' or \
            config['scoring_func'] != 'sigmoid' or \
            config['topk_method'] != 'noaux_tc' or \
            not config['norm_topk_prob'] or config['moe_layer_freq'] != 1 \
            or config['n_group'] != 1 or config['topk_group'] != 1 or \
            config['tie_word_embeddings'] or \
            config['num_nextn_predict_layers'] or \
            config['rope_scaling']['type'] != 'yarn' or \
            config['num_key_value_heads'] != config['num_attention_heads']:
        raise ValueError('serve_sessions: the configuration is not the '
                         'block this runner builds')
    depth = config['num_hidden_layers']
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth,
        d_model=config['hidden_size'],
        d_inner=config['moe_intermediate_size'], block='latent_moe',
        layer_types=[FULL] * depth,
        latent={FULL: dict(
            n_head=config['num_attention_heads'],
            q_rank=config['q_lora_rank'], kv_rank=config['kv_lora_rank'],
            d_nope=config['qk_nope_head_dim'],
            d_rope=config['qk_rope_head_dim'], d_v=config['v_head_dim'],
            rope_theta=config['rope_theta'],
            rope_scaling=config['rope_scaling'])},
        dense_layers=min(config['first_k_dense_replace'], depth),
        d_inner_dense=config['intermediate_size'],
        index_topk=0, lora_rescale=False, attn_gate=False,
        routed_scale=config['routed_scaling_factor'],
        n_experts=config['published']['n_routed_experts'],
        experts_held=config['n_routed_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['num_experts_per_tok'],
        n_shared_experts=config['n_shared_experts'],
        norm_eps=config['rms_norm_eps'], dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed)
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, engine, traffic, config, signatures)
    finally:
        engine.shutdown(drain=False)


def held_sample(good, asks, cached, reference, rng):
    """A seeded sample of ``reference['requests']`` completed requests:
    ``long_requests`` first asks that ran past ``long_tokens`` tokens
    (prompt and answer together), ``shared_requests`` later asks of
    different documents that were served from shared pages, the rest as
    the seed orders them."""
    order = [good[i] for i in rng.permutation(len(good))]
    long_ones = [r for r in order
                 if asks[r.request.index].ask == 0
                 and r.request.prompt_len + r.request.answer_len
                 > reference['long_tokens']][:reference['long_requests']]
    shared, documents = [], set()
    for r in order:
        ask = asks[r.request.index]
        if ask.ask and cached.get(r.request.index) and \
                ask.document not in documents and \
                len(shared) < reference['shared_requests']:
            shared.append(r)
            documents.add(ask.document)
    first = long_ones + shared
    rest = [r for r in order if r not in first]
    return first + rest[:max(0, reference['requests'] - len(first))], \
        len(long_ones), len(shared)


def chunks_dispatched(t_trace):
    """Every prefill chunk the worker dispatched, in order, as
    ``readers/prefill_ops_mxu.py`` takes them: the prefill it is of
    (``run``), that prefill's ``decode.prefill.run`` span in seconds
    from ``t_trace`` by the recorder's clock (``t``, ``dur``), the
    chunk's ``bucket`` and its ``pairs`` (None on a program whose spans
    carry no count). A prefill of one chunk has no chunk span: the
    prefill's span is the chunk's."""
    from paddle_tpu import observe
    recorder = observe.spans()
    events = sorted((ev for ev in recorder.events() if ev.get('name') in (
        'decode.prefill.run', 'decode.prefill.chunk')),
        key=lambda ev: ev['ts'])
    out, run = [], -1
    for ev in events:
        args = ev.get('args') or {}
        if ev['name'] == 'decode.prefill.run':
            run += 1
            span = {'run': run, 'dur': ev['dur'] / 1e6,
                    't': recorder.perf_time(ev) - t_trace}
            if args.get('chunks', 1) > 1:
                continue
        elif run < 0:
            continue        # the ring lost this chunk's prefill
        out.append(dict(span, bucket=args.get('bucket'),
                        pairs=args.get('attn_pairs')))
    return out


def hand_over_prefill_chunks(ctx):
    """What ``readers/prefill_ops_mxu.py`` sets against each other, in a
    traced run: the chunks as dispatched and the programs as the chip
    ran them. The trace's directory is the harness's own; it reduces and
    removes it after the runner returns."""
    if ctx.t_trace is None:
        return
    from benchmark import tracelib
    reader = manifest.load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'readers', 'prefill_ops_mxu.py'))
    path = tracelib.find_xplane(getattr(ctx, '_trace_dir', ''))
    if path:
        ctx.sources['prefill_chunks'] = chunks_dispatched(ctx.t_trace)
        ctx.sources['prefill_program_runs'] = reader.program_runs(path)


def serve(ctx, engine, traffic, config, signatures):
    vocab = config['model']['vocab_size']
    blocks = config['engine']['num_blocks']
    limits = config['reference']
    preroll = traffic['preroll_s']
    requests, asks = sessions.schedule(traffic, ctx.seed, ctx.seconds)
    prompts = {r.index: sessions.prompt_tokens(r, asks[r.index], vocab)
               for r in requests}
    streams = {}

    def submit(request):
        stream = engine.submit(prompts[request.index],
                               max_new_tokens=request.answer_len)
        streams[request.index] = stream
        return stream

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
    # what did not finish keeps the engine busy: the checks below need it
    # idle (a dispatch donates the arrays the reference reads)
    idle = engine.drain(timeout=traffic['drain_s'])
    # once idle: every prefill that ran in the traced tail has closed
    # its span, and reading the trace delays no token's time stamp
    hand_over_prefill_chunks(ctx)

    sample = [r for r in records if r.request.due >= preroll]
    good = [r for r in sample if r.complete]
    refused = sum(1 for r in sample if r.refused)
    errored = sum(1 for r in sample if r.error)
    ttft = [r.ttft for r in good]
    gaps = [g for r in good for g in r.gaps]
    in_window = sum(1 for r in records for t in r.token_at if lo <= t < hi)
    late = [r.sent_at - r.due_at for r in records]
    # the prompt tokens the engine took from shared pages, by request
    cached = {i: getattr(s, 'cached_tokens', None) or 0
              for i, s in streams.items()}
    hit = [r.ttft for r in good if cached.get(r.request.index)]
    miss = [r.ttft for r in good if not cached.get(r.request.index)]
    offered = sum(r.request.prompt_len for r in sample)

    # the engine's invariant, on the chip: the same prompts one at a
    # time through the same programs (module docstring)
    rng = np.random.RandomState(ctx.seed % (1 << 32))
    short = [r for r in good
             if r.request.answer_len <= traffic['recheck_max_answer']]

    def alone(r):
        return engine.generate(prompts[r.request.index],
                               max_new_tokens=r.request.answer_len,
                               timeout=600) == r.tokens
    hits = [r for r in short if cached.get(r.request.index)]
    hit_again = alone(hits[rng.randint(len(hits))]) if hits else None
    missed = [r for r in short if not cached.get(r.request.index)]
    again = [missed[i] for i in rng.permutation(len(missed))[
        :traffic['recheck_requests']]]
    same = True
    for r in again:
        engine.prefix_cache.clear()
        same = alone(r) and same
    held, n_long, n_shared = held_sample(good, asks, cached, limits, rng)
    recorder = _Recorder(ctx.reference)
    ctx.reference, kept = recorder, ctx.reference
    try:
        reference = against_reference(ctx, engine, config, prompts, held)
    finally:
        ctx.reference = kept
    agrees = reference['reference_agrees']
    # the window has to be held to the reference where it is hard: a long
    # first ask prefilled in chunks, and suffixes after shared pages
    reaches = (n_long >= limits['long_requests']
               and n_shared >= limits['shared_requests'])

    ms = 1000.0

    def mean_ms(values):
        return ms * sum(values) / len(values) if values else None

    return {
        'correct': bool(same and again and agrees and reaches and idle
                        and len(good) == len(sample) and unfinished == 0),
        'attempted': len(sample),
        'failed': len(sample) - len(good),
        'end_to_end': {
            'ttft_mean_ms': mean_ms(ttft),
            'itl_mean_ms': mean_ms(gaps),
            'serve_tokens_per_s': in_window / ctx.seconds,
            'ttft_p90_ms': ms * stats.percentile(ttft, 90),
            'itl_p95_ms': ms * stats.percentile(gaps, 95),
            'prefix_hit_ttft_ms': mean_ms(hit),
            'prefix_miss_ttft_ms': mean_ms(miss),
        },
        'notes': dict(reference, **{
            'signatures': signatures, 'requests_sent': len(records),
            'refused': refused, 'errored': errored,
            'unfinished': unfinished, 'rechecked': len(again),
            'same_one_at_a_time': same, 'hit_again_same': hit_again,
            'reference_share_over': {
                str(t): sum(1 for g in recorder.gaps if g > t)
                / float(len(recorder.gaps) or 1) for t in LADDER},
            'held_long_first_asks': n_long,
            'held_shared_later_asks': n_shared,
            'held_cached_tokens': [cached.get(r.request.index, 0)
                                   for r in held],
            'held_asks': [asks[r.request.index].ask for r in held],
            'requests_hit': len(hit), 'requests_missed': len(miss),
            'documents_in_window': len({asks[r.request.index].document
                                        for r in sample}),
            'cached_share_of_prompt_tokens': sum(
                cached.get(r.request.index, 0) for r in sample)
            / float(offered),
            'schedule_shared_share': sessions.shared_share(
                requests, asks, config['engine']['block_size'], preroll),
            'ttft_p50_ms': ms * stats.percentile(ttft, 50),
            'itl_p50_ms': ms * stats.percentile(gaps, 50),
            'itl_p99_ms': ms * stats.percentile(gaps, 99),
            'itl_max_ms': ms * max(gaps),
            'ttft_samples': len(ttft), 'itl_samples': len(gaps),
            'prompt_len_p50': stats.percentile(
                [r.request.prompt_len for r in sample], 50),
            'prompt_len_mean': offered / float(len(sample)),
            'answer_len_p50': stats.percentile(
                [r.request.answer_len for r in sample], 50),
            'requests_over_long_tokens': sum(
                1 for r in sample if r.request.prompt_len
                + r.request.answer_len > limits['long_tokens']),
            'offered_tokens_per_s': sum(
                r.request.answer_len for r in sample) / ctx.seconds,
            'pacer_late_ms_p50': ms * stats.percentile(late, 50),
            'pacer_late_ms_p95': ms * stats.percentile(late, 95),
            'pacer_late_ms_max': ms * max(late),
        }),
    }
