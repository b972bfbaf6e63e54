"""Serving cells of a configuration given by its published config's keys:
a DecodeEngine over an ``LMSpec`` block family under open-loop traffic.

``runners/serve.py`` builds ``LMSpec(**config['model'])`` and moves every
weight through the host (``export_weights()`` to numpy to ``device_put``)
for the redraw and again for the reference. A model whose weights fill
most of the chip can afford neither, so this runner

- reads the model from the configuration's own keys (``hidden_size``,
  ``num_experts`` ... as the source's config.json names them), so that
  the file is the published config with the cut beside it;
- draws every matrix on the device from ``--seed`` at the dtype the
  engine declared, one parameter at a time, a layer at a time inside
  it, and never copies one;
- hands the reference the engine's own device arrays
  (``engine.device_weights()``), which it upcasts one matrix or one
  expert at a time (``references/<config>.py``);
- holds to the reference a seeded sample of the window's requests of
  which at least ``long_requests`` passed ``long_tokens`` tokens in their
  life, so that the window and the chunked prefill are compared at the
  published width on the chip.

The window, the pre-roll, the sample, the one-at-a-time check and what
``correct`` means are ``runners/serve.py``'s; its ``poll`` is used as it
is.
"""

import os
import time

import numpy as np

from benchmark import loadgen, manifest, stats, weights

_base = manifest.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), 'serve.py'))
poll = _base.poll


def spec_of(config):
    """The LMSpec of a cohere2_moe config.json, cut as the file says:
    ``num_experts`` is what is held here of ``published.num_experts``."""
    from paddle_tpu.serving.decode import LMSpec
    if config['model_type'] != 'cohere2_moe' or \
            not config['use_parallel_block'] or config['use_qk_norm'] or \
            config['attention_bias'] or not config['tie_word_embeddings'] \
            or config['first_k_dense_replace'] or \
            config['expert_selection_fn'] != 'sigmoid' or \
            not config['norm_topk_prob'] or \
            config['position_embedding_type'] != 'rope_gptj' or \
            config['shared_expert_combination_strategy'] != 'average' or \
            not config['use_gated_activation'] or \
            config['hidden_act'] != 'silu' or config['rotary_pct'] != 1:
        raise ValueError('serve_block: the configuration is not the '
                         'block this runner builds')
    depth = config['num_hidden_layers']
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth,
        n_head=config['num_attention_heads'],
        n_kv_head=config['num_key_value_heads'],
        d_key=config['head_dim'], d_value=config['head_dim'],
        d_model=config['hidden_size'], d_inner=config['intermediate_size'],
        block='parallel_moe', layer_types=config['layer_types'][:depth],
        sliding_window=config['sliding_window'],
        rope_theta=config['rope_theta'],
        n_experts=config['published']['num_experts'],
        experts_held=config['num_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['num_experts_per_tok'],
        n_shared_experts=config['num_shared_experts'],
        norm_eps=config['layer_norm_eps'],
        logit_scale=config['logit_scale'], dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed)
    # benchmark/sweep.py reads the vocabulary from here
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


def _drawn(key, shape, dtype, std):
    """A matrix drawn N(0, std^2): one slice of the leading axis at a
    time where it is stacked, so that the random bits of 0.3 G elements
    are alive at once and not those of 1.1 G."""
    import jax
    import jax.numpy as jnp

    def one(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    if len(shape) < 3:
        return one(key, shape)
    return jax.lax.map(lambda k: one(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def draw_weights(engine, seed):
    """Every matrix drawn again on the device from the seed, N(0, 1 /
    fan-in) as the engine's own initializer draws it; the norms' gains
    stay ones. One parameter at a time, and no reference to the old one
    kept: the engine drops it as the new one is loaded, so the chip
    holds one parameter twice at most (2.1 GB), never the model."""
    import jax
    from paddle_tpu.serving.decode.model import moe_param_shapes
    draw = jax.jit(_drawn, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    for i, (name, (shape, fan_in, _)) in enumerate(
            moe_param_shapes(engine.spec).items()):
        if fan_in is not None:      # a matrix, kept at the spec's dtype
            engine.load_weights({name: draw(
                jax.random.fold_in(key, i), tuple(shape),
                engine.spec.dtype, fan_in ** -0.5)})


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, engine, traffic, config, signatures)
    finally:
        engine.shutdown(drain=False)


def held_sample(good, reference, rng):
    """A seeded sample of ``reference['requests']`` completed requests,
    of which at least ``long_requests`` (where the window has them) ran
    past ``long_tokens`` tokens, prompt and answer together."""
    order = [good[i] for i in rng.permutation(len(good))]
    long_ones = [r for r in order
                 if r.request.prompt_len + r.request.answer_len
                 > reference['long_tokens']][:reference['long_requests']]
    rest = [r for r in order if r not in long_ones]
    return long_ones + rest[:max(0, reference['requests'] - len(long_ones))]


def within_limits(gaps, limits):
    """Whether the served tokens agree with the reference: ``gaps`` is,
    token by token, how far the reference's largest logit lies over its
    logit of the token served. A routed model under the stated bf16
    arithmetic now and then flips a near-tied choice of expert, which
    moves that one position's logits by a few tenths and nothing else,
    so the largest gap of hundreds of tokens is no measure of the
    arithmetic (PERF.md section 6). At most ``gap_outlier_share_tol``
    of the tokens may lie more than ``logit_gap_tol`` under, and none
    more than ``logit_gap_cap``."""
    if not gaps:
        return False
    over = sum(1 for g in gaps if g > limits['logit_gap_tol'])
    return max(gaps) <= limits['logit_gap_cap'] and \
        over <= limits['gap_outlier_share_tol'] * len(gaps)


def against_reference(ctx, engine, config, prompts, served):
    """The served tokens' gaps by the reference's logits over ``served``
    (records): whether they are within the config's limits, the largest
    and where it lies, how many pass ``logit_gap_tol`` and how many
    differ from the reference's choice; the deviation of its logits."""
    spec = engine.spec
    limits = config['reference']
    gaps, deviation, longest, worst = [], 0.0, 0, None
    for r in served:
        # every dispatch donates the scope and hands the weights back as
        # new arrays: take them as they are now, with the engine idle
        one, deviation = ctx.reference.token_gaps(
            engine.device_weights(), ctx.reference.arch_of(spec),
            ctx.reference.held_of(spec),
            prompts[r.request.index], r.tokens, limits['pad_to'])
        if one and (worst is None or max(one) > worst[0]):
            worst = (max(one), r.request.index, one.index(max(one)))
        gaps.extend(one)
        longest = max(longest, r.request.prompt_len + len(r.tokens))
    return {'reference_agrees': within_limits(gaps, limits),
            'reference_gap_max': max(gaps) if gaps else None,
            'reference_gap_mean': sum(gaps) / len(gaps) if gaps else None,
            'reference_worst_request': worst[1] if worst else None,
            'reference_worst_token': worst[2] if worst else None,
            'reference_tokens': len(gaps),
            'reference_tokens_over_tol': sum(
                1 for g in gaps if g > limits['logit_gap_tol']),
            'reference_tokens_not_first': sum(1 for g in gaps if g > 0),
            'reference_logit_std': deviation,
            'reference_requests': len(served),
            'reference_longest_tokens': longest}


def serve(ctx, engine, traffic, config, signatures):
    vocab = config['model']['vocab_size']
    blocks = config['engine']['num_blocks']
    preroll = traffic['preroll_s']
    requests = loadgen.schedule(traffic, ctx.seed, ctx.seconds)
    prompts = {r.index: loadgen.prompt_tokens(r, vocab) for r in requests}

    def submit(request):
        return engine.submit(prompts[request.index],
                             max_new_tokens=request.answer_len)

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
                               timeout=300) == r.tokens for r in again)
    held = held_sample(good, config['reference'], rng)
    reference = against_reference(ctx, engine, config, prompts, held)
    agrees = reference['reference_agrees']
    # the window has to be held to the reference, not only the short ones
    reaches = (reference['reference_longest_tokens']
               > config['reference']['long_tokens'])

    ms = 1000.0
    return {
        'correct': bool(same and again and agrees and reaches and idle
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
            'prompt_len_mean': sum(r.request.prompt_len for r in sample)
            / float(len(sample)),
            'answer_len_p50': stats.percentile(
                [r.request.answer_len for r in sample], 50),
            'requests_over_long_tokens': sum(
                1 for r in sample if r.request.prompt_len
                + r.request.answer_len > config['reference']['long_tokens']),
            'offered_tokens_per_s': sum(
                r.request.answer_len for r in sample) / ctx.seconds,
            'pacer_late_ms_p50': ms * stats.percentile(late, 50),
            'pacer_late_ms_p95': ms * stats.percentile(late, 95),
            'pacer_late_ms_max': ms * max(late),
        }),
    }
