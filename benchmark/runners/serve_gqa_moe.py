"""Serving cells of the gqa_moe block family (mellum2_12b): a
DecodeEngine over ``LMSpec(block='gqa_moe')`` under open-loop traffic.

``runners/serve_block.py`` builds one block (``cohere2_moe``) and draws
its weights from ``moe_param_shapes``. This runner reads another
published config.json's keys and another parameter list, and is
otherwise that file, loaded as ``serve_latent.py`` loads it: the window,
the pre-roll, the held sample, the one-at-a-time check, the limits and
what ``correct`` means (``serve``, ``held_sample``, ``within_limits``,
``against_reference``), the reader of a stream (``poll``) and the drawing
of one matrix on the device (``_drawn``) are its own, used as they are.

One thing is added. ``serve`` samples the pages used of the pool that
``engine.free_pages()`` speaks for, the first. This block's cache lies
in two page pools (the full layers' keeps every page, the sliding
layers' a window's), so ``serve`` is handed the engine behind
``_PoolsSampled``, which at each of ``serve``'s own samples also notes
every pool's used share under ``kv_pool_used_pct.<pool>``: the
``sampled_gauge`` readers of ``serve.full_kind_pool_used_pct`` and
``serve.window_kind_pool_used_pct`` read those.
"""

import os

from benchmark import manifest, weights

_block = manifest.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), 'serve_block.py'))
poll = _block.poll
serve = _block.serve
held_sample = _block.held_sample
within_limits = _block.within_limits
against_reference = _block.against_reference

FULL, SLIDING = 'full_attention', 'sliding_attention'


def spec_of(config):
    """The LMSpec of a mellum config.json, cut as the file says:
    ``num_hidden_layers`` the leading layers of ``layer_types`` that are
    run; every expert and the whole vocabulary are held."""
    from paddle_tpu.serving.decode import LMSpec
    depth = config['num_hidden_layers']
    if config['model_type'] != 'mellum' or config['attention_bias'] or \
            config['hidden_act'] != 'silu' or \
            not config['norm_topk_prob'] or \
            config['tie_word_embeddings'] or \
            not config['use_sliding_window'] or \
            set(config['mlp_layer_types'][:depth]) != {'sparse'}:
        raise ValueError('serve_gqa_moe: the configuration is not the '
                         'block this runner builds')
    kinds = config['layer_types'][:depth]
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth,
        n_head=config['num_attention_heads'],
        n_kv_head=config['num_key_value_heads'],
        d_key=config['head_dim'], d_value=config['head_dim'],
        d_model=config['hidden_size'],
        d_inner=config['moe_intermediate_size'], block='gqa_moe',
        layer_types=kinds, sliding_window=config['sliding_window'],
        rope_parameters={kind: config['rope_parameters'][kind]
                         for kind in set(kinds)},
        n_experts=config['published'].get('num_experts',
                                          config['num_experts']),
        experts_held=config['num_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['num_experts_per_tok'],
        norm_eps=config['rms_norm_eps'], dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed)
    # benchmark/sweep.py reads the vocabulary from here
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


def draw_weights(engine, seed):
    """Every matrix drawn again on the device from the seed, N(0, 1 /
    fan-in) as the engine's own initializer draws it; the norms' gains
    stay ones. One parameter at a time, a layer at a time inside it
    (``serve_block._drawn``), and no reference to the old one kept."""
    import jax
    from paddle_tpu.serving.decode.model import block_param_shapes
    draw = jax.jit(_block._drawn, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    for i, (name, (shape, fan_in, _)) in enumerate(
            block_param_shapes(engine.spec).items()):
        if fan_in is not None:      # a matrix, kept at the spec's dtype
            engine.load_weights({name: draw(
                jax.random.fold_in(key, i), tuple(shape),
                engine.spec.dtype, fan_in ** -0.5)})


class _PoolsSampled(object):
    """The engine as ``serve`` is handed it: everything is the
    engine's, and ``free_pages()``, which ``serve`` calls once a sample
    inside the window, also notes every page pool's used share."""

    def __init__(self, engine, samples):
        self._engine, self._samples = engine, samples

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def free_pages(self):
        for pool in self._engine.pools:
            self._samples.setdefault(
                'kv_pool_used_pct.%s' % (pool.kind or 'full'), []).append(
                    100.0 * pool.used_blocks() / pool.num_blocks)
        return self._engine.free_pages()


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, _PoolsSampled(engine, ctx.samples), traffic,
                     config, signatures)
    finally:
        engine.shutdown(drain=False)
