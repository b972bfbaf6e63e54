"""Serving cells of the latent_moe block family (dots3_note): a
DecodeEngine over ``LMSpec(block='latent_moe')`` under open-loop
traffic.

``runners/serve_block.py`` builds one block (``cohere2_moe``) and draws
its weights from ``moe_param_shapes``. This runner reads another
published config.json's keys and another parameter list, and is
otherwise that file, loaded as it loads ``serve.py``: the window, the
pre-roll, the held sample, the one-at-a-time check, the limits and what
``correct`` means (``serve``, ``held_sample``, ``within_limits``,
``against_reference``), the reader of a stream (``poll``) and the
drawing of one matrix on the device (``_drawn``) are its own, used as
they are.
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
# a bias (the router's selection bias, the index key's LayerNorm bias) is
# drawn this wide: against sigmoid scores whose 8th and 9th of 256 lie
# about 0.01 apart, it changes which experts are chosen
BIAS_STD = 0.05


def spec_of(config):
    """The LMSpec of a dots3_note config.json, cut as the file says:
    ``n_routed_experts`` is what is held here of
    ``published.n_routed_experts``, ``num_hidden_layers`` the leading
    layers of ``layer_types`` that are run."""
    from paddle_tpu.serving.decode import LMSpec
    if config['model_type'] != 'dots3_note' or config['attention_bias'] \
            or config['hidden_act'] != 'silu' or \
            config['scoring_func'] != 'sigmoid' or \
            config['topk_method'] != 'noaux_tc' or \
            not config['norm_topk_prob'] or config['moe_layer_freq'] != 1 \
            or config['routed_scaling_factor'] != 1 or \
            config['rope_scaling'] is not None or \
            config['tie_word_embeddings'] or \
            config['attention_gate_type'] != 'headwise' or \
            config['swa_attention_gate_type'] != 'headwise' or \
            config['num_key_value_heads'] != config['num_attention_heads'] \
            or config['swa_num_key_value_heads'] != \
            config['swa_num_attention_heads']:
        raise ValueError('serve_latent: the configuration is not the '
                         'block this runner builds')
    depth = config['num_hidden_layers']

    def shape(prefix, theta):
        return dict(n_head=config[prefix + 'num_attention_heads'],
                    q_rank=config[prefix + 'q_lora_rank'],
                    kv_rank=config[prefix + 'kv_lora_rank'],
                    d_nope=config[prefix + 'qk_nope_head_dim'],
                    d_rope=config[prefix + 'qk_rope_head_dim'],
                    d_v=config[prefix + 'v_head_dim'], rope_theta=theta)
    kinds = set(config['layer_types'][:depth])
    latent = {}
    if FULL in kinds:
        latent[FULL] = shape('', config['rope_theta'])
    if SLIDING in kinds:
        latent[SLIDING] = shape('swa_', config['swa_rope_theta'])
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth,
        d_model=config['hidden_size'],
        d_inner=config['moe_intermediate_size'], block='latent_moe',
        layer_types=config['layer_types'][:depth],
        sliding_window=config['sliding_window_size'], latent=latent,
        dense_layers=min(config['first_k_dense_replace'], depth),
        d_inner_dense=config['intermediate_size'],
        index_n_heads=config['index_n_heads'],
        index_head_dim=config['index_head_dim'],
        index_topk=config['index_topk'],
        n_experts=config['published']['n_routed_experts'],
        experts_held=config['n_routed_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['num_experts_per_tok'],
        n_shared_experts=config['n_shared_experts'],
        norm_eps=config['rms_norm_eps'],
        lora_rescale=config['apply_mla_qkv_lora_rescale'],
        dtype=config['dtype'])


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
    fan-in) as the engine's own initializer draws it, and every bias
    N(0, BIAS_STD^2); the norms' gains stay ones. One parameter at a
    time, a layer at a time inside it (``serve_block._drawn``), and no
    reference to the old one kept."""
    import jax
    from paddle_tpu.serving.decode.model import block_param_shapes
    draw = jax.jit(_block._drawn, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    for i, (name, (shape, fan_in, _)) in enumerate(
            block_param_shapes(engine.spec).items()):
        if fan_in is None:          # a gain
            continue
        engine.load_weights({name: draw(
            jax.random.fold_in(key, i), tuple(shape),
            engine.spec.dtype if fan_in else 'float32',
            fan_in ** -0.5 if fan_in else BIAS_STD)})


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, engine, traffic, config, signatures)
    finally:
        engine.shutdown(drain=False)
