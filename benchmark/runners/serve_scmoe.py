"""Serving cells of the shortcut_moe block family (longcat_flash_chat): a
DecodeEngine over ``LMSpec(block='shortcut_moe')`` under open-loop
traffic.

``runners/serve_latent.py::spec_of`` is written for ``latent_moe`` alone
(one attention and one FFN a layer, sigmoid routing, a shared expert).
This runner reads another published config.json's keys (``num_layers``,
``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``,
``zero_expert_num``, ``mla_scale_*``) and another parameter table
(``serving/decode/model.py``: ``shortcut_param_shapes``), and is
otherwise ``runners/serve_block.py``, loaded as ``serve_latent.py`` and
``serve_gqa_moe.py`` load it: the window, the pre-roll, the held sample,
the one-at-a-time check, the limits and what ``correct`` means
(``serve``, ``held_sample``, ``within_limits``, ``against_reference``),
the reader of a stream (``poll``) and the drawing of one matrix on the
device (``_drawn``) are its own, used as they are.

One thing is this configuration's: the router's draw. Every other matrix
is drawn N(0, 1 / fan-in); the router's columns are drawn
``router_logit_std`` times that, so that its logits have that deviation
and the softmax over 768 outputs is peaked as a trained router's is
(``configs/longcat_flash_chat.json``: ``assumed.router`` has the
measured sums), and its selection-only bias N(0, ``router_bias_std``^2),
of the order of the gap between the 12th and the 13th score, so that it
changes choices without making them.
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

FULL = 'full_attention'


def spec_of(config):
    """The LMSpec of a LongCat-Flash config.json, cut as the file says:
    ``n_routed_experts`` is what is held here of
    ``published.n_routed_experts`` (the router stays as wide as the
    published real and identity experts together), ``num_layers`` the
    leading layers that are run."""
    from paddle_tpu.serving.decode import LMSpec
    if config['attention_method'] != 'MLA' or config['attention_bias'] or \
            config['zero_expert_type'] != 'identity' or \
            not config['mla_scale_q_lora'] or \
            not config['mla_scale_kv_lora'] or \
            config.get('rope_scaling') is not None:
        raise ValueError('serve_scmoe: the configuration is not the '
                         'block this runner builds')
    depth = config['num_layers']
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth,
        d_model=config['hidden_size'],
        d_inner=config['expert_ffn_hidden_size'], block='shortcut_moe',
        layer_types=[FULL] * depth,
        latent={FULL: dict(n_head=config['num_attention_heads'],
                           q_rank=config['q_lora_rank'],
                           kv_rank=config['kv_lora_rank'],
                           d_nope=config['qk_nope_head_dim'],
                           d_rope=config['qk_rope_head_dim'],
                           d_v=config['v_head_dim'],
                           rope_theta=config['rope_theta'])},
        d_inner_dense=config['ffn_hidden_size'],
        n_experts=config['published']['n_routed_experts'],
        zero_experts=config['zero_expert_num'],
        experts_held=config['n_routed_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['moe_topk'],
        routed_scale=config['routed_scaling_factor'],
        norm_eps=config['rms_norm_eps'], lora_rescale=True,
        attn_gate=False, dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed, config['router_logit_std'],
                 config['router_bias_std'])
    # benchmark/sweep.py reads the vocabulary from here
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


def draw_weights(engine, seed, router_logit_std, router_bias_std):
    """Every matrix drawn again on the device from the seed, N(0, 1 /
    fan-in) as the engine's own initializer draws it (the router's
    ``router_logit_std`` times as wide), and the router's bias N(0,
    ``router_bias_std``^2); the norms' gains stay ones. One parameter at
    a time, a layer at a time inside it (``serve_block._drawn``), and no
    reference to the old one kept."""
    import jax
    from paddle_tpu.serving.decode.model import block_param_shapes
    draw = jax.jit(_block._drawn, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    for i, (name, (shape, fan_in, _)) in enumerate(
            block_param_shapes(engine.spec).items()):
        if fan_in is None:          # a gain
            continue
        std = fan_in ** -0.5 if fan_in else router_bias_std
        if name == 'lm_moe_router.w':
            std *= router_logit_std
        engine.load_weights({name: draw(
            jax.random.fold_in(key, i), tuple(shape),
            engine.spec.dtype if fan_in else 'float32', std)})


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, engine, traffic, config, signatures)
    finally:
        engine.shutdown(drain=False)
