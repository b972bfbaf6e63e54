"""Serving cells of the ssm_hybrid block whose layer is one sublayer
(nemotron_3_super: ``model_type`` nemotron_h): a DecodeEngine over
``LMSpec(block='ssm_hybrid', mixer_only=True)`` under open-loop traffic.

``runners/serve_ssm.py::spec_of`` is written for granite's config.json
(a mixer and a dense MLP in every layer, one state group, no experts,
``layer_types`` a list) and refuses everything else. This runner reads
another published config.json's keys (``hybrid_override_pattern``,
``n_groups``, ``moe_latent_size``, ``mlp_hidden_act`` relu2 ...) and a
cut that starts at a layer of its own (``first_layer``), and is
otherwise that file, loaded as it loads ``serve_block.py``: the window,
the pre-roll, the held sample, the one-at-a-time check, the limits and
what ``correct`` means (``serve``, ``held_sample``, ``within_limits``,
``against_reference``), the reader of a stream (``poll``), the state
pool's used share and the traced tail's prefill chunks (``_Watched``,
``chunks_dispatched``) are its own, used as they are.

The weights (``draw_weights``): every matrix N(0, 1 / fan-in) from the
program's own table; the four that write to the residual stream (the
Mamba-2 and the attention's output projections, the projection out of
the experts' latent and the shared expert's second matrix) times
``1 / sqrt(2 x published layers)``, the published
``rescale_prenorm_residual``; the router's selection bias N(0, 0.05^2),
so that it changes choices; the convolution's bias zero, the gains and
the skip gain ones. A parameter of a gigabyte or more (the two expert
stacks, 3.5 GB each) is dropped before its successor is drawn: held
twice beside the model it does not fit the chip.
"""

import math
import os

from benchmark import manifest, weights

_HERE = os.path.dirname(os.path.abspath(__file__))
_ssm = manifest.load_module(os.path.join(_HERE, 'serve_ssm.py'))
_block = _ssm._block
poll = _ssm.poll
serve = _ssm.serve
held_sample = _ssm.held_sample
within_limits = _ssm.within_limits
against_reference = _ssm.against_reference
chunks_dispatched = _ssm.chunks_dispatched

KINDS = {'M': 'mamba', '*': 'attention', 'E': 'moe'}
# the matrices whose product is added to the residual stream
TO_RESIDUAL = ('lm_mamba_out.w', 'lm_attn_o.w', 'lm_moe_lat_out.w',
               'lm_moe_shr_down.w')
BIAS_STD = 0.05
# a parameter this large (at 2 bytes an element) is dropped before it is
# drawn again
DROP_FIRST_BYTES = 1 << 30


def spec_of(config):
    """The LMSpec of a nemotron_h config.json, cut as the file says:
    ``hybrid_override_pattern`` is the ``num_hidden_layers`` layers from
    ``first_layer`` on of the published pattern; ``n_routed_experts`` is
    what is held here of ``published.n_routed_experts``."""
    from paddle_tpu.serving.decode import LMSpec
    heads, width = config['mamba_num_heads'], config['mamba_head_dim']
    pattern = config['hybrid_override_pattern']
    if config['model_type'] != 'nemotron_h' or config['attention_bias'] \
            or config['mamba_proj_bias'] or config['mlp_bias'] or \
            config['use_bias'] or not config['use_conv_bias'] or \
            config['mlp_hidden_act'] != 'relu2' or \
            config['mamba_hidden_act'] != 'silu' or \
            config['n_group'] != 1 or config['topk_group'] != 1 or \
            not config['norm_topk_prob'] or config['tie_word_embeddings'] \
            or config['n_shared_experts'] != 1 or \
            config['norm_eps'] != config['layer_norm_epsilon'] or \
            heads * width != config['expand'] * config['hidden_size'] or \
            set(pattern) - set(KINDS):
        raise ValueError('serve_ssm_moe: the configuration is not the '
                         'block this runner builds')
    first, depth = config['first_layer'], config['num_hidden_layers']
    published = config['published']
    if len(published['hybrid_override_pattern']) != \
            published['num_hidden_layers'] or \
            published['hybrid_override_pattern'][first:first + depth] \
            != pattern:
        raise ValueError('serve_ssm_moe: layers %d..%d of the published '
                         'pattern are not %r' % (first, first + depth - 1,
                                                 pattern))
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth,
        n_head=config['num_attention_heads'],
        n_kv_head=config['num_key_value_heads'],
        d_key=config['head_dim'], d_value=config['head_dim'],
        d_model=config['hidden_size'],
        d_inner=config['moe_intermediate_size'], block='ssm_hybrid',
        layer_types=[KINDS[c] for c in pattern], mixer_only=True,
        tie_embeddings=False, ssm_heads=heads, ssm_head_dim=width,
        ssm_state=config['ssm_state_size'], ssm_conv=config['conv_kernel'],
        ssm_chunk=config['chunk_size'], ssm_groups=config['n_groups'],
        n_experts=published['n_routed_experts'],
        experts_held=config['n_routed_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['num_experts_per_tok'],
        n_shared_experts=config['n_shared_experts'],
        routed_scale=config['routed_scaling_factor'],
        moe_latent=config['moe_latent_size'],
        d_inner_shared=config['moe_shared_expert_intermediate_size'],
        norm_eps=config['norm_eps'], dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed, config)
    # benchmark/sweep.py reads the vocabulary from here
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


def _time_constants(key, shape, dt_range, floor):
    """(dt_bias, A_log) of ``shape`` [layers, heads], float32, as
    ``serve_ssm._time_constants`` draws them over the configuration's
    own range: ``dt`` log-uniform over ``dt_range`` and at least
    ``floor``, the bias its inverse under softplus; ``A`` uniform over
    ``serve_ssm.A_RANGE`` and ``A_log`` its logarithm."""
    import jax
    import jax.numpy as jnp
    k_dt, k_a = jax.random.split(key)
    lo, hi = (jnp.log(v) for v in dt_range)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        k_dt, shape, jnp.float32, lo, hi)), floor)
    return (dt + jnp.log(-jnp.expm1(-dt)),
            jnp.log(jax.random.uniform(k_a, shape, jnp.float32,
                                       *_ssm.A_RANGE)))


def draw_weights(engine, seed, config):
    """Every matrix and bias drawn again on the device from the seed
    (module docstring), one parameter at a time, a layer at a time
    inside it (``serve_block._drawn``); the time-step bias and ``A_log``
    as the published implementation initialises them, over
    ``time_step_min`` .. ``time_step_max``."""
    import jax
    from paddle_tpu.serving.decode.model import block_param_shapes
    draw = jax.jit(_block._drawn, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    shapes = block_param_shapes(engine.spec)
    rescale = (2 * config['published']['num_hidden_layers']) ** -0.5 \
        if config['rescale_prenorm_residual'] else 1.0
    for i, (name, (shape, fan_in, _)) in enumerate(shapes.items()):
        if fan_in is None or name in (
                'lm_mamba_conv.b', 'lm_mamba_dt.b', 'lm_mamba_a_log'):
            continue
        std = fan_in ** -0.5 if fan_in else BIAS_STD
        if name in TO_RESIDUAL:
            std *= rescale
        if math.prod(shape) * 2 >= DROP_FIRST_BYTES:
            engine.device_weights()[name].delete()
        engine.load_weights({name: draw(
            jax.random.fold_in(key, i), tuple(shape),
            engine.spec.dtype if fan_in else 'float32', std)})
    if 'lm_mamba_dt.b' in shapes:
        dt_bias, a_log = jax.jit(_time_constants, static_argnums=(1, 2, 3))(
            jax.random.fold_in(key, len(shapes)),
            tuple(shapes['lm_mamba_dt.b'][0]),
            (config['time_step_min'], config['time_step_max']),
            config['time_step_floor'])
        engine.load_weights({'lm_mamba_dt.b': dt_bias,
                             'lm_mamba_a_log': a_log})


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, _ssm._Watched(engine, ctx), traffic, config,
                     signatures)
    finally:
        engine.shutdown(drain=False)
