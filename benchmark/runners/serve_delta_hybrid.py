"""Serving cells of the delta_hybrid block (qwen3_next: ``model_type``
qwen3_next): a DecodeEngine over ``LMSpec(block='delta_hybrid')`` under
open-loop traffic.

``runners/serve_ssm.py::spec_of`` is written for granite's config.json
and refuses everything else. This runner reads another published
config.json's keys (``linear_num_value_heads``, ``full_attention_interval``,
``partial_rotary_factor``, ``shared_expert_intermediate_size`` ...) and a
cut that starts at a layer and an expert of its own (``first_layer``,
``first_expert``), and is otherwise that file, loaded as
``serve_ssm_moe.py`` loads it: the window, the pre-roll, the held
sample, the one-at-a-time check, the limits and what ``correct`` means
(``serve``, ``held_sample``, ``within_limits``, ``against_reference``),
the reader of a stream (``poll``), the state pool's used share and the
traced tail's prefill chunks (``_Watched``, ``chunks_dispatched``) are
its own, used as they are.

The weights (``draw_weights``): every matrix N(0, 1 / fan-in) from the
program's own table (the convolution's taps N(0, 1 / 4), the shared
expert's gate N(0, 1 / hidden)); the zero-centred gains stay zero (the
norm multiplies by 1 + them) and the gated norm's plain gain one;
``dt_bias`` ones and ``A`` uniform in (0, 16), ``A_log`` its logarithm,
as the published initialiser draws them. A parameter of a gigabyte or
more (the three expert stacks, 2.1 GB each) is dropped before its
successor is drawn.
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

LINEAR, FULL = 'linear_attention', 'full_attention'
# the published initialiser of a head's decay: A uniform in (0, 16)
A_RANGE = (1e-4, 16.0)
# a parameter this large (at 2 bytes an element) is dropped before it is
# drawn again
DROP_FIRST_BYTES = 1 << 30
# the rows of a prefill program's scan chunk: the published chunked
# delta rule's (fla's chunk_gated_delta_rule: 64)
SCAN_CHUNK = 64


def layer_types(config):
    """The kinds of the ``num_hidden_layers`` layers from ``first_layer``
    on: layer ``i`` of the published model is full attention where ``(i
    + 1) % full_attention_interval == 0``."""
    first, every = config['first_layer'], config['full_attention_interval']
    return [FULL if (first + i + 1) % every == 0 else LINEAR
            for i in range(config['num_hidden_layers'])]


def spec_of(config):
    """The LMSpec of a qwen3_next config.json, cut as the file says:
    ``num_experts`` is what is held here of ``published.num_experts``."""
    from paddle_tpu.serving.decode import LMSpec
    if config['model_type'] != 'qwen3_next' or \
            config['hidden_act'] != 'silu' or \
            not config['norm_topk_prob'] or \
            config['tie_word_embeddings'] or config['use_sliding_window'] \
            or config['rope_scaling'] or config['mlp_only_layers'] or \
            config['decoder_sparse_step'] != 1:
        raise ValueError('serve_delta_hybrid: the configuration is not '
                         'the block this runner builds')
    return LMSpec(
        vocab_size=config['vocab_size'],
        n_layer=config['num_hidden_layers'],
        n_head=config['num_attention_heads'],
        n_kv_head=config['num_key_value_heads'],
        d_key=config['head_dim'], d_value=config['head_dim'],
        d_model=config['hidden_size'],
        d_inner=config['moe_intermediate_size'], block='delta_hybrid',
        layer_types=layer_types(config),
        rotary_dim=int(config['head_dim'] * config['partial_rotary_factor']),
        rope_theta=config['rope_theta'],
        ssm_heads=config['linear_num_value_heads'],
        ssm_head_dim=config['linear_value_head_dim'],
        ssm_groups=config['linear_num_key_heads'],
        ssm_state=config['linear_key_head_dim'],
        ssm_conv=config['linear_conv_kernel_dim'], ssm_chunk=SCAN_CHUNK,
        n_experts=config['published']['num_experts'],
        experts_held=config['num_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['num_experts_per_tok'],
        n_shared_experts=1,
        d_inner_shared=config['shared_expert_intermediate_size'],
        norm_eps=config['rms_norm_eps'], dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed)
    # benchmark/sweep.py reads the vocabulary from here
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


def _decays(key, shape):
    """(dt_bias, A_log) of ``shape`` [layers, heads], float32: ones, and
    the logarithm of ``A`` uniform over ``A_RANGE``."""
    import jax
    import jax.numpy as jnp
    return (jnp.ones(shape, jnp.float32),
            jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE)))


def draw_weights(engine, seed):
    """Every matrix drawn again on the device from the seed (module
    docstring), one parameter at a time, a layer at a time inside it
    (``serve_block._drawn``)."""
    import jax
    from paddle_tpu.serving.decode.model import block_param_shapes
    draw = jax.jit(_block._drawn, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    shapes = block_param_shapes(engine.spec)
    for i, (name, (shape, fan_in, _)) in enumerate(shapes.items()):
        if not fan_in:          # a gain, or a vector drawn below
            continue
        if math.prod(shape) * 2 >= DROP_FIRST_BYTES:
            engine.device_weights()[name].delete()
        engine.load_weights({name: draw(
            jax.random.fold_in(key, i), tuple(shape), engine.spec.dtype,
            fan_in ** -0.5)})
    if 'lm_gdn_dt.b' in shapes:
        dt_bias, a_log = jax.jit(_decays, static_argnums=1)(
            jax.random.fold_in(key, len(shapes)),
            tuple(shapes['lm_gdn_dt.b'][0]))
        engine.load_weights({'lm_gdn_dt.b': dt_bias,
                             'lm_gdn_a_log': a_log})


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
