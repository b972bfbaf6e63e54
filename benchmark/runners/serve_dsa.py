"""Serving cells of the latent_moe block under a carried selection
(glm_5_2: ``model_type`` glm_moe_dsa): a DecodeEngine over
``LMSpec(block='latent_moe', indexer_types=...)`` under open-loop
traffic.

``runners/serve_latent.py::spec_of`` is written for dots3_note's
config.json (two attention shapes by ``layer_types``, a headwise gate,
the latents' rescale, every full layer scoring). This runner reads
another published config.json's keys (``indexer_types``,
``mlp_layer_types``, ``indexer_rope_interleave``, ``rope_parameters``,
one attention shape, no gate and no rescale) and a cut that starts at a
layer of its own (``first_layer``), and is otherwise that file, loaded
as it loads ``serve_block.py``: the window, the pre-roll, the held
sample, the one-at-a-time check, the limits and what ``correct`` means
(``serve``, ``held_sample``, ``within_limits``, ``against_reference``),
the reader of a stream (``poll``) and the draw of the weights
(``draw_weights``: every matrix N(0, 1 / fan-in) and every bias
N(0, 0.05^2) from the program's own table, whatever the block) are its
own, used as they are. In a traced run the tail's prefill chunks are
handed to ``readers/prefill_ops_mxu.py`` as ``runners/serve_sessions.py``
hands them (``hand_over_prefill_chunks``, used as it is).
"""

import os

from benchmark import manifest

_HERE = os.path.dirname(os.path.abspath(__file__))
_latent = manifest.load_module(os.path.join(_HERE, 'serve_latent.py'))
_sessions = manifest.load_module(os.path.join(_HERE, 'serve_sessions.py'))
poll = _latent.poll
serve = _latent.serve
held_sample = _latent.held_sample
within_limits = _latent.within_limits
against_reference = _latent.against_reference
draw_weights = _latent.draw_weights

FULL = 'full_attention'


def spec_of(config):
    """The LMSpec of a GLM-5.2 config.json, cut as the file says:
    ``num_hidden_layers`` layers from ``first_layer`` on, read off the
    published per-layer lists; ``n_routed_experts`` is what is held here
    of ``published.n_routed_experts``."""
    from paddle_tpu.serving.decode import LMSpec
    if config['model_type'] != 'glm_moe_dsa' or config['attention_bias'] \
            or config['hidden_act'] != 'silu' or \
            config['scoring_func'] != 'sigmoid' or \
            config['topk_method'] != 'noaux_tc' or \
            not config['norm_topk_prob'] or config['moe_layer_freq'] != 1 \
            or config['n_group'] != 1 or config['topk_group'] != 1 or \
            config['rope_parameters']['rope_type'] != 'default' or \
            not config['rope_interleave'] or \
            config['index_topk_pattern'] is not None or \
            config['tie_word_embeddings'] or \
            config['num_key_value_heads'] != config['num_attention_heads'] \
            or config['qk_head_dim'] != config['qk_nope_head_dim'] \
            + config['qk_rope_head_dim']:
        raise ValueError('serve_dsa: the configuration is not the block '
                         'this runner builds')
    first, depth = config['first_layer'], config['num_hidden_layers']
    run = slice(first, first + depth)
    indexer, mlp = config['indexer_types'][run], \
        config['mlp_layer_types'][run]
    dense = mlp.count('dense')
    published = config['published']['num_hidden_layers']
    if len(config['indexer_types']) != published or \
            len(config['mlp_layer_types']) != published or \
            len(indexer) != depth or mlp != ['dense'] * dense \
            + ['sparse'] * (depth - dense) or \
            config['mlp_layer_types'].count('dense') != \
            config['first_k_dense_replace']:
        raise ValueError('serve_dsa: layers %d..%d of the published lists '
                         'are not leading dense layers and then sparse '
                         'ones' % (first, first + depth - 1))
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth,
        d_model=config['hidden_size'],
        d_inner=config['moe_intermediate_size'], block='latent_moe',
        layer_types=[FULL] * depth,
        latent={FULL: dict(n_head=config['num_attention_heads'],
                           q_rank=config['q_lora_rank'],
                           kv_rank=config['kv_lora_rank'],
                           d_nope=config['qk_nope_head_dim'],
                           d_rope=config['qk_rope_head_dim'],
                           d_v=config['v_head_dim'],
                           rope_theta=config['rope_parameters'][
                               'rope_theta'])},
        dense_layers=dense, d_inner_dense=config['intermediate_size'],
        index_n_heads=config['index_n_heads'],
        index_head_dim=config['index_head_dim'],
        index_topk=config['index_topk'], indexer_types=indexer,
        index_rope_interleave=config['indexer_rope_interleave'],
        n_experts=config['published']['n_routed_experts'],
        experts_held=config['n_routed_experts'],
        first_expert=config['first_expert'],
        experts_per_token=config['num_experts_per_tok'],
        n_shared_experts=config['n_shared_experts'],
        routed_scale=config['routed_scaling_factor'],
        norm_eps=config['rms_norm_eps'], lora_rescale=False,
        attn_gate=False, dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed)
    # benchmark/sweep.py reads the vocabulary from here
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


class _Watched(object):
    """The engine as ``serve`` is handed it: everything is the engine's;
    ``drain()``, which ``serve`` calls once after the window, then hands
    the traced tail's prefill chunks to the readers."""

    def __init__(self, engine, ctx):
        self._engine, self._ctx = engine, ctx

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def drain(self, timeout=None):
        idle = self._engine.drain(timeout=timeout)
        _sessions.hand_over_prefill_chunks(self._ctx)
        return idle


def run(ctx):
    traffic = ctx.sized(ctx.traffic)
    engine, config = build_engine(ctx)
    try:
        signatures = engine.warmup()
        engine.start()
        return serve(ctx, _Watched(engine, ctx), traffic, config,
                     signatures)
    finally:
        engine.shutdown(drain=False)
