"""Serving cells of the ssm_hybrid block family (granite_4_0_h_micro): a
DecodeEngine over ``LMSpec(block='ssm_hybrid')`` under open-loop traffic.

``runners/serve_block.py`` builds one block (``cohere2_moe``) and draws
its weights from ``moe_param_shapes``. This runner reads another
published config.json's keys and another parameter list, and is
otherwise that file, loaded as ``serve_gqa_moe.py`` loads it: the window,
the pre-roll, the held sample, the one-at-a-time check, the limits and
what ``correct`` means (``serve``, ``held_sample``, ``within_limits``,
``against_reference``), the reader of a stream (``poll``) and the drawing
of one matrix on the device (``_drawn``) are its own, used as they are.

Four things are added.

- **The matrices that write to the residual stream** (the MLP's down
  projection, the Mamba-2 output projection, the attention's ``o``) are
  drawn N(0, 1 / (fan-in x residual_multiplier^2)): the block adds
  ``residual_multiplier`` (0.22) times a sublayer's output, a factor the
  published weights were trained under, and drawn by the fan-in alone
  the 80 sublayers add a residual of norm 55 to an embedding row of
  norm 12 (``embedding_multiplier`` times a unit row). The tied head
  then reads the input token's own row back ten deviations above every
  other logit, each served token repeats the one before it whatever the
  mixers compute, and no control of the precision can fail (my chip
  run, PR 45: 1,254 held tokens, none not the reference's first choice,
  largest gap 0.0). With the factor undone a sublayer adds what it
  would add to a plain residual and the own row stands under two
  deviations.
- **The time-step bias and the decay.** A matrix is drawn N(0, 1 /
  fan-in) like every other configuration's; the two vectors that set how
  fast a Mamba-2 head forgets are drawn as the published implementation
  initialises them (``draw_weights``), since zeros would make every head
  forget in two or three tokens and the state, which is what this
  configuration is here for, would carry nothing.
- **The state pool's used share.** ``serve`` samples the pages used of
  the first pool. The engine is handed to it behind ``_Watched``, which
  at each of ``serve``'s own samples also notes the used share of the
  pool of state slots under ``state_slots_used_pct`` (the
  ``sampled_gauge`` reader of ``serve.ssm_state_slots_used_pct``).
- **The prefill chunks of the traced tail**, for
  ``readers/prefill_ops_mxu.py``: the chunks as the worker dispatched
  them (each with the (row, layer) steps of the recurrence its scan
  takes, the ``scan_rows`` of its span, under the reader's key
  ``pairs``) and the program runs as the chip ran them, handed over
  where ``serve`` finds the engine idle after the window
  (``runners/serve_sessions.py`` does the same inside its own
  ``serve``).
"""

import os

from benchmark import manifest, tracelib, weights

_HERE = os.path.dirname(os.path.abspath(__file__))
_block = manifest.load_module(os.path.join(_HERE, 'serve_block.py'))
poll = _block.poll
serve = _block.serve
held_sample = _block.held_sample
within_limits = _block.within_limits
against_reference = _block.against_reference

# the matrices whose product is added to the residual stream
TO_RESIDUAL = ('lm_stack_mlp_down.w', 'lm_mamba_out.w', 'lm_attn_o.w')
# the published initialisation of a Mamba-2 head's time step and decay
# (mamba_ssm's Mamba2: dt log-uniform in [0.001, 0.1] through the inverse
# of softplus, A uniform in [1, 16])
DT_RANGE = (0.001, 0.1)
A_RANGE = (1.0, 16.0)


def spec_of(config):
    """The LMSpec of a granitemoehybrid config.json without experts."""
    from paddle_tpu.serving.decode import LMSpec
    heads, width = config['mamba_n_heads'], config['mamba_d_head']
    if config['model_type'] != 'granitemoehybrid' or \
            config['attention_bias'] or config['mamba_proj_bias'] or \
            not config['mamba_conv_bias'] or config['hidden_act'] != 'silu' \
            or config['normalization_function'] != 'rmsnorm' or \
            config['position_embedding_type'] != 'nope' or \
            config['num_local_experts'] or config['num_experts_per_tok'] \
            or not config['tie_word_embeddings'] or \
            config['mamba_n_groups'] != 1 or \
            heads * width != config['mamba_expand'] * config['hidden_size'] \
            or config['num_hidden_layers'] > len(config['layer_types']):
        raise ValueError('serve_ssm: the configuration is not the block '
                         'this runner builds')
    depth = config['num_hidden_layers']
    n_head = config['num_attention_heads']
    return LMSpec(
        vocab_size=config['vocab_size'], n_layer=depth, n_head=n_head,
        n_kv_head=config['num_key_value_heads'],
        d_key=config['hidden_size'] // n_head,
        d_value=config['hidden_size'] // n_head,
        d_model=config['hidden_size'],
        d_inner=config['shared_intermediate_size'], block='ssm_hybrid',
        layer_types=config['layer_types'][:depth], ssm_heads=heads,
        ssm_head_dim=width, ssm_state=config['mamba_d_state'],
        ssm_conv=config['mamba_d_conv'],
        ssm_chunk=config['mamba_chunk_size'],
        embed_scale=config['embedding_multiplier'],
        residual_scale=config['residual_multiplier'],
        attn_scale=config['attention_multiplier'],
        logit_scale=1.0 / config['logits_scaling'],
        norm_eps=config['rms_norm_eps'], dtype=config['dtype'])


def build_engine(ctx):
    from paddle_tpu.serving.decode import DecodeEngine
    config = ctx.sized(ctx.config)
    spec = spec_of(config)
    engine = DecodeEngine(spec, **config['engine'])
    draw_weights(engine, ctx.seed)
    # benchmark/sweep.py reads the vocabulary from here
    return engine, dict(config, model={'vocab_size': spec.vocab_size})


def _time_constants(key, shape):
    """(dt_bias, A_log) of ``shape`` [layers, heads], float32: ``dt``
    log-uniform over ``DT_RANGE`` and the bias its inverse under
    softplus, ``dt + log(-expm1(-dt))``; ``A`` uniform over ``A_RANGE``
    and ``A_log`` its logarithm."""
    import jax
    import jax.numpy as jnp
    k_dt, k_a = jax.random.split(key)
    lo, hi = (jnp.log(v) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(k_dt, shape, jnp.float32, lo, hi))
    return (dt + jnp.log(-jnp.expm1(-dt)),
            jnp.log(jax.random.uniform(k_a, shape, jnp.float32, *A_RANGE)))


def draw_weights(engine, seed):
    """Every matrix drawn again on the device from the seed, N(0, 1 /
    fan-in) as the engine's own initializer draws it, one parameter at a
    time (``serve_block._drawn``), the three that write to the residual
    stream with the residual multiplier undone (module docstring); the
    gains stay ones and the convolution's bias zero; the time-step bias
    and ``A_log`` as the published implementation initialises them
    (``_time_constants``)."""
    import jax
    from paddle_tpu.serving.decode.model import block_param_shapes
    draw = jax.jit(_block._drawn, static_argnums=(1, 2, 3))
    key = weights.seed_key(seed)
    shapes = block_param_shapes(engine.spec)
    for i, (name, (shape, fan_in, _)) in enumerate(shapes.items()):
        if fan_in:                  # a matrix, kept at the spec's dtype
            std = fan_in ** -0.5
            if name in TO_RESIDUAL:
                std /= engine.spec.residual_scale
            engine.load_weights({name: draw(
                jax.random.fold_in(key, i), tuple(shape),
                engine.spec.dtype, std)})
    if 'lm_mamba_dt.b' in shapes:
        dt_bias, a_log = jax.jit(_time_constants, static_argnums=1)(
            jax.random.fold_in(key, len(shapes)),
            tuple(shapes['lm_mamba_dt.b'][0]))
        engine.load_weights({'lm_mamba_dt.b': dt_bias,
                             'lm_mamba_a_log': a_log})


def chunks_dispatched(t_trace):
    """Every prefill chunk the worker dispatched, in order, as
    ``readers/prefill_ops_mxu.py`` takes them
    (``serve_sessions.chunks_dispatched``), with ``pairs`` the chunk's
    ``scan_rows`` (None on a program whose spans carry none)."""
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
                        pairs=args.get('scan_rows')))
    return out


class _Watched(object):
    """The engine as ``serve`` is handed it: everything is the engine's;
    ``free_pages()``, which ``serve`` calls once a sample inside the
    window, also notes the state pool's used share, and ``drain()``,
    which it calls once after the window, then hands the traced tail's
    prefill chunks to the readers."""

    def __init__(self, engine, ctx):
        self._engine, self._ctx = engine, ctx

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def free_pages(self):
        for pool in self._engine.pools:
            if pool.whole:
                self._ctx.samples.setdefault(
                    'state_slots_used_pct', []).append(
                        100.0 * pool.used_blocks() / pool.num_blocks)
        return self._engine.free_pages()

    def drain(self, timeout=None):
        idle = self._engine.drain(timeout=timeout)
        ctx = self._ctx
        if ctx.t_trace is not None and \
                'prefill_program_runs' not in ctx.sources:
            reader = manifest.load_module(os.path.join(
                os.path.dirname(_HERE), 'readers', 'prefill_ops_mxu.py'))
            path = tracelib.find_xplane(getattr(ctx, '_trace_dir', ''))
            if path:
                ctx.sources['prefill_chunks'] = chunks_dispatched(
                    ctx.t_trace)
                ctx.sources['prefill_program_runs'] = \
                    reader.program_runs(path)
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
