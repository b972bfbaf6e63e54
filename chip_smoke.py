#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at Transformer-base width on one TPU:

  train      models.transformer.transformer_base at the BASELINE shape
             (vocab 32,000, 6+6 layers, d_model 512, batch 64 x seq 64,
             Adam, bf16 amp) through Executor(TPUPlace(0)): startup,
             per-step exe.run, one run_steps window
  serve      DecodeEngine at the same width: warmup(), the compiled
             decode step and top prefill bucket read for arena-sized
             copies (none allowed), start(), concurrent submit()s, then
             the same prompts one at a time
  kernels    every Pallas kernel in the tree, compiled for the chip
             (interpret=False) and compared with its jnp reference; the
             blocked paged attention against its dense oracle
  four_chip  the train leg over make_mesh(dp=2, tp=2) with ZeRO-1, when
             jax sees four devices; otherwise skipped and said so

One process, no subprocesses. The first failing check raises, so the
exit code is non-zero and no result line is printed. Without a TPU the
script exits 2 before building any model. The printed times are
information for the reader, not metrics. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--rehearsal` asks for the host CPU by name, shrinks every size, runs
the kernels interpreted and prints `REHEARSAL platform=cpu`: it exists
so the command can be debugged without a chip and is never entered
automatically.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# 'model' is the width and depth of both legs: transformer_base's
# overrides, and (with the vocabulary) the decode engine's LMSpec
FULL = {
    'model': dict(n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
                  d_inner=2048),
    'vocab': 32000, 'batch': 64, 'seq': 64, 'steps': 5, 'window': 10,
    # a pool whose layer (2,048 x 32 x 512) is larger than the largest
    # weight (32,000 x 512): the compiler prefetches a weight into fast
    # memory with a copy, and that is not a copy of an arena
    'engine': dict(max_batch=16, block_size=32, num_blocks=2048,
                   pages_per_seq=16, max_prompt_len=64),
    'requests': 8, 'max_new': 32,
    'flash': [(64, 8, 64, 64), (2, 8, 1024, 64)],   # [B, H, T, D]
    'ln': (4096, 512), 'bn': (12544, 256),
}
REHEARSAL = {
    'model': dict(n_layer=1, n_head=2, d_key=8, d_value=8, d_model=16,
                  d_inner=32),
    'vocab': 64, 'batch': 8, 'seq': 8, 'steps': 5, 'window': 3,
    # a pool larger than an iteration's pages (8 pairs x 4 pages), so
    # that what it gathers is not of a layer's arena size
    'engine': dict(max_batch=4, block_size=8, num_blocks=64,
                   pages_per_seq=4, max_prompt_len=8),
    'requests': 8, 'max_new': 6,
    'flash': [(2, 2, 16, 8)],
    'ln': (16, 128), 'bn': (32, 128),
}


class CacheCounter(object):
    """jax's own persistent-compilation-cache hit/miss events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.misses += 1

    def take(self):
        out = {'cache_hits': self.hits, 'cache_misses': self.misses}
        self.hits = self.misses = 0
        return out


def report(leg, cache, **fields):
    fields.update(cache.take())
    print('LEG %s %s' % (leg, json.dumps(fields, sort_keys=True)),
          flush=True)


def on_platform(arrays, platform):
    for a in arrays:
        found = {d.platform for d in a.devices()}
        assert found == {platform}, \
            'fetched array lives on %s, expected %s' % (found, platform)


# ---------------------------------------------------------------- train
def run_train(cfg, platform, mesh=None):
    """Build, start and train the transformer; returns the per-step
    losses, the run_steps window's losses and the wall times."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    fluid.reset_default_programs()
    fluid.global_scope().clear()
    vocab, batch, seq = cfg['vocab'], cfg['batch'], cfg['seq']
    avg_cost, _ = T.transformer_base(
        src_vocab_size=vocab, trg_vocab_size=vocab, src_seq_len=seq,
        trg_seq_len=seq, max_length=max(256, seq), **cfg['model'])
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    prog = fluid.default_main_program()
    prog.amp = 'bf16'
    feed = T.make_fake_batch(batch, seq, seq, vocab, vocab)
    if mesh is None:
        feed = {k: jax.device_put(v) for k, v in feed.items()}
    else:
        from jax.sharding import NamedSharding
        from __graft_entry__ import TRANSFORMER_TP_RULES
        from paddle_tpu.parallel.transpiler import (ParallelStrategy,
                                                    transpile)
        transpile(prog, mesh, ParallelStrategy(
            data_parallel=True, tensor_parallel=True,
            tp_rules=TRANSFORMER_TP_RULES, shard_optimizer_states=True))
        # the input pipeline's job: each batch lands already split the
        # way the program's data vars are
        feed = {k: jax.device_put(
            v, NamedSharding(mesh, prog.var_shardings[k]))
            for k, v in feed.items()}

    exe = fluid.Executor(fluid.TPUPlace(0))
    t0 = time.perf_counter()
    exe.run(fluid.default_startup_program())
    startup_s = time.perf_counter() - t0

    losses, step_s = [], []
    for _ in range(cfg['steps']):
        t0 = time.perf_counter()
        out = exe.run(feed=feed, fetch_list=[avg_cost],
                      return_numpy=False)
        jax.block_until_ready(out)
        step_s.append(time.perf_counter() - t0)
        on_platform(out, platform)
        losses.append(float(np.asarray(out[0], 'float32').reshape(-1)[0]))

    window_s = []
    for _ in range(2):                # first call compiles the window
        t0 = time.perf_counter()
        out = exe.run_steps(cfg['window'], feed=feed,
                            fetch_list=[avg_cost], return_numpy=False)
        jax.block_until_ready(out)
        window_s.append(time.perf_counter() - t0)
        on_platform(out, platform)
    window = np.asarray(out[0], 'float32').reshape(-1)

    assert np.isfinite(losses).all() and np.isfinite(window).all(), \
        'non-finite loss: steps %s window %s' % (losses, window)
    assert window.shape == (cfg['window'],)
    assert window[-1] < losses[0], \
        'loss did not fall on a fixed batch: %.4f -> %.4f' \
        % (losses[0], window[-1])
    return {
        'losses': losses, 'window': window, 'feed': feed,
        'times': {
            'startup_s': round(startup_s, 3),
            'step_compile_s': round(step_s[0] - step_s[-1], 3),
            'step_s': round(step_s[-1], 4),
            'window_compile_s': round(window_s[0] - window_s[1], 3),
            'window_step_s': round(window_s[1] / cfg['window'], 4),
        }}


def leg_train(cfg, platform, cache):
    r = run_train(cfg, platform)
    report('train', cache, first_loss=round(r['losses'][0], 4),
           last_loss=round(float(r['window'][-1]), 4), **r['times'])
    return r['losses'][0]


def leg_four_chip(cfg, platform, cache, one_chip_first_loss):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.parallel.mesh import make_mesh

    n = jax.device_count()
    if n < 4:
        print('four_chip: skipped, %d device(s)' % n, flush=True)
        return
    r = run_train(cfg, platform, mesh=make_mesh(dp=2, tp=2))
    d_model, d_inner = cfg['model']['d_model'], cfg['model']['d_inner']
    scope = fluid.global_scope()

    def spans_four(arr, shard_shape, what):
        got = arr.addressable_shards[0].data.shape
        assert len(arr.sharding.device_set) == 4 and got == shard_shape, \
            '%s: %d devices, shard %s, expected 4 and %s' % (
                what, len(arr.sharding.device_set), got, shard_shape)
        return list(got)

    shards = {
        # Megatron column split over tp, replicated over dp
        'enc_0_ffn_1.w': spans_four(
            scope.find('enc_0_ffn_1.w'), (d_model, d_inner // 2),
            'tp-split weight'),
        # ZeRO-1: its Adam moment also takes dp on the free axis
        'enc_0_ffn_1.w_moment1_acc': spans_four(
            scope.find('enc_0_ffn_1.w_moment1_acc'),
            (d_model // 2, d_inner // 2), 'ZeRO-1 moment'),
        'src_word': spans_four(
            r['feed']['src_word'], (cfg['batch'] // 2, cfg['seq']),
            'dp-split batch'),
    }
    # same seed, same batch; bf16 matmuls reduce in another order over
    # tp and the dropout bits differ per shard, so not bit-equal
    np.testing.assert_allclose(r['losses'][0], one_chip_first_loss,
                               rtol=2e-2)
    report('four_chip', cache, mesh='dp=2,tp=2', shards=shards,
           first_loss=round(r['losses'][0], 4),
           one_chip_first_loss=round(one_chip_first_loss, 4),
           last_loss=round(float(r['window'][-1]), 4), **r['times'])


# ---------------------------------------------------------------- serve
def leg_serve(cfg, cache):
    from paddle_tpu import observe
    from paddle_tpu.serving.decode import DecodeEngine, LMSpec
    from paddle_tpu.serving.decode.hlo_check import (
        arena_sized_instructions)
    vocab = cfg['vocab']

    def misses():
        return sum(v for k, v in observe.snapshot()['counters'].items()
                   if k.startswith('executor.cache_miss_total'))

    rng = np.random.RandomState(0)
    top = cfg['engine']['max_prompt_len']
    prompts = [rng.randint(0, vocab,
                           int(rng.randint(1, top + 1))).tolist()
               for _ in range(cfg['requests'])]
    max_new = cfg['max_new']

    engine = DecodeEngine(LMSpec(vocab_size=vocab, **cfg['model']),
                          **cfg['engine'])
    try:
        t0 = time.perf_counter()
        signatures = engine.warmup()
        warmup_s = time.perf_counter() - t0
        # the arenas are written in place: in the programs the compiler
        # hands back, nothing of a layer's arena size is copied or
        # re-laid outside the attention gather (a compile-cache hit
        # after warmup; the reader needs no trace)
        layer_elements = (cfg['engine']['num_blocks'] *
                          cfg['engine']['block_size'] *
                          cfg['model']['n_head'] * cfg['model']['d_key'])
        arena_copies = {}
        for which in ('decode', engine.prompt_buckets[-1]):
            found = arena_sized_instructions(
                engine.trace_program(which).lower().compile().as_text(),
                layer_elements)
            arena_copies[str(which)] = len(found)
            assert not found, \
                '%s copies or re-lays an arena: %s' % (
                    which, [(i.name, i.shape) for i in found[:8]])
        engine.start()
        warm_misses = misses()

        # submit() returns at once, so all requests are in flight
        # together; result() re-raises whatever failed a request
        t0 = time.perf_counter()
        streams = [engine.submit(p, max_new_tokens=max_new)
                   for p in prompts]
        got = [s.result(600) for s in streams]
        concurrent_s = time.perf_counter() - t0
        streamed = [list(s) for s in streams]

        t0 = time.perf_counter()
        alone = [engine.generate(p, max_new_tokens=max_new, timeout=600)
                 for p in prompts]
        alone_s = time.perf_counter() - t0
        live_misses = misses() - warm_misses
    finally:
        engine.shutdown(drain=False)

    for i, toks in enumerate(got):
        assert len(toks) == max_new and streamed[i] == toks, \
            'request %d: %d tokens, %d streamed, expected %d' % (
                i, len(toks), len(streamed[i]), max_new)
        assert all(0 <= t < vocab for t in toks)
    assert live_misses == 0, \
        '%d executor cache misses after warmup' % live_misses
    assert got == alone, \
        'greedy tokens differ between concurrent and one-at-a-time ' \
        'serving: requests %s' % [i for i in range(len(got))
                                  if got[i] != alone[i]]
    report('serve', cache, signatures=signatures,
           warmup_s=round(warmup_s, 3), requests=len(prompts),
           prompt_lens=[len(p) for p in prompts], new_tokens=max_new,
           concurrent_s=round(concurrent_s, 3),
           one_at_a_time_request_s=round(alone_s / len(prompts), 4),
           misses_after_warmup=live_misses,
           arena_sized_copies=arena_copies)


# -------------------------------------------------------------- kernels
def timed(fn, *args):
    """(result, first-call seconds): trace + compile + one run."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, round(time.perf_counter() - t0, 3)


def check_flash(shape, masked):
    """Forward and both backward kernels against the jnp reference, at
    the dtype the bf16 train leg hands them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import (_reference,
                                                       flash_attention)
    b, h, t, d = shape
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(*shape) * 0.5, jnp.bfloat16)
                  for _ in range(4))
    # the decoder's self-attention is causal and unpadded; the encoder's
    # is bidirectional with per-example key lengths
    causal = not masked
    lens = jnp.asarray(rng.randint(t // 2, t + 1, (b,)), jnp.int32) \
        if masked else None

    def fwd_bwd(attn):
        def f(q, k, v):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(g.astype(out.dtype))
        return jax.jit(f)

    got, secs = timed(fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, kv_len=lens)), q, k, v)
    want = fwd_bwd(lambda q, k, v: _reference(
        q, k, v, causal, d ** -0.5, lens))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, w, tol in zip(('out', 'dq', 'dk', 'dv'), got, want,
                               (3e-2, 6e-2, 6e-2, 6e-2)):
        np.testing.assert_allclose(
            np.asarray(a, 'float32'), np.asarray(w, 'float32'),
            atol=tol, rtol=tol, err_msg=name)
    return secs


def check_layer_norm(shape):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.layer_norm import (_ln_pallas,
                                                  _ln_reference)
    n, d = shape
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    gamma = jnp.asarray(rng.rand(d) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(d), jnp.float32)
    got, secs = timed(jax.jit(lambda *a: _ln_pallas(*a, 1e-5)),
                      x, gamma, beta)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ln_reference(x, gamma, beta, 1e-5)),
        atol=1e-4, rtol=1e-4)
    return secs


def check_batch_norm(shape):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.batch_norm import (_bn_reference,
                                                  _fused_bn_fwd)
    r, c = shape
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(r, c), jnp.bfloat16)
    scale = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(c), jnp.float32)
    got, secs = timed(jax.jit(lambda *a: _fused_bn_fwd(*a, 1e-5, 512)),
                      x, scale, bias)
    want = _bn_reference(x, scale, bias, 1e-5)
    for name, a, w, tol in zip(('y', 'mean', 'var'), got, want,
                               (5e-2, 1e-3, 1e-3)):
        np.testing.assert_allclose(
            np.asarray(a, dtype='float32'), np.asarray(w, dtype='float32'),
            atol=tol, rtol=tol, err_msg=name)
    return secs


def check_paged(model, eng):
    """The decode step's attention at the serve leg's arena geometry."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_blocked, paged_attention_reference)
    b, p = eng['max_batch'], eng['pages_per_seq']
    nb, bs = eng['num_blocks'], eng['block_size']
    h, d, dv = model['n_head'], model['d_key'], model['d_value']
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    kp = jnp.asarray(rng.randn(1, nb, bs, h * d), jnp.float32)
    vp = jnp.asarray(rng.randn(1, nb, bs, h * dv), jnp.float32)
    # every row owns distinct pages; lengths from 1 token to capacity
    tables = jnp.asarray(rng.permutation(nb)[:b * p].reshape(b, p),
                         jnp.int32)
    lens = jnp.asarray(np.linspace(1, p * bs, b).astype('int32'))
    got, secs = timed(jax.jit(paged_attention_blocked),
                      q, kp, vp, tables, lens)
    want = paged_attention_reference(q, kp, vp, tables, lens)
    # the running softmax over blocks and the dense one sum in different
    # orders
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2, rtol=1e-2)
    return secs


def leg_kernels(cfg, cache, rehearsal):
    from paddle_tpu.ops.pallas import interpret_mode
    assert interpret_mode() == rehearsal, \
        'PADDLE_TPU_PALLAS_INTERPRET must be set only by --rehearsal'
    compile_s = {}
    for shape in cfg['flash']:
        tag = 'x'.join(map(str, shape))
        compile_s['flash_causal_fwd_bwd_' + tag] = check_flash(shape,
                                                               False)
        compile_s['flash_masked_fwd_bwd_' + tag] = check_flash(shape,
                                                               True)
    compile_s['layer_norm'] = check_layer_norm(cfg['ln'])
    compile_s['batch_norm'] = check_batch_norm(cfg['bn'])
    compile_s['paged_attention'] = check_paged(cfg['model'],
                                                   cfg['engine'])
    report('kernels', cache, interpret=rehearsal,
           first_call_s=compile_s)


# ----------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--rehearsal', action='store_true',
                    help='tiny sizes on the host CPU, kernels interpreted')
    args = ap.parse_args(argv)

    # importing the package does not start jax's backend
    from paddle_tpu import observe
    from paddle_tpu.core.platform_boot import (arm_compile_cache,
                                               force_host_cpu)
    # the XLA cost probe would compile every step a second time
    os.environ.setdefault('PADDLE_TPU_OBSERVE_COST', '0')
    if args.rehearsal:
        force_host_cpu(8)
        os.environ['PADDLE_TPU_PALLAS_INTERPRET'] = '1'

    import jax
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': jax.device_count()}
    if args.rehearsal:
        print('REHEARSAL platform=%s' % dev.platform, flush=True)
    elif dev.platform != 'tpu':
        sys.stderr.write(
            'chip_smoke: needs a TPU, jax found platform %r (%s x%d). '
            'Nothing was run.\n' % (dev.platform, dev.device_kind,
                                    device['count']))
        return 2
    print('DEVICE %s' % json.dumps(device, sort_keys=True), flush=True)

    arm_compile_cache()
    print('COMPILE_CACHE %s' % jax.config.jax_compilation_cache_dir,
          flush=True)
    observe.enable()
    cache = CacheCounter()
    cfg = REHEARSAL if args.rehearsal else FULL

    t0 = time.perf_counter()
    first_loss = leg_train(cfg, dev.platform, cache)
    leg_serve(cfg, cache)
    leg_kernels(cfg, cache, args.rehearsal)
    leg_four_chip(cfg, dev.platform, cache, first_loss)
    print('TOTAL %.1f s' % (time.perf_counter() - t0), flush=True)

    result = {'ok': True, 'device': device}
    if args.rehearsal:
        result['rehearsal'] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
