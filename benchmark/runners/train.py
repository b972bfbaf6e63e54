"""Training cells: the config's model through ``Executor.run``, one step
a call, with a bounded number of dispatches in flight.

A widened copy of ``chip_smoke.run_train`` (PR 21). It never builds the
``run_steps`` window: one compile per cell, and the executor's own
dispatch stays in the measurement, which a scanned window would hide.

The traffic file gives the batch, the padded length, how many seeded
host batches are cycled and how many dispatches may be in flight.
Weights are made on the device from --seed in one jitted call (the
program's own random_seed is part of its HLO, so it stays fixed and the
compile cache holds across seeds); the batches are drawn from --seed on
the host.

During set-up the seeded weights are held to the config's plain
reference (``references/<config>.py``): the program's inference clone
and the reference compute the logits and the loss of the first rows of
batch 0, and the logits may differ by the config's ``logits_rel_rms_tol``
(root mean square of the difference over the deviation of the
reference's logits). The training step's own loss cannot be compared:
its dropout masks are the program's.
"""

import collections
import importlib
import time

import numpy as np

from benchmark import weights


def build(ctx, model, traffic):
    """(loss var, logits var, program, executor) with the startup
    program run."""
    import paddle_tpu as fluid
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    module, function = ctx.config['builder']['function'].split(':')
    builder = getattr(importlib.import_module(module), function)
    seq = traffic['seq_len']
    sizes = {k: v for k, v in model.items() if k != 'vocab_size'}
    avg_cost, logits = builder(
        src_vocab_size=model['vocab_size'], trg_vocab_size=model['vocab_size'],
        src_seq_len=seq, trg_seq_len=seq, max_length=max(256, seq),
        **dict(sizes, **ctx.config['builder'].get('kwargs', {})))
    opt = ctx.config['optimizer']
    getattr(fluid.optimizer, opt['type'])(
        learning_rate=opt['learning_rate']).minimize(avg_cost)
    prog = fluid.default_main_program()
    prog.amp = ctx.config['amp']
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    return avg_cost, logits, prog, exe


def against_reference(ctx, model, prog, exe, avg_cost, logits, batch):
    """The program's inference clone against the plain reference on the
    first rows of ``batch``, with the weights as they stand in the
    scope. ``Program.clone(for_test=True)`` carries neither ``amp`` nor
    the inference flag of ``fused_attention`` (whose output dropout
    would stay on), so both are set here."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    spec = ctx.sized(ctx.config)['reference']
    rows = {k: v[:spec['rows']] for k, v in batch.items()}
    clone = prog.clone(for_test=True)
    clone.amp = prog.amp
    for op in clone.global_block().ops:
        if op.type == 'fused_attention':
            op.attrs['is_test'] = True
    got_loss, got = exe.run(clone, feed=rows, fetch_list=[avg_cost, logits],
                            return_numpy=False)
    scope = fluid.global_scope()
    weights = {p.name: scope.find(p.name)
               for p in prog.global_block().all_parameters()}
    want, want_loss = jax.jit(ctx.reference.forward,
                              static_argnums=(2, 3, 4, 5))(
        weights, rows, model['n_layer'], model['n_head'],
        model['dropout_rate'], model['label_smooth_eps'])
    got = jnp.asarray(got, jnp.float32).reshape(want.shape)
    rel_rms = float(jnp.sqrt(jnp.mean(jnp.square(got - want)))
                    / jnp.std(want))
    got_loss = float(jnp.asarray(got_loss, jnp.float32).reshape(-1)[0])
    return {'reference_logits_rel_rms': rel_rms,
            'reference_logit_std': float(jnp.std(want)),
            'reference_loss': float(want_loss),
            'inference_loss': got_loss,
            'reference_agrees': bool(
                rel_rms <= spec['logits_rel_rms_tol']
                and abs(got_loss - float(want_loss))
                <= spec['loss_abs_tol'])}


def reseed_weights(prog, seed):
    """Every trainable matrix drawn again on the device from the seed
    (weights.redraw); vectors (biases, layer-norm gains) keep their
    constants."""
    import jax
    import paddle_tpu as fluid
    scope = fluid.global_scope()
    old = {p.name: scope.find(p.name)
           for p in prog.global_block().all_parameters()
           if p.trainable and len(p.shape) >= 2}
    new = jax.jit(weights.redraw, donate_argnums=(0,))(
        old, weights.seed_key(seed))
    for n, v in new.items():
        scope.set(n, v)


def host_batches(traffic, vocab, seed):
    """The pool of host batches, a pure function of the seed: random
    token ids at the padded length, every position weighted."""
    rng = np.random.RandomState(seed % (1 << 32))
    batch, seq = traffic['batch'], traffic['seq_len']
    pool = []
    for _ in range(traffic['host_batches']):
        pool.append({
            'src_word': rng.randint(1, vocab, (batch, seq)).astype('int64'),
            'src_length': np.full((batch,), seq, dtype='int64'),
            'trg_word': rng.randint(1, vocab, (batch, seq)).astype('int64'),
            'lbl_word': rng.randint(1, vocab, (batch, seq)).astype('int64'),
            'lbl_weight': np.ones((batch, seq), dtype='float32'),
        })
    return pool


def run(ctx):
    import jax
    traffic = ctx.sized(ctx.traffic)
    model = ctx.sized(ctx.config)['model']
    avg_cost, logits, prog, exe = build(ctx, model, traffic)
    reseed_weights(prog, ctx.seed)
    pool = host_batches(traffic, model['vocab_size'], ctx.seed)
    tokens_per_step = traffic['batch'] * traffic['seq_len']
    reference = against_reference(ctx, model, prog, exe, avg_cost, logits,
                                  pool[0])

    def dispatch(i):
        with ctx.span('bench.dispatch'):
            return exe.run(feed=pool[i % len(pool)], fetch_list=[avg_cost],
                           return_numpy=False)[0]

    losses = []
    for i in range(traffic['warm_steps']):      # the first one compiles
        losses.append(jax.block_until_ready(dispatch(i)))

    in_flight = collections.deque()
    done_at = []
    step = traffic['warm_steps']
    ctx.begin_window()
    while ctx.window_left() > 0:
        ctx.tick()
        in_flight.append(dispatch(step))
        step += 1
        if len(in_flight) >= traffic['max_in_flight']:
            with ctx.span('bench.wait_oldest'):
                losses.append(jax.block_until_ready(in_flight.popleft()))
            done_at.append(time.perf_counter())
    while in_flight:
        losses.append(jax.block_until_ready(in_flight.popleft()))
        done_at.append(time.perf_counter())
    elapsed = done_at[-1] - ctx.t_window
    ctx.end_window()

    if ctx.t_trace is not None:
        ctx.sources['trace_steps'] = sum(1 for t in done_at
                                         if t > ctx.t_trace)
    values = np.asarray([np.asarray(x, 'float32').reshape(-1)[0]
                         for x in losses])
    finite = bool(np.isfinite(values).all())
    k = min(8, len(values) // 2)
    fell = bool(values[-k:].mean() < values[:k].mean())
    steps = len(done_at)
    return {
        'correct': finite and fell and reference['reference_agrees'],
        'attempted': steps,
        'failed': 0 if finite else int((~np.isfinite(values)).sum()),
        'end_to_end': {'train_tokens_per_s':
                       steps * tokens_per_step / elapsed},
        'notes': dict(reference, steps=steps,
                      tokens_per_step=tokens_per_step, window_s=elapsed,
                      first_losses=values[:k].tolist(),
                      last_losses=values[-k:].tolist(),
                      step_ms=1000.0 * elapsed / steps),
    }
