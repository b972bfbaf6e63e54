"""Headline benchmark (SURVEY.md §5). Trains the two BASELINE workloads on
the chip and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": ...}

Baselines (BASELINE.json, reference-era P100 fp32 batch 64):
ResNet-50 ~200 img/s, Transformer base ~4500 tok/s. The headline metric is
the geometric-mean speedup over both; `value` is Transformer tok/s.

`python bench.py` runs each headline workload in its own child process
from a parent that never imports jax (a chip belongs to one process),
stamps platform / device_kind / device count into the JSON, and exits
non-zero when a workload fails or the platform is not `tpu`. There is no
other shape and no other backend to fall to. `--workload NAME` runs one
workload in this process on whatever platform jax was given.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

BASE_RESNET_IMG_S = 200.0
BASE_TRANSFORMER_TOK_S = 4500.0


def _fresh():
    import paddle_tpu as fluid
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    return fluid


def _time_steps(run_step, warmup=3, iters=20):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(run_step())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run_step()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _single_dispatch():
    # BENCH_SINGLE_DISPATCH=1 restores the one-dispatch-per-step loop
    # (the pre-round-3 measurement mode, kept as an ablation). Default
    # is Executor.run_steps: the training loop compiles INTO the XLA
    # program (lax.scan over steps), so per-dispatch overhead is paid
    # once per window — the intended TPU training loop, exactly
    # trajectory-equal to per-step dispatch (tests/test_executor.py).
    return os.environ.get('BENCH_SINGLE_DISPATCH') == '1'


def _time_multi(exe, feed, fetch, iters):
    """Per-step seconds using run_steps windows (one dispatch/window)."""
    import jax
    out = exe.run_steps(iters, feed=feed, fetch_list=fetch,
                        return_numpy=False)
    arr = np.asarray(out[0])  # compile + warmup window
    if not np.isfinite(arr).all():
        raise RuntimeError('non-finite loss in warmup window')
    t0 = time.perf_counter()
    out = exe.run_steps(iters, feed=feed, fetch_list=fetch,
                        return_numpy=False)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _to_device(feed):
    import jax
    return {k: jax.device_put(v) for k, v in feed.items()}


def bench_transformer(batch=64, seq=64, vocab=32000, iters=20,
                      dropout=None, big=False):
    """dropout=None keeps each builder's canonical rate (base 0.1,
    big 0.3) — an explicit value is an override, not a default, so
    big=True cannot silently bench a lighter model."""
    fluid = _fresh()
    from paddle_tpu.models import transformer as T
    builder = T.transformer_big if big else T.transformer_base
    overrides = {} if dropout is None else {'dropout_rate': dropout}
    avg_cost, _ = builder(
        src_vocab_size=vocab, trg_vocab_size=vocab,
        src_seq_len=seq, trg_seq_len=seq,
        max_length=max(256, seq), **overrides)
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    # Device-resident feed: real input pipelines prefetch to HBM
    # (reader.prefetch_to_device); the bench measures the train step.
    feed = _to_device(T.make_fake_batch(batch, seq, seq, vocab, vocab))

    if not _single_dispatch():
        return batch * seq / _time_multi(exe, feed, [avg_cost], iters)

    def step():
        return exe.run(feed=feed, fetch_list=[avg_cost], return_numpy=False)

    dt = _time_steps(step, iters=iters)
    return batch * seq / dt


def _transformer_train_flops(batch, src_len, trg_len, vocab, n_layer=6,
                             n_head=8, d_key=64, d_model=512, d_inner=2048):
    """Analytic matmul FLOPs of one train step: fwd projections +
    attention einsums + FFN + logits, ×3 for backward (standard
    1 fwd + 2 bwd accounting; optimizer update is noise). Counted at
    the PADDED shapes — the dense work the hardware is asked to do —
    so MFU compares fairly across attention paths (a kernel that skips
    masked blocks shows up as >nominal utilization, which the
    mask_ratio field contextualizes)."""
    B, S, T = float(batch), float(src_len), float(trg_len)

    def proj(tokens, din, dout):
        return 2.0 * tokens * din * dout

    enc = n_layer * (
        4 * proj(B * S, d_model, d_model)             # q,k,v,o
        + 2 * 2.0 * B * n_head * S * S * d_key        # qkᵀ + p·v
        + 2 * proj(B * S, d_model, d_inner))          # both FFN mats
    dec = n_layer * (
        4 * proj(B * T, d_model, d_model)             # self q,k,v,o
        + 2 * 2.0 * B * n_head * T * T * d_key
        + 2 * proj(B * T, d_model, d_model)           # cross q,o
        + 2 * proj(B * S, d_model, d_model)           # cross k,v
        + 2 * 2.0 * B * n_head * T * S * d_key
        + 2 * proj(B * T, d_model, d_inner))
    logits = proj(B * T, d_model, vocab)
    return 3.0 * (enc + dec + logits)


def transformer_mfu_est(tok_s, batch=64, seq=64, vocab=32000):
    """THE MFU formula — shared by the headline detail and
    bench_trainspeed (ISSUE 19 satellite: one accounting path, not
    two). Analytic matmul FLOPs per token at the given shapes
    (:func:`_transformer_train_flops`) against the chip peak from
    ``observe.device_peak_flops``. None off-TPU: a CPU run has no
    utilization to report."""
    from paddle_tpu import observe
    flops_per_tok = _transformer_train_flops(batch, seq, seq, vocab) \
        / (batch * seq)
    peak = observe.device_peak_flops()
    if peak is None:
        return None
    return tok_s * flops_per_tok / peak


def bench_transformer_masked(batch=8, seq=512, vocab=32000, iters=10):
    """Masked co-headline (VERDICT r4 next-#4): a variable-length batch
    at seq 512 — the actual NMT workload shape, where attention matters
    and rows carry real padding. src lengths drawn uniform [seq/2, seq];
    lbl_weight masks the same rows so the loss is honest. Reports padded
    tok/s (comparable to the seq-64 headline), real tok/s, and MFU from
    analytic matmul FLOPs vs the chip's bf16 peak
    (observe.device_peak_flops; None off-TPU)."""
    fluid = _fresh()
    from paddle_tpu.models import transformer as T
    avg_cost, _ = T.transformer_base(
        src_vocab_size=vocab, trg_vocab_size=vocab,
        src_seq_len=seq, trg_seq_len=seq, max_length=max(512, seq))
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = T.make_fake_batch(batch, seq, seq, vocab, vocab)
    lens = rng.randint(seq // 2, seq + 1, (batch,)).astype('int64')
    feed['src_length'] = lens
    feed['lbl_weight'] = (np.arange(seq)[None, :] <
                          lens[:, None]).astype('float32')
    feed = _to_device(feed)
    dt = _time_multi(exe, feed, [avg_cost], iters)
    flops = _transformer_train_flops(batch, seq, seq, vocab)
    from paddle_tpu import observe
    peak = observe.device_peak_flops()
    return {'tok_per_sec': round(batch * seq / dt, 1),
            'real_tok_per_sec': round(float(lens.sum()) / dt, 1),
            'mask_ratio': round(float(lens.sum()) / (batch * seq), 3),
            'analytic_tflops_per_step': round(flops / 1e12, 3),
            'mfu': None if peak is None else round(flops / dt / peak, 4),
            'attention_path': 'pallas' if os.environ.get(
                'PADDLE_TPU_USE_PALLAS') == '1' else 'xla'}


def bench_moe(batch=32, seq=64, vocab=32000, num_experts=8,
              capacity_factor=1.25, n_layer=4, iters=10):
    """Switch-MoE LM train throughput (tokens/s) — the ep-axis flagship
    measured on one chip (routing + capacity dispatch overhead vs the
    dense transformer). The capacity-factor sweep ablation quantifies
    the drop-rate/throughput trade the Switch paper tunes."""
    fluid = _fresh()
    from paddle_tpu.models.moe import switch_transformer_lm
    # scan_layers: the moe_layer_stack scan compiles flat over depth;
    # the unrolled 4-block MoE graph has never finished compiling on a
    # chip (ROADMAP S5)
    avg_cost, _ = switch_transformer_lm(
        vocab_size=vocab, seq_len=seq, n_layer=n_layer, n_head=8,
        d_model=512, d_inner=2048, num_experts=num_experts,
        capacity_factor=capacity_factor, dropout_rate=0.1,
        max_length=max(512, seq),
        scan_layers=os.environ.get('BENCH_MOE_SCAN', '1') != '0')
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    words = rng.randint(1, vocab, (batch, seq)).astype('int64')
    feed = _to_device({'word': words,
                       'label': np.roll(words, -1, axis=1)})
    return batch * seq / _time_multi(exe, feed, [avg_cost], iters)


def bench_rnn_lstm(batch=128, seq=100, vocab=30000, hidden=128,
                   lstm_num=1, iters=20):
    """The reference benchmark/paddle/rnn/rnn.py config (stacked-LSTM
    IMDB sentiment), built VERBATIM through the v1
    trainer_config_helpers shim — the rnn/ half of the benchmark suite
    beside image/. Reports tokens/s (batch*seq / step)."""
    fluid = _fresh()
    from paddle_tpu.trainer_config_helpers import (
        AdamOptimizer, L2Regularization, SoftmaxActivation,
        classification_cost, data_layer, embedding_layer, fc_layer,
        last_seq, settings, simple_lstm)
    net = data_layer('data', size=vocab, dtype='int64', seq_type=1)
    net = embedding_layer(input=net, size=128)
    for _ in range(lstm_num):
        net = simple_lstm(input=net, size=hidden)
    net = last_seq(input=net)
    net = fc_layer(input=net, size=2, act=SoftmaxActivation())
    lab = data_layer('label', 1, dtype='int64')
    loss = classification_cost(input=net, label=lab)
    settings(batch_size=batch, learning_rate=2e-3,
             learning_method=AdamOptimizer(),
             regularization=L2Regularization(8e-4),
             gradient_clipping_threshold=25).minimize(loss)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = _to_device({
        'data': rng.randint(1, vocab, (batch, seq)).astype('int64'),
        'data_len': np.full((batch,), seq, 'int32'),
        'label': rng.randint(0, 2, (batch, 1)).astype('int64')})
    return batch * seq / _time_multi(exe, feed, [loss], iters)


def bench_pipeline_ablation(model='transformer', steps=20, batch=None,
                            seq=64, vocab=32000, image=224,
                            depths=(1, 2, 4)):
    """Sync-vs-async trainer loop (ISSUE 4): the same HOST-FED workload
    through Trainer.train at pipeline_depth 1/2/4. Unlike the headline
    bench (device-resident feed, run_steps windows), every step here
    pays reader iteration + _to_feed + h2d + metric fetch — exactly the
    overheads the pipelined loop overlaps with device compute. Epoch 0
    warms the compile cache; epoch 1 is timed. Reports per-depth
    throughput plus the measured overlap fraction
    (1 - (host_blocked + device_blocked)/wall over the timed epoch),
    which also lands in the metrics JSONL as gauges."""
    import time as _t
    from paddle_tpu import observe as _observe
    import paddle_tpu.trainer as _trmod

    out = {'model': model, 'steps_per_epoch': steps}
    for d in depths:
        fluid = _fresh()
        if model == 'transformer':
            from paddle_tpu.models import transformer as T
            b = batch or 64

            def train_func():
                avg_cost, _ = T.transformer_base(
                    src_vocab_size=vocab, trg_vocab_size=vocab,
                    src_seq_len=seq, trg_seq_len=seq,
                    max_length=max(256, seq))
                return [avg_cost]

            def reader():
                for i in range(steps):
                    yield T.make_fake_batch(b, seq, seq, vocab, vocab,
                                            seed=i)

            unit = b * seq
            opt = lambda: fluid.optimizer.Adam(learning_rate=1e-4)
        else:
            from paddle_tpu.models.resnet import resnet50_with_loss
            b = batch or 64

            def train_func():
                _, avg_cost, _ = resnet50_with_loss()
                return [avg_cost]

            def reader():
                rng = np.random.RandomState(0)
                for i in range(steps):
                    yield {'image': rng.rand(b, 3, image,
                                             image).astype('float32'),
                           'label': rng.randint(
                               0, 1000, (b, 1)).astype('int64')}

            unit = b
            opt = lambda: fluid.optimizer.Momentum(learning_rate=0.1,
                                                   momentum=0.9)

        state = {}

        def handler(e, state=state):
            if isinstance(e, _trmod.BeginEpochEvent) and e.epoch == 1:
                state['hb0'] = _observe.get_gauge(
                    'trainer.host_blocked_seconds') or 0.0
                state['db0'] = _observe.get_gauge(
                    'trainer.device_blocked_seconds') or 0.0
                state['t0'] = _t.perf_counter()
            elif isinstance(e, _trmod.EndEpochEvent) and e.epoch == 1:
                state['t1'] = _t.perf_counter()
                state['hb1'] = _observe.get_gauge(
                    'trainer.host_blocked_seconds') or 0.0
                state['db1'] = _observe.get_gauge(
                    'trainer.device_blocked_seconds') or 0.0

        trainer = fluid.Trainer(train_func=train_func,
                                optimizer_func=opt,
                                place=fluid.TPUPlace(0))
        trainer.program.amp = 'bf16'
        trainer.train(num_epochs=2, event_handler=handler, reader=reader,
                      pipeline_depth=d,
                      host_prefetch=(2 if d > 1 else 0))
        wall = state['t1'] - state['t0']
        key = 'd%d' % d
        out[key + '_per_sec'] = round(unit * steps / wall, 1)
        if _observe.enabled():
            overlap = max(0.0, 1.0 - (
                (state['hb1'] - state['hb0']) +
                (state['db1'] - state['db0'])) / wall)
            out[key + '_overlap'] = round(overlap, 4)
            # into the metrics JSONL beside the throughput rows
            _observe.set_gauge('bench.pipeline_overlap_fraction',
                               overlap, model=model, depth=d)
            _observe.set_gauge('bench.pipeline_per_sec',
                               out[key + '_per_sec'], model=model,
                               depth=d)
    if out.get('d1_per_sec'):
        for d in depths[1:]:
            k = 'd%d_per_sec' % d
            if out.get(k):
                out['async_speedup_d%d' % d] = round(
                    out[k] / out['d1_per_sec'], 3)
    return out


def bench_decode(duration=8.0, clients=8, max_batch=16, block_size=32,
                 num_blocks=512, pages_per_seq=16, vocab=8000, n_layer=4,
                 n_head=8, d_model=256, d_inner=512, prompt_lo=16,
                 prompt_hi=64, max_new=64, shared_prefix=0.95,
                 shared_prefix_len=None, spec_k=3):
    """Decode-serving scenario: continuous batching + paged KV cache
    (serving/decode) under closed-loop streaming clients, on the
    fleet-realistic traffic mix (``shared_prefix`` of requests open
    with one shared system prompt). Two legs ablate speculative
    decoding off/on over the global prefix cache; cache-hit-rate,
    prefill-tokens-skipped, and accepted-draft-length land in the
    metrics JSONL (decode.prefix_* / decode.spec_*) beside tokens/sec
    and inter-token latency."""
    import threading

    from paddle_tpu import observe
    from paddle_tpu.serving.decode import DecodeEngine, LMSpec
    from paddle_tpu.serving.loadgen import Stats, closed_loop, percentiles

    d_head = max(8, d_model // n_head)
    spec = LMSpec(vocab_size=vocab, n_layer=n_layer, n_head=n_head,
                  d_key=d_head, d_value=d_head, d_model=d_model,
                  d_inner=d_inner)
    capacity = pages_per_seq * block_size
    prompt_hi = min(prompt_hi, capacity - max_new)
    n_shared = shared_prefix_len or max(block_size,
                                        (prompt_lo + prompt_hi) // 2)
    n_shared = min(n_shared, max(1, prompt_hi - 1))
    shared_ids = np.random.RandomState(1234).randint(
        0, vocab, n_shared).tolist()

    def counter_delta(after, before, name):
        return after['counters'].get(name, 0) - \
            before['counters'].get(name, 0)

    def run_leg(leg_spec_k):
        engine = DecodeEngine(spec, max_batch=max_batch,
                              block_size=block_size,
                              num_blocks=num_blocks,
                              pages_per_seq=pages_per_seq,
                              max_queue_depth=4 * clients,
                              prefix_cache=True, spec_k=leg_spec_k)
        t_w0 = time.time()
        signatures = engine.warmup()
        warmup_s = time.time() - t_w0
        engine.start()

        stats = Stats()
        gaps, tokens = [], [0]
        mu = threading.Lock()

        def do_request(rng):
            plen = int(rng.randint(prompt_lo, prompt_hi + 1))
            if rng.rand() < shared_prefix:
                tail = max(1, plen - n_shared)
                prompt = shared_ids + \
                    rng.randint(0, vocab, tail).tolist()
            else:
                prompt = rng.randint(0, vocab, plen).tolist()
            stream = engine.submit(prompt, max_new_tokens=max_new)
            n, t_prev, local = 0, None, []
            for _tok in stream:
                now = time.perf_counter()
                if t_prev is not None:
                    local.append(now - t_prev)
                t_prev = now
                n += 1
            with mu:
                gaps.extend(local)
                tokens[0] += n
            return n

        before = observe.snapshot()
        t0 = time.perf_counter()
        closed_loop(do_request, stats, t0 + duration, clients)
        engine.shutdown(drain=True)
        wall = time.perf_counter() - t0
        snap = observe.snapshot()
        occ = snap['histograms'].get('decode.batch_occupancy', {})
        acc = snap['histograms'].get('decode.spec_accepted_len', {})
        tps = tokens[0] / wall if wall else 0.0
        hit = counter_delta(
            snap, before,
            'decode.prefix_cache_lookups_total{outcome=hit}')
        miss = counter_delta(
            snap, before,
            'decode.prefix_cache_lookups_total{outcome=miss}')
        spec_steps = counter_delta(snap, before,
                                   'decode.spec_steps_total')
        accepted = counter_delta(snap, before,
                                 'decode.spec_accepted_tokens_total')
        return {
            'spec_k': leg_spec_k,
            'tokens_per_s': round(tps, 2),
            'tokens': tokens[0],
            'requests_ok': stats.ok,
            'duration_s': round(wall, 3),
            'inter_token_ms': percentiles(gaps),
            'request_ms': percentiles(stats.latencies),
            'batch_occupancy_mean': occ.get('mean'),
            'preemptions': counter_delta(snap, before,
                                         'decode.preemptions_total'),
            'cache_hit_rate': round(hit / float(hit + miss), 4)
            if (hit + miss) else None,
            'prefill_tokens_skipped': counter_delta(
                snap, before, 'decode.prefix_tokens_reused_total'),
            'accepted_draft_len_mean': acc.get('mean')
            if spec_steps else None,
            'accepted_draft_len_p50': acc.get('p50')
            if spec_steps else None,
            'accepted_tokens_total': accepted,
            'warmup': {'signatures': signatures,
                       'seconds': round(warmup_s, 3)},
        }

    legs = {'spec_off': run_leg(0)}
    if spec_k:
        legs['spec_on'] = run_leg(spec_k)
    head = legs.get('spec_on') or legs['spec_off']
    observe.set_gauge('decode.bench_tokens_per_s',
                      head['tokens_per_s'])
    out = dict(head)
    out.update({
        'workload': 'decode_transformer',
        'shared_prefix': shared_prefix,
        'shared_prefix_len': n_shared,
        'spec_ablation': legs,
        'spec_speedup': round(
            legs['spec_on']['tokens_per_s'] /
            legs['spec_off']['tokens_per_s'], 3)
        if 'spec_on' in legs and legs['spec_off']['tokens_per_s']
        else None,
        'engine': {'max_batch': max_batch, 'block_size': block_size,
                   'num_blocks': num_blocks,
                   'pages_per_seq': pages_per_seq},
        'model': {'vocab': vocab, 'n_layer': n_layer, 'n_head': n_head,
                  'd_model': d_model},
    })
    return out


class _ChaosPredictor(object):
    """Duck-typed predictor with a fixed per-batch compute floor: the
    overload arithmetic (offered rows/s vs replica capacity) stops
    depending on how fast THIS machine's tiny MLP runs, so chaos
    windows burn error budget by construction. Shared by the fleet and
    autoscale chaos workloads."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay_s = delay_s

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def predict(self, feed):
        out = self._inner.predict(feed)
        if self._delay_s:
            time.sleep(self._delay_s)
        return out


def _save_chaos_model(in_dim):
    """Save the tiny MLP the chaos scenarios serve; returns its dir."""
    import tempfile
    fluid = _fresh()
    model_dir = os.path.join(tempfile.mkdtemp(prefix='fleet_bench_'),
                             'model')
    x = fluid.layers.data(name='x', shape=[in_dim], dtype='float32')
    h = fluid.layers.fc(input=x, size=16, act='relu')
    out = fluid.layers.fc(input=h, size=4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(model_dir, ['x'], [out], exe)
    fluid.reset_default_programs()
    fluid.global_scope().clear()
    return model_dir


def bench_fleet(replicas=3, duration=6.0, steady_qps=40.0,
                spike_qps=700.0, spike_at=2.0, spike_s=1.5, kill_at=2.4,
                latency_budget_s=0.025, availability=0.95, window_s=1.5,
                max_batch=8, max_queue_depth=12, trace_sample=0.05,
                in_dim=8, retries=3, compute_delay_ms=10.0):
    """Fleet chaos scenario (ROADMAP item 5): a >=3-replica router under
    a diurnal open-loop load with a flash-crowd burst and a replica
    kill mid-spike (fault.inject.kill_replica). Asserts nothing itself
    — it measures and returns: accepted/completed/lost request counts
    (the zero-loss contract), the burn-rate and goodput timelines
    around the kill window, per-phase reject/error counts (plottable
    shed windows), readiness flips, and the sampled-trace census.
    slo.*/router.* metrics land in the metrics JSONL beside the
    results store; tools/metrics_report.py --slo renders them."""
    import threading

    from paddle_tpu import observe
    from paddle_tpu.fault import inject
    from paddle_tpu.observe.slo import Objective, SloTracker
    from paddle_tpu.serving import (NoReplicaAvailableError, Router,
                                    ServingEngine)
    from paddle_tpu.serving.loadgen import (Stats, diurnal, flash_crowd,
                                            heavy_tailed_rows, open_loop,
                                            percentiles)

    model_dir = _save_chaos_model(in_dim)
    from paddle_tpu.inference import create_predictor

    delay_s = float(compute_delay_ms) / 1000.0
    engines = [ServingEngine(_ChaosPredictor(create_predictor(model_dir),
                                             delay_s),
                             max_batch_size=max_batch,
                             batch_timeout_ms=1.0,
                             max_queue_depth=max_queue_depth,
                             name='replica%d' % i)
               for i in range(replicas)]
    t_w0 = time.perf_counter()
    for eng in engines:
        eng.warmup()
        eng.start()
    warmup_s = time.perf_counter() - t_w0

    tracker = SloTracker([Objective('fleet', latency_budget_s,
                                    availability_target=availability,
                                    window_s=window_s)])
    router = Router(engines, slo=tracker, route='fleet',
                    retries=retries)

    schedule = flash_crowd(
        diurnal(steady_qps, 1.25 * steady_qps, period_s=2 * duration),
        spike_qps, spike_at, spike_s)

    stats = Stats()
    submitted = [0]
    no_replica = [0]

    def submit_request(rng):
        rows = heavy_tailed_rows(rng, 1, max_batch)
        feed = {'x': rng.rand(rows, in_dim).astype('float32')}
        try:
            fut = router.submit(feed, session=int(rng.randint(0, 64)),
                                deadline_s=latency_budget_s)
        except NoReplicaAvailableError:
            no_replica[0] += 1
            return None   # counted as a reject in the ledger
        # QueueFullError (incl. SLOShedError) propagates: the loop
        # counts it as a reject with a timestamp
        submitted[0] += 1
        return fut, rows

    victim = engines[-1]
    ready_before_kill = [None]
    ready_after_kill = [None]
    burn_timeline, goodput_timeline = [], []
    t0 = time.perf_counter()
    stop = threading.Event()

    def sampler():
        while not stop.wait(0.05):
            now = time.perf_counter()
            burn_timeline.append(
                (round(now - t0, 3), tracker.burn_rate('fleet', now)))
            goodput_timeline.append(
                (round(now - t0, 3), tracker.goodput('fleet', now)))

    def killer():
        wait = kill_at - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        ready_before_kill[0] = victim.ready()
        inject.kill_replica(victim, drain=False)
        ready_after_kill[0] = victim.ready()

    threads = [threading.Thread(target=sampler, daemon=True),
               threading.Thread(target=killer, daemon=True)]
    # sampled requests leave cross-thread trace timelines + exemplars;
    # per-call env read, restored after the run
    prev_sample = os.environ.get('PADDLE_TPU_TRACE_SAMPLE')
    os.environ['PADDLE_TPU_TRACE_SAMPLE'] = str(trace_sample)
    try:
        for t in threads:
            t.start()
        open_loop(submit_request, stats, t0 + duration, schedule)
        for eng in engines:
            if eng is not victim:
                eng.shutdown(drain=True)
        # router callbacks resolve synchronously with the inner
        # futures; a short grace covers the last callback chain
        t_end = time.perf_counter() + 10.0
        while stats.ok + stats.errors < submitted[0] and \
                time.perf_counter() < t_end:
            time.sleep(0.01)
    finally:
        stop.set()
        if prev_sample is None:
            os.environ.pop('PADDLE_TPU_TRACE_SAMPLE', None)
        else:
            os.environ['PADDLE_TPU_TRACE_SAMPLE'] = prev_sample
    wall = time.perf_counter() - t0
    for t in threads:
        t.join(timeout=5)
    router.close()
    tracker.publish()

    # sampled-trace census: distinct trace ids and the widest thread
    # spread any one of them achieved (the >=3-thread acceptance)
    by_trace = {}
    for ev in observe.spans().events():
        tid = (ev.get('args') or {}).get('trace_id')
        if tid and ev.get('ph') == 'X':
            by_trace.setdefault(tid, set()).add(ev.get('tid'))
    kill_window = (kill_at, min(kill_at + 2.0, duration))
    burn_during_kill = max(
        [b for t, b in burn_timeline
         if kill_window[0] <= t <= kill_window[1]] or [0.0])
    tail = [g for t, g in goodput_timeline if t >= 0.8 * duration]
    accepted = submitted[0]
    completed = stats.ok + stats.errors
    phases = {
        'steady': stats.counts_between(0.0, spike_at),
        'spike': stats.counts_between(spike_at, spike_at + spike_s),
        'after': stats.counts_between(spike_at + spike_s, duration),
    }
    snap = observe.snapshot()
    return {
        'workload': 'fleet',
        'replicas': replicas,
        'duration_s': round(wall, 3),
        'accepted': accepted,
        'completed': completed,
        'lost': accepted - completed,
        'requests_ok': stats.ok,
        'requests_rejected': stats.rejected,
        'requests_errored': stats.errors,
        'no_replica': no_replica[0],
        'latency_ms': percentiles(stats.latencies),
        'phases': phases,
        'burn_during_kill': round(burn_during_kill, 4),
        'burn_timeline': burn_timeline,
        'goodput_end_rps': round(sum(tail) / len(tail), 2)
        if tail else 0.0,
        'goodput_timeline': goodput_timeline,
        'kill': {'victim': victim.name, 'at_s': kill_at,
                 'ready_before': ready_before_kill[0],
                 'ready_after': ready_after_kill[0]},
        'failovers': sum(
            v for k, v in snap['counters'].items()
            if k.startswith('router.failover_total')),
        'sheds': sum(v for k, v in snap['counters'].items()
                     if k.startswith('router.shed_total')),
        'sampled_traces': len(by_trace),
        'max_trace_threads': max(
            [len(tids) for tids in by_trace.values()] or [0]),
        'slo': {'route': 'fleet',
                'latency_budget_s': latency_budget_s,
                'availability_target': availability,
                'window_s': window_s},
        'warmup_s': round(warmup_s, 3),
    }


def bench_quant(dp=8, steps=150, hidden=256, in_dim=64,
                kv_duration=2.5, kv_block_size=8, kv_pages_per_seq=8,
                kv_blocks_fp32=16, fleet_ab=True, fleet_duration=4.0):
    """Quantization ablation (ISSUE 13), three asserted legs:

    1. **int8 gradient allreduce** — the same MLP regression trained
       twice on a dp mesh, fp32 vs quantized grads
       (ParallelStrategy(quantized_allreduce=True)); asserts the
       simulated dp comm bytes drop >= 3x (quant.allreduce_* gauges
       from the executor's wire model) with final-loss delta within
       tolerance, off-leg bit-identical to baseline, and the REAL
       shard_map quantized_all_reduce within rel-err of exact psum.
    2. **quantized KV arena** — equal ARENA BYTES, fp32 pages vs the
       int8 pages that budget buys; closed-loop decode load measures
       resident_seqs_peak on each (assert >= 1.8x), decode outputs
       pass the parity bound (paged-attention cosine vs fp32 + token
       agreement), and kv_dtype off is bit-identical to default.
    3. **fleet A/B** — the chaos fleet scenario with baseline replicas
       vs 'quantized' replicas whose per-replica concurrency ceiling
       is scaled by the capacity ratio leg 2 MEASURED (decode replicas
       are HBM-bound: resident sequences == batch ceiling) — goodput
       and burn rate under the same flash-crowd + kill schedule, so
       the win is judged on fleet SLOs, not microbenchmarks.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import observe, quant
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.transpiler import (ParallelStrategy,
                                                transpile)

    out = {'workload': 'quant'}
    dp = max(1, min(int(dp), jax.device_count()))

    # ---- leg 1: int8 gradient allreduce on the trainer path --------
    def train_leg(quant_on):
        fluid = _fresh()
        np.random.seed(0)
        true_w = np.random.randn(in_dim, 1).astype('float32')
        x = fluid.layers.data(name='x', shape=[in_dim], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(input=x, size=hidden, act='relu',
                            param_attr=fluid.ParamAttr(name='q_w1'))
        h = fluid.layers.fc(input=h, size=64, act='relu')
        pred = fluid.layers.fc(input=h, size=1, act=None)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.02).minimize(cost)
        if dp > 1:
            transpile(fluid.default_main_program(), make_mesh(dp=dp),
                      ParallelStrategy(data_parallel=True,
                                       quantized_allreduce=quant_on))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        losses = []
        for _ in range(steps):
            xs = np.random.randn(8 * dp, in_dim).astype('float32')
            ys = xs @ true_w
            got = exe.run(feed={'x': xs, 'y': ys}, fetch_list=[cost])
            losses.append(float(np.asarray(got[0]).reshape(())))
        w1 = np.asarray(fluid.global_scope().find('q_w1'))
        return losses, w1

    loss_f, w_f = train_leg(False)
    loss_f2, w_f2 = train_leg(False)     # off-leg determinism baseline
    loss_q, w_q = train_leg(True)
    snap = observe.snapshot()
    g = snap['gauges']
    bytes_fp32 = g.get('quant.allreduce_bytes_fp32', 0)
    bytes_quant = g.get('quant.allreduce_bytes_quant', 1)
    compression = g.get('quant.allreduce_compression', 0)
    loss_delta = abs(loss_q[-1] - loss_f[-1])
    loss_tol = max(0.05, 0.25 * abs(loss_f[-1]))
    assert np.array_equal(w_f, w_f2), \
        'quantized_allreduce=False must stay bit-identical run to run'
    if dp > 1:
        assert compression >= 3.0, \
            'int8 allreduce compression %.2fx < 3x' % compression
        assert loss_delta <= loss_tol, \
            'quantized final loss %.4f vs fp32 %.4f (tol %.4f)' \
            % (loss_q[-1], loss_f[-1], loss_tol)

    # the REAL two-leg schedule vs exact psum, over the same mesh
    qar = {'dp': dp}
    if dp > 1:
        from jax.sharding import Mesh, PartitionSpec as P
        try:
            from jax import shard_map
        except ImportError:
            from jax.experimental.shard_map import shard_map
        from paddle_tpu.parallel import collective
        mesh = Mesh(np.array(jax.devices()[:dp]).reshape(dp), ('dp',))
        xs = np.random.RandomState(1).randn(dp, 1 << 14) \
            .astype('float32')
        f = shard_map(
            lambda a: collective.quantized_all_reduce(
                a.reshape(-1), 'dp',
                key=jax.random.PRNGKey(3)).reshape(a.shape),
            mesh=mesh, in_specs=(P('dp', None),),
            out_specs=P('dp', None))
        got = np.asarray(jax.jit(f)(xs))
        exact = np.tile(xs.sum(0, keepdims=True), (dp, 1))
        rel = float(np.abs(got - exact).max() / np.abs(exact).max())
        assert rel < 0.05, 'quantized_all_reduce rel err %.4f' % rel
        qar['rel_err_vs_psum'] = round(rel, 6)
    out['allreduce'] = {
        'dp': dp, 'steps': steps,
        'final_loss_fp32': round(loss_f[-1], 6),
        'final_loss_int8': round(loss_q[-1], 6),
        'loss_delta': round(loss_delta, 6),
        'bytes_fp32_per_step': bytes_fp32,
        'bytes_int8_per_step': bytes_quant,
        'compression_x': round(compression, 3),
        'collective': qar,
        'off_leg_bit_identical': True,
    }
    observe.set_gauge('quant.bench_allreduce_compression', compression)

    # ---- leg 2: quantized KV arena at equal bytes ------------------
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_reference)
    from paddle_tpu.serving.decode import (DecodeEngine, LMSpec,
                                           random_weights)
    from paddle_tpu.serving.decode.model import (arena_bytes,
                                                 kv_bytes_per_token,
                                                 num_blocks_for_budget)
    from paddle_tpu.serving.loadgen import Stats, closed_loop

    spec = LMSpec(vocab_size=256, n_layer=2, n_head=2, d_key=16,
                  d_value=16, d_model=32, d_inner=64)
    weights = random_weights(spec, seed=3)
    budget = arena_bytes(spec, kv_blocks_fp32, kv_block_size, 'float32')
    nb_int8 = num_blocks_for_budget(budget, spec, kv_block_size, 'int8')
    capacity_ratio = nb_int8 / float(kv_blocks_fp32)

    def kv_leg(kv_dtype, num_blocks):
        eng = DecodeEngine(spec, max_batch=12, block_size=kv_block_size,
                           num_blocks=num_blocks,
                           pages_per_seq=kv_pages_per_seq,
                           max_queue_depth=64, weights=weights,
                           kv_dtype=kv_dtype)
        eng.warmup()
        eng.start()
        stats = Stats()

        def do_request(rng):
            plen = int(rng.randint(16, 25))
            prompt = rng.randint(0, 256, plen).tolist()
            return len(eng.submit(prompt, max_new_tokens=24)
                       .result(120))

        closed_loop(do_request, stats,
                    time.perf_counter() + kv_duration, 10)
        eng.shutdown(drain=True)
        return {'kv_dtype': eng.kv_dtype, 'num_blocks': num_blocks,
                'arena_bytes': arena_bytes(spec, num_blocks,
                                           kv_block_size, eng.kv_dtype),
                'kv_bytes_per_token': eng.kv_bytes_per_token,
                'resident_seqs_peak': eng.resident_seqs_peak,
                'requests_ok': stats.ok}

    leg_f = kv_leg('fp32', kv_blocks_fp32)
    leg_q = kv_leg('int8', nb_int8)
    resident_ratio = leg_q['resident_seqs_peak'] / \
        max(1.0, leg_f['resident_seqs_peak'])
    assert leg_q['arena_bytes'] <= budget, 'equal-bytes violated'
    assert resident_ratio >= 1.8, \
        'resident seqs %.2fx < 1.8x at equal arena bytes (fp32 peak ' \
        '%d, int8 peak %d)' % (resident_ratio,
                               leg_f['resident_seqs_peak'],
                               leg_q['resident_seqs_peak'])

    # parity bound: the dequantized attention path vs fp32, and token
    # agreement between fp32/int8 engines on identical prompts
    rng = np.random.RandomState(7)
    nb, h_, bs, d = 8, 2, kv_block_size, 16
    kf = rng.randn(1, nb, bs, h_, d).astype('float32')  # per-head rows
    vf = rng.randn(1, nb, bs, h_, d).astype('float32')
    kq, ks = quant.quantize_rows(jnp.asarray(kf), 'int8')
    vq, vs = quant.quantize_rows(jnp.asarray(vf), 'int8')

    def arena(x):                  # -> the engine's [L, NB, bs, H*D]
        return np.asarray(x).reshape(1, nb, bs, h_ * d)
    q = rng.randn(3, h_, d).astype('float32')
    tables = np.array([[0, 1, 2, 7], [3, 4, 8, 8], [5, 6, 8, 8]],
                      'int32')
    lens = np.array([4 * bs - 2, 2 * bs, bs + 3], 'int32')
    ref = np.asarray(paged_attention_reference(q, arena(kf), arena(vf),
                                               tables, lens))
    got = np.asarray(paged_attention_reference(
        q, arena(kq), arena(vq), tables, lens,
        k_scales=np.asarray(ks), v_scales=np.asarray(vs)))
    cos = float((ref * got).sum() /
                (np.linalg.norm(ref) * np.linalg.norm(got) + 1e-12))
    assert cos >= 0.99, 'paged-attention parity cosine %.5f' % cos

    def token_streams(kv_dtype):
        eng = DecodeEngine(spec, max_batch=4, block_size=kv_block_size,
                           num_blocks=kv_blocks_fp32,
                           pages_per_seq=kv_pages_per_seq,
                           weights=weights, kv_dtype=kv_dtype)
        eng.start()
        prng = np.random.RandomState(11)
        outs = [eng.generate(prng.randint(0, 256, 12).tolist(),
                             max_new_tokens=12, timeout=120)
                for _ in range(6)]
        eng.shutdown()
        return outs

    tok_f = token_streams('fp32')
    tok_default = token_streams(None)      # knob off == fp32, bit-exact
    tok_q = token_streams('int8')
    assert tok_f == tok_default, 'kv_dtype off must be bit-identical'
    agree = []
    for a, b in zip(tok_f, tok_q):
        n = sum(1 for t_a, t_b in zip(a, b) if t_a == t_b)
        agree.append(n / float(max(len(a), 1)))
    token_match = float(np.mean(agree))
    out['kv'] = {
        'arena_budget_bytes': budget,
        'fp32': leg_f, 'int8': leg_q,
        'capacity_ratio_pages': round(capacity_ratio, 3),
        'resident_seqs_ratio': round(resident_ratio, 3),
        'parity': {'attention_cosine': round(cos, 6),
                   'token_match_mean': round(token_match, 4)},
        'off_bit_identical': True,
    }
    observe.set_gauge('quant.bench_kv_resident_ratio', resident_ratio)
    observe.set_gauge('quant.bench_kv_parity_cosine', cos)

    # ---- leg 3: fleet A/B on goodput + burn rate -------------------
    if fleet_ab:
        fleet_kw = dict(duration=fleet_duration, steady_qps=30.0,
                        spike_qps=500.0, spike_at=1.0, spike_s=1.0,
                        kill_at=1.2, window_s=1.0, max_queue_depth=10)
        base = bench_fleet(max_batch=8, **fleet_kw)
        # quantized replicas: the measured KV capacity ratio raises the
        # per-replica concurrency ceiling (decode replicas are
        # HBM-bound — resident sequences ARE the batch ceiling)
        q_batch = int(round(8 * min(resident_ratio, 2.5)))
        quant_leg = bench_fleet(max_batch=q_batch, **fleet_kw)

        def trim(r):
            return {k: r[k] for k in
                    ('accepted', 'completed', 'lost', 'requests_ok',
                     'requests_rejected', 'goodput_end_rps',
                     'burn_during_kill', 'latency_ms')}

        assert base['lost'] == 0 and quant_leg['lost'] == 0
        out['fleet_ab'] = {
            'baseline_max_batch': 8,
            'quantized_max_batch': q_batch,
            'baseline': trim(base),
            'quantized': trim(quant_leg),
            'goodput_delta_rps': round(
                quant_leg['goodput_end_rps'] - base['goodput_end_rps'],
                2),
            'burn_delta': round(quant_leg['burn_during_kill'] -
                                base['burn_during_kill'], 4),
        }
    return out


def bench_trainspeed(dp=8, steps=24, hidden=64, in_dim=32, batch=8,
                     overlap_iters=6, fp8_n=64, mfu_batch=2, mfu_seq=16,
                     mfu_vocab=512, mfu_iters=3):
    """Training raw speed (ISSUE 19), asserted legs:

    1. **bucketed exact allreduce** — the same dyadic MLP+SGD
       regression trained unbucketed vs bucketed
       (ParallelStrategy(grad_bucket_mb=...)) on the dp CPU mesh;
       asserts final params BIT-IDENTICAL (the exact path is a pure
       relayout) and >= 2 buckets formed (trainer.grad_bucket_count).
    2. **backward/allreduce overlap** — three-point estimate
       (observe.overlap_fraction): bucketed step vs unbucketed step vs
       the per-bucket collective round-trip alone; asserts
       trainer.allreduce_overlap_fraction is published and > 0.
    3. **fp8 matmul** — parity (rel err <= 5e-2 at fp8_n x fp8_n),
       dispatch strictly follows the tuner table (fp8 dispatched iff
       the measured winner is fp8 — fp8.matmul_dispatch_total
       counter), and PADDLE_TPU_FP8_MATMUL beats the table both ways.
    4. **ZeRO-1 sharded optimizer state** — Adam, replicated vs
       shard_optimizer_state=True; asserts final params bit-identical
       and the analytic optimizer_state_bytes model shows per-device
       state reduced >= 0.8*dp (gauged at transpile).
    5. **quantized + bucketed composition** — both knobs on; asserts
       final-loss delta within the quant tolerance (EQuARX compression
       and bucket overlap stack).
    6. **MFU accounting** — the unified transformer_mfu_est accounting
       vs XLA cost-analysis FLOPs on a small transformer (analytic
       / cost-analysis ratio within [1/3, 3]); mfu_est is None off-TPU.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import observe, tuning
    from paddle_tpu.ops.fp8_matmul import fp8_matmul, maybe_fp8_matmul
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.transpiler import (ParallelStrategy,
                                                optimizer_state_bytes,
                                                transpile)
    from paddle_tpu.trainer import record_allreduce_overlap

    out = {'workload': 'trainspeed'}
    dp = max(1, min(int(dp), jax.device_count()))
    rng = np.random.RandomState(0)
    # dyadic feeds: every value is k/8, so dp partial sums are exact in
    # fp32 under ANY association — bit-identity asserts stay meaningful
    X = (rng.randint(-8, 8, (batch * dp, in_dim)) / 8.0) \
        .astype('float32')
    Y = (rng.randint(-8, 8, (batch * dp, 1)) / 8.0).astype('float32')

    def train_leg(bucket_mb=None, shard_opt=False, quant_on=False,
                  opt='sgd', n_steps=steps):
        fluid = _fresh()
        x = fluid.layers.data(name='x', shape=[in_dim], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(input=x, size=hidden, act='relu')
        h = fluid.layers.fc(input=h, size=hidden, act='relu')
        pred = fluid.layers.fc(input=h, size=1, act=None)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        if opt == 'sgd':
            fluid.optimizer.SGD(learning_rate=0.125).minimize(cost)
        else:
            fluid.optimizer.Adam(learning_rate=0.125).minimize(cost)
        prog = fluid.default_main_program()
        prog.random_seed = 7
        if dp > 1:
            transpile(prog, make_mesh(dp=dp), ParallelStrategy(
                grad_bucket_mb=bucket_mb,
                shard_optimizer_state=True if shard_opt else None,
                quantized_allreduce=quant_on))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        losses, t0 = [], None
        for i in range(n_steps):
            got = exe.run(feed={'x': X, 'y': Y}, fetch_list=[cost])
            losses.append(float(np.asarray(got[0]).reshape(())))
            if i == 0:
                t0 = time.perf_counter()   # after the compiling step
        per_step = (time.perf_counter() - t0) / max(1, n_steps - 1)
        weights = {p.name: np.asarray(fluid.global_scope().find(p.name))
                   for p in prog.all_parameters()}
        return losses, weights, per_step, prog

    # ---- legs 1+2: bucketed bit-identity, then overlap -------------
    loss_f, w_f, t_fused, _ = train_leg()
    loss_b, w_b, t_buck, _ = train_leg(bucket_mb=0.001)
    g = observe.snapshot()['gauges']
    n_buckets = g.get('trainer.grad_bucket_count', 0)
    bit_identical = all(np.array_equal(w_f[k], w_b[k]) for k in w_f)
    if dp > 1:
        assert bit_identical, \
            'bucketed exact path must be bit-identical to unbucketed'
        assert n_buckets >= 2, \
            'bucket target 0.001MB formed %s buckets (< 2)' % n_buckets
    out['bucketing'] = {
        'dp': dp, 'steps': steps, 'n_buckets': int(n_buckets),
        'target_bytes': int(g.get('trainer.grad_bucket_target_bytes', 0)),
        'max_bucket_bytes': int(g.get('trainer.grad_bucket_max_bytes', 0)),
        'final_loss': round(loss_b[-1], 6),
        'bit_identical_to_unbucketed': bool(bit_identical),
    }

    overlap = {'dp': dp}
    if dp > 1:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh(dp=dp)
        sizes = [max(dp, -(-int(w.size) // dp) * dp)
                 for w in w_f.values()]
        arrs = [jnp.ones((s,), jnp.float32) for s in sizes]

        @jax.jit
        def comm_fn(arrs):
            # the bucket collective boundary alone: one P('dp')/P()
            # constraint round trip per bucket-sized array
            outs = []
            for a in arrs:
                c = jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, P('dp')))
                outs.append(jax.lax.with_sharding_constraint(
                    c, NamedSharding(mesh, P())))
            return outs

        np.asarray(comm_fn(arrs)[0])                   # compile
        t0 = time.perf_counter()
        for _ in range(max(1, overlap_iters)):
            r = comm_fn(arrs)
        np.asarray(r[0])
        t_comm = (time.perf_counter() - t0) / max(1, overlap_iters)
        frac = record_allreduce_overlap(t_buck, t_fused, t_comm)
        assert frac is not None and frac > 0.0, \
            'overlap fraction %r (step %.5fs compute %.5fs comm %.5fs)' \
            % (frac, t_buck, t_fused, t_comm)
        g = observe.snapshot()['gauges']
        assert 'trainer.allreduce_overlap_fraction' in g, \
            'overlap gauge must be published'
        overlap.update(
            step_seconds=round(t_buck, 6),
            compute_seconds=round(t_fused, 6),
            comm_seconds=round(t_comm, 6),
            fraction=round(float(frac), 4))
    out['overlap'] = overlap

    # ---- leg 3: fp8 matmul parity + dispatch discipline ------------
    prng = np.random.RandomState(5)
    a = jnp.asarray(prng.randn(fp8_n, fp8_n).astype('float32'))
    b = jnp.asarray(prng.randn(fp8_n, fp8_n).astype('float32'))
    ref = np.asarray(jnp.matmul(a, b))
    rel = float(np.linalg.norm(np.asarray(fp8_matmul(a, b)) - ref)
                / np.linalg.norm(ref))
    assert rel <= 0.05, 'fp8 matmul rel err %.4f > 0.05' % rel

    import tempfile
    saved = {k: os.environ.get(k) for k in
             ('PADDLE_TPU_AUTOTUNE', 'PADDLE_TPU_TUNING_TABLE',
              'PADDLE_TPU_FP8_MATMUL')}
    tdir = tempfile.mkdtemp(prefix='trainspeed_tune_')

    def dispatch_count():
        return observe.snapshot()['counters'].get(
            'fp8.matmul_dispatch_total', 0)

    try:
        os.environ['PADDLE_TPU_AUTOTUNE'] = 'record'
        os.environ.pop('PADDLE_TPU_FP8_MATMUL', None)
        # fp8-winning table -> dispatched (and counted)
        os.environ['PADDLE_TPU_TUNING_TABLE'] = \
            os.path.join(tdir, 'fp8_wins.json')
        tuning.reset()
        tuning.set_timer(lambda op, key, v, t:
                         0.001 if v.get('impl') == 'fp8' else 0.010)
        c0 = dispatch_count()
        assert maybe_fp8_matmul(a, b) is not None, \
            'fp8 table winner must dispatch fp8'
        assert dispatch_count() == c0 + 1, 'dispatch counter must move'
        # explicit off gate beats the fp8-winning table
        os.environ['PADDLE_TPU_FP8_MATMUL'] = '0'
        assert maybe_fp8_matmul(a, b) is None, 'off gate beats table'
        # native-winning table -> NOT dispatched
        os.environ.pop('PADDLE_TPU_FP8_MATMUL', None)
        os.environ['PADDLE_TPU_TUNING_TABLE'] = \
            os.path.join(tdir, 'native_wins.json')
        tuning.reset()
        tuning.set_timer(lambda op, key, v, t:
                         0.001 if v.get('impl') == 'native' else 0.010)
        c0 = dispatch_count()
        assert maybe_fp8_matmul(a, b) is None, \
            'native table winner must NOT dispatch fp8'
        assert dispatch_count() == c0, \
            'no dispatch may be counted on the native path'
        # explicit on gate beats the native-winning table
        os.environ['PADDLE_TPU_FP8_MATMUL'] = '1'
        assert maybe_fp8_matmul(a, b) is not None, 'on gate beats table'
    finally:
        tuning.set_timer(None)
        tuning.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    out['fp8'] = {'n': fp8_n, 'rel_err': round(rel, 5),
                  'dispatch_follows_table': True,
                  'env_gate_beats_table': True}

    # ---- leg 4: ZeRO-1 sharded optimizer state ---------------------
    loss_a, w_a, _, prog_a = train_leg(opt='adam')
    loss_z, w_z, _, prog_z = train_leg(opt='adam', shard_opt=True)
    z_bit = all(np.array_equal(w_a[k], w_z[k]) for k in w_a)
    mem_r = optimizer_state_bytes(prog_a)
    mem_z = optimizer_state_bytes(prog_z)
    if dp > 1:
        assert z_bit, 'ZeRO-1 params must be bit-identical to replicated'
        assert mem_z['reduction'] >= 0.8 * dp, \
            'optimizer state reduction %.2fx < 0.8*dp (dp=%d)' \
            % (mem_z['reduction'], dp)
        g = observe.snapshot()['gauges']
        assert 'trainer.optimizer_state_bytes_per_device' in g, \
            'ZeRO-1 memory gauge must be published at transpile'
    out['zero1'] = {
        'dp': dp, 'bit_identical_to_replicated': bool(z_bit),
        'state_bytes_total': mem_z['total'],
        'state_bytes_per_device_replicated': mem_r['per_device'],
        'state_bytes_per_device_sharded': mem_z['per_device'],
        'reduction_x': round(mem_z['reduction'], 3),
    }

    # ---- leg 5: quantized + bucketed composition -------------------
    loss_qb, _, _, _ = train_leg(bucket_mb=0.001, quant_on=True)
    delta = abs(loss_qb[-1] - loss_f[-1])
    tol = max(0.05, 0.25 * abs(loss_f[-1]))
    if dp > 1:
        assert delta <= tol, \
            'quantized+bucketed final loss %.4f vs exact %.4f (tol %.4f)' \
            % (loss_qb[-1], loss_f[-1], tol)
    out['quant_bucketed'] = {
        'final_loss_exact': round(loss_f[-1], 6),
        'final_loss_quant_bucketed': round(loss_qb[-1], 6),
        'loss_delta': round(delta, 6), 'tolerance': round(tol, 6),
    }

    # ---- leg 6: MFU — unified accounting --------------------------
    saved_cost = os.environ.get('PADDLE_TPU_OBSERVE_COST')
    os.environ['PADDLE_TPU_OBSERVE_COST'] = '1'  # need executor.step_flops
    try:
        fluid = _fresh()
        from paddle_tpu.models import transformer as T
        avg_cost, _ = T.transformer_base(
            src_vocab_size=mfu_vocab, trg_vocab_size=mfu_vocab,
            src_seq_len=mfu_seq, trg_seq_len=mfu_seq,
            max_length=max(256, mfu_seq))
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(fluid.default_startup_program())
        feed = _to_device(T.make_fake_batch(mfu_batch, mfu_seq, mfu_seq,
                                            mfu_vocab, mfu_vocab))
        got = exe.run(feed=feed, fetch_list=[avg_cost])  # single-step key
        np.asarray(got[0])
        xla_flops = observe.snapshot()['gauges'].get(
            'executor.step_flops', 0)
        dt = _time_multi(exe, feed, [avg_cost], mfu_iters)
    finally:
        if saved_cost is None:
            os.environ.pop('PADDLE_TPU_OBSERVE_COST', None)
        else:
            os.environ['PADDLE_TPU_OBSERVE_COST'] = saved_cost
    tok_s = mfu_batch * mfu_seq / dt
    analytic = _transformer_train_flops(mfu_batch, mfu_seq, mfu_seq,
                                        mfu_vocab)
    assert xla_flops, 'executor.step_flops gauge missing — the unified ' \
        'MFU path needs the XLA cost analysis'
    ratio = analytic / xla_flops
    # analytic counts matmul FLOPs only (x3 bwd); XLA counts the whole
    # program — agreement within 3x is the unification contract
    assert 1.0 / 3.0 <= ratio <= 3.0, \
        'analytic %.3e vs cost-analysis %.3e FLOPs (ratio %.3f)' \
        % (analytic, xla_flops, ratio)
    mfu = transformer_mfu_est(tok_s, mfu_batch, mfu_seq, mfu_vocab)
    mfu_leg = {
        'batch': mfu_batch, 'seq': mfu_seq, 'vocab': mfu_vocab,
        'tok_per_sec': round(tok_s, 1),
        'mfu_est': None if mfu is None else round(mfu, 6),
        'analytic_flops_per_step': analytic,
        'xla_cost_analysis_flops': xla_flops,
        'analytic_vs_xla_ratio': round(ratio, 3),
    }
    out['mfu'] = mfu_leg
    return out


def bench_autoscale(in_dim=8, max_batch=8, max_queue_depth=12,
                    compute_delay_ms=10.0, latency_budget_s=0.05,
                    availability=0.95, window_s=1.5,
                    flash_duration=4.0, flash_steady_qps=30.0,
                    flash_spike_qps=500.0, flash_spike_at=1.2,
                    crash_duration=4.0, crash_qps=40.0, crash_kills=4,
                    crash_interval_s=0.45, crash_first_kill_at=0.6,
                    trough_duration=4.0, trough_high_qps=40.0,
                    trough_low_qps=4.0, trough_drop_at=1.0,
                    retry_budget=0.1, retry_budget_burst=20.0,
                    trace_sample=0.05):
    """Self-healing autoscaling chaos suite (ISSUE 11): three scenarios
    through one FleetController + hedging Router, each measured (the
    test asserts):

    1. **flash crowd** — offered load jumps ~15x; the controller must
       scale out before the error budget burns
       through: burn spikes >1x then recovers <1x within the run,
       with zero accepted-request loss.
    2. **crash loop** — one replica slot is killed repeatedly
       (fault.inject.crash_loop); the circuit breaker must quarantine
       the flapping lineage (flight event + counter) and goodput must
       recover on the survivors.
    3. **diurnal trough** — load drops ~10x; the controller must scale
       in by drain-then-shutdown with zero accepted-request loss and
       zero errors.

    Hedged requests run throughout: the returned ``hedge`` ledger
    proves retry traffic (hedges + failovers) stayed inside the token
    budget ``retry_budget x accepted + burst`` and that no hedge ever
    produced a result differing from its primary
    (``router.hedge_mismatch_total == 0``). Periodic JSONL snapshots
    (observe.flush) make the scale timeline reconstructable by
    ``tools/metrics_report.py --fleet``."""
    import threading

    from paddle_tpu import observe
    from paddle_tpu.fault import inject
    from paddle_tpu.observe.slo import Objective, SloTracker
    from paddle_tpu.serving import (FleetController,
                                    NoReplicaAvailableError, Router,
                                    ServingEngine)
    from paddle_tpu.serving.loadgen import (Stats, flash_crowd,
                                            open_loop, percentiles)

    model_dir = _save_chaos_model(in_dim)
    from paddle_tpu.inference import create_predictor

    delay_s = float(compute_delay_ms) / 1000.0
    # one loaded program and one executor under every replica of this
    # process: the first warmup compiles the ladder, and a spawn's
    # warmup (the scale-up path) is served by the executor's in-memory
    # cache instead of compiling the same program again
    pred = _ChaosPredictor(create_predictor(model_dir), delay_s)

    def make_engine(name):
        """The ReplicaFactory: an engine of its own (queue, batcher,
        thread) over the one loaded model."""
        return ServingEngine(pred, max_batch_size=max_batch,
                             batch_timeout_ms=1.0,
                             max_queue_depth=max_queue_depth,
                             name=name)

    def counter_sum(snap, prefix):
        return sum(v for k, v in snap['counters'].items()
                   if k.startswith(prefix))

    def run_scenario(tag, qps_spec, duration, n_start, ctl_kw,
                     chaos=None, deadline_s=None):
        """One scenario: fresh fleet + controller, open-loop load,
        sampler thread (burn/goodput/census timeline + periodic JSONL
        snapshots), optional chaos thread. Returns the measured dict
        (counter values are per-scenario deltas)."""
        snap0 = observe.snapshot()
        engines = []
        t_w0 = time.perf_counter()
        for i in range(n_start):
            eng = make_engine('%s%d' % (tag, i))
            eng.warmup()
            eng.start()
            engines.append(eng)
        warmup_s = time.perf_counter() - t_w0
        tracker = SloTracker([Objective(tag, latency_budget_s,
                                        availability_target=availability,
                                        window_s=window_s)])
        router = Router(engines, slo=tracker, route=tag, retries=3,
                        hedge=True, retry_budget=retry_budget,
                        retry_budget_burst=retry_budget_burst)
        ctl = FleetController(router, make_engine, slo=tracker,
                              route=tag, name_prefix='%s-auto' % tag,
                              **ctl_kw)
        ctl.start()

        stats = Stats()
        submitted = [0]
        no_replica = [0]

        def submit_request(rng):
            rows = int(rng.randint(1, max(2, max_batch // 2)))
            feed = {'x': rng.rand(rows, in_dim).astype('float32')}
            try:
                fut = router.submit(feed,
                                    session=int(rng.randint(0, 64)),
                                    deadline_s=deadline_s)
            except NoReplicaAvailableError:
                no_replica[0] += 1
                return None
            submitted[0] += 1
            return fut, rows

        burn_timeline, census_timeline = [], []
        goodput_timeline = []
        t0 = time.perf_counter()
        stop = threading.Event()

        def sampler():
            last_flush = 0.0
            while not stop.wait(0.05):
                now = time.perf_counter()
                t = round(now - t0, 3)
                burn_timeline.append(
                    (t, tracker.burn_rate(tag, now)))
                goodput_timeline.append(
                    (t, tracker.goodput(tag, now)))
                census_timeline.append((t, ctl.census()))
                if now - last_flush >= 0.25:
                    last_flush = now
                    observe.flush(kind='snapshot')

        threads = [threading.Thread(target=sampler, daemon=True)]
        chaos_result = {}
        if chaos is not None:
            threads.append(threading.Thread(
                target=lambda: chaos_result.update(chaos(ctl, t0)),
                daemon=True))
        for t in threads:
            t.start()
        open_loop(submit_request, stats, t0 + duration, qps_spec)
        ctl.close()                    # stop ticking before teardown
        for name, rep in router.replicas():
            rep.shutdown(drain=True)
        t_end = time.perf_counter() + 15.0
        while stats.ok + stats.errors < submitted[0] and \
                time.perf_counter() < t_end:
            time.sleep(0.01)
        stop.set()
        wall = time.perf_counter() - t0
        for t in threads:
            t.join(timeout=10)
        ctl.close(shutdown_replicas=True)
        router.close()
        tracker.publish()
        observe.flush(kind='snapshot')

        snap1 = observe.snapshot()
        delta = lambda prefix: (counter_sum(snap1, prefix)  # noqa: E731
                                - counter_sum(snap0, prefix))
        accepted = submitted[0]
        completed = stats.ok + stats.errors
        # end-of-LOAD burn (samples past `duration` are teardown decay
        # and would flatter the recovery claim)
        tail = [b for t, b in burn_timeline
                if 0.85 * duration <= t <= duration]
        peak_census = {}
        for _, c in census_timeline:
            for k, v in c.items():
                peak_census[k] = max(peak_census.get(k, 0), v)
        return dict({
            'scenario': tag,
            'duration_s': round(wall, 3),
            'accepted': accepted,
            'completed': completed,
            'lost': accepted - completed,
            'requests_ok': stats.ok,
            'requests_rejected': stats.rejected,
            'requests_errored': stats.errors,
            'no_replica': no_replica[0],
            'latency_ms': percentiles(stats.latencies),
            'warmup_s': round(warmup_s, 3),
            'burn_peak': round(max([b for _, b in burn_timeline]
                                   or [0.0]), 4),
            'burn_end': round(min(tail) if tail else 0.0, 4),
            'burn_timeline': burn_timeline,
            'goodput_end_rps': round(
                sum(g for _, g in goodput_timeline[-6:])
                / max(1, len(goodput_timeline[-6:])), 2),
            'census_timeline': census_timeline[::4],
            'census_peak': peak_census,
            'scale_outs': delta('controller.scale_out_total'),
            'scale_ins': delta('controller.scale_in_total'),
            'heals': delta('controller.heals_total'),
            'deaths': delta('controller.deaths_total'),
            'quarantines': delta('controller.quarantines_total'),
            'spawn_failures':
                delta('controller.spawn_failures_total'),
            'drain_timeouts': delta('controller.drain_timeouts_total'),
            'dispatches': delta('router.dispatch_total'),
            'hedges': delta('router.hedge_total'),
            'hedge_mismatches': delta('router.hedge_mismatch_total'),
            'failovers': delta('router.failover_total'),
        }, **chaos_result)

    prev_sample = os.environ.get('PADDLE_TPU_TRACE_SAMPLE')
    os.environ['PADDLE_TPU_TRACE_SAMPLE'] = str(trace_sample)
    try:
        # 1 — flash crowd: must scale out before the budget burns away
        flash = run_scenario(
            'flash',
            flash_crowd(flash_steady_qps, flash_spike_qps,
                        flash_spike_at,
                        flash_duration - flash_spike_at),
            flash_duration, n_start=2,
            ctl_kw=dict(min_replicas=2, max_replicas=6,
                        interval_s=0.1, burn_high=1.0, queue_high=3.0,
                        scale_out_cooldown_s=0.35, trough_s=1e9,
                        scale_step=2),
            deadline_s=latency_budget_s)

        # 2 — crash loop: repeated kills of ONE slot must quarantine
        def crash_chaos(ctl, t0):
            wait = crash_first_kill_at - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            # the lineage-aware resolver: every kill lands on whatever
            # replacement the controller spawned for slot 'crash2'
            kills = inject.crash_loop(
                lambda: ctl.current('crash2'),
                kills=crash_kills, interval_s=crash_interval_s)
            return {'kills_performed': kills}

        crash = run_scenario(
            'crash', crash_qps, crash_duration, n_start=3,
            ctl_kw=dict(min_replicas=2, max_replicas=4,
                        interval_s=0.1, backoff_base_s=0.05,
                        backoff_max_s=0.4, crash_loop_threshold=2,
                        crash_window_s=10.0, quarantine_s=60.0,
                        trough_s=1e9, scale_out_cooldown_s=1e9),
            chaos=crash_chaos)

        # 3 — diurnal trough: scale-in drains with zero request loss
        trough = run_scenario(
            'trough',
            [(0.0, trough_high_qps), (trough_drop_at, trough_low_qps)],
            trough_duration, n_start=4,
            ctl_kw=dict(min_replicas=2, max_replicas=4,
                        interval_s=0.1, burn_low=0.5, queue_low=1.5,
                        trough_s=0.6, scale_in_cooldown_s=0.5,
                        scale_out_cooldown_s=1e9, queue_high=1e9,
                        burn_high=1e9))
    finally:
        if prev_sample is None:
            os.environ.pop('PADDLE_TPU_TRACE_SAMPLE', None)
        else:
            os.environ['PADDLE_TPU_TRACE_SAMPLE'] = prev_sample

    # the hedging contract across all three scenarios: retry traffic
    # (every dispatch past each request's primary) never exceeded the
    # token budget, and no hedge disagreed with its primary
    accepted = sum(s['accepted'] for s in (flash, crash, trough))
    retry_dispatches = sum(s['dispatches'] - s['accepted']
                           for s in (flash, crash, trough))
    bound = retry_budget * accepted + 3 * retry_budget_burst
    return {
        'workload': 'autoscale',
        'flash_crowd': flash,
        'crash_loop': crash,
        'trough': trough,
        'hedge': {
            'accepted': accepted,
            'hedges': sum(s['hedges'] for s in (flash, crash, trough)),
            'failovers': sum(s['failovers']
                             for s in (flash, crash, trough)),
            'retry_dispatches': retry_dispatches,
            'retry_budget': retry_budget,
            'retry_budget_burst': retry_budget_burst,
            'bound': round(bound, 2),
            'within_budget': retry_dispatches <= bound,
            'mismatches': sum(s['hedge_mismatches']
                              for s in (flash, crash, trough)),
        },
    }


def bench_crosshost(in_dim=8, max_batch=4, max_queue_depth=16,
                    compute_delay_ms=15.0, latency_budget_s=0.2,
                    availability=0.9, window_s=1.5,
                    kill_duration=8.0, kill_qps=18.0, kill_at=2.5,
                    hung_duration=10.0, hung_qps=10.0, stall_at=2.0,
                    crash_duration=12.0, crash_qps=8.0, crash_kills=3,
                    crash_interval_s=2.5, crash_first_kill_at=1.0,
                    heartbeat_timeout_s=0.6, replace_window_s=45.0,
                    spawn_timeout_s=180.0, identity_requests=12,
                    trace_sample=0.05):
    """Cross-host fleet chaos (ISSUE 16): the replica-kill / hung-
    worker / crash-loop scenarios with the fleet split across REAL
    worker processes (serving.rpc.ProcessReplicaFactory spawning
    tools/replica_worker.py), kills delivered as real SIGKILL to live
    PIDs (fault.inject.kill_process). Asserts the tentpole contract
    directly:

    1. **replica kill** — SIGKILL one worker mid-load: zero
       accepted-request loss (router failover resubmits in-flight
       work typed as RemoteReplicaError), only typed error classes
       observed, the victim's /readyz flip seen over plain HTTP, and
       the controller heals the slot (a fresh process).
    2. **hung worker** — SIGSTOP (alive but wedged): the /readyz
       heartbeat timeout declares it dead, the corpse is SIGKILLed +
       reaped, and a replacement is UP within ``replace_window_s``.
    3. **crash loop** — repeated kill_process on one lineage's
       replacements trips the quarantine breaker.
    4. **bit identity** — the same deterministic request stream
       through a subprocess replica and an in-process engine yields
       byte-identical outputs.

    Per-worker metrics JSONLs land beside the parent's sink;
    ``tools/metrics_report.py --fleet <dir>`` renders the merged run
    (per-replica census from child-emitted worker.* gauges)."""
    import signal as _signal
    import threading

    from paddle_tpu import observe
    from paddle_tpu.fault import inject
    from paddle_tpu.inference import create_predictor
    from paddle_tpu.observe.slo import Objective, SloTracker
    from paddle_tpu.serving import (FleetController,
                                    NoReplicaAvailableError,
                                    ProcessReplicaFactory, Router,
                                    ServingEngine)
    from paddle_tpu.serving.loadgen import (Stats, open_loop,
                                            percentiles)

    model_dir = _save_chaos_model(in_dim)
    delay_s = float(compute_delay_ms) / 1000.0

    # the typed vocabulary: every error a chaos run is ALLOWED to
    # surface to a client (anything else is a bug, asserted below)
    typed_errors = {'RemoteReplicaError', 'EngineClosedError',
                    'QueueFullError', 'SLOShedError',
                    'NoReplicaAvailableError', 'TimeoutError'}

    worker_config = {
        'kind': 'serving', 'model_dir': model_dir, 'backend': 'cpu',
        'compute_delay_ms': compute_delay_ms,
        'engine': {'max_batch_size': max_batch,
                   'batch_timeout_ms': 1.0,
                   'max_queue_depth': max_queue_depth}}

    def http_readyz(url, timeout=1.0):
        """GET /readyz over plain HTTP: status code, or None when the
        TCP layer already says dead — the flip a real balancer sees."""
        import http.client
        hostport = url.rstrip('/').split('://', 1)[-1]
        host, _, port = hostport.rpartition(':')
        try:
            conn = http.client.HTTPConnection(host, int(port),
                                              timeout=timeout)
            conn.request('GET', '/readyz')
            resp = conn.getresponse()
            resp.read()
            conn.close()
            return resp.status
        except Exception:
            return None

    def counter_sum(snap, prefix):
        return sum(v for k, v in snap['counters'].items()
                   if k.startswith(prefix))

    def run_scenario(tag, qps, duration, n_start, ctl_kw, chaos=None):
        """One scenario over a fresh SUBPROCESS fleet. Same shape as
        bench_autoscale's runner; every replica here is a PID."""
        snap0 = observe.snapshot()
        factory = ProcessReplicaFactory(
            worker_config, spawn_timeout_s=spawn_timeout_s,
            heartbeat_timeout_s=heartbeat_timeout_s,
            admission_timeout_s=3.0)
        t_w0 = time.perf_counter()
        replicas = [factory.create('%s%d' % (tag, i))
                    for i in range(n_start)]
        warmup_s = time.perf_counter() - t_w0
        tracker = SloTracker([Objective(tag, latency_budget_s,
                                        availability_target=availability,
                                        window_s=window_s)])
        router = Router(replicas, slo=tracker, route=tag, retries=3,
                        hedge=False)
        ctl = FleetController(router, factory, slo=tracker, route=tag,
                              name_prefix='%s-x' % tag, **ctl_kw)
        ctl.start()

        stats = Stats()
        submitted = [0]
        no_replica = [0]
        error_types = set()

        def submit_request(rng):
            rows = int(rng.randint(1, max_batch + 1))
            feed = {'x': rng.rand(rows, in_dim).astype('float32')}
            try:
                fut = router.submit(feed,
                                    session=int(rng.randint(0, 64)))
            except NoReplicaAvailableError:
                no_replica[0] += 1
                return None
            submitted[0] += 1

            def _type_cb(f):
                exc = f.exception()
                if exc is not None:
                    error_types.add(type(exc).__name__)
            fut.add_done_callback(_type_cb)
            return fut, rows

        goodput_timeline, census_timeline = [], []
        t0 = time.perf_counter()
        stop = threading.Event()

        def sampler():
            last_flush = 0.0
            while not stop.wait(0.05):
                now = time.perf_counter()
                t = round(now - t0, 3)
                goodput_timeline.append((t, tracker.goodput(tag, now)))
                census_timeline.append((t, ctl.census()))
                if now - last_flush >= 0.25:
                    last_flush = now
                    observe.flush(kind='snapshot')

        threads = [threading.Thread(target=sampler, daemon=True)]
        chaos_result = {}
        if chaos is not None:
            threads.append(threading.Thread(
                target=lambda: chaos_result.update(
                    chaos(ctl, router, factory, t0)), daemon=True))
        for t in threads:
            t.start()
        open_loop(submit_request, stats, t0 + duration, qps)
        ctl.close()                    # stop ticking before teardown
        for _name, rep in router.replicas():
            rep.shutdown(drain=True)
        t_end = time.perf_counter() + 20.0
        while stats.ok + stats.errors < submitted[0] and \
                time.perf_counter() < t_end:
            time.sleep(0.01)
        stop.set()
        wall = time.perf_counter() - t0
        for t in threads:
            t.join(timeout=15)
        ctl.close(shutdown_replicas=True)
        router.close()
        factory.close()                # no PID outlives the scenario
        tracker.publish()
        observe.flush(kind='snapshot')

        snap1 = observe.snapshot()
        delta = lambda prefix: (counter_sum(snap1, prefix)  # noqa: E731
                                - counter_sum(snap0, prefix))
        accepted = submitted[0]
        completed = stats.ok + stats.errors
        return dict({
            'scenario': tag,
            'duration_s': round(wall, 3),
            'spawn_s': round(warmup_s, 3),
            'accepted': accepted,
            'completed': completed,
            'lost': accepted - completed,
            'requests_ok': stats.ok,
            'requests_rejected': stats.rejected,
            'requests_errored': stats.errors,
            'no_replica': no_replica[0],
            'error_types': sorted(error_types),
            'untyped_errors': sorted(error_types - typed_errors),
            'latency_ms': percentiles(stats.latencies),
            'goodput_end_rps': round(
                sum(g for _, g in goodput_timeline[-6:])
                / max(1, len(goodput_timeline[-6:])), 2),
            'census_timeline': census_timeline[::6],
            'heals': delta('controller.heals_total'),
            'deaths': delta('controller.deaths_total'),
            'quarantines': delta('controller.quarantines_total'),
            'spawn_failures': delta('controller.spawn_failures_total'),
            'failovers': delta('router.failover_total'),
            'process_kills': delta('fault.process_kills_total'),
        }, **chaos_result)

    def wait_replaced(ctl, base, victim, t_from, budget):
        """Block until lineage ``base`` holds a DIFFERENT live replica
        than ``victim`` (the controller declared the death and spawned
        a replacement process); seconds-to-heal or None on timeout."""
        deadline = t_from + budget
        while time.perf_counter() < deadline:
            cur = ctl.current(base)
            if cur is not None and cur is not victim:
                return round(time.perf_counter() - t_from, 3)
            time.sleep(0.05)
        return None

    def kill_chaos(ctl, router, factory, t0):
        wait = kill_at - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        victim = ctl.current('kill0')
        if victim is None:           # slot already churned: any UP one
            live = [r for _n, r in router.replicas() if r.ready()]
            victim = live[0] if live else None
        if victim is None:
            return {'killed_pid': None}
        readyz_before = http_readyz(victim.url)
        pid = inject.kill_process(victim)
        t_kill = time.perf_counter()
        readyz_after = None
        for _ in range(200):         # the HTTP-visible flip
            status = http_readyz(victim.url, timeout=0.25)
            if status != 200:
                readyz_after = status
                break
            time.sleep(0.02)
        healed_in = wait_replaced(ctl, 'kill0', victim, t_kill,
                                  replace_window_s)
        return {'killed_pid': pid,
                'readyz_before': readyz_before,
                'readyz_after': readyz_after,
                'readyz_flipped': (readyz_before == 200
                                   and readyz_after != 200),
                'healed_in_s': healed_in}

    def hung_chaos(ctl, router, factory, t0):
        wait = stall_at - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        victim = ctl.current('hung0')
        if victim is None:
            return {'stalled_pid': None}
        pid = inject.kill_process(victim, sig=_signal.SIGSTOP)
        t_stop = time.perf_counter()
        # the worker is ALIVE (kernel still completes its TCP
        # handshakes) but answers nothing: only the heartbeat timeout
        # can declare it dead
        replaced_in = wait_replaced(ctl, 'hung0', victim, t_stop,
                                    replace_window_s)
        # defence in depth: the controller's reap path SIGKILLs the
        # stopped corpse; if the window elapsed without that, unwedge
        # so no stopped PID outlives the bench
        try:
            os.kill(pid, _signal.SIGKILL)
        except (OSError, TypeError):
            pass
        return {'stalled_pid': pid, 'replaced_in_s': replaced_in,
                'declared_dead_by_heartbeat': replaced_in is not None}

    def crash_chaos(ctl, router, factory, t0):
        wait = crash_first_kill_at - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        kills = 0
        for i in range(crash_kills):
            if i:
                time.sleep(crash_interval_s)
            # lineage-aware: every kill lands on whatever replacement
            # the controller just spawned for slot 'crash1'
            pid = inject.kill_process(lambda: ctl.current('crash1'))
            if pid is not None:
                kills += 1
        # the breaker engaging is a census fact, not a counter: the
        # flapping lineage must land in QUARANTINED
        engaged = False
        deadline = time.perf_counter() + replace_window_s
        while time.perf_counter() < deadline:
            if ctl.census().get('QUARANTINED', 0) >= 1:
                engaged = True
                break
            time.sleep(0.05)
        return {'kills_performed': kills,
                'quarantine_engaged': engaged}

    def identity_leg():
        """Same deterministic request stream through a subprocess
        replica and an in-process engine: outputs must be
        byte-identical."""
        factory = ProcessReplicaFactory(
            worker_config, spawn_timeout_s=spawn_timeout_s,
            heartbeat_timeout_s=heartbeat_timeout_s)
        remote = factory.create('ident0')
        local = ServingEngine(
            _ChaosPredictor(create_predictor(model_dir), delay_s),
            max_batch_size=max_batch, batch_timeout_ms=1.0,
            max_queue_depth=max_queue_depth, name='ident-local')
        local.warmup()
        local.start()
        rng = np.random.RandomState(1234)
        mismatches = 0
        try:
            for i in range(identity_requests):
                rows = (i % max_batch) + 1
                feed = {'x': rng.rand(rows, in_dim).astype('float32')}
                r_out = remote.submit(dict(feed)).result(30)
                l_out = local.submit(dict(feed)).result(30)
                for a, b in zip(r_out, l_out):
                    a, b = np.asarray(a), np.asarray(b)
                    if a.dtype != b.dtype or a.shape != b.shape or \
                            a.tobytes() != b.tobytes():
                        mismatches += 1
        finally:
            local.shutdown(drain=True)
            remote.shutdown(drain=True)
            factory.close()
        return {'requests': identity_requests,
                'mismatches': mismatches,
                'bit_identical': mismatches == 0}

    prev_sample = os.environ.get('PADDLE_TPU_TRACE_SAMPLE')
    os.environ['PADDLE_TPU_TRACE_SAMPLE'] = str(trace_sample)
    try:
        kill = run_scenario(
            'kill', kill_qps, kill_duration, n_start=2,
            ctl_kw=dict(min_replicas=2, max_replicas=3,
                        interval_s=0.1, backoff_base_s=0.05,
                        backoff_max_s=0.4, trough_s=1e9,
                        scale_out_cooldown_s=1e9, queue_high=1e9,
                        burn_high=1e9),
            chaos=kill_chaos)
        hung = run_scenario(
            'hung', hung_qps, hung_duration, n_start=2,
            ctl_kw=dict(min_replicas=2, max_replicas=3,
                        interval_s=0.1, backoff_base_s=0.05,
                        backoff_max_s=0.4, trough_s=1e9,
                        scale_out_cooldown_s=1e9, queue_high=1e9,
                        burn_high=1e9),
            chaos=hung_chaos)
        crash = run_scenario(
            'crash', crash_qps, crash_duration, n_start=2,
            ctl_kw=dict(min_replicas=1, max_replicas=3,
                        interval_s=0.1, backoff_base_s=0.05,
                        backoff_max_s=0.3, crash_loop_threshold=2,
                        crash_window_s=60.0, quarantine_s=120.0,
                        trough_s=1e9, scale_out_cooldown_s=1e9,
                        queue_high=1e9, burn_high=1e9),
            chaos=crash_chaos)
        identity = identity_leg()
    finally:
        if prev_sample is None:
            os.environ.pop('PADDLE_TPU_TRACE_SAMPLE', None)
        else:
            os.environ['PADDLE_TPU_TRACE_SAMPLE'] = prev_sample

    result = {
        'workload': 'crosshost',
        'replica_kill': kill,
        'hung_worker': hung,
        'crash_loop': crash,
        'bit_identity': identity,
    }
    # the tentpole contract, asserted HERE (ISSUE 16 acceptance): a
    # crosshost bench run that returns is a crosshost bench run that
    # held the line
    assert kill['lost'] == 0, 'accepted requests lost: %r' % kill
    assert not kill['untyped_errors'], \
        'untyped errors surfaced: %s' % kill['untyped_errors']
    assert kill.get('killed_pid'), 'chaos never killed a live PID'
    assert kill.get('readyz_flipped'), \
        'readyz flip not observed over HTTP: %r' % kill
    assert kill.get('healed_in_s') is not None, \
        'controller never healed the killed slot: %r' % kill
    assert hung.get('declared_dead_by_heartbeat'), \
        'hung worker not declared dead within %.0fs: %r' \
        % (replace_window_s, hung)
    assert hung['lost'] == 0 and not hung['untyped_errors'], \
        'hung-worker scenario lost/mistyped requests: %r' % hung
    assert crash.get('quarantine_engaged'), \
        'crash loop never tripped quarantine: %r' % crash
    assert identity['bit_identical'], \
        'subprocess vs in-process results diverged: %r' % identity
    return result


def bench_multitenant(in_dim=8, max_batch=8, max_queue_depth=16,
                      compute_delay_ms=8.0, interactive_qps=25.0,
                      batch_quota_rps=10.0, flood_factor=10.0,
                      mix_duration=3.0, quota_rps=8.0, quota_qps=40.0,
                      quota_duration=2.0, inv_batch_new=40,
                      inv_inter_new=8, latency_budget_s=0.002,
                      window_s=1.2, tick_s=0.05, train_batches=10,
                      train_split=3):
    """Multi-tenant fleet chaos (ISSUE 18): four scenarios through the
    serving.tenancy policy layer, the acceptance contract asserted
    inline (a run that returns is a run that held the line):

    1. **noisy neighbor** — an interactive tenant's goodput is first
       measured solo, then again while a batch tenant floods
       ``flood_factor``x its request quota: the token bucket sheds the
       flood at admission, so interactive goodput stays within 10% of
       the solo baseline.
    2. **quota exhaustion** — a tenant offered well past its quota:
       every shed is the typed ``QuotaExceededError`` (never a bare
       queue-full), and the in-quota traffic that WAS admitted loses
       nothing.
    3. **priority inversion** — a decode engine whose KV pool the
       batch class has saturated receives interactive arrivals: pool
       exhaustion preempts only batch sequences (lowest class first),
       interactive preemptions stay zero while every interactive
       request completes.
    4. **co-location** — a background fine-tuning Trainer shares the
       host with serving; SLO-violating traffic drives the burn rate
       past 1 and ``colocation_yield`` yields the trainer within one
       FleetController tick (``tenant_yield`` flight event +
       ``tenant.trainer_yields_total``), calm resumes it, and the
       final params are bit-identical to an uninterrupted run at the
       same step count.

    ``tenant.admitted/shed/preempted/evicted_pages`` land in the
    metrics JSONL; ``tools/metrics_report.py --tenants`` renders the
    per-tenant isolation panel."""
    import threading

    from paddle_tpu import observe
    from paddle_tpu.observe.slo import Objective, SloTracker
    from paddle_tpu.serving import (FleetController, QueueFullError,
                                    NoReplicaAvailableError,
                                    QuotaExceededError, Router,
                                    ServingEngine, TenantRegistry,
                                    colocation_yield,
                                    slo_burn_pressure)
    from paddle_tpu.serving.loadgen import (Stats, open_loop,
                                            percentiles)

    model_dir = _save_chaos_model(in_dim)
    from paddle_tpu.inference import create_predictor

    delay_s = float(compute_delay_ms) / 1000.0

    def make_engine(name):
        pred = _ChaosPredictor(create_predictor(model_dir), delay_s)
        return ServingEngine(pred, max_batch_size=max_batch,
                             batch_timeout_ms=1.0,
                             max_queue_depth=max_queue_depth,
                             name=name)

    def counter_sel(snap, prefix, substr=''):
        return sum(v for k, v in snap['counters'].items()
                   if k.startswith(prefix) and substr in k)

    # ------------------------------------------------- mix harness
    def run_mix(tag, registry, traffic, duration, n_engines=2):
        """Open-loop pacers, one per tenant (``traffic`` is
        ``[(tenant, qps, sessions)]``), through one quota-equipped
        Router. Returns per-tenant admission/goodput ledgers plus the
        tenant.* counter deltas for the window."""
        snap0 = observe.snapshot()
        engines = []
        for i in range(n_engines):
            eng = make_engine('%s%d' % (tag, i))
            eng.warmup()
            eng.start()
            engines.append(eng)
        router = Router(engines, route=tag, tenants=registry)
        t0 = time.perf_counter()
        per, threads = {}, []
        for seed, (name, qps, sessions) in enumerate(traffic):
            led = {'stats': Stats(t0), 'submitted': [0],
                   'typed': [0], 'untyped': [0]}

            def submit_request(rng, name=name, sessions=sessions,
                               led=led):
                feed = {'x': rng.rand(1, in_dim).astype('float32')}
                session = '%s/s%d' % (name,
                                      int(rng.randint(sessions)))
                try:
                    fut = router.submit(feed, session=session)
                except QuotaExceededError:
                    led['typed'][0] += 1
                    return None
                except (QueueFullError, NoReplicaAvailableError):
                    led['untyped'][0] += 1
                    return None
                led['submitted'][0] += 1
                return fut, 1

            per[name] = led
            threads.append(threading.Thread(
                target=open_loop,
                args=(submit_request, led['stats'], t0 + duration,
                      qps),
                kwargs=dict(seed=101 + seed), daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for eng in engines:
            eng.shutdown(drain=True)
        accepted = sum(led['submitted'][0] for led in per.values())
        t_end = time.perf_counter() + 15.0
        while sum(led['stats'].ok + led['stats'].errors
                  for led in per.values()) < accepted and \
                time.perf_counter() < t_end:
            time.sleep(0.01)
        router.close()
        snap1 = observe.snapshot()
        out = {'scenario': tag, 'duration_s': duration, 'tenants': {}}
        for name, led in per.items():
            s = led['stats']
            out['tenants'][name] = {
                'offered': led['submitted'][0] + s.rejected,
                'admitted': led['submitted'][0],
                'ok': s.ok,
                'errors': s.errors,
                'lost': led['submitted'][0] - (s.ok + s.errors),
                'quota_sheds': led['typed'][0],
                'untyped_rejects': led['untyped'][0],
                'goodput_rps': round(s.ok / duration, 2),
                'latency_ms': percentiles(s.latencies),
                'shed_counter': counter_sel(
                    snap1, 'tenant.shed', 'tenant=%s' % name)
                - counter_sel(snap0, 'tenant.shed',
                              'tenant=%s' % name),
            }
        return out

    # 1 — noisy neighbor: batch flood vs interactive goodput
    def mk_registry():
        reg = TenantRegistry()
        reg.add('fg', priority='interactive')
        reg.add('bg', priority='batch', request_rate=batch_quota_rps)
        return reg

    solo = run_mix('nnsolo', mk_registry(),
                   [('fg', interactive_qps, 8)], mix_duration)
    flood_qps = flood_factor * batch_quota_rps
    mixed = run_mix('nnmix', mk_registry(),
                    [('fg', interactive_qps, 8),
                     ('bg', flood_qps, 8)], mix_duration)
    solo_fg = solo['tenants']['fg']
    mix_fg = mixed['tenants']['fg']
    mix_bg = mixed['tenants']['bg']
    isolation = mix_fg['ok'] / float(max(1, solo_fg['ok']))
    noisy = {'solo': solo, 'mixed': mixed,
             'flood_qps': flood_qps,
             'isolation_ratio': round(isolation, 4)}

    # 2 — quota exhaustion: typed sheds, zero loss for admitted work
    reg = TenantRegistry()
    reg.add('acme', priority='standard', request_rate=quota_rps)
    quota = run_mix('quota', reg, [('acme', quota_qps, 4)],
                    quota_duration, n_engines=1)
    acme = quota['tenants']['acme']
    quota['quota_rps'] = quota_rps
    quota['offered_qps'] = quota_qps

    # 3 — priority inversion: batch saturates the KV pool, then
    # interactive arrives; only batch may be preempted
    def run_inversion():
        from paddle_tpu.serving.decode import DecodeEngine, LMSpec
        spec = LMSpec(vocab_size=256, n_layer=1, n_head=2, d_key=8,
                      d_value=8, d_model=16, d_inner=32)
        # 3 batch seqs want 3*ceil((8+inv_batch_new)/4) pages >> 24:
        # exhaustion mid-decode is guaranteed while batch runs
        engine = DecodeEngine(spec, max_batch=4, block_size=4,
                              num_blocks=24, pages_per_seq=16,
                              max_queue_depth=16)
        engine.warmup()
        engine.start()
        before = observe.snapshot()
        rng = np.random.RandomState(5)
        batch_streams = [
            engine.submit(rng.randint(0, 256, 8).tolist(),
                          max_new_tokens=inv_batch_new, seed=i,
                          tenant='bulk', priority='batch')
            for i in range(3)]
        time.sleep(0.25)       # let the batch class occupy the pool
        inter_streams = [
            engine.submit(rng.randint(0, 256, 8).tolist(),
                          max_new_tokens=inv_inter_new, seed=10 + i,
                          tenant='fg', priority='interactive')
            for i in range(2)]
        inter_lens = [len(s.result(timeout=300))
                      for s in inter_streams]
        batch_lens = [len(s.result(timeout=300))
                      for s in batch_streams]
        engine.shutdown(drain=True)
        snap = observe.snapshot()
        sel = lambda substr: (  # noqa: E731
            counter_sel(snap, 'tenant.preempted', substr)
            - counter_sel(before, 'tenant.preempted', substr))
        return {
            'scenario': 'inversion',
            'preempted_batch': sel('priority=batch'),
            'preempted_interactive': sel('priority=interactive'),
            'interactive_tokens': inter_lens,
            'batch_tokens': batch_lens,
        }

    inversion = run_inversion()

    # 4 — co-location: SLO pressure yields the trainer, calm resumes
    # it, params stay bit-identical to the uninterrupted run
    def make_batches():
        rng = np.random.RandomState(3)
        w = rng.randn(4, 1).astype('float32')
        r = np.random.RandomState(4)
        out = []
        for _ in range(train_batches):
            xs = r.randn(8, 4).astype('float32')
            out.append({'x': xs, 'y': xs @ w})
        return out

    def train_run(fluid, reader, hooks=None):
        """One fresh linreg training run; ``hooks(trainer)`` runs
        between construction and train() (the colo leg wires the
        controller there). Returns the final persistables."""
        from paddle_tpu import io as _io

        def train_func():
            x = fluid.layers.data(name='x', shape=[4],
                                  dtype='float32')
            y = fluid.layers.data(name='y', shape=[1],
                                  dtype='float32')
            pred = fluid.layers.fc(input=x, size=1)
            return [fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))]

        trainer = fluid.Trainer(
            train_func=train_func,
            optimizer_func=lambda: fluid.optimizer.SGD(
                learning_rate=0.1),
            place=fluid.CPUPlace())
        done = hooks(trainer) if hooks is not None else None
        trainer.train(num_epochs=1, event_handler=lambda e: None,
                      reader=reader)
        arrays, _ = _io._snapshot_vars(trainer.program,
                                       predicate=_io._is_persistable)
        arrays = {k: np.array(v) for k, v in arrays.items()}
        if done is not None:
            done()
        return arrays

    def run_colocation():
        batches = make_batches()
        base = train_run(_fresh(), lambda: iter(batches))

        gate_hit, gate_go = threading.Event(), threading.Event()

        def gated_reader():
            for i, b in enumerate(batches):
                if i == train_split:
                    gate_hit.set()
                    gate_go.wait(timeout=120)
                yield b

        tracker = SloTracker([Objective(
            'colo', latency_budget_s,
            availability_target=0.5, window_s=window_s)])
        engine = make_engine('colo0')
        engine.warmup()
        engine.start()
        # admission='none': the tracker must SEE every breach (burn is
        # the yield signal here) — SLO admission would shed the chaos
        # burst before it ever recorded a violation
        router = Router([engine], slo=tracker, route='colo',
                        admission='none')
        measured = {}

        fluid = _fresh()

        def hooks(trainer):
            pf, cf = colocation_yield(
                trainer, *slo_burn_pressure(tracker, 'colo'),
                route='colo')
            ctl = FleetController(router, make_engine, slo=tracker,
                                  route='colo', min_replicas=1,
                                  max_replicas=1, interval_s=tick_s,
                                  pressure_fn=pf, calm_fn=cf)
            ctl.start()

            def chaos():
                # trainer is mid-run, parked at the reader gate with
                # the pipeline drained of steps [0, train_split)
                gate_hit.wait(timeout=120)
                # burn the budget: every request breaches the 2ms
                # deadline by construction (8ms compute floor)
                rng = np.random.RandomState(11)
                for _ in range(20):
                    feed = {'x': rng.rand(1, in_dim)
                            .astype('float32')}
                    router.submit(feed, session='fg/s0').result(
                        timeout=30)
                t_flip = time.perf_counter()
                t_dead = t_flip + 5.0
                while time.perf_counter() < t_dead:
                    if observe.get_counter('tenant.trainer_yields_total',
                                           route='colo'):
                        measured['yield_latency_s'] = round(
                            time.perf_counter() - t_flip, 4)
                        break
                    time.sleep(0.002)
                gate_go.set()      # loop resumes, sees the request,
                t_dead = time.perf_counter() + 10.0   # drains, parks
                while time.perf_counter() < t_dead:
                    if trainer.yielded():
                        measured['parked'] = True
                        break
                    time.sleep(0.002)
                # calm: no more traffic — the violation window slides
                # out, burn drops, the controller resumes the trainer
                # (train() returning IS the resume evidence)

            th = threading.Thread(target=chaos, daemon=True)
            th.start()

            def done():
                th.join(timeout=60)
                measured['resumed'] = not trainer.yielded()
                ctl.close(shutdown_replicas=False)
            return done

        colo_params = train_run(fluid, gated_reader, hooks=hooks)
        engine.shutdown(drain=True)
        router.close()
        bit_identical = set(colo_params) == set(base) and all(
            np.array_equal(colo_params[k], base[k]) for k in base)
        return dict({
            'scenario': 'colocation',
            'train_steps': len(batches),
            'tick_s': tick_s,
            'bit_identical': bit_identical,
            'parked': measured.get('parked', False),
            'resumed': measured.get('resumed', False),
            'yield_latency_s': measured.get('yield_latency_s'),
        })

    colo = run_colocation()

    result = {
        'workload': 'multitenant',
        'noisy_neighbor': noisy,
        'quota_exhaustion': quota,
        'priority_inversion': inversion,
        'colocation': colo,
    }
    # the acceptance contract (ISSUE 18), asserted HERE
    assert isolation >= 0.9, \
        'noisy neighbor broke isolation: %r' % noisy
    assert mix_bg['quota_sheds'] > 0, \
        'batch flood was never shed: %r' % mix_bg
    assert acme['quota_sheds'] > 0 and acme['untyped_rejects'] == 0, \
        'over-quota sheds not typed QuotaExceededError: %r' % acme
    assert acme['lost'] == 0 and acme['errors'] == 0, \
        'in-quota traffic lost work: %r' % acme
    assert inversion['preempted_interactive'] == 0, \
        'interactive sequences were preempted: %r' % inversion
    assert inversion['preempted_batch'] > 0, \
        'pool pressure never preempted the batch class: %r' % inversion
    assert all(n == inv_inter_new
               for n in inversion['interactive_tokens']), \
        'interactive decode did not complete: %r' % inversion
    assert colo['yield_latency_s'] is not None and \
        colo['yield_latency_s'] <= tick_s + 0.2, \
        'trainer did not yield within a controller tick: %r' % colo
    assert colo['parked'] and colo['resumed'], \
        'trainer never parked/resumed around pressure: %r' % colo
    assert colo['bit_identical'], \
        'co-located training diverged from the solo run: %r' % colo
    return result


def bench_disagg(duration=5.0, clients=10, n_prefill=1, n_decode=2,
                 vocab=4000, n_layer=4, n_head=4, d_model=128,
                 d_inner=256, max_batch=8, block_size=16,
                 num_blocks=256, pages_per_seq=16,
                 long_prompt_frac=0.35, shared_prefix=0.6,
                 shared_prefix_len=32, ttft_budget_s=3.0,
                 kv_dtype=None, seed=0):
    """Disaggregated-vs-colocated fleet A/B at EQUAL total chip count
    (ISSUE 14's headline). Both legs run the same engines-per-fleet
    count (``n_prefill + n_decode``), the same weights, and the same
    mixed long-prompt/long-decode chaos mix (``loadgen.phase_mix``:
    a minority of prefill-heavy requests stall everything behind them
    on a colocated replica); the disaggregated leg splits the fleet
    into a prefill pool and a decode pool joined by the zero-copy KV
    handoff, the colocated leg serves both phases on every replica.
    Asserted here (and re-asserted by tests/test_handoff.py):

    - **inter-token p99**: disaggregated strictly below colocated —
      decode replicas never run a long prefill, so the inter-token
      tail collapses to the decode-step cadence plus a small suffix
      prefill.
    - **TTFT within budget**: the handoff hop (prefill elsewhere +
      packet install + suffix prefill) keeps p95 TTFT under
      ``ttft_budget_s``.
    - **lost == 0 on both fleets**: every accepted request completes.
    - **zero post-warmup executor cache misses on BOTH fleets**: the
      handoff installs pages between dispatches, the decode side's
      suffix prefill rides a warmed bucket — no new XLA signature on
      either side of the boundary.

    ``kv_dtype='int8'`` shrinks handoff wire bytes 3-4x (per-row
    scales ride in the packet); the returned ``handoff`` ledger
    reports measured bytes/page either way."""
    import threading

    from paddle_tpu import observe
    from paddle_tpu.serving import PhaseRouter, QueueFullError
    from paddle_tpu.serving.decode import (DecodeEngine, LMSpec,
                                           kv_page_bytes,
                                           random_weights)
    from paddle_tpu.serving.loadgen import (Stats, closed_loop,
                                            percentiles, phase_mix)

    d_head = max(8, d_model // n_head)
    spec = LMSpec(vocab_size=vocab, n_layer=n_layer, n_head=n_head,
                  d_key=d_head, d_value=d_head, d_model=d_model,
                  d_inner=d_inner)
    weights = random_weights(spec, seed=11)
    capacity = pages_per_seq * block_size
    # long prompts land in the TOP prefill bucket (a dispatch tens of
    # times a decode step's cost — the stall colocation suffers);
    # leave room for the long-prompt leg's short decode
    long_hi = capacity - 56
    shared_ids = np.random.RandomState(1234).randint(
        0, vocab, shared_prefix_len).tolist()

    def make_engine(name):
        return DecodeEngine(spec, max_batch=max_batch,
                            block_size=block_size,
                            num_blocks=num_blocks,
                            pages_per_seq=pages_per_seq,
                            max_queue_depth=8 * clients,
                            prefix_cache=True, kv_dtype=kv_dtype,
                            weights=weights, name=name)

    def misses(snap):
        return sum(v for k, v in snap['counters'].items()
                   if k.startswith('executor.cache_miss_total'))

    def counter_sum(snap, prefix):
        return sum(v for k, v in snap['counters'].items()
                   if k.startswith(prefix))

    def run_leg(tag, disagg):
        n_pre = n_prefill if disagg else 0
        n_dec = n_decode if disagg else n_prefill + n_decode
        pre = [make_engine('%s-pf%d' % (tag, i)) for i in range(n_pre)]
        dec = [make_engine('%s-dc%d' % (tag, i)) for i in range(n_dec)]
        for e in pre + dec:
            e.warmup()
            e.start()
        router = PhaseRouter(pre, dec, route=tag,
                             colocated=not disagg,
                             max_inflight=4 * clients)
        # the zero-recompile window opens AFTER warmup: anything from
        # here on is a live-traffic signature the invariant forbids
        snap0 = observe.snapshot()
        stats = Stats()
        mu = threading.Lock()
        gaps, ttfts = [], []
        accepted = [0]
        completed = [0]

        def do_request(rng):
            plen, max_new = phase_mix(
                rng, long_prompt_frac=long_prompt_frac,
                long_prompt=(long_hi - 32, long_hi))
            if rng.rand() < shared_prefix:
                tail = max(1, plen - shared_prefix_len)
                prompt = shared_ids + \
                    rng.randint(0, vocab, tail).tolist()
            else:
                prompt = rng.randint(0, vocab, plen).tolist()
            t_sub = time.perf_counter()
            stream = router.submit(prompt, max_new_tokens=max_new,
                                   seed=int(rng.randint(1 << 20)),
                                   session=int(rng.randint(0, 16)))
            with mu:
                accepted[0] += 1
            n, t_prev, local = 0, None, []
            t_first = None
            for _tok in stream:
                now = time.perf_counter()
                if t_first is None:
                    t_first = now
                if t_prev is not None:
                    local.append(now - t_prev)
                t_prev = now
                n += 1
            with mu:
                completed[0] += 1
                gaps.extend(local)
                if t_first is not None:
                    ttfts.append(t_first - t_sub)
            return n

        t0 = time.perf_counter()
        closed_loop(do_request, stats, t0 + duration, clients)
        router.close(shutdown_replicas=True)
        wall = time.perf_counter() - t0
        snap1 = observe.snapshot()
        return {
            'fleet': tag,
            'engines': n_pre + n_dec,
            'prefill_replicas': n_pre,
            'decode_replicas': n_dec,
            'duration_s': round(wall, 3),
            'requests_ok': stats.ok,
            'requests_rejected': stats.rejected,
            'requests_errored': stats.errors,
            'accepted': accepted[0],
            'completed': completed[0],
            'lost': accepted[0] - completed[0],
            'tokens': len(gaps) + len(ttfts),
            'inter_token_ms': percentiles(gaps),
            'ttft_ms': percentiles(ttfts),
            'request_ms': percentiles(stats.latencies),
            'post_warmup_cache_misses': misses(snap1) - misses(snap0),
            'handoffs': counter_sum(snap1, 'handoff.count_total')
            - counter_sum(snap0, 'handoff.count_total'),
            'handoff_pages_installed':
                counter_sum(snap1, 'handoff.pages_installed_total')
                - counter_sum(snap0, 'handoff.pages_installed_total'),
            'handoff_pages_deduped':
                counter_sum(snap1, 'handoff.pages_deduped_total')
                - counter_sum(snap0, 'handoff.pages_deduped_total'),
            'handoff_bytes':
                counter_sum(snap1, 'handoff.bytes_total')
                - counter_sum(snap0, 'handoff.bytes_total'),
            'preemptions':
                counter_sum(snap1, 'decode.preemptions_total')
                - counter_sum(snap0, 'decode.preemptions_total'),
        }

    observe.flush(kind='snapshot')
    coloc = run_leg('coloc', disagg=False)
    observe.flush(kind='snapshot')
    split = run_leg('disagg', disagg=True)
    observe.flush(kind='snapshot')

    p99_coloc = coloc['inter_token_ms'].get('p99')
    p99_disagg = split['inter_token_ms'].get('p99')
    ttft_p95 = split['ttft_ms'].get('p95')
    # the headline contract — each one a hard assertion, not a report
    assert coloc['lost'] == 0 and split['lost'] == 0, \
        'request loss: coloc=%d disagg=%d' % (coloc['lost'],
                                              split['lost'])
    assert coloc['post_warmup_cache_misses'] == 0, \
        'colocated fleet recompiled post-warmup: %d misses' \
        % coloc['post_warmup_cache_misses']
    assert split['post_warmup_cache_misses'] == 0, \
        'disaggregated fleet recompiled post-warmup: %d misses ' \
        '(the handoff must not mint signatures)' \
        % split['post_warmup_cache_misses']
    assert p99_coloc is not None and p99_disagg is not None, \
        'no inter-token samples'
    assert p99_disagg < p99_coloc, \
        'disaggregation did not beat colocated inter-token p99: ' \
        '%.2fms vs %.2fms' % (p99_disagg, p99_coloc)
    assert ttft_p95 is not None and \
        ttft_p95 <= ttft_budget_s * 1000.0, \
        'disagg TTFT p95 %.1fms blew the %.1fms budget' \
        % (ttft_p95 or -1, ttft_budget_s * 1000.0)
    assert split['handoffs'] > 0, 'no handoffs happened'

    from paddle_tpu.quant.core import resolve_kv_dtype
    kv = resolve_kv_dtype(kv_dtype)
    observe.set_gauge('disagg.inter_token_p99_ms', p99_disagg)
    observe.set_gauge('disagg.coloc_inter_token_p99_ms', p99_coloc)
    observe.set_gauge('disagg.ttft_p95_ms', ttft_p95)
    return {
        'workload': 'disagg',
        'colocated': coloc,
        'disaggregated': split,
        'inter_token_p99_improvement': round(p99_coloc / p99_disagg, 3)
        if p99_disagg else None,
        'ttft_budget_s': ttft_budget_s,
        'kv_dtype': kv,
        'page_wire_bytes': kv_page_bytes(spec, block_size, kv),
        'page_wire_bytes_fp32': kv_page_bytes(spec, block_size,
                                              'float32'),
        'traffic': {'clients': clients,
                    'long_prompt_frac': long_prompt_frac,
                    'shared_prefix': shared_prefix,
                    'shared_prefix_len': shared_prefix_len},
    }


def _build_resnet_step(batch, image, train=True):
    """One source of truth for the ResNet bench setup — the headline
    img/s (train=True) and the anatomy profile share it, so the
    anatomy numbers always explain the headline they sit beside."""
    fluid = _fresh()
    from paddle_tpu.models.resnet import resnet50_with_loss
    _, avg_cost, _ = resnet50_with_loss()
    if train:
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(
            avg_cost)
    fluid.default_main_program().amp = 'bf16'
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = _to_device(
        {'image': rng.rand(batch, 3, image, image).astype('float32'),
         'label': rng.randint(0, 1000, (batch, 1)).astype('int64')})
    return exe, feed, avg_cost


def bench_resnet50(batch=64, image=224, iters=20):
    exe, feed, avg_cost = _build_resnet_step(batch, image)

    if not _single_dispatch():
        return batch / _time_multi(exe, feed, [avg_cost], iters)

    def step():
        return exe.run(feed=feed, fetch_list=[avg_cost], return_numpy=False)

    dt = _time_steps(step, iters=iters)
    return batch / dt


def resnet_step_anatomy_phases(batch=64, image=224, iters=10):
    """ResNet-50 step anatomy (VERDICT r3 #2: the bwd gap): fwd-only
    vs full-step wall time on identical shapes, plus the compiled step's
    XLA cost analysis (flops / bytes accessed). detail math: if
    bytes_per_step / step_time approaches the chip's HBM bandwidth
    (~819 GB/s on v5e), the residual bwd gap is a memory-bandwidth
    floor, not a schedulable loss.

    Yields the growing dict once per phase — measured wall times first,
    cost analysis (a third full compile) last — so the caller can print
    the measured times before the cost probe runs."""
    import jax

    out = {'batch': batch}
    # fwd(+loss) only — no backward_marker in the program
    exe, feed, cost = _build_resnet_step(batch, image, train=False)
    out['fwd_ms'] = round(
        _time_multi(exe, feed, [cost], iters) * 1e3, 2)
    # full train step, same shapes
    exe, feed, cost = _build_resnet_step(batch, image, train=True)
    out['step_ms'] = round(
        _time_multi(exe, feed, [cost], iters) * 1e3, 2)
    out['bwd_update_ms'] = round(out['step_ms'] - out['fwd_ms'], 2)
    yield dict(out)

    # XLA cost analysis of the one-step compiled train fn
    try:
        fn, scope_vals, feed_vals = exe.compile_step(
            feed=feed, fetch_list=[cost])
        compiled = jax.jit(fn).lower(scope_vals, feed_vals,
                                     np.int32(0)).compile()
        ca = compiled.cost_analysis()
        flops = float(ca.get('flops', 0.0))
        byts = float(ca.get('bytes accessed', 0.0))
        out['xla_flops_per_step'] = flops
        out['xla_bytes_per_step'] = byts
        if out['step_ms'] > 0:
            out['achieved_tflops'] = round(
                flops / (out['step_ms'] * 1e-3) / 1e12, 1)
            out['achieved_hbm_gbps'] = round(
                byts / (out['step_ms'] * 1e-3) / 1e9, 1)
    except Exception as e:  # cost analysis is best-effort
        out['cost_analysis_error'] = str(e)[:200]
    yield out


def attention_microbench(batch_tokens=4096, d=64, heads=8, inner=8,
                         seqs=(1024, 4096)):
    """Direct fwd+bwd attention timing, XLA reference vs Pallas flash
    kernels, at the shapes the dispatch gate admits (seq >= 512, d_head
    64) — the dated on-chip table VERDICT r3 #8 asks for, isolated from
    the model (whose encoder/cross attention carries key_length and so
    never dispatches Pallas). `inner` grad steps run INSIDE one jitted
    fori_loop with inputs chained through the gradients, so dispatch
    latency is paid once per `inner` steps."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import reference_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    out = {}
    rng = np.random.RandomState(0)
    for seq in seqs:
        batch = max(1, batch_tokens // seq)
        shape = (batch, heads, seq, d)
        q0, k0, v0 = (jnp.asarray(rng.randn(*shape) * 0.1, jnp.bfloat16)
                      for _ in range(3))
        # masked legs (r5): per-example lengths at 75% of seq — the
        # variable-length NMT case; the Pallas kernel skips masked key
        # BLOCKS, so its masked leg should beat its dense one
        lens = jnp.full((batch,), max(1, (3 * seq) // 4), jnp.int32)
        legs = {'xla': lambda q, k, v: reference_attention(
                    q, k, v, causal=True),
                'pallas': lambda q, k, v: flash_attention(
                    q, k, v, causal=True),
                'xla_masked': lambda q, k, v: reference_attention(
                    q, k, v, causal=True, key_length=lens),
                'pallas_masked': lambda q, k, v: flash_attention(
                    q, k, v, causal=True, kv_len=lens)}
        for name, fn in legs.items():
            def loss(q, k, v, fn=fn):
                return fn(q, k, v).astype(jnp.float32).sum()

            grad_fn = jax.value_and_grad(loss, argnums=(0, 1, 2))

            def many(q, k, v, grad_fn=grad_fn):
                def body(_, carry):
                    q, k, v = carry
                    _, (dq, dk, dv) = grad_fn(q, k, v)
                    # chain grads into the inputs: every iteration
                    # depends on the last
                    return (q + 1e-3 * dq, k + 1e-3 * dk, v + 1e-3 * dv)

                return jax.lax.fori_loop(0, inner, body, (q, k, v))

            jmany = jax.jit(many)
            # warm-up compiles; its outputs feed the timed call
            warm = jax.block_until_ready(jmany(q0, k0, v0))
            t0 = time.perf_counter()
            jax.block_until_ready(jmany(*warm))
            dt = (time.perf_counter() - t0) / inner
            out['seq%d_%s_fwdbwd_ms' % (seq, name)] = round(dt * 1e3, 3)
        xla = out['seq%d_xla_fwdbwd_ms' % seq]
        pal = out['seq%d_pallas_fwdbwd_ms' % seq]
        out['seq%d_winner' % seq] = 'pallas' if pal < xla * 0.98 else 'xla'
        xm = out['seq%d_xla_masked_fwdbwd_ms' % seq]
        pm = out['seq%d_pallas_masked_fwdbwd_ms' % seq]
        out['seq%d_masked_winner' % seq] = \
            'pallas' if pm < xm * 0.98 else 'xla'
    return out


def pallas_parity():
    """On-chip numerics of the Pallas kernels vs their XLA reference
    paths (VERDICT r2 weak #4: the kernels had never been parity-checked
    on real hardware). Returns {kernel: max_abs_err}."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                      _reference)
    from paddle_tpu.ops.pallas.layer_norm import (_ln_pallas, _ln_reference)

    rng = np.random.RandomState(0)
    b, h, t, d = 2, 4, 128, 64
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    out = {}
    for causal in (False, True):
        got = np.asarray(jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=causal))(q, k, v))
        want = np.asarray(_reference(q, k, v, causal, d ** -0.5))
        out['flash_causal%d' % causal] = float(np.abs(got - want).max())
    x2 = jnp.asarray(rng.randn(512, 256), jnp.float32)
    gamma = jnp.asarray(rng.rand(256) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(256), jnp.float32)
    got = np.asarray(jax.jit(
        lambda x, g, b: _ln_pallas(x, g, b, 1e-5))(x2, gamma, beta))
    want = np.asarray(_ln_reference(x2, gamma, beta, 1e-5))
    out['layer_norm'] = float(np.abs(got - want).max())
    return out


def bench_autotune(seqs=(1024, 4096), batch_tokens=4096, d=64, heads=8,
                   iters=5):
    """ISSUE 8: tuned vs default-gated attention at seq 1024/4096,
    d_head 64. A fresh tuning table is measured in-process
    (PADDLE_TPU_AUTOTUNE=on), then the tuner's pick is timed against
    the env-gated default (XLA, since PADDLE_TPU_USE_PALLAS is unset).
    `winners_differ` records whether the winner flips between the
    shapes; the table lands at `table_path` for tools/tuning_inspect.py."""
    import tempfile
    import jax
    import jax.numpy as jnp
    from paddle_tpu import observe, tuning
    from paddle_tpu.ops.attention_ops import reference_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    out = {}
    tmp = tempfile.mkdtemp(prefix='paddle_tpu_autotune_')
    table_path = os.path.join(tmp, 'tuning.json')
    os.environ['PADDLE_TPU_TUNING_TABLE'] = table_path
    os.environ['PADDLE_TPU_AUTOTUNE'] = 'on'
    tuning.reset()
    rng = np.random.RandomState(0)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))        # compile + warm
        best = float('inf')
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    winners = []
    for seq in seqs:
        batch = max(1, batch_tokens // seq)
        shape = (batch, heads, seq, d)
        q, k, v = (jnp.asarray(rng.randn(*shape) * 0.1, jnp.bfloat16)
                   for _ in range(3))
        default_fn = jax.jit(
            lambda q, k, v: reference_attention(q, k, v, causal=True))
        picked = tuning.decide_attention(batch, heads, seq, seq, d,
                                         'bfloat16', True, False) or \
            {'impl': 'xla'}
        if picked.get('impl') == 'pallas':
            bq, bk = picked.get('block_q'), picked.get('block_k')
            tuned_fn = jax.jit(
                lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk))
        else:
            tuned_fn = default_fn
        d_ms = timed(default_fn, q, k, v) * 1e3
        t_ms = timed(tuned_fn, q, k, v) * 1e3
        out['seq%d_default_ms' % seq] = round(d_ms, 3)
        out['seq%d_tuned_ms' % seq] = round(t_ms, 3)
        out['seq%d_winner' % seq] = picked.get('impl')
        winners.append(picked.get('impl'))
        observe.set_gauge('tuning.bench_speedup', d_ms / max(t_ms, 1e-9),
                          seq=seq)
    out['winners_differ'] = len(set(winners)) > 1
    out['table_entries'] = tuning.current_table().size()
    out['table_path'] = table_path
    return out


def bench_verify(batch=8, seq=64, vocab=32000, iters=10):
    """ISSUE 9 overhead guard: the static verifier must stay noise next
    to the cold compile it precedes. Builds the transformer train
    program, times a full run of every analysis pass (best of `iters`
    — the verifier is pure Python over the op list), then times the
    COLD compile+first-step of the same program, and reports the
    ratio. Gauges analysis.verify_seconds /
    analysis.verify_vs_compile_ratio land in the metrics JSONL; `ok`
    is the acceptance bit (ratio < 1%)."""
    fluid = _fresh()
    from paddle_tpu import analysis, observe
    from paddle_tpu.models import transformer as T
    avg_cost, _ = T.transformer_base(
        src_vocab_size=vocab, trg_vocab_size=vocab,
        src_seq_len=seq, trg_seq_len=seq, max_length=max(256, seq))
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    prog = fluid.default_main_program()

    best = float('inf')
    diags = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        diags = analysis.run_passes(prog, fetch_names=[avg_cost.name])
        best = min(best, time.perf_counter() - t0)

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    feed = _to_device(T.make_fake_batch(batch, seq, seq, vocab, vocab))
    t0 = time.perf_counter()
    out = exe.run(feed=feed, fetch_list=[avg_cost])
    np.asarray(out[0])
    cold = time.perf_counter() - t0

    ratio = best / cold if cold > 0 else float('inf')
    observe.set_gauge('analysis.verify_seconds', best)
    observe.set_gauge('analysis.verify_vs_compile_ratio', ratio)
    counts = analysis.summarize(diags)
    return {'verify_seconds': round(best, 6),
            'cold_compile_seconds': round(cold, 4),
            'verify_vs_compile_ratio': round(ratio, 6),
            'ops': len(prog.global_block().ops),
            'diagnostics': counts,
            'ok': bool(ratio < 0.01 and counts['error'] == 0)}


def bench_linalg(n_parity=256, tune_points=((512, 2048, 512),
                                            (256, 4096, 256)),
                 n_fact=256, n_pow=1024, powit_iters=40, runs=5):
    """Distributed linear algebra at pod scale (ISSUE 15), four
    asserted legs over the dp x tp mesh:

    1. **SUMMA parity + zero recompiles** — blocked matmul matches
       numpy at the parity shape; after the first (compiling) run,
       `runs` more dispatches hit the executor cache with ZERO misses.
    2. **autotuned panel** — PADDLE_TPU_AUTOTUNE=record sweeps the
       legal panel ladder at each (N, K, M) tuning point; asserts the
       recorded winner STRICTLY beats the default panel's measured
       time on at least one point (the r4 lesson: no single panel is
       right for every shape), then asserts the memory contract —
       per-shard peak arena bytes within 1.5x of the O(N^2/P) ideal —
       at the LARGEST SUMMA shape with its default panel.
    3. **blocked Cholesky / QR** — factorization residuals
       (reconstruction, orthogonality, triangularity) at n_fact on a
       1-D dp mesh.
    4. **power iteration** — dominant eigenvalue matches numpy to
       rel-err < 1e-3 through exact psum and < 5e-2 through the PR 13
       quantized allreduce, with the analytic wire-bytes compression
       >= 3x reported from the linalg.powit_* gauges. The reduction IS
       the step here, which is what makes this the second measurement
       axis for the compressed-collective trade.
    """
    import jax

    from paddle_tpu import linalg, observe, tuning
    from paddle_tpu.core.executor import Executor
    from paddle_tpu.parallel.mesh import make_mesh

    count = jax.device_count()
    dp = 2 if count >= 2 else 1
    tp = max(1, min(4, count // dp))
    while tp > 1 and count < dp * tp:
        tp //= 2
    grid = make_mesh(dp=dp, tp=tp)
    dp1 = 1
    while dp1 * 2 <= min(8, count):
        dp1 *= 2
    line = make_mesh(dp=dp1)
    out = {'workload': 'linalg', 'grid': {'dp': dp, 'tp': tp},
           'line_dp': dp1}
    rng = np.random.RandomState(0)

    # ---- leg 1: SUMMA parity + zero recompiles ---------------------
    n = n_parity
    a = rng.randn(n, n).astype('float32')
    b = rng.randn(n, n).astype('float32')
    exe = Executor()
    prog, c_var = linalg.build_matmul_program(n, n, n, mesh=grid,
                                             panel=32)
    t0 = time.perf_counter()
    got = exe.run(prog, feed={'summa_x': a, 'summa_y': b},
                  fetch_list=[c_var])[0]
    first = time.perf_counter() - t0
    ref = a.astype('float64') @ b.astype('float64')
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    assert rel < 1e-4, 'SUMMA parity rel err %.2e' % rel
    snap = observe.snapshot()
    miss0 = sum(v for k, v in snap.get('counters', {}).items()
                if k.startswith('executor.cache_miss_total'))
    best = float('inf')
    for _ in range(runs):
        t0 = time.perf_counter()
        np.asarray(exe.run(prog, feed={'summa_x': a, 'summa_y': b},
                           fetch_list=[c_var])[0])
        best = min(best, time.perf_counter() - t0)
        assert not exe.last_cache_miss, \
            'SUMMA warm dispatch missed the compile cache'
    snap = observe.snapshot()
    miss1 = sum(v for k, v in snap.get('counters', {}).items()
                if k.startswith('executor.cache_miss_total'))
    assert miss1 == miss0, 'cache misses after warmup: %d' \
        % (miss1 - miss0)
    gf = 2.0 * n * n * n / best / 1e9
    out['summa'] = {'n': n, 'rel_err': rel,
                    'first_dispatch_s': round(first, 4),
                    'warm_step_s': round(best, 5),
                    'gflops': round(gf, 2),
                    'cache_misses_after_warmup': 0}
    observe.set_gauge('linalg.bench_summa_gflops', gf)

    # ---- leg 2: autotuned panel vs default + memory contract -------
    tune_dir = os.environ.get('TMPDIR', '/tmp')
    table_path = os.path.join(tune_dir, 'bench_linalg_tuning_%d.json'
                              % os.getpid())
    saved = {k: os.environ.get(k) for k in ('PADDLE_TPU_AUTOTUNE',
                                            'PADDLE_TPU_TUNING_TABLE')}
    os.environ['PADDLE_TPU_AUTOTUNE'] = 'record'
    os.environ['PADDLE_TPU_TUNING_TABLE'] = table_path
    tuning.reset()
    try:
        points = []
        beats = 0
        for (pn, pk, pm) in tune_points:
            win = tuning.decide_summa_panel(pn, pk, pm, 'float32', grid)
            default = linalg.default_panel(pk, dp, tp, n=pn, m=pm)
            key = ('summa_matmul|n%d k%d m%d|dp%d tp%d|float32'
                   % (pn, pk, pm, dp, tp))
            ent = tuning.current_table().lookup(tuning.device_kind(),
                                                key)
            timings = {k: v for k, v in ent['timings'].items()
                       if v >= 0}
            def_label = 'summa panel%d' % default
            win_label = 'summa panel%d' % int(win['panel'])
            t_def = timings.get(def_label)
            t_win = timings.get(win_label)
            strictly = (win['panel'] != default and t_def is not None
                        and t_win is not None and t_win < t_def)
            beats += bool(strictly)
            points.append({
                'shape': [pn, pk, pm], 'default_panel': default,
                'tuned_panel': int(win['panel']),
                'default_ms': round(t_def * 1e3, 3) if t_def else None,
                'tuned_ms': round(t_win * 1e3, 3) if t_win else None,
                'tuned_beats_default': strictly})
        assert beats >= 1, \
            'autotuned panel never beat the default: %r' % points
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        tuning.reset()
    # memory contract at the LARGEST SUMMA shape, default panel
    big = max(tune_points, key=lambda d: d[0] * d[1] + d[1] * d[2])
    model = linalg.assert_memory_contract(
        'summa_matmul', grid, big, panel=linalg.default_panel(
            big[1], dp, tp, n=big[0], m=big[2]), factor=1.5)
    observe.set_gauge('linalg.bench_memory_factor', model['factor'])
    out['autotune'] = {'points': points, 'tuned_beats_default': beats}
    out['memory'] = {'shape': list(big), 'per_shard_peak': model['peak'],
                     'ideal': model['ideal'],
                     'factor': round(model['factor'], 3),
                     'participants': model['participants']}

    # ---- leg 3: blocked Cholesky / QR residuals --------------------
    nf = n_fact
    m0 = rng.randn(nf, nf).astype('float32')
    spd = (m0 @ m0.T + nf * np.eye(nf)).astype('float32')
    exe3 = Executor()
    l = np.asarray(linalg.cholesky(spd, mesh=line, executor=exe3))
    chol_res = float(np.abs(l @ l.T - spd).max() / np.abs(spd).max())
    assert chol_res < 1e-5, 'cholesky residual %.2e' % chol_res
    assert float(np.abs(np.triu(l, 1)).max()) == 0.0

    tall = rng.randn(nf * 2, nf).astype('float32')
    q, r = linalg.qr(tall, mesh=line, executor=exe3)
    q, r = np.asarray(q), np.asarray(r)
    orth = float(np.abs(q.T @ q - np.eye(nf)).max())
    recon = float(np.abs(q @ r - tall).max() / np.abs(tall).max())
    assert orth < 1e-4, 'QR orthogonality %.2e' % orth
    assert recon < 1e-4, 'QR reconstruction %.2e' % recon
    out['factorizations'] = {
        'n': nf, 'dp': dp1,
        'cholesky_residual': chol_res,
        'qr_orthogonality': orth, 'qr_reconstruction': recon}

    # ---- leg 4: power iteration, exact vs quantized reduction ------
    npow = n_pow
    qo, _ = np.linalg.qr(rng.randn(npow, npow))
    spectrum = np.concatenate([[10.0, 6.0],
                               np.linspace(1.0, 2.0, npow - 2)])
    sym = ((qo * spectrum) @ qo.T).astype('float32')
    sym = (sym + sym.T) / 2
    dom = np.linalg.eigvalsh(sym)
    dom = float(dom[np.abs(dom).argmax()])
    exe4 = Executor()
    lam, _ = linalg.power_iteration(sym, iters=powit_iters, mesh=line,
                                    executor=exe4)
    assert not exe4.last_cache_miss, \
        'power_iteration re-compiled inside the loop'
    rel_exact = abs(lam - dom) / abs(dom)
    assert rel_exact < 1e-3, \
        'power iteration (psum) rel err %.2e' % rel_exact
    lam_q, _ = linalg.power_iteration(sym, iters=powit_iters,
                                      mesh=line, quantized=True,
                                      executor=exe4)
    rel_quant = abs(lam_q - dom) / abs(dom)
    assert rel_quant < 5e-2, \
        'power iteration (quantized) rel err %.2e' % rel_quant
    g = observe.snapshot().get('gauges', {})
    compression = g.get('linalg.powit_compression', 0.0)
    if dp1 > 1:
        assert compression >= 3.0, \
            'quantized reduction compression %.2fx < 3x' % compression
    out['power_iteration'] = {
        'n': npow, 'iters': powit_iters, 'numpy_eigval': dom,
        'exact': {'eigval': lam, 'rel_err': rel_exact},
        'quantized': {'eigval': lam_q, 'rel_err': rel_quant,
                      'compression_x': round(compression, 2),
                      'bytes_fp32': g.get('linalg.powit_bytes_fp32'),
                      'bytes_quant': g.get('linalg.powit_bytes_quant')},
    }
    observe.set_gauge('linalg.bench_powit_rel_err_exact', rel_exact)
    observe.set_gauge('linalg.bench_powit_rel_err_quant', rel_quant)
    out['ok'] = True
    return out


def _run_workload_child(workload):
    """Run ONE workload in this process. Prints 'DEVICE <json>' first,
    then 'RESULT <number>' or 'RESULT_JSON <json>'. Which device it may
    use is TPUPlace's rule (core/place.py): a TPU, or the host CPU when
    JAX_PLATFORMS=cpu asked for it by name."""
    import jax
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'device_kind': dev.device_kind,
              'device_count': jax.device_count()}
    print('DEVICE %s' % json.dumps(device), flush=True)
    from paddle_tpu import observe
    # The XLA cost probe (~doubles each compile) stays off by default
    # here: bench computes its MFU analytically, and
    # executor.first_dispatch_seconds still records per-key compile
    # wall for free. Opt back in with PADDLE_TPU_OBSERVE_COST=1.
    os.environ.setdefault('PADDLE_TPU_OBSERVE_COST', '0')
    observe.enable(jsonl=os.environ.get('PADDLE_TPU_METRICS_JSONL'),
                   trace=os.environ.get('PADDLE_TPU_TRACE_JSON'))

    result = WORKLOADS[workload]()
    # resnet50_anatomy yields once per phase: the wall-time split prints
    # before the best-effort cost analysis (a reader keeps the LAST line)
    for value in (result if hasattr(result, '__next__') else [result]):
        if isinstance(value, dict):
            print('RESULT_JSON %s' % json.dumps(value), flush=True)
        else:
            print('RESULT %r' % value, flush=True)


# Every workload --workload accepts: name -> a call with the full-size
# arguments. Rates come back as numbers, everything else as a dict.
WORKLOADS = {
    'transformer': bench_transformer,
    # long-sequence configs hold 4096 tokens/step like the base config,
    # so the tok/s numbers are comparable (SURVEY §7.10)
    'transformer_seq256': lambda: bench_transformer(batch=16, seq=256),
    # seq >= 512, d_head 64 AND dropout 0: the shapes where the
    # flash-attention gate dispatches (attention-output dropout would
    # block the kernel)
    'transformer_seq1024': lambda: bench_transformer(
        dropout=0.0, batch=4, seq=1024, iters=10),
    'transformer_seq4096': lambda: bench_transformer(
        dropout=0.0, batch=1, seq=4096, iters=8),
    # the reference benchmark suite's other NMT config (d_model 1024 /
    # 16 heads / d_inner 4096), canonical dropout 0.3
    'transformer_big': lambda: bench_transformer(
        big=True, batch=32, seq=64, iters=10),
    'transformer_seq512_masked': bench_transformer_masked,
    'rnn_lstm': bench_rnn_lstm,
    'resnet50': bench_resnet50,
    'resnet50_anatomy': resnet_step_anatomy_phases,
    'attention_microbench': attention_microbench,
    'pallas_parity': pallas_parity,
    'moe_cap1.0': lambda: bench_moe(capacity_factor=1.0),
    'moe_cap1.25': lambda: bench_moe(capacity_factor=1.25),
    'moe_cap2.0': lambda: bench_moe(capacity_factor=2.0),
    'pipeline_transformer': lambda: bench_pipeline_ablation('transformer'),
    'pipeline_resnet50': lambda: bench_pipeline_ablation('resnet50'),
    'decode_transformer': bench_decode,
    'fleet': bench_fleet,
    'autoscale': bench_autoscale,
    'quant': bench_quant,
    'disagg': bench_disagg,
    'linalg': bench_linalg,
    'autotune': bench_autotune,
    'verify': bench_verify,
    'crosshost': bench_crosshost,
    'multitenant': bench_multitenant,
    'trainspeed': bench_trainspeed,
}
WORKLOAD_CHOICES = list(WORKLOADS)


HEADLINE_WORKLOADS = ('transformer', 'resnet50')


def _run_headline(workload):
    """One headline workload in its own process (this one never imports
    jax, so the child gets the chip). Returns (value, device); exits
    non-zero when the child fails, prints no result, or ran on
    anything but a TPU."""
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), '--workload',
         workload],
        stdout=subprocess.PIPE, text=True)      # stderr passes through
    value = device = None
    for line in r.stdout.splitlines():
        if line.startswith('DEVICE '):
            device = json.loads(line[len('DEVICE '):])
        elif line.startswith('RESULT '):
            value = float(line[len('RESULT '):])
    if r.returncode != 0 or value is None or device is None:
        sys.exit('bench: workload %r failed (exit %d)'
                 % (workload, r.returncode))
    if device['platform'] != 'tpu':
        sys.exit('bench: workload %r ran on platform %r, not a TPU'
                 % (workload, device['platform']))
    return value, device


def main():
    # The headline is a chip number. Asked for by name, the host CPU
    # would run every workload to the end (TPUPlace's rule), so refuse
    # here, before any child starts. With the platform unset or 'tpu'
    # and no TPU, the child fails at Executor(TPUPlace(0)) or earlier.
    asked = os.environ.get('JAX_PLATFORMS', '').split(',')[0].strip()
    if asked == 'cpu':
        sys.exit('bench: needs a TPU and JAX_PLATFORMS asks for the '
                 'cpu. Nothing was run.')
    values, device = {}, None
    for workload in HEADLINE_WORKLOADS:
        values[workload], dev = _run_headline(workload)
        if device is not None and dev != device:
            sys.exit('bench: device changed between workloads: %s then '
                     '%s' % (device, dev))
        device = dev
    tok_s, img_s = values['transformer'], values['resnet50']
    speedup = ((tok_s / BASE_TRANSFORMER_TOK_S) *
               (img_s / BASE_RESNET_IMG_S)) ** 0.5
    detail = dict(device)
    detail.update({
        'transformer_tok_per_sec': round(tok_s, 1),
        'resnet50_img_per_sec': round(img_s, 1),
        'baseline': {'resnet50': BASE_RESNET_IMG_S,
                     'transformer': BASE_TRANSFORMER_TOK_S}})
    print(json.dumps({
        'metric': 'transformer_base_train_tokens_per_sec',
        'value': round(tok_s, 1),
        'unit': 'tokens/s',
        'vs_baseline': round(speedup, 3),
        'detail': detail,
    }))


if __name__ == '__main__':
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    p.add_argument('--workload', choices=WORKLOAD_CHOICES,
                   help='run one workload in this process')
    a = p.parse_args()
    if a.workload:
        _run_workload_child(a.workload)
    else:
        main()
