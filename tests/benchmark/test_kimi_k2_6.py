"""The ``kimi_k2_6`` configuration and its cell, off the chip: the file
holds the published config with the cut beside it, its parameters add up
to the stated cut, the runner builds the block it describes, the
sessions' schedule is a pure function that shares the heads it says, the
shape function and the reader this PR brings do their arithmetic, the
benchmark's copy of the plain reference is the repository's, and the
cell rehearses end to end on the CPU. No test here describes a TPU
topology."""

import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, sessions    # noqa: E402
from benchmark import run as bench          # noqa: E402

MANIFEST = manifest.load(REPO)
CELL = 'kimi_k2_6.doc_qa_sessions'
BENCH = os.path.join(REPO, 'benchmark')
FULL = 'full_attention'

# config.json of moonshotai/Kimi-K2.6, every number of it, as published
# (the catalog row beside the model-configs guide)
PUBLISHED = {
    'hidden_size': 7168, 'intermediate_size': 18432,
    'moe_intermediate_size': 2048, 'num_attention_heads': 64,
    'num_key_value_heads': 64, 'q_lora_rank': 1536, 'kv_lora_rank': 512,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'v_head_dim': 128,
    'rope_theta': 50000, 'num_experts_per_tok': 8, 'n_shared_experts': 1,
    'first_k_dense_replace': 1, 'moe_layer_freq': 1, 'n_group': 1,
    'topk_group': 1, 'routed_scaling_factor': 2.827, 'rms_norm_eps': 1e-05,
    'max_position_embeddings': 262144, 'ep_size': 1,
    'num_nextn_predict_layers': 0}
STATED = {
    'attention_bias': False, 'hidden_act': 'silu', 'model_type': 'kimi_k2',
    'norm_topk_prob': True, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_method': 'noaux_tc',
    'rope_scaling': {'beta_fast': 32, 'beta_slow': 1, 'factor': 64,
                     'mscale': 1, 'mscale_all_dim': 1,
                     'original_max_position_embeddings': 4096,
                     'type': 'yarn'}}
CUT = {'num_hidden_layers': (6, 61), 'n_routed_experts': (12, 384),
       'vocab_size': (20480, 163840)}
# the entries that carry this configuration's shapes or its prefix cache
OWN_METRICS = {
    'serve.mla_attn_busy_share', 'serve.mla_decode_attn_roofline_share',
    'serve.mla_prefill_attn_mxu_share', 'serve.mla_moe_ffn_busy_share',
    'serve.prefix_tokens_reused_share', 'serve.prefix_hit_ttft_ms',
    'serve.prefix_miss_ttft_ms', 'serve.prefix_evicted_pages'}
# the shared readers, the one entry a reader over the serving cells, and
# what its engine fed and ISSUE 36's cap of sixteen entries left unread
SHARED_METRICS = {
    'serve.moe_local_assignment_pct', 'serve.moe_load_max_over_mean',
    'serve.prefill_chunk_ms', 'serve.decode_step_ms', 'serve.queue_wait_ms',
    'serve.worker_prefill_share', 'serve.kv_pool_used_pct',
    'serve.recompiles', 'serve.mla_prefill_expanded_chunk_share',
    'serve.worker_step_share', 'serve.worker_idle_share',
    'serve.batch_occupancy', 'serve.step_build_ms',
    'serve.step_dispatch_ms', 'serve.step_fetch_ms',
    'serve.steps_ahead_share', 'serve.prefill_chunks_per_prompt',
    'serve.attn_pages_read_share', 'serve.attn_pages_held_share',
    'serve.ttft_p90_ms', 'serve.itl_p95_ms', 'serve.tokens_per_s',
    'serve.gap_under_prefill_ms', 'serve.idle_in_device_empty_pct'}


def _module(kind, name):
    return manifest.load_module(os.path.join(BENCH, kind, name + '.py'))


@pytest.fixture(scope='module')
def resolved():
    return manifest.resolve(MANIFEST, CELL)


# ------------------------------------------------------- the files
def shape_the_cell_resolves_to_files_by_name(m):
    assert manifest.problems(m) == []
    r = manifest.resolve(m, CELL)
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_sessions'
    assert r['cell']['chips'] == 1 and r['cell']['traffic'] == \
        'doc_qa_sessions'
    assert r['config']['reference'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    (entry,) = [c for c in m['configs'] if c['name'] == 'kimi_k2_6']
    assert entry['reduced'] == r['config']['reduced'] == list(CUT)
    assert len(entry['source']) <= 200 and len(r['cell']['why']) <= 200
    assert entry['source'].startswith(r['config']['source'])


def shape_the_cell_reports_its_metrics_and_the_two_end_to_end(m):
    """The cell reports each per-layer metric named here and the two
    end-to-end metrics under the bounds they have. Membership only: a
    later cell may join these lists."""
    resolved = manifest.resolve(m, CELL)
    mine = {p['entry']['name'] for p in resolved['per_layer']}
    assert mine >= OWN_METRICS | SHARED_METRICS
    for metric in m['per_layer']:
        if metric['name'].startswith('serve.mla_') and \
                metric['name'] in OWN_METRICS:
            assert all(cell.startswith('kimi_k2_6.')
                       for cell in metric['workloads'])
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in m['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e
    # no share of a roofline or of a peak can be joined: none is open
    for metric in m['per_layer']:
        if 'roofline' in metric['name'] or 'mfu' in metric['name']:
            assert 'workloads' in metric


def test_the_cell_resolves_to_files_by_name():
    shape_the_cell_resolves_to_files_by_name(MANIFEST)


def test_the_cell_reports_its_metrics_and_the_two_end_to_end():
    shape_the_cell_reports_its_metrics_and_the_two_end_to_end(MANIFEST)


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_config_holds_the_published_value(resolved, key):
    assert resolved['config'][key] == PUBLISHED[key]


@pytest.mark.parametrize('key', sorted(STATED))
def test_config_holds_the_published_setting(resolved, key):
    assert resolved['config'][key] == STATED[key]


@pytest.mark.parametrize('key', sorted(CUT))
def test_config_states_each_cut_beside_the_published_value(resolved, key):
    config = resolved['config']
    held, published = CUT[key]
    assert config[key] == held and config['published'][key] == published
    assert key in config['reduced']


def test_config_is_the_catalog_row_but_for_the_cut(resolved):
    """Where the catalog is installed: every key of its ``config`` is in
    the file under the same name with the same value, but the three that
    are cut."""
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.isfile(catalog):
        pytest.skip('no catalog here')
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r['name'] == 'Kimi-K2.6']
    config = resolved['config']
    assert config['source'] == row['source_url']
    differs = {k for k, v in row['config'].items() if config.get(k) != v}
    assert differs == set(CUT)


def test_config_states_the_deployment_and_what_it_assumes(resolved):
    config = resolved['config']
    assert '32' in config['deployment'] and config['first_expert'] == 0
    # the guide's floors for a cut: the dense layer and four expert
    # layers, 8 experts, 1/8 of the vocabulary
    assert config['num_hidden_layers'] >= \
        config['first_k_dense_replace'] + 4
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= config['published']['vocab_size']
    for word in ('scope', 'block', 'attention', 'rotary', 'router',
                 'weights', 'precision', 'geometry', 'sampling'):
        assert len(config['assumed'][word]) > 40 or word == 'sampling'
    geometry = config['engine']
    assert geometry['pages_per_seq'] * geometry['block_size'] >= \
        geometry['max_prompt_len'] + 256
    assert geometry['prefix_cache'] is True and geometry['spec_k'] == 0
    limits = config['reference']
    assert limits['long_requests'] >= 1 and limits['shared_requests'] >= 2
    assert limits['long_tokens'] == 16384 and limits['requests'] == 5


def test_parameters_add_up_to_the_stated_cut(resolved):
    """ISSUE 36's arithmetic, recounted from the program's own parameter
    table: attention 101.1 M a layer, a routed expert 44.04 M and 12 of
    them 528.5 M, an expert layer 676.4 M, the dense layer 497.5 M,
    embedding + head 293.6 M: 4.173 B, 8.35 GB in bfloat16."""
    from paddle_tpu.serving.decode.model import block_param_shapes
    spec = _module('runners', 'serve_sessions').spec_of(resolved['config'])
    shapes = block_param_shapes(spec)

    def millions(*prefixes):
        return sum(int(np.prod(shape)) for name, (shape, _, _) in
                   shapes.items() if name.startswith(prefixes)) / 1e6
    n_layer, n_moe = 6, 5
    attention = millions('lm_full_') / n_layer
    assert round(attention, 1) == 101.1
    np.testing.assert_allclose(
        [7168 * 1536 / 1e6, 1536 * 64 * 192 / 1e6, 7168 * 576 / 1e6,
         2 * 64 * 128 * 512 / 1e6, 64 * 128 * 7168 / 1e6],
        [11.01, 18.87, 4.13, 8.39, 58.72], atol=0.005)
    assert round(millions('lm_moe_shr_') / n_moe, 1) == 44.0
    assert round(millions('lm_moe_router') / n_moe, 2) == 2.75
    routed = millions('lm_moe_exp_') / n_moe
    assert round(routed / 12, 2) == 44.04 and round(routed, 1) == 528.5
    assert round(attention + millions('lm_moe_') / n_moe, 1) == 676.4
    assert round(attention + millions('lm_dense_'), 1) == 497.5
    assert round(millions('lm_emb', 'lm_head'), 1) == 293.6
    total = sum(int(np.prod(shape)) for shape, _, _ in shapes.values())
    assert round(total / 1e9, 3) == 4.173
    assert round(total * 2 / 1e9, 2) == 8.35
    assert 'lm_full_gate.w' not in shapes and \
        not [n for n in shapes if 'idx' in n]
    # the cache: one kind, 576 values a layer stored 640, 7,680 B a token
    from paddle_tpu.serving.decode.model import (arena_bytes,
                                                 kv_bytes_per_token)
    assert kv_bytes_per_token(spec, 'bfloat16') == 7680
    geometry = resolved['config']['engine']
    assert round(arena_bytes(spec, geometry['num_blocks'],
                             geometry['block_size'], 'bfloat16') / 1e9,
                 2) == round(geometry['num_blocks'] * 32 * 7680 / 1e9, 2)


# ------------------------------------------------------ the runner
def test_runner_builds_the_block_the_config_describes(resolved):
    runner = _module('runners', 'serve_sessions')
    spec = runner.spec_of(resolved['config'])
    assert (spec.block, spec.n_layer, spec.d_model, spec.d_inner,
            spec.d_inner_dense, spec.dense_layers) == \
        ('latent_moe', 6, 7168, 2048, 18432, 1)
    assert spec.layer_types == (FULL,) * 6
    assert spec.layer_plan() == ((FULL,), (FULL,), 5, ())
    shape = spec.latent[FULL]
    assert (shape.n_head, shape.q_rank, shape.kv_rank, shape.d_nope,
            shape.d_rope, shape.d_v, shape.rope_theta) == \
        (64, 1536, 512, 128, 64, 128, 50000.0)
    assert shape.yarn_range() == (8, 20)
    assert round(shape.softmax_multiplier(), 4) == 2.0047
    assert (spec.index_topk, spec.lora_rescale, spec.attn_gate,
            spec.routed_scale) == (0, False, False, 2.827)
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.experts_per_token, spec.n_shared_experts) == \
        (384, 12, 0, 8, 1)
    assert spec.vocab_size == 20480 and spec.dtype == 'bfloat16'
    assert [(k.name, k.layers, k.width, k.stored, k.reads)
            for k in spec.cache_kinds()] == [
        ('lm_latent_full', (0, 1, 2, 3, 4, 5), 576, 640, (0,) * 6)]
    assert spec.shares_frozen_pages()
    reference = _module('references', 'kimi_k2_6')
    arch = reference.arch_of(spec)
    assert arch['top_k'] == 8 and arch['routed_scale'] == 2.827
    assert arch['yarn'] and arch['softmax_mscale'] and arch['scale_routed']
    assert arch['latent']['rope_scaling']['factor'] == 64
    assert reference.held_of(spec) == (0, 12)
    for wrong in (dict(model_type='deepseek_v3'), dict(topk_method='greedy'),
                  dict(tie_word_embeddings=True), dict(n_group=8),
                  dict(rope_scaling=dict(STATED['rope_scaling'],
                                         type='linear'))):
        with pytest.raises(ValueError, match='not the block'):
            runner.spec_of(dict(resolved['config'], **wrong))


# ------------------------------------------------- the sessions' schedule
def test_sessions_schedule_is_a_pure_function_of_its_arguments(resolved):
    traffic = {k: v for k, v in resolved['traffic'].items()
               if k != 'rehearsal'}
    a, asks_a = sessions.schedule(traffic, 5, 51.0)
    b, asks_b = sessions.schedule(traffic, 5, 51.0)
    c, asks_c = sessions.schedule(traffic, 3600000036, 51.0)
    assert a == b and asks_a == asks_b
    # another seed: the same requests at the same instants, other tokens
    assert [(r.due, r.prompt_len, r.answer_len) for r in a] == \
        [(r.due, r.prompt_len, r.answer_len) for r in c]
    assert [(k.document, k.ask, k.doc_len) for k in asks_a.values()] == \
        [(k.document, k.ask, k.doc_len) for k in asks_c.values()]
    assert [r.token_seed for r in a] != [r.token_seed for r in c]
    assert [r.due for r in a] == sorted(r.due for r in a)
    assert [r.index for r in a] == list(range(len(a)))
    # the offered rate, exactly
    preroll = traffic['preroll_s']
    in_window = [r for r in a if r.due >= preroll]
    assert len(in_window) == int(round(traffic['rate_rps'] * 51.0))
    assert all(r.due < preroll + 51.0 for r in a)
    # the ISSUE's floor and what the held sample needs of the trace
    assert len(in_window) >= 40
    assert any(asks_a[r.index].ask == 0 and
               r.prompt_len + r.answer_len > 16384 for r in in_window)


def test_sessions_share_the_heads_they_say(resolved):
    traffic = {k: v for k, v in resolved['traffic'].items()
               if k != 'rehearsal'}
    requests, asks = sessions.schedule(traffic, 7, 51.0)
    by_doc = {}
    for r in requests:
        by_doc.setdefault(asks[r.index].document, []).append(r)
    vocab = 20480
    lo, hi = traffic['asks']
    for doc, rs in list(by_doc.items())[:4]:
        ks = [asks[r.index] for r in rs]
        assert [k.ask for k in ks] == list(range(len(ks))) and \
            len(ks) <= hi
        n = ks[0].doc_len
        assert traffic['doc_len'][0] <= n <= traffic['doc_len'][1]
        prompts = [sessions.prompt_tokens(r, k, vocab)
                   for r, k in zip(rs, ks)]
        for r, k, p in zip(rs, ks, prompts):
            assert len(p) == r.prompt_len == n + k.question_len
            assert p[:n] == prompts[0][:n] and max(p) < vocab
            assert traffic['question_len'][0] <= k.question_len <= \
                traffic['question_len'][1]
        if len(prompts) > 1:     # questions are their own
            assert prompts[0][n:n + 8] != prompts[1][n:n + 8]
        # a later ask is due at least the floor after the one before
        for before, after in zip(rs, rs[1:]):
            assert after.due - before.due >= traffic['ask_gap_floor_s']
    # two documents do not share a head
    first = [sessions.prompt_tokens(rs[0], asks[rs[0].index], vocab)[:64]
             for rs in list(by_doc.values())[:3]]
    assert first[0] != first[1] != first[2]
    share = sessions.shared_share(requests, asks, 32, traffic['preroll_s'])
    assert 0.6 < share < 0.85


# ------------------------------------------- the shape function and reader
def test_prefill_attention_flops_are_the_expanded_forms(resolved):
    fn = _module('shape_fns', 'mla_prefill_attn_flops')
    config = resolved['config']
    # a chunk of 512 queries at depth 16,384 in one layer
    pairs = sum(range(16384 - 512 + 1, 16384 + 1))
    got = fn.least_flops(pairs, config)
    assert got == pairs * 64 * 320 * 2
    # ISSUE 36: the absorbed chunk costs 1.17 TFLOP a layer, 3.4 x this
    absorbed = 512 * 16384 * 64 * (576 + 512) * 2
    assert round(absorbed / 1e12, 2) == 1.17
    assert round((576 + 512) / 320.0, 1) == 3.4


def _prefill_sources(resolved):
    """A traced tail of 10 s, written in milliseconds: a first ask of
    four chunks whose prefill began before the tail, a hit of one chunk
    whole inside it, and a first ask of three chunks in whose third
    the profiler stopped (no op starts after that run)."""
    attn = 'fusion.1 = f32[64,512,512]{2,1,0} fusion(...)'
    other = 'fusion.2 = bf16[512,7168]{1,0} fusion(...)'
    ms = 1000000

    def at(events):
        return [(name, s * ms, d * ms) for name, s, d in events]
    chunks = (
        [dict(run=0, t=-3.0, dur=6.5, bucket=512, pairs=p)
         for p in (100, 200, 300)]
        + [dict(run=0, t=-3.0, dur=6.5, bucket=128, pairs=50)]
        + [dict(run=1, t=4.02, dur=0.9003, bucket=64, pairs=7)]
        + [dict(run=2, t=6.01, dur=5.0, bucket=512, pairs=p)
           for p in (1000, 2000, 4000)])
    runs = at([('jit_prefill_512(1)', -2000, 1500),  # before the trace
               ('jit_prefill_512(1)', -400, 1400),   # cut by the start
               ('jit_prefill_512(1)', 1100, 1400),
               ('jit_prefill_128(2)', 2600, 700),
               ('jit_decode(3)', 3400, 100),
               ('jit_prefill_64(4)', 4100, 600),
               ('jit_prefill_512(1)', 6100, 1500),
               ('jit_prefill_512(1)', 7700, 1800),
               ('jit_prefill_512(1)', 9600, 350)])   # cut by the stop
    host = at([('decode.prefill.run', 4000, 900), ('decode.step', 3350, 200),
               ('decode.prefill.run', 6000, 5000)])  # straddles the end
    device = at([(attn, 1200, 300), (other, 1600, 50), (attn, 2700, 100),
                 (attn, 3420, 20),                   # a step's, not counted
                 (attn, 4200, 40), (attn, 6200, 500), (attn, 7800, 900),
                 (attn, 9700, 250)])                 # the cut run's
    return dict(
        trace={'window': (0, 10000 * ms), 'first': device, 'host': host},
        peaks={'flops_bf16': 1e9}, config=resolved['config'],
        bench_dir=BENCH, prefill_chunks=chunks, prefill_program_runs=runs)


def test_prefill_reader_sets_each_chunks_flops_against_its_own_program_run(
        resolved, capsys):
    reader = _module('readers', 'prefill_ops_mxu')
    sources = _prefill_sources(resolved)
    spec = resolved_metric(resolved, 'serve.mla_prefill_attn_mxu_share')
    args = dict(spec['args'], match=[r'f32\[64,512,512\]'])
    got = reader.read(args, sources)
    # the chunks whose runs lie whole inside the tail, whichever edge
    # their prefill straddles: the first ask's last two, the hit's one,
    # the second first ask's first two
    pairs = 300 + 50 + 7 + 1000 + 2000
    ns = 300 + 100 + 40 + 500 + 900
    np.testing.assert_allclose(
        got, 100.0 * (pairs * 64 * 320 * 2 / 1e9) / (ns / 1e3))
    said = json.loads(capsys.readouterr().out.split('PREFILL_CHUNKS ')[1])
    assert said == {'read': 5, 'dropped': 2, 'why': None, 'shift': 0}


@pytest.mark.parametrize('early_ms, shift', [(0.9, 1), (1.9, 1), (0.0, 0)])
def test_prefill_reader_reads_a_tail_whose_device_clock_runs_early(
        resolved, capsys, early_ms, shift):
    """The profiler sets the device's clock against the host's to within
    the 0.5-1.8 ms the host takes from a prefill span's start to its
    first dispatch: where every program run appears that much earlier
    than it ran, the anchor's first chunk starts just before its span,
    the first run at or after the span is its second, and every chunk
    would be set one run off (one of PR 37's five traced runs read
    nothing so). The neighbouring shift is tried and read; the answer is
    the aligned trace's."""
    reader = _module('readers', 'prefill_ops_mxu')
    sources = _prefill_sources(resolved)
    # the anchor (the hit at 4,000-4,900 ms, its run at 4,100) with its
    # dispatch 0.5 ms after the span's start, as on the chip
    early = int(early_ms * 1e6)
    tight = 99500000 if early else 0
    sources['prefill_program_runs'] = [
        (name, s - tight - early, d) for name, s, d in
        sources['prefill_program_runs']]
    sources['trace']['first'] = [
        (name, s - tight - early, d) for name, s, d in
        sources['trace']['first']]
    spec = resolved_metric(resolved, 'serve.mla_prefill_attn_mxu_share')
    args = dict(spec['args'], match=[r'f32\[64,512,512\]'])
    got = reader.read(args, sources)
    pairs = 300 + 50 + 7 + 1000 + 2000
    ns = 300 + 100 + 40 + 500 + 900
    np.testing.assert_allclose(
        got, 100.0 * (pairs * 64 * 320 * 2 / 1e9) / (ns / 1e3))
    said = json.loads(capsys.readouterr().out.split('PREFILL_CHUNKS ')[1])
    assert said == {'read': 5, 'dropped': 2, 'why': None, 'shift': shift}


@pytest.mark.parametrize('change,why', [
    (dict(prefill_chunks=None), 'the program gave no count'),
    ('no count', 'the program gave no count'),
    ('no anchor', 'no prefill whole inside the tail'),
    ('another bucket', 'run 3 is prefill_128, its chunk 512'),
    ('a run too many', 'the runs under a span are not one prefill'),
])
def test_prefill_reader_says_why_it_read_nothing(resolved, capsys, change,
                                                 why):
    reader = _module('readers', 'prefill_ops_mxu')
    sources = _prefill_sources(resolved)
    chunks = sources['prefill_chunks']
    if change == 'no count':         # the parent's spans
        change = dict(prefill_chunks=[dict(c, pairs=None) for c in chunks])
    elif change == 'no anchor':      # one prefill longer than the tail
        trace = dict(sources['trace'], host=sources['trace']['host'][1:])
        change = dict(trace=trace)
    elif change == 'another bucket':
        change = dict(prefill_chunks=[
            dict(c, bucket=512) if c['bucket'] == 128 else c
            for c in chunks])
    elif change == 'a run too many':  # two runs under the hit's one chunk
        change = dict(prefill_program_runs=sorted(
            sources['prefill_program_runs']
            + [('jit_prefill_512(1)', 4750000000, 100000000)],
            key=lambda run: run[1]))
    spec = resolved_metric(resolved, 'serve.mla_prefill_attn_mxu_share')
    assert reader.read(spec['args'], dict(sources, **change)) is None
    said = json.loads(capsys.readouterr().out.split('PREFILL_CHUNKS ')[1])
    assert said['read'] == 0 and said['why'] == why


def test_prefill_reader_is_silent_without_a_trace_or_program_runs(resolved):
    reader = _module('readers', 'prefill_ops_mxu')
    sources = _prefill_sources(resolved)
    spec = resolved_metric(resolved, 'serve.mla_prefill_attn_mxu_share')
    assert reader.read(spec['args'], dict(sources, trace=None)) is None
    assert reader.read(spec['args'], dict(
        sources, prefill_program_runs=None)) is None


def test_runner_hands_over_every_chunk_in_dispatch_order():
    """``chunks_dispatched``: from the recorder's spans, a prefill of
    one chunk is its run's span, a chunked one its chunk spans (which
    close before their run's), each with its prefill's span on the
    clock of the profiler's start. The order is held exactly. The times
    are held to a microsecond, not to a relative 1e-7: ``t`` is the
    difference of two readings of the recorder's clock, which stand near
    1.8e9 s where float64 is spaced 2.4e-7 apart."""
    from paddle_tpu import observe
    runner = _module('runners', 'serve_sessions')
    observe.reset()        # what an earlier test of this process left
    observe.enable()
    try:
        rec = observe.spans()
        rec.add_span('decode.prefill.run', 10.0, 10.5, dict(
            bucket=64, chunks=1, cached_tokens=96, attn_pairs=7))
        for i, pairs in enumerate((100, 200, 50)):
            rec.add_span('decode.prefill.chunk', 11.01 + i, 11.02 + i, dict(
                bucket=512 if i < 2 else 128, start=512 * i,
                attn_pairs=pairs))
        rec.add_span('decode.step', 10.6, 10.7)
        rec.add_span('decode.prefill.run', 11.0, 15.0, dict(
            bucket=512, chunks=3, cached_tokens=0, attn_pairs=350))
        got = runner.chunks_dispatched(12.0)
    finally:
        observe.disable()
        observe.reset()
    assert [(c['run'], c['bucket'], c['pairs']) for c in got] == [
        (0, 64, 7), (1, 512, 100), (1, 512, 200), (1, 128, 50)]
    np.testing.assert_allclose([c['t'] for c in got], [-2.0] + [-1.0] * 3,
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose([c['dur'] for c in got], [0.5] + [4.0] * 3,
                               rtol=0, atol=1e-6)


def resolved_metric(resolved, name):
    (metric,) = [m for m in resolved['per_layer']
                 if m['entry']['name'] == name]
    return metric['spec']


def test_trace_patterns_are_the_configs_numbers(resolved):
    """The patterns of the device-trace readers name this cell's
    geometry: the stored latent row, the heads, the column block, the
    chunk, the expert stacks."""
    config = resolved['config']
    attn = resolved_metric(resolved, 'serve.mla_attn_busy_share')
    text = ' '.join(attn['args']['match'])
    spec = _module('runners', 'serve_sessions').spec_of(config)
    (kind,) = spec.cache_kinds()
    assert ',%d\\]' % kind.stored in text
    heads, geometry = config['num_attention_heads'], config['engine']
    buckets, b = [], geometry['min_prompt_bucket']
    while b <= geometry['prefill_chunk']:      # the prefill programs
        buckets.append(str(b))
        b *= 2
    assert 'f32\\[(1,1,)?%d,(%s),512\\]' % (heads, '|'.join(buckets)) \
        in text
    from paddle_tpu.ops.pallas import paged_attention as pa
    rank = spec.latent[FULL].kv_rank
    assert 'f32\\[%d,1,%d,1,%d' % (pa.BLOCK_ROWS, heads, rank) in text
    # the decode loop's scatter of closed rows and a pair's normaliser
    assert 'f32\\[%d,%d,%d\\]' % (geometry['max_batch'] + pa.BLOCK_ROWS,
                                    heads, rank) in text
    assert 'f32\\[%d,%d\\]' % (pa.BLOCK_ROWS, heads) in text
    assert resolved_metric(
        resolved, 'serve.mla_decode_attn_roofline_share')['args'][
            'match'] == attn['args']['match']
    # a prefill program has no pair loop: its reader keeps the patterns
    # of the arena, the score blocks and the chunks, and not those two
    assert resolved_metric(
        resolved, 'serve.mla_prefill_attn_mxu_share')['args']['match'] == \
        attn['args']['match'][:3]
    # 8 rows by heads is a pair loop's shape only while a step's batch is
    # not 8 rows itself
    assert geometry['max_batch'] != pa.BLOCK_ROWS
    ffn = resolved_metric(resolved, 'serve.mla_moe_ffn_busy_share')
    assert 'bf16\\[%d,(%d|1),(%d,%d|%d,%d)\\]' % (
        config['num_hidden_layers'] - 1, config['n_routed_experts'],
        config['hidden_size'], config['moe_intermediate_size'],
        config['moe_intermediate_size'], config['hidden_size']) \
        in ffn['args']['match'][0]


# Op lines of the decode program as the v5e's compiler writes them at the
# configuration's heads, ranks and max_batch (compiled here for a described
# chip, PR 42; layouts shortened): the pair loop's, which the patterns PR 42
# added are for, then ops near them in shape that are not the attention's.
PAIR_LOOP_OPS = {
    'scatter': '%fusion.472 = f32[40,64,512]{2,1,0:T(8,128)S(1)} fusion('
               'f32[40,64,512]{2,1,0:T(8,128)S(1)} %out, s32[8]{0:T(128)S(1)} '
               '%goes, f32[8,64,512]{2,1,0:T(8,128)S(1)} %done)',
    'zeros': '%broadcast_in_dim.392 = f32[40,64,512]{2,1,0:T(8,128)S(1)} '
             'broadcast(f32[]{:T(128)} %constant.352)',
    'result': '%copy.125 = bf16[40,64,512]{2,0,1:T(8,128)(2,1)S(1)} copy('
              'f32[40,64,512]{2,1,0:T(8,128)S(1)} %while.56)',
    'normaliser': '%fusion.457 = f32[8,64]{1,0:T(8,128)S(1)} fusion('
                  'f32[8,64,512]{2,1,0:T(8,128)S(1)} %weights, f32[8,64]{1,0:'
                  'T(8,128)S(1)} %top, pred[8,512]{1,0:T(8,128)(4,1)S(1)} '
                  '%seen)',
    'open_row': '%compare_bitcast_fusion.20 = pred[64]{0:T(512)(128)(4,1)S(1)'
                '} fusion(f32[1,64,1]{1,2,0:T(1,128)S(1)} %state, f32[8,64]'
                '{1,0:T(8,128)S(1)} %top)',
    'scale': '%fusion.461 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)}) '
             'fusion(f32[1,64,1]{1,2,0:T(1,128)S(1)} %state, f32[8,64]{1,0:'
             'T(8,128)S(1)} %top)',
    'merge': '%bitcast_dynamic-update-slice_fusion.33 = f32[8,1,64,1,512]'
             '{4,2,3,1,0:T(8,128)S(1)} fusion(f32[8,1,64,1,512]{4,2,3,1,0:'
             'T(8,128)S(1)} %acc, f32[64]{0:T(128)S(1)} %keep, f32[64]'
             '{0:T(128)S(1)} %scale, f32[8,1,64,1,512]{4,2,3,1,0:T(8,128)'
             'S(1)} %partial)'}
OTHER_OPS = {
    # the new key's rotary part: batch rows by the rope width, 64 as the
    # heads are
    'rope_slice': '%gather.61 = f32[32,64]{1,0:T(8,128)S(1)} slice('
                  'f32[32,576]{1,0:T(8,128)S(1)} %fusion.181)',
    # the query's up-projection and its no-rope part (the doc's "query's
    # scaling": the step's, not the loop's)
    'query_up': '%fusion.186 = f32[32,64,192]{2,0,1:T(8,128)S(1)} fusion('
                'bf16[64,192,1536]{2,1,0:T(8,128)(2,1)} %w_uq, f32[32,1536]'
                '{1,0:T(8,128)S(1)} %q)',
    'query_nope': '%slice.623 = f32[32,64,64]{2,0,1:T(8,128)S(1)} slice('
                  'f32[32,64,192]{2,0,1:T(8,128)S(1)} %fusion.186)',
    # the router: batch rows by the 8 experts a token takes
    'gate': '%broadcast_add_fusion = (f32[32,8]{0,1:T(8,128)S(1)}, f32[32,8]'
            '{0,1:T(8,128)S(1)}) fusion(f32[8]{0:T(128)S(1)} %bias, bf16[1,'
            '7168,12]{1,2,0:T(8,128)(2,1)S(1)} %w, f32[32,7168]{1,0} %x)',
    'top_k': '%sort.1 = (f32[32,8]{0,1:T(8,128)}, s32[32,8]{0,1:T(8,128)S(1)'
             '}) sort(f32[32,8]{0,1:T(8,128)S(1)} %scores, s32[32,8]{0,1:'
             'T(8,128)S(1)} %iota.11)',
    # the pair list's own: the mask and a pair's pages
    'mask': '%fusion.455 = pred[8,512]{1,0:T(8,128)(4,1)S(1)} fusion(s32[8]'
            '{0:T(128)S(1)} %lo, s32[8]{0:T(128)S(1)} %hi, s32[8]{0:T(128)'
            'S(1)} %at)',
    'pages': '%fusion.453 = s32[8,16]{1,0:T(8,128)S(1)} fusion(s32[2080,16]'
             '{1,0:T(8,128)} %tables, s32[]{:T(128)S(6)} %first)',
    # the layer loop carries the attention's state and lasts the program
    'loop': '%while.56 = (s32[], f32[40,64,512]{2,1,0}, f32[8,64]{1,0}, '
            'bf16[6,16384,32,640]{3,2,1,0}) while(%tuple.9)'}


@pytest.mark.parametrize('op', sorted(PAIR_LOOP_OPS) + sorted(OTHER_OPS))
def test_the_attention_patterns_find_the_pair_loop_and_nothing_near_it(
        resolved, op):
    for name in ('serve.mla_attn_busy_share',
                 'serve.mla_decode_attn_roofline_share'):
        patterns = resolved_metric(resolved, name)['args']['match']
        line = PAIR_LOOP_OPS.get(op) or OTHER_OPS[op]
        assert any(re.search(p, line) for p in patterns) == \
            (op in PAIR_LOOP_OPS), (name, op)
    # ... and a prefill chunk's reader, which keeps the patterns it had,
    # knows none of the loop's ops but the merge it always knew
    prefill = resolved_metric(
        resolved, 'serve.mla_prefill_attn_mxu_share')['args']['match']
    assert any(re.search(p, (PAIR_LOOP_OPS.get(op) or OTHER_OPS[op]))
               for p in prefill) == (op == 'merge')


def test_the_benchmarks_reference_is_the_repositorys():
    mine = os.path.join(REPO, 'paddle_tpu', 'models', 'reference',
                        'kimi_k2_6.py')
    with open(mine) as a, open(os.path.join(
            BENCH, 'references', 'kimi_k2_6.py')) as b:
        assert a.read() == b.read()
    with open(mine) as f:
        assert 'paddle_tpu' not in f.read().split('"""')[2]   # the code


# ---------------------------------------------------- the rehearsal
@pytest.fixture
def own_environment(monkeypatch):
    """benchmark/run.py turns the executor's cost probe off for its
    process and, traced, ``observe`` on: in a test process both have to
    end with the test, and the registry the run counted into is emptied,
    so that no later test of this worker hangs on whether this file ran
    before it (xdist gives a worker whole files in any order)."""
    from paddle_tpu import observe
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def test_the_cell_rehearses_in_process(capsys, own_environment):
    assert bench.main(['--workload', CELL, '--seed', '3600000036',
                       '--seconds', '3', '--trace', '0',
                       '--rehearsal']) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    window = json.loads([ln for ln in lines
                         if ln.startswith('WINDOW ')][-1][7:])
    assert last['rehearsal'] is True and last['correct'] is True
    assert last['attempted'] > 10 and last['failed'] == 0
    assert set(last['metrics']) == {'ttft_mean_ms', 'itl_mean_ms',
                                    'setup_s'}
    assert all(m['value'] is None for m in last['metrics'].values())
    assert window['same_one_at_a_time'] is True and window['rechecked'] == 2
    assert window['reference_gap_max'] <= 1e-4
    # the sample holds a long first ask and two asks from shared pages
    assert window['held_long_first_asks'] == 1
    assert window['held_shared_later_asks'] == 2
    assert sum(1 for c in window['held_cached_tokens'] if c) >= 2
    assert window['reference_longest_tokens'] > 32
    assert window['refused'] == 0 and window['compiles_in_window'] == 0
    # what the engine served from shared pages is what the schedule says
    assert abs(window['cached_share_of_prompt_tokens']
               - window['schedule_shared_share']) < 0.02
    assert 'pacer_late_ms_max' in window


def test_the_traced_rehearsal_reads_the_counters_this_pr_adds(
        capsys, own_environment):
    """Under --trace 1 the program's counters reach the line: the share
    of prompt tokens from shared pages, no eviction, no recompile, the
    local share of 3 held of 8."""
    assert bench.main(['--workload', CELL, '--seed', '2147483683',
                       '--seconds', '3', '--trace', '1',
                       '--rehearsal']) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    window = json.loads([ln for ln in lines
                         if ln.startswith('WINDOW ')][-1][7:])
    got = {k: v['value'] for k, v in last['metrics'].items()}
    assert last['correct'] is True
    assert abs(got['serve.prefix_tokens_reused_share']
               - 100 * window['schedule_shared_share']) < 10
    assert got['serve.prefix_evicted_pages'] == 0
    assert got['serve.recompiles'] == 0
    assert 0 < got['serve.moe_local_assignment_pct'] <= 100
    assert 0 < got['serve.kv_pool_used_pct'] <= 100
    assert 0 < got['serve.attn_pages_held_share'] <= 100
    assert 'serve.mla_decode_attn_roofline_share' not in got   # no device
    assert 'serve.mla_prefill_attn_mxu_share' not in got


def test_the_fault_probe_rehearses(capsys):
    """benchmark/probe_session_faults.py at the toy size: plain rope, a
    softmax scale without m^2, an unscaled routed sum and a suffix at
    positions counted from 0 each fail the cell's limits; the bfloat16
    state shows least (the CPU logits tests hold it)."""
    from benchmark import probe_session_faults as probe
    assert probe.main(['--workload', CELL, '--rehearsal', '--seed', '5',
                       '--lengths', '72', '--rows', '24', '--suffix', '12',
                       '--faults', 'yarn,softmax_mscale,scale_routed,'
                       'offset,state']) == 0
    lines = [json.loads(ln[8:]) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('PLANTED ')]
    assert [ln['fault'] for ln in lines] == [
        'yarn', 'softmax_mscale', 'scale_routed', 'offset', 'state']
    for line in lines[:4]:
        assert line['within_limits'] is False and line['not_first'] > 0
    assert lines[4]['logits_rms_diff'] < min(
        ln['logits_rms_diff'] for ln in lines[:4])


def test_the_sessions_sweep_rehearses(capsys):
    from benchmark import sweep_sessions
    assert sweep_sessions.main(['--workload', CELL, '--rehearsal',
                                '--rates', '6,8', '--seconds', '3']) == 0
    lines = [json.loads(ln[6:]) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('SWEEP ')]
    assert [ln['rate_rps'] for ln in lines] == [6.0, 8.0]
    for line in lines:
        assert line['unfinished'] == 0 and line['refused'] == 0
        assert line['requests'] == int(round(line['rate_rps'] * 3))
        assert line['evictions'] == 0
