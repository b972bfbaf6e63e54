"""The ``mellum2_12b`` configuration and its cell, off the chip: the file
holds the published config with the cut beside it, its parameters add up
to the stated cut, the runner builds the block it describes (two page
pools, a position table a layer kind, a softmax router over 64 experts
all held), the shape function and the reader this PR brings do their
arithmetic, the trace patterns are the configuration's numbers, the
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

from benchmark import loadgen, manifest     # noqa: E402
from benchmark import run as bench          # noqa: E402

MANIFEST = manifest.load(REPO)
CELL = 'mellum2_12b.repo_ctx_steady'
BENCH = os.path.join(REPO, 'benchmark')
FULL, SLIDING = 'full_attention', 'sliding_attention'

# config.json of JetBrains/Mellum2-12B-A2.5B-Instruct, every number of
# it, as published (the catalog row beside the model-configs guide)
PUBLISHED = {
    'head_dim': 128, 'hidden_size': 2304, 'intermediate_size': 7168,
    'max_position_embeddings': 131072, 'max_window_layers': 0,
    'moe_intermediate_size': 896, 'num_attention_heads': 32,
    'num_experts': 64, 'num_experts_per_tok': 8, 'num_key_value_heads': 4,
    'rms_norm_eps': 1e-06, 'sliding_window': 1024, 'vocab_size': 98304}
STATED = {
    'attention_bias': False, 'hidden_act': 'silu', 'model_type': 'mellum',
    'norm_topk_prob': True, 'tie_word_embeddings': False,
    'use_sliding_window': True,
    'layer_types': [SLIDING, SLIDING, SLIDING, FULL] * 7,
    'mlp_layer_types': ['sparse'] * 28,
    'rope_parameters': {
        FULL: {'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
               'original_max_position_embeddings': 8192, 'beta_fast': 32,
               'beta_slow': 1, 'attention_factor': 1.2772588722239782},
        SLIDING: {'rope_type': 'default', 'rope_theta': 500000}}}
CUT = {'num_hidden_layers': (8, 28)}
# the entries that carry this configuration's shapes or its two pools,
# in the order they were handed in
OWN_IN_ORDER = [
    'serve.window_pages_freed_share', 'serve.window_kind_pool_used_pct',
    'serve.full_kind_pool_used_pct', 'serve.gqa_attn_busy_share',
    'serve.gqa_decode_attn_roofline_share', 'serve.gqa_moe_ffn_busy_share',
    'serve.gqa_moe_ffn_roofline_share', 'serve.gqa_moe_step_hbm_share']
OWN_METRICS = set(OWN_IN_ORDER)
# the shared readers whose series its engine feeds
SHARED_METRICS = {
    'serve.recompiles', 'serve.queue_wait_ms', 'serve.prefill_ms',
    'serve.decode_step_ms', 'serve.batch_occupancy', 'serve.ttft_p90_ms',
    'serve.itl_p95_ms', 'serve.tokens_per_s', 'serve.worker_prefill_share',
    'serve.worker_step_share', 'serve.worker_idle_share',
    'serve.step_build_ms', 'serve.step_dispatch_ms', 'serve.step_fetch_ms',
    'serve.step_emit_ms', 'serve.live_tokens_per_step',
    'serve.moe_load_max_over_mean', 'serve.window_bound_row_share',
    'serve.prefill_chunks_per_prompt', 'serve.attn_pages_read_share',
    'serve.attn_pages_held_share', 'serve.moe_row_tiles_run_share',
    'serve.prefill_chunk_ms', 'serve.steps_ahead_share'}


def _module(kind, name):
    return manifest.load_module(os.path.join(BENCH, kind, name + '.py'))


@pytest.fixture(scope='module')
def resolved():
    return manifest.resolve(MANIFEST, CELL)


def resolved_metric(resolved, name):
    (metric,) = [m for m in resolved['per_layer']
                 if m['entry']['name'] == name]
    return metric['spec']


# ------------------------------------------------------- the files
def shape_the_mellum_cell_resolves_to_files_by_name(m):
    assert manifest.problems(m) == []
    r = manifest.resolve(m, CELL)
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_gqa_moe'
    assert r['cell']['chips'] == 1 and r['cell']['traffic'] == \
        'repo_ctx_steady'
    assert r['config']['reference'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    (entry,) = [c for c in m['configs'] if c['name'] == 'mellum2_12b']
    assert entry['reduced'] == r['config']['reduced'] == list(CUT)
    assert len(entry['why']) <= 200 and len(r['cell']['why']) <= 200
    assert entry['source'] == r['config']['source']


def shape_the_mellum_cell_reports_its_metrics_and_the_two_end_to_end(m):
    """Membership only: a later cell may join these lists."""
    resolved = manifest.resolve(m, CELL)
    mine = {p['entry']['name'] for p in resolved['per_layer']}
    assert mine >= OWN_METRICS | SHARED_METRICS
    # what the configuration lacks is left off: no prefix cache, no
    # latent form, no selection, every expert held (a constant 100)
    assert not [n for n in mine if n.startswith((
        'serve.prefix_', 'serve.latent_', 'serve.mla_', 'serve.sparse_',
        'serve.indexer_', 'train.'))]
    assert 'serve.moe_local_assignment_pct' not in mine
    # a value under one name: the full pool's share is its own entry's
    assert 'serve.kv_pool_used_pct' not in mine
    for metric in m['per_layer']:
        if metric['name'] in OWN_METRICS:
            assert all(cell.startswith('mellum2_12b.')
                       for cell in metric['workloads'])
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in m['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e


def test_the_cell_resolves_to_files_by_name():
    shape_the_mellum_cell_resolves_to_files_by_name(MANIFEST)


def test_the_cell_reports_its_metrics_and_the_two_end_to_end():
    shape_the_mellum_cell_reports_its_metrics_and_the_two_end_to_end(
        MANIFEST)


def shape_the_mellum_entries_are_there_in_the_order_handed_in(m):
    """By name and membership: the cell is in ``workloads``, its
    configuration in ``configs``, its eight entries in ``per_layer`` in
    the order they were handed in, relative to each other. Where they
    stand in their lists is not held: later PRs append behind them."""
    assert CELL in [c['name'] for c in m['workloads']]
    assert 'mellum2_12b' in [c['name'] for c in m['configs']]
    assert [p['name'] for p in m['per_layer']
            if p['name'] in OWN_METRICS] == OWN_IN_ORDER


def test_the_entries_were_appended():
    shape_the_mellum_entries_are_there_in_the_order_handed_in(MANIFEST)


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
    the file under the same name with the same value, but the depth."""
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.isfile(catalog):
        pytest.skip('no catalog here')
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r['name'] == 'Mellum2-12B-A2.5B-Instruct']
    config = resolved['config']
    assert config['source'] == row['source_url']
    differs = {k for k, v in row['config'].items() if config.get(k) != v}
    assert differs == set(CUT)


def test_config_states_the_deployment_and_what_it_assumes(resolved):
    config = resolved['config']
    assert 'pipeline' in config['deployment'] and config['first_expert'] == 0
    # two whole periods, every expert and the whole vocabulary held
    assert config['layer_types'][:config['num_hidden_layers']] == \
        [SLIDING, SLIDING, SLIDING, FULL] * 2
    for word in ('scope', 'block', 'attention', 'rotary', 'router',
                 'weights', 'precision', 'geometry', 'sampling'):
        assert len(config['assumed'][word]) > 40 or word == 'sampling'
    assert 'MTP' in config['assumed']['scope']
    geometry = config['engine']
    assert geometry['pages_per_seq'] * geometry['block_size'] == \
        geometry['max_prompt_len'] + 512
    # every slot can hold a whole sequence of the full layers' pool
    assert geometry['num_blocks'] == \
        geometry['max_batch'] * geometry['pages_per_seq']
    assert set(geometry['pool_blocks']) == {'sliding'}
    assert geometry['prefix_cache'] is False and geometry['spec_k'] == 0
    limits = config['reference']
    assert limits['long_requests'] >= 1 and limits['requests'] == 5
    assert limits['long_tokens'] == 8192       # past YaRN's original range
    assert limits['long_tokens'] == \
        config['rope_parameters'][FULL]['original_max_position_embeddings']


def test_parameters_and_cache_add_up_to_the_stated_cut(resolved):
    """ISSUE 43's arithmetic, recounted from the program's own parameter
    table: attention 21.23 M a layer, router 0.15 M, an expert 6.19 M
    and 64 of them 396.4 M, a layer 417.7 M, embedding + head 453.0 M:
    3.795 B, 7.59 GB in bfloat16; the cache 4,096 B a token in the full
    pool and 12,288 B in the sliding one: 4.36 + 0.63 GB."""
    from paddle_tpu.serving.decode.model import (arena_bytes,
                                                 block_param_shapes,
                                                 kv_bytes_per_kind)
    spec = _module('runners', 'serve_gqa_moe').spec_of(resolved['config'])
    shapes = block_param_shapes(spec)

    def millions(*prefixes):
        return sum(int(np.prod(shape)) for name, (shape, _, _) in
                   shapes.items() if name.startswith(prefixes)) / 1e6
    layers = 8
    assert round(millions('lm_stack_slf_') / layers, 2) == 21.23
    assert round(millions('lm_stack_router') / layers, 2) == 0.15
    routed = millions('lm_stack_exp_') / layers
    assert round(routed / 64, 2) == 6.19 and round(routed, 1) == 396.4
    assert round(millions('lm_stack_') / layers, 1) == 417.7
    assert round(millions('lm_emb', 'lm_head'), 1) == 453.0
    total = sum(int(np.prod(shape)) for shape, _, _ in shapes.values())
    assert round(total / 1e9, 3) == 3.795
    assert round(total * 2 / 1e9, 2) == 7.59
    assert not [n for n in shapes if 'shr' in n or 'idx' in n]
    per_kind = kv_bytes_per_kind(spec, 'bfloat16')
    assert [per_kind[k.name] for k in spec.cache_kinds()] == \
        [2 * 1024, 2 * 1024, 6 * 1024, 6 * 1024]
    geometry = resolved['config']['engine']
    pages = {'': geometry['num_blocks'],
             'sliding': geometry['pool_blocks']['sliding']}
    arenas = arena_bytes(spec, pages, geometry['block_size'], 'bfloat16')
    assert round(33280 * 32 * 4096 / 1e9, 2) == 4.36
    assert round(1600 * 32 * 12288 / 1e9, 2) == 0.63
    assert arenas == 33280 * 32 * 4096 + 1600 * 32 * 12288
    assert (total * 2 + arenas) / 1e9 > 11
    # kept whole, the sliding layers' cache alone would not fit the chip
    assert 32 * 33280 * 12288 / 1e9 > 13


# ------------------------------------------------------ the runner
def test_runner_builds_the_block_the_config_describes(resolved):
    runner = _module('runners', 'serve_gqa_moe')
    spec = runner.spec_of(resolved['config'])
    assert (spec.block, spec.n_layer, spec.d_model, spec.d_inner,
            spec.n_head, spec.n_kv_head, spec.d_key, spec.d_value) == \
        ('gqa_moe', 8, 2304, 896, 32, 4, 128, 128)
    assert spec.layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 2
    assert spec.layer_plan() == ((), (SLIDING, SLIDING, SLIDING, FULL), 2,
                                 ())
    assert spec.windows() == [1024, 1024, 1024, 0] * 2
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.experts_per_token, spec.n_shared_experts) == \
        (64, 64, 0, 8, 0)
    assert spec.vocab_size == 98304 and spec.dtype == 'bfloat16'
    assert spec.norm_eps == 1e-6
    from paddle_tpu.serving.decode import model as lm
    assert lm.yarn_range(128, 500000, spec.rope_parameters[FULL]) == (18, 35)
    tables = spec.rope_tables()
    assert tables[FULL][1] == 1.2772588722239782 and tables[SLIDING][1] == 1
    full, sliding = spec.page_pools()
    assert (full.name, full.keeps) == ('', 0)
    assert (sliding.name, sliding.keeps) == ('sliding', 1024)
    assert [(k.name, k.layers, k.width, k.stored, k.reads[0], k.pool)
            for k in spec.cache_kinds()] == [
        ('lm_kcache_full', (3, 7), 512, 512, 0, ''),
        ('lm_vcache_full', (3, 7), 512, 512, 0, ''),
        ('lm_kcache_sliding', (0, 1, 2, 4, 5, 6), 512, 512, 1024,
         'sliding'),
        ('lm_vcache_sliding', (0, 1, 2, 4, 5, 6), 512, 512, 1024,
         'sliding')]
    assert not spec.shares_frozen_pages()
    reference = _module('references', 'mellum2_12b')
    arch = reference.arch_of(spec)
    assert arch['top_k'] == 8 and arch['window'] == 1024
    assert arch['yarn'] and arch['windowed'] and \
        arch['state_dtype'] == 'float32'
    assert arch['rope'][FULL]['factor'] == 16
    assert reference.held_of(spec) == (0, 64)
    for wrong in (dict(model_type='qwen3_moe'), dict(attention_bias=True),
                  dict(tie_word_embeddings=True),
                  dict(norm_topk_prob=False),
                  dict(mlp_layer_types=['dense'] * 28)):
        with pytest.raises(ValueError, match='not the block'):
            runner.spec_of(dict(resolved['config'], **wrong))


def test_the_runner_samples_every_pool_where_serve_samples_the_first():
    runner = _module('runners', 'serve_gqa_moe')

    class Pool(object):
        def __init__(self, kind, used, total):
            self.kind, self.num_blocks, self._used = kind, total, used

        def used_blocks(self):
            return self._used

    class Engine(object):
        pools = [Pool('full', 10, 40), Pool('sliding', 3, 12)]
        spec = 'the spec'

        def free_pages(self):
            return 30
    samples = {}
    seen = runner._PoolsSampled(Engine(), samples)
    assert seen.free_pages() == 30 and seen.free_pages() == 30
    assert seen.spec == 'the spec'
    assert samples == {'kv_pool_used_pct.full': [25.0, 25.0],
                       'kv_pool_used_pct.sliding': [25.0, 25.0]}
    for name, gauge in (('serve.full_kind_pool_used_pct', 'full'),
                        ('serve.window_kind_pool_used_pct', 'sliding')):
        spec = manifest.read_json(os.path.join(
            BENCH, 'layer_metrics', name + '.json'))
        assert _module('readers', spec['reader']).read(
            spec['args'], {'samples': samples}) == 25.0
        assert spec['args']['gauge'] == 'kv_pool_used_pct.' + gauge


def test_the_traffic_is_the_issues_mix(resolved):
    traffic = resolved['traffic']
    assert traffic['prompt_len'] == [1024, 32768]
    assert traffic['answer_len'] == [32, 512]
    assert (traffic['alpha'], traffic['pool_seed'], traffic['preroll_s']) \
        == (1.3, 43, 8)
    requests = loadgen.schedule(
        {k: v for k, v in traffic.items() if k != 'rehearsal'}, 1, 51)
    window = [r for r in requests if r.due >= traffic['preroll_s']]
    assert len(window) >= 30                   # some tens in the window
    prompts = sorted(r.prompt_len for r in window)
    # every row is past the window from its first decode step; the
    # longest fills the table; some pass YaRN's original range
    assert prompts[0] >= 1024 and prompts[-1] == 32768
    assert sum(1 for r in window
               if r.prompt_len + r.answer_len > 8192) >= 3
    geometry = resolved['config']['engine']
    assert max(r.prompt_len + r.answer_len for r in requests) <= \
        geometry['pages_per_seq'] * geometry['block_size']
    assert '4/5' in traffic['note'] or 'four fifths' in traffic['note']


# ------------------------------------------- the shape function, the reader
def test_the_step_bytes_are_this_configs_keys(resolved):
    config = resolved['config']
    fn = _module('shape_fns', 'gqa_moe_decode_live_bytes')
    assert fn.expert_bytes(config) == 3 * 2304 * 896 * 2
    attention = 2 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64)
    head = 2 * 98304 * 2304
    gains = 4 * 2304 * 17
    assert fn.weight_bytes(config, 0) == 8 * attention + head + gains
    assert fn.weight_bytes(config, 64) - fn.weight_bytes(config, 0) == \
        8 * 64 * fn.expert_bytes(config)
    # the whole model but the embedding: ISSUE 43's ~7.1 GB a full step
    assert round(fn.weight_bytes(config, 64) / 1e9, 2) == 7.14

    def snapshot(steps, seconds, live, window, touched):
        return {'histograms': {
            'decode.step_seconds': {'sum': seconds, 'count': steps},
            'decode.step_live_tokens': {'sum': live, 'count': steps},
            'decode.step_window_tokens': {'sum': window, 'count': steps}},
            'counters': {'decode.moe_experts_touched': touched,
                         'decode.moe_layer_steps': 8 * steps}}
    before = snapshot(10, 1.0, 1000, 500, 80 * 20)
    after = snapshot(30, 1.4, 1000 + 20 * 90000, 500 + 20 * 20000,
                     80 * 20 + 160 * 50)
    got = fn.compute({'registry_before': before, 'registry_after': after,
                      'config': config})
    kv = 2 * 4 * 128 * 2 * (6 * 20000 + 2 * 90000)
    assert got == pytest.approx((fn.weight_bytes(config, 50) + kv) / 0.02)
    assert fn.compute({'registry_before': None, 'registry_after': None,
                       'config': config}) is None


def test_the_expert_roofline_counts_each_chosen_expert_once(resolved):
    config = resolved['config']
    reader = _module('readers', 'gqa_moe_ffn_roofline')
    assert reader.least_bytes_per_step(config, 40) == \
        8 * 40 * 3 * 2304 * 896 * 2
    line = '%fusion.7 = f32[32,896] fusion(bf16[32,2304] %x, ' \
        'bf16[8,64,2304,896]{3,2,1,0} %w, s32[] %layer)'
    args = resolved_metric(resolved, 'serve.gqa_moe_ffn_roofline_share')[
        'args']
    steps = [('decode.step', 1000 + 10000 * i, 9000) for i in range(4)]
    ops = [(line, 2000 + 10000 * i, 4000) for i in range(4)] + \
        [(line, 50000, 4000), ('%fusion.9 = f32[32,2304] fusion()', 2500,
                               100)]
    tail = {'counters': {'decode.moe_experts_touched': 0,
                         'decode.moe_layer_steps': 0}}
    after = {'counters': {'decode.moe_experts_touched': 8 * 4 * 40,
                          'decode.moe_layer_steps': 8 * 4}}
    sources = {'trace': {'window': (0, 45000), 'first': ops, 'host': steps},
               'peaks': {'hbm_bytes_per_s': 819e9}, 'registry_tail': tail,
               'registry_after': after, 'config': config}
    want = 100.0 * (8 * 40 * 3 * 2304 * 896 * 2 / 819e9) / 4e-6
    assert reader.read(args, sources) == pytest.approx(want)
    assert reader.read(args, dict(sources, registry_tail=None)) is None
    assert reader.read(args, dict(sources, trace=None)) is None


def test_the_attention_roofline_counts_a_window_and_a_whole_context(
        resolved):
    """``decode.cache_bytes_read`` by kind, which the engine counts from
    the rows' lengths and ``CacheKind.reads``: a sliding kind's rows
    inside the window, a full kind's every row, 1,024 B each."""
    args = resolved_metric(
        resolved, 'serve.gqa_decode_attn_roofline_share')['args']
    assert args['function'] == 'latent_decode_bytes'
    assert args['function_args']['kinds'] == [
        'lm_kcache_full', 'lm_vcache_full', 'lm_kcache_sliding',
        'lm_vcache_sliding']
    fn = _module('shape_fns', 'latent_decode_bytes')
    seen = np.asarray([600, 5000, 33000])
    per_kind = {'full': 2 * 1024 * int(seen.sum()),
                'sliding': 6 * 1024 * int(np.minimum(seen, 1024).sum())}
    counters = {'decode.steps_total': 1}
    for name in args['function_args']['kinds']:
        counters['decode.cache_bytes_read{kind=%s}' % name] = \
            per_kind[name.rsplit('_', 1)[1]]
    assert fn.per_step({'counters': {}}, {'counters': counters},
                       **args['function_args']) == \
        2 * (per_kind['full'] + per_kind['sliding'])


# --------------------------------------------------- the trace patterns
def test_trace_patterns_are_the_configs_numbers(resolved):
    """The patterns of the device-trace readers name this cell's
    geometry: a cached row of 4 KV heads x 128 in pages of 32, the
    column block, the pair loop's 8 pairs, the heads' groups, the
    chunks, the expert stacks."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    config = resolved['config']
    attn = resolved_metric(resolved, 'serve.gqa_attn_busy_share')
    text = ' '.join(attn['args']['match'])
    kv, d = config['num_key_value_heads'], config['head_dim']
    heads = config['num_attention_heads']
    group, geometry = heads // kv, config['engine']
    row, cols = kv * d, pa.BLOCK_COLS
    assert ',%d,%d\\]' % (geometry['block_size'], row) in text
    assert 'bf16\\[(%d|1),%d,%d\\]' % (pa.BLOCK_ROWS, cols, row) in text
    assert '(f32|bf16)\\[%d,[1%d],%d,1,(%d|%d)\\]' % (
        pa.BLOCK_ROWS, kv, group, cols, d) in text
    assert 'f32\\[%d,%d,%d\\]' % (pa.BLOCK_ROWS, kv, group) in text
    # the decode loop's scatter of closed rows and an iteration's
    assert 'f32\\[(%d|%d),%d,%d\\]' % (
        geometry['max_batch'] + pa.BLOCK_ROWS, pa.BLOCK_ROWS, heads, d) \
        in text
    assert 'bf16\\[(%d|%d),%d,%d\\]' % (
        pa.BLOCK_ROWS, geometry['max_batch'], heads, d) in text
    buckets, b = [], geometry['min_prompt_bucket']
    while b <= geometry['prefill_chunk']:      # the prefill programs
        buckets.append(str(b))
        b *= 2
    assert '(f32|bf16)\\[(1,)?%d,%d,(%s)(,%d|,%d)?\\]' % (
        kv, group, '|'.join(buckets), cols, d) in text
    assert 'bf16\\[(%s),(%d|%d),%d(,1)?\\]' % (
        '|'.join(buckets), kv, heads, d) in text
    assert resolved_metric(
        resolved, 'serve.gqa_decode_attn_roofline_share')['args'][
            'match'] == attn['args']['match']
    # 8 rows by heads is a pair loop's shape only while a step's batch is
    # not 8 rows itself
    assert geometry['max_batch'] != pa.BLOCK_ROWS
    ffn = resolved_metric(resolved, 'serve.gqa_moe_ffn_busy_share')
    assert 'bf16\\[%d,%d,(%d,%d|%d,%d)\\]' % (
        config['num_hidden_layers'], config['num_experts'],
        config['hidden_size'], config['moe_intermediate_size'],
        config['moe_intermediate_size'], config['hidden_size']) \
        in ffn['args']['match'][0]
    assert resolved_metric(
        resolved, 'serve.gqa_moe_ffn_roofline_share')['args']['match'] == \
        ffn['args']['match']


# Op lines of the decode step and the 512 chunk as the v5e's compiler
# writes them at the configuration's heads, widths and max_batch
# (compiled here for a described chip, PR 43; layouts shortened): the
# attention's, then ops near them in shape that are not the attention's.
ATTENTION_OPS = {
    'gather_sliding': '%fusion.1210 = bf16[128,32,512]{2,1,0} fusion('
                      'bf16[6,1600,32,512]{3,2,1,0} %arena, s32[128]{0} '
                      '%pages)',
    'gather_full': '%fusion.1310 = bf16[128,32,512]{2,1,0} fusion('
                   'bf16[2,33280,32,512]{3,2,1,0} %arena, s32[128]{0} '
                   '%pages)',
    'write': '%dynamic-update-slice.4 = bf16[6,1600,32,512]{3,2,1,0} '
             'dynamic-update-slice(bf16[6,1600,32,512]{3,2,1,0} %arena, '
             'bf16[1,1,1,512]{3,2,1,0} %row, s32[] %layer)',
    'scores': '%fusion.1235 = f32[8,1,8,1,512]{4,2,0,3,1} fusion('
              'bf16[8,4,8,1,128]{4,2,0,3,1} %q, bf16[8,512,512]{2,1,0} '
              '%block)',
    'sums': '%fusion.1250 = f32[8,1,8,1,128]{4,2,0,3,1} fusion('
            'bf16[8,4,8,1,512]{4,2,0,3,1} %w, bf16[8,512,512]{2,1,0} '
            '%block)',
    'normaliser': '%fusion.1240 = f32[8,4,8]{2,0,1} fusion('
                  'f32[8,1,8,1,512]{4,2,0,3,1} %scores, pred[8,512]{1,0} '
                  '%seen)',
    'open_row': '%fusion.1260 = pred[4,8]{1,0} fusion(f32[4,8,1]{1,0,2} '
                '%state, pred[] %opens)',
    'scatter': '%fusion.1273 = f32[40,32,128]{2,1,0} fusion('
               'f32[40,32,128]{2,1,0} %out, f32[8,32,128]{2,1,0} %done, '
               's32[8]{0} %goes)',
    'queries': '%fusion.1199 = bf16[8,32,128]{2,1,0} fusion('
               'bf16[32,32,128]{2,1,0} %q, s32[1024]{0} %at)',
    'chunk_scores': '%fusion.690 = f32[4,8,512,512]{3,2,1,0} fusion('
                    'bf16[1,4,8,512,128]{4,3,2,1,0} %q, bf16[512,4,128,1]'
                    '{2,1,0,3} %keys, pred[512,512]{1,0} %seen)',
    'chunk_sums': '%fusion.700 = f32[1,4,8,512,128]{4,3,2,1,0} fusion('
                  'bf16[512,4,128,1]{2,1,0,3} %values, f32[1,4,8,512,128]'
                  '{4,3,2,1,0} %acc, f32[4,8,512,512]{3,2,1,0} %w)',
    'chunk_gather': '%fusion.660 = bf16[16,32,512]{2,1,0} fusion('
                    'bf16[2,33280,32,512]{3,2,1,0} %arena, s32[16]{0} '
                    '%pages)'}
OTHER_OPS = {
    # the k and v projections' weights end in 512 too: not pages
    'kv_projection': '%fusion.90 = f32[32,512]{1,0} fusion(f32[32,2304]'
                     '{1,0} %x, bf16[8,2304,512]{2,1,0} %w, s32[] %layer)',
    'rotation': '%fusion.95 = f32[32,32,64]{2,1,0} fusion(f32[32,4096]'
                '{1,0} %q, f32[32,64]{1,0} %cos)',
    'router': '%fusion.120 = f32[32,64]{1,0} fusion(f32[32,2304]{1,0} %x, '
              'bf16[8,2304,64]{2,1,0} %w, s32[] %layer)',
    'top_k': '%sort.1 = (f32[32,8]{0,1}, s32[32,8]{0,1}) sort(f32[32,64]'
             '{1,0} %scores, s32[32,64]{1,0} %iota)',
    'experts': '%fusion.200 = f32[32,896]{1,0} fusion(bf16[32,2304]{1,0} '
               '%x, bf16[8,64,2304,896]{3,2,1,0} %w, s32[] %layer)',
    'mask': '%fusion.1230 = pred[8,512]{1,0} fusion(s32[8]{0} %lo, s32[8]'
            '{0} %hi)',
    'pages': '%fusion.1205 = s32[8,16]{1,0} fusion(s32[2080,16]{1,0} '
             '%tables, s32[] %first)',
    'head': '%fusion.900 = f32[32,98304]{1,0} fusion(bf16[32,2304]{1,0} '
            '%y, bf16[98304,2304]{1,0} %head)',
    # the layer loop carries the attention's state and lasts the program
    'loop': '%while.56 = (s32[], f32[40,32,128]{2,1,0}, '
            'bf16[6,1600,32,512]{3,2,1,0}) while(%tuple.9)'}


@pytest.mark.parametrize('op', sorted(ATTENTION_OPS) + sorted(OTHER_OPS))
def test_the_attention_patterns_find_the_attention_and_nothing_near_it(
        resolved, op):
    patterns = resolved_metric(
        resolved, 'serve.gqa_attn_busy_share')['args']['match']
    line = ATTENTION_OPS.get(op) or OTHER_OPS[op]
    assert any(re.search(p, line) for p in patterns) == \
        (op in ATTENTION_OPS), op
    experts = resolved_metric(
        resolved, 'serve.gqa_moe_ffn_busy_share')['args']['match']
    assert any(re.search(p, line) for p in experts) == (op == 'experts')


def test_the_benchmarks_reference_is_the_repositorys():
    mine = os.path.join(REPO, 'paddle_tpu', 'models', 'reference',
                        'mellum2_12b.py')
    with open(mine) as a, open(os.path.join(
            BENCH, 'references', 'mellum2_12b.py')) as b:
        assert a.read() == b.read()
    with open(mine) as f:
        assert 'paddle_tpu' not in f.read().split('"""')[2]   # the code


# ---------------------------------------------------- the rehearsal
@pytest.fixture
def own_environment(monkeypatch):
    """benchmark/run.py turns the executor's cost probe off for its
    process and, traced, ``observe`` on: in a test process both have to
    end with the test, and the registry the run counted into is
    emptied (tests/benchmark/test_kimi_k2_6.py: the same fixture)."""
    from paddle_tpu import observe
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def test_the_cell_rehearses_in_process(capsys, own_environment):
    assert manifest.problems(MANIFEST) == []
    assert bench.main(['--workload', CELL, '--seed', '4300000043',
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
    # the held sample reaches past the (toy) original range and window
    assert window['reference_longest_tokens'] > 32
    assert window['refused'] == 0 and window['compiles_in_window'] == 0
    assert window['signatures'] == 3           # chunks of 8 and 16, the step


def test_the_traced_rehearsal_reads_the_counters_this_pr_adds(
        capsys, own_environment):
    """Under --trace 1 the program's counters reach the line: pages
    given back behind the window, both pools' used shares, every row
    past the window, no recompile."""
    assert bench.main(['--workload', CELL, '--seed', '2147483690',
                       '--seconds', '3', '--trace', '1',
                       '--rehearsal']) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    got = {k: v['value'] for k, v in last['metrics'].items()}
    assert last['correct'] is True
    assert 0 < got['serve.window_pages_freed_share'] < 100
    assert 0 < got['serve.window_kind_pool_used_pct'] <= 100
    assert 0 < got['serve.full_kind_pool_used_pct'] <= 100
    assert got['serve.recompiles'] == 0
    assert got['serve.window_bound_row_share'] > 50
    assert 0 < got['serve.attn_pages_held_share'] <= 100
    assert got['serve.moe_load_max_over_mean'] >= 1
    assert 'serve.gqa_decode_attn_roofline_share' not in got   # no device
    assert 'serve.gqa_moe_ffn_roofline_share' not in got


def test_the_precision_probe_rehearses(capsys):
    """benchmark/probe_precision.py at the toy size: the reference with
    its float32 state in bfloat16, and with every matrix at float8's
    three mantissa bits, each against the stated precision."""
    from benchmark import probe_precision as probe
    assert probe.main(['--workload', CELL, '--rehearsal', '--seed', '5',
                       '--lengths', '40,72', '--rows', '24']) == 0
    lines = [json.loads(ln[8:]) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('CONTROL ')]
    assert [ln['control'] for ln in lines] == ['state', 'weights']
    assert all(ln['largest_gap_max'] >= 0 for ln in lines)


def test_the_sweep_rehearses(capsys):
    from benchmark import sweep
    assert sweep.main(['--workload', CELL, '--rehearsal', '--rates', '6,8',
                       '--seconds', '3']) == 0
    lines = [json.loads(ln[5:]) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('RATE ')]
    assert [ln['rate_rps'] for ln in lines] == [6.0, 8.0]
    for line in lines:
        assert line['unfinished'] == 0 and line['refused'] == 0
