"""The ``longcat_flash_chat`` configuration and its cell, off the chip:
the file holds the published config with the cut beside it, its
parameters add up to the stated cut, the runner builds the block it
describes, the trace is the fixed one the traffic file describes, the
shape function and the reader this PR brings do their arithmetic, the
trace patterns are the configuration's numbers, the benchmark's copy of
the plain reference is the repository's, and the cell rehearses end to
end on the CPU. Entries are found by name and by membership, never by
place. No test here describes a TPU topology."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import loadgen, manifest     # noqa: E402

MANIFEST = manifest.load(REPO)
CONFIG = 'longcat_flash_chat'
CELL = 'longcat_flash_chat.chat_decode_heavy'
BENCH = os.path.join(REPO, 'benchmark')
FULL = 'full_attention'

# config.json of meituan-longcat/LongCat-Flash-Chat, every key of the
# catalog row's ``config`` that is not cut, as published
PUBLISHED = {
    'attention_bias': False, 'hidden_size': 6144, 'ffn_hidden_size': 12288,
    'expert_ffn_hidden_size': 2048, 'num_attention_heads': 64,
    'kv_lora_rank': 512, 'q_lora_rank': 1536, 'qk_rope_head_dim': 64,
    'v_head_dim': 128, 'qk_nope_head_dim': 128, 'mla_scale_q_lora': True,
    'mla_scale_kv_lora': True, 'routed_scaling_factor': 6,
    'max_position_embeddings': 131072, 'rms_norm_eps': 1e-05,
    'rope_theta': 10000000, 'attention_method': 'MLA',
    'zero_expert_num': 256, 'zero_expert_type': 'identity', 'moe_topk': 12}
CUT = {'num_layers': (4, 28), 'n_routed_experts': (16, 512),
       'vocab_size': (16384, 131072)}
# the entries that carry this configuration's shapes or its mechanisms
OWN_METRICS = {
    'serve.scmoe_zero_assignment_pct', 'serve.scmoe_real_experts_per_token',
    'serve.scmoe_moe_ffn_busy_share', 'serve.scmoe_moe_ffn_roofline_share',
    'serve.scmoe_dense_ffn_busy_share', 'serve.scmoe_attn_busy_share',
    'serve.scmoe_decode_attn_roofline_share', 'serve.scmoe_step_hbm_share'}
# shared entries whose series this cell's engine feeds
SHARED_METRICS = {
    'serve.recompiles', 'serve.queue_wait_ms', 'serve.prefill_ms',
    'serve.decode_step_ms', 'serve.batch_occupancy',
    'serve.kv_pool_used_pct', 'serve.ttft_p90_ms', 'serve.itl_p95_ms',
    'serve.tokens_per_s', 'serve.worker_prefill_share',
    'serve.worker_step_share', 'serve.worker_idle_share',
    'serve.step_build_ms', 'serve.step_dispatch_ms', 'serve.step_fetch_ms',
    'serve.step_emit_ms', 'serve.live_tokens_per_step',
    'serve.moe_local_assignment_pct', 'serve.moe_load_max_over_mean',
    'serve.prefill_chunks_per_prompt', 'serve.prefill_chunk_ms',
    'serve.attn_pages_read_share', 'serve.attn_pages_held_share',
    'serve.moe_row_tiles_run_share', 'serve.steps_ahead_share'}
# the mechanisms the configuration lacks: their entries are left off
ABSENT = ('serve.window_', 'serve.sparse_', 'serve.prefix_', 'serve.ssm_',
          'serve.indexer_', 'serve.mla_', 'serve.latent_', 'serve.gqa_')


def _module(kind, name):
    return manifest.load_module(os.path.join(BENCH, kind, name + '.py'))


def _sized(block):
    return {k: v for k, v in block.items() if k != 'rehearsal'}


@pytest.fixture(scope='module')
def resolved():
    return manifest.resolve(MANIFEST, CELL)


def resolved_metric(resolved, name):
    (metric,) = [p['spec'] for p in resolved['per_layer']
                 if p['entry']['name'] == name]
    return metric


# ------------------------------------------------------- the files
def shape_the_scmoe_cell_resolves_to_files_by_name(m):
    assert manifest.problems(m) == []
    r = manifest.resolve(m, CELL)
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_scmoe'
    assert r['cell']['chips'] == 1 and r['cell']['traffic'] == \
        'chat_decode_heavy'
    assert r['config']['reference'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    (entry,) = [c for c in m['configs'] if c['name'] == CONFIG]
    assert entry['reduced'] == r['config']['reduced'] == list(CUT)
    assert len(entry['source']) <= 200 and len(r['cell']['why']) <= 200
    assert entry['source'] == r['config']['source']
    assert entry['file'] == 'benchmark/configs/%s.json' % CONFIG


def shape_the_scmoe_cell_reports_its_metrics_and_the_two_end_to_end(m):
    """The cell reports each per-layer metric named here and the two
    end-to-end metrics under the bounds they have; an entry that carries
    this configuration's shapes lists this configuration's cells alone.
    Membership only: a later cell may join the shared lists."""
    resolved = manifest.resolve(m, CELL)
    mine = {p['entry']['name'] for p in resolved['per_layer']}
    assert mine >= OWN_METRICS | SHARED_METRICS
    assert not [n for n in mine if n.startswith(ABSENT)]
    for metric in m['per_layer']:
        if metric['name'] in OWN_METRICS:
            assert all(cell.startswith(CONFIG + '.')
                       for cell in metric['workloads'])
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in m['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e
    # every share of a roofline or of a peak lists its cells
    for metric in m['per_layer']:
        if 'roofline' in metric['name'] or 'mfu' in metric['name']:
            assert 'workloads' in metric


def test_the_cell_resolves_to_files_by_name():
    shape_the_scmoe_cell_resolves_to_files_by_name(MANIFEST)


def test_the_cell_reports_its_metrics_and_the_two_end_to_end():
    shape_the_scmoe_cell_reports_its_metrics_and_the_two_end_to_end(
        MANIFEST)


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_config_holds_the_published_value(resolved, key):
    assert resolved['config'][key] == PUBLISHED[key]
    assert type(resolved['config'][key]) is type(PUBLISHED[key])


@pytest.mark.parametrize('key', sorted(CUT))
def test_config_states_each_cut_beside_the_published_value(resolved, key):
    config = resolved['config']
    held, published = CUT[key]
    assert config[key] == held and config['published'][key] == published
    assert key in config['reduced']


def test_config_is_the_catalog_row_but_for_the_cut(resolved):
    """Where the catalog is installed: every key of its ``config`` is in
    the file under the same name with the same value, but the three that
    are cut; and the table above is that row."""
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.isfile(catalog):
        pytest.skip('no catalog here')
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r['name'] == 'LongCat-Flash-Chat']
    config = resolved['config']
    assert config['source'] == row['source_url']
    differs = {k for k, v in row['config'].items() if config.get(k) != v}
    assert differs == set(CUT)
    assert {k: v for k, v in row['config'].items() if k not in CUT} \
        == PUBLISHED
    assert {k: row['config'][k] for k in CUT} == \
        {k: v[1] for k, v in CUT.items()}


def test_no_width_is_reduced(resolved):
    widths = [k for k in resolved['config']['reduced']
              if k.endswith(('_dim', '_rank', '_size')) and
              k != 'vocab_size' or k == 'moe_topk']
    assert widths == []


def test_config_states_the_deployment_and_what_it_assumes(resolved):
    config = resolved['config']
    for said in ('32 that share each layer', 'layers 0-3 of 28',
                 'identity term is computed here in whole',
                 'chip that owns the row', '32 times their share',
                 '4 of 28 layers'):
        assert said in config['deployment'], said
    assert config['first_expert'] == 0
    # the guide's floors for a cut: four layers, 8 experts, 1/8 vocabulary
    assert config['num_layers'] >= 4 and config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= config['published']['vocab_size']
    for word in ('scope', 'absent_keys', 'block', 'attention', 'rotary',
                 'router', 'weights', 'precision', 'geometry', 'sampling'):
        assert len(config['assumed'][word]) > 40 or word == 'sampling'
    for key in ('tie_word_embeddings', 'norm_topk_prob', 'router_bias'):
        assert key in config['assumed']['absent_keys']
        assert key not in config
    assert 'with the second FFN' in config['assumed']['block']
    assert 'interleaved' in config['assumed']['rotary']
    geometry = config['engine']
    assert geometry['pages_per_seq'] * geometry['block_size'] >= \
        4096 + 2048 == geometry['max_prompt_len'] + 2048
    assert (geometry['max_batch'], geometry['prefill_chunk']) == (64, 512)
    assert geometry['prefix_cache'] is False and geometry['spec_k'] == 0
    limits = config['reference']
    assert limits['long_requests'] >= 1 and limits['requests'] == 5
    assert limits['long_tokens'] == 4096 and limits['pad_to'] == 1024
    assert 0 < limits['logit_gap_tol'] < limits['logit_gap_cap']
    assert 0 < limits['gap_outlier_share_tol'] < 0.5
    # each planted fault is named with its reading
    for fault in ('identity', 'scale_routed', 'raw_weights',
                  'shortcut_last', 'own_rows', 'float8', 'bfloat16'):
        assert fault in limits['note'], fault


def test_parameters_add_up_to_the_stated_cut(resolved):
    """ISSUE 49's arithmetic, recounted from the program's own parameter
    table: one latent attention 90.57 M, one dense FFN 226.49 M, the
    router 4.72 M, a layer outside its experts 638.8 M; a routed expert
    37.75 M and 16 of them 604.0 M; a layer here 1,242.8 M; embedding +
    head 201.3 M: 5.17 B, 10.35 GB in bfloat16."""
    from paddle_tpu.serving.decode.model import (arena_bytes,
                                                 block_param_shapes,
                                                 kv_bytes_per_token)
    spec = _module('runners', 'serve_scmoe').spec_of(resolved['config'])
    shapes = block_param_shapes(spec)

    def millions(*prefixes):
        return sum(int(np.prod(shape)) for name, (shape, _, _) in
                   shapes.items() if name.startswith(prefixes)
                   and len(shape) > 2) / 1e6
    layers, sub = 4, 8
    attention = millions('lm_full_') / sub
    assert round(attention, 2) == 90.57
    np.testing.assert_allclose(
        [6144 * 1536 / 1e6, 1536 * 64 * 192 / 1e6, 6144 * 576 / 1e6,
         2 * 64 * 128 * 512 / 1e6, 64 * 128 * 6144 / 1e6],
        [9.437, 18.874, 3.539, 8.389, 50.332], atol=0.0006)
    dense = millions('lm_dense_') / sub
    assert round(dense, 2) == 226.49
    router = millions('lm_moe_router.w') / layers
    assert round(router, 2) == 4.72
    assert round(2 * attention + 2 * dense + router, 1) == 638.8
    routed = millions('lm_moe_exp_') / layers
    assert round(routed / 16, 2) == 37.75 and round(routed, 1) == 604.0
    assert round(2 * attention + 2 * dense + router + routed, 1) == 1242.8
    emb = sum(int(np.prod(shapes[n][0])) for n in ('lm_emb', 'lm_head.w'))
    assert round(emb / 1e6, 1) == 201.3
    total = sum(int(np.prod(shape)) for shape, _, _ in shapes.values())
    assert round(total / 1e9, 2) == 5.17
    matrices = sum(int(np.prod(shape)) for shape, fan_in, _ in
                   shapes.values() if fan_in)
    assert round(matrices * 2 / 1e9, 2) == 10.35
    assert not [n for n in shapes if 'shr' in n or 'idx' in n] and \
        'lm_full_gate.w' not in shapes
    # the cache: one kind, 8 cache layers of 576 values stored 640,
    # 10,240 B a token; 8,192 pages of 32 = 2.68 GB
    assert kv_bytes_per_token(spec, 'bfloat16') == 10240
    geometry = resolved['config']['engine']
    assert geometry['num_blocks'] * geometry['block_size'] == 262144
    assert round(arena_bytes(spec, geometry['num_blocks'],
                             geometry['block_size'], 'bfloat16') / 1e9,
                 2) == 2.68


# ------------------------------------------------------ the runner
def test_runner_builds_the_block_the_config_describes(resolved):
    runner = _module('runners', 'serve_scmoe')
    spec = runner.spec_of(_sized(resolved['config']))
    assert (spec.block, spec.n_layer, spec.sublayers, spec.d_model,
            spec.d_inner, spec.d_inner_dense, spec.dense_layers) == \
        ('shortcut_moe', 4, 2, 6144, 2048, 12288, 0)
    assert spec.layer_plan() == ((), (FULL,), 4, ())
    shape = spec.latent[FULL]
    assert (shape.n_head, shape.q_rank, shape.kv_rank, shape.d_nope,
            shape.d_rope, shape.d_v, shape.rope_theta) == \
        (64, 1536, 512, 128, 64, 128, 1e7)
    assert shape.rope_scaling is None and shape.softmax_multiplier() == 1.0
    assert (spec.index_topk, spec.lora_rescale, spec.attn_gate,
            spec.routed_scale) == (0, True, False, 6.0)
    assert (spec.n_experts, spec.zero_experts, spec.experts_held,
            spec.first_expert, spec.experts_per_token,
            spec.n_shared_experts) == (512, 256, 16, 0, 12, 0)
    assert spec.vocab_size == 16384 and spec.dtype == 'bfloat16'
    assert [(k.name, k.layers, k.width, k.stored, k.reads)
            for k in spec.cache_kinds()] == [
        ('lm_latent_full', tuple(range(8)), 576, 640, (0,) * 8)]
    assert not spec.shares_frozen_pages()
    reference = _module('references', CONFIG)
    arch = reference.arch_of(spec)
    assert (arch['top_k'], arch['routed_scale'], arch['n_real'],
            arch['n_layer']) == (12, 6.0, 512, 4)
    for switch in ('identity', 'scale_routed', 'raw_weights',
                   'shortcut_last', 'own_rows'):
        assert arch[switch] is True
    assert arch['state_dtype'] == 'float32'
    assert reference.held_of(spec) == (0, 16)
    for wrong in (dict(attention_method='GQA'), dict(attention_bias=True),
                  dict(zero_expert_type='zero'),
                  dict(mla_scale_q_lora=False),
                  dict(rope_scaling={'type': 'yarn', 'factor': 4})):
        with pytest.raises(ValueError, match='not the block'):
            runner.spec_of(dict(_sized(resolved['config']), **wrong))


def test_the_benchmarks_reference_is_the_repositorys_copy():
    with open(os.path.join(BENCH, 'references', CONFIG + '.py')) as f:
        mine = f.read()
    with open(os.path.join(REPO, 'paddle_tpu', 'models', 'reference',
                           CONFIG + '.py')) as f:
        theirs = f.read()
    assert mine == theirs
    assert 'paddle_tpu' not in [
        line.split()[1].split('.')[0] for line in mine.split('\n')
        if line.startswith(('import ', 'from '))]


# ------------------------------------------------------- the traffic
def test_the_trace_is_the_fixed_one_the_traffic_file_describes(resolved):
    traffic = _sized(resolved['traffic'])
    assert (traffic['prompt_len'], traffic['answer_len'], traffic['alpha'],
            traffic['pool_seed']) == ([256, 4096], [128, 2048], 1.3, 49)
    a = loadgen.schedule(traffic, 5, 51.0)
    b = loadgen.schedule(traffic, 3400000049, 51.0)
    assert [(r.due, r.prompt_len, r.answer_len) for r in a] == \
        [(r.due, r.prompt_len, r.answer_len) for r in b]
    assert [r.token_seed for r in a] != [r.token_seed for r in b]
    preroll = traffic['preroll_s']
    window = [r for r in a if r.due >= preroll]
    assert len(window) == int(round(traffic['rate_rps'] * 51.0)) >= 60
    assert all(256 <= r.prompt_len <= 4096 and 128 <= r.answer_len <= 2048
               for r in a)
    # decode-heavy: the mean prompt under two chunks of 512, answers of
    # some hundred tokens with a tail to two thousand
    prompts = np.mean([r.prompt_len for r in window])
    answers = np.mean([r.answer_len for r in window])
    assert 600 < prompts < 1300 and 300 < answers < 700
    assert max(r.answer_len for r in window) > 1500
    # what the held sample needs of the trace: a request past 4,096
    # tokens in its life that ends inside the window and its drain
    limits = resolved['config']['reference']
    assert [r for r in window if r.prompt_len + r.answer_len
            > limits['long_tokens']]
    # the one-at-a-time check finds its short answers
    assert sum(1 for r in window if r.answer_len
               <= traffic['recheck_max_answer']) >= \
        traffic['recheck_requests']
    # a sequence's capacity covers the longest life
    geometry = resolved['config']['engine']
    assert max(r.prompt_len + r.answer_len for r in a) <= \
        geometry['pages_per_seq'] * geometry['block_size']


# --------------------------------------- the shape function, the reader
def _registry(counters, histograms):
    return {'counters': dict(counters), 'gauges': {}, 'histograms': {
        name: {'count': n, 'sum': total, 'mean': total / n}
        for name, (n, total) in histograms.items()}}


def test_step_bytes_are_the_weights_once_the_touched_experts_and_the_rows(
        resolved):
    shapes = _module('shape_fns', 'scmoe_decode_live_bytes')
    config = resolved['config']
    assert shapes.expert_bytes(config) == 3 * 6144 * 2048 * 2
    assert shapes.attention_params(config) == 90570752
    # nothing touched: the replicated weights and the head, 5.1 GB + 0.2
    bare = shapes.weight_bytes(config, 0)
    assert round((bare - 16384 * 6144 * 2) / 1e9, 2) == 5.11
    assert shapes.weight_bytes(config, 8) - bare == \
        4 * 8 * shapes.expert_bytes(config)
    # 40 live rows at 1,200 tokens: 8 cache layers x 576 x 2 B a token
    assert shapes.live_cache_bytes(config, 48000) == 48000 * 9216
    before = _registry({'decode.moe_layer_steps': 0,
                        'decode.moe_experts_touched': 0},
                       {'decode.step_seconds': (1, 0.0),
                        'decode.step_live_tokens': (1, 0.0)})
    after = _registry({'decode.moe_layer_steps': 400,
                       'decode.moe_experts_touched': 3200},
                      {'decode.step_seconds': (101, 1.5),
                       'decode.step_live_tokens': (101, 4800000.0)})
    sources = {'registry_before': before, 'registry_after': after,
               'config': config}
    per_second = shapes.compute(sources)
    np.testing.assert_allclose(
        per_second, (shapes.weight_bytes(config, 8.0)
                     + 48000 * 9216) / 0.015, rtol=1e-9)
    # against the HBM peak: under 100
    peak = manifest.read_json(os.path.join(BENCH, 'peaks.json'))[
        'devices']['TPU v5 lite']
    assert 0 < 100 * per_second / peak['hbm_bytes_per_s'] < 100
    # a program without the counters gives nothing to read
    assert shapes.compute(dict(sources, registry_after=before)) is None


def test_the_routed_products_roofline_counts_the_touched_experts(resolved):
    reader = _module('readers', 'scmoe_moe_ffn_roofline')
    config = resolved['config']
    assert reader.least_bytes_per_step(config, 8.0) == \
        4 * 8.0 * 3 * 6144 * 2048 * 2
    assert reader.least_bytes_per_step(config, 0) == 0
    step = ('decode.step', 1000, 10000)
    device = [('%fusion.1 = bf16[4,16,6144,2048] kernel', 2000, 3000),
              ('%fusion.2 = f32[64,6144] other', 6000, 1000),
              ('%fusion.3 = bf16[4,16,2048,6144] late', 20000, 5000)]
    spec = resolved_metric(resolved, 'serve.scmoe_moe_ffn_roofline_share')
    tail = _registry({'decode.moe_layer_steps': 0,
                      'decode.moe_experts_touched': 0}, {})
    after = _registry({'decode.moe_layer_steps': 4,
                       'decode.moe_experts_touched': 32}, {})
    sources = {'trace': {'first': device, 'host': [step],
                         'window': (0, 30000)},
               'peaks': {'hbm_bytes_per_s': 819e9}, 'registry_tail': tail,
               'registry_after': after, 'config': config}
    got = reader.read(spec['args'], sources)
    want = 100.0 * (4 * 8 * 3 * 6144 * 2048 * 2 / 819e9) / 3000e-9
    np.testing.assert_allclose(got, want, rtol=1e-9)
    # nothing to read without the tail's snapshot or the counters
    assert reader.read(spec['args'], dict(sources, registry_tail=None)) \
        is None
    assert reader.read(spec['args'], dict(sources, registry_after=tail)) \
        is None


def test_trace_patterns_are_the_configs_numbers(resolved):
    """The patterns of the device-trace readers name this cell's shapes:
    derived here from the configuration and the pair loop's rows."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    config = resolved['config']
    geometry = config['engine']
    attn = resolved_metric(resolved, 'serve.scmoe_attn_busy_share')
    text = ' '.join(attn['args']['match'])
    heads, rank = config['num_attention_heads'], config['kv_lora_rank']
    stored = -(-(rank + config['qk_rope_head_dim']) // 128) * 128
    assert 'bf16\\[[\\d,]*,%d\\]' % stored in text
    assert 'f32\\[%d,1,%d,1,%d' % (pa.BLOCK_ROWS, heads, rank) in text
    buckets, b = [], geometry['min_prompt_bucket']
    while b <= geometry['prefill_chunk']:
        buckets.append(str(b))
        b *= 2
    assert 'f32\\[(1,1,)?%d,(%s),%d\\]' % (heads, '|'.join(buckets),
                                            rank) in text
    assert 'f32\\[%d,%d,%d\\]' % (geometry['max_batch'] + pa.BLOCK_ROWS,
                                  heads, rank) in text
    assert 'f32\\[%d,%d\\]' % (pa.BLOCK_ROWS, heads) in text
    assert resolved_metric(
        resolved, 'serve.scmoe_decode_attn_roofline_share')['args'][
            'match'] == attn['args']['match']
    roof = resolved_metric(resolved,
                           'serve.scmoe_decode_attn_roofline_share')['args']
    assert roof['function_args'] == {'kinds': ['lm_latent_full']}
    assert geometry['max_batch'] != pa.BLOCK_ROWS
    d, f, fd = (config['hidden_size'], config['expert_ffn_hidden_size'],
                config['ffn_hidden_size'])
    layers, held = config['num_layers'], config['n_routed_experts']
    for name in ('serve.scmoe_moe_ffn_busy_share',
                 'serve.scmoe_moe_ffn_roofline_share'):
        (pattern,) = resolved_metric(resolved, name)['args']['match']
        assert 'bf16\\[%d,%d,(%d,%d|%d,%d)\\]' % (layers, held, d, f, f, d) \
            in pattern
    (pattern,) = resolved_metric(
        resolved, 'serve.scmoe_dense_ffn_busy_share')['args']['match']
    assert 'bf16\\[%d,(%d,%d|%d,%d)\\]' % (2 * layers, d, fd, fd, d) \
        in pattern
    # each finds its own stacks and not the others'
    lines = {'moe': '%k = f32[64,6144] custom-call(bf16[4,16,6144,2048] %a)',
             'dense': '%f = f32[64,12288] fusion(bf16[8,6144,12288] %b)',
             'attn': '%g = bf16[8,8192,32,640] dynamic-update-slice(...)'}
    for name, line in (('serve.scmoe_moe_ffn_busy_share', 'moe'),
                       ('serve.scmoe_dense_ffn_busy_share', 'dense'),
                       ('serve.scmoe_attn_busy_share', 'attn')):
        patterns = resolved_metric(resolved, name)['args']['match']
        for key, text in lines.items():
            assert any(re.search(p, text) for p in patterns) == \
                (key == line), (name, key)
    # a loop's own line is never counted: its body's ops are
    loop = '%while.3 = (bf16[8,8192,32,640]) while(...)'
    assert not any(re.search(p, loop) for p in attn['args']['match'])


def test_counter_entries_read_the_programs_counters(resolved):
    zero = resolved_metric(resolved, 'serve.scmoe_zero_assignment_pct')
    assert zero['reader'] == 'registry_ratio' and zero['args'] == {
        'counter': 'decode.moe_zero_assignments',
        'per': 'decode.moe_assignments', 'scale': 100}
    real = resolved_metric(resolved, 'serve.scmoe_real_experts_per_token')
    assert real['reader'] == 'registry_mean' and real['args'] == {
        'histogram': 'decode.moe_real_experts_per_token'}
    before = _registry({'decode.moe_zero_assignments': 10,
                        'decode.moe_assignments': 30}, {})
    after = _registry({'decode.moe_zero_assignments': 410,
                       'decode.moe_assignments': 1230}, {})
    got = _module('readers', 'registry_ratio').read(
        zero['args'], {'registry_before': before, 'registry_after': after})
    np.testing.assert_allclose(got, 100 * 400 / 1200.0)


# ------------------------------------------------------------ rehearsal
def test_the_cell_rehearses_end_to_end_on_the_cpu():
    """The harness and the cell's files at the rehearsal sizes: correct
    against the reference, the counters this PR adds read through their
    entries (a third of the choices identity, as the tiny router has 8
    of 12 outputs real)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload', CELL,
         '--seed', '3400000049', '--seconds', '2', '--trace', '1',
         '--rehearsal'], cwd=REPO, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().split('\n')
    assert lines[0].startswith('REHEARSAL platform=cpu')
    line = json.loads(lines[-1])
    assert line['correct'] is True and line['failed'] == 0
    assert line['rehearsal'] is True and line['attempted'] >= 6
    metrics = line['metrics']
    assert 10 < metrics['serve.scmoe_zero_assignment_pct']['value'] < 60
    assert 0 < metrics['serve.scmoe_real_experts_per_token']['value'] < 6
    assert metrics['serve.recompiles']['value'] == 0
    # a time is never reported from a CPU
    assert metrics['serve.decode_step_ms']['value'] is None
