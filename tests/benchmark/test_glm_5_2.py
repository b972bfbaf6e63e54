"""The ``glm_5_2`` configuration and its cell, off the chip: the file
holds the published config with the cut beside it, its parameters add
up to the stated cut, the runner builds the block it describes (a
carried selection: two scoring layers, three that share), the trace is
the fixed one the traffic file describes, the shape function and the
reader this PR brings do their arithmetic, the trace patterns are the
configuration's numbers, the benchmark's copy of the plain reference is
the repository's, and the cell rehearses end to end on the CPU. Entries
are found by name and by membership, never by place or count. No test
here describes a TPU topology."""

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
CONFIG = 'glm_5_2'
CELL = 'glm_5_2.long_ctx_long_answers'
BENCH = os.path.join(REPO, 'benchmark')
FULL, CARRIED = 'full_attention', 'carried_selection'

# the widths of config.json of zai-org/GLM-5.2 and the keys the runner
# reads, as published (the whole row is held to the catalog below)
PUBLISHED = {
    'hidden_size': 6144, 'num_attention_heads': 64,
    'num_key_value_heads': 64, 'q_lora_rank': 2048, 'kv_lora_rank': 512,
    'qk_nope_head_dim': 192, 'qk_rope_head_dim': 64, 'qk_head_dim': 256,
    'head_dim': 192, 'v_head_dim': 256, 'index_n_heads': 32,
    'index_head_dim': 128, 'index_topk': 2048, 'index_topk_freq': 4,
    'index_skip_topk_offset': 3, 'indexer_rope_interleave': True,
    'rope_interleave': True, 'moe_intermediate_size': 2048,
    'intermediate_size': 12288, 'num_experts_per_tok': 8,
    'n_shared_experts': 1, 'routed_scaling_factor': 2.5,
    'first_k_dense_replace': 3, 'scoring_func': 'sigmoid',
    'topk_method': 'noaux_tc', 'norm_topk_prob': True, 'n_group': 1,
    'topk_group': 1, 'rms_norm_eps': 1e-05, 'model_type': 'glm_moe_dsa',
    'num_nextn_predict_layers': 1, 'index_share_for_mtp_iteration': True,
    'max_position_embeddings': 1048576, 'tie_word_embeddings': False}
CUT = {'num_hidden_layers': (5, 78), 'n_routed_experts': (16, 256),
       'vocab_size': (19360, 154880)}
OWN_METRICS = {
    'serve.dsa_carried_selection_share', 'serve.dsa_indexer_busy_share',
    'serve.dsa_indexer_roofline_share', 'serve.dsa_attn_busy_share',
    'serve.dsa_decode_attn_roofline_share',
    'serve.dsa_prefill_attn_mxu_share', 'serve.dsa_moe_ffn_busy_share',
    'serve.dsa_moe_ffn_roofline_share', 'serve.dsa_step_hbm_share'}
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
    'serve.moe_row_tiles_run_share', 'serve.steps_ahead_share',
    # the selection's two counters, which this cell's engine feeds as
    # dots3_note's does
    'serve.sparse_selected_share', 'serve.sparse_live_row_share'}
# the mechanisms the configuration lacks, and the other configurations'
# own entries: left off
ABSENT = ('serve.window_', 'serve.prefix_', 'serve.ssm_', 'serve.indexer_',
          'serve.mla_', 'serve.latent_', 'serve.gqa_', 'serve.scmoe_')


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
def shape_the_dsa_cell_resolves_to_files_by_name(m):
    assert manifest.problems(m) == []
    r = manifest.resolve(m, CELL)
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_dsa'
    assert r['cell']['chips'] == 1 and r['cell']['traffic'] == \
        'long_ctx_long_answers'
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


def shape_the_dsa_cell_reports_its_metrics_and_the_two_end_to_end(m):
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
            assert metric['moves'] == (
                'ttft_mean_ms' if 'prefill' in metric['name']
                else 'itl_mean_ms')
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in m['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e
    for metric in m['per_layer']:
        if 'roofline' in metric['name'] or 'mfu' in metric['name']:
            assert 'workloads' in metric
    # all cells take one chip
    assert all(c['chips'] == 1 for c in m['workloads'])


def test_the_cell_resolves_to_files_by_name():
    shape_the_dsa_cell_resolves_to_files_by_name(MANIFEST)


def test_the_cell_reports_its_metrics_and_the_two_end_to_end():
    shape_the_dsa_cell_reports_its_metrics_and_the_two_end_to_end(MANIFEST)


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
    the file under the same name with the same value (the per-layer
    lists and ``rope_parameters`` whole), but the three that are cut."""
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.isfile(catalog):
        pytest.skip('no catalog here')
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r['name'] == 'GLM-5.2']
    config = resolved['config']
    assert config['source'] == row['source_url']
    differs = {k for k, v in row['config'].items() if config.get(k) != v}
    assert differs == set(CUT)
    assert {k: row['config'][k] for k in PUBLISHED} == PUBLISHED
    assert {k: row['config'][k] for k in CUT} == \
        {k: v[1] for k, v in CUT.items()}


def test_the_per_layer_lists_are_whole_and_the_run_reads_its_five(resolved):
    config = resolved['config']
    period = ['shared', 'shared', 'shared', 'full']
    assert config['indexer_types'] == ['full'] * 3 + period * 18 + \
        ['shared'] * 3
    assert config['mlp_layer_types'] == ['dense'] * 3 + ['sparse'] * 75
    first, depth = config['first_layer'], config['num_hidden_layers']
    assert (first, depth) == (2, 5)
    assert config['indexer_types'][first:first + depth] == ['full'] + period
    assert config['mlp_layer_types'][first:first + depth] == \
        ['dense'] + ['sparse'] * 4
    assert config['rope_parameters'] == {'rope_theta': 8000000,
                                         'rope_type': 'default'}


def test_no_width_is_reduced(resolved):
    widths = [k for k in resolved['config']['reduced']
              if k.endswith(('_dim', '_rank', '_size')) and
              k != 'vocab_size' or k == 'num_experts_per_tok']
    assert widths == []


def test_config_states_the_deployment_and_what_it_assumes(resolved):
    config = resolved['config']
    for said in ('16 that share each layer', 'layers 2-6 of 78',
                 'split 8 ways by rows', '73 layers would lie on further',
                 '16 times their share', '5 of 78 layers', '3,881 M',
                 'No code stands in for the absent chips'):
        assert said in config['deployment'], said
    assert config['first_expert'] == 0
    # the guide's floors for a cut: a whole period and four layers past
    # the leading ones, 8 experts, 1/8 of the vocabulary
    assert config['num_hidden_layers'] - 1 >= 4
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= config['published']['vocab_size']
    for word in ('scope', 'block', 'indexer', 'rotary', 'router', 'weights',
                 'precision', 'geometry', 'sampling'):
        assert len(config['assumed'][word]) > 40 or word == 'sampling'
    assert 'multi-token-prediction' in config['assumed']['scope']
    assert 'the list decides' in config['assumed']['indexer']
    assert 'interleaved' in config['assumed']['rotary']
    geometry = config['engine']
    assert geometry['pages_per_seq'] * geometry['block_size'] == \
        34816 == geometry['max_prompt_len'] + 2048
    assert geometry['num_blocks'] >= 12 * geometry['pages_per_seq']
    assert (geometry['max_batch'], geometry['prefill_chunk'],
            geometry['block_size']) == (16, 512, 32)
    assert geometry['prefix_cache'] is False and geometry['spec_k'] == 0
    limits = config['reference']
    assert limits['long_requests'] >= 1 and limits['long_tokens'] == 16384
    assert limits['pad_to'] == 1024
    assert 0 < limits['logit_gap_tol'] < limits['logit_gap_cap']
    assert 0 < limits['gap_outlier_share_tol'] < 0.5
    # each control is named with its reading
    for control in ('float8', 'no carry', 'all positions', 'bfloat16'):
        assert control in limits['note'], control


def test_parameters_add_up_to_the_stated_cut(resolved):
    """ISSUE 54's arithmetic, recounted from the program's own parameter
    table: attention 165.02 M a layer, an indexer 9.37 M, the dense FFN
    226.49 M, the shared expert and each routed expert 37.75 M, the
    router 1.57 M, 16 routed experts 603.98 M, embedding + head 237.90
    M: 3,881 M, 7.76 GB in bfloat16; and the arenas: 6,912 B a token."""
    from paddle_tpu.serving.decode.model import (arena_bytes,
                                                 block_param_shapes,
                                                 kv_bytes_per_kind,
                                                 kv_bytes_per_token)
    spec = _module('runners', 'serve_dsa').spec_of(_sized(resolved['config']))
    shapes = block_param_shapes(spec)

    def millions(*prefixes):
        return sum(int(np.prod(shape)) for name, (shape, _, _) in
                   shapes.items() if name.startswith(prefixes)
                   and len(shape) > 2) / 1e6
    index = millions('lm_full_idx_')
    attention = (millions('lm_full_') - index) / 5
    assert round(attention, 2) == 165.02
    np.testing.assert_allclose(
        [6144 * 2048 / 1e6, 2048 * 64 * 256 / 1e6, 6144 * 576 / 1e6,
         512 * 64 * (192 + 256) / 1e6, 64 * 256 * 6144 / 1e6],
        [12.58, 33.55, 3.54, 14.68, 100.66], atol=0.006)
    assert round(index / 2, 2) == 9.37       # two scoring layers
    assert shapes['lm_full_idx_q.w'][0][0] == 2
    assert shapes['lm_full_q_a.w'][0][0] == 5
    dense = millions('lm_dense_')
    assert round(dense, 2) == 226.49
    router = millions('lm_moe_router.w') / 4
    assert round(router, 2) == 1.57
    routed = millions('lm_moe_exp_') / 4
    shared = millions('lm_moe_shr_') / 4
    assert round(routed / 16, 2) == round(shared, 2) == 37.75
    assert round(routed, 2) == 603.98
    assert round(attention + index / 2 + dense, 2) == 400.88
    assert round(attention + shared + router + routed, 2) == 808.32
    assert round(attention + index / 2 + shared + router + routed, 2) \
        == 817.69
    emb = sum(int(np.prod(shapes[n][0])) for n in ('lm_emb', 'lm_head.w'))
    assert round(emb / 1e6, 2) == 237.90
    matrices = sum(int(np.prod(shape)) for shape, fan_in, _ in
                   shapes.values() if fan_in)
    assert round(matrices / 1e6) == 3881
    assert round(matrices * 2 / 1e9, 2) == 7.76
    assert 'lm_full_gate.w' not in shapes and \
        not [n for n in shapes if n.startswith('lm_swa_')]
    # the cache: five latent layers stored 640 wide, two index layers
    assert kv_bytes_per_kind(spec, 'bfloat16') == {
        'lm_latent_full': 5 * 640 * 2, 'lm_index_full': 2 * 128 * 2}
    assert kv_bytes_per_token(spec, 'bfloat16') == 6912
    geometry = resolved['config']['engine']
    tokens = geometry['num_blocks'] * geometry['block_size']
    assert tokens == geometry['max_batch'] * 34816 == 557056
    assert round(arena_bytes(spec, geometry['num_blocks'],
                             geometry['block_size'], 'bfloat16') / 1e9,
                 2) == 3.85


# ------------------------------------------------------ the runner
def test_runner_builds_the_block_the_config_describes(resolved):
    runner = _module('runners', 'serve_dsa')
    spec = runner.spec_of(_sized(resolved['config']))
    assert (spec.block, spec.n_layer, spec.sublayers, spec.d_model,
            spec.d_inner, spec.d_inner_dense, spec.dense_layers) == \
        ('latent_moe', 5, 1, 6144, 2048, 12288, 1)
    assert spec.layer_types == (FULL,) * 5
    assert spec.indexer_types == ('full', 'shared', 'shared', 'shared',
                                  'full')
    assert spec.layer_plan() == ((FULL,), (CARRIED,) * 3 + (FULL,), 1, ())
    assert spec.scoring_layers() == (0, 4)
    shape = spec.latent[FULL]
    assert (shape.n_head, shape.q_rank, shape.kv_rank, shape.d_nope,
            shape.d_rope, shape.d_v, shape.rope_theta) == \
        (64, 2048, 512, 192, 64, 256, 8e6)
    assert shape.rope_scaling is None and shape.softmax_multiplier() == 1.0
    assert (spec.index_n_heads, spec.index_head_dim, spec.index_topk,
            spec.index_rope_interleave) == (32, 128, 2048, True)
    assert (spec.lora_rescale, spec.attn_gate, spec.routed_scale) == \
        (False, False, 2.5)
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.experts_per_token, spec.n_shared_experts) == \
        (256, 16, 0, 8, 1)
    assert spec.vocab_size == 19360 and spec.dtype == 'bfloat16'
    assert [(k.name, k.layers, k.width, k.stored, k.reads)
            for k in spec.cache_kinds()] == [
        ('lm_latent_full', (0, 1, 2, 3, 4), 576, 640, (2048,) * 5),
        ('lm_index_full', (0, 4), 128, 128, (0, 0))]
    # refused by the same properties as for dots3_note
    assert not spec.shares_frozen_pages() and not spec.per_head_cache()
    reference = _module('references', CONFIG)
    arch = reference.arch_of(spec)
    assert (arch['top_k'], arch['routed_scale'], arch['index_topk'],
            arch['indexer_types']) == (8, 2.5, 2048, list(
                spec.indexer_types))
    assert arch['select'] is True and arch['carry'] is True
    assert arch['state_dtype'] == 'float32'
    assert reference.held_of(spec) == (0, 16)
    # the published 78 layers through the same reading
    whole = dict(_sized(resolved['config']), first_layer=0,
                 num_hidden_layers=78)
    assert runner.spec_of(whole).layer_plan() == (
        (FULL,) * 3, (CARRIED,) * 3 + (FULL,), 18, (CARRIED,) * 3)
    for wrong in (dict(model_type='dots3_note'), dict(attention_bias=True),
                  dict(scoring_func='softmax'), dict(n_group=8),
                  dict(rope_interleave=False), dict(index_topk_pattern=[1]),
                  dict(mlp_layer_types=['sparse'] * 78),
                  dict(rope_parameters={'rope_theta': 8e6,
                                        'rope_type': 'yarn'})):
        with pytest.raises(ValueError, match='serve_dsa'):
            runner.spec_of(dict(_sized(resolved['config']), **wrong))
    # a cut that starts at a layer which shares has no selection to reuse
    with pytest.raises(ValueError, match='indexer_types'):
        runner.spec_of(dict(_sized(resolved['config']), first_layer=3))


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
    assert 'precision=HIGHEST' in mine and 'lax.top_k' in mine


# ------------------------------------------------------- the traffic
def test_the_trace_is_the_fixed_one_the_traffic_file_describes(resolved):
    traffic = _sized(resolved['traffic'])
    assert (traffic['answer_len'], traffic['alpha'],
            traffic['pool_seed']) == ([256, 2048], 1.3, 54)
    lo, hi = traffic['prompt_len']
    # ISSUE 54's rule: to 32,768, or to 24,576 where four fifths of the
    # knee sent fewer than 24 requests into the window
    assert lo == 8192 and hi in (32768, 24576)
    a = loadgen.schedule(traffic, 5, 51.0)
    b = loadgen.schedule(traffic, 3400000054, 51.0)
    assert [(r.due, r.prompt_len, r.answer_len) for r in a] == \
        [(r.due, r.prompt_len, r.answer_len) for r in b]
    assert [r.token_seed for r in a] != [r.token_seed for r in b]
    preroll = traffic['preroll_s']
    window = [r for r in a if r.due >= preroll]
    assert len(window) == int(round(traffic['rate_rps'] * 51.0))
    if hi == 32768:
        assert len(window) >= 24
    assert all(lo <= r.prompt_len <= hi and 256 <= r.answer_len <= 2048
               for r in a)
    # every prompt at least four times index_topk: the selection keeps at
    # most a quarter of a row, in every decode step
    config = resolved['config']
    assert lo >= 4 * config['index_topk']
    prompts = np.mean([r.prompt_len for r in window])
    answers = np.mean([r.answer_len for r in window])
    assert 9000 < prompts < 16000 and 350 < answers < 900
    # what the held sample needs of the trace: a request past 16,384
    # tokens in its life
    limits = config['reference']
    assert [r for r in window if r.prompt_len + r.answer_len
            > limits['long_tokens']]
    assert sum(1 for r in window if r.answer_len
               <= traffic['recheck_max_answer']) >= \
        traffic['recheck_requests']
    # a sequence's capacity covers the longest life, and the pool every
    # slot at its longest
    geometry = config['engine']
    assert max(r.prompt_len + r.answer_len for r in a) <= \
        geometry['pages_per_seq'] * geometry['block_size']
    assert max(r.prompt_len for r in a) <= geometry['max_prompt_len']
    for word in ('knee', 'four fifths', 'sweep'):
        assert word in resolved['traffic']['note'], word


# --------------------------------------- the shape function, the reader
def _registry(counters, histograms):
    return {'counters': dict(counters), 'gauges': {}, 'histograms': {
        name: {'count': n, 'sum': total, 'mean': total / n}
        for name, (n, total) in histograms.items()}}


def test_step_bytes_are_the_weights_once_the_touched_experts_and_the_rows(
        resolved):
    """``shape_fns/dsa_decode_live_bytes.py`` against a hand count."""
    shapes = _module('shape_fns', 'dsa_decode_live_bytes')
    config = resolved['config']
    assert shapes.layers_run(config) == (5, 2, 1)
    assert shapes.routed_layers(config) == 4
    assert shapes.expert_bytes(config) == 3 * 6144 * 2048 * 2
    assert shapes.attention_params(config) == 165019648
    assert shapes.indexer_params(config) == \
        2048 * 32 * 128 + 6144 * 128 + 6144 * 32 == 9371648
    # nothing touched: five attentions, two indexers, the dense FFN, four
    # routers and shared experts, the head; the embedding is not read
    bare = shapes.weight_bytes(config, 0)
    by_hand = 2 * (5 * 165019648 + 2 * 9371648 + 3 * 6144 * 12288
                   + 4 * 6144 * 256 + 4 * 3 * 6144 * 2048
                   + 19360 * 6144)
    gains = 4 * (5 * (2 * 6144 + 2048 + 512) + 2 * 2 * 128 + 4 * 256 + 6144)
    assert bare == by_hand + gains
    assert round(bare / 1e9, 2) == 2.69
    assert shapes.weight_bytes(config, 3.5) - bare == \
        4 * 3.5 * shapes.expert_bytes(config)
    # eight live rows of 15,000 positions: 2,048 latent rows of 576 in
    # five layers, every index key of 128 in two, bfloat16
    latent = 8 * 5 * 2048 * 576 * 2
    index = 8 * 2 * 15000 * 128 * 2
    steps = 100
    before = _registry({'decode.moe_layer_steps': 0,
                        'decode.moe_experts_touched': 0,
                        'decode.steps_total': 0,
                        'decode.cache_bytes_read{kind=lm_latent_full}': 0,
                        'decode.cache_bytes_read{kind=lm_index_full}': 0},
                       {'decode.step_seconds': (1, 0.0)})
    after = _registry({'decode.moe_layer_steps': 4 * steps,
                       'decode.moe_experts_touched': 4 * steps * 3.5,
                       'decode.steps_total': steps,
                       'decode.cache_bytes_read{kind=lm_latent_full}':
                       steps * latent,
                       'decode.cache_bytes_read{kind=lm_index_full}':
                       steps * index},
                      {'decode.step_seconds': (steps + 1, 1.6)})
    sources = {'registry_before': before, 'registry_after': after,
               'config': config}
    per_second = shapes.compute(sources)
    np.testing.assert_allclose(
        per_second, (shapes.weight_bytes(config, 3.5) + latent + index)
        / 0.016, rtol=1e-9)
    peak = manifest.read_json(os.path.join(BENCH, 'peaks.json'))[
        'devices']['TPU v5 lite']
    assert 0 < 100 * per_second / peak['hbm_bytes_per_s'] < 100
    # a program without the counters gives nothing to read
    assert shapes.compute(dict(sources, registry_after=before)) is None
    entry = resolved_metric(resolved, 'serve.dsa_step_hbm_share')
    assert entry['reader'] == 'shape_fn' and entry['args'] == {
        'function': 'dsa_decode_live_bytes', 'peak': 'hbm_bytes_per_s'}


def test_the_routed_products_roofline_counts_the_touched_experts(resolved):
    reader = _module('readers', 'dsa_moe_ffn_roofline')
    config = resolved['config']
    # the touched routed experts and the shared one, in four layers
    assert reader.least_bytes_per_step(config, 3.0) == \
        4 * (3.0 + 1) * 3 * 6144 * 2048 * 2
    assert reader.least_bytes_per_step(config, 0) == \
        4 * 3 * 6144 * 2048 * 2
    step = ('decode.step', 1000, 10000)
    device = [('%moe_routed_product.1 = f32[16,6144] custom-call('
               'bf16[4,16,6144,2048] %w)', 2000, 3000),
              ('%fusion.2 = f32[16,6144] fusion(bf16[4,1,6144,2048] %shr)',
               6000, 1000),
              ('%fusion.3 = bf16[4,16,2048,6144] late', 20000, 5000)]
    spec = resolved_metric(resolved, 'serve.dsa_moe_ffn_roofline_share')
    tail = _registry({'decode.moe_layer_steps': 0,
                      'decode.moe_experts_touched': 0}, {})
    after = _registry({'decode.moe_layer_steps': 4,
                       'decode.moe_experts_touched': 12}, {})
    sources = {'trace': {'first': device, 'host': [step],
                         'window': (0, 30000)},
               'peaks': {'hbm_bytes_per_s': 819e9}, 'registry_tail': tail,
               'registry_after': after, 'config': config}
    got = reader.read(spec['args'], sources)
    want = 100.0 * (4 * 4 * 3 * 6144 * 2048 * 2 / 819e9) / 4000e-9
    np.testing.assert_allclose(got, want, rtol=1e-9)
    assert reader.read(spec['args'], dict(sources, registry_tail=None)) \
        is None
    assert reader.read(spec['args'], dict(sources, registry_after=tail)) \
        is None


def test_trace_patterns_are_the_configs_numbers(resolved):
    """The patterns of the device-trace readers name this cell's shapes:
    derived here from the configuration, the engine's geometry and the
    pair loop's rows."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    config = resolved['config']
    geometry = config['engine']
    spec = _module('runners', 'serve_dsa').spec_of(_sized(config))
    stored = {k.name: k.stored for k in spec.cache_kinds()}
    layers = {k.name: len(k.layers) for k in spec.cache_kinds()}
    capacity = geometry['pages_per_seq'] * geometry['block_size']
    cols = geometry['block_size'] * pa.pages_per_block(
        geometry['pages_per_seq'], geometry['block_size'])
    heads, rank = spec.latent[FULL].n_head, spec.latent[FULL].kv_rank
    rows = '(%d|%d)' % (geometry['max_batch'], geometry['prefill_chunk'])
    buckets, b = [], geometry['min_prompt_bucket']
    while b <= geometry['prefill_chunk']:
        buckets.append(str(b))
        b *= 2
    skip = r'^(?!%?(while|conditional|call)[.\d]*( |=)).*'
    assert rank == cols
    # an expanded chunk's keys and values a head are as wide as each other
    wide = spec.latent[FULL].d_nope + spec.latent[FULL].d_rope
    assert wide == spec.latent[FULL].d_v
    attn = [skip + r'bf16\[[\d,]*,%d\]' % stored['lm_latent_full'],
            # a chunk's score blocks
            skip + r'f32\[(1,1,)?%d,(%s),%d\]' % (heads, '|'.join(buckets),
                                                  cols),
            # an expanded chunk's keys, values and accumulators a head
            skip + r'(f32|bf16)\[(1,1,)?%d,(%s),%d\]' % (
                heads, '|'.join(buckets), wide)]
    index = [skip + r'bf16\[%d,%d,%d,%d\]' % (
                 layers['lm_index_full'], geometry['num_blocks'],
                 geometry['block_size'], stored['lm_index_full']),
             skip + r'\[%s,(%d|%d,128)\]' % (rows, capacity,
                                             capacity // 128),
             skip + r'= f32\[%s,%d\]\S* fusion\(.*bf16\[%s,%d,%d\]' % (
                 rows, cols, rows, spec.index_n_heads,
                 spec.index_head_dim)]
    experts = [skip + r'bf16\[%d,(%d|%d),(%d,%d|%d,%d)\]' % (
        spec.n_layer - spec.dense_layers, spec.experts_held,
        spec.n_shared_experts, spec.d_model, spec.d_inner, spec.d_inner,
        spec.d_model)]
    assert layers == {'lm_latent_full': 5, 'lm_index_full': 2}
    for name, want in (
            ('serve.dsa_attn_busy_share', attn),
            ('serve.dsa_decode_attn_roofline_share', attn),
            ('serve.dsa_prefill_attn_mxu_share', attn),
            ('serve.dsa_indexer_busy_share', index),
            ('serve.dsa_indexer_roofline_share', index),
            ('serve.dsa_moe_ffn_busy_share', experts),
            ('serve.dsa_moe_ffn_roofline_share', experts)):
        assert resolved_metric(resolved, name)['args']['match'] == want, name
    assert resolved_metric(
        resolved, 'serve.dsa_decode_attn_roofline_share')['args'][
            'function_args'] == {'kinds': ['lm_latent_full']}
    assert resolved_metric(
        resolved, 'serve.dsa_indexer_roofline_share')['args'][
            'function_args'] == {'kinds': ['lm_index_full']}
    mxu = resolved_metric(resolved, 'serve.dsa_prefill_attn_mxu_share')
    assert mxu['reader'] == 'prefill_ops_mxu' and \
        mxu['args']['function'] == 'mla_prefill_attn_flops'
    flops = _module('shape_fns', 'mla_prefill_attn_flops')
    assert flops.least_flops(10, config) == 10 * 64 * 2 * (192 + 64 + 256)
    # each finds its own ops and not the others'
    lines = {
        'moe': '%moe_routed_product.8 = f32[16,6144]{1,0} custom-call('
               'bf16[4,16,6144,2048] %w)',
        'attn': '%paged_decode_attention.3 = f32[16,64,512]{2,1,0} '
                'custom-call(f32[16,64,640] %q, bf16[5,17408,32,640] %a)',
        'index': '%fusion.12 = bf16[2,17408,32,128]{3,2,1,0} fusion('
                 'bf16[2,17408,32,128] %arena, bf16[16,128] %rows)',
        'choice': '%or_select_fusion.1 = u32[16,34816]{1,0} fusion('
                  'f32[16,34816] %scores)',
        'shared': '%fusion.7 = f32[16,6144] fusion(bf16[4,1,6144,2048] %w)'}
    for name, mine in (('serve.dsa_moe_ffn_busy_share', {'moe', 'shared'}),
                       ('serve.dsa_attn_busy_share', {'attn'}),
                       ('serve.dsa_indexer_busy_share', {'index', 'choice'})):
        patterns = resolved_metric(resolved, name)['args']['match']
        for key, text in lines.items():
            assert any(re.search(p, text) for p in patterns) == \
                (key in mine), (name, key)
    # a loop's own line is never counted: its body's ops are
    loop = '%while.3 = (bf16[5,17408,32,640], bf16[2,17408,32,128]) while()'
    for name in ('serve.dsa_attn_busy_share', 'serve.dsa_indexer_busy_share'):
        assert not any(re.search(p, loop) for p in resolved_metric(
            resolved, name)['args']['match'])


def test_counter_entries_read_the_programs_counters(resolved):
    carried = resolved_metric(resolved, 'serve.dsa_carried_selection_share')
    assert carried['reader'] == 'registry_ratio' and carried['args'] == {
        'counter': 'decode.selection_layer_calls{how=carried}',
        'per': 'decode.selection_layer_calls', 'scale': 100}
    before = _registry({'decode.selection_layer_calls{how=carried}': 30,
                        'decode.selection_layer_calls{how=scored}': 20}, {})
    after = _registry({'decode.selection_layer_calls{how=carried}': 630,
                       'decode.selection_layer_calls{how=scored}': 420}, {})
    reader = _module('readers', 'registry_ratio')
    got = reader.read(carried['args'], {'registry_before': before,
                                        'registry_after': after})
    np.testing.assert_allclose(got, 60.0)
    # the parent has no such counter: nothing to read, and no raise
    assert reader.read(carried['args'], {
        'registry_before': _registry({}, {}),
        'registry_after': _registry({}, {})}) is None


@pytest.mark.parametrize('shared', ['serve.sparse_selected_share',
                                    'serve.sparse_live_row_share'])
def test_the_selection_shares_list_this_cell_beside_dots3_notes(shared):
    """The engine feeds these two entries' counters in this cell too
    (``decode.sparse_positions_*``, ``decode.sparse_rows*``), and
    ``test_benchmark.py`` allows no copy under a second name: both cells
    are on the one entry, which reads the program's own counters and
    carries no configuration's shapes."""
    (entry,) = [e for e in MANIFEST['per_layer'] if e['name'] == shared]
    assert {CELL, 'dots3_note.long_ctx_steady'} <= set(entry['workloads'])
    assert entry['moves'] == 'itl_mean_ms'
    spec = manifest.read_json(os.path.join(
        BENCH, 'layer_metrics', shared + '.json'))
    assert spec['reader'] == 'registry_ratio'
    assert spec['args']['counter'].startswith('decode.sparse_')


# ------------------------------------------------------------ rehearsal
def test_the_cell_rehearses_end_to_end_on_the_cpu():
    """The harness and the cell's files at the rehearsal sizes: correct
    against the reference, the counters this PR adds read through their
    entries (three of five layer-calls carried)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload', CELL,
         '--seed', '3400000054', '--seconds', '2', '--trace', '1',
         '--rehearsal'], cwd=REPO, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().split('\n')
    assert lines[0].startswith('REHEARSAL platform=cpu')
    line = json.loads(lines[-1])
    assert line['correct'] is True and line['failed'] == 0
    assert line['rehearsal'] is True and line['attempted'] >= 6
    metrics = line['metrics']
    assert metrics['serve.dsa_carried_selection_share']['value'] == 60.0
    # every toy prompt is past the toy index_topk: every live row is
    # sparse, and the selection keeps under all it holds
    assert metrics['serve.sparse_live_row_share']['value'] == 100.0
    assert 0 < metrics['serve.sparse_selected_share']['value'] < 100
    assert metrics['serve.recompiles']['value'] == 0
    # a time is never reported from a CPU
    assert metrics['serve.decode_step_ms']['value'] is None
