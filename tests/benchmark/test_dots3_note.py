"""The ``dots3_note`` configuration and its cell, off the chip: the file
holds the published config with the cut beside it, the runner builds the
block it describes, the shape function and the reader this PR brings do
their arithmetic, the benchmark's copy of the plain reference is the
repository's, and the cell rehearses end to end on the CPU. No test here
describes a TPU topology."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest              # noqa: E402
from benchmark import run as bench          # noqa: E402

MANIFEST = manifest.load(REPO)
CELL = 'dots3_note.long_ctx_steady'
BENCH = os.path.join(REPO, 'benchmark')
FULL, SLIDING = 'full_attention', 'sliding_attention'

# config.json of dots-studio/dots3-note-prev, every number of it, as
# published (the catalog row beside the model-configs guide)
PUBLISHED = {
    'hidden_size': 5120, 'intermediate_size': 13824,
    'moe_intermediate_size': 1536, 'num_attention_heads': 128,
    'num_key_value_heads': 128, 'q_lora_rank': 1024, 'kv_lora_rank': 512,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'v_head_dim': 128,
    'rope_theta': 80000000, 'swa_num_attention_heads': 64,
    'swa_num_key_value_heads': 64, 'swa_q_lora_rank': 1024,
    'swa_kv_lora_rank': 1024, 'swa_qk_nope_head_dim': 192,
    'swa_qk_rope_head_dim': 64, 'swa_v_head_dim': 128,
    'swa_rope_theta': 50000, 'sliding_window_size': 513,
    'index_head_dim': 128, 'index_n_heads': 64, 'index_topk': 2048,
    'num_experts_per_tok': 8, 'n_shared_experts': 1,
    'first_k_dense_replace': 1, 'moe_layer_freq': 1,
    'routed_scaling_factor': 1, 'rms_norm_eps': 1e-05,
    'max_position_embeddings': 524288}
STATED = {
    'apply_mla_qkv_lora_rescale': True, 'attention_bias': False,
    'attention_gate_type': 'headwise', 'swa_attention_gate_type': 'headwise',
    'hidden_act': 'silu', 'model_type': 'dots3_note', 'norm_topk_prob': True,
    'rope_scaling': None, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_method': 'noaux_tc'}
CUT = {'num_hidden_layers': (5, 46), 'n_routed_experts': (32, 256),
       'vocab_size': (19008, 152064)}
# the entries that carry this configuration's shapes or mechanisms
OWN_METRICS = {
    'serve.latent_attn_busy_share', 'serve.indexer_busy_share',
    'serve.latent_moe_ffn_busy_share', 'serve.latent_attn_roofline_share',
    'serve.indexer_roofline_share'}
# the shared readers of the step, the chunk, the queue, the pool, the
# worker, the batch, the tails, the load balance and the page bounds: the
# one entry a reader, which lists every serving cell that feeds it
SHARED_METRICS = {
    'serve.decode_step_ms', 'serve.prefill_chunk_ms', 'serve.queue_wait_ms',
    'serve.recompiles', 'serve.kv_pool_used_pct',
    'serve.prefill_chunks_per_prompt', 'serve.moe_local_assignment_pct',
    'serve.worker_prefill_share', 'serve.worker_step_share',
    'serve.worker_idle_share', 'serve.batch_occupancy', 'serve.ttft_p90_ms',
    'serve.itl_p95_ms', 'serve.tokens_per_s', 'serve.moe_load_max_over_mean',
    'serve.attn_pages_read_share',
    # what the cell's engine fed and no list could take until PR 42
    'serve.steps_ahead_share',
    'serve.idle_under_states_pct', 'serve.idle_in_device_empty_pct',
    'serve.device_empty_idle_share', 'serve.attn_pages_held_share',
    # the selection's two counters, which glm_5_2's engine feeds too, and
    # the expanded chunks' share, which kimi_k2_6's engine feeds too
    'serve.sparse_selected_share', 'serve.sparse_live_row_share',
    'serve.mla_prefill_expanded_chunk_share'}


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
    assert r['config']['runner'] == 'serve_latent'
    assert r['cell']['chips'] == 1
    assert r['config']['reference'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    (entry,) = [c for c in m['configs'] if c['name'] == 'dots3_note']
    assert entry['reduced'] == r['config']['reduced'] == list(CUT)
    assert len(entry['source']) <= 200 and len(r['cell']['why']) <= 200
    assert entry['source'].startswith(r['config']['source'])


def shape_the_cell_reports_its_metrics_and_the_two_end_to_end(m):
    """By name and by membership: the cell is on the list of each entry
    named here and on the two end-to-end lists, wherever on them; a
    later cell joins the same lists. The entries of its own shapes name
    no other configuration's cell."""
    resolved = manifest.resolve(m, CELL)
    mine = {p['entry']['name'] for p in resolved['per_layer']}
    assert mine >= OWN_METRICS | SHARED_METRICS
    for metric in m['per_layer']:
        if metric['name'] in OWN_METRICS:
            assert all(cell.startswith('dots3_note.')
                       for cell in metric['workloads'])
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in m['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e


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
    (row,) = [r for r in rows if r['name'] == 'dots3-note-prev']
    config = resolved['config']
    assert config['source'] == row['source_url']
    differs = {k for k, v in row['config'].items() if config.get(k) != v}
    assert differs == set(CUT)


def test_config_keeps_the_published_layer_list_and_runs_its_head(resolved):
    config = resolved['config']
    kinds = config['layer_types']
    assert len(kinds) == 46
    assert kinds == [FULL] + [FULL, SLIDING, SLIDING, SLIDING] * 11 + [FULL]
    assert kinds.count(FULL) == 13 and kinds.count(SLIDING) == 33
    # the cut: the leading dense layer and one whole period
    assert config['num_hidden_layers'] == 1 + 4
    assert '8' in config['deployment'] and config['first_expert'] == 0
    # the guide's floors for a cut: a period, 8 experts, 1/8 vocabulary
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= config['published']['vocab_size']
    for word in ('lora_rescale', 'gate', 'rotary', 'indexer', 'window',
                 'router', 'precision', 'geometry', 'scope'):
        assert config['assumed'][word]
    geometry = config['engine']
    assert geometry['pages_per_seq'] * geometry['block_size'] >= \
        geometry['max_prompt_len'] + 512
    assert not geometry['prefix_cache'] and geometry['spec_k'] == 0


# ------------------------------------------------------ the runner
def test_runner_builds_the_block_the_config_describes(resolved):
    runner = _module('runners', 'serve_latent')
    spec = runner.spec_of(resolved['config'])
    assert (spec.block, spec.n_layer, spec.d_model, spec.d_inner,
            spec.d_inner_dense, spec.dense_layers) == \
        ('latent_moe', 5, 5120, 1536, 13824, 1)
    assert spec.layer_types == (FULL, FULL, SLIDING, SLIDING, SLIDING)
    assert spec.layer_plan() == ((FULL,), (FULL, SLIDING, SLIDING, SLIDING),
                                 1, ())
    assert vars(spec.latent[FULL]) == dict(
        n_head=128, q_rank=1024, kv_rank=512, d_nope=128, d_rope=64,
        d_v=128, rope_theta=8e7)
    assert vars(spec.latent[SLIDING]) == dict(
        n_head=64, q_rank=1024, kv_rank=1024, d_nope=192, d_rope=64,
        d_v=128, rope_theta=5e4)
    assert (spec.index_n_heads, spec.index_head_dim, spec.index_topk,
            spec.sliding_window, spec.lora_rescale) == \
        (64, 128, 2048, 513, True)
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.experts_per_token, spec.n_shared_experts) == \
        (256, 32, 0, 8, 1)
    assert spec.vocab_size == 19008 and spec.dtype == 'bfloat16'
    assert [(k.name, k.layers, k.width, k.stored)
            for k in spec.cache_kinds()] == [
        ('lm_latent_full', (0, 1), 576, 640),
        ('lm_index_full', (0, 1), 128, 128),
        ('lm_latent_sliding', (2, 3, 4), 1088, 1152)]
    reference = _module('references', 'dots3_note')
    arch = reference.arch_of(spec)
    assert arch['top_k'] == 8 and arch['index_topk'] == 2048
    assert arch['select'] and arch['gate'] and arch['rescale']
    assert reference.held_of(spec) == (0, 32)
    for wrong in (dict(model_type='deepseek_v3'), dict(topk_method='greedy'),
                  dict(tie_word_embeddings=True),
                  dict(attention_gate_type='elementwise')):
        with pytest.raises(ValueError, match='not the block'):
            runner.spec_of(dict(resolved['config'], **wrong))


def test_runner_is_serve_blocks_window_and_limits(resolved):
    """The third runner file copies nothing of the window or of what
    ``correct`` means: they are ``serve_block.py``'s own functions."""
    runner = _module('runners', 'serve_latent')
    for name in ('serve', 'held_sample', 'within_limits',
                 'against_reference', 'poll'):
        assert getattr(runner, name).__module__.endswith(
            'serve_block') or name == 'poll'
    assert runner.poll.__module__.endswith('runners_serve')
    limits = resolved['config']['reference']
    assert runner.within_limits([0.0] * 500, limits)
    assert not runner.within_limits([0.0] * 500 + [limits['logit_gap_cap']
                                                   * 1.01], limits)
    assert limits['long_tokens'] == 8192 == 4 * 2048     # 16 windows deep


def test_parameters_add_up_to_the_stated_cut(resolved):
    """4.087 B parameters = 8.17 GB in bfloat16, the arithmetic of the
    configuration's ``deployment``; 9,344 B of cache a token, stored as
    9,984 B in whole lane tiles."""
    from paddle_tpu.serving.decode import model as lm
    spec = _module('runners', 'serve_latent').spec_of(resolved['config'])
    count = 0
    for shape, fan_in, _ in lm.block_param_shapes(spec).values():
        n = 1
        for d in shape:
            n *= d
        count += n if fan_in else 0
    assert round(count / 1e9, 3) == 4.087
    assert sum(len(k.layers) * k.width * 2
               for k in spec.cache_kinds()) == 9344
    assert lm.kv_bytes_per_token(spec, 'bfloat16') == 9984
    geometry = resolved['config']['engine']
    arenas = lm.arena_bytes(spec, geometry['num_blocks'],
                            geometry['block_size'], 'bfloat16')
    assert 0.25 * 16e9 < 2 * count < 2 * count + arenas < 15.75e9


# ---------------------------------------- shape function and reader
def _snapshot(steps, full, index, sliding):
    return {'counters': {
        'decode.steps_total': steps,
        'decode.cache_bytes_read{kind=lm_latent_full}': full,
        'decode.cache_bytes_read{kind=lm_index_full}': index,
        'decode.cache_bytes_read{kind=lm_latent_sliding}': sliding}}


def test_shape_function_counts_a_steps_least_bytes_by_kind():
    fn = _module('shape_fns', 'latent_decode_bytes')
    before = _snapshot(100, 1000, 2000, 3000)
    after = _snapshot(150, 1000 + 50 * 40, 2000 + 50 * 7, 3000 + 50 * 9)
    assert fn.per_step(before, after, ['lm_latent_full']) == 40
    assert fn.per_step(before, after, ['lm_index_full']) == 7
    assert fn.per_step(before, after,
                       ['lm_latent_full', 'lm_latent_sliding']) == 49
    # no step, or a program without the counters: nothing to read
    assert fn.per_step(before, before, ['lm_latent_full']) is None
    assert fn.per_step({'counters': {}}, {'counters': {
        'decode.steps_total': 5}}, ['lm_latent_full']) is None
    assert fn.per_step(None, None, ['lm_index_full']) is None


def test_roofline_reader_sets_the_tails_bytes_against_the_tails_ops():
    reader = _module('readers', 'step_ops_roofline')
    attn = '%fusion.9 = bf16[128,32,640]{2,1,0} fusion(bf16[2,12288,32,640]' \
           '{3,2,1,0} %arena, s32[128]{0} %pages)'
    other = '%fusion.2 = f32[32,5120]{1,0} fusion(bf16[4,32,5120,1536] %w)'
    device = [(attn, 110, 20), (other, 130, 50), (attn, 210, 30),
              (attn, 320, 40),          # under a prefill, not a step
              (attn, 420, 10)]          # a step that ends past the window
    host = [('decode.step', 100, 90), ('decode.step', 200, 90),
            ('decode.prefill', 300, 90), ('decode.step', 400, 200),
            ('bench.window', 0, 500)]
    args = {'function': 'latent_decode_bytes',
            'function_args': {'kinds': ['lm_latent_full',
                                        'lm_latent_sliding']},
            'match': [r'bf16\[[\d,]*,(640|1152)\]'],
            'peak': 'hbm_bytes_per_s'}
    sources = {
        'trace': {'first': device, 'host': host, 'window': (0, 500)},
        'peaks': {'hbm_bytes_per_s': 819e9}, 'bench_dir': BENCH,
        'registry_before': _snapshot(0, 0, 0, 0),
        'registry_tail': _snapshot(1000, 5000, 0, 7000),
        'registry_after': _snapshot(1002, 5000 + 8190, 10 ** 9,
                                    7000 + 8190)}
    # 8,190 B a step over 819e9 B/s = 10 ns, against 25 ns of ops a step
    assert reader.read(args, sources) == pytest.approx(100.0 * 10 / 25)
    # without a tail snapshot, counters or a trace there is nothing
    assert reader.read(args, dict(sources, registry_tail=None)) is None
    assert reader.read(args, dict(
        sources, registry_after=sources['registry_tail'])) is None
    assert reader.read(args, dict(sources, trace=None)) is None
    assert reader.read(dict(args, match=['no such op']), sources) is None


def test_the_op_patterns_find_their_ops_and_not_each_others(resolved):
    """The patterns the trace readers match, against op lines of the
    programs as the v5e's compiler writes them (compiled here for a
    described chip at the published widths, PR 34)."""
    specs = {m['entry']['name']: m['spec'] for m in resolved['per_layer']}
    gather = ('%fusion.1060 = bf16[128,32,640]{2,1,0:T(8,128)(2,1)S(1)} '
              'fusion(bf16[2,12288,32,640]{3,2,1,0:T(8,128)(2,1)} %gte, '
              's32[128]{0} %reshape.1443)')
    window = ('%fusion.989 = bf16[8,1,64,1,1152]{4,2,0,3,1} fusion('
              'f32[32,64,1152] %q, s32[] %i)')
    keys = ('%fusion.969 = f32[32,512]{1,0} fusion(bf16[2,12288,32,128]'
            '{3,2,1,0:T(8,128)(2,1)} %gte, bf16[32,64,128] %q)')
    count = ('%convert_reduce_fusion.15 = s32[512]{0} fusion(u32[512,16896]'
             '{1,0} %key, u32[512]{0} %kth)')
    passes = ('%fusion.837 = s32[32,132,128]{1,0,2} fusion(s32[32,132,128] '
              '%ties)')
    scores = ('%fusion.1018 = f32[512,512]{1,0:T(8,128)S(1)} fusion(bf16[512,'
              '64,128]{2,1,0} %q, bf16[512,128,1]{1,0,2} %k, f32[512,64] %w)')
    # the sliding layers' value product has the index queries' shape
    values = ('%fusion.350 = bf16[512,64,128]{0,2,1} fusion(f32[1,1,64,512,'
              '1024] %acc, f32[64,512] %norm, bf16[3,64,1024,128] %w_uv)')
    rowmax = ('%fusion.1071 = f32[128,512]{1,0} fusion(f32[128,512,512]{1,2,'
              '0} %scores, f32[128,512] %top, pred[512,512] %seen)')
    expert = ('%fusion.77 = f32[32,1536]{1,0} fusion(bf16[4,32,5120,1536]'
              '{3,2,1,0} %w, s32[] %layer, s32[] %expert)')
    shared = ('%fusion.78 = f32[1,32,5120]{2,1,0} fusion(bf16[4,1,1536,5120]'
              '{3,2,1,0} %w, s32[] %layer)')
    loop = ('%while.38 = (s32[], bf16[2,12288,32,640]{3,2,1,0}, '
            'bf16[2,12288,32,128]{3,2,1,0}, bf16[4,32,5120,1536]) while(%t)')
    # the decode loop over (row, column block) pairs (PR 41), as the v5e's
    # compiler writes it at the published widths (compiled here, PR 42):
    # the scatter of closed rows, a pair's normaliser, a sliding layer's
    # merge of a pair's partial into its row's state
    scatter = ('%fusion.654 = f32[40,128,512]{2,1,0:T(8,128)S(1)} fusion('
               'f32[40,128,512]{2,1,0:T(8,128)S(1)} %out, s32[8]{0} %goes, '
               'f32[8,128,512]{2,1,0:T(8,128)S(1)} %done)')
    norm = ('%fusion.664 = f32[8,64]{1,0:T(8,128)S(1)} fusion(f32[8,64,512]'
            '{2,1,0} %weights, f32[8,64]{1,0} %top, pred[8,512]{1,0} %seen)')
    merge = ('%bitcast_dynamic-update-slice_fusion.41 = f32[8,1,64,1,1024]'
             '{4,2,0,3,1} fusion(f32[8,1,64,1,1024]{4,2,0,3,1} %acc, f32[64]'
             '{0} %keep, f32[64]{0} %scale)')
    # ops near those in shape that are nobody's: the index heads' weights
    # (batch rows by 64 heads, as a sliding layer has), the router's
    # scores and choice (batch rows by the 8 experts a token takes), the
    # query's up-projection, the pair loop's mask and its pairs' pages
    head_w = ('%fusion.355 = f32[32,64]{1,0:T(8,128)S(1)} fusion(bf16[1,5120,'
              '64]{1,2,0:T(8,128)(2,1)S(1)} %w, f32[5120]{0} %gain, f32[32]'
              '{0:T(128)S(1)} %rms, bf16[32,5120]{1,0} %x)')
    gate = ('%broadcast_add_fusion.2 = (f32[32,8]{0,1:T(8,128)S(1)}, '
            'f32[32,8]{0,1:T(8,128)S(1)}) fusion(f32[8]{0:T(128)S(1)} %bias, '
            'bf16[4,5120,32]{1,2,0:T(8,128)(2,1)S(1)} %w, f32[32,5120] %x)')
    top_k = ('%sort.2 = (f32[32,8]{0,1:T(8,128)}, s32[32,8]{0,1:T(8,128)S(1)'
             '}) sort(f32[32,8]{0,1:T(8,128)S(1)} %scores, s32[32,8]{0,1:'
             'T(8,128)S(1)} %iota.13)')
    query = ('%fusion.185 = f32[32,128,192]{1,0,2:T(8,128)S(1)} fusion('
             'bf16[128,192,1024]{2,1,0:T(8,128)(2,1)S(1)} %w_uq, f32[32,1024]'
             '{1,0:T(8,128)S(1)} %q)')
    mask = ('%fusion.637 = pred[8,512]{1,0:T(8,128)(4,1)S(1)} fusion('
            'pred[8,512]{1,0:T(8,128)(4,1)S(1)} %chosen, s32[8]{0:T(128)S(1)} '
            '%lo, s32[8]{0:T(128)S(1)} %hi)')
    pages = ('%fusion.634 = s32[8,16]{1,0:T(8,128)S(1)} fusion(s32[1056,16]'
             '{1,0:T(8,128)S(1)} %tables, s32[]{:T(128)S(6)} %first)')
    nobodys = [head_w, gate, top_k, query, mask, pages]
    attn = [gather, window, rowmax, scatter, norm, merge]
    index = [keys, count, passes, scores]
    mine = {'serve.latent_attn_busy_share': attn,
            'serve.latent_attn_roofline_share': attn,
            'serve.indexer_busy_share': index,
            'serve.indexer_roofline_share': index,
            'serve.latent_moe_ffn_busy_share': [expert, shared]}
    lines = attn + index + [expert, shared, values] + nobodys
    for name, wanted in mine.items():
        patterns = specs[name]['args']['match']
        for line in lines:
            hit = any(re.search(p, line) for p in patterns)
            assert hit == (line in wanted), (name, line)
        # the layer loop carries the arenas and lasts the whole program
        assert not any(re.search(p, loop) for p in patterns)


def test_the_op_patterns_carry_the_configurations_geometry(resolved):
    """The patterns name ops by their shapes, so the numbers in them are
    the configuration's: the pool's pages, a sequence's capacity (whole
    and in lane tiles), a stored latent row, a chunk, a column block, a
    row block, the two head counts. A change of ``engine`` or of a width
    that the patterns do not follow would read nothing; it fails here
    first."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    config = resolved['config']
    engine = config['engine']
    spec = _module('runners', 'serve_latent').spec_of(config)
    stored = {k.name: k.stored for k in spec.cache_kinds()}
    capacity = engine['pages_per_seq'] * engine['block_size']
    cols = engine['block_size'] * pa.pages_per_block(
        engine['pages_per_seq'], engine['block_size'])
    heads = '(%d|%d)' % (spec.latent[FULL].n_head,
                         spec.latent[SLIDING].n_head)
    rows = '(%d|%d)' % (engine['max_batch'], engine['prefill_chunk'])
    arena = r'bf16\[%d,%d,%d,%d\]' % (
        len(spec.layers_of(FULL)), engine['num_blocks'],
        engine['block_size'], stored['lm_index_full'])
    skip = r'^(?!%?(while|conditional|call)[.\d]*( |=)).*'
    # the pair loop's state and result are as wide as a kind's values:
    # its rank (the latent form's values are the row's first columns)
    ranks = '(%d|%d)' % (spec.latent[FULL].kv_rank,
                         spec.latent[SLIDING].kv_rank)
    assert spec.latent[FULL].kv_rank == cols      # so one group serves both
    # 8 rows by heads is a pair loop's shape only while a step's batch is
    # not 8 rows itself
    assert engine['max_batch'] != pa.BLOCK_ROWS
    attn = [skip + r'bf16\[[\d,]*,(%d|%d)\]' % (
                stored['lm_latent_full'], stored['lm_latent_sliding']),
            # the score blocks of BLOCK_ROWS pairs, and their merges
            skip + r'f32\[%d,1,%s,1,%s' % (pa.BLOCK_ROWS, heads, ranks),
            skip + r'f32\[%s,%d,%d\]' % (heads, engine['prefill_chunk'],
                                         cols),
            # the scatter of closed rows into max_batch + BLOCK_ROWS rows
            skip + r'f32\[%d,%s,%s\]' % (
                engine['max_batch'] + pa.BLOCK_ROWS, heads, ranks),
            # a pair's normaliser and row maximum
            skip + r'f32\[%d,%s\]' % (pa.BLOCK_ROWS, heads)]
    index = [skip + arena,
             skip + r'\[%s,(%d|%d,128)\]' % (rows, capacity,
                                             capacity // 128),
             skip + r'\[%d,1,%d\]' % (pa.BLOCK_ROWS, capacity),
             skip + r'= f32\[%s,%d\]\S* fusion\(.*bf16\[%s,%d,%d\]' % (
                 rows, cols, rows, spec.index_n_heads,
                 spec.index_head_dim)]
    experts = [skip + r'bf16\[%d,(%d|%d),(%d,%d|%d,%d)\]' % (
        spec.n_layer - spec.dense_layers, spec.experts_held,
        spec.n_shared_experts, spec.d_model, spec.d_inner, spec.d_inner,
        spec.d_model)]
    specs = {m['entry']['name']: m['spec'] for m in resolved['per_layer']}
    for name, want in (('serve.latent_attn_busy_share', attn),
                       ('serve.latent_attn_roofline_share', attn),
                       ('serve.indexer_busy_share', index),
                       ('serve.indexer_roofline_share', index),
                       ('serve.latent_moe_ffn_busy_share', experts)):
        assert specs[name]['args']['match'] == want, name


# ---------------------------------------------------- the reference
def test_the_benchmarks_reference_is_the_repositorys():
    mine = os.path.join(REPO, 'paddle_tpu', 'models', 'reference',
                        'dots3_note.py')
    with open(mine) as a, open(os.path.join(
            BENCH, 'references', 'dots3_note.py')) as b:
        assert a.read() == b.read()
    with open(mine) as f:
        assert 'paddle_tpu' not in f.read().split('"""')[2]   # the code


# ---------------------------------------------------- the rehearsal
@pytest.fixture
def own_environment(monkeypatch):
    """benchmark/run.py turns the executor's cost probe off for its
    process and, traced, ``observe`` on: in a test process both have to
    end with the test, and the registry the run counted into is emptied
    (as in test_kimi_k2_6.py: no later test of this worker hangs on
    whether this file ran before it)."""
    from paddle_tpu import observe
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def test_the_cell_rehearses_in_process(capsys, own_environment):
    assert bench.main(['--workload', CELL, '--seed', '3400000034',
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
    # past the toy index_topk (8), window (5) and long_tokens (16)
    assert window['reference_longest_tokens'] > 16
    assert window['refused'] == 0 and window['compiles_in_window'] == 0


def test_the_traced_rehearsal_reads_the_counters_this_pr_adds(
        capsys, own_environment):
    """Under --trace 1 the program's new counters reach the line: every
    toy prompt is past the toy index_topk, so every live row is sparse
    and the selection keeps under all it holds."""
    assert bench.main(['--workload', CELL, '--seed', '2147483681',
                       '--seconds', '3', '--trace', '1',
                       '--rehearsal']) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    got = {k: v['value'] for k, v in last['metrics'].items()}
    assert got['serve.sparse_live_row_share'] == 100.0
    assert 0 < got['serve.sparse_selected_share'] < 100
    assert got['serve.recompiles'] == 0
    assert got['serve.prefill_chunks_per_prompt'] >= 1
    assert 0 < got['serve.moe_local_assignment_pct'] <= 100
    assert 0 < got['serve.attn_pages_held_share'] <= 100
    assert 0 <= got['serve.steps_ahead_share'] <= 100
    # the counter is fed and read: the toy chunks (16 rows at most) lie
    # under the rows from which the rule sends a chunk to the expanded
    # form, so none expands (97.9 on the chip: PERF.md, PR 42)
    assert got['serve.mla_prefill_expanded_chunk_share'] == 0.0
    assert 'serve.latent_attn_roofline_share' not in got   # no device here


def test_the_fault_probe_rehearses(capsys):
    """benchmark/probe_faults.py at the toy size: the selection replaced
    by all positions fails the cell's limits, the bfloat16 state does
    not show in the tokens (the CPU logits tests hold it)."""
    from benchmark import probe_faults
    assert probe_faults.main(['--workload', CELL, '--rehearsal', '--seed',
                              '5', '--lengths', '41', '--rows', '24',
                              '--faults', 'select,state']) == 0
    lines = [json.loads(ln[8:]) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('PLANTED ')]
    assert [ln['fault'] for ln in lines] == ['select', 'state']
    assert lines[0]['within_limits'] is False and lines[0]['not_first'] > 12
    assert lines[0]['share_over']['0.5'] > 0.25
    assert lines[1]['gap_max'] < lines[0]['gap_max']
