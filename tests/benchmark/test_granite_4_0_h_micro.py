"""The ``granite_4_0_h_micro`` configuration and its cell, off the chip:
the file holds the published config whole (nothing reduced), its
parameters and arenas add up to what the configuration states, the
runner builds the block it describes (a state kind in a pool of slots
beside K and V in pages), the shape functions this PR brings do their
arithmetic, the trace patterns are the configuration's numbers, the
benchmark's copy of the plain reference is the repository's, and the cell
rehearses end to end on the CPU. No test here describes a TPU topology."""

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
CONFIG = 'granite_4_0_h_micro'
CELL = CONFIG + '.chat_long_answers'
BENCH = os.path.join(REPO, 'benchmark')
M, A = 'mamba', 'attention'

# config.json of ibm-granite/granite-4.0-h-micro, every key of the
# catalog row beside the model-configs guide, as published
PUBLISHED = {
    'attention_bias': False, 'attention_multiplier': 0.015625,
    'embedding_multiplier': 12, 'hidden_act': 'silu', 'hidden_size': 2048,
    'intermediate_size': 8192,
    'layer_types': ([M] * 5 + [A] + [M] * 4) * 4,
    'logits_scaling': 8, 'mamba_chunk_size': 256, 'mamba_conv_bias': True,
    'mamba_d_conv': 4, 'mamba_d_head': 64, 'mamba_d_state': 128,
    'mamba_expand': 2, 'mamba_n_groups': 1, 'mamba_n_heads': 64,
    'mamba_proj_bias': False, 'max_position_embeddings': 131072,
    'model_type': 'granitemoehybrid', 'normalization_function': 'rmsnorm',
    'num_attention_heads': 32, 'num_experts_per_tok': 0,
    'num_hidden_layers': 40, 'num_key_value_heads': 8,
    'num_local_experts': 0, 'position_embedding_type': 'nope',
    'residual_multiplier': 0.22, 'rms_norm_eps': 1e-05,
    'rope_scaling': None, 'rope_theta': 10000,
    'shared_intermediate_size': 8192, 'tie_word_embeddings': True,
    'vocab_size': 100352}
OWN_METRICS = {
    'serve.ssm_state_update_busy_share',
    'serve.ssm_state_update_roofline_share', 'serve.ssm_scan_busy_share',
    'serve.ssm_scan_mxu_share', 'serve.ssm_attn_busy_share',
    'serve.ssm_step_hbm_share', 'serve.ssm_state_slots_used_pct'}
# the shared readers whose series its engine feeds
SHARED_METRICS = {
    'serve.recompiles', 'serve.queue_wait_ms', 'serve.prefill_ms',
    'serve.decode_step_ms', 'serve.batch_occupancy',
    'serve.kv_pool_used_pct', 'serve.copy_busy_share', 'serve.ttft_p90_ms',
    'serve.itl_p95_ms', 'serve.tokens_per_s', 'serve.worker_step_share',
    'serve.live_tokens_per_step', 'serve.prefill_chunks_per_prompt',
    'serve.attn_pages_read_share', 'serve.attn_pages_held_share',
    'serve.prefill_chunk_ms', 'serve.steps_ahead_share',
    'serve.device_empty_step_share', 'serve.idle_under_states_pct'}


def _module(kind, name):
    return manifest.load_module(os.path.join(BENCH, kind, name + '.py'))


@pytest.fixture(scope='module')
def resolved():
    return manifest.resolve(MANIFEST, CELL)


def _metric(resolved, name):
    (metric,) = [m for m in resolved['per_layer']
                 if m['entry']['name'] == name]
    return metric['spec']


# ------------------------------------------------------- the files
def test_the_cell_resolves_to_files_by_name(resolved):
    assert manifest.problems(MANIFEST) == []
    r = resolved
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_ssm'
    assert r['cell']['chips'] == 1 and \
        r['cell']['traffic'] == 'chat_long_answers'
    assert r['config']['reference'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    (entry,) = [c for c in MANIFEST['configs'] if c['name'] == CONFIG]
    assert entry['reduced'] == r['config']['reduced'] == []
    assert len(entry['why']) <= 200 and len(r['cell']['why']) <= 200
    assert entry['source'] == r['config']['source']


def test_the_cell_reports_its_metrics_and_the_two_end_to_end(resolved):
    """Membership only: a later cell may join these lists."""
    mine = {p['entry']['name'] for p in resolved['per_layer']}
    assert mine >= OWN_METRICS | SHARED_METRICS
    # what the configuration lacks is left off: no window, no prefix
    # cache, no routed expert, no latent form, no selection
    assert not [n for n in mine if n.startswith((
        'serve.prefix_', 'serve.latent_', 'serve.mla_', 'serve.sparse_',
        'serve.indexer_', 'serve.window_', 'serve.moe_', 'serve.gqa_',
        'train.'))]
    for metric in MANIFEST['per_layer']:
        if metric['name'] in OWN_METRICS:
            assert metric['workloads'][0] == CELL and metric['unit'] == '%'
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in MANIFEST['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e
    moves = {m['name']: m['moves'] for m in MANIFEST['per_layer']}
    assert moves['serve.ssm_scan_busy_share'] == \
        moves['serve.ssm_scan_mxu_share'] == 'ttft_mean_ms'
    assert moves['serve.ssm_state_update_roofline_share'] == 'itl_mean_ms'


def test_the_entries_stand_behind_what_was_there():
    """Appended: every entry this PR brings lies behind every entry the
    benchmark had (a later PR's may lie behind these)."""
    cells = [c['name'] for c in MANIFEST['workloads']]
    configs = [c['name'] for c in MANIFEST['configs']]
    assert cells.index(CELL) > cells.index('mellum2_12b.repo_ctx_steady')
    assert configs.index(CONFIG) > configs.index('mellum2_12b')
    names = [p['name'] for p in MANIFEST['per_layer']]
    first = min(names.index(n) for n in OWN_METRICS)
    assert first > names.index('serve.gqa_moe_step_hbm_share')
    assert set(names[first:first + len(OWN_METRICS)]) == OWN_METRICS


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_config_holds_the_published_value(resolved, key):
    assert resolved['config'][key] == PUBLISHED[key]


def test_config_is_the_catalog_row_whole(resolved):
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.isfile(catalog):
        pytest.skip('no catalog beside the guides here')
    with open(catalog) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r['name'] == 'granite-4.0-h-micro']
    assert row['source_url'] == resolved['config']['source']
    assert row['config'] == PUBLISHED
    assert {k: resolved['config'][k] for k in row['config']} == row['config']


def test_config_states_the_deployment_and_what_it_assumes(resolved):
    config = resolved['config']
    assert config['reduced'] == [] and config['published'] == {}
    assert config['dtype'] == 'bfloat16'
    assert 'one chip holds the model whole' in config['deployment'].lower()
    assumed = config['assumed']
    for key in ('state', 'gate', 'time_step', 'groups', 'weights',
                'precision', 'geometry', 'sampling'):
        assert assumed[key], key
    assert 'float32' in assumed['state'] and \
        "model's dtype" in assumed['state']
    geometry = config['engine']
    assert (geometry['max_batch'], geometry['block_size'],
            geometry['prefill_chunk'], geometry['max_prompt_len']) == \
        (64, 32, 512, 2048)
    assert geometry['pages_per_seq'] * geometry['block_size'] == 4096
    assert geometry['kv_dtype'] == 'bfloat16' and not geometry[
        'prefix_cache'] and geometry['spec_k'] == 0
    limits = config['reference']
    assert limits['long_tokens'] == 2048 and limits['long_requests'] >= 1
    assert limits['logit_gap_tol'] < limits['logit_gap_cap']


def test_parameters_and_arenas_add_up_to_what_the_issue_counts(resolved):
    from paddle_tpu.serving.decode.model import (arena_bytes,
                                                 block_param_shapes,
                                                 unit_bytes_per_kind)
    runner = _module('runners', 'serve_ssm')
    spec = runner.spec_of(resolved['config'])
    sizes = {name: int(np.prod(shape)) for name, (shape, _, _)
             in block_param_shapes(spec).items()}
    mamba = sum(n for name, n in sizes.items()
                if name.startswith('lm_mamba')) // 36
    attention = sum(n for name, n in sizes.items()
                    if name.startswith('lm_attn')) // 4
    mlp = sum(n for name, n in sizes.items()
              if name.startswith('lm_stack_mlp')) // 40
    assert sizes['lm_mamba_in.w'] // 36 == 2048 * 8512
    assert mlp == 2048 * 16384 + 8192 * 2048
    assert round((mamba + mlp + 2 * 2048) / 1e6, 2) == 76.18
    assert round((attention + mlp + 2 * 2048) / 1e6, 2) == 60.82
    assert sizes['lm_emb'] == 100352 * 2048
    total = sum(sizes.values())
    assert round(total / 1e6) == 3191
    geometry = resolved['config']['engine']
    unit = unit_bytes_per_kind(spec, geometry['block_size'], 'bfloat16')
    assert unit['lm_ssm_state'] == 36 * 128 * 4096 * 4
    assert unit['lm_ssm_conv'] == 36 * 3 * 4352 * 2
    assert unit['lm_kcache'] == unit['lm_vcache'] == 4 * 32 * 512 * 2
    pages = {'': geometry['num_blocks'], 'state': geometry['max_batch']}
    arenas = arena_bytes(spec, pages, geometry['block_size'], 'bfloat16')
    assert arenas == 65 * (unit['lm_ssm_state'] + unit['lm_ssm_conv']) \
        + geometry['num_blocks'] * 2 * unit['lm_kcache']
    # weights 6.38 GB + state 4.9 GB + K/V 1.07 GB: 12 to 13.5 GB
    assert 12e9 < total * 2 + arenas < 13.5e9


# ------------------------------------------------------ the runner
def test_runner_builds_the_block_the_config_describes(resolved):
    runner = _module('runners', 'serve_ssm')
    spec = runner.spec_of(resolved['config'])
    assert (spec.block, spec.n_layer, spec.d_model, spec.d_inner,
            spec.n_head, spec.n_kv_head, spec.d_key, spec.d_value) == \
        ('ssm_hybrid', 40, 2048, 8192, 32, 8, 64, 64)
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
            spec.ssm_conv, spec.ssm_chunk) == (64, 64, 128, 4, 256)
    assert (spec.embed_scale, spec.residual_scale, spec.attn_scale,
            spec.logit_scale) == (12.0, 0.22, 0.015625, 0.125)
    assert spec.layer_plan() == ((), tuple([M] * 5 + [A] + [M] * 4), 4, ())
    assert spec.layers_of(A) == (5, 15, 25, 35)
    assert spec.vocab_size == 100352 and spec.dtype == 'bfloat16'
    first, state = spec.page_pools()
    assert (first.name, first.per_sequence) == ('', False)
    assert (state.name, state.per_sequence, state.table_width(128)) == \
        ('state', True, 1)
    assert [(k.name, len(k.layers), k.per_seq, k.dtype, k.pool)
            for k in spec.cache_kinds()] == [
        ('lm_kcache', 4, (), None, ''), ('lm_vcache', 4, (), None, ''),
        ('lm_ssm_state', 36, (128, 4096), 'float32', 'state'),
        ('lm_ssm_conv', 36, (3 * 4352,), 'bfloat16', 'state')]
    assert not spec.shares_frozen_pages()
    reference = _module('references', CONFIG)
    arch = reference.arch_of(spec)
    assert arch['state_dtype'] == 'float32' and arch['d_skip'] and \
        arch['dt_bias'] and arch['gate']
    assert arch['attn_scale'] == 0.015625 and arch['ssm_state'] == 128
    for wrong in (dict(model_type='bamba'), dict(attention_bias=True),
                  dict(num_local_experts=8), dict(mamba_n_groups=8),
                  dict(position_embedding_type='rope'),
                  dict(tie_word_embeddings=False)):
        with pytest.raises(ValueError, match='not the block'):
            runner.spec_of(dict(resolved['config'], **wrong))


def test_the_time_constants_are_drawn_as_published():
    import jax
    runner = _module('runners', 'serve_ssm')
    dt_bias, a_log = runner._time_constants(jax.random.PRNGKey(3),
                                            (36, 64))
    dt = np.log1p(np.exp(np.asarray(dt_bias)))
    assert 0.001 * 0.99 <= dt.min() and dt.max() <= 0.1 * 1.01
    a = np.exp(np.asarray(a_log))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 3
    # a head keeps its state for tens to thousands of tokens
    assert np.median(np.exp(-dt * a)) > 0.9


def test_the_runner_samples_the_state_pool_and_hands_the_chunks_over():
    runner = _module('runners', 'serve_ssm')

    class Pool(object):
        def __init__(self, whole, used, total):
            self.whole, self.num_blocks, self._used = whole, total, used

        def used_blocks(self):
            return self._used

    class Engine(object):
        pools = [Pool(False, 10, 40), Pool(True, 16, 64)]
        drained = []

        def free_pages(self):
            return 30

        def drain(self, timeout=None):
            self.drained.append(timeout)
            return True

    class Ctx(object):
        samples, sources, t_trace = {}, {}, None

    watched = runner._Watched(Engine(), Ctx)
    assert watched.free_pages() == 30 and watched.free_pages() == 30
    assert Ctx.samples == {'state_slots_used_pct': [25.0, 25.0]}
    assert watched.drain(timeout=7) is True and Engine.drained == [7]
    assert Ctx.sources == {}                  # an untraced run
    assert watched.pools is Engine.pools


def test_the_traffic_is_the_issues_mix(resolved):
    traffic = resolved['traffic']
    assert (traffic['prompt_len'], traffic['answer_len'], traffic['alpha'],
            traffic['pool_seed'], traffic['preroll_s'], traffic['drain_s'],
            traffic['recheck_requests']) == (
        [32, 2048], [64, 2048], 1.3, 45, 20, 120, 2)
    requests = loadgen.schedule(traffic, 7, MANIFEST['run_seconds'])
    window = [r for r in requests if r.due >= traffic['preroll_s']]
    assert len(window) == round(traffic['rate_rps']
                                * MANIFEST['run_seconds'])
    prompts = [r.prompt_len for r in window]
    answers = [r.answer_len for r in window]
    assert 32 <= min(prompts) and max(prompts) == 2048
    assert 64 <= min(answers) and max(answers) == 2048
    assert 250 < np.mean(prompts) < 500 and 250 < np.mean(answers) < 500
    # the held sample reaches one of them: prompt + answer past 2,048
    assert sum(1 for r in window
               if r.prompt_len + r.answer_len > 2048) >= 3
    # and the one-at-a-time check finds short answers to serve again
    assert sum(1 for a in answers
               if a <= traffic['recheck_max_answer']) >= 10
    assert max(p + a for p, a in zip(prompts, answers)) <= 4096


# ----------------------------------------------- the shape functions
def test_the_state_update_moves_a_row_s_state_once_each_way(resolved):
    shapes = _module('shape_fns', 'ssm_state_update_bytes')
    config = resolved['config']
    assert shapes.row_layer_bytes(config) == 2 * 2097152 + 2 * 26112
    spec = _metric(resolved, 'serve.ssm_state_update_roofline_share')
    assert spec['args']['function_args'] == {
        'row_layer_bytes': shapes.row_layer_bytes(config)}
    before = {'counters': {'decode.steps_total': 10,
                           'decode.step_state_rows_total': 36 * 100}}
    after = {'counters': {'decode.steps_total': 30,
                          'decode.step_state_rows_total': 36 * 900}}
    assert shapes.row_layers_per_step(before, after) == 36 * 40
    assert shapes.per_step(before, after, 4246528) == 36 * 40 * 4246528
    # 40 live rows: 6.1 GB a step, beside 6.4 GB of weights
    assert round(shapes.per_step(before, after, 4246528) / 1e9, 1) == 6.1
    assert shapes.per_step(before, before, 4246528) is None
    assert shapes.per_step({}, {}, 4246528) is None     # the parent


def test_the_step_bytes_are_weights_state_and_the_attended_rows(resolved):
    shapes = _module('shape_fns', 'ssm_decode_live_bytes')
    config = resolved['config']
    assert round(shapes.weight_bytes(config) / 1e9, 2) == 6.38
    # 4 attention layers x K and V x 8 heads of 64 x 2 B a token
    assert shapes.kv_bytes(config, 1000) == 1000 * 8192
    hist = 'decode.step_seconds'
    live = 'decode.step_live_tokens'
    before = {'counters': {'decode.steps_total': 0,
                           'decode.step_state_rows_total': 0},
              'histograms': {hist: {'sum': 0.0, 'count': 0},
                             live: {'sum': 0.0, 'count': 0}}}
    after = {'counters': {'decode.steps_total': 100,
                          'decode.step_state_rows_total': 36 * 4000},
             'histograms': {hist: {'sum': 2.5, 'count': 100},
                            live: {'sum': 100 * 30000.0, 'count': 100}}}
    got = shapes.compute({'registry_before': before,
                          'registry_after': after, 'config': config})
    want = (shapes.weight_bytes(config) + 36 * 40 * 4246528
            + 30000 * 8192) / 0.025
    assert abs(got - want) < 1e-6 * want
    assert 100 * got / 819e9 < 100
    assert shapes.compute({'registry_before': None, 'registry_after': None,
                           'config': config}) is None


def test_the_scan_s_least_operations_are_the_recurrence_s(resolved):
    shapes = _module('shape_fns', 'ssm_scan_flops')
    config = resolved['config']
    assert shapes.least_flops(1, config) == 5 * 64 * 64 * 128
    # a chunk of 512 live rows through 36 layers: 48 GFLOP
    assert round(shapes.least_flops(512 * 36, config) / 1e9) == 48
    spec = _metric(resolved, 'serve.ssm_scan_mxu_share')
    assert spec['reader'] == 'prefill_ops_mxu'
    assert spec['args']['function'] == 'ssm_scan_flops'
    assert re.compile(spec['args']['program']).search(
        'jit_prefill_512').group(1) == '512'


def test_trace_patterns_are_the_configs_numbers(resolved):
    """Each pattern names a shape the configuration and the engine's
    geometry give: the state arena ``[36, 65, 128, 4096]`` and the
    convolution rows' ``[36, 65, 13056]``, a slot ``[128, 4096]``, the
    decode batch's ``[64, 4096]``; the scan's decays ``[64, Q, Q]`` and
    its rows ``[S, 64, 64]``; the K and V arenas ``[4, 4096, 32, 512]``."""
    config, geometry = resolved['config'], resolved['config']['engine']
    heads, width = config['mamba_n_heads'], config['mamba_d_head']
    inner, n = heads * width, config['mamba_d_state']
    layers = config['layer_types'].count(M)
    slots = geometry['max_batch'] + 1
    conv = (config['mamba_d_conv'] - 1) * (inner + 2 * n)
    update = _metric(resolved, 'serve.ssm_state_update_busy_share')
    assert _metric(resolved, 'serve.ssm_state_update_roofline_share')[
        'args']['match'] == update['args']['match']
    text = ' '.join(update['args']['match'])
    assert 'f32\\[%d,%d,%d,%d\\]' % (layers, slots, n, inner) in text
    assert 'bf16\\[%d,%d,%d\\]' % (layers, slots, conv) in text
    assert '%d,%d\\]' % (geometry['max_batch'], inner) in text
    scan = _metric(resolved, 'serve.ssm_scan_busy_share')
    assert _metric(resolved, 'serve.ssm_scan_mxu_share')['args'][
        'match'] == scan['args']['match']
    assert '%d' % config['mamba_chunk_size'] in ' '.join(
        scan['args']['match'])
    attn = ' '.join(_metric(resolved, 'serve.ssm_attn_busy_share')[
        'args']['match'])
    kv = config['num_key_value_heads'] * (
        config['hidden_size'] // config['num_attention_heads'])
    assert 'bf16\\[4,%d,%d,%d\\]' % (
        geometry['num_blocks'], geometry['block_size'], kv) in attn
    for spec in (update, scan):
        for pattern in spec['args']['match']:
            rx = re.compile(pattern)
            assert not rx.search('%while.3 = (s32[], f32[36,65,128,4096])'
                                 ' while(%tuple.1)')


def test_the_benchmarks_reference_is_the_repositorys():
    mine = os.path.join(REPO, 'paddle_tpu', 'models', 'reference',
                        CONFIG + '.py')
    with open(mine) as a, open(os.path.join(
            BENCH, 'references', CONFIG + '.py')) as b:
        assert a.read() == b.read()
    with open(mine) as f:
        assert 'paddle_tpu' not in f.read().split('"""')[2]   # the code


# ---------------------------------------------------- the rehearsal
@pytest.fixture
def own_environment(monkeypatch):
    """benchmark/run.py turns the executor's cost probe off for its
    process and, traced, ``observe`` on: in a test process both have to
    end with the test, and the registry the run counted into is emptied
    (tests/benchmark/test_kimi_k2_6.py: the same fixture)."""
    from paddle_tpu import observe
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def test_the_cell_rehearses_in_process(capsys, own_environment):
    assert bench.main(['--workload', CELL, '--seed', '4500000045',
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
    assert window['reference_longest_tokens'] > 32
    assert window['refused'] == 0 and window['compiles_in_window'] == 0
    assert window['signatures'] == 3           # chunks of 8 and 16, the step


def test_the_traced_rehearsal_reads_the_counters_this_pr_adds(
        capsys, own_environment):
    """Under --trace 1 the program's counters reach the line: the state
    pool's used share, the whole step's bytes (a share of no peak on the
    CPU: left out), no recompile."""
    assert bench.main(['--workload', CELL, '--seed', '2147483692',
                       '--seconds', '3', '--trace', '1',
                       '--rehearsal']) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    got = {k: v['value'] for k, v in last['metrics'].items()}
    assert last['correct'] is True
    assert 0 < got['serve.ssm_state_slots_used_pct'] <= 100
    assert got['serve.recompiles'] == 0
    assert 0 <= got['serve.kv_pool_used_pct'] <= 100
    assert got['serve.live_tokens_per_step'] > 0
    assert got['serve.prefill_chunks_per_prompt'] >= 1
    assert 'serve.ssm_state_update_roofline_share' not in got   # no device
    assert 'serve.ssm_scan_mxu_share' not in got


def test_the_precision_probe_rehearses(capsys):
    """benchmark/probe_precision.py at the toy size: the reference with
    its float32 state (the recurrent state with it) in bfloat16, and with
    every matrix at float8's three mantissa bits."""
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
