"""The ``nemotron_3_super`` configuration and its cell, off the chip: the
file is the catalog row but for the four cut keys, its parameters and
arenas add up to what ISSUE 58 counts (for the cut and, from the same
table, for the published model), the runner builds the block the file
describes, the shape functions this PR brings do their arithmetic, the
trace patterns are the configuration's numbers, the benchmark's copy of
the plain reference is the repository's, and the cell rehearses end to
end on the CPU. Entries are found by name and membership only. No test
here describes a TPU topology."""

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
CONFIG = 'nemotron_3_super'
CELL = CONFIG + '.agent_ctx_long_answers'
BENCH = os.path.join(REPO, 'benchmark')
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
CUT = {'num_hidden_layers': 11, 'hybrid_override_pattern': '*EMEMEMEMEM',
       'n_routed_experts': 128, 'vocab_size': 32768}
PATTERN = ('MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*'
           'EMEMEMEMEM*EMEMEMEM*EMEMEMEME')
# what ISSUE 58 pins of the published config.json; the whole row is held
# to the catalog where the catalog is there
PUBLISHED = {
    'model_type': 'nemotron_h', 'hidden_size': 4096, 'num_hidden_layers': 88,
    'hybrid_override_pattern': PATTERN, 'mamba_num_heads': 128,
    'mamba_head_dim': 64, 'n_groups': 8, 'ssm_state_size': 128,
    'conv_kernel': 4, 'chunk_size': 128, 'expand': 2,
    'num_attention_heads': 32, 'num_key_value_heads': 2, 'head_dim': 128,
    'n_routed_experts': 512, 'num_experts_per_tok': 22,
    'moe_intermediate_size': 2688, 'moe_latent_size': 1024,
    'moe_shared_expert_intermediate_size': 5376, 'n_shared_experts': 1,
    'routed_scaling_factor': 5, 'mlp_hidden_act': 'relu2',
    'vocab_size': 131072, 'tie_word_embeddings': False,
    'norm_eps': 1e-05, 'intermediate_size': 2688,
    'num_nextn_predict_layers': 1, 'mtp_hybrid_override_pattern': '*E'}
# the two kernels' roofline shares: per_layer holds 128 entries at most
# and had 126 (PERF.md section 7 has the seven that wait for room)
OWN_METRICS = {'serve.ssmoe_moe_ffn_roofline_share',
               'serve.ssmoe_state_update_roofline_share'}
# shared entries whose series its engine feeds, among them the ones
# ISSUE 58 names
SHARED_METRICS = {
    'serve.ssm_state_slots_used_pct', 'serve.moe_local_assignment_pct',
    'serve.moe_load_max_over_mean', 'serve.moe_row_tiles_run_share',
    'serve.recompiles', 'serve.queue_wait_ms', 'serve.prefill_ms',
    'serve.decode_step_ms', 'serve.batch_occupancy',
    'serve.kv_pool_used_pct', 'serve.live_tokens_per_step',
    'serve.prefill_chunks_per_prompt', 'serve.attn_pages_read_share',
    'serve.prefill_chunk_ms', 'serve.steps_ahead_share'}


def _module(kind, name):
    return manifest.load_module(os.path.join(BENCH, kind, name + '.py'))


@pytest.fixture(scope='module')
def resolved():
    return manifest.resolve(MANIFEST, CELL)


def _metric(resolved, name):
    (metric,) = [m for m in resolved['per_layer']
                 if m['entry']['name'] == name]
    return metric['spec']


def _spec(resolved, **over):
    return _module('runners', 'serve_ssm_moe').spec_of(
        dict(resolved['config'], **over))


# ------------------------------------------------------- the files
def test_the_cell_resolves_to_files_by_name(resolved):
    assert manifest.problems(MANIFEST) == []
    r = resolved
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_ssm_moe'
    assert r['cell']['chips'] == 1 and \
        r['cell']['traffic'] == 'agent_ctx_long_answers'
    assert r['config']['reference']['note'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    (entry,) = [c for c in MANIFEST['configs'] if c['name'] == CONFIG]
    assert entry['reduced'] == r['config']['reduced'] == list(CUT)
    assert len(entry['why']) <= 200 and len(r['cell']['why']) <= 200
    assert entry['source'] == r['config']['source']
    with open(os.path.join(REPO, 'BENCHMARK.json'), 'rb') as f:
        assert len(f.read()) < 65536


def test_the_cell_is_on_its_own_and_the_shared_lists(resolved):
    """Membership only: a later cell may join these lists, and a later
    entry may stand behind these."""
    mine = {p['entry']['name'] for p in resolved['per_layer']}
    assert mine >= OWN_METRICS | SHARED_METRICS
    # what the configuration lacks is left off: no window, no prefix
    # cache, no latent attention, no selection, no other block's shapes
    assert not [n for n in mine if n.startswith((
        'serve.prefix_', 'serve.latent_', 'serve.mla_', 'serve.sparse_',
        'serve.indexer_', 'serve.window_', 'serve.gqa_', 'serve.dsa_',
        'serve.scmoe_', 'serve.moe_ffn', 'serve.moe_step', 'train.'))]
    by_name = {m['name']: m for m in MANIFEST['per_layer']}
    for name in OWN_METRICS:
        assert CELL in by_name[name]['workloads']
        assert by_name[name]['unit'] == '%'
        assert by_name[name]['layer'] == 'op lowerings'
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in MANIFEST['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e
    assert len(MANIFEST['per_layer']) <= 128
    assert by_name['serve.ssmoe_state_update_roofline_share']['moves'] \
        == by_name['serve.ssmoe_moe_ffn_roofline_share']['moves'] \
        == 'itl_mean_ms'


# ------------------------------------------------------ the configuration
@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_config_holds_the_published_value(resolved, key):
    config = resolved['config']
    want = PUBLISHED[key]
    if key in CUT:
        assert config[key] == CUT[key]
        assert config['published'][key] == want
    else:
        assert config[key] == want and type(config[key]) is type(want)


def test_config_is_the_catalog_row_but_for_the_cut(resolved):
    if not os.path.isfile(CATALOG):
        pytest.skip('the catalog is not on this machine')
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r['name'] == 'NVIDIA-Nemotron-3-Super-120B-A12B-BF16']
    config = resolved['config']
    assert config['source'] == row['source_url']
    differing = [k for k, v in row['config'].items() if config.get(k) != v]
    assert sorted(differing) == sorted(CUT) == sorted(config['reduced'])
    assert config['published'] == {k: row['config'][k] for k in CUT}


def test_config_states_the_deployment_and_what_it_assumes(resolved):
    config = resolved['config']
    assert config['first_layer'] == 25 and config['first_expert'] == 0
    assert PATTERN[25:36] == config['hybrid_override_pattern']
    # the first of the four whole 11-layer periods
    assert [i for i in range(len(PATTERN) - 10)
            if PATTERN[i:i + 11] == '*EMEMEMEMEM'] == [25, 36, 47, 58]
    assert config['dtype'] == 'bfloat16'
    for word in ('4', 'expert-parallel', 'replicated', 'pipeline'):
        assert word in config['deployment']
    assert {'scope', 'attention', 'time_step', 'weights', 'state',
            'precision', 'geometry'} <= set(config['assumed'])
    assert 'no rotation' in config['assumed']['attention'] or \
        'no position' in config['assumed']['attention']
    assert config['engine'] == dict(
        config['engine'], max_batch=64, block_size=32, pages_per_seq=576,
        max_prompt_len=16384, prefill_chunk=512, prefix_cache=False,
        spec_k=0, kv_dtype='bfloat16')
    assert config['geometry']['v5e_compile']


def _params(spec):
    """{name: elements} of the program's own table."""
    from paddle_tpu.serving.decode.model import block_param_shapes
    return {name: int(np.prod(shape)) for name, (shape, _, _)
            in block_param_shapes(spec).items()}


def test_parameters_and_arenas_add_up_to_what_the_issue_counts(resolved):
    """From the program's table: a Mamba-2 layer 109.64 M, an attention
    layer 35.66 M, an expert layer 54.53 M outside its experts of 5.505 M
    each; 4,648 M held (9.30 GB in bfloat16); and, the same table at the
    published counts, 120.67 B in all and 12.77 B active a token."""
    from paddle_tpu.serving.decode import model as lm
    spec = _spec(resolved)
    table = _params(spec)

    def layer(prefix, n, but=()):
        return sum(v for k, v in table.items()
                   if k.startswith(prefix) and k not in but) / n + 4096
    expert = 2 * 1024 * 2688
    stacks = ('lm_moe_exp_up.w', 'lm_moe_exp_down.w')
    mamba, attn = layer('lm_mamba_', 5), layer('lm_attn_', 1)
    moe = layer('lm_moe_', 5, stacks)
    assert round(mamba / 1e6, 2) == 109.64
    assert round(attn / 1e6, 2) == 35.66
    assert round(moe / 1e6, 2) == 54.53
    assert table['lm_moe_exp_up.w'] == 5 * 128 * expert // 2
    assert round(expert / 1e6, 3) == 5.505
    held = sum(table.values())
    assert round(held / 1e6) == 4648
    assert held == round(attn + 5 * mamba + 5 * (moe + 128 * expert)
                         + 2 * 32768 * 4096 + 4096)
    n = {kind: PATTERN.count(kind) for kind in 'M*E'}
    assert n == {'M': 40, '*': 8, 'E': 40}
    heads = 2 * 131072 * 4096 + 4096
    whole = n['M'] * mamba + n['*'] * attn \
        + n['E'] * (moe + 512 * expert) + heads
    active = n['M'] * mamba + n['*'] * attn \
        + n['E'] * (moe + 22 * expert) + heads
    assert round(whole / 1e9, 2) == 120.67
    assert round(active / 1e9, 2) == 12.77
    # the arenas: 5 layers x 65 slots of state and convolution rows, and
    # the one attention layer's K and V pages
    engine = resolved['config']['engine']
    units = lm.unit_bytes_per_kind(spec, engine['block_size'],
                                   engine['kv_dtype'])
    assert units['lm_ssm_state'] == 5 * 128 * 8192 * 4
    assert units['lm_ssm_conv'] == 5 * 3 * 10240 * 2
    assert units['lm_kcache'] == units['lm_vcache'] == 32 * 256 * 2
    arenas = lm.arena_bytes(
        spec, {'': engine['num_blocks'], 'state': engine['max_batch']},
        engine['block_size'], engine['kv_dtype'])
    assert arenas == 65 * (units['lm_ssm_state'] + units['lm_ssm_conv']) \
        + engine['num_blocks'] * 2 * units['lm_kcache']
    geometry = resolved['config']['geometry']
    assert geometry['weights_bytes'] == 2 * held - 2 * sum(
        v for k, v in table.items()
        if lm.block_param_shapes(spec)[k][1] in (None, 0)) + 4 * sum(
        v for k, v in table.items()
        if lm.block_param_shapes(spec)[k][1] in (None, 0))
    assert geometry['arena_bytes'] == arenas


def test_runner_builds_the_block_the_config_describes(resolved):
    from paddle_tpu.serving.decode import model as lm
    spec = _spec(resolved)
    assert spec.block == 'ssm_hybrid' and spec.mixer_only
    assert not spec.tie_embeddings and spec.ssm_groups == 8
    assert spec.layer_types == (lm.ATTENTION,) + (lm.MOE, lm.MAMBA) * 5
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
            spec.ssm_chunk) == (128, 64, 128, 128)
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.experts_per_token, spec.routed_scale) == (
                512, 128, 0, 22, 5.0)
    assert (spec.d_inner, spec.moe_latent, spec.d_inner_shared) == (
        2688, 1024, 5376)
    assert spec.attn_scale == 128 ** -0.5
    kinds = {k.name: k.layers for k in spec.cache_kinds()}
    assert kinds == {'lm_kcache': (0,), 'lm_vcache': (0,),
                     'lm_ssm_state': (2, 4, 6, 8, 10),
                     'lm_ssm_conv': (2, 4, 6, 8, 10)}
    runner = _module('runners', 'serve_ssm_moe')
    for over in (dict(n_groups=1, hybrid_override_pattern='*EMEMEMEMEM-'),
                 dict(mlp_hidden_act='silu'), dict(first_layer=24),
                 dict(tie_word_embeddings=True)):
        with pytest.raises(ValueError, match='serve_ssm_moe'):
            runner.spec_of(dict(resolved['config'], **over))
    # what serve_ssm.py builds is another block: it refuses this file
    with pytest.raises((ValueError, KeyError)):
        _module('runners', 'serve_ssm').spec_of(resolved['config'])


def test_the_time_constants_keep_to_the_configurations_range(resolved):
    import jax
    runner = _module('runners', 'serve_ssm_moe')
    config = resolved['config']
    dt_bias, a_log = runner._time_constants(
        jax.random.PRNGKey(3), (5, 128),
        (config['time_step_min'], config['time_step_max']),
        config['time_step_floor'])
    dt = np.log1p(np.exp(np.asarray(dt_bias, 'float64')))
    assert dt.min() >= 0.001 * 0.999 and dt.max() <= 0.1 * 1.001
    assert 1.0 <= np.exp(np.asarray(a_log)).min() and \
        np.exp(np.asarray(a_log)).max() <= 16.0
    assert set(runner.TO_RESIDUAL) == {
        'lm_mamba_out.w', 'lm_attn_o.w', 'lm_moe_lat_out.w',
        'lm_moe_shr_down.w'}


def test_the_traffic_is_the_issues_mix(resolved):
    traffic = resolved['traffic']
    assert traffic['kind'] == 'serve' and traffic['pool_seed'] == 58
    assert traffic['prompt_len'] == [1024, 16384]
    assert traffic['answer_len'] == [128, 2048] and traffic['alpha'] == 1.3
    requests = loadgen.schedule(traffic, 1, 51)
    window = [r for r in requests if r.due >= traffic['preroll_s']]
    assert len(window) >= 40
    assert any(r.prompt_len + r.answer_len
               > resolved['config']['reference']['long_tokens']
               for r in window)
    assert max(r.prompt_len + r.answer_len for r in requests) \
        <= 576 * 32
    # another seed, the same requests at the same instants
    again = loadgen.schedule(traffic, 2, 51)
    assert [(r.due, r.prompt_len, r.answer_len) for r in requests] == \
        [(r.due, r.prompt_len, r.answer_len) for r in again]


# --------------------------------------------------- the shape functions
def _registry(**counters):
    return {'counters': dict(counters), 'histograms': {}, 'gauges': {}}


def test_the_state_update_moves_a_row_s_state_once_each_way(resolved):
    shapes = _module('shape_fns', 'ssmoe_decode_live_bytes')
    config = resolved['config']
    assert shapes.row_layer_bytes(config) == 2 * 4194304 + 2 * 61440
    spec = _metric(resolved, 'serve.ssmoe_state_update_roofline_share')
    assert spec['args']['function_args']['row_layer_bytes'] == \
        shapes.row_layer_bytes(config)
    fn = _module('shape_fns', spec['args']['function'])
    before = _registry(**{'decode.steps_total': 10,
                          'decode.step_state_rows_total': 100})
    after = _registry(**{'decode.steps_total': 20,
                         'decode.step_state_rows_total': 2100})
    # 40 live rows x 5 layers a step
    assert fn.per_step(before, after, **spec['args']['function_args']) \
        == 200 * 8511488
    assert fn.per_step(before, before,
                       **spec['args']['function_args']) is None


def test_the_expert_bytes_are_two_matrices_a_touched_expert(resolved):
    shapes = _module('shape_fns', 'ssmoe_decode_live_bytes')
    reader = _module('readers', 'ssmoe_moe_ffn_roofline')
    config = resolved['config']
    assert shapes.expert_bytes(config) == 2 * 1024 * 2688 * 2
    assert [shapes.layers_of(config, k) for k in 'M*E'] == [5, 1, 5]
    # ISSUE 58: 106 of 128 touched in each of five layers, 1.17 GB a layer
    assert round(reader.least_bytes_per_step(config, 106) / 5 / 1e9, 2) \
        == 1.17
    assert reader.read({'match': [], 'peak': 'hbm_bytes_per_s'},
                       {'trace': None, 'peaks': None}) is None


def test_the_step_bytes_are_weights_state_and_the_attended_rows(resolved):
    """Every weight held once with all 128 experts touched is the
    program's table at its dtypes less the embedding, of which a step
    reads its rows' rows."""
    shapes = _module('shape_fns', 'ssmoe_decode_live_bytes')
    config = resolved['config']
    geometry = config['geometry']
    emb = 2 * 32768 * 4096
    assert shapes.weight_bytes(config, 128) == \
        geometry['weights_bytes'] - emb
    assert shapes.weight_bytes(config, 128) \
        - shapes.weight_bytes(config, 106) == 5 * 22 * 2 * 1024 * 2688 * 2
    # 1,024 B a token: one attention layer of 2 KV heads x 128, K and V
    assert shapes.kv_bytes(config, 1000) == 1000 * 1024


def test_trace_patterns_are_the_configs_numbers(resolved):
    """The patterns name the arenas and the stacks by the shapes the
    program builder gives them: a pattern that drifted from the
    configuration would match nothing and read 0."""
    from paddle_tpu.serving.decode import model as lm
    spec = _spec(resolved)
    engine = resolved['config']['engine']
    table = lm.block_param_shapes(spec)
    experts = 'serve.ssmoe_moe_ffn_roofline_share'
    update = 'serve.ssmoe_state_update_roofline_share'

    def typed(name, dtype='bf16'):
        return '%s[%s]' % (dtype, ','.join(map(str, table[name][0])))

    def matches(metric, text):
        return any(re.search(p, '%fusion.1 = f32[8] fusion(' + text + ')')
                   for p in _metric(resolved, metric)['args']['match'])
    for name in ('lm_moe_exp_up.w', 'lm_moe_exp_down.w'):
        assert matches(experts, typed(name))
        assert not matches(update, typed(name))
    # the products on the hidden width and the mixers' are nobody's here
    for name in ('lm_moe_lat_in.w', 'lm_moe_lat_out.w', 'lm_moe_shr_up.w',
                 'lm_moe_shr_down.w', 'lm_mamba_in.w', 'lm_mamba_out.w',
                 'lm_attn_q.w', 'lm_moe_router.w'):
        assert not matches(experts, typed(name))
        assert not matches(update, typed(name))
    arenas = {k.name: [len(k.layers), (engine['max_batch'] + 1)
                       if k.per_seq else engine['num_blocks']]
              + list(k.unit_shape(engine['block_size']))
              for k in spec.cache_kinds()}
    state = 'f32[%s]' % ','.join(map(str, arenas['lm_ssm_state']))
    conv = 'bf16[%s]' % ','.join(map(str, arenas['lm_ssm_conv']))
    kv = 'bf16[%s]' % ','.join(map(str, arenas['lm_kcache']))
    assert (state, conv, kv) == ('f32[5,65,128,8192]', 'bf16[5,65,30720]',
                                 'bf16[1,%d,32,256]' % engine['num_blocks'])
    for arena in (state, conv):
        assert matches(update, arena) and not matches(experts, arena)
    assert not matches(update, kv) and not matches(experts, kv)
    # a loop, a conditional or a call is named with its whole body's time
    assert not any(re.search(p, '%while.3 = (' + state + ') while(...)')
                   for p in _metric(resolved, update)['args']['match'])
    for metric in (experts, update):
        assert _metric(resolved, metric)['args']['peak'] == \
            'hbm_bytes_per_s'


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
    end with the test (tests/benchmark/test_kimi_k2_6.py: the same
    fixture)."""
    from paddle_tpu import observe
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def test_the_cell_rehearses_and_reads_the_series_it_is_listed_on(
        capsys, own_environment):
    """The cell end to end on the CPU, traced (one run: an untraced one
    takes the same path without the readers): correct against the
    reference, the one-at-a-time check, no compile in the window; and
    under --trace 1 the program's counters reach the line: every shared
    program_counter entry ISSUE 58 names reads a number, the state
    pool's used share with them; a share of a peak has no device to be
    of on the CPU and is left out."""
    assert bench.main(['--workload', CELL, '--seed', '2147483705',
                       '--seconds', '3', '--trace', '1',
                       '--rehearsal']) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    window = json.loads([ln for ln in lines
                         if ln.startswith('WINDOW ')][-1][7:])
    got = {k: v['value'] for k, v in last['metrics'].items()}
    assert last['rehearsal'] is True and last['correct'] is True
    assert last['attempted'] > 10 and last['failed'] == 0
    assert window['same_one_at_a_time'] is True and window['rechecked'] == 2
    assert window['reference_gap_max'] <= 1e-4
    assert window['reference_longest_tokens'] > 32
    assert window['refused'] == 0 and window['compiles_in_window'] == 0
    assert window['signatures'] == 3           # chunks of 8 and 16, the step
    assert 0 < got['serve.ssm_state_slots_used_pct'] <= 100
    # 4 of 8 experts held and 3 chosen: some choices are local
    assert 0 < got['serve.moe_local_assignment_pct'] < 100
    assert got['serve.moe_load_max_over_mean'] >= 1
    assert 0 < got['serve.moe_row_tiles_run_share'] <= 100
    assert got['serve.recompiles'] == 0
    assert 0 <= got['serve.kv_pool_used_pct'] <= 100
    assert got['serve.live_tokens_per_step'] > 0
    assert got['serve.prefill_chunks_per_prompt'] >= 1
    for name in OWN_METRICS:
        assert name not in got                              # no device
