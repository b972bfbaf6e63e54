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
# the entries that carry this configuration's shapes, each with the
# end-to-end metric it moves
OWN_MOVES = {
    'serve.ssmoe_moe_ffn_roofline_share': 'itl_mean_ms',
    'serve.ssmoe_state_update_roofline_share': 'itl_mean_ms',
    'serve.ssmoe_moe_ffn_busy_share': 'itl_mean_ms',
    'serve.ssmoe_latent_proj_busy_share': 'itl_mean_ms',
    'serve.ssmoe_state_update_busy_share': 'itl_mean_ms',
    'serve.ssmoe_scan_busy_share': 'ttft_mean_ms',
    'serve.ssmoe_scan_mxu_share': 'ttft_mean_ms',
    'serve.ssmoe_attn_busy_share': 'itl_mean_ms',
    'serve.ssmoe_step_hbm_share': 'itl_mean_ms'}
OWN_METRICS = set(OWN_MOVES)
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
    assert len(MANIFEST['per_layer']) <= 128       # the contract's most
    # each moves what its sibling under serve.ssm_* moves (the scan's two
    # the first token, the others the gap between tokens)
    for name, moves in OWN_MOVES.items():
        assert by_name[name]['moves'] == moves
        sibling = by_name.get(name.replace('serve.ssmoe_', 'serve.ssm_'))
        assert sibling is None or sibling['moves'] == moves
        assert by_name[name]['source'] == (
            'program_span' if name.endswith('_hbm_share')
            else 'device_trace')
        assert by_name[name]['better'] == (
            'lower' if name.endswith('_busy_share') else 'higher')


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


def test_the_step_share_is_the_shape_functions_bytes_over_the_peak(
        resolved):
    """``serve.ssmoe_step_hbm_share``: ``readers/shape_fn.py`` over
    ``shape_fns/ssmoe_decode_live_bytes.py::compute``, window before ->
    after: 18 live rows a step, 70 experts touched a layer, 65,000 live
    positions, a step of 9.25 ms."""
    spec = _metric(resolved, 'serve.ssmoe_step_hbm_share')
    assert spec['reader'] == 'shape_fn' and spec['args'] == {
        'function': 'ssmoe_decode_live_bytes', 'peak': 'hbm_bytes_per_s'}
    shapes = _module('shape_fns', 'ssmoe_decode_live_bytes')
    config = resolved['config']
    before = {'counters': {}, 'histograms': {}}
    after = {'counters': {'decode.steps_total': 100,
                          'decode.step_state_rows_total': 5 * 1800,
                          'decode.moe_experts_touched': 5 * 100 * 70,
                          'decode.moe_layer_steps': 5 * 100},
             'histograms': {
                 'decode.step_seconds': {'sum': 0.925, 'count': 100},
                 'decode.step_live_tokens': {'sum': 6.5e6, 'count': 100}}}
    sources = {'registry_before': before, 'registry_after': after,
               'config': config, 'bench_dir': BENCH,
               'peaks': {'hbm_bytes_per_s': 819e9}}
    want = (shapes.weight_bytes(config, 70) + 5 * 18 * 8511488
            + 65000 * 1024) / 0.00925
    got = _module('readers', 'shape_fn').read(spec['args'], sources)
    assert abs(got - 100 * want / 819e9) < 1e-9 * got
    # 1.98 GB of weights outside the routed experts, 3.85 GB of touched
    # experts, 0.77 GB of state, 0.07 GB of K and V: 6.67 GB a step
    assert round(shapes.weight_bytes(config, 0) / 1e9, 2) == 1.98
    assert round(want * 0.00925 / 1e9, 2) == 6.67 and 85 < got < 90
    assert _module('readers', 'shape_fn').read(
        spec['args'], dict(sources, peaks=None)) is None
    assert _module('readers', 'shape_fn').read(
        spec['args'], dict(sources, registry_after=before)) is None


def test_the_scan_s_least_operations_are_the_recurrence_s(resolved):
    """``shape_fns/ssmoe_scan_flops.py`` by hand at the published widths:
    S = decay S + (dt x) (x) B is 3, y = S C is 2 operations an element
    of a head's 64 x 128 state, 128 heads: 5,242,880 a (row, layer)."""
    shapes = _module('shape_fns', 'ssmoe_scan_flops')
    config = resolved['config']
    assert (config['mamba_num_heads'], config['mamba_head_dim'],
            config['ssm_state_size']) == (128, 64, 128)
    assert shapes.least_flops(1, config) == 5 * 128 * 64 * 128 == 5242880
    # a chunk of 512 live rows through the 5 Mamba-2 layers: 13.4 GFLOP,
    # 68 us at the bf16 peak
    assert shapes.least_flops(512 * 5, config) == 13421772800
    # twice granite's a (row, layer): twice the heads at the same widths
    granite = manifest.read_json(os.path.join(
        BENCH, 'configs', 'granite_4_0_h_micro.json'))
    assert shapes.least_flops(7, config) == \
        2 * _module('shape_fns', 'ssm_scan_flops').least_flops(7, granite)
    # granite's function reads granite's keys and stays as it is
    with pytest.raises(KeyError):
        _module('shape_fns', 'ssm_scan_flops').least_flops(1, config)
    spec = _metric(resolved, 'serve.ssmoe_scan_mxu_share')
    assert spec['reader'] == 'prefill_ops_mxu'
    assert spec['args']['function'] == 'ssmoe_scan_flops'
    assert spec['args']['peak'] == 'flops_bf16'
    assert re.compile(spec['args']['program']).search(
        'jit_prefill_512').group(1) == '512'
    assert spec['args']['match'] == _metric(
        resolved, 'serve.ssmoe_scan_busy_share')['args']['match']


def test_the_scan_share_is_read_by_chunk_over_a_traced_tail(resolved,
                                                             capsys):
    """``serve.ssmoe_scan_mxu_share`` through ``readers/prefill_ops_mxu``
    on a made-up tail of 10 s, written in milliseconds: one prefill of a
    512 and a 128 chunk whole inside it, the scan's ops inside each
    chunk's program run counted, the slot's read, the gated norm and the
    routed experts beside them not, nor a step's ops."""
    ms = 1000000
    scan = SCAN_OPS['masked_product']
    chunks = [dict(run=0, t=2.0, dur=3.0, bucket=512, pairs=512 * 5),
              dict(run=0, t=2.0, dur=3.0, bucket=128, pairs=100 * 5)]
    runs = [('jit_prefill_512(1)', 2100 * ms, 1500 * ms),
            ('jit_decode_step(2)', 3700 * ms, 100 * ms),
            ('jit_prefill_128(3)', 3900 * ms, 800 * ms)]
    host = [('decode.prefill.run', 2000 * ms, 3000 * ms)]
    device = [(scan, 2200 * ms, 300 * ms),
              (STATE_OPS['slot_read'], 2600 * ms, 100 * ms),
              (OTHER_OPS['gated_norm'], 2800 * ms, 100 * ms),
              (EXPERT_OPS['chunk_kernel'], 3000 * ms, 400 * ms),
              (scan, 3720 * ms, 50 * ms),      # under a step: not a chunk's
              (SCAN_OPS['rows'], 4000 * ms, 200 * ms),
              (scan, 9000 * ms, 10 * ms)]      # under no program run
    spec = _metric(resolved, 'serve.ssmoe_scan_mxu_share')
    sources = dict(
        trace={'window': (0, 10000 * ms), 'first': device, 'host': host},
        peaks={'flops_bf16': 1e12}, config=resolved['config'],
        bench_dir=BENCH, prefill_chunks=chunks, prefill_program_runs=runs)
    got = _module('readers', 'prefill_ops_mxu').read(spec['args'], sources)
    flops = (512 + 100) * 5 * 5242880
    np.testing.assert_allclose(got, 100.0 * (flops / 1e12) / 0.5)
    said = json.loads(capsys.readouterr().out.split('PREFILL_CHUNKS ')[1])
    assert said == {'read': 2, 'dropped': 0, 'why': None, 'shift': 0}
    # the busy share of the same ops over the same tail: 560 of 1,160 ms
    busy = _module('readers', 'trace_share').read(
        _metric(resolved, 'serve.ssmoe_scan_busy_share')['args'], sources)
    np.testing.assert_allclose(busy, 100.0 * 560 / 1160)


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
    # a busy share goes by its roofline share's patterns
    assert _metric(resolved, 'serve.ssmoe_moe_ffn_busy_share')['args'][
        'match'] == _metric(resolved, experts)['args']['match']
    assert _metric(resolved, 'serve.ssmoe_state_update_busy_share')[
        'args']['match'] == _metric(resolved, update)['args']['match']
    # the stacks every row meets in an expert layer, by the table
    latent = 'serve.ssmoe_latent_proj_busy_share'
    for name in ('lm_moe_lat_in.w', 'lm_moe_lat_out.w', 'lm_moe_shr_up.w',
                 'lm_moe_shr_down.w'):
        assert matches(latent, typed(name))
    for name in ('lm_moe_exp_up.w', 'lm_moe_exp_down.w', 'lm_moe_router.w',
                 'lm_mamba_in.w', 'lm_mamba_out.w', 'lm_attn_q.w',
                 'lm_attn_o.w'):
        assert not matches(latent, typed(name))
    # the attention's arena, a column block's pages (8 pairs x 16 pages
    # of 32 = 512 keys) and the scatter's max_batch + BLOCK_ROWS rows
    from paddle_tpu.ops.pallas import paged_attention as pa
    attn = ' '.join(_metric(resolved, 'serve.ssmoe_attn_busy_share')[
        'args']['match'])
    assert re.escape(kv) in attn
    heads, width = spec.n_head, spec.d_key
    kv_heads = spec.n_kv_head
    assert (heads, kv_heads, width) == (32, 2, 128)
    assert 'f32\\[(%d|%d),%d,%d\\]' % (
        engine['max_batch'] + pa.BLOCK_ROWS, pa.BLOCK_ROWS, heads,
        width) in attn
    assert 'f32\\[%d,%d,%d\\]' % (
        pa.BLOCK_ROWS, kv_heads, heads // kv_heads) in attn
    assert engine['max_batch'] != pa.BLOCK_ROWS
    # the scan's chunk, heads, groups and state
    config = resolved['config']
    scan = ' '.join(_metric(resolved, 'serve.ssmoe_scan_busy_share')[
        'args']['match'])
    q, n, g = config['chunk_size'], config['ssm_state_size'], \
        config['n_groups']
    h, w = config['mamba_num_heads'], config['mamba_head_dim']
    assert '\\[%d,%d,%d\\]' % (q, h, w) in scan
    assert 'f32\\[%d,(%d,)?%d,%d\\]' % (g, h // g, q, q) in scan
    assert 'bf16\\[%d,%d,%d\\]' % (q, g, n) in scan
    assert 'f32\\[%d,%d,%d\\]' % (g, h // g * w, n) in scan
    assert '\\[%d,%d\\]' % (q, h * w) in scan
    assert re.escape(state) in scan                     # left out
    in_width = 2 * h * w + 2 * g * n + h
    assert in_width == table['lm_mamba_in.w'][0][-1] and \
        '%d' % in_width in scan
    assert q > engine['max_batch']      # no step's op has a chunk's rows


# Op lines of the decode step over the 64 slots, of the 512 chunk and of
# the 128 chunk as the v5e's compiler wrote them at the published widths
# (a trace names an op by its line with the operands' types; layouts left
# out but for one): each family's own, then ops near them in shape that
# are nobody's. `step_`, `chunk_` and `chunk_128_` say which program a
# line is from. They are examples a reader can follow; what holds the
# patterns to today's lowering is the test on the text compiled now,
# further down.
EXPERT_OPS = {
    'step_kernel': '%moe_routed_product.10 = f32[64,1024] custom-call(s32[] '
                   '%bitcast.276, s32[] %constant.313, s32[1] %constant.426, '
                   's32[128] %broadcast_minimum_fusion.5, s32[1] '
                   '%dynamic_slice.117, bf16[64,1024] %fusion.388, f32[128,'
                   '64] %select_bitcast_fusion.4, bf16[5,128,1024,2688] '
                   '%w__ExpUp__.1, bf16[5,128,2688,1024] %w__ExpDown__.1)',
    'chunk_kernel': '%moe_routed_product.10 = f32[512,1024] custom-call(s32[]'
                    ' %bitcast.32, s32[216] %broadcast_minimum_fusion.4, '
                    's32[11264] %add_select_fusion.5, bf16[512,1024] '
                    '%fusion.505, f32[128,512] %bitcast_select_fusion.11, '
                    's32[128,512] %get-tuple-element.466, bf16[5,128,1024,'
                    '2688] %w__ExpUp__.1, bf16[5,128,2688,1024] '
                    '%w__ExpDown__.1)'}
LATENT_OPS = {
    'step_latent_in': '%fusion.388 = bf16[64,1024] fusion(bf16[5,4096,1024] '
                      '%custom-call.47, f32[64,4096] %get-tuple-element.850, '
                      'f32[4096] %bitcast.1169, f32[64] %add_rsqrt_fusion.15)',
    'step_latent_out': '%multiply_reduce_fusion.13 = (f32[64], f32[64,4096]) '
                       'fusion(f32[64,4096] %get-tuple-element.850, f32[64,'
                       '4096] %fusion.146, bf16[5,1024,4096] %w__LatOut__.1, '
                       'f32[64,1024] %moe_routed_product.10)',
    'chunk_shared_up': '%fusion.147 = bf16[512,5376] fusion(bf16[5,4096,5376] '
                       '%w__ShrUp__.1, f32[512,4096] %get-tuple-element.556, '
                       'f32[4096] %bitcast.1466, f32[512] %add_rsqrt_fusion.'
                       '14)',
    'chunk_shared_down': '%multiply_reduce_fusion.9 = (f32[512], f32[512,4096]'
                         ') fusion(f32[512,4096] %get-tuple-element.556, '
                         'f32[512,4096] %fusion.254, bf16[512,5376] '
                         '%fusion.147, bf16[5,5376,4096] %w__ShrDown__.1)',
    'step_staging': '%slice-start.5 = ((bf16[5,4096,1024]), bf16[2,4096,1024]'
                    ', s32[]) slice-start(bf16[5,4096,1024] %w__LatIn__.1)'}
STATE_OPS = {
    'step_kernel': '%ssm_state_update.10 = (f32[5,65,128,8192], f32[64,8192], '
                   'bf16[5,65,30720]) custom-call(s32[] %max.9, s32[64] '
                   '%bitcast.1249, s32[64] %sort.16, f32[5,65,128,8192] '
                   '%a__SsmState__.1, f32[64,8192] %reshape.94, f32[64,8192] '
                   '%slice_multiply_fusion.4, f32[64,8,128] %bitcast.1174, '
                   'f32[64,8,128] %bitcast.1175, bf16[64,30720] '
                   '%bitcast.1140, bf16[5,65,30720] %a__SsmConv__.1)',
    'step_conv_rows': '%slice_bitcast_fusion.19 = bf16[65,30720] fusion('
                      'bf16[5,65,30720] %a__SsmConv__.1)',
    'slot_read': '%dynamic-slice_bitcast_fusion.5 = f32[128,8192] fusion('
                 'f32[5,65,128,8192] %a__SsmState__.1, s32[] %select_n.38)',
    'chunk_slot_write': '%bitcast_dynamic-update-slice_fusion.4 = (f32[5,65,'
                        '128,8192], bf16[128,8192], f32[128,8192]) fusion('
                        'f32[5,65,128,8192] %a__SsmState__.1, s32[] '
                        '%select_n.38, f32[128,8192] %copy.1692, f32[8192] '
                        '%mul.898, f32[128,8192] %dynamic-slice_bitcast_'
                        'fusion.5, pred[] %eq.79)',
    'chunk_conv_write': '%dynamic_update_slice.16 = bf16[5,65,30720] '
                        'dynamic-update-slice(bf16[5,65,30720] '
                        '%a__SsmConv__.1, bf16[1,1,30720] %reshape.224, '
                        's32[] %constant.327, s32[] %select_n.38, s32[] '
                        '%constant.327)'}
SCAN_OPS = {
    'masked_product': '%fusion.301 = f32[128,128,64]{0,2,1:T(8,128)S(1)} '
                      'fusion(f32[128,128,128]{2,1,0:T(8,128)S(1)} '
                      '%bitcast.1249, f32[128,128]{1,0:T(8,128)S(1)} '
                      '%copy.1633, f32[128,128,64]{0,2,1:T(8,128)S(1)} '
                      '%bitcast.1292, pred[128,128]{1,0:T(8,128)(4,1)S(1)} '
                      '%iota_compare_fusion.11, f32[128,128]{0,1:T(8,128)'
                      'S(1)} %copy.1635)',
    'scores': '%fusion.682 = f32[8,128,128] fusion(bf16[16,8,8,128] '
              '%get-tuple-element.331, bf16[16,8,8,128] '
              '%get-tuple-element.330)',
    'spread': '%broadcast_in_dim.63 = f32[8,16,128,128] broadcast('
              'f32[8,128,128] %fusion.682)',
    'state_left': '%fusion.680 = f32[8,1024,128] fusion(bf16[128,8,1024] '
                  '%fusion.367, bf16[16,8,8,128] %get-tuple-element.331)',
    'b_and_c': '%fusion.947 = (bf16[128,8,128], bf16[128,8,128], '
               'bf16[128,8,128]) fusion(bf16[512,8,128] %bitcast.1384)',
    'rows': '%slice.596 = f32[128,8192] slice(f32[512,10240] '
            '%get-tuple-element.292)',
    'casts': '%fusion.937 = (bf16[128,8192], bf16[128,8192]) fusion('
             'f32[128,8192] %get-tuple-element.286, f32[128,8192] '
             '%copy.1657, f32[8192] %mul.801)',
    'laid_end_to_end': '%custom-call.64 = f32[128,8192] custom-call('
                       'f32[32,8192] %slice-done.20, f32[32,8192] '
                       '%slice-done.21, f32[32,8192] %slice-done.22, '
                       'f32[32,8192] %slice-done.23)',
    # a 128 bucket's rows are one scan chunk: what feeds the scan there
    # stands outside its scope and is matched as in the longer chunks
    'chunk_128_rows': '%slice.295 = f32[128,8192] slice(f32[128,10240] '
                      '%get-tuple-element.272)',
    'chunk_128_state_staged': '%copy-start.3 = (f32[128,8192], f32[128,8192]'
                              ', u32[]) copy-start(f32[128,8192] '
                              '%dynamic-slice_bitcast_fusion.4)'}
ATTN_OPS = {
    'step_gather': '%fusion.584 = bf16[128,32,256] fusion(bf16[1,36864,32,256]'
                   ' %get-tuple-element.1256, s32[128] %reshape.1490)',
    'step_scores': '%fusion.588 = f32[8,1,16,1,512] fusion(bf16[8,512,256] '
                   '%bitcast.1105, bf16[8,2,16,1,128] %bitcast.1134)',
    'step_sums': '%fusion.598 = f32[8,1,16,1,128] fusion(bf16[8,512,256] '
                 '%bitcast.1103, bf16[8,2,16,1,512] %get-tuple-element.1125)',
    'step_maxima': '%fusion.590 = f32[8,2,16] fusion(f32[8,1,16,1,512] '
                   '%fusion.587, f32[8,1,16,1,512] %fusion.588, pred[8,512] '
                   '%fusion.589)',
    'step_open_row': '%broadcast_divide_fusion.23 = f32[2,16,1,128] fusion('
                     'f32[2,16,1,128] %get-tuple-element.1168, f32[2,16] '
                     '%fusion.614)',
    'step_queries': '%fusion.586 = bf16[8,32,128] fusion(bf16[64,32,128] '
                    '%get-tuple-element.1254, s32[1024] %pad_clamp_fusion.4)',
    'step_scatter': '%fusion.615 = f32[72,32,128] fusion(f32[72,32,128] '
                    '%get-tuple-element.1234, s32[8] %get-tuple-element.1155,'
                    ' f32[8,32,128] %constant_dynamic-update-slice_fusion.23)',
    'step_write': '%dynamic_update_slice.7 = bf16[1,36864,32,256] '
                  'dynamic-update-slice(bf16[1,36864,32,256] '
                  '%get-tuple-element.1187, bf16[1,1,1,256] '
                  '%broadcast_select_fusion.26, s32[] %select_n.1174)',
    'chunk_gather': '%fusion.1053 = bf16[16,32,256] fusion(bf16[1,36864,32,'
                    '256] %get-tuple-element.814, s32[16] %or_bitcast_fusion'
                    '.2)',
    'chunk_scores': '%fusion.1054 = (f32[2,16,512], f32[2,16,512,512]) fusion('
                    'bf16[512,2,128,1] %bitcast.1271, bf16[1,2,16,512,128] '
                    '%get-tuple-element.816, pred[512,512] '
                    '%compare_and_fusion.4)',
    'chunk_sums': '%fusion.1058 = f32[1,2,16,512,128] fusion(f32[2,16,512,512]'
                  ' %get-tuple-element.738, f32[2,16,512] %bitcast.1269, '
                  'pred[512,512] %compare_and_fusion.4, bf16[512,2,128,1] '
                  '%bitcast.1272)',
    'chunk_normalised': '%divide_convert_fusion = bf16[1,2,16,512,128] fusion('
                        'f32[1,2,16,512,128] %copy.1618, f32[2,16,512] '
                        '%fusion.833)'}
OTHER_OPS = {
    # the Mamba-2 projections: plain products on the hidden width
    'step_mamba_in': '%fusion.108 = f32[64,18560] fusion(bf16[5,4096,18560] '
                     '%w__SsmIn__.1, f32[64,4096] %get-tuple-element.854, '
                     'f32[4096] %bitcast.1236, f32[64] %add_rsqrt_fusion.14)',
    'step_mamba_out': '%multiply_reduce_fusion.12 = (f32[64], f32[64,4096]) '
                      'fusion(f32[64,4096] %get-tuple-element.854, '
                      'bf16[5,8192,4096] %w__SsmOut__.1, f32[64,8192] '
                      '%get-tuple-element.477, f32[8192] %bitcast.1223)',
    # ... whose operands in a 128 bucket have a scan chunk's shape: the
    # output projection as the compiler writes it (the gated norm fused
    # in) and as it would stand alone, the input projection, the
    # convolution
    'chunk_128_mamba_out': '%fusion.54 = f32[128,4096] fusion(bf16[5,8192,'
                           '4096] %w__SsmOut__.1, f32[128,8192] %bitcast.825,'
                           ' f32[8192] %bitcast.884, f32[128,8192] %copy.695,'
                           ' f32[128,18560] %fusion.39)',
    'chunk_128_mamba_out_alone': '%multiply_reduce_fusion.12 = (f32[128], '
                                 'f32[128,4096]) fusion(f32[128,4096] '
                                 '%get-tuple-element.854, bf16[5,8192,4096] '
                                 '%w__SsmOut__.1, f32[128,8192] '
                                 '%get-tuple-element.477, f32[8192] '
                                 '%bitcast.1223)',
    'chunk_128_mamba_in': '%fusion.47 = f32[128,18560] fusion(bf16[5,4096,'
                          '18560] %w__SsmIn__.1, f32[128,4096] '
                          '%get-tuple-element.370, f32[4096] %bitcast.894, '
                          'f32[128] %add_rsqrt_fusion.13)',
    'chunk_128_convolution': '%fusion.215 = f32[131,10240] fusion('
                             'bf16[3,10240] %reshape.476, bf16[128,10240] '
                             '%slice.800)',
    # ... and its rows x experts held the shape of the scan's small ops
    'chunk_128_routing_weights': '%select_bitcast_fusion.4 = f32[128,128] '
                                 'fusion(f32[128,128] %copy.592, '
                                 'pred[128,128] %copy.588, pred[128] '
                                 '%iota_compare_fusion.11)',
    # the gated norm reads the scan's results and is not the scan
    'gated_norm': '%fusion.929 = (f32[512,8192], f32[512,8192]) fusion('
                  'f32[512,18560] %fusion.62, f32[512,10240] '
                  '%get-tuple-element.292, f32[8192] %reshape.485, '
                  'f32[128,8192] %copy.1671, f32[128,8192] %custom-call.64)',
    # the scan's small ops: [rows, heads] is [128, 128] here, which a 128
    # bucket's router and routed product also have (rows x experts held)
    'chunk_scan_decays': '%reduce-window.38 = f32[128,128] reduce-window('
                         'f32[128,128] %copy.1632, f32[] %constant.321)',
    # the router and its top-k
    'step_router': '%broadcast_add_fusion.4 = (f32[64,512], f32[64,512]) '
                   'fusion(f32[512] %bitcast.1248, bf16[5,4096,512] '
                   '%custom-call.48, f32[64,4096] %get-tuple-element.850, '
                   'f32[4096] %bitcast.1168, f32[64] %add_rsqrt_fusion.15)',
    'step_top_k': '%sort.14 = (f32[64,512], s32[64,512]) sort(f32[64,512] '
                  '%get-tuple-element.851, s32[64,512] %iota.4)',
    'step_routing_weights': '%select_bitcast_fusion.4 = f32[128,64] fusion('
                            'f32[64,128] %copy.360, pred[64,128] %copy.356, '
                            'pred[64] %bitcast.1185)',
    # the attention's projections, the pair list's mask and pages
    'step_query_projection': '%fusion.321 = bf16[64,32,128] fusion('
                             'bf16[32,128,4096] %bitcast.1103, f32[4096] '
                             '%bitcast.1104, f32[64] %add_rsqrt_fusion.2, '
                             'bf16[64,4096] %fusion.13, pred[64] '
                             '%compare_and_fusion.1)',
    'step_output_projection': '%multiply_reduce_fusion.14 = (f32[64], '
                              'f32[64,4096]) fusion(bf16[1,4096,4096] '
                              '%copy-done, bf16[8,8,32,128] %bitcast.1141, '
                              'bf16[64,4096] %fusion.13, pred[64] '
                              '%compare_and_fusion.1)',
    'step_pair_mask': '%fusion.589 = pred[8,512] fusion(s32[8] '
                      '%get-tuple-element.1094, s32[8] '
                      '%get-tuple-element.1095, s32[8] '
                      '%get-tuple-element.1096)',
    'step_pair_pages': '%fusion.583 = s32[8,16] fusion(s32[2304,16] '
                       '%get-tuple-element.1255, s32[] %select_n.1193)',
    # the embedding's gather and a loop, which is named with its body's
    # time
    'step_embedding': '%fusion.13 = bf16[64,4096] fusion(bf16[32768,4096] '
                      '%w__Emb__.1, s32[1024] %pad_clamp_fusion.2)',
    'layer_loop': '%while.4 = (s32[], f32[5,65,128,8192], bf16[5,65,30720], '
                  'bf16[1,36864,32,256], bf16[5,128,1024,2688], '
                  'bf16[5,4096,1024], f32[128,8192]) while(%tuple.9)'}
FAMILIES = {
    'serve.ssmoe_moe_ffn_busy_share': EXPERT_OPS,
    'serve.ssmoe_latent_proj_busy_share': LATENT_OPS,
    'serve.ssmoe_state_update_busy_share': STATE_OPS,
    'serve.ssmoe_scan_busy_share': SCAN_OPS,
    'serve.ssmoe_attn_busy_share': ATTN_OPS}
OP_CASES = [(metric, op) for metric, ops in sorted(FAMILIES.items())
            for op in sorted(ops)] + [
                (None, op) for op in sorted(OTHER_OPS)]


@pytest.mark.parametrize('family, op', OP_CASES)
def test_a_family_s_patterns_find_its_ops_and_no_other_family_s(
        resolved, family, op):
    """Each of the five families of ops that the new trace entries share
    the busy time among matches the lines of its own layer and none of
    another's, and nobody matches the ops left to no entry; the scan's
    MXU share goes by the scan's busy share's patterns, and the two
    roofline shares PR 58 brought by their busy shares'."""
    line = FAMILIES[family][op] if family else OTHER_OPS[op]
    for metric in FAMILIES:
        patterns = _metric(resolved, metric)['args']['match']
        assert any(re.search(p, line) for p in patterns) == \
            (metric == family), (metric, op)
    # no prefill op matches an entry of the decode step alone: the two
    # roofline shares are read under decode.step spans, and of the lines
    # from a chunk they know only the slot's and the kernel's own
    for metric, own in (
            ('serve.ssmoe_moe_ffn_roofline_share', EXPERT_OPS),
            ('serve.ssmoe_state_update_roofline_share', STATE_OPS)):
        patterns = _metric(resolved, metric)['args']['match']
        assert any(re.search(p, line) for p in patterns) == \
            (family is not None and FAMILIES[family] is own)
    # ... and no line of the decode step is the scan's
    if op.startswith('step_'):
        assert not any(re.search(p, line) for p in _metric(
            resolved, 'serve.ssmoe_scan_mxu_share')['args']['match'])


# ------------------------------------- the same, on the text compiled now
# The named scope of each family's layer in an op's ``op_name``: the
# program's own words (ops/ssm_hybrid_ops.py, ops/ssm_ops.py), which the
# trace does not carry and the compiled text does.
LAYER_SCOPES = {
    'serve.ssmoe_moe_ffn_busy_share': ('moe_routed_relu2',),
    'serve.ssmoe_latent_proj_busy_share': (
        'moe_latent_in', 'moe_latent_out', 'moe_shared_relu2'),
    'serve.ssmoe_state_update_busy_share': ('ssm_state_update',),
    'serve.ssmoe_scan_busy_share': ('ssm_chunk_scan',),
    'serve.ssmoe_attn_busy_share': ('attn_nope',)}
# the Mamba-2 projections' weight stacks: plain products no entry takes
PROJECTION_STACKS = ('bf16[5,4096,18560]', 'bf16[5,8192,4096]')
PROGRAMS = {'step': ('paged_decode_step', 64),
            'chunk_512': ('paged_prefill', 512),
            'chunk_128': ('paged_prefill', 128)}
_INSTRUCTION = re.compile(
    r'^\s+(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$')
_NOT_TRACED = ('parameter', 'get-tuple-element', 'bitcast', 'tuple',
               'constant', 'while', 'conditional', 'call')


def _as_a_trace_names_them(hlo):
    """[(line, op_name)] of the instructions outside fused computations
    that the device runs as ops of their own: ``%name = type opcode(the
    operands, each with its type)``, which is how the profiler's trace
    names an op, and the named scopes its metadata carries."""
    rows, types, fused, comp = [], {}, False, None
    for text in hlo.split('\n'):
        head = re.match(r'^(?:ENTRY )?(%[\w.\-]+) \(', text)
        if head:
            comp, fused = head.group(1), 'fused_computation' in head.group(1)
        found = None if fused else _INSTRUCTION.match(text)
        if found:
            types[comp, found.group(1)] = found.group(2)
            rows.append((comp,) + found.groups())
    out = []
    for comp, name, kind, opcode, rest in rows:
        if opcode in _NOT_TRACED:
            continue
        depth, end = 1, 0
        while end < len(rest) and depth:
            depth += (rest[end] == '(') - (rest[end] == ')')
            end += 1
        operands = re.sub(
            r'%[\w.\-]+',
            lambda m: '%s %s' % (types.get((comp, m.group(0)), ''),
                                 m.group(0)),
            re.sub(r'/\*index=\d+\*/', '', rest[:end - 1]))
        scope = re.search(r'op_name="([^"]*)"', rest[end:])
        out.append(('%s = %s %s(%s)' % (name, kind, opcode, operands),
                    scope.group(1) if scope else ''))
    return out


@pytest.fixture(scope='module')
def compiled_ops():
    """{program: [(line, op_name)]} of the cell's decode step over its 64
    slots, its 512 chunk and its 128 chunk, compiled here and now for a
    described v5e by the builder tests/test_v5e_compile.py has (its
    ``_compiled_at_published_size``: every published width, nothing
    allocated), so that a lowering that changes a shape or an op's
    operands shows in the patterns' test and not first as a silent entry
    on the chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:          # no TPU compiler in this install
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    tests = os.path.join(REPO, 'tests')
    if tests not in sys.path:
        sys.path.insert(0, tests)
    import test_v5e_compile as builder
    from util import cell_spec
    spec, geometry = cell_spec(CELL)
    chip = SingleDeviceSharding(topo.devices[0])
    return {program: _as_a_trace_names_them(
        builder._compiled_at_published_size(
            chip, spec, geometry, op, rows, slack=4 << 20)[0])
        for program, (op, rows) in PROGRAMS.items()}


@pytest.mark.parametrize('program', sorted(PROGRAMS))
@pytest.mark.parametrize('family', sorted(FAMILIES))
def test_a_family_s_patterns_hold_on_the_text_compiled_now(
        resolved, compiled_ops, family, program):
    """On the programs as the compiler writes them today: a family's
    patterns find ops in every program its layer runs in (the scan in no
    decode step); where the program has ops under the layer's named scope
    some of them are found; no op found carries another family's scope,
    is found by another family's patterns, or reads a Mamba-2
    projection's weights."""
    patterns = {metric: [re.compile(p) for p in _metric(
        resolved, metric)['args']['match']] for metric in FAMILIES}
    ops = compiled_ops[program]
    found = [(line, scope) for line, scope in ops
             if any(p.search(line) for p in patterns[family])]
    scan = 'serve.ssmoe_scan_busy_share'
    assert bool(found) == ((family, program) != (scan, 'step'))
    own = LAYER_SCOPES[family]
    if any(word in scope for _, scope in ops for word in own):
        assert any(word in scope for _, scope in found for word in own)
    foreign = [word for metric, words in LAYER_SCOPES.items()
               if metric != family for word in words]
    for line, scope in found:
        assert not any(word in scope for word in foreign), line[:200]
        assert not any(p.search(line) for metric in FAMILIES
                       if metric != family for p in patterns[metric]), \
            line[:200]
        assert not any(stack in line for stack in PROJECTION_STACKS), \
            line[:200]
    # the programs do hold the projections (else the last line is idle)
    for stack in PROJECTION_STACKS:
        assert any(stack in line for line, _ in ops)


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
        capsys, own_environment, resolved, monkeypatch):
    """The cell end to end on the CPU, traced (one run: an untraced one
    takes the same path without the readers): correct against the
    reference, the one-at-a-time check, no compile in the window; and
    under --trace 1 the program's counters reach the line: every shared
    program_counter entry ISSUE 58 names reads a number, the state
    pool's used share with them; a share of a peak has no device to be
    of on the CPU and is left out. The counters the seven entries of PR
    60 count by are fed all the same: the step's bytes come out of the
    run's own registry, and the chunks carry their ``scan_rows``."""
    from paddle_tpu import observe
    slots, set_gauge = {'peak': 0, 'total': 0}, observe.set_gauge

    def watched(name, value, **labels):
        if name == 'decode.state_slots_used':
            slots['peak'] = max(slots['peak'], value)
        elif name == 'decode.state_slots_total':
            slots['total'] = value
        set_gauge(name, value, **labels)
    monkeypatch.setattr(observe, 'set_gauge', watched)
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
    # the pool of state slots was fed and held rows: the peak of its
    # gauge over the run, which no host's load can move. The entry is the
    # mean of 30 samples of that pool, of which one catches the toy
    # engine holding a row (0.8333 on a quiet host): it lies between
    # nought and the peak (how _Watched feeds the samples,
    # tests/benchmark/test_granite_4_0_h_micro.py holds on a stub), and
    # the entry's reader over one sample of a held pool is above nought
    assert slots['total'] == 4 and 0 < slots['peak'] <= slots['total']
    assert 0 <= got['serve.ssm_state_slots_used_pct'] \
        <= 100.0 * slots['peak'] / slots['total']
    pool = _metric(resolved, 'serve.ssm_state_slots_used_pct')
    assert _module('readers', pool['reader']).read(
        pool['args'], {'samples': {'state_slots_used_pct': [0.0, 25.0]}}
    ) == 12.5
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
    # what serve.ssmoe_step_hbm_share divides: the program's counters of
    # this run, at the rehearsal's sizes (toy widths, 3 experts a token)
    after = observe.snapshot()
    for counter in ('decode.moe_latent_rows_total',
                    'decode.step_state_rows_total',
                    'decode.moe_experts_touched', 'decode.moe_layer_steps'):
        assert after['counters'][counter] > 0, counter
    config = dict(resolved['config'], **resolved['config']['rehearsal'])
    shapes = _module('shape_fns', 'ssmoe_decode_live_bytes')
    per_second = shapes.compute({
        'registry_before': {'counters': {}, 'histograms': {}},
        'registry_after': after, 'config': config})
    touched = after['counters']['decode.moe_experts_touched'] \
        / after['counters']['decode.moe_layer_steps']
    assert 0 < touched <= config['n_routed_experts']
    assert per_second > shapes.weight_bytes(config, 0) \
        / (after['histograms']['decode.step_seconds']['sum']
           / after['histograms']['decode.step_seconds']['count'])
    # what serve.ssmoe_scan_mxu_share counts by: every chunk the worker
    # dispatched hands its (row, layer) steps over
    chunks = _module('runners', 'serve_ssm_moe').chunks_dispatched(0.0)
    assert chunks and all(c['pairs'] > 0 and c['bucket'] in (8, 16)
                          for c in chunks)
    assert _module('shape_fns', 'ssmoe_scan_flops').least_flops(
        sum(c['pairs'] for c in chunks), config) > 0
