"""The ``qwen3_next`` configuration and its cell, off the chip: the file is
the catalog row but for the three cut keys, its parameters and arenas
add up to what ISSUE 61 counts (for the cut and, from the same table,
for the published model), the runner builds the block the file
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
CONFIG = 'qwen3_next'
CELL = CONFIG + '.long_ctx_chat'
BENCH = os.path.join(REPO, 'benchmark')
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
CUT = {'num_hidden_layers': 8, 'num_experts': 128, 'vocab_size': 37984}
# what ISSUE 61 pins of the published config.json; the whole row is held
# to the catalog where the catalog is there
PUBLISHED = {
    'model_type': 'qwen3_next', 'hidden_size': 2048,
    'num_hidden_layers': 48, 'full_attention_interval': 4,
    'linear_num_key_heads': 16, 'linear_num_value_heads': 32,
    'linear_key_head_dim': 128, 'linear_value_head_dim': 128,
    'linear_conv_kernel_dim': 4, 'num_attention_heads': 16,
    'num_key_value_heads': 2, 'head_dim': 256,
    'partial_rotary_factor': 0.25, 'rope_theta': 10000000,
    'num_experts': 512, 'num_experts_per_tok': 10,
    'moe_intermediate_size': 512, 'shared_expert_intermediate_size': 512,
    'norm_topk_prob': True, 'decoder_sparse_step': 1,
    'mlp_only_layers': [], 'vocab_size': 151936,
    'tie_word_embeddings': False, 'rms_norm_eps': 1e-06,
    'intermediate_size': 5120, 'rope_scaling': None,
    'use_sliding_window': False, 'hidden_act': 'silu',
    'max_position_embeddings': 262144}
# the entries that carry this configuration's shapes, each with the
# end-to-end metric it moves
OWN_MOVES = {
    'serve.gdn_state_update_roofline_share': 'itl_mean_ms',
    'serve.gdn_state_update_busy_share': 'itl_mean_ms',
    'serve.gdn_scan_busy_share': 'ttft_mean_ms',
    'serve.gdn_scan_mxu_share': 'ttft_mean_ms',
    'serve.gdn_moe_ffn_roofline_share': 'itl_mean_ms',
    'serve.gdn_moe_ffn_busy_share': 'itl_mean_ms',
    'serve.gdn_attn_busy_share': 'itl_mean_ms',
    'serve.gdn_step_hbm_share': 'itl_mean_ms'}
OWN_METRICS = set(OWN_MOVES)
# shared entries whose series its engine feeds, among them the one
# ISSUE 61 names
SHARED_METRICS = {
    'serve.ssm_state_slots_used_pct', 'serve.moe_local_assignment_pct',
    'serve.moe_load_max_over_mean', 'serve.moe_row_tiles_run_share',
    'serve.recompiles', 'serve.queue_wait_ms', 'serve.prefill_ms',
    'serve.decode_step_ms', 'serve.batch_occupancy',
    'serve.kv_pool_used_pct', 'serve.live_tokens_per_step',
    'serve.prefill_chunks_per_prompt', 'serve.attn_pages_read_share',
    'serve.prefill_chunk_ms', 'serve.steps_ahead_share'}
STATE, CONV = 'f32[6,33,32,128,128]', 'bf16[6,33,24576]'
PAGES = 'bf16[2,34816,32,512]'


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
    return _module('runners', 'serve_delta_hybrid').spec_of(
        dict(resolved['config'], **over))


# ------------------------------------------------------- the files
def test_the_cell_resolves_to_files_by_name(resolved):
    assert manifest.problems(MANIFEST) == []
    r = resolved
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_delta_hybrid'
    assert r['cell']['chips'] == 1 and \
        r['cell']['traffic'] == 'long_ctx_chat'
    assert r['config']['reference']['note'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    (entry,) = [c for c in MANIFEST['configs'] if c['name'] == CONFIG]
    assert entry['reduced'] == r['config']['reduced'] == list(CUT)
    assert len(entry['why']) <= 200 and len(r['cell']['why']) <= 200
    for said in ('0.625 rows an expert', '8 rows a chip', '4x', '8 of 48'):
        assert said in r['cell']['why']
    assert entry['source'] == r['config']['source']
    with open(os.path.join(REPO, 'BENCHMARK.json'), 'rb') as f:
        assert len(f.read()) < 65536


def test_the_cell_is_on_its_own_and_the_shared_lists(resolved):
    """Membership only: a later cell may join these lists, and a later
    entry may stand behind these."""
    mine = {p['entry']['name'] for p in resolved['per_layer']}
    assert mine >= OWN_METRICS | SHARED_METRICS
    # what the configuration lacks is left off: no window, no prefix
    # cache, no latent form, no selection, no other block's shapes
    assert not [n for n in mine if n.startswith((
        'serve.prefix_', 'serve.latent_', 'serve.mla_', 'serve.sparse_',
        'serve.indexer_', 'serve.window_', 'serve.gqa_', 'serve.dsa_',
        'serve.scmoe_', 'serve.ssmoe_', 'serve.moe_ffn', 'serve.moe_step',
        'serve.full_kind_', 'train.'))]
    assert not [n for n in mine - SHARED_METRICS
                if n.startswith('serve.ssm_')]
    by_name = {m['name']: m for m in MANIFEST['per_layer']}
    assert len(OWN_METRICS) <= 8
    for name in OWN_METRICS:
        assert by_name[name]['workloads'] == [CELL]
        assert by_name[name]['unit'] == '%'
        assert by_name[name]['layer'] == 'op lowerings'
    for name in ('ttft_mean_ms', 'itl_mean_ms'):
        (e,) = [e for e in MANIFEST['end_to_end'] if e['name'] == name]
        assert CELL in e['workloads'] and e['bound'] == 0.1
    e2e = {e['name'] for e in resolved['end_to_end']}
    for metric in resolved['per_layer']:
        assert metric['entry']['moves'] in e2e
    assert len(MANIFEST['per_layer']) <= 128       # the contract's most
    # each moves what its sibling under serve.ssmoe_* moves (the scan's
    # two the first token, the others the gap between tokens)
    for name, moves in OWN_MOVES.items():
        assert by_name[name]['moves'] == moves
        sibling = by_name[name.replace('serve.gdn_', 'serve.ssmoe_')]
        assert sibling['moves'] == moves
        assert by_name[name]['source'] == sibling['source']
        assert by_name[name]['better'] == sibling['better']


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
                  if r['name'] == 'Qwen3-Next-80B-A3B-Instruct']
    config = resolved['config']
    assert config['source'] == row['source_url']
    differing = [k for k, v in row['config'].items() if config.get(k) != v]
    assert sorted(differing) == sorted(CUT) == sorted(config['reduced'])
    assert config['published'] == {k: row['config'][k] for k in CUT}


def test_config_states_the_deployment_and_what_it_assumes(resolved):
    config = resolved['config']
    assert config['first_layer'] == 0 and config['first_expert'] == 0
    assert config['dtype'] == 'bfloat16'
    for word in ('4 chips', 'expert-parallel', 'replicated', 'pipeline',
                 '6 '):
        assert word in config['deployment']
    assert {'scope', 'block', 'delta_net', 'attention', 'experts',
            'weights', 'precision', 'geometry', 'sampling'} \
        <= set(config['assumed'])
    for said in ('MTP', 'multi-token'):
        assert said in config['assumed']['scope']
    assert 'in_proj_qkvz' in config['assumed']['delta_net']
    assert '(0, 16)' in config['assumed']['weights']
    assert config['engine'] == dict(
        config['engine'], max_batch=32, block_size=32, pages_per_seq=1088,
        num_blocks=34816, max_prompt_len=32768, prefill_chunk=512,
        prefix_cache=False, spec_k=0, kv_dtype='bfloat16')
    assert config['geometry']['v5e_compile']
    assert config['reference']['long_tokens'] == 16384


def _params(spec):
    """{name: elements} of the program's own table."""
    from paddle_tpu.serving.decode.model import block_param_shapes
    return {name: int(np.prod(shape)) for name, (shape, _, _)
            in block_param_shapes(spec).items()}


def test_parameters_and_arenas_add_up_to_what_the_issue_counts(resolved):
    """From the program's table: a Gated-DeltaNet layer 33.72 M, a gated
    attention layer 27.26 M, a layer's router, shared expert and gate
    4.20 M beside experts of 3.146 M each; 3,667 M held (7.33 GB in
    bfloat16); and, the same table at the published counts, 79.7 B in
    all and 3.9 B active a token with the embedding and the head (the
    row's 80B-A3B counts neither)."""
    from paddle_tpu.serving.decode import model as lm
    spec = _spec(resolved)
    table = _params(spec)
    kinds = lm.block_param_shapes(spec)

    def layer(prefix, n, but=()):
        return sum(v for k, v in table.items()
                   if k.startswith(prefix) and k not in but) / n
    expert = 3 * 2048 * 512
    stacks = ('lm_moe_exp_gate.w', 'lm_moe_exp_up.w', 'lm_moe_exp_down.w')
    delta, attn = layer('lm_gdn_', 6), layer('lm_attn_', 2)
    moe = layer('lm_moe_', 8, stacks)
    assert delta == 25165824 + 131072 + 32768 + 8388608 + 192
    assert attn == 16777216 + 2 * 1048576 + 8388608 + 512
    assert moe == 1048576 + 3145728 + 2048
    assert round(delta / 1e6, 2) == 33.72
    assert round(attn / 1e6, 2) == 27.26
    assert round(moe / 1e6, 2) == 4.20
    assert table['lm_moe_exp_up.w'] == 8 * 128 * expert // 3
    assert round(expert / 1e6, 3) == 3.146
    norms = 2 * 8 * 2048 + 2048
    held = sum(table.values())
    assert held == 6 * delta + 2 * attn + 8 * (moe + 128 * expert) \
        + 2 * 37984 * 2048 + norms
    assert round(held / 1e6) == 3667
    whole = 36 * delta + 12 * attn + 48 * (moe + 512 * expert) \
        + 2 * 151936 * 2048 + 2 * 48 * 2048 + 2048
    active = 36 * delta + 12 * attn + 48 * (moe + 10 * expert) \
        + 2 * 151936 * 2048
    assert round(whole / 1e9, 1) == 79.7
    assert round(active / 1e9, 1) == 3.9
    # the arenas: 6 layers x 33 slots of state and convolution rows, and
    # the two full layers' K and V pages
    engine = resolved['config']['engine']
    units = lm.unit_bytes_per_kind(spec, engine['block_size'],
                                   engine['kv_dtype'])
    assert units['lm_ssm_state'] == 6 * 32 * 128 * 128 * 4 == 6 * 2097152
    assert units['lm_ssm_conv'] == 6 * 3 * 8192 * 2
    assert units['lm_kcache'] == units['lm_vcache'] == 2 * 32 * 512 * 2
    # 2,048 B a token a layer, as mellum2_12b's 4 heads of 128
    assert lm.kv_bytes_per_token(spec, 'bfloat16') == 2 * 2048
    arenas = lm.arena_bytes(
        spec, {'': engine['num_blocks'], 'state': engine['max_batch']},
        engine['block_size'], engine['kv_dtype'])
    assert arenas == 33 * (units['lm_ssm_state'] + units['lm_ssm_conv']) \
        + engine['num_blocks'] * 2 * units['lm_kcache']
    # a prompt of 32,768 and an answer of 2,048 in every slot
    assert engine['num_blocks'] == 32 * engine['pages_per_seq'] and \
        engine['pages_per_seq'] * 32 == 32768 + 2048
    geometry = resolved['config']['geometry']
    vectors = sum(v for k, v in table.items() if not kinds[k][1])
    assert geometry['weights_bytes'] == 2 * (held - vectors) + 4 * vectors
    assert geometry['arena_bytes'] == arenas
    peak = max(geometry['peak_bytes'].values())
    assert 0.70 * 16e9 < peak < 15.75 * (1 << 30)


def test_runner_builds_the_block_the_config_describes(resolved):
    from paddle_tpu.serving.decode import model as lm
    spec = _spec(resolved)
    assert spec.block == 'delta_hybrid'
    assert spec.layer_types == (lm.LINEAR,) * 3 + (lm.FULL,) \
        + (lm.LINEAR,) * 3 + (lm.FULL,)
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_groups,
            spec.ssm_state, spec.ssm_conv, spec.ssm_chunk) == (
                32, 128, 16, 128, 4, 64)
    assert (spec.n_head, spec.n_kv_head, spec.d_key, spec.rotary_dim,
            spec.rope_theta) == (16, 2, 256, 64, 1e7)
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.experts_per_token) == (512, 128, 0, 10)
    assert (spec.d_inner, spec.d_inner_shared, spec.norm_eps) == (
        512, 512, 1e-6)
    kinds = {k.name: (k.layers, k.per_seq) for k in spec.cache_kinds()}
    assert kinds == {
        'lm_kcache': ((3, 7), ()), 'lm_vcache': ((3, 7), ()),
        'lm_ssm_state': ((0, 1, 2, 4, 5, 6), (32, 128, 128)),
        'lm_ssm_conv': ((0, 1, 2, 4, 5, 6), (3 * 8192,))}
    runner = _module('runners', 'serve_delta_hybrid')
    # a cut that starts inside a period has its full layer elsewhere
    assert runner.layer_types(dict(resolved['config'], first_layer=2))[:2] \
        == [lm.LINEAR, lm.FULL]
    for over in (dict(model_type='qwen3_moe'), dict(norm_topk_prob=False),
                 dict(tie_word_embeddings=True), dict(mlp_only_layers=[0]),
                 dict(decoder_sparse_step=2),
                 dict(rope_scaling={'type': 'yarn'})):
        with pytest.raises(ValueError, match='serve_delta_hybrid'):
            runner.spec_of(dict(resolved['config'], **over))
    # what serve_gqa_moe.py builds is another block: it refuses this file
    with pytest.raises((ValueError, KeyError)):
        _module('runners', 'serve_gqa_moe').spec_of(resolved['config'])


def test_the_decays_are_drawn_as_the_published_initialiser_draws_them():
    import jax
    runner = _module('runners', 'serve_delta_hybrid')
    dt_bias, a_log = runner._decays(jax.random.PRNGKey(3), (6, 32))
    assert np.asarray(dt_bias).tolist() == [[1.0] * 32] * 6
    a = np.exp(np.asarray(a_log, 'float64'))
    assert 0 < a.min() < 2 and 14 < a.max() <= 16.0


def test_the_traffic_is_the_issues_mix(resolved):
    traffic = resolved['traffic']
    assert traffic['kind'] == 'serve' and traffic['pool_seed'] == 61
    assert traffic['prompt_len'] == [2048, 32768]
    assert traffic['answer_len'] == [128, 2048] and traffic['alpha'] == 1.3
    requests = loadgen.schedule(traffic, 1, 51)
    window = [r for r in requests if r.due >= traffic['preroll_s']]
    assert len(window) >= 40
    assert any(r.prompt_len + r.answer_len
               > resolved['config']['reference']['long_tokens']
               for r in window)
    assert max(r.prompt_len + r.answer_len for r in requests) \
        <= 1088 * 32
    mean = sum(r.prompt_len for r in window) / float(len(window))
    assert 5000 < mean < 9000
    # another seed, the same requests at the same instants
    again = loadgen.schedule(traffic, 2, 51)
    assert [(r.due, r.prompt_len, r.answer_len) for r in requests] == \
        [(r.due, r.prompt_len, r.answer_len) for r in again]


# --------------------------------------------------- the shape functions
def _registry(**counters):
    return {'counters': dict(counters), 'histograms': {}, 'gauges': {}}


def test_the_state_update_moves_a_row_s_state_once_each_way(resolved):
    shapes = _module('shape_fns', 'gdn_decode_live_bytes')
    config = resolved['config']
    assert shapes.row_layer_bytes(config) == 2 * 2097152 + 2 * 49152
    spec = _metric(resolved, 'serve.gdn_state_update_roofline_share')
    assert spec['reader'] == 'step_ops_roofline'
    assert spec['args']['function'] == 'ssm_state_update_bytes'
    assert spec['args']['function_args']['row_layer_bytes'] == \
        shapes.row_layer_bytes(config)
    fn = _module('shape_fns', spec['args']['function'])
    before = _registry(**{'decode.steps_total': 10,
                          'decode.step_state_rows_total': 100})
    after = _registry(**{'decode.steps_total': 20,
                         'decode.step_state_rows_total': 700})
    # 10 live rows x 6 layers a step
    assert fn.per_step(before, after, **spec['args']['function_args']) \
        == 60 * 4292608
    assert fn.per_step(before, before,
                       **spec['args']['function_args']) is None


def test_the_expert_bytes_are_three_matrices_a_touched_expert(resolved):
    """``serve.gdn_moe_ffn_roofline_share`` is read by mellum2_12b's
    reader, whose count takes this configuration's keys as they are:
    every layer, no shared expert in the count."""
    spec = _metric(resolved, 'serve.gdn_moe_ffn_roofline_share')
    assert spec['reader'] == 'gqa_moe_ffn_roofline'
    reader = _module('readers', spec['reader'])
    shapes = _module('shape_fns', 'gdn_decode_live_bytes')
    config = resolved['config']
    assert shapes.expert_bytes(config) == 3 * 2048 * 512 * 2
    assert [shapes.layers_of(config, k) for k in ('linear', 'full')] \
        == [6, 2]
    # ISSUE 61: 23 of 128 touched in each of eight layers, 1.15 GB + a bit
    assert reader.least_bytes_per_step(config, 23) \
        == 8 * 23 * shapes.expert_bytes(config)
    assert round(reader.least_bytes_per_step(config, 23) / 1e9, 2) == 1.16
    assert reader.read(spec['args'], {'trace': None, 'peaks': None}) is None
    assert spec['args']['match'] == _metric(
        resolved, 'serve.gdn_moe_ffn_busy_share')['args']['match']


def test_the_step_bytes_are_weights_state_and_the_attended_rows(resolved):
    """Every weight held once with all 128 experts touched is the
    program's table at its dtypes less the embedding, of which a step
    reads its rows' rows."""
    shapes = _module('shape_fns', 'gdn_decode_live_bytes')
    config = resolved['config']
    emb = 2 * 37984 * 2048
    assert shapes.weight_bytes(config, 128) == \
        config['geometry']['weights_bytes'] - emb
    assert shapes.weight_bytes(config, 128) \
        - shapes.weight_bytes(config, 23) == 8 * 105 * 3 * 2048 * 512 * 2
    # 4,096 B a token: two full layers of 2 KV heads x 256, K and V
    assert shapes.kv_bytes(config, 1000) == 1000 * 4096
    # ISSUE 61's reckoning of a step at 10 live rows: DeltaNet weights
    # 0.40 GB, attention weights 0.11 GB, head 0.16 GB
    assert round(6 * 33718464 * 2 / 1e9, 2) == 0.40
    assert round(2 * 27263488 * 2 / 1e9, 2) == 0.11
    assert round(emb / 1e9, 2) == 0.16


def test_the_step_share_is_the_shape_functions_bytes_over_the_peak(
        resolved):
    """``serve.gdn_step_hbm_share``: ``readers/shape_fn.py`` over
    ``shape_fns/gdn_decode_live_bytes.py::compute``, window before ->
    after: 10 live rows a step, 23 experts touched a layer, 80,000 live
    positions, a step of 4 ms."""
    spec = _metric(resolved, 'serve.gdn_step_hbm_share')
    assert spec['reader'] == 'shape_fn' and spec['args'] == {
        'function': 'gdn_decode_live_bytes', 'peak': 'hbm_bytes_per_s'}
    shapes = _module('shape_fns', 'gdn_decode_live_bytes')
    config = resolved['config']
    before = {'counters': {}, 'histograms': {}}
    after = {'counters': {'decode.steps_total': 100,
                          'decode.step_state_rows_total': 6 * 1000,
                          'decode.moe_experts_touched': 8 * 100 * 23,
                          'decode.moe_layer_steps': 8 * 100},
             'histograms': {
                 'decode.step_seconds': {'sum': 0.4, 'count': 100},
                 'decode.step_live_tokens': {'sum': 8e6, 'count': 100}}}
    sources = {'registry_before': before, 'registry_after': after,
               'config': config, 'bench_dir': BENCH,
               'peaks': {'hbm_bytes_per_s': 819e9}}
    want = (shapes.weight_bytes(config, 23) + 6 * 10 * 4292608
            + 80000 * 4096) / 0.004
    got = _module('readers', 'shape_fn').read(spec['args'], sources)
    assert abs(got - 100 * want / 819e9) < 1e-9 * got
    # 0.74 GB of weights outside the routed experts, 1.16 GB of touched
    # experts, 0.26 GB of state, 0.33 GB of K and V: 2.48 GB a step
    assert round(shapes.weight_bytes(config, 0) / 1e9, 2) == 0.74
    assert round(want * 0.004 / 1e9, 2) == 2.48 and 70 < got < 80
    assert _module('readers', 'shape_fn').read(
        spec['args'], dict(sources, peaks=None)) is None
    assert _module('readers', 'shape_fn').read(
        spec['args'], dict(sources, registry_after=before)) is None


def test_the_scan_s_least_operations_are_the_recurrence_s(resolved):
    """``shape_fns/gdn_scan_flops.py`` by hand at the published widths:
    the decay is 1, the two read-outs 2 each and the rank-one write 2
    operations an element of a head's 128 x 128 state, 32 heads:
    3,670,016 a (row, layer)."""
    shapes = _module('shape_fns', 'gdn_scan_flops')
    config = resolved['config']
    assert shapes.least_flops(1, config) == 7 * 32 * 128 * 128 == 3670016
    # a chunk of 512 live rows through the 6 layers: 11.3 GFLOP, 57 us at
    # the bf16 peak
    assert shapes.least_flops(512 * 6, config) == 11274289152
    spec = _metric(resolved, 'serve.gdn_scan_mxu_share')
    assert spec['reader'] == 'prefill_ops_mxu'
    assert spec['args']['function'] == 'gdn_scan_flops'
    assert spec['args']['peak'] == 'flops_bf16'
    assert re.compile(spec['args']['program']).search(
        'jit_prefill_512').group(1) == '512'
    assert spec['args']['match'] == _metric(
        resolved, 'serve.gdn_scan_busy_share')['args']['match']


# op lines as the v5e's trace names them (my chip run, PR 61, seed
# 6100000001: the whole HLO line, cut here), by the family that has to
# find each, or none
OP_CASES = [
    ('state_update',
     '%gdn_state_update.27 = (f32[6,33,32,128,128]{4,3,2,1,0:T(8,128)}, '
     'f32[32,32,128]{2,1,0:T(8,128)S(1)}, bf16[6,33,24576]{2,1,0:T(8,128)'
     '(2,1)}) custom-call(s32[]{:T(128)S(6)} %max.266, s32[1]{0:T(128)} '
     '%bitcast.1169, s32[32]{0:T(128)S(1)} %get-tuple-element.3451'),
    ('state_update',
     '%dynamic-slice_bitcast_fusion.9 = bf16[33,24576]{1,0:T(8,128)(2,1)'
     'S(1)} fusion(bf16[6,33,24576]{2,1,0:T(8,128)(2,1)} %pallas_call.67, '
     's32[]{:T(128)S(6)} %select_n.1498), kind=kLoop'),
    # a chunk's read of its slot: the arena's, though the result has the
    # scan's shape
    ('state_update',
     '%dynamic-slice_bitcast_fusion.10 = f32[32,128,128]{2,1,0:T(8,128)'
     'S(1)} fusion(f32[6,33,32,128,128]{4,3,2,1,0:T(8,128)} '
     '%bitcast_dynamic-update-slice_fusion.13, s32[]{:T(128)S(6)} '
     '%select_n.1284), kind=kLoop'),
    ('moe_ffn',
     '%moe_routed_product.39 = f32[32,2048]{1,0:T(8,128)S(1)} custom-call('
     's32[]{:T(128)} %bitcast.1222, bf16[32,2048]{1,0:T(8,128)(2,1)S(1)} '
     '%get-tuple-element.3059, bf16[8,128,2048,512]{3,2,1,0:T(8,128)(2,1)} '
     '%p.1, bf16[8,128,2048,512]{3,2,1,0:T(8,128)(2,1)} %p.2, '
     'bf16[8,128,512,2048]{3,2,1,0:T(8,128)(2,1)} %p.3)'),
    ('attn',
     '%fusion.788 = bf16[128,32,512]{2,1,0:T(8,128)(2,1)S(1)} fusion('
     'bf16[2,34816,32,512]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.3277, '
     's32[128]{0:T(128)S(1)} %reshape.1593), kind=kCustom'),
    ('attn',
     '%fusion.1255 = f32[1,2,8,512,256]{4,3,2,1,0:T(8,128)S(1)} fusion('
     'f32[2,8,512,512]{2,3,1,0:T(8,128)S(1)} %get-tuple-element.3664, '
     'f32[2,8,512]{2,1,0:T(8,128)S(1)} %bitcast.1614)'),
    ('scan',
     '%convolution_add_fusion.51 = f32[32,128,128]{2,1,0:T(8,128)S(1)} '
     'fusion(f32[32,64,128]{2,1,0:T(8,128)S(1)} %fusion.1220, '
     'f32[32,128,128]{2,1,0:T(8,128)S(1)} %get-tuple-element.3677, '
     'f32[8,32,64,128]{3,2,1,0:T(8,128)S(1)} %get-tuple-element.3692)'),
    ('scan',
     '%fusion.1108 = f32[8,32,64,128]{3,2,1,0:T(8,128)S(1)} fusion('
     'f32[8,32,64,64]{3,2,1,0:T(8,128)S(1)} %convolution_add_fusion.40, '
     'f32[8,64,32,128]{3,2,1,0:T(8,128)S(1)} %bitcast.1605, '
     'f32[8,32,64]{2,1,0:T(8,128)S(1)} %copy_bitcast_fusion.12)'),
    # a 128 bucket's two scan chunks
    ('scan',
     '%fusion.9 = f32[2,32,64,64]{3,2,1,0:T(8,128)S(1)} fusion('
     'f32[2,16,64,128]{3,2,1,0:T(8,128)S(1)} %copy.1)'),
    (None,      # the input projection of a linear-attention layer
     '%fusion.720 = f32[32,12288]{1,0:T(8,128)S(1)} fusion('
     'bf16[6,2048,12288]{2,1,0:T(8,128)(2,1)} %get-tuple-element.3443, '
     'f32[32,2048]{1,0:T(8,128)S(1)} %get-tuple-element.3358)'),
    (None,      # the head
     '%fusion.478 = f32[32,37984]{1,0:T(8,128)S(1)} fusion('
     'bf16[37984,2048]{1,0:T(8,128)(2,1)} %scope_vals__lm_head_w__.1, '
     'f32[32,2048]{1,0:T(8,128)S(1)} %while.36)'),
    (None,      # the compiler's staging of the output projections
     '%copy-done = bf16[6,4096,2048]{2,1,0:T(8,128)(2,1)S(1)} copy-done(('
     'bf16[6,4096,2048]{2,1,0:T(8,128)(2,1)S(1)}, bf16[6,4096,2048]'
     '{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) %copy-start)'),
    (None,      # the shared expert
     '%fusion.753 = (f32[32]{0:T(128)S(1)}, f32[32,512]{1,0:T(8,128)S(1)}) '
     'fusion(bf16[8,2048,512]{2,1,0:T(8,128)(2,1)} %get-tuple-element.3438)'),
    (None,      # a loop is its body's ops over again
     '%while.36 = (s32[], f32[32,2048], bf16[2,34816,32,512]{3,2,1,0}, '
     'f32[6,33,32,128,128]{4,3,2,1,0}) while(%tuple.1)'),
]
FAMILIES = ('state_update', 'moe_ffn', 'attn', 'scan')


@pytest.mark.parametrize('family, op', OP_CASES)
def test_a_family_s_patterns_find_its_ops_and_no_other_family_s(
        resolved, family, op):
    for name in FAMILIES:
        spec = _metric(resolved, 'serve.gdn_%s_busy_share' % name)
        assert spec['reader'] == 'trace_share'
        hit = any(re.search(p, op) for p in spec['args']['match'])
        assert hit == (name == family), (name, op[:60])


def test_trace_patterns_are_the_configs_numbers(resolved):
    """The patterns name the arenas and the stacks by the shapes the
    program builder gives them: a pattern that drifted from the
    configuration would match nothing and read 0."""
    from paddle_tpu.serving.decode import model as lm
    spec = _spec(resolved)
    engine = resolved['config']['engine']
    table = lm.block_param_shapes(spec)

    def typed(shape, dtype='bf16'):
        return '%s[%s]' % (dtype, ','.join(map(str, shape)))
    arenas = {k.name: typed(
        (len(k.layers), engine['max_batch'] + 1 if k.per_seq
         else engine['num_blocks']) + tuple(k.unit_shape(
             engine['block_size'])), 'f32' if k.dtype == 'float32'
        else 'bf16') for k in spec.cache_kinds()}
    assert arenas['lm_ssm_state'] == STATE
    assert arenas['lm_ssm_conv'] == CONV
    assert arenas['lm_kcache'] == arenas['lm_vcache'] == PAGES
    update = _metric(resolved, 'serve.gdn_state_update_roofline_share')
    assert update['args']['match'] == _metric(
        resolved, 'serve.gdn_state_update_busy_share')['args']['match']
    for arena in (STATE, CONV):
        assert any(re.search(p, '%k = f32[8] custom-call(' + arena + ')')
                   for p in update['args']['match'])
    experts = _metric(resolved, 'serve.gdn_moe_ffn_busy_share')
    for name in ('lm_moe_exp_gate.w', 'lm_moe_exp_up.w',
                 'lm_moe_exp_down.w'):
        assert any(re.search(p, '%k = f32[8] custom-call('
                             + typed(table[name][0]) + ')')
                   for p in experts['args']['match'])
    attn = _metric(resolved, 'serve.gdn_attn_busy_share')
    assert any(re.search(p, '%f = f32[8] fusion(' + PAGES + ')')
               for p in attn['args']['match'])
    # a scan chunk's shapes: chunks x value heads x rows x (rows | width)
    scan = _metric(resolved, 'serve.gdn_scan_busy_share')['args']['match']
    rows, heads, width = spec.ssm_chunk, spec.ssm_heads, spec.ssm_head_dim
    for bucket in (128, 256, 512):
        for last in (rows, width):
            shape = typed((bucket // rows, heads, rows, last), 'f32')
            assert any(re.search(p, '%f = ' + shape + ' fusion()')
                       for p in scan), shape


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
    end with the test (tests/benchmark/test_nemotron_3_super.py: the
    same fixture)."""
    from paddle_tpu import observe
    monkeypatch.setenv('PADDLE_TPU_OBSERVE_COST', '0')
    observe.disable()
    observe.reset()
    yield
    observe.disable()
    observe.reset()


def test_the_cell_rehearses_and_reads_the_series_it_is_listed_on(
        capsys, own_environment, resolved):
    """The cell end to end on the CPU, traced (one run: an untraced one
    takes the same path without the readers): correct against the
    reference, the one-at-a-time check, no compile in the window; and
    under --trace 1 the program's counters reach the line: every shared
    program_counter entry named above reads a number, the state pool's
    used share with them; a share of a peak has no device to be of on
    the CPU and is left out. The counters the eight entries of this PR
    count by are fed all the same: the step's bytes come out of the
    run's own registry, and the chunks carry their ``scan_rows``."""
    from paddle_tpu import observe
    assert bench.main(['--workload', CELL, '--seed', '2147483709',
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
    assert window['reference_gap_max'] <= 1e-3
    assert window['reference_longest_tokens'] > 32
    assert window['refused'] == 0 and window['compiles_in_window'] == 0
    assert window['signatures'] == 3           # chunks of 8 and 16, the step
    assert 0 <= got['serve.ssm_state_slots_used_pct'] <= 100
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
    # what serve.gdn_step_hbm_share divides: the program's counters of
    # this run, at the rehearsal's sizes
    after = observe.snapshot()
    for counter in ('decode.step_state_rows_total',
                    'decode.prefill_scan_rows_total',
                    'decode.moe_experts_touched', 'decode.moe_layer_steps'):
        assert after['counters'][counter] > 0, counter
    config = dict(resolved['config'], **resolved['config']['rehearsal'])
    shapes = _module('shape_fns', 'gdn_decode_live_bytes')
    per_second = shapes.compute({
        'registry_before': {'counters': {}, 'histograms': {}},
        'registry_after': after, 'config': config})
    touched = after['counters']['decode.moe_experts_touched'] \
        / after['counters']['decode.moe_layer_steps']
    assert 0 < touched <= config['num_experts']
    assert per_second > shapes.weight_bytes(config, 0) \
        / (after['histograms']['decode.step_seconds']['sum']
           / after['histograms']['decode.step_seconds']['count'])
    # what serve.gdn_scan_mxu_share counts by: every chunk the worker
    # dispatched hands its (row, layer) steps over
    chunks = _module('runners', 'serve_delta_hybrid').chunks_dispatched(0.0)
    assert chunks and all(c['pairs'] > 0 and c['bucket'] in (8, 16)
                          for c in chunks)
    assert _module('shape_fns', 'gdn_scan_flops').least_flops(
        sum(c['pairs'] for c in chunks), config) > 0
