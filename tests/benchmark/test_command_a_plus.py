"""The ``command_a_plus`` configuration and its cell, off the chip: the
file holds the published config with the cut beside it, the runner
builds the block it describes, the shape function and the two readers
this PR brings do their arithmetic, the benchmark's copy of the plain
reference is the repository's, and the cell rehearses end to end on the
CPU. No test here describes a TPU topology."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest              # noqa: E402
from benchmark import run as bench          # noqa: E402

MANIFEST = manifest.load(REPO)
CELL = 'command_a_plus.mixed_len_steady'
BENCH = os.path.join(REPO, 'benchmark')

# config.json of CohereLabs/command-a-plus-05-2026, the numbers a
# builder sizes by, as published
PUBLISHED = {
    'hidden_size': 4096, 'intermediate_size': 4096, 'head_dim': 128,
    'num_attention_heads': 128, 'num_key_value_heads': 8,
    'num_experts_per_tok': 8, 'num_shared_experts': 4,
    'sliding_window': 4096, 'rope_theta': 50000, 'layer_norm_eps': 1e-05,
    'logit_scale': 1, 'first_k_dense_replace': 0, 'layer_switch': 4,
    'max_position_embeddings': 200000, 'rotary_pct': 1,
    'prefix_dense_intermediate_size': 16384,
    'prefix_dense_sliding_window_pattern': 1}
CUT = {'num_hidden_layers': (4, 32), 'num_experts': (16, 128),
       'vocab_size': (32768, 262144)}
# what ``tbig_lm.chat_steady`` reported when this cell came (PR 28) and
# reports still, and PR 25's serving metrics, which this cell's engine
# feeds too and which list it since PR 42 (less the five it retired)
OLDER_CELL = 'tbig_lm.chat_steady'
SHARED_SINCE_PR28 = {
    'serve.recompiles', 'serve.queue_wait_ms', 'serve.prefill_ms',
    'serve.decode_step_ms', 'serve.batch_occupancy',
    'serve.kv_pool_used_pct', 'serve.copy_busy_share', 'serve.ttft_p90_ms',
    'serve.itl_p95_ms', 'serve.tokens_per_s'}
PR25_SERVING = {
    'serve.worker_prefill_share', 'serve.worker_step_share',
    'serve.worker_idle_share', 'serve.step_build_ms',
    'serve.step_dispatch_ms', 'serve.step_fetch_ms', 'serve.step_emit_ms',
    'serve.live_tokens_per_step'}
MINE = {'serve.moe_step_hbm_share', 'serve.moe_ffn_busy_share',
        'serve.moe_ffn_roofline_share', 'serve.moe_local_assignment_pct',
        'serve.moe_load_max_over_mean', 'serve.window_bound_row_share',
        'serve.prefill_chunks_per_prompt'}


def _module(kind, name):
    return manifest.load_module(os.path.join(BENCH, kind, name + '.py'))


@pytest.fixture(scope='module')
def resolved():
    return manifest.resolve(MANIFEST, CELL)


# ------------------------------------------------------- the files
def shape_the_cell_resolves_to_files_by_name(m):
    """What test_benchmark.py asserts of every cell, for a cell whose
    configuration is a cut with a runner of its own."""
    assert manifest.problems(m) == []
    r = manifest.resolve(m, CELL)
    assert os.path.isfile(r['runner']) and os.path.isfile(r['reference'])
    assert r['config']['runner'] == 'serve_block'
    assert r['cell']['chips'] == 1
    assert r['config']['reference'] and r['config']['assumed']
    assert 'rehearsal' in r['config'] and 'rehearsal' in r['traffic']
    assert {e['name'] for e in r['end_to_end']} == {
        'setup_s', 'ttft_mean_ms', 'itl_mean_ms'}
    for metric in r['per_layer']:
        assert os.path.isfile(metric['reader']) and metric['spec']['doc']
    names = {p['entry']['name'] for p in r['per_layer']}
    assert MINE | SHARED_SINCE_PR28 | PR25_SERVING <= names
    # tbig_lm's shape function reads tbig_lm's model keys: an entry whose
    # ``args`` carry one configuration's shapes is that configuration's
    assert 'serve.decode_step_hbm_share' not in names
    (entry,) = [c for c in m['configs'] if c['name'] == 'command_a_plus']
    assert entry['reduced'] == r['config']['reduced'] == sorted(
        CUT, key=list(CUT).index)
    assert len(entry['source']) <= 200


def shape_every_cell_keeps_the_metrics_it_had(m):
    """The new cell joined lists; it took nothing from the old ones. By
    name and by membership: a later cell joins the same lists."""
    mine = {p['entry']['name']: p for p in manifest.resolve(
        m, CELL)['per_layer']}
    theirs = {p['entry']['name']: p for p in manifest.resolve(
        m, OLDER_CELL)['per_layer']}
    kept = SHARED_SINCE_PR28 | PR25_SERVING | {'serve.decode_step_hbm_share'}
    assert kept <= set(theirs)
    assert kept - set(mine) == {'serve.decode_step_hbm_share'}
    for name in kept:
        entry = theirs[name]['entry']
        assert OLDER_CELL in entry['workloads']
        assert (CELL in entry['workloads']) == (name in mine)
        assert 'bound' not in entry and theirs[name]['spec']['doc']


def test_the_cell_resolves_to_files_by_name():
    shape_the_cell_resolves_to_files_by_name(MANIFEST)


def test_every_cell_keeps_the_metrics_it_had():
    shape_every_cell_keeps_the_metrics_it_had(MANIFEST)


@pytest.mark.parametrize('key', sorted(PUBLISHED))
def test_config_holds_the_published_value(resolved, key):
    assert resolved['config'][key] == PUBLISHED[key]


@pytest.mark.parametrize('key', sorted(CUT))
def test_config_states_each_cut_beside_the_published_value(resolved, key):
    config = resolved['config']
    held, published = CUT[key]
    assert config[key] == held and config['published'][key] == published
    assert key in config['reduced']


def test_config_keeps_a_whole_period_of_the_published_layer_list(resolved):
    config = resolved['config']
    assert len(config['layer_types']) == 32
    assert config['layer_types'][:4] == ['sliding_attention'] * 3 + \
        ['full_attention']
    assert config['layer_types'] == config['layer_types'][:4] * 8
    assert '8' in config['deployment'] and config['first_expert'] == 0
    # the guide's floors for a cut: a period, 8 experts, 1/8 vocabulary
    assert config['num_experts'] >= 8
    assert config['vocab_size'] * 8 >= config['published']['vocab_size']


# ------------------------------------------------------ the runner
def test_runner_builds_the_block_the_config_describes(resolved):
    runner = _module('runners', 'serve_block')
    spec = runner.spec_of(resolved['config'])
    assert (spec.block, spec.n_layer, spec.n_head, spec.n_kv_head,
            spec.d_key, spec.d_model, spec.d_inner) == \
        ('parallel_moe', 4, 128, 8, 128, 4096, 4096)
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.experts_per_token, spec.n_shared_experts) == \
        (128, 16, 0, 8, 4)
    assert spec.windows() == [4096, 4096, 4096, 0]
    assert spec.rotary() == [True, True, True, False]
    assert spec.vocab_size == 32768 and spec.dtype == 'bfloat16'
    reference = _module('references', 'command_a_plus')
    arch = reference.arch_of(spec)
    assert arch['top_k'] == 8 and arch['n_kv_head'] == 8
    assert reference.held_of(spec) == (0, 16)
    with pytest.raises(ValueError, match='not the block'):
        runner.spec_of(dict(resolved['config'], use_qk_norm=True))
    with pytest.raises(ValueError, match='not the block'):
        runner.spec_of(dict(resolved['config'],
                            expert_selection_fn='softmax'))


@pytest.mark.parametrize('gaps, agrees', [
    ([0.0] * 680 + [0.003, 0.077], True),       # the sound runs
    ([0.0] * 681 + [0.335], True),              # one flipped expert
    ([0.0] * 310 + [0.3] * 3, True),            # 3 of 313: under 1%
    ([0.0] * 309 + [0.3] * 4, False),           # over 1% of the tokens
    ([0.0] * 195 + [0.21] * 61, False),         # float8 weights: 24% off
    ([0.0] * 5000 + [1.2], False),              # one token past the cap
    ([], False),                                # nothing was compared
], ids=['sound', 'one_flip', 'three_of_313', 'four_of_313', 'float8',
        'cap', 'empty'])
def test_the_limits_on_the_served_tokens_gaps(resolved, gaps, agrees):
    """One flipped near-tie passes; a lowered precision, or a token the
    model would not have said, does not."""
    runner = _module('runners', 'serve_block')
    limits = resolved['config']['reference']
    assert (limits['logit_gap_tol'], limits['gap_outlier_share_tol'],
            limits['logit_gap_cap']) == (0.2, 0.01, 1.0)
    assert runner.within_limits(gaps, limits) is agrees


def test_the_sample_held_to_the_reference_reaches_past_the_window():
    import collections
    import numpy as np
    runner = _module('runners', 'serve_block')
    Req = collections.namedtuple('Req', 'index prompt_len answer_len')
    Rec = collections.namedtuple('Rec', 'request')
    good = [Rec(Req(i, 200 + i, 40)) for i in range(30)]
    good[17] = Rec(Req(17, 4000, 200))
    good[23] = Rec(Req(23, 6144, 64))
    reference = {'requests': 5, 'long_requests': 1, 'long_tokens': 4096}
    for seed in range(5):
        held = runner.held_sample(good, reference,
                                  np.random.RandomState(seed))
        assert len(held) == 5 and len(set(held)) == 5
        assert sum(1 for r in held if r.request.prompt_len
                   + r.request.answer_len > 4096) >= 1
    # a window without one gives what it has
    short = [r for r in good if r.request.prompt_len < 4000]
    assert len(runner.held_sample(short, reference,
                                  np.random.RandomState(0))) == 5


# ---------------------------------------- shape function and readers
def _step_snapshot(step_s, live, window, steps, touched=None):
    """A registry in which ``steps`` decode steps of 4 layers ran."""
    snap = {'histograms': {
        'decode.step_seconds': {'sum': step_s * steps, 'count': steps},
        'decode.step_live_tokens': {'sum': live * steps, 'count': steps},
        'decode.step_window_tokens': {'sum': window * steps,
                                      'count': steps}}}
    if touched is not None:
        snap['counters'] = {'decode.moe_layer_steps': 4 * steps,
                            'decode.moe_experts_touched':
                                touched * 4 * steps}
    return snap


def test_shape_function_counts_the_weights_held_and_the_live_kv(resolved):
    fn = _module('shape_fns', 'moe_decode_live_bytes')
    config = resolved['config']
    per_layer = (2 * 4096 * 16384 + 2 * 4096 * 1024 + 4096 * 128
                 + 20 * 3 * 4096 * 4096)
    # every routed expert held chosen: every weight held, once
    assert fn.weight_bytes(config, 16) == \
        2 * (4 * per_layer + 32768 * 4096) + 4 * 4096 * 5
    assert round(fn.weight_bytes(config, 16) / 1e9, 2) == 9.47
    assert fn.kv_bytes_per_token_layer(config) == 2 * 8 * 128 * 2
    # three sliding layers read the capped positions, the full one all
    assert fn.live_kv_bytes(config, 10000, 6000) == \
        4096 * (3 * 6000 + 10000)
    sources = {'config': config,
               'registry_before': _step_snapshot(0.03, 0, 0, 0, 0),
               'registry_after': _step_snapshot(0.03, 10000, 6000, 100, 16)}
    assert fn.compute(sources) == pytest.approx(
        (fn.weight_bytes(config, 16) + 4096 * 28000) / 0.03)
    # a program without the window histogram: nothing to read
    del sources['registry_after']['histograms']['decode.step_window_tokens']
    assert fn.compute(sources) is None


@pytest.mark.parametrize('touched', [0, 6.56, 16])
def test_shape_function_counts_the_routed_experts_some_row_chose(
        resolved, touched):
    """An expert no live row chose need not be read: the routed experts
    count ``touched`` a layer, the window's mean, and nothing else of the
    step's bytes depends on the routing."""
    fn = _module('shape_fns', 'moe_decode_live_bytes')
    config = resolved['config']
    one = 3 * 4096 * 4096 * 2
    assert fn.expert_bytes(config) == one
    rest = 2 * (4 * (2 * 4096 * 16384 + 2 * 4096 * 1024 + 4096 * 128)
                + 32768 * 4096) + 4 * 4096 * 5 + 4 * 4 * one
    assert fn.weight_bytes(config, touched) == \
        pytest.approx(rest + 4 * touched * one, rel=1e-12)
    if touched == 0:
        assert fn.weight_bytes(config, 0) == rest    # the shared stay
    before = _step_snapshot(0.02, 500, 400, 50, 9.0)
    after = _step_snapshot(0.02, 500, 400, 50, 9.0)
    for kind in ('histograms', 'counters'):      # 100 more steps
        for name, grew in _step_snapshot(0.012, 9000, 7000, 100,
                                         touched)[kind].items():
            slot = after[kind][name]
            if kind == 'counters':
                after[kind][name] = slot + grew
            else:
                after[kind][name] = {k: slot[k] + grew[k] for k in slot}
    sources = {'config': config, 'registry_before': before,
               'registry_after': after}
    assert fn.experts_touched(before, after) == pytest.approx(touched)
    assert fn.compute(sources) == pytest.approx(
        (fn.weight_bytes(config, touched) + 4096 * (3 * 7000 + 9000))
        / 0.012)


@pytest.mark.parametrize('after', [
    _step_snapshot(0.03, 10000, 6000, 100),             # no such counter
    dict(_step_snapshot(0.03, 10000, 6000, 100),        # it did not grow
         counters={'decode.moe_layer_steps': 40,
                   'decode.moe_experts_touched': 300}),
], ids=['absent', 'still'])
def test_shape_function_reads_nothing_where_no_layer_step_was_counted(
        resolved, after):
    fn = _module('shape_fns', 'moe_decode_live_bytes')
    before = dict(_step_snapshot(0.03, 0, 0, 0),
                  counters=dict(after.get('counters', {})))
    assert fn.experts_touched(before, after) is None
    assert fn.experts_touched(None, None) is None
    assert fn.compute({'config': resolved['config'],
                       'registry_before': before,
                       'registry_after': after}) is None


@pytest.mark.parametrize('before,after,args,want', [
    ({'counters': {'a': 10, 'b': 100}}, {'counters': {'a': 40, 'b': 300}},
     {'counter': 'a', 'per': 'b', 'scale': 100}, 15.0),
    ({'counters': {}}, {'counters': {'a{x=1}': 3, 'a{x=2}': 5, 'b': 4}},
     {'counter': 'a', 'per': 'b'}, 2.0),
    # the parent's program has no such counter
    ({'counters': {'a': 1}}, {'counters': {'a': 9}},
     {'counter': 'a', 'per': 'b'}, None),
    (None, None, {'counter': 'a', 'per': 'b'}, None),
])
def test_registry_ratio(before, after, args, want):
    got = _module('readers', 'registry_ratio').read(
        args, {'registry_before': before, 'registry_after': after})
    assert got == (None if want is None else pytest.approx(want))


def test_roofline_reader_counts_decode_steps_only(resolved):
    reader = _module('readers', 'moe_ffn_roofline')
    config = resolved['config']
    one = 3 * 4096 * 4096 * 2
    assert reader.least_bytes_per_step(config, 12.0) == 4 * 16 * one
    expert = '%fusion.1 = f32[16,32,4096]{2,1,0} fusion(' \
             'bf16[4,16,4096,4096]{3,2,1,0} %w, s32[] %i)'
    other = '%fusion.2 = f32[32,4096]{1,0} fusion(bf16[4,4096,16384] %q)'
    device = [(expert, 110, 20), (other, 130, 50), (expert, 210, 30),
              (expert, 320, 40),          # under a prefill, not a step
              (expert, 420, 10)]          # a step that ends past the window
    host = [('decode.step', 100, 90), ('decode.step', 200, 90),
            ('decode.prefill', 300, 90), ('decode.step', 400, 200),
            ('bench.window', 0, 500)]
    match = ['bf16\\[4,(16|4),4096,4096\\]']
    assert reader.step_op_ns(device, host, match, 0, 500) == (50, 2)
    assert reader.step_op_ns(device, host, match, 150, 500) == (30, 1)
    assert reader.step_op_ns(device, [], match, 0, 500) == (0, 0)
    sources = {
        'trace': {'first': device, 'host': host, 'window': (0, 500)},
        'peaks': {'hbm_bytes_per_s': 819e9}, 'config': config,
        'registry_before': {'counters': {}},
        'registry_tail': {'counters': {'decode.moe_experts_touched': 900,
                                       'decode.moe_layer_steps': 60}},
        'registry_after': {'counters': {'decode.moe_experts_touched': 996,
                                        'decode.moe_layer_steps': 68}}}
    args = {'match': match, 'peak': 'hbm_bytes_per_s'}
    want = 100.0 * (4 * 16 * one / 819e9) / (25e-9)
    assert reader.read(args, sources) == pytest.approx(want)
    # the parent's program counts no experts; an untraced run has no trace
    sources['registry_after'] = sources['registry_tail'] = {'counters': {}}
    assert reader.read(args, sources) is None
    sources['trace'] = None
    assert reader.read(args, sources) is None


def _planted_run(reader, config, window_touched, tail_touched):
    """A 51 s window of which the last 3 s are traced: 250 decode steps
    of 4 layers in the tail, 4,250 in the window, each tail step's
    expert ops taking exactly what the tail's count needs at the HBM
    peak."""
    peak = 819e9
    step_ns = int(round(1e9 * reader.least_bytes_per_step(
        config, tail_touched) / peak))
    host = [('bench.window', 0, 250 * 2 * step_ns)]
    device = []
    for i in range(250):
        host.append(('decode.step', 2 * i * step_ns, 2 * step_ns - 1))
        device.append(('%fusion.1 = f32[32,4096] fusion(bf16[4,16,4096,'
                       '4096]{3,2,1,0} %w)', 2 * i * step_ns + 5, step_ns))
    in_window, in_tail = 4 * 4250, 4 * 250

    def counters(layer_steps, touched):
        return {'counters': {'decode.moe_layer_steps': layer_steps,
                             'decode.moe_experts_touched': touched}}
    return {
        'trace': {'first': device, 'host': host,
                  'window': (0, 250 * 2 * step_ns)},
        'peaks': {'hbm_bytes_per_s': peak}, 'config': config,
        'registry_before': counters(1000, 7000.0),
        'registry_tail': counters(
            1000 + in_window - in_tail,
            7000.0 + window_touched * in_window - tail_touched * in_tail),
        'registry_after': counters(
            1000 + in_window, 7000.0 + window_touched * in_window)}


def test_roofline_reader_counts_the_experts_of_the_traced_tail(resolved):
    """A busy window with a quiet tail (the replayed trace's last 3 s):
    ops at the HBM peak for what the tail's rows chose read 100, where
    bytes by the window's count over the tail's seconds read 153 (what
    refused PR 31 at 115)."""
    reader = _module('readers', 'moe_ffn_roofline')
    config = resolved['config']
    sources = _planted_run(reader, config, 9.2, 4.6)
    args = {'match': ['bf16\\[4,(16|4),4096,4096\\]'],
            'peak': 'hbm_bytes_per_s'}
    shapes = _module('shape_fns', 'moe_decode_live_bytes')
    assert shapes.experts_touched(
        sources['registry_before'], sources['registry_after']) == \
        pytest.approx(9.2)
    assert shapes.experts_touched(
        sources['registry_tail'], sources['registry_after']) == \
        pytest.approx(4.6)
    assert reader.read(args, sources) == pytest.approx(100.0, rel=1e-6)
    ns, steps = reader.step_op_ns(
        sources['trace']['first'], sources['trace']['host'], args['match'],
        *sources['trace']['window'])
    old_way = 100.0 * (reader.least_bytes_per_step(config, 9.2) / 819e9) \
        / (ns / 1e9 / steps)
    assert old_way == pytest.approx(100.0 * 13.2 / 8.6, rel=1e-6)
    # a busy tail in a quiet window is held to its own count as well
    assert reader.read(args, _planted_run(reader, config, 4.6, 9.2)) == \
        pytest.approx(100.0, rel=1e-6)


@pytest.mark.parametrize('tail', ['absent', None])
def test_roofline_reader_without_a_tail_snapshot_reads_nothing(
        resolved, tail):
    """Never the window's count in its place: that is the fault."""
    reader = _module('readers', 'moe_ffn_roofline')
    sources = _planted_run(reader, resolved['config'], 9.2, 4.6)
    if tail == 'absent':
        del sources['registry_tail']
    else:
        sources['registry_tail'] = None
    assert reader.read({'match': ['bf16\\[4,(16|4),4096,4096\\]'],
                        'peak': 'hbm_bytes_per_s'}, sources) is None


def test_an_experts_bytes_are_written_in_one_place(resolved, monkeypatch):
    """The step share and the roofline share count an expert by the same
    function: change it and both counts follow."""
    reader = _module('readers', 'moe_ffn_roofline')
    config = resolved['config']
    shapes = reader.shapes
    assert shapes.__name__ == 'benchmark.shape_fns.moe_decode_live_bytes'
    assert shapes.__file__ == os.path.join(
        BENCH, 'shape_fns', 'moe_decode_live_bytes.py')
    one = shapes.expert_bytes(config)
    rest = shapes.weight_bytes(config, 0) - 4 * 4 * one    # no expert in it
    monkeypatch.setattr(shapes, 'expert_bytes', lambda config: 7)
    assert reader.least_bytes_per_step(config, 5.5) == 4 * 9.5 * 7
    assert shapes.weight_bytes(config, 5.5) == rest + 4 * 9.5 * 7


def test_the_expert_op_pattern_is_the_metrics_own(resolved):
    import re
    specs = {m['entry']['name']: m['spec'] for m in resolved['per_layer']}
    for name in ('serve.moe_ffn_busy_share', 'serve.moe_ffn_roofline_share'):
        (pattern,) = specs[name]['args']['match']
        assert re.search(pattern, 'fusion(bf16[4,16,4096,4096]{3,2,1,0} %a')
        assert re.search(pattern, 'fusion(bf16[4,4,4096,4096]{3,2,1,0} %a')
        assert not re.search(pattern, 'fusion(bf16[4,4096,16384]{2,1,0} %q')
        assert not re.search(pattern, 'bf16[4,4096,32,1024]{3,2,1,0} %kv')
        # the layer loop carries the weights and lasts the whole program
        assert not re.search(pattern, '%while.38 = (s32[], bf16[4,16,4096,'
                                      '4096]{3,2,1,0}) while(...)')


# ---------------------------------------------------- the reference
def test_the_benchmarks_reference_is_the_repositorys():
    mine = os.path.join(REPO, 'paddle_tpu', 'models', 'reference',
                        'command_a_plus.py')
    with open(mine) as a, open(os.path.join(
            BENCH, 'references', 'command_a_plus.py')) as b:
        assert a.read() == b.read()
    with open(mine) as f:
        assert 'paddle_tpu' not in f.read().split('"""')[2]   # the code


# ---------------------------------------------------- the rehearsal
def test_the_cell_rehearses_in_process(capsys):
    assert bench.main(['--workload', CELL, '--seed', '2750000027',
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
    assert window['reference_longest_tokens'] > 16      # past the window
    assert window['refused'] == 0 and window['compiles_in_window'] == 0
