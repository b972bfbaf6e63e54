"""What the v5e's compiler makes of the serving programs' two costly
parts at the published widths, compiled here for a described chip
(nothing runs, no time is measured): the expert product over
layer-stacked weights once cost a copy of its largest operand and the
attention once gathered every page of every table and re-laid what it
had gathered (PERF.md, PR 26, PR 28, PR 29), and these tests keep that
from coming back. So does the train step's attention, which once re-laid
its queries, keys, values and their cotangents in passes of their own
(PERF.md, PR 44). One file, the topology in a fixture
(on-chip-measurement guide, 2)."""

import math
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from util import cell_spec, heads_of_held

SIZE = {'bf16': 2, 'f32': 4, 's32': 4, 'pred': 1, 'u32': 4}
PASSIVE = ('parameter', 'get-tuple-element', 'bitcast', 'tuple', 'while')


@pytest.fixture(scope='module')
def one_chip():
    import os
    from jax.experimental import topologies
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')   # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:          # no TPU compiler in this install
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


def _outside_fusions(hlo):
    """The lines of ``hlo`` that are not inside a fused computation."""
    fused = False
    for line in hlo.split('\n'):
        head = re.match(r'^(ENTRY )?(%[\w.\-]+) \(', line)
        if head:
            fused = 'fused_computation' in head.group(2)
        if not fused:
            yield line


def _materialized(hlo, at_least):
    """(op, type[dims], bytes, memory space) of every result outside a
    fused computation that is ``at_least`` bytes or more: an
    instruction's one array or each element of its tuple (a fusion with
    several outputs, an asynchronous copy's or slice's destination and
    source), each with the space its own layout names ('S(1)' for the
    chip's fast memory, '' for HBM). A ``ConcatBitcast`` call writes
    nothing: it names two asynchronous slices that lie end to end."""
    out = []
    for line in _outside_fusions(hlo):
        m = re.match(r'^\s+(ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(',
                     line)
        if not m or m.group(3) in PASSIVE or \
                'custom_call_target="ConcatBitcast"' in line:
            continue
        for dtype, dims, layout in re.findall(
                r'(\w+)\[([\d,]+)\](\{[^}]*\})?', m.group(2)):
            if dtype not in SIZE:
                continue
            n = SIZE[dtype]
            for d in dims.split(','):
                n *= int(d)
            if n >= at_least:
                space = re.search(r'S\(\d+\)', layout)
                out.append((m.group(3), '%s[%s]' % (dtype, dims), n,
                            space.group(0) if space else ''))
    return out


def _fusions_reading(hlo, operand_type):
    """The fusion instructions outside fused computations one of whose
    operands has ``operand_type`` (``dtype[dims]``)."""
    kind = dict(re.findall(r'(%[\w.\-]+) = (\w+\[[\d,]*\])', hlo))
    out = []
    for line in _outside_fusions(hlo):
        m = re.match(r'^\s+(ROOT )?(%[\w.\-]+) = \S+ fusion\(([^)]*)\)',
                     line)
        if m and any(kind.get(name) == operand_type
                     for name in re.findall(r'%[\w.\-]+', m.group(3))):
            out.append(m.group(2))
    return out


def _shaped(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (layers, experts held, D, F) of the two expert shapes the kernel meets
# whole and in blocks of F: command_a_plus' and mellum2_12b's
WIDTHS = {'command_a_plus': (2, 16, 4096, 4096),
          'mellum2_12b': (8, 64, 2304, 896)}


@pytest.mark.parametrize('form,rows,widths', [
    ('shared', 32, 'command_a_plus'), ('routed', 32, 'command_a_plus'),
    ('routed', 512, 'command_a_plus'), ('routed', 32, 'mellum2_12b'),
    ('routed', 512, 'mellum2_12b'), ('routed', 2048, 'command_a_plus'),
    ('loop', 4096, 'command_a_plus')])
def test_expert_product_reads_the_stacked_weights_where_they_lie(
        one_chip, form, rows, widths):
    """A decode step's 32 rows and a chunk of 512 in a scan over the
    stacked layers at the published widths: the 4 shared experts
    through the dense gate-masked product on the layer's slice, the
    routed ones through one kernel a layer body
    (ops/pallas/moe_routed_product.py) that takes the three stacks
    whole and addresses (layer, expert, block of F) by its block specs.
    No instruction but those of the rows' own ``[rows, D]`` writes
    anything of one expert matrix's size (32 MB of command_a_plus',
    whose layer's are 0.5 GB; 4 MB of mellum's), so each is read once,
    inside the product; no ``copy`` has a stack's
    shape; the kernel's scratch stays under the limit it states. A
    chunk of 2,048 rows is the longest the kernel takes at
    command_a_plus' widths (``moe_routed_product.serves``: the rows and
    their result stay in VMEM); one of 4,096 keeps the loop over the
    tiles, which slices (layer, expert) out of the stacks in its three
    products and writes the chunk's float32 results, one row an
    assignment it can make."""
    from paddle_tpu.ops import moe_held_ops as moe
    L, E, D, F = WIDTHS[widths]
    K = 8
    if form == 'shared':
        E = 4

    def shared(x, chosen, weight, valid, wg, wu, wd):
        def body(h, w):
            return h + moe.gated_experts(h, weight[:, :E], *w), None
        return jax.lax.scan(body, x, (wg, wu, wd))[0]

    def routed(x, chosen, weight, valid, wg, wu, wd):
        def body(h, layer):
            gate, hit = moe.held_gates(chosen, weight, 0, E)
            return h + moe.routed_experts(h, gate, hit, valid, K, wg, wu,
                                          wd, layer), None
        return jax.lax.scan(body, x, jnp.arange(L, dtype=jnp.int32))[0]

    hlo = jax.jit(shared if form == 'shared' else routed).lower(
        _shaped(one_chip, (rows, D), jnp.float32),
        _shaped(one_chip, (rows, K), jnp.int32),
        _shaped(one_chip, (rows, K), jnp.float32),
        _shaped(one_chip, (rows,), jnp.bool_),
        _shaped(one_chip, (L, E, D, F), jnp.bfloat16),
        _shaped(one_chip, (L, E, D, F), jnp.bfloat16),
        _shaped(one_chip, (L, E, F, D), jnp.bfloat16)).compile().as_text()
    # (a chunk's own [512, 2304] arrays are as large as one of mellum's
    # matrices, 4 MB: what is held to the rule is everything else)
    results = 'f32[%d,%d]' % (rows * K + moe.TILE_ROWS, D)
    assert [m for m in _materialized(hlo, D * F * 2)
            if not m[1].endswith('[%d,%d]' % (rows, D))
            and (form, m[1]) != ('loop', results)] == []
    up, down = ('bf16[%d,%d,%d,%d]' % (L, E, a, b)
                for a, b in ((D, F), (F, D)))
    if form != 'routed':
        # the products take the stacked weights themselves as an
        # operand: a device trace names an op by its line with the
        # operands' types, and the benchmark's readers find the expert
        # products by it
        # (D == F at these widths: the three products' stacks have
        # one type)
        assert up == down and len(_fusions_reading(hlo, up)) >= 3
        assert 'tpu_custom_call' not in hlo
        return
    kernels = [line for line in _outside_fusions(hlo)
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and '%moe_routed_product' in kernels[0]
    # ... custom-call(operands), ..., operand_layout_constraints={types}
    operands = re.findall(r'\w+\[[\d,]*\]', kernels[0].split(
        'operand_layout_constraints={', 1)[1])
    assert sorted(o for o in operands if o in (up, down)) \
        == sorted([up, up, down])
    assert not [line for line in hlo.split('\n')
                if re.search(r' copy(-start)?\(', line)
                and (up in line or down in line)]
    # no loop is left over the tiles: the one loop is the layers'
    loops = [line for line in _outside_fusions(hlo)
             if re.match(r'\s+(ROOT )?%[\w.\-]+ = .* while\(', line)]
    assert len(loops) == 1, [line[:120] for line in loops]
    # what the kernel keeps in VMEM is under the limit it states, and
    # that under the v5e's 128 MiB with room to spare
    stated, used = (int(re.search(
        r'"%s":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"' % key,
        kernels[0]).group(1))
        for key in ('scoped_memory_configs', 'used_scoped_memory_configs'))
    assert used <= stated <= 100 << 20, (used, stated)


# name -> (LMSpec arguments, engine arguments): the serving
# configurations of the benchmark at their own attention geometry
# (heads, head width or latent ranks, arena dtype, slots, pages a table,
# page, top prefill bucket), cut in depth, pool, vocabulary, hidden and
# FFN width, which no attention instruction is sized by. The pool is smaller than the
# tables' extent, so that nothing but a whole-table gather or what
# consumes one reaches max_batch x pages_per_seq pages.
SERVING = {
    'tbig_lm': (
        dict(vocab_size=256, n_layer=2, n_head=16, d_key=64, d_value=64,
             d_model=1024, d_inner=256),
        dict(max_batch=64, block_size=32, pages_per_seq=24, num_blocks=512,
             max_prompt_len=512, kv_dtype='float32')),
    'command_a_plus': (
        dict(vocab_size=256, n_layer=2, n_head=128, n_kv_head=8, d_key=128,
             d_value=128, d_model=256, d_inner=64, block='parallel_moe',
             layer_types=('sliding_attention', 'full_attention'),
             sliding_window=4096, rope_theta=50000.0, n_experts=8,
             experts_held=2, experts_per_token=2, n_shared_experts=1,
             dtype='bfloat16'),
        dict(max_batch=32, block_size=32, pages_per_seq=208,
             num_blocks=1024, max_prompt_len=6144, prefill_chunk=512,
             min_prompt_bucket=512, kv_dtype='bfloat16')),
    # the latent block at the published ranks, heads and head widths of
    # both layer kinds, the indexer's 64 x 128 and its 2,048 positions,
    # the 513 window; one full and one sliding layer, both routed
    'dots3_note': (
        dict(vocab_size=256, n_layer=2, d_model=256, d_inner=64,
             block='latent_moe',
             layer_types=('full_attention', 'sliding_attention'),
             sliding_window=513,
             latent={'full_attention': dict(
                 n_head=128, q_rank=1024, kv_rank=512, d_nope=128,
                 d_rope=64, d_v=128, rope_theta=8e7),
                 'sliding_attention': dict(
                 n_head=64, q_rank=1024, kv_rank=1024, d_nope=192,
                 d_rope=64, d_v=128, rope_theta=5e4)},
             index_n_heads=64, index_head_dim=128, index_topk=2048,
             n_experts=8, experts_held=2, experts_per_token=2,
             n_shared_experts=1, dtype='bfloat16'),
        dict(max_batch=32, block_size=32, pages_per_seq=528,
             num_blocks=2048, max_prompt_len=16384, prefill_chunk=512,
             min_prompt_bucket=512, kv_dtype='bfloat16')),
    # the same block as kimi_k2_6 runs it: dense latent attention (no
    # indexer, one arena) at the published rank, heads and head widths,
    # YaRN's table and m^2, tables of 1,040 pages
    'kimi_k2_6': (
        dict(vocab_size=256, n_layer=2, d_model=256, d_inner=64,
             block='latent_moe', layer_types=('full_attention',) * 2,
             latent={'full_attention': dict(
                 n_head=64, q_rank=1536, kv_rank=512, d_nope=128,
                 d_rope=64, d_v=128, rope_theta=5e4, rope_scaling=dict(
                     type='yarn', factor=64, beta_fast=32, beta_slow=1,
                     mscale=1, mscale_all_dim=1,
                     original_max_position_embeddings=4096))},
             dense_layers=1, d_inner_dense=128, index_topk=0,
             lora_rescale=False, attn_gate=False, routed_scale=2.827,
             n_experts=8, experts_held=2, experts_per_token=2,
             n_shared_experts=1, dtype='bfloat16'),
        dict(max_batch=32, block_size=32, pages_per_seq=1040,
             num_blocks=4096, max_prompt_len=33024, prefill_chunk=512,
             min_prompt_bucket=512, kv_dtype='bfloat16')),
    # the gqa_moe block as mellum2_12b runs it: 32 query heads over 4 KV
    # heads of 128, one period of (sliding, sliding, sliding, full), the
    # 1,024 window, both position tables, each kind's arenas under a
    # pool and a table of its own
    'mellum2_12b': (
        dict(vocab_size=256, n_layer=4, n_head=32, n_kv_head=4, d_key=128,
             d_value=128, d_model=256, d_inner=64, block='gqa_moe',
             layer_types=('sliding_attention',) * 3 + ('full_attention',),
             sliding_window=1024, n_experts=8, experts_per_token=2,
             norm_eps=1e-6, dtype='bfloat16', rope_parameters={
                 'full_attention': dict(
                     rope_type='yarn', rope_theta=500000, factor=16,
                     original_max_position_embeddings=8192, beta_fast=32,
                     beta_slow=1, attention_factor=1.2772588722239782),
                 'sliding_attention': dict(rope_type='default',
                                           rope_theta=500000)}),
        dict(max_batch=32, block_size=32, pages_per_seq=1040,
             num_blocks=4096, pool_blocks={'sliding': 1600},
             max_prompt_len=32768, prefill_chunk=512,
             min_prompt_bucket=512, kv_dtype='bfloat16')),
}


@pytest.fixture(scope='module', params=sorted(SERVING))
def engine(request):
    from paddle_tpu.serving.decode import DecodeEngine, LMSpec
    spec, sizes = SERVING[request.param]
    eng = DecodeEngine(LMSpec(**spec), **sizes)
    yield eng
    eng.shutdown(drain=False)


@pytest.mark.parametrize('which', ['decode', 'prefill'])
def test_no_serving_program_gathers_a_whole_table(one_chip, engine, which):
    """The decode step and the largest prefill bucket of every block,
    as the engine jits them: no instruction, gathers included,
    materialises max_batch x pages_per_seq pages (the extent the parent
    gathered, re-tiled and multiplied a layer whatever the rows held),
    and the gathers that are there are a block's: at most 8 tables x
    one column block of pages (a page: of the widest cache kind, as it
    is stored). Nor is any arena re-laid: the latent rows are stored in
    whole lane tiles (CacheKind.stored), which is what keeps the
    compiler from laying the page axis minor."""
    from jax.extend.core import jaxpr_as_fun
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving.decode.hlo_check import arena_sized_instructions
    closed = engine.trace_program(
        'decode' if which == 'decode' else engine.prompt_buckets[-1]).jaxpr
    hlo = jax.jit(jaxpr_as_fun(closed)).lower(
        *[_shaped(one_chip, a.shape, a.dtype)
          for a in closed.in_avals]).compile().as_text()
    kinds = engine.spec.cache_kinds()
    page = engine.block_size * max(k.stored for k in kinds)
    # every arena stays row-major, pages major and the row minor (this
    # program is compiled without the executor's donation, so copies of
    # an arena are not counted here; tests/test_latent_moe_block.py and
    # chip_smoke.py count them on the program as it is run)
    short = {'float32': 'f32', 'bfloat16': 'bf16'}[engine.kv_dtype]
    pages = {pool.name: engine.pools[i].num_blocks
             for i, pool in enumerate(engine.spec.page_pools())}
    for k in kinds:
        arena = '%s[%d,%d,%d,%d]' % (short, len(k.layers), pages[k.pool],
                                     engine.block_size, k.stored)
        assert set(re.findall(re.escape(arena) + r'\{([\d,]+)', hlo)) \
            == {'3,2,1,0'}, arena
    assert arena_sized_instructions(
        hlo, engine.max_batch * engine.pages_per_seq * page,
        gathers=True) == []
    per = pa.pages_per_block(engine.pages_per_seq, engine.block_size)
    others = arena_sized_instructions(hlo, page)
    gathers = [i for i in arena_sized_instructions(hlo, page, gathers=True)
               if i not in others]
    assert gathers, 'no attention gather found'
    assert max(i.elements for i in gathers) <= pa.BLOCK_ROWS * per * page


def test_a_decode_program_traces_one_kernel_a_head_shape(engine,
                                                         monkeypatch):
    """What a warm start pays for the kernel, held by a count and not by
    a clock: building a decode program, the kernel's body is traced
    once for each latent kind's head shape, not once a call site (the
    lead layers one by one and the scanned periods hand ``layer`` as an
    operand to one jitted wrapper), and not at all where no layer is
    latent."""
    from paddle_tpu.ops.pallas import paged_decode_attention as kernel
    traces = []
    body = kernel._kernel
    monkeypatch.setattr(kernel, '_kernel', lambda *refs, **static: (
        traces.append(static), body(*refs, **static))[1])
    kernel._pair_attention.clear_cache()
    spec = engine.spec
    lead, period, n_periods, tail = spec.layer_plan()
    sites = len(lead) + len(period) * bool(n_periods) + len(tail)
    engine.trace_program('decode')
    kernel._pair_attention.clear_cache()
    assert len(traces) == len(spec.latent)
    if spec.latent and lead and n_periods:
        assert sites * spec.sublayers > len(traces)


def _while_bodies(hlo):
    """{computation name: its lines} of the computations some ``while``
    runs as its body."""
    bodies = set(re.findall(r'body=(%[\w.\-]+)', hlo))
    out, name = {}, None
    for line in hlo.split('\n'):
        head = re.match(r'^(ENTRY )?(%[\w.\-]+) \(', line)
        if head:
            name = head.group(2) if head.group(2) in bodies else None
            if name:
                out[name] = []
        elif name:
            out[name].append(line)
    return out


# name -> (heads, rank, d_nope, d_rope, d_v, stored row, pages a table):
# the latent kinds of the two configurations at their published widths
EXPANDED = {
    'kimi_k2_6': (64, 512, 128, 64, 128, 640, 1040),
    'dots3_note_full': (128, 512, 128, 64, 128, 640, 528),
    'dots3_note_sliding': (64, 1024, 192, 64, 128, 1152, 528),
}


@pytest.mark.parametrize('kind', sorted(EXPANDED))
def test_an_expanded_chunk_makes_keys_and_values_head_major(one_chip, kind):
    """A chunk of 512 rows in the expanded latent form: inside the column
    block loop the gathered rows go into the two up-projections and come
    out of them as the per-head products read them. No ``copy`` or
    ``transpose`` there is as large as a block's values (512 x H x d_v
    bfloat16), which is what a re-lay of the expanded block would be;
    the accumulator is [1, 1, H, 512, d_v]; and a score block keeps the
    axes the benchmark's trace patterns name it by ([1, 1, H, 512, 512]:
    benchmark/layer_metrics/serve.mla_attn_busy_share.json)."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving.decode.model import latent_expands
    h, rank, d_nope, d_rope, d_v, stored, pages = EXPANDED[kind]
    s, bs = 512, 32
    assert latent_expands(rank, d_nope, d_v, s)

    def chunk(q, arena, table, lens, lo, w_uk, w_uv):
        return pa.paged_attention_one_table(
            q, arena, None, table, lens, layer=1, lo=lo, latent=rank,
            expand=(w_uk, w_uv))
    hlo = jax.jit(chunk).lower(
        _shaped(one_chip, (s, h, d_nope + d_rope), jnp.float32),
        _shaped(one_chip, (2, 2048, bs, stored), jnp.bfloat16),
        _shaped(one_chip, (pages,), jnp.int32),
        _shaped(one_chip, (s,), jnp.int32),
        _shaped(one_chip, (s,), jnp.int32),
        _shaped(one_chip, (h, d_nope, rank), jnp.bfloat16),
        _shaped(one_chip, (h, rank, d_v), jnp.bfloat16)).compile().as_text()
    (body,) = [lines for lines in _while_bodies(hlo).values()
               if any(' gather(' in line or 'kind=kCustom' in line
                      for line in lines)]
    relaid = [m for m in _materialized('\n'.join(body), 512 * h * d_v * 2)
              if m[0] in ('copy', 'transpose', 'copy-start')]
    assert relaid == []
    assert 'f32[1,1,%d,%d,512]' % (h, s) in hlo
    assert 'f32[1,1,%d,%d,%d]' % (h, s, d_v) in hlo


@pytest.mark.parametrize('side', ['program', 'reference'])
@pytest.mark.parametrize('rows,heads', [(32, 32), (512, 4)])
def test_the_half_split_rotation_compiles_as_a_program_of_its_own(
        one_chip, side, rows, heads):
    """mellum2_12b turns the two halves of a 128-wide head. Written as a
    concatenate of two halves of 64 columns the rotation aborted the
    v5e's compiler wherever it was a program of its own
    (``fusion_emitter.cc: IsFusibleUnalignedDUS``: the plain reference's
    first sequence on the chip, PR 43; inside the serving programs the
    same lines happened to fuse otherwise). Both sides now swap the
    halves by a roll; this compiles each at the published widths."""
    if side == 'program':
        from paddle_tpu.ops.gqa_moe_ops import rope_half_at as rotate
        extra = ()
    else:
        from paddle_tpu.models.reference.mellum2_12b import \
            rotate_halves as rotate
        extra = (_shaped(one_chip, (), jnp.float32),)
    hlo = jax.jit(rotate).lower(
        _shaped(one_chip, (rows, heads, 128), jnp.float32),
        _shaped(one_chip, (rows,), jnp.int32),
        _shaped(one_chip, (64,), jnp.float32), *extra).compile().as_text()
    assert 'f32[%d,%d,128]' % (rows, heads) in hlo


def _entry_copies(hlo, under):
    """(elements, op_name) of every ``copy`` in the ENTRY computation
    whose ``op_name`` has ``under`` among its scopes."""
    out, inside = [], False
    for line in hlo.split('\n'):
        if line.startswith('ENTRY '):
            inside = True
        elif inside and line.startswith('}'):
            break
        m = inside and re.match(
            r'^\s+(ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* copy\(', line)
        name = m and re.search(r'op_name="([^"]*)"', line)
        if name and under in name.group(1):
            n = 1
            for d in filter(None, m.group(2).split(',')):
                n *= int(d)
            out.append((n, name.group(1)))
    return out


# batch, tokens, heads, head size, d_model, FFN, dropout rate
TRAIN_LAYER = (128, 128, 16, 64, 1024, 4096, 0.3)


@pytest.fixture(scope='module', params=['self', 'causal', 'cross'])
def train_layer_hlo(request, one_chip):
    """One layer of `tbig_nmt.train_seq128`'s step as the executor jits
    it (128 x 128 tokens, 16 heads of 64, d_model 1,024, FFN 4,096,
    dropout 0.3, bf16 matmuls, forward, backward and Adam) with each of
    the three attentions the model has, compiled for the described
    chip: ``self`` is the encoder layer, ``causal`` and ``cross`` the
    decoder layer's two, each with the layer's FFN behind it."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as T
    kind = request.param
    b, t, h, d, m, ffn, rate = TRAIN_LAYER
    with pytest.MonkeyPatch.context() as patch, \
            fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.scope_guard(fluid.Scope()):
        patch.setenv('PADDLE_TPU_PRNG', 'rbg')   # as on the chip
        x = layers.data(name='x', shape=[t, m], dtype='float32')
        mem = layers.data(name='mem', shape=[t, m], dtype='float32')
        length = layers.data(name='length', shape=[], dtype='int64')
        target = layers.data(name='target', shape=[t, m], dtype='float32')
        # the attention reads what a layer before it wrote, as in the
        # model, not a feed whose layout the compiler cannot choose
        x = layers.layer_norm(x, begin_norm_axis=2)
        mem = layers.layer_norm(mem, begin_norm_axis=2)
        attn = T._multi_head_attention(
            x, mem if kind == 'cross' else x, d, d, m, h, rate,
            causal=kind == 'causal',
            key_length=None if kind == 'causal' else length, name='attn')
        x = T._post_process(x, attn, rate, name='pp1')
        out = T._post_process(x, T._ffn(x, ffn, m, rate), rate, name='pp2')
        loss = layers.mean(layers.elementwise_mul(x=out, y=target))
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
        prog = fluid.default_main_program()
        prog.amp = 'bf16'
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        acts = np.zeros((b, t, m), 'float32')
        step, scope_vals, feed_vals = exe.compile_step(
            prog, feed={'x': acts, 'mem': acts, 'target': acts,
                        'length': np.full((b,), t, 'int64')},
            fetch_list=[loss])

        def shaped(tree):
            return jax.tree_util.tree_map(
                lambda a: _shaped(one_chip, np.shape(a), a.dtype), tree)
        return jax.jit(step, donate_argnums=(0,)).lower(
            shaped(scope_vals), shaped(feed_vals),
            _shaped(one_chip, (), jnp.int32)).compile().as_text()


def test_a_train_layer_relays_nothing_around_its_attention(train_layer_hlo):
    """The ENTRY computation holds no ``copy`` of B x T x H x D elements
    under ``fused_attention``: when the model projected q, k and v
    itself and split the heads by a reshape and a transpose, each
    attention cost four such passes (one forward, three backward: 72 a
    step of the cell, 33.5 MB each). Whatever re-tiling is left happens
    where a matmul stores its result."""
    b, t, h, d = TRAIN_LAYER[:4]
    assert 'fused_attention' in train_layer_hlo
    relaid = [c for c in _entry_copies(train_layer_hlo, 'fused_attention')
              if c[0] >= b * t * h * d]
    assert relaid == []


def test_a_train_layer_draws_16_bits_a_dropped_element(train_layer_hlo):
    """The layer's four dropout sites (the attention's output, the two
    post-process sites, the FFN's hidden) each draw their mask from
    ``u32`` words of half the site's elements: no ``rng-bit-generator``
    writes a 32-bit word an element, which was half of what dropout
    cost the step (PERF.md, PR 59)."""
    b, t, h, d, m, ffn, _ = TRAIN_LAYER
    drawn = sorted(
        (dtype, math.prod(int(n) for n in dims.split(',')))
        for dtype, dims in re.findall(
            r' = (?:\(u64\[2\]\S*, )?(\w+)\[([\d,]+)\]\S* '
            r'rng-bit-generator\(', train_layer_hlo))
    assert drawn == sorted(
        ('u32', n // 2)
        for n in (b * h * t * d, b * t * m, b * t * m, b * t * ffn))


def test_the_state_arenas_are_written_where_they_lie(one_chip):
    """The ssm_hybrid block at the published state geometry (64 heads of
    64 over a state of 128, a convolution over 4,352 columns; one period
    of m m m m m a m m m m; hidden, MLP and vocabulary cut, which size
    no instruction of the state's): the decode step and the 512 chunk as
    the engine jits them. The compiler keeps both state arenas row-major
    wherever they appear (the convolution's three rows lie end to end in
    one row a slot: kept ``[3, 4352]`` it laid the slot axis inside the
    rows and re-laid the arena around every program), and outside the
    entry computation, which here copies every undonated argument, no
    instruction materialises a layer of the state arena and no ``copy``
    has an arena's shape. The decode step holds one kernel a Mamba layer
    (ops/pallas/ssm_state_update.py) with both arenas among its operands,
    aliased to its results and kept in HBM, and no loop over the rows of
    a Mamba layer (the two loops left are the attention layer's); the chunk
    slices its one slot, advances it and writes it back where it lies
    (ops/ssm_ops.py)."""
    from jax.extend.core import jaxpr_as_fun
    from paddle_tpu.serving.decode import DecodeEngine, LMSpec
    from paddle_tpu.serving.decode.hlo_check import arena_sized_instructions
    spec = LMSpec(
        vocab_size=256, n_layer=10, n_head=32, n_kv_head=8, d_key=64,
        d_value=64, d_model=256, d_inner=512, block='ssm_hybrid',
        layer_types=['mamba'] * 5 + ['attention'] + ['mamba'] * 4,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_conv=4,
        ssm_chunk=256, embed_scale=12.0, residual_scale=0.22,
        attn_scale=0.015625, logit_scale=0.125, dtype='bfloat16')
    eng = DecodeEngine(spec, max_batch=16, block_size=32, num_blocks=512,
                       pages_per_seq=128, max_prompt_len=2048,
                       prefill_chunk=512, min_prompt_bucket=512,
                       kv_dtype='bfloat16')
    try:
        state, conv = 'f32[9,17,128,4096]', 'bf16[9,17,13056]'
        hlos = {}
        for which in ('decode', 512):
            closed = eng.trace_program(which).jaxpr
            hlo = hlos[which] = jax.jit(jaxpr_as_fun(closed)).lower(
                *[_shaped(one_chip, a.shape, a.dtype)
                  for a in closed.in_avals]).compile().as_text()
            assert set(re.findall(re.escape(state) + r'\{([\d,]+)', hlo)) \
                == {'3,2,1,0'}, which
            assert set(re.findall(re.escape(conv) + r'\{([\d,]+)', hlo)) \
                == {'2,1,0'}, which
            inner = [i for i in arena_sized_instructions(
                hlo, 17 * 128 * 4096) if not i.computation.startswith('main')]
            assert inner == [], which
            copies = [i for i in arena_sized_instructions(hlo, 17 * 13056)
                      if i.opcode.startswith('copy')
                      and not i.computation.startswith('main')
                      and re.match(r'(f32\[\d+,17,128,4096\]|'
                                   r'bf16\[\d+,17,13056\])', i.shape)]
            assert copies == [], which
    finally:
        eng.shutdown(drain=False)
    kernels = [line for line in _outside_fusions(hlos['decode'])
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 9 and 'tpu_custom_call' not in hlos[512]
    typed = re.compile(r'\w+\[[\d,]*\]')
    for line in kernels:
        # %ssm_state_update.N = (results) custom-call(operands), ...,
        # operand_layout_constraints={types}, output_to_operand_aliasing=
        # {{0}: (5, {}), {2}: (10, {})}: each arena its operand's buffer
        head = re.match(r'\s+%ssm_state_update[.\d]* = \((.*?)\) '
                        r'custom-call\(', line)
        assert head, line[:120]
        results = typed.findall(head.group(1))
        operands = typed.findall(line.split(
            'operand_layout_constraints={', 1)[1].split('}, output_to')[0])
        alias = {int(out): int(op) for out, op in re.findall(
            r'\{(\d+)\}: \((\d+), \{\}\)', line)}
        assert sorted(alias) == [0, 2], line[:200]
        assert results[0] == operands[alias[0]] == state
        assert results[2] == operands[alias[2]] == conv
        # and stays in HBM: left to choose, the compiler moved the
        # convolution's arena into VMEM before a period's first kernel
        # and back behind its last, whole and every step (PERF.md, PR 46)
        assert not re.search(r'(%s|%s)\{[^}]*S\(1\)' % (
            re.escape(state), re.escape(conv)), head.group(1)), line[:200]
    # the row loop is gone from the Mamba layers: what loops is the
    # attention layer's pair loop and the loop over its pairs' pages
    loops = [line for line in _outside_fusions(hlos['decode'])
             if re.match(r'\s+(ROOT )?%[\w.\-]+ = .* while\(', line)]
    assert len(loops) == 2, [line[:120] for line in loops]
    assert not any(state in line or conv in line for line in loops)


# ------------------------------------- a whole program at published size
class _OpInputs(object):
    """What a paged op's lowering reads of its context, over abstract
    values: the op at a configuration's full size without an engine (an
    engine draws its weights and zeroes its arenas, 13 GB here)."""

    def __init__(self, attrs, inputs):
        self._attrs, self._inputs, self.outputs = attrs, inputs, {}

    def attr(self, name, default=None):
        return self._attrs.get(name, default)

    def input(self, slot):
        return self._inputs[slot]

    def has_input(self, slot):
        return slot in self._inputs

    def out_dtype(self, slot, default):
        return 'int32'

    def set_output(self, slot, value):
        self.outputs[slot] = value


def _paged_op(spec, geometry, op, rows):
    """(function of (weights, arenas, feeds) -> (tokens, arenas), the
    three arguments' shapes) of ``paged_decode_step`` over ``rows`` slots
    or ``paged_prefill`` of a chunk of ``rows``, for a spec with one page
    pool (and, where it keeps a state, its pool of slots), from the
    program builder's own tables."""
    from paddle_tpu.core.registry import get_lowering
    from paddle_tpu.serving.decode import model as lm
    import paddle_tpu.ops.paged_decode_ops  # noqa: F401  (registers)
    attrs = lm._block_attrs(spec, geometry['block_size'])
    # a weight at the shape the programs hold it in
    weights = {slot: (tuple(getattr(shape, 'held', shape)),
                      spec.dtype if fan_in else 'float32')
               for shape, fan_in, slot in
               lm.block_param_shapes(spec).values()}
    # a kind with a size a sequence: a slot a batch row and the spare,
    # at its own dtype (``model._arenas``)
    arenas = {k.slot: ((len(k.layers), geometry['max_batch'] + 1
                        if k.per_seq else geometry['num_blocks'])
                       + tuple(k.unit_shape(geometry['block_size'])),
                       k.dtype or geometry['kv_dtype'])
              for k in spec.cache_kinds()}
    pages = geometry['pages_per_seq']
    if op == 'paged_decode_step':
        feeds = {'Tokens': ((rows,), 'int32'), 'SeqLens': ((rows,), 'int32'),
                 'BlockTables': ((rows, pages), 'int32'),
                 'Temps': ((rows,), 'float32'), 'Seeds': ((rows,), 'int32')}
        out = 'NextTokens'
    else:
        feeds = {'Ids': ((rows,), 'int32'), 'Len': ((), 'int32'),
                 'Cached': ((), 'int32'), 'BlockTable': ((pages,), 'int32'),
                 'Temp': ((), 'float32'), 'Seed': ((), 'int32')}
        out = 'NextToken'
    if spec.keeps_state():
        # the state pool's table: the one slot index a row
        feeds.update({'BlockTablesState': ((rows, 1), 'int32')}
                     if op == 'paged_decode_step'
                     else {'BlockTableState': ((1,), 'int32')})
    lowering = get_lowering(op)

    def fn(w, a, f):
        ctx = _OpInputs(attrs, dict(w, **dict(a, **f)))
        lowering(ctx)
        return ctx.outputs[out], {slot: ctx.outputs[slot + 'Out']
                                  for slot in a}
    return fn, (weights, arenas, feeds)


def _compiled_at_published_size(one_chip, spec, geometry, op, rows,
                                slack=1 << 20):
    """``op`` over ``rows`` compiled for the v5e, the arenas donated as
    the executor donates them: (its HLO, the weights' bytes, the arenas'
    bytes), the argument bytes held to their sum (to ``slack``: the
    feeds, and what the chip's tiling pads) and the whole program to the
    chip's 15.75 GiB."""
    import math
    fn, shapes = _paged_op(spec, geometry, op, rows)
    args = [{slot: _shaped(one_chip, shape, dtype)
             for slot, (shape, dtype) in group.items()}
            for group in shapes]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    memory = compiled.memory_analysis()
    weights_b, arena_b = (
        sum(jnp.dtype(d).itemsize * math.prod(s)
            for s, d in group.values()) for group in shapes[:2])
    assert abs(memory.argument_size_in_bytes
               - weights_b - arena_b) < slack, op
    # the arenas are aliased to their outputs, and what the program
    # keeps beside its arguments fits the chip with them
    assert memory.alias_size_in_bytes >= arena_b
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes \
        < 15.75 * (1 << 30), op
    return compiled.as_text(), weights_b, arena_b


_PUBLISHED = {}


def _published_program(one_chip, cell, op):
    """(spec, geometry, HLO, weights' bytes, arenas' bytes) of a cell's
    decode step over its slots or prefill of its longest chunk
    (``_compiled_at_published_size``), compiled once for the tests that
    read it."""
    if (cell, op) not in _PUBLISHED:
        spec, geometry = cell_spec(cell)
        rows = geometry['max_batch' if op == 'paged_decode_step'
                        else 'prefill_chunk']
        _PUBLISHED[cell, op] = (spec, geometry) + \
            _compiled_at_published_size(one_chip, spec, geometry, op, rows)
    return _PUBLISHED[cell, op]


def _kernels(hlo, name):
    return [line for line in _outside_fusions(hlo)
            if 'custom_call_target="tpu_custom_call"' in line
            and '%' + name in line]


def test_the_shortcut_block_loads_at_its_published_geometry(one_chip):
    """longcat_flash_chat as the benchmark runs it (every published
    width, layers 0-3, 16 of 512 experts, 1/8 vocabulary; 64 slots,
    8,192 pages of 32, tables of 192 pages): the decode step and the 512
    chunk compile for the v5e, the arena donated as the executor donates
    it, with the argument bytes the configuration states: 10.35 GB of
    weights + 2.68 GB of arena = 13.0 GB, and the whole program under
    the chip's 15.75 GiB. One moe_routed_product kernel a program (in the
    layers' scan) whose operands are the stacked experts; in the decode
    step the layer's two attentions are two paged_decode_attention
    kernels over the arena, which stays row-major with eight cache
    layers, and a chunk's attention stays the one-table loop."""
    from paddle_tpu.serving.decode import LMSpec
    weights_b = arena_b = None
    for op in ('paged_decode_step', 'paged_prefill'):
        spec, _, hlo, weights_b, arena_b = _published_program(
            one_chip, 'longcat_flash_chat.chat_decode_heavy', op)
        assert isinstance(spec, LMSpec) and spec.d_model == 6144
        assert set(re.findall(r'bf16\[8,8192,32,640\]\{([\d,]+)', hlo)) \
            == {'3,2,1,0'}, op
        kernels = _kernels(hlo, 'moe_routed_product')
        assert len(kernels) == 1 and 'bf16[4,16,6144,2048]' in kernels[0], op
        attends = _kernels(hlo, 'paged_decode_attention')
        assert len(attends) == (2 if op == 'paged_decode_step' else 0), op
        assert all('bf16[8,8192,32,640]' in line for line in attends)
    assert round(weights_b / 1e9, 2) == 10.35
    assert round(arena_b / 1e9, 2) == 2.68


def test_the_carried_selection_loads_at_its_published_geometry(one_chip):
    """glm_5_2 as the benchmark runs it (every published width, layers
    2-6 of 78, 16 of 256 experts, 1/8 vocabulary; 16 slots, 17,408 pages
    of 32, tables of 1,088 pages = 34,816 positions): the decode step
    and the 512 chunk compile for the v5e with the argument bytes the
    configuration states: 7.76 GB of weights + 3.85 GB of arenas = 11.6
    GB, and the whole program under the chip's 15.75 GiB. The latent
    arena holds five layers and the index arena two, both row-major; the
    selection is carried on the device: bool [rows, 34,816] in the
    layers' loop and in no output; a decode step attends by the
    paged_decode_attention kernel in the lead layer and in the period's
    layers (the scoring and the carried sites alike), and the
    moe_routed_product kernel serves the routed layers."""
    cell = 'glm_5_2.long_ctx_long_answers'
    weights_b = arena_b = None
    for op in ('paged_decode_step', 'paged_prefill'):
        spec, geometry, hlo, weights_b, arena_b = _published_program(
            one_chip, cell, op)
        assert spec.layer_plan()[1:3] == (
            ('carried_selection',) * 3 + ('full_attention',), 1)
        for arena in ('bf16[5,17408,32,640]', 'bf16[2,17408,32,128]'):
            assert set(re.findall(re.escape(arena) + r'\{([\d,]+)', hlo)) \
                == {'3,2,1,0'}, (op, arena)
        rows = geometry['max_batch' if op == 'paged_decode_step'
                        else 'prefill_chunk']
        assert 'pred[%d,34816]' % rows in hlo, op
        # the cut's scan runs one period, so the compiler inlines it: the
        # period's four routed layers are four sites of the one kernel
        kernels = _kernels(hlo, 'moe_routed_product')
        assert len(kernels) == 4, op
        assert all('bf16[4,16,6144,2048]' in line for line in kernels), op
        attends = _kernels(hlo, 'paged_decode_attention')
        if op == 'paged_decode_step':
            # the lead layer's call and the period's four
            assert len(attends) == 5, len(attends)
            assert all('bf16[5,17408,32,640]' in line for line in attends)
        else:
            assert attends == []
    assert round(weights_b / 1e9, 2) == 7.76
    assert round(arena_b / 1e9, 2) == 3.85


def _whiles_to(hlo, trips):
    """The ``while`` instructions outside fused computations whose
    condition holds its counter to the constant ``trips``."""
    counted = set()
    for head, body in re.findall(r'^(%[\w.\-]+) \([^\n]*\n(.*?)^}', hlo,
                                 re.M | re.S):
        if re.search(r's32\[\]\S* constant\(%d\)' % trips, body) and \
                'direction=LT' in body:
            counted.add(head)
    return [line for line in _outside_fusions(hlo)
            if re.search(r' while\(', line) and
            re.search(r'condition=(%[\w.\-]+)', line).group(1) in counted]


@pytest.mark.parametrize('cell,index_arena', [
    ('glm_5_2.long_ctx_long_answers', 'bf16[2,17408,32,128]'),
    ('dots3_note.long_ctx_steady', 'bf16[2,12288,32,128]')])
def test_the_selection_works_over_what_the_rows_hold(one_chip, cell,
                                                     index_arena):
    """The two cells under a learned selection as the benchmark runs
    them, compiled for the v5e. A decode step gathers index keys for its
    pair list, ``BLOCK_ROWS`` (row, column block) pairs an iteration,
    and nowhere a column block of every slot's table (``bf16[slots, 16,
    32, 128]`` each iteration to the longest row, before PR 57). In both
    programs the k-th key of a row is found by the selection_kth kernel,
    one call a scoring layer, over the score buffer where it lies; no
    loop of 32 trips carries an array of the scores' extent, as the
    counting passes' ``u32[rows, columns]`` keys were carried through
    32 passes, whatever the rows held."""
    from paddle_tpu.ops.pallas.paged_attention import BLOCK_ROWS
    for op in ('paged_decode_step', 'paged_prefill'):
        spec, geometry, hlo, _, _ = _published_program(one_chip, cell, op)
        rows = geometry['max_batch' if op == 'paged_decode_step'
                        else 'prefill_chunk']
        columns = geometry['pages_per_seq'] * geometry['block_size']
        kernels = _kernels(hlo, 'selection_kth')
        assert len(kernels) == len(spec.scoring_layers()), op
        assert all('f32[%d,%d]' % (rows, columns) in line
                   for line in kernels), op
        extent = r'\[%d,(%d|%d,128)\]' % (rows, columns, columns // 128)
        assert [line for line in _whiles_to(hlo, 32)
                if re.search(extent, line)] == [], op
        if op == 'paged_decode_step':
            block = '16,%d,128]' % geometry['block_size']
            assert 'bf16[%d,%s' % (BLOCK_ROWS, block) in hlo
            assert 'bf16[%d,%s' % (rows, block) not in hlo
            # the pairs' gather is a fusion over the index arena
            reads = re.findall(
                r'^%%fused_computation[^\n]*%s[^\n]*\n(.*?)^}'
                % re.escape(index_arena), hlo, re.M | re.S)
            assert any('bf16[%d,%s' % (BLOCK_ROWS, block) in body
                       for body in reads)


@pytest.mark.parametrize('cell,arenas,calls', [
    # the lead layer's call and the scanned layers' are the one kernel
    ('kimi_k2_6.doc_qa_sessions', ['bf16[6,16384,32,640]'], 2),
    # a full layer's (chosen columns) and a sliding layer's (a lower
    # bound, another head shape) in the lead, the period and the tail
    ('dots3_note.long_ctx_steady',
     ['bf16[2,12288,32,640]', 'bf16[3,12288,32,1152]'], None),
])
def test_a_latent_decode_step_attends_by_the_kernel_at_published_size(
        one_chip, cell, arenas, calls):
    """The other two latent cells' decode steps as the benchmark runs
    them compile for the v5e with the argument bytes their
    configurations state, their attention the paged_decode_attention
    kernel over each latent arena where it lies."""
    hlo = _published_program(one_chip, cell, 'paged_decode_step')[2]
    attends = _kernels(hlo, 'paged_decode_attention')
    for arena in arenas:
        assert any(arena in line for line in attends), arena
        assert set(re.findall(re.escape(arena) + r'\{([\d,]+)', hlo)) \
            == {'3,2,1,0'}, arena
    if calls:
        assert len(attends) == calls


# what moves a result without a program's compute waiting on it
ASYNC = ('slice-start', 'slice-done', 'copy-start', 'copy-done')
# what the one form leaves, in both programs of the cell: dots3_note's
# full kind has a layer in the lead and one in the inlined period, the
# compiler brings the whole stack of two into the fast memory and slices
# the period's layer back out of it into HBM ('') (PERF.md section 7,
# after PR 55)
LEFT = {'dots3_note.long_ctx_steady': [
    ('slice', 'bf16[1,128,192,1024]', 128 * 192 * 1024 * 2, '')]}


@pytest.mark.parametrize('op', ['paged_decode_step', 'paged_prefill'])
@pytest.mark.parametrize('cell', [
    'longcat_flash_chat.chat_decode_heavy', 'kimi_k2_6.doc_qa_sessions',
    'dots3_note.long_ctx_steady', 'glm_5_2.long_ctx_long_answers'])
def test_no_latent_program_relays_a_projection_out_of_the_query_rank(
        one_chip, cell, op):
    """The four latent cells' decode step and 512 chunk as the benchmark
    runs them, compiled for the v5e with ``q_b`` of every kind and the
    indexer's ``idx_q`` held ``[n, out, q_rank]``
    (``model.HeldTransposed``) and shaped to their heads before a layer
    is taken (``latent_moe_ops._heads_at``): outside fused computations
    nothing but an asynchronous slice or copy has a result, a tuple's
    elements included, with the extents of one of those stacks or of one
    layer's slice of it, as held or as shaped to heads, in any order of
    its axes: no ``fusion``, ``slice``, ``copy`` or ``transpose`` writes
    one; the products read the parameter where it lies. Held as
    declared, ``[n, q_rank, out]``, the compiler re-laid them before it
    multiplied (PERF.md, PR 52): longcat's whole stack at each program's
    entry (``copy bf16[8,1536,12288]{1,2,0}``, 302 MB), kimi's
    ``bf16[1,1536,12288]`` in the scan and ``[12288,1536]`` in the lead
    layer, dots3's ``[1,1024,24576]``, ``[16384,1024]`` and the
    indexer's ``[8192,1024]``. Sliced before it was shaped, each layer's
    slice was copied out of its stack at the program's entry (PERF.md,
    PR 55): glm_5_2's ``fusion (bf16[1,16384,2048] x 5)``, four of them
    into HBM, 335 MB read and 268 written a program, and the indexer's
    ``(bf16[1,4096,2048] x 2)``; the other three cells' into the fast
    memory. What stays is ``LEFT``."""
    from paddle_tpu.serving.decode import model as lm
    spec, _, hlo, _, _ = _published_program(one_chip, cell, op)
    table = lm.block_param_shapes(spec)
    marked = lm.held_transposed(spec)
    assert marked

    def extents(dims):
        return tuple(sorted(int(d) for d in dims if int(d) != 1))
    # a stack's extents or one layer's, held or shaped, in whatever order
    theirs = set()
    for name in marked:
        n, q_rank, out = table[name][0]
        heads = heads_of_held(spec, name)
        for layer in ((out, q_rank), (heads, out // heads, q_rank)):
            theirs |= {extents(layer), extents((n,) + layer)}
    # an unmarked parameter's layer in its own order of axes is that
    # parameter's: the value up-projection of dots3's sliding kind,
    # [64, 1024, 128] a layer, has the extents of the indexer's queries
    # shaped to heads, [64, 128, 1024]
    unmarked = {tuple(d for d in shape[1:] if d != 1)
                for name, (shape, _, _) in table.items()
                if name not in marked}

    def dims(shape):
        return [int(d) for d in shape.split('[')[1].rstrip(']').split(',')]
    written = [r for r in _materialized(hlo, 1 << 22)
               if r[0] not in ASYNC and extents(dims(r[1])) in theirs
               and tuple(d for d in dims(r[1]) if d != 1) not in unmarked]
    assert written == LEFT.get(cell, []), written


def test_the_one_sublayer_block_loads_at_its_published_geometry(one_chip):
    """nemotron_3_super as the benchmark runs it (every published width,
    the period *EMEMEMEMEM = layers 25-35 of 88, 128 of 512 experts, 1/4
    vocabulary; 64 slots, 36,864 pages of 32, tables of 576 pages): the
    decode step and the 512 chunk compile for the v5e (the 128 and 256
    chunks are the 512's program at another extent: compiled once by
    hand, PR 58, not here), the arenas donated, with the argument bytes the configuration's
    ``geometry`` states (9.30 GB of weights + 2.59 GB of arenas = 11.9
    GB) and the whole program under the chip's 15.75 GiB. By
    ``serving/decode/hlo_check.py`` no instruction outside the entry
    computation materialises a layer of the state arena or the K/V
    arena, and every arena stays row-major. The decode step holds one
    ssm_state_update kernel a Mamba-2 layer (8 state groups: B and C
    ``[64, 8, 128]`` among its operands) with both state arenas aliased
    to its results; every program one moe_routed_product kernel an
    expert layer whose weight operands are the two stacks of the
    two-matrix expert and no third."""
    from paddle_tpu.serving.decode.hlo_check import arena_sized_instructions
    cell = 'nemotron_3_super.agent_ctx_long_answers'
    import json
    import os
    spec, geometry = cell_spec(cell)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'benchmark', 'configs',
            'nemotron_3_super.json')) as f:
        stated = json.load(f)['geometry']
    state, conv = 'f32[5,65,128,8192]', 'bf16[5,65,30720]'
    pages = 'bf16[1,36864,32,256]'
    up, down = 'bf16[5,128,1024,2688]', 'bf16[5,128,2688,1024]'
    programs = [('paged_decode_step', 64), ('paged_prefill', 512)]
    for op, rows in programs:
        # the convolution rows' 65 slots are padded to whole tiles: 2.6 MB
        hlo, weights_b, arena_b = _compiled_at_published_size(
            one_chip, spec, geometry, op, rows, slack=4 << 20)
        assert weights_b == stated['weights_bytes'], op
        assert arena_b == stated['arena_bytes'], op
        for arena, order in ((state, '3,2,1,0'), (conv, '2,1,0'),
                             (pages, '3,2,1,0')):
            assert set(re.findall(re.escape(arena) + r'\{([\d,]+)', hlo)) \
                == {order}, (op, rows, arena)
        for layer in (65 * 128 * 8192, 36864 * 32 * 256):
            inner = [i for i in arena_sized_instructions(hlo, layer)
                     if not i.computation.startswith('main')]
            assert inner == [], (op, rows)
        routed = _kernels(hlo, 'moe_routed_product')
        assert len(routed) == 5, (op, rows)
        for line in routed:
            operands = line.split('operand_layout_constraints={', 1)[1]
            assert up in operands and down in operands
            assert len(re.findall(r'bf16\[5,128,\d+,\d+\]', operands)) == 2
        updates = _kernels(hlo, 'ssm_state_update')
        assert len(updates) == (5 if op == 'paged_decode_step' else 0)
        for line in updates:
            alias = {int(out): int(at) for out, at in re.findall(
                r'\{(\d+)\}: \((\d+), \{\}\)', line)}
            assert sorted(alias) == [0, 2], line[:200]
            assert 'f32[64,8,128]' in line
    assert round(stated['weights_bytes'] / 1e9, 2) == 9.30
    assert round(stated['arena_bytes'] / 1e9, 2) == 2.59


def test_the_delta_hybrid_block_loads_at_its_published_geometry(one_chip):
    """qwen3_next as the benchmark runs it (every published width, layers
    0-7 of 48 = two periods of three Gated-DeltaNet layers to a gated
    attention layer, 128 of 512 experts, 1/4 vocabulary; 32 slots,
    34,816 pages of 32, tables of 1,088 pages): the decode step and the
    512 chunk compile for the v5e, the arenas donated, with the argument
    bytes the configuration's ``geometry`` states (7.33 GB of weights +
    4.99 GB of arenas = 12.3 GB) and the whole program under the chip's
    15.75 GiB. By ``serving/decode/hlo_check.py`` no instruction outside
    the entry computation materialises a layer of the state arena or of
    the K/V arena, and every arena stays row-major. The decode step
    holds one gdn_state_update kernel a linear-attention layer of a
    period (the second body of the pipeline over live rows' slots: a row's decay,
    write strength, q, k and v ``[32, 32, 128]`` among its operands) with
    both state arenas aliased to its results, and no ssm_state_update
    kernel; every program one moe_routed_product kernel a layer over the
    three stacks of the gated expert, and heads of 256 go through the
    paged attention every per-head block uses."""
    from paddle_tpu.serving.decode.hlo_check import arena_sized_instructions
    import json
    import os
    cell = 'qwen3_next.long_ctx_chat'
    spec, geometry = cell_spec(cell)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'benchmark', 'configs',
            'qwen3_next.json')) as f:
        stated = json.load(f)['geometry']
    state, conv = 'f32[6,33,32,128,128]', 'bf16[6,33,24576]'
    pages = 'bf16[2,34816,32,512]'
    stacks = ('bf16[8,128,2048,512]', 'bf16[8,128,512,2048]')
    for op, rows in (('paged_decode_step', 32), ('paged_prefill', 512)):
        # the convolution rows' 33 slots are padded to whole tiles
        hlo, weights_b, arena_b = _compiled_at_published_size(
            one_chip, spec, geometry, op, rows, slack=4 << 20)
        assert weights_b == stated['weights_bytes'], op
        assert arena_b == stated['arena_bytes'], op
        for arena, order in ((state, '4,3,2,1,0'), (conv, '2,1,0'),
                             (pages, '3,2,1,0')):
            assert set(re.findall(re.escape(arena) + r'\{([\d,]+)', hlo)) \
                == {order}, (op, rows, arena)
        for layer in (33 * 32 * 128 * 128, 34816 * 32 * 512):
            # but for the whole stack of output projections (100 MB),
            # which the compiler stages into fast memory in the loop
            inner = [i for i in arena_sized_instructions(hlo, layer)
                     if not i.computation.startswith('main')
                     and not i.shape.startswith('bf16[6,4096,2048]')]
            assert inner == [], (op, rows)
        # a period's four layers are the body of one loop over the two
        routed = _kernels(hlo, 'moe_routed_product')
        assert len(routed) == 4, (op, rows)
        for line in routed:
            operands = line.split('operand_layout_constraints={', 1)[1]
            assert all(stack in operands for stack in stacks)
        assert not _kernels(hlo, 'ssm_state_update')
        updates = _kernels(hlo, 'gdn_state_update')
        assert len(updates) == (3 if op == 'paged_decode_step' else 0)
        for line in updates:
            alias = {int(out): int(at) for out, at in re.findall(
                r'\{(\d+)\}: \((\d+), \{\}\)', line)}
            assert sorted(alias) == [0, 2], line[:200]
            assert line.count('f32[32,32,128]') >= 5
    assert round(stated['weights_bytes'] / 1e9, 2) == 7.33
    assert round(stated['arena_bytes'] / 1e9, 2) == 4.99
