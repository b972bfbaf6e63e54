"""What the v5e's compiler makes of the routed block's two products at
the published widths, compiled here for a described chip (nothing runs,
no time is measured): the expert product over layer-stacked weights and
the grouped-head gather attention each once cost a copy of their largest
operand (PERF.md, PR 28), and these tests keep that from coming back.
One file, the topology in a fixture (on-chip-measurement guide, 2)."""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

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


def _materialized(hlo, at_least):
    """(op, type[dims], bytes) of every instruction outside a fused
    computation whose result is ``at_least`` bytes or more."""
    out, fused = [], False
    for line in hlo.split('\n'):
        head = re.match(r'^(ENTRY )?(%[\w.\-]+) \(', line)
        if head:
            fused = 'fused_computation' in head.group(2)
        m = re.match(r'^\s+(ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* '
                     r'([\w\-]+)\(', line)
        if not m or fused or m.group(2) not in SIZE:
            continue
        n = SIZE[m.group(2)]
        for d in m.group(3).split(','):
            n *= int(d)
        if n >= at_least and m.group(4) not in PASSIVE:
            out.append((m.group(4), '%s[%s]' % (m.group(2), m.group(3)), n))
    return out


def _shaped(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_expert_product_reads_the_stacked_weights_where_they_lie(one_chip):
    """32 decode rows against 16 experts of 3 x 4096 x 4096 in a scan over
    2 stacked layers: no instruction writes anything of an expert
    tensor's size (0.5 GB a layer), so each is read once, inside the
    product."""
    from paddle_tpu.ops.moe_held_ops import gated_experts
    L, E, D, F, N = 2, 16, 4096, 4096, 32

    def step(x, gate, wg, wu, wd):
        def body(h, w):
            return h + gated_experts(h, gate, *w), None
        return jax.lax.scan(body, x, (wg, wu, wd))[0]

    hlo = jax.jit(step).lower(
        _shaped(one_chip, (N, D), jnp.float32),
        _shaped(one_chip, (N, E), jnp.float32),
        _shaped(one_chip, (L, E, D, F), jnp.bfloat16),
        _shaped(one_chip, (L, E, D, F), jnp.bfloat16),
        _shaped(one_chip, (L, E, F, D), jnp.bfloat16)).compile().as_text()
    assert _materialized(hlo, E * D * F * 2 // 4) == []


def test_grouped_gather_attention_does_not_relay_the_pages(one_chip):
    """32 rows x 208 pages x 32 slots of 8 KV heads x 128 under 128 query
    heads: the two gathers (0.44 GB each) are the only instructions of
    that size; nothing re-lays what they gathered."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    B, P, BS, NB, H, HKV, D = 32, 208, 32, 4096, 128, 8, 128

    def attend(q, k, v, tables, lens, lo):
        return paged_attention(q, k, v, tables, lens, layer=1, lo=lo)

    arena = _shaped(one_chip, (4, NB, BS, HKV * D), jnp.bfloat16)
    ints = _shaped(one_chip, (B,), jnp.int32)
    hlo = jax.jit(attend).lower(
        _shaped(one_chip, (B, H, D), jnp.float32), arena, arena,
        _shaped(one_chip, (B, P), jnp.int32), ints, ints).compile().as_text()
    gathered = B * P * BS * HKV * D * 2
    big = _materialized(hlo, gathered // 2)
    assert len(big) == 2 and all(n == gathered for _, _, n in big), big
