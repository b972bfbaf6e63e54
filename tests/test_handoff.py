"""Disaggregated prefill/decode: KV handoff packet round-trips at
every arena dtype (bit-identical, scales included), typed dtype/
geometry refusal, host-staging no-allocation-growth, pool
fragmentation + alloc-stall observability, the PhaseRouter pipeline
(prefill replica -> zero-copy handoff -> decode replica) bit-identical
to single-replica decode with zero post-warmup executor cache misses,
preempt-and-resume after a handoff, per-phase autoscaling policies,
and the disagg chaos acceptance (tests/chaos.py)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.serving import (EngineClosedError, HandoffError,
                                KVDtypeMismatchError, KVGeometryError,
                                KVPacket, PhaseRouter, SLOShedError,
                                handoff as handoff_mod,
                                page_pressure, ttft_pressure)
from paddle_tpu.serving.decode import (DecodeEngine, KVPool, LMSpec,
                                       random_weights)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC = LMSpec(vocab_size=60, n_layer=2, n_head=2, d_key=8, d_value=8,
              d_model=16, d_inner=32)
WEIGHTS = random_weights(SPEC, seed=3)


@pytest.fixture(autouse=True)
def _observe_clean():
    from paddle_tpu import observe
    yield
    observe._SINK['path'] = None
    observe._SINK['trace_path'] = None
    observe.disable()
    observe.reset()


def _engine(**kw):
    kw.setdefault('max_batch', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('num_blocks', 64)
    kw.setdefault('pages_per_seq', 8)
    kw.setdefault('weights', WEIGHTS)
    kw.setdefault('place', fluid.CPUPlace())
    kw.setdefault('prefix_cache', True)
    return DecodeEngine(SPEC, **kw)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 60, n)]


def _arena_dtypes():
    from paddle_tpu.quant.core import kv_fp8_supported
    out = ['float32', 'bfloat16', 'int8']
    if kv_fp8_supported():
        out.append('fp8')
    return out


# --------------------------------------------------- pool observability
def test_pool_fragmentation_and_alloc_stall():
    """Satellite: free-count vs largest-contiguous-run gauges and the
    alloc-stall histogram — allocator pressure must be visible."""
    from paddle_tpu import observe
    observe.enable()
    pool = KVPool(num_blocks=8, block_size=4)
    assert pool.largest_free_run() == 8
    assert pool.fragmentation() == 0.0
    # carve holes: claim all, free alternating pages
    ids = pool.alloc(8)
    pool.free([ids[i] for i in range(0, 8, 2)])
    assert pool.free_blocks() == 4
    assert pool.largest_free_run() == 1
    assert pool.fragmentation() == pytest.approx(0.75)
    snap = observe.snapshot()
    assert snap['gauges']['decode.kv_free_pages'] == 4
    assert snap['gauges']['decode.kv_largest_free_run'] == 1
    assert snap['gauges']['decode.kv_fragmentation'] == \
        pytest.approx(0.75)

    # a shortfall that the reclaimer rescues records a stall sample
    def reclaimer(n):
        held = [i for i in range(8) if pool.refcount(i) > 0][:n]
        if held:
            pool.free(held)
        return len(held)

    pool.set_reclaimer(reclaimer)
    got = pool.alloc(6)
    assert got is not None and len(got) == 6
    snap = observe.snapshot()
    stall = snap['histograms'].get('decode.alloc_stall_seconds', {})
    assert stall.get('count', 0) >= 1


def test_alloc_stall_on_exhaustion():
    from paddle_tpu import observe
    observe.enable()
    pool = KVPool(num_blocks=4, block_size=4)
    pool.alloc(4)
    assert pool.alloc(1) is None        # no reclaimer: stall recorded
    snap = observe.snapshot()
    assert snap['histograms'][
        'decode.alloc_stall_seconds']['count'] >= 1


# ------------------------------------------------------ packet wire form
@pytest.mark.parametrize('kv_dtype', _arena_dtypes())
def test_packet_roundtrip_bit_identical(kv_dtype):
    """Satellite: bytes -> restored page bit-identical to the source
    page at every arena dtype, per-row scales included."""
    eng = _engine(kv_dtype=kv_dtype)
    eng.start()
    prompt = _prompt(11, seed=1)
    eng.generate(prompt, max_new_tokens=1, timeout=120)
    pkt = handoff_mod.export_packet(eng, prompt)
    assert pkt is not None and pkt.n_pages == 2   # 11 tokens, bs=4
    assert pkt.kv_dtype == eng.kv_dtype
    assert pkt.tokens == prompt[:8]
    if kv_dtype in ('int8', 'fp8'):
        assert set(pkt.arrays) == {'lm_kcache', 'lm_vcache',
                                   'lm_kscale', 'lm_vscale'}
    else:
        assert set(pkt.arrays) == {'lm_kcache', 'lm_vcache'}

    back = KVPacket.from_bytes(pkt.to_bytes())
    assert back.header['kv_dtype'] == pkt.header['kv_dtype']
    assert back.tokens == pkt.tokens
    for name, arr in pkt.arrays.items():
        got = back.arrays[name]
        assert got.shape == arr.shape
        assert np.asarray(got).tobytes() == np.asarray(arr).tobytes(), \
            'arena %s not bit-identical across the wire' % name

    # install into a fresh engine and read the pages back out: the
    # restored arena content must match the packet bit-for-bit too
    dst = _engine(kv_dtype=kv_dtype)
    covered, installed, dedup = handoff_mod.install_packet(dst, back)
    assert covered == 8 and installed == 2 and dedup == 0
    ids, n = dst.prefix_cache.acquire(prompt)
    assert n == 8
    staged = dst.read_pages(ids)
    for name, arr in back.arrays.items():
        assert np.asarray(staged[name]).tobytes() == \
            np.asarray(arr).tobytes(), \
            'installed arena %s differs from the packet' % name
    dst.pool.free(ids)
    eng.shutdown()
    dst.shutdown(drain=False)


def test_cross_dtype_mismatch_raises_typed():
    """Satellite: an int8 packet must REFUSE an fp32 destination (and
    vice versa) instead of silently dequantizing."""
    a = _engine(kv_dtype='int8')
    a.start()
    prompt = _prompt(9, seed=2)
    a.generate(prompt, max_new_tokens=1, timeout=120)
    pkt = handoff_mod.export_packet(a, prompt)
    b = _engine()                       # fp32 arenas
    with pytest.raises(KVDtypeMismatchError):
        handoff_mod.install_packet(b, pkt)
    # geometry mismatch is its own typed error
    c = _engine(block_size=8, kv_dtype='int8')
    with pytest.raises(KVGeometryError):
        handoff_mod.install_packet(c, pkt)
    a.shutdown()
    b.shutdown(drain=False)
    c.shutdown(drain=False)


def test_packet_verify_knob_catches_corruption(monkeypatch):
    """PADDLE_TPU_HANDOFF_VERIFY (read per call): sha1 over the page
    payload, checked on decode."""
    eng = _engine()
    eng.start()
    prompt = _prompt(9, seed=3)
    eng.generate(prompt, max_new_tokens=1, timeout=120)
    monkeypatch.setenv('PADDLE_TPU_HANDOFF_VERIFY', '1')
    pkt = handoff_mod.export_packet(eng, prompt)
    wire = bytearray(pkt.to_bytes())
    assert KVPacket.from_bytes(bytes(wire)).tokens == prompt[:8]
    wire[-3] ^= 0xFF                    # flip a payload byte
    with pytest.raises(HandoffError):
        KVPacket.from_bytes(bytes(wire))
    monkeypatch.setenv('PADDLE_TPU_HANDOFF_VERIFY', '0')
    with pytest.raises(HandoffError):
        # a STAMPED packet is always verified on receive — the knob
        # gates whether the writer stamps (ISSUE 16: a socket packet
        # that went bad in flight must refuse typed, never install)
        KVPacket.from_bytes(bytes(wire))
    unstamped = bytearray(handoff_mod.export_packet(eng, prompt)
                          .to_bytes())
    assert b'sha1' not in bytes(unstamped)
    unstamped[-3] ^= 0xFF
    KVPacket.from_bytes(bytes(unstamped))   # knob off: never stamped
    eng.shutdown()


def test_staging_no_per_handoff_allocation_growth():
    """Satellite: page export serializes through REUSED host staging
    buffers — one per (arena, dtype), allocated on first use, never
    per handoff."""
    eng = _engine()
    eng.start()
    prompt = _prompt(30, seed=4)        # 7 full pages of 4
    eng.generate(prompt, max_new_tokens=1, timeout=120)
    first = handoff_mod.export_packet(eng, prompt)
    allocs_after_first = eng._staging_allocs
    assert allocs_after_first >= 1
    wires = {first.to_bytes()}
    for _ in range(4):
        pkt = handoff_mod.export_packet(eng, prompt)
        wires.add(pkt.to_bytes())
    assert eng._staging_allocs == allocs_after_first, \
        'staging buffers must be reused across handoffs'
    assert len(wires) == 1, 'repeated exports must be byte-identical'
    eng.shutdown()


def test_export_owns_its_arrays():
    """Regression: read_pages used to return views of the shared
    staging buffers, so a later export (the router runs handoffs on a
    thread pool) silently overwrote an earlier packet's payload.
    Packets must own their arrays."""
    eng = _engine()
    eng.start()
    a, b = _prompt(16, seed=11), _prompt(16, seed=12)
    eng.generate(a, max_new_tokens=1, timeout=120)
    eng.generate(b, max_new_tokens=1, timeout=120)
    pkt_a = handoff_mod.export_packet(eng, a)
    before = {name: np.asarray(arr).tobytes()
              for name, arr in pkt_a.arrays.items()}
    handoff_mod.export_packet(eng, b)
    for name, arr in pkt_a.arrays.items():
        assert np.asarray(arr).tobytes() == before[name], \
            'arena %s of an exported packet was overwritten by a ' \
            'later export' % name
    eng.shutdown()


def test_install_failure_frees_pages(monkeypatch):
    """Regression: a write_pages failure mid-install must release the
    acquired head pins AND the freshly allocated tail pages — repeated
    handoff failures must not drain the decode pool."""
    src = _engine()
    src.start()
    prompt = _prompt(16, seed=13)
    src.generate(prompt, max_new_tokens=1, timeout=120)
    pkt = handoff_mod.export_packet(src, prompt)
    dst = _engine()
    free0 = dst.pool.free_blocks()

    def boom(*a, **kw):
        raise RuntimeError('injected write failure')

    monkeypatch.setattr(dst, 'write_pages', boom)
    with pytest.raises(RuntimeError):
        handoff_mod.install_packet(dst, pkt)
    assert dst.pool.free_blocks() == free0, \
        'failed install leaked KV pool pages'
    src.shutdown()
    dst.shutdown(drain=False)


def test_arena_set_mismatch_raises_before_alloc():
    """A packet whose arena-name set does not match the destination
    (e.g. scales missing) is refused as KVGeometryError before any
    page is allocated."""
    src = _engine()
    src.start()
    prompt = _prompt(9, seed=14)
    src.generate(prompt, max_new_tokens=1, timeout=120)
    pkt = handoff_mod.export_packet(src, prompt)
    pkt.header['arena_names'] = ['lm_kcache']
    dst = _engine()
    free0 = dst.pool.free_blocks()
    with pytest.raises(KVGeometryError):
        handoff_mod.install_packet(dst, pkt)
    assert dst.pool.free_blocks() == free0
    src.shutdown()
    dst.shutdown(drain=False)


def test_oversized_page_group_chunks_through_warmed_rungs():
    """Regression: page groups larger than pages_per_seq (a packet
    from a replica configured with a larger pages_per_seq) used to
    pad the gather/scatter to a shape warmup never traced; they now
    chunk through the warmed rungs. Round-trip stays bit-identical."""
    eng = _engine()
    n = eng.pages_per_seq + 3
    ids = eng.pool.alloc(n)
    assert ids is not None and len(ids) == n
    shapes = {name: np.asarray(arr).shape
              for name, arr in eng.read_pages(ids).items()}
    rng = np.random.RandomState(15)
    payload = {name: rng.uniform(-1, 1, size=shp).astype('float32')
               for name, shp in shapes.items()}
    eng.write_pages(ids, payload)
    back = eng.read_pages(ids)
    for name, want in payload.items():
        assert np.array_equal(np.asarray(back[name]), want), \
            'arena %s lost data across the chunked round-trip' % name
    eng.pool.free(ids)
    eng.shutdown(drain=False)


# ------------------------------------------------------------ e2e hops
@pytest.mark.parametrize('kv_dtype', ['float32', 'int8'])
def test_handoff_e2e_bit_identical(kv_dtype):
    """Acceptance: prefill on replica A, decode on replica B ==
    single-replica decode, bit for bit, at fp32 and int8 KV."""
    prompt = _prompt(13, seed=5)
    base = _engine(kv_dtype=kv_dtype)
    base.start()
    ref = base.generate(prompt, max_new_tokens=10, temperature=0.7,
                        seed=42, timeout=120)
    base.shutdown()

    a = _engine(kv_dtype=kv_dtype)
    b = _engine(kv_dtype=kv_dtype)
    a.start()
    b.start()
    a.generate(prompt, max_new_tokens=1, temperature=0.7, seed=42,
               timeout=120)
    covered = handoff_mod.handoff(a, b, prompt)
    assert covered == (len(prompt) // 4) * 4
    got = b.generate(prompt, max_new_tokens=10, temperature=0.7,
                     seed=42, timeout=120)
    assert got == ref
    a.shutdown()
    b.shutdown()


def test_handoff_then_preempt_and_resume_on_b():
    """Acceptance: after the handoff, replica B preempts the sequence
    under page pressure and the recompute-requeue continuation is
    still bit-exact."""
    from paddle_tpu import observe
    observe.enable()
    long_prompt = _prompt(14, seed=6)
    other_prompt = _prompt(12, seed=7)
    refs = []
    for p, mn in ((long_prompt, 12), (other_prompt, 12)):
        e = _engine()
        e.start()
        refs.append(e.generate(p, max_new_tokens=mn, temperature=0.6,
                               seed=9, timeout=120))
        e.shutdown()

    a = _engine()
    a.start()
    a.generate(long_prompt, max_new_tokens=1, temperature=0.6, seed=9,
               timeout=120)
    # B: 12 pages total; each sequence needs up to 7 — two running
    # sequences exhaust the pool and preempt the youngest
    b = _engine(num_blocks=12)
    b.start()
    handoff_mod.handoff(a, b, long_prompt)
    s1 = b.submit(long_prompt, max_new_tokens=12, temperature=0.6,
                  seed=9)
    s2 = b.submit(other_prompt, max_new_tokens=12, temperature=0.6,
                  seed=9)
    got = [s1.result(120), s2.result(120)]
    snap = observe.snapshot()
    assert snap['counters'].get('decode.preemptions_total', 0) > 0, \
        'test must actually exercise preemption on B'
    assert got == refs
    a.shutdown()
    b.shutdown()
    assert b.pool.free_blocks() == b.pool.num_blocks


def test_phase_router_e2e_zero_misses():
    """The pipeline: mixed requests through 1 prefill + 2 decode
    replicas == sequential single-engine decode, with ZERO post-warmup
    executor cache misses on either fleet and dedup across the
    handoff boundary for the shared system prompt."""
    from paddle_tpu import observe
    observe.enable()
    shared = _prompt(8, seed=8)
    rng = np.random.RandomState(9)
    reqs = []
    for i in range(6):
        tail = [int(t) for t in rng.randint(0, 60, 3 + i)]
        reqs.append(dict(prompt_ids=shared + tail,
                         max_new_tokens=5 + (i % 3),
                         temperature=0.0 if i % 2 else 0.6,
                         seed=100 + i))
    base = _engine()
    base.start()
    refs = [base.generate(timeout=120, **r) for r in reqs]
    base.shutdown()

    pre = [_engine(name='pf0')]
    dec = [_engine(name='dc0'), _engine(name='dc1')]
    for e in pre + dec:
        e.warmup()
        e.start()
    router = PhaseRouter(pre, dec, route='hx')

    def misses(snap):
        return sum(v for k, v in snap['counters'].items()
                   if k.startswith('executor.cache_miss_total'))

    snap0 = observe.snapshot()
    streams = [router.submit(r['prompt_ids'],
                             max_new_tokens=r['max_new_tokens'],
                             temperature=r['temperature'],
                             seed=r['seed'], session='s1')
               for r in reqs]
    got = [s.result(120) for s in streams]
    snap1 = observe.snapshot()
    assert got == refs
    assert misses(snap1) - misses(snap0) == 0, \
        'handoff traffic must not mint executor signatures'
    assert snap1['counters'].get('handoff.count_total', 0) >= 1
    # the shared prefix crossed the wire once per decode replica at
    # most — later handoffs dedup against the destination cache
    assert snap1['counters'].get('handoff.pages_deduped_total', 0) > 0
    gauges = snap1['gauges']
    assert gauges.get('router.phase_replicas{phase=prefill,'
                      'route=hx}') == 1
    assert gauges.get('router.phase_replicas{phase=decode,'
                      'route=hx}') == 2
    router.close(shutdown_replicas=True)


def test_phase_router_colocated_and_sheds():
    dec = [_engine(name='c0')]
    dec[0].warmup()
    dec[0].start()
    router = PhaseRouter([], dec, route='cx', colocated=True)
    prompt = _prompt(9, seed=10)
    base = _engine()
    base.start()
    ref = base.generate(prompt, max_new_tokens=6, timeout=120)
    base.shutdown()
    assert router.generate(prompt, timeout=120,
                           max_new_tokens=6) == ref
    # expired deadline sheds synchronously, before any phase runs
    with pytest.raises(SLOShedError):
        router.submit(prompt, deadline_s=-0.001)
    router.close()
    with pytest.raises(EngineClosedError):
        router.submit(prompt)
    dec[0].shutdown()


def test_phase_pressure_policies():
    """ttft_pressure / page_pressure close the per-phase scaling loop
    over the PhaseRouter's signals."""

    class FakePR(object):
        ttft = None
        frac = None

        def prefill_phase_p95(self):
            return self.ttft

        def decode_free_page_frac(self):
            return self.frac

    pr = FakePR()
    press, calm = ttft_pressure(pr, budget_s=0.5)
    assert press(0.0) == (False, None, {'ttft_p95': None,
                                        'ttft_budget': 0.5,
                                        'mean_queue_depth': 0.0,
                                        'burn_rate': None})
    pr.ttft = 0.6
    hot, reason, signals = press(1.0)
    assert hot and reason == 'ttft_burn'
    assert not calm(signals)
    pr.ttft = 0.2
    _, _, signals = press(2.0)
    assert calm(signals)

    press, calm = page_pressure(pr, free_low=0.2, free_high=0.5)
    assert press(0.0)[0] is False       # no decode replicas yet
    pr.frac = 0.1
    hot, reason, signals = press(1.0)
    assert hot and reason == 'page_pressure'
    assert not calm(signals)
    pr.frac = 0.7
    _, _, signals = press(2.0)
    assert calm(signals)


def test_statusz_panels_show_handoff_and_phases():
    from paddle_tpu import observe
    from paddle_tpu.observe.diagnostics import (_decode_status,
                                                _router_status)
    observe.enable()
    a = _engine()
    b = _engine()
    a.start()
    b.start()
    prompt = _prompt(12, seed=11)
    a.generate(prompt, max_new_tokens=1, timeout=120)
    handoff_mod.handoff(a, b, prompt)
    observe.set_gauge('router.phase_replicas', 1, phase='prefill',
                      route='r')
    observe.set_gauge('router.phase_replicas_ready', 1,
                      phase='prefill', route='r')
    observe.inc('router.phase_dispatch_total', phase='prefill',
                replica='pf0', route='r')
    snap = observe.snapshot()
    doc = _decode_status(snap)
    assert doc['kv_largest_free_run'] is not None
    assert doc['kv_fragmentation'] is not None
    assert doc['handoff_total'] == 1
    assert doc['handoff_pages_installed_total'] == 3
    assert doc['handoff_bytes_total'] > 0
    rdoc = _router_status(snap)
    assert rdoc['phases']['prefill']['total'] == 1
    assert rdoc['phases']['prefill']['dispatched'] == 1
    a.shutdown()
    b.shutdown()


# ------------------------------------------------------------- tooling
def test_metrics_report_fleet_phase_split(tmp_path):
    """Satellite: --fleet renders the phase-split view (census,
    handoff, TTFT attribution) from a snapshot JSONL — schema-stable,
    no jax import."""
    from paddle_tpu import observe
    observe.enable(jsonl=str(tmp_path / 'm.jsonl'))
    observe.set_gauge('router.phase_replicas', 1, phase='prefill',
                      route='dx')
    observe.set_gauge('router.phase_replicas', 2, phase='decode',
                      route='dx')
    observe.set_gauge('router.phase_replicas_ready', 2,
                      phase='decode', route='dx')
    observe.inc('router.phase_dispatch_total', 7, phase='decode',
                replica='dc0', route='dx')
    observe.inc('handoff.count_total', 7)
    observe.inc('handoff.bytes_total', 7168)
    observe.inc('handoff.pages_installed_total', 20)
    observe.inc('handoff.pages_deduped_total', 8)
    for v in (0.01, 0.02, 0.03):
        observe.record('handoff.seconds', v)
        observe.record('handoff.ttft_attributed_seconds', v * 2,
                       route='dx')
        observe.record('decode.inter_token_seconds', v / 2)
    observe.record('decode.ttft_seconds', 0.05, cached='0')
    observe.flush(kind='summary')

    tool = os.path.join(REPO, 'tools', 'metrics_report.py')
    r = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--fleet',
         '--json'],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    ph = doc['phases']
    assert ph['census']['prefill']['replicas'] == 1
    assert ph['census']['decode']['replicas'] == 2
    assert ph['census']['decode']['replicas_ready'] == 2
    assert ph['census']['decode']['dispatched'] == 7
    assert ph['handoff']['count'] == 7
    assert ph['handoff']['bytes'] == 7168
    assert ph['handoff']['pages_deduped'] == 8
    assert ph['handoff']['seconds']['count'] == 3
    assert ph['attribution']['prefill_plus_handoff']['count'] == 3
    assert ph['attribution']['ttft_cold']['count'] == 1
    assert ph['attribution']['inter_token']['count'] == 3
    # human rendering names the sections
    r2 = subprocess.run(
        [sys.executable, tool, str(tmp_path / 'm.jsonl'), '--fleet'],
        capture_output=True, text=True, timeout=60)
    assert r2.returncode == 0, r2.stderr
    assert 'phase split' in r2.stdout
    assert 'TTFT vs inter-token attribution' in r2.stdout
    # no jax import on the --fleet path
    probe = subprocess.run(
        [sys.executable, '-c',
         'import importlib.util, sys\n'
         'spec = importlib.util.spec_from_file_location("mr", %r)\n'
         'm = importlib.util.module_from_spec(spec)\n'
         'spec.loader.exec_module(m)\n'
         'assert m.main([%r, "--fleet"]) == 0\n'
         'assert "jax" not in sys.modules\n'
         % (tool, str(tmp_path / 'm.jsonl'))],
        capture_output=True, text=True, timeout=60)
    assert probe.returncode == 0, probe.stderr


def test_bench_disagg_acceptance():
    """ISSUE 14 headline, in counts: under the mixed
    long-prompt/long-decode chaos schedule (``chaos.disagg_chaos``)
    neither the colocated nor the disaggregated fleet of equal size
    loses a request or compiles anything after warm-up, and the
    disaggregated one hands pages off and deduplicates shared ones.
    Which of the two has the shorter inter-token tail is a speed: not
    measured here."""
    from chaos import disagg_chaos
    from paddle_tpu import observe
    observe.enable()
    out = disagg_chaos()
    assert out['colocated']['accepted'] > 0
    assert out['disaggregated']['accepted'] > 0
    assert out['colocated']['lost'] == 0
    assert out['disaggregated']['lost'] == 0
    assert out['disaggregated']['post_warmup_cache_misses'] == 0
    assert out['colocated']['post_warmup_cache_misses'] == 0
    assert out['disaggregated']['handoffs'] > 0
    assert out['disaggregated']['handoff_pages_deduped'] > 0
    assert out['page_wire_bytes_fp32'] > 0
