"""Zero-copy KV page handoff between prefill and decode replicas.

The disaggregated serving architecture (docs/serving.md) splits the
fleet by phase: prefill replicas are compute-bound and bucket-laddered,
decode replicas are HBM-bound and paged. A request prefills on one
replica and decodes on another — which means the sequence's KV pages
must cross replica boundaries. This module is that wire:

- **export** (:func:`export_packet`) — after prefill, the sequence's
  frozen FULL pages sit in the prefill replica's radix prefix cache
  (publish happens the moment ``cache_len`` crosses each page
  boundary). Export pins the chain (``PrefixCache.acquire`` — one pool
  ref per page so LRU eviction cannot pull the pages mid-read), reads
  every arena's pages through the engine's reused host-staging buffers
  (one device gather + transfer per arena per warmed page-rung chunk,
  never a per-page ``device_get`` round trip, and zero fresh staging
  allocations after the first export), copies them out under the
  arena lock — concurrent exports on the router's handoff thread pool
  each get their OWN arrays — serializes them into a
  :class:`KVPacket`, and releases the pins.
- **install** (:func:`install_packet`) — the decode replica first
  walks its OWN prefix cache with the packet's token chain: pages the
  replica already caches (a shared system prompt handed off earlier,
  or published by its own traffic) are deduplicated — never
  re-installed, never double-stored. Only the uncovered tail pages are
  allocated from the decode pool, scattered into the arenas through
  the engine's fixed write path (between executor dispatches, under
  the arena lock — no new XLA executor signature, so the
  zero-recompile invariant holds on the receiving fleet), and
  published into the decode replica's radix cache. The subsequent
  ``submit`` of the request on the decode replica then admission-
  matches the chain like any cache hit and prefills ONLY the uncached
  suffix (the partial last page + the sampling position) — a dispatch
  in the smallest warm bucket.

The packet is **topology-neutral** the same way PR 7's checkpoints
are: the header records the page payload's logical geometry
(layer/head/head-dim/block-size), the storage dtype, and each arena's
logical PartitionSpec via ``io.spec_to_json`` — never device
positions — so a packet written by a replica on one mesh installs on
a replica laid out on any other (the install path places data under
the DESTINATION arena's sharding; on a single device that is a plain
scatter). Quantized arenas ship their per-row fp32 scale pages in the
same packet: at ``kv_dtype='int8'`` the wire bytes shrink ~3-4x vs
fp32 (``model.kv_page_bytes``), and a dtype mismatch between packet
and destination raises :class:`KVDtypeMismatchError` — the wire NEVER
silently dequantizes.

Env knob (read per call — this file is in tools/repo_lint.py's
ENV_SCOPED_FILES): ``PADDLE_TPU_HANDOFF_VERIFY`` adds a sha1 over the
page payload to every packet. The default is **transport-dependent**:
in-process handoff keeps it opt-in (``1`` to enable — the e2e
bit-identity tests are the stronger check there), but a packet
serialized for the **socket** transport (``to_bytes(transport=
'socket')``, which is what serving/rpc.py's cross-host hop uses)
stamps the sha1 unless explicitly disabled with ``0`` — a corrupted
network packet must be a typed refusal, never silent KV corruption.
``from_bytes`` verifies whenever the header carries a sha1,
regardless of the env: a stamped packet is always checked on receive.
"""

import hashlib
import json
import os
import struct
import time

import numpy as np

from .. import observe as _obs

__all__ = ['KVPacket', 'HandoffError', 'KVDtypeMismatchError',
           'KVGeometryError', 'export_packet', 'install_packet',
           'packet_wire_bytes', 'handoff_verify_enabled']

_MAGIC = b'PTKV'
_VERSION = 1


class HandoffError(RuntimeError):
    """Base class for KV handoff failures (typed so the phase router
    can fail the request instead of hanging it)."""


class KVDtypeMismatchError(HandoffError):
    """Packet arena dtype != destination arena dtype. Refusing is the
    contract: an int8 packet installed into an fp32 arena (or the
    reverse) would silently dequantize/requantize and break the
    bit-identity invariant the handoff e2e asserts."""


class KVGeometryError(HandoffError):
    """Packet page geometry (layers/heads/head dims/block size) does
    not match the destination arenas."""


class CacheKindError(HandoffError):
    """The engine's block caches something other than per-head K/V rows
    (``LMSpec.cache_kinds``: a latent row, an index key), for which the
    packet has no format: raised by name rather than shipping pages a
    destination would misread."""


def handoff_verify_enabled(transport='inproc'):
    """PADDLE_TPU_HANDOFF_VERIFY knob, read per call. Unset, the
    default depends on the transport: OFF for the in-process hop
    (opt-in), ON for ``transport='socket'`` (a wire that can corrupt
    must be verified by default). An explicit ``0`` disables either;
    an explicit ``1`` enables either."""
    raw = os.environ.get('PADDLE_TPU_HANDOFF_VERIFY')
    if raw is None or raw == '':
        return transport == 'socket'
    return raw not in ('0', 'false', 'False')


class KVPacket(object):
    """One sequence's frozen KV pages on the wire.

    ``header`` is a JSON-safe dict: format version, the token chain
    the pages encode (length = n_pages * block_size), the geometry/
    dtype contract, and per-arena entries (name, numpy dtype string,
    per-page shape, logical PartitionSpec json). ``arrays`` maps arena
    name -> host array [L, n_pages, ...] — the concatenation of every
    layer's pages for that arena, scales included for quantized
    dtypes."""

    __slots__ = ('header', 'arrays')

    def __init__(self, header, arrays):
        self.header = header
        self.arrays = arrays

    @property
    def tokens(self):
        return self.header['tokens']

    @property
    def n_pages(self):
        return self.header['n_pages']

    @property
    def kv_dtype(self):
        return self.header['kv_dtype']

    def wire_bytes(self):
        """Payload bytes this packet moves (header excluded)."""
        return sum(a.nbytes for a in self.arrays.values())

    # ------------------------------------------------------------ wire
    def to_bytes(self, transport='inproc'):
        """MAGIC + u32 header length + header JSON + raw arena bytes
        in header arena order. bf16 ships as its raw 2-byte payload
        (io.to_numpy's uint16 view); the header records the logical
        dtype so from_bytes restores it exactly. ``transport='socket'``
        (the cross-host RPC hop) stamps the payload sha1 by default —
        see handoff_verify_enabled."""
        from .. import io as _io
        blobs, arenas = [], []
        for name in sorted(self.arrays):
            arr = self.arrays[name]
            raw, dtype_name = _io._to_numpy(arr)
            raw = np.ascontiguousarray(raw)
            arenas.append({'name': name, 'dtype': dtype_name,
                           'shape': list(arr.shape),
                           'spec': self.header.get('specs', {})
                           .get(name, [])})
            blobs.append(raw.tobytes())
        header = dict(self.header, arenas=arenas)
        if handoff_verify_enabled(transport):
            sha = hashlib.sha1()
            for b in blobs:
                sha.update(b)
            header['sha1'] = sha.hexdigest()
        hj = json.dumps(header, sort_keys=True).encode()
        return b''.join([_MAGIC, struct.pack('<I', len(hj)), hj] + blobs)

    @classmethod
    def from_bytes(cls, data):
        from .. import io as _io
        if data[:4] != _MAGIC:
            raise HandoffError('not a KV handoff packet (bad magic)')
        (hlen,) = struct.unpack('<I', data[4:8])
        header = json.loads(data[8:8 + hlen].decode())
        if header.get('version') != _VERSION:
            raise HandoffError('KV packet version %r (this build reads '
                               '%d)' % (header.get('version'), _VERSION))
        off = 8 + hlen
        arrays = {}
        payload_start = off
        for ent in header['arenas']:
            dtype_name = ent['dtype']
            shape = tuple(ent['shape'])
            base = 'uint16' if dtype_name == 'bfloat16' else dtype_name
            n = int(np.prod(shape)) * np.dtype(base).itemsize
            raw = np.frombuffer(data[off:off + n], dtype=base) \
                .reshape(shape)
            arrays[ent['name']] = _io._from_numpy(raw, dtype_name)
            off += n
        if header.get('sha1'):
            # a stamped packet is ALWAYS verified on receive — the env
            # knob gates whether the writer stamps, never whether the
            # reader checks (a socket packet that went bad in flight
            # must refuse typed, not install silently)
            sha = hashlib.sha1(data[payload_start:off]).hexdigest()
            if sha != header['sha1']:
                raise HandoffError('KV packet payload corrupt: sha1 '
                                   '%s != recorded %s'
                                   % (sha, header['sha1']))
        return cls(header, arrays)


def packet_wire_bytes(spec, n_pages, block_size, kv_dtype='float32'):
    """Analytic payload bytes of an ``n_pages`` handoff at
    ``kv_dtype`` — what the quantized-arena shrink claim is measured
    against (model.kv_page_bytes per page)."""
    from .decode.model import kv_page_bytes
    return kv_page_bytes(spec, block_size, kv_dtype) * int(n_pages)


def _geometry_header(engine):
    geo = engine.kv_geometry()
    out = {k: geo[k] for k in ('n_layer', 'n_head', 'd_key', 'd_value',
                               'block_size', 'kv_dtype')}
    out['arena_names'] = sorted(geo['arena_names'])
    return out


def _check_geometry(engine, header):
    """Destination contract: dtype mismatches get their own typed
    error (the silently-dequantize trap), everything else —
    layer/head geometry AND the arena-name set — is geometry,
    checked BEFORE any page is allocated."""
    geo = _geometry_header(engine)
    if header['kv_dtype'] != geo['kv_dtype']:
        raise KVDtypeMismatchError(
            'KV packet carries %s pages but the destination arena is '
            '%s — a handoff never converts dtypes; re-export from a '
            'matching-dtype replica' % (header['kv_dtype'],
                                        geo['kv_dtype']))
    bad = {k: (header.get(k), geo[k]) for k in
           ('n_layer', 'n_head', 'd_key', 'd_value', 'block_size')
           if header.get(k) != geo[k]}
    if bad:
        raise KVGeometryError(
            'KV packet geometry does not match the destination '
            'arenas: %s' % ', '.join(
                '%s packet=%r dest=%r' % (k, p, d)
                for k, (p, d) in sorted(bad.items())))
    pk_names = sorted(header.get('arena_names') or [])
    if pk_names != geo['arena_names']:
        raise KVGeometryError(
            'KV packet arena set does not match the destination: '
            'packet=%r dest=%r' % (pk_names, geo['arena_names']))


# ----------------------------------------------------------- export
def export_packet(engine, tokens):
    """Serialize the frozen full pages covering ``tokens``' prefix out
    of ``engine``'s arenas. The pages must already be published to the
    engine's prefix cache (they are, the moment prefill crosses each
    page boundary), so export is: pin the chain, read, release.
    Returns a :class:`KVPacket` covering the longest cached chain —
    possibly fewer pages than ``len(tokens) // block_size`` if
    eviction raced us (the receiver simply prefills a longer suffix;
    bit-identity is unaffected) — or None when nothing is cached.
    Requires ``prefix_cache=True`` on the engine."""
    if engine.prefix_cache is None:
        raise HandoffError('export_packet needs prefix_cache=True on '
                           'the prefill engine (frozen pages live in '
                           'the cache between prefill and export)')
    t0 = time.perf_counter()
    tokens = [int(t) for t in tokens]
    page_ids, covered = engine.prefix_cache.acquire(tokens)
    if not page_ids:
        _obs.inc('handoff.empty_exports_total')
        return None
    try:
        # read_pages copies out of the engine-owned staging buffers
        # under the arena lock and returns caller-owned arrays, so
        # concurrent exports (the router's handoff thread pool) can
        # never corrupt each other's packets
        arrays = engine.read_pages(page_ids)
    finally:
        engine.pool.free(page_ids)
    from ..io import spec_to_json
    header = dict(_geometry_header(engine),
                  version=_VERSION,
                  tokens=tokens[:covered],
                  n_pages=len(page_ids),
                  specs={name: spec_to_json(spec) for name, spec
                         in engine.arena_specs().items()})
    pkt = KVPacket(header, arrays)
    if _obs.enabled():
        _obs.record('handoff.export_seconds',
                    time.perf_counter() - t0)
        _obs.inc('handoff.pages_exported_total', len(page_ids))
    return pkt


# ----------------------------------------------------------- install
def install_packet(engine, packet):
    """Install ``packet``'s pages into ``engine``'s arena and register
    the chain in its radix prefix cache. Returns
    ``(covered_tokens, installed_pages, dedup_pages)``.

    A packet whose header carries a ``trace`` entry (the exporting
    side's reqtrace wire form) gets its install spanned under that
    trace_id — the KV hop shows up on the installing process's track
    in the merged fleet timeline.

    Dedup across the handoff boundary: the packet's chain is first
    walked against the destination cache — pages already resident
    (earlier handoff of the same system prompt, or local traffic) are
    reused as-is; only the uncovered tail is allocated, written, and
    published. When the pool cannot supply the tail (pages exhausted
    even after LRU reclaim), the tail is simply dropped: the request
    prefills a longer suffix, correctness unchanged."""
    if engine.prefix_cache is None:
        raise HandoffError('install_packet needs prefix_cache=True on '
                           'the decode engine (handed-off pages are '
                           'registered in, and matched from, its '
                           'radix cache)')
    _check_geometry(engine, packet.header)
    t0 = time.perf_counter()
    tokens = [int(t) for t in packet.tokens]
    bs = engine.block_size
    cache, pool = engine.prefix_cache, engine.pool
    n_pages = packet.n_pages

    # 1. dedup: how much of the chain does this replica already hold?
    have_ids, covered = cache.acquire(tokens)
    have = len(have_ids)
    tail = n_pages - have
    installed = 0
    new_ids = []
    try:
        if tail > 0:
            new_ids = pool.alloc(tail)
            if new_ids is None:
                # page pressure: install what fits page-by-page, front
                # first (a shorter chain is still a win)
                new_ids = []
                for _ in range(tail):
                    one = pool.alloc(1)
                    if one is None:
                        break
                    new_ids.extend(one)
            if new_ids:
                # 2. scatter the tail pages into every arena (one
                # device write per arena, under the engine's arena
                # lock — no executor dispatch, no new signature)
                sl = slice(have, have + len(new_ids))
                engine.write_pages(
                    new_ids, {name: arr[:, sl]
                              for name, arr in packet.arrays.items()})
                installed = len(new_ids)
        # 3. publish the full chain (reused head + installed tail) so
        # admission matches it; publish Dedups per node, increfing
        # only chain nodes it creates
        from .decode.kv_pool import BlockTable
        table = BlockTable()
        table.block_ids = list(have_ids) + list(new_ids)
        chain_tokens = tokens[:len(table.block_ids) * bs]
        cache.publish(chain_tokens, table, len(chain_tokens))
    finally:
        # 4. drop OUR references (the acquire pins + fresh allocs) on
        # every path: after a successful publish the cache's own refs
        # keep the chain resident (and evictable under pressure, like
        # any cached pages); on an error this is what stops a failed
        # handoff from leaking pinned pages until the pool is empty
        ours = list(have_ids) + list(new_ids or [])
        if ours:
            pool.free(ours)
    covered_tokens = len(chain_tokens)
    dedup = have
    ctx = None
    if packet.header.get('trace'):
        from ..observe import reqtrace as _reqtrace
        ctx = _reqtrace.from_wire(packet.header['trace'])
    if ctx is not None:
        ctx.stage('kv_install', t0, time.perf_counter(),
                  pages=installed, dedup=dedup,
                  covered_tokens=covered_tokens)
    if _obs.enabled():
        _obs.record('handoff.install_seconds',
                    time.perf_counter() - t0)
        _obs.inc('handoff.pages_installed_total', installed)
        if dedup:
            _obs.inc('handoff.pages_deduped_total', dedup)
        if n_pages - have - installed > 0:
            _obs.inc('handoff.pages_dropped_total',
                     n_pages - have - installed)
    return covered_tokens, installed, dedup


def handoff(src_engine, dst_engine, tokens, via_bytes=True, ctx=None):
    """The whole hop: export from ``src_engine``, (optionally) round-
    trip through the wire encoding, install into ``dst_engine``.
    Returns the covered token count (0 when nothing was cached to
    ship). One ``kv_handoff`` flight event + ``handoff.*`` metrics per
    call — the unit the phase router's pipeline drives.

    Either side may be a cross-host ``serving.rpc.RemoteReplica``
    (duck-typed on ``export_packet_bytes`` / ``install_packet_bytes``):
    the packet then moves as its socket wire encoding — sha1-stamped
    by default (handoff_verify_enabled('socket')) — and the install
    runs on the destination WORKER against its own prefix cache, so
    the dedup-against-destination path is identical to the in-process
    hop: shared prefixes still ship once per decode host.

    ``ctx`` (a reqtrace.RequestContext, when the hop belongs to a
    traced request) is stamped into the packet header as its wire
    form, so whichever process performs the install — this one or a
    remote worker — spans it under the same trace_id."""
    t0 = time.perf_counter()
    remote_src = callable(getattr(src_engine, 'export_packet_bytes',
                                  None))
    remote_dst = callable(getattr(dst_engine, 'install_packet_bytes',
                                  None))
    transport = 'socket' if (remote_src or remote_dst) else 'inproc'
    if remote_src:
        data = (src_engine.export_packet_bytes(tokens, ctx=ctx)
                if ctx is not None
                else src_engine.export_packet_bytes(tokens))
        if not data:
            return 0
        pkt = KVPacket.from_bytes(data)
    else:
        pkt = export_packet(src_engine, tokens)
        if pkt is None:
            return 0
        if ctx is not None:
            pkt.header['trace'] = ctx.to_wire()
    wire = pkt.wire_bytes()
    if remote_dst:
        covered, installed, dedup = dst_engine.install_packet_bytes(
            pkt.to_bytes(transport='socket'))
    else:
        if via_bytes and not remote_src:  # remote src already rode the wire
            pkt = KVPacket.from_bytes(pkt.to_bytes(transport=transport))
        covered, installed, dedup = install_packet(dst_engine, pkt)
    dt = time.perf_counter() - t0
    if _obs.enabled():
        _obs.inc('handoff.count_total')
        _obs.inc('handoff.bytes_total', wire)
        _obs.record('handoff.seconds', dt)
    _obs.flight_event('kv_handoff', pages=pkt.n_pages,
                      installed=installed, dedup=dedup,
                      covered_tokens=covered, bytes=wire,
                      kv_dtype=pkt.kv_dtype, transport=transport,
                      seconds=round(dt, 6))
    return covered
