"""Paged KV-cache pool: host-side bookkeeping for the HBM page arena.

The device side is a preallocated arena ``[L, NB, bs, H*D]`` (one
fixed tensor per K and V, living in the engine's scope and updated in
place through executor donation). This module owns the *map* of that
arena: which physical pages are free, which sequence holds which pages
in which logical order (its block table), and how many owners each
page has. Pure host Python — no jax — so it is trivially testable and
adds zero work to the device step.

Reference counting: pages default to one owner, but ``fork()`` lets a
new sequence share a prefix's pages (prefix caching / beam-style
branching), bumping refcounts; ``free`` only returns a page to the
free list when its count hits zero. The free list is LIFO so recently
touched pages are reused first (warm in cache).

Exhaustion is a normal state, not an error: ``alloc`` returns None and
the continuous-batching scheduler reacts by preempting a victim
sequence (freeing its pages, requeueing it) — see scheduler.py.

Lifetimes: a pool built with ``keep`` > 0 indexes arenas whose layers
all read at most the last ``keep`` positions (a sliding window;
``model.LMSpec.page_pools``). ``trim`` hands back a table's leading
pages once every position in them lies further back than that from
every query still to come; the table keeps its logical indexing (entry i
covers positions i * bs ...), the given-back entries are None (on the
device: past the pool, like an unowned entry) and ``grow`` goes on at
the end. A sequence then holds about ``keep`` positions of such a pool
however long it is.

Slots: a pool built with ``whole`` indexes arenas whose unit is one
sequence's whole state (a recurrent layer's: ``model.CacheKind.per_seq``),
not a page of token rows. It is the same free list of ids; what differs
is how many a sequence needs: one, whatever its length (``blocks_for``),
taken at admission like its first pages, kept while it runs, given back
by ``release`` at its finish or its preemption.

Cache integration: a global prefix cache (prefix_cache.py) parks
frozen pages at refcount 1 so future requests can map them instead of
re-prefilling. Those pages are *reclaimable*, not free — ``alloc``
consults the installed ``set_reclaimer`` callback before reporting
exhaustion, so cached pages are LRU-evicted back into the free list on
demand and the cache can never starve admission (and the scheduler
only preempts a victim once the cache has nothing left to give).
"""

import threading
import time

from ... import observe as _obs

__all__ = ['KVPool', 'BlockTable']

# The fragmentation gauges need a sort over the free list, so _publish
# only refreshes them every Nth alloc/free; direct largest_free_run()
# / fragmentation() reads always recompute (and re-publish) fresh.
_FRAG_PUBLISH_EVERY = 64


class BlockTable(object):
    """One sequence's logical->physical page map. ``freed`` leading
    entries were given back behind a window (``KVPool.trim``) and are
    None."""

    __slots__ = ('block_ids', 'freed')

    def __init__(self):
        self.block_ids = []
        self.freed = 0

    def __len__(self):
        return len(self.block_ids)

    def capacity(self, block_size):
        return len(self.block_ids) * block_size


class KVPool(object):
    """Free-list allocator over ``num_blocks`` physical pages of
    ``block_size`` token slots each. ``kind`` names the pool where an
    engine has several (its series then carry ``kind=``; the one pool
    of an engine publishes them bare, as it always did). ``keep`` > 0
    is the pool's lifetime (module docstring) and ``ahead`` the most
    consecutive positions one program writes (a prefill chunk): a
    sequence never holds more than ``span_pages()``, which is what
    admission asks of such a pool, whatever the prompt's length.
    ``whole``: a unit is a sequence's whole state, and a sequence holds
    one (module docstring)."""

    def __init__(self, num_blocks, block_size, kind=None, keep=0, ahead=1,
                 whole=False):
        if num_blocks < 1 or block_size < 1:
            raise ValueError('KVPool: need num_blocks >= 1 and '
                             'block_size >= 1, got %d / %d'
                             % (num_blocks, block_size))
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.kind = kind
        self.keep = int(keep)
        self.ahead = int(ahead)
        self.whole = bool(whole)
        self._labels = {'kind': kind} if kind else {}
        self._mu = threading.Lock()
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._refs = [0] * self.num_blocks
        self._reclaimer = None
        self._frag_seq = 0
        self._publish()

    def set_reclaimer(self, fn):
        """Install ``fn(n) -> freed_count``, consulted by ``alloc``
        when fewer than ``n`` pages are free. The prefix cache installs
        its LRU evictor here; ``fn`` is called OUTSIDE the pool lock
        (it frees pages through ``free``, which takes it)."""
        self._reclaimer = fn

    # ------------------------------------------------------------ stats
    def free_blocks(self):
        with self._mu:
            return len(self._free)

    def used_blocks(self):
        with self._mu:
            return self.num_blocks - len(self._free)

    def occupancy(self):
        with self._mu:
            return 1.0 - len(self._free) / float(self.num_blocks)

    def largest_free_run(self):
        """Length of the longest run of CONTIGUOUS free page ids — the
        fragmentation signal. Page handoff (serving/handoff.py) lands
        whole page groups at once, so a pool whose free count is high
        but whose largest run is short is fragmented: allocations
        still succeed (pages are position-independent through block
        tables) but the gauge pair free-vs-largest-run makes allocator
        churn visible across replicas. Reading it refreshes the
        gauges, so a scrape always sees a fresh value."""
        with self._mu:
            run = self._largest_run_locked()
            self._publish_frag_locked(run)
            return run

    def _largest_run_locked(self):
        if not self._free:
            return 0
        ids = sorted(self._free)
        best = run = 1
        for prev, cur in zip(ids, ids[1:]):
            run = run + 1 if cur == prev + 1 else 1
            if run > best:
                best = run
        return best

    def fragmentation(self):
        """1 - largest_free_run / free_pages (0.0 = one contiguous
        run or empty free list). Refreshes the gauges like
        largest_free_run."""
        with self._mu:
            free = len(self._free)
            run = self._largest_run_locked()
            self._publish_frag_locked(run)
            if not free:
                return 0.0
            return 1.0 - run / float(free)

    def _publish_frag_locked(self, run):
        if _obs.enabled():
            free = len(self._free)
            _obs.set_gauge('decode.kv_largest_free_run', run,
                           **self._labels)
            _obs.set_gauge('decode.kv_fragmentation',
                           1.0 - run / float(free) if free else 0.0,
                           **self._labels)

    def _publish(self):
        if _obs.enabled():
            free = len(self._free)
            labels = self._labels
            _obs.set_gauge('decode.kv_blocks_free', free, **labels)
            _obs.set_gauge('decode.kv_free_pages', free, **labels)
            _obs.set_gauge('decode.kv_blocks_total', self.num_blocks,
                           **labels)
            _obs.set_gauge('decode.kv_block_occupancy',
                           1.0 - free / float(self.num_blocks), **labels)
            if labels:
                _obs.set_gauge('decode.kv_pages_used',
                               self.num_blocks - free, **labels)
            if self.whole:
                _obs.set_gauge('decode.state_slots_used',
                               self.num_blocks - free)
                _obs.set_gauge('decode.state_slots_total', self.num_blocks)
            # largest-run is an O(free log free) sort — keep it OFF
            # the per-alloc/free hot path: refresh every Nth publish
            # (and on every direct largest_free_run/fragmentation
            # read, so scrapes stay fresh)
            self._frag_seq += 1
            if self._frag_seq % _FRAG_PUBLISH_EVERY == 1:
                self._publish_frag_locked(self._largest_run_locked())

    def blocks_for(self, n_tokens):
        """Pages needed to hold n_tokens positions; of a pool of whole
        states, the one slot a sequence of any length holds."""
        if self.whole:
            return min(1, max(0, int(n_tokens)))
        return max(0, (int(n_tokens) + self.block_size - 1)
                   // self.block_size)

    def span_pages(self, n_tokens):
        """Pages a sequence of ``n_tokens`` positions holds of this
        pool at most at one time: all of them, or under a lifetime the
        pages that ``keep`` positions behind the first row of a program
        and its ``ahead`` rows touch."""
        pages = self.blocks_for(n_tokens)
        if not self.keep:
            return pages
        return min(pages, self.blocks_for(self.keep + self.ahead) + 2)

    def refcount(self, page_id):
        with self._mu:
            return self._refs[page_id]

    # ------------------------------------------------------- alloc/free
    def alloc(self, n):
        """Claim ``n`` pages (refcount 1 each). Returns the page-id list,
        or None when fewer than ``n`` are free — the caller decides
        whether that means preempt, wait, or reject. A shortfall first
        asks the installed reclaimer (prefix-cache LRU eviction) to top
        the free list back up before giving up."""
        n = int(n)
        t0 = None
        while True:
            with self._mu:
                if n <= len(self._free):
                    ids = [self._free.pop() for _ in range(n)]
                    for i in ids:
                        self._refs[i] = 1
                    self._publish()
                    self._record_stall(t0)
                    _obs.inc('decode.kv_pages_allocated_total', n,
                             **self._labels)
                    return ids
                short = n - len(self._free)
            # the stall clock starts at the first shortfall: everything
            # past this point (reclaimer eviction, or the caller's
            # preempt-and-retry) is time a request spent waiting on the
            # allocator — the cross-replica pressure signal the decode
            # /statusz panel surfaces
            if t0 is None:
                t0 = time.perf_counter()
            if self._reclaimer is None or self._reclaimer(short) <= 0:
                self._record_stall(t0)
                return None

    def _record_stall(self, t0):
        if t0 is not None and _obs.enabled():
            _obs.record('decode.alloc_stall_seconds',
                        time.perf_counter() - t0)

    def grow(self, table, n_tokens):
        """Ensure ``table`` covers ``n_tokens`` positions, allocating
        pages as needed. True on success; False (table unchanged) when
        the pool cannot supply them."""
        need = self.blocks_for(n_tokens) - len(table.block_ids)
        if need <= 0:
            return True
        ids = self.alloc(need)
        if ids is None:
            return False
        table.block_ids.extend(ids)
        return True

    def incref(self, ids):
        with self._mu:
            for i in ids:
                if self._refs[i] <= 0:
                    raise ValueError('incref of free page %d' % i)
                self._refs[i] += 1

    def free(self, ids):
        """Drop one reference from each page; pages reaching zero return
        to the free list."""
        with self._mu:
            for i in ids:
                if self._refs[i] <= 0:
                    raise ValueError('double free of page %d' % i)
                self._refs[i] -= 1
                if self._refs[i] == 0:
                    self._free.append(i)
            self._publish()

    def trim(self, table, first_query):
        """Give back the leading pages of ``table`` that no query at or
        past position ``first_query`` reads: a query at ``p`` reads keys
        ``p - keep < j <= p``, so the pages all of whose positions lie
        below ``first_query + 1 - keep``. Nothing under a pool without
        a lifetime. Returns the pages given back. Their next owner's
        programs are enqueued after every program of this sequence that
        could read them, and the device runs programs in order."""
        if not self.keep:
            return 0
        upto = min((int(first_query) + 1 - self.keep) // self.block_size,
                   len(table.block_ids))
        if upto <= table.freed:
            return 0
        ids = table.block_ids[table.freed:upto]
        table.block_ids[table.freed:upto] = [None] * len(ids)
        table.freed = upto
        self.free(ids)
        _obs.inc('decode.kv_pages_freed_behind_window_total', len(ids),
                 **self._labels)
        return len(ids)

    def release(self, table):
        """Free a sequence's whole table."""
        ids = table.block_ids[table.freed:]
        table.block_ids, table.freed = [], 0
        self.free(ids)

    def fork(self, table, frozen_tokens=None):
        """A new BlockTable sharing ``table``'s pages (copy-on-nothing:
        pages are append-only per position, so sharing a frozen prefix
        is safe; the new sequence must grow into fresh pages before
        writing past the shared prefix).

        ``frozen_tokens`` caps sharing at the last *full* page boundary
        below it: a page still being appended to (the donor's partial
        last page) must never be shared — the donor's next decode write
        would land inside the child's view. With ``frozen_tokens=None``
        every page is shared and the CALLER promises the donor is
        frozen (finished, or forked exactly at a page boundary)."""
        if table.freed:
            raise ValueError('fork of a table trimmed behind a window')
        ids = table.block_ids
        if frozen_tokens is not None:
            ids = ids[:int(frozen_tokens) // self.block_size]
        self.incref(ids)
        t = BlockTable()
        t.block_ids = list(ids)
        return t
