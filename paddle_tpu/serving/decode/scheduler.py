"""Continuous-batching scheduler for autoregressive decode.

State machine per request (a ``Sequence``)::

    WAITING --admit/prefill--> RUNNING --eos/max_tokens--> FINISHED
       ^                         |
       +------preempt/requeue----+   (pool exhaustion)

The running set occupies at most ``max_batch`` slots of ONE fixed-shape
decode executable; sequences join the running batch the moment a slot
and enough KV pages are free (continuous batching — no barrier on the
rest of the batch) and leave it the moment they finish, immediately
freeing their pages for the admission of the next waiting request.

Pool exhaustion (a sequence crossing into a page the pool cannot
supply) preempts the *lowest-priority-class, youngest* running
sequence (serving.tenancy classes; all-equal priorities reduce to
plain youngest — the one that loses the least progress), releases its
pages, and requeues it at the front of the waiting line with
``prompt + generated-so-far`` as its new prefill prefix
(recompute-style preemption: already-streamed tokens are never
re-streamed; the re-prefill rebuilds their KV and decoding continues
from where it stopped). Admission is highest-class-first (FIFO within
a class), so ``batch`` traffic backfills only the slots no
latency-class request wants. The scheduler is driven by the engine's
single worker thread; only the waiting queue is touched from submit()
threads (under the engine lock).

Decode-position bookkeeping: ``cache_len`` counts KV entries
materialized on device. After prefilling a prefix of length p the
cache holds p entries and the sampled next token is *pending* (its KV
is written by the decode step that consumes it), so while running
``cache_len == len(prefix) + len(generated) - 1``. ``ahead`` counts the
decode steps that hold the sequence and whose token the worker has not
fetched yet (engine.py runs one step ahead): the next step's position
is ``cache_len + ahead``, and ``cache_len`` itself moves only when a
token is emitted.
"""

import collections
import queue as _queue
import threading
import time

from concurrent.futures import Future

from ... import observe as _obs
from ..tenancy import priority_rank
from .kv_pool import BlockTable

__all__ = ['Sequence', 'GenerationStream', 'Scheduler',
           'WAITING', 'RUNNING', 'FINISHED']

WAITING, RUNNING, FINISHED = 'waiting', 'running', 'finished'

_END = object()


class GenerationStream(object):
    """Per-request token stream + future.

    Iterate for tokens as they are generated (``for tok in stream:``),
    or block for the whole thing with ``result(timeout)`` (the list of
    generated token ids, prompt excluded). ``finish_reason`` is
    'eos' | 'max_tokens' | 'error' once done. ``cached_tokens`` is how
    many of the prompt's tokens the last prefill took from the prefix
    cache's shared pages (None until the request is admitted)."""

    def __init__(self, request_id, prompt_len):
        self.request_id = request_id
        self.prompt_len = prompt_len
        self.finish_reason = None
        self.cached_tokens = None
        self._q = _queue.Queue()
        self._future = Future()
        self._future.set_running_or_notify_cancel()

    # engine-side
    def _put(self, token):
        self._q.put(int(token))

    def _finish(self, reason, tokens):
        self.finish_reason = reason
        self._q.put(_END)
        self._future.set_result(list(tokens))

    def _fail(self, exc):
        self.finish_reason = 'error'
        self._q.put(_END)
        if not self._future.done():
            self._future.set_exception(exc)

    # client-side
    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _END:
                return
            yield item

    def result(self, timeout=None):
        return self._future.result(timeout)

    def done(self):
        return self._future.done()


class Sequence(object):
    """One in-flight generation request."""

    __slots__ = ('request_id', 'prompt', 'max_new_tokens', 'temperature',
                 'seed', 'eos_id', 'tables', 'generated', 'streamed',
                 'state', 'stream', 'cache_len', 'pending_token',
                 't_submit', 't_admit', 't_first_token', 't_last_token',
                 'preemptions', 'cached_len', 'published_pages', 'ctx',
                 'tenant', 'priority', 'prio_rank', 'ahead',
                 'clk_submit', 'clk_last_token')

    def __init__(self, request_id, prompt, max_new_tokens, temperature,
                 seed, eos_id, ctx=None, tenant=None, priority=None):
        self.request_id = request_id
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.eos_id = eos_id
        # one block table a page pool of the engine (the scheduler adds
        # the others' as it takes the request in)
        self.tables = [BlockTable()]
        self.generated = []
        self.streamed = 0
        self.state = WAITING
        self.stream = GenerationStream(request_id, len(self.prompt))
        self.cache_len = 0
        self.ahead = 0             # steps enqueued, token not fetched
        self.pending_token = None
        self.t_submit = time.perf_counter()
        self.t_admit = None
        self.t_first_token = None
        self.t_last_token = None
        # the engine's worker clock (seconds by state) as it read at
        # t_submit and at t_last_token; None where observe was off
        self.clk_submit = None
        self.clk_last_token = None
        self.preemptions = 0
        self.cached_len = 0        # prefix-cache hit span (this prefill)
        self.published_pages = 0   # full pages already offered to cache
        self.ctx = ctx      # reqtrace.RequestContext (trace correlation)
        # multi-tenant scheduling citizenship (serving.tenancy): None
        # lands on 'standard', so untenanted traffic schedules exactly
        # as before
        self.tenant = tenant
        self.priority = priority
        self.prio_rank = priority_rank(priority)

    @property
    def table(self):
        """The first page pool's block table."""
        return self.tables[0]

    def prefix(self):
        """Tokens whose KV must exist before the next decode step —
        after a preemption this is what re-prefills."""
        return self.prompt + self.generated

    def position(self):
        """Where the next step enqueued for the sequence writes: past the
        cache and the steps already enqueued."""
        return self.cache_len + self.ahead

    def continues(self):
        """Whether the sequence needs a token beyond those of the steps
        already enqueued for it: known by count, without their result."""
        return len(self.generated) + self.ahead < self.max_new_tokens

    def finished(self):
        if len(self.generated) >= self.max_new_tokens:
            return 'max_tokens'
        if self.eos_id is not None and self.generated and \
                self.generated[-1] == self.eos_id:
            return 'eos'
        return None


class Scheduler(object):
    """Owns the waiting queue, the running set, and the page budget.
    All mutation happens on the engine worker thread except ``add``
    (submit path, engine-locked). With a ``cache`` (prefix_cache.py),
    admission first maps the prompt's cached pages into the block
    table — and because the cache is the pool's reclaimer, every grow
    below LRU-evicts reclaimable cached pages before this scheduler
    ever preempts a running victim. ``pool`` is the engine's KVPool or,
    where its cache kinds lie in several page pools, the list of them:
    a sequence then has a table a pool, admission and growth need every
    pool's pages, and a pool with a lifetime is trimmed behind its
    window before it is grown (``grow``)."""

    def __init__(self, pool, max_batch, cache=None):
        self.pools = list(pool) if isinstance(pool, (list, tuple)) \
            else [pool]
        self.pool = self.pools[0]
        self.max_batch = int(max_batch)
        self.cache = cache
        self.waiting = collections.deque()
        self.running = []          # admission order (oldest first)
        self.peak_running = 0      # high-water mark of resident seqs
        self._mu = threading.Lock()

    # ------------------------------------------------------------ intake
    def add(self, seq):
        while len(seq.tables) < len(self.pools):
            seq.tables.append(BlockTable())
        with self._mu:
            self.waiting.append(seq)
        self._publish()

    def counts(self):
        with self._mu:
            return len(self.waiting), len(self.running)

    def free_slots(self):
        """Batch slots not currently occupied — one of the two decode-
        phase admission signals (the other is the pool's free pages)
        the phase-aware router ranks decode replicas by."""
        with self._mu:
            return max(0, self.max_batch - len(self.running))

    def _publish(self):
        if _obs.enabled():
            w, r = self.counts()
            _obs.set_gauge('decode.waiting_seqs', w)
            _obs.set_gauge('decode.running_seqs', r)

    # --------------------------------------------------------- admission
    def _head(self):
        """Index of the waiting sequence admission takes next: highest
        class first, FIFO within the class — so the batch class only
        backfills slots no latency-class request is waiting for
        (all-equal priorities reduce to plain FIFO, including preempted
        sequences requeued at the front). Caller holds the lock."""
        idx, best = 0, self.waiting[0].prio_rank
        if best > 0:
            for i, s in enumerate(self.waiting):
                if s.prio_rank < best:
                    idx, best = i, s.prio_rank
                    if best == 0:
                        break
        return idx

    def admittable(self):
        """Whether ``pop_admittable`` would admit a request now, with
        nothing changed: a slot is free and the pool, with every page
        the prefix cache holds (mapped on a hit or given back on a
        miss), covers the head request's prefix plus one write. The
        engine asks before it leaves a decode step in flight."""
        with self._mu:
            if len(self.running) >= self.max_batch or not self.waiting:
                return False
            seq = self.waiting[self._head()]
        tokens = len(seq.prompt) + len(seq.generated) + 1
        for pool, table in zip(self.pools, seq.tables):
            have = pool.free_blocks()
            if self.cache is not None:
                have += self.cache.cached_pages()
            if pool.span_pages(tokens) - len(table) > have:
                return False
        return True

    def pop_admittable(self):
        """Admit the next waiting sequence if a batch slot is free and
        the pool covers its prefill prefix plus one decode write. A
        prefix-cache hit maps the shared pages first (refcount bump,
        frozen), so only the uncached suffix needs fresh pages.
        Returns the Sequence (pages allocated, state RUNNING) or None."""
        with self._mu:
            if len(self.running) >= self.max_batch or not self.waiting:
                return None
            idx = self._head()
            seq = self.waiting[idx]
            prefix = seq.prefix()
            if self.cache is not None and not seq.table.block_ids:
                seq.cached_len = self.cache.match(prefix, seq.table)
                seq.published_pages = seq.cached_len // \
                    self.pool.block_size
            if not self._admit_pages(seq, len(prefix) + 1):
                if seq.cached_len:
                    # roll the match back: pinned cache pages would
                    # block the very evictions admission is waiting on
                    self.cache.unmatch(seq.table, seq.cached_len)
                    seq.cached_len = 0
                    seq.published_pages = 0
                _obs.inc('decode.admission_blocked_total')
                return None
            del self.waiting[idx]
            seq.state = RUNNING
            seq.t_admit = time.perf_counter()
            self.running.append(seq)
            if len(self.running) > self.peak_running:
                self.peak_running = len(self.running)
                if _obs.enabled():
                    _obs.set_gauge('decode.running_seqs_peak',
                                   self.peak_running)
        self._publish()
        return seq

    def _admit_pages(self, seq, tokens):
        """The pages ``seq`` is admitted with, of every pool or of none:
        a prefix of ``tokens`` positions whole, or under a lifetime its
        first ``span_pages`` pages, the most the sequence ever holds of
        that pool (``grow`` trims before it grows and the worker is the
        one thread that takes pages, so a prefill's later chunks find
        theirs)."""
        for i, (pool, table) in enumerate(zip(self.pools, seq.tables)):
            if not pool.grow(table, min(
                    tokens, pool.span_pages(tokens) * pool.block_size)):
                # a later pool's shortfall (no prefix cache there, so
                # the tables were empty): nothing is kept
                for done, mine in zip(self.pools[:i], seq.tables[:i]):
                    done.release(mine)
                return False
        return True

    # ----------------------------------------------------------- growth
    def grow(self, seq, need_tokens, first_query=None):
        """Make ``seq``'s tables cover ``need_tokens`` positions for
        programs whose first query sits at ``first_query`` (default:
        the last position, one row), in every pool: a pool with a
        lifetime first gives back the pages that lie behind that
        query's window. False where some pool cannot supply its pages
        (what was given back or grown stays so)."""
        if first_query is None:
            first_query = need_tokens - 1
        ok = True
        for pool, table in zip(self.pools, seq.tables):
            pool.trim(table, first_query)
            ok = pool.grow(table, need_tokens) and ok
        return ok

    def ensure_growth(self, seq, need_tokens=None):
        """Make sure ``seq`` owns the pages its next decode write lands
        in (``need_tokens`` positions — default one write past the steps
        already enqueued; speculative steps need cache_len + k + 1),
        preempting victims on
        exhaustion. Cache-reclaimable pages are consulted first: grow
        only fails once the prefix cache's LRU evictor (the pool's
        reclaimer) has nothing left to give. False when ``seq`` itself
        was preempted (caller must drop it from this step)."""
        if need_tokens is None:
            need_tokens = seq.position() + 1
        while not self.grow(seq, need_tokens, seq.position()):
            _obs.inc('decode.pool_exhausted_total')
            _obs.flight_event('decode_pool_exhausted',
                              request_id=seq.request_id,
                              free_blocks=self.pool.free_blocks(),
                              running=len(self.running),
                              waiting=len(self.waiting))
            victim = self._pick_victim()
            self.preempt(victim)
            if victim is seq:
                return False
        return True

    def _pick_victim(self):
        # lowest priority CLASS first (batch before standard before
        # interactive), youngest within the class — the youngest loses
        # the least progress, and the preemption mechanics (release +
        # front-requeue + bit-exact re-prefill) are identical for every
        # class. All-equal priorities reduce to the old youngest-victim
        # rule exactly.
        worst = max(seq.prio_rank for seq in self.running)
        for seq in reversed(self.running):
            if seq.prio_rank == worst:
                return seq
        return self.running[-1]

    def preempt(self, seq):
        """Release pages, requeue at the FRONT with prompt+generated as
        the new prefill prefix. Already-streamed tokens stay streamed."""
        with self._mu:
            self.running.remove(seq)
            self.waiting.appendleft(seq)
        self._release(seq)
        seq.state = WAITING
        seq.cache_len = 0
        seq.pending_token = None
        # shared cached pages just lost this sequence's reference —
        # refcount-1 survivors are demoted back to evictable, and the
        # re-prefill will re-match whatever is still cached
        seq.cached_len = 0
        seq.published_pages = 0
        seq.preemptions += 1
        _obs.inc('decode.preemptions_total')
        _obs.inc('tenant.preempted', tenant=seq.tenant or 'default',
                 priority=seq.priority or 'standard')
        _obs.flight_event('decode_preempt', request_id=seq.request_id,
                          generated=len(seq.generated),
                          freed_blocks=self.pool.free_blocks())
        if seq.ctx is not None:
            seq.ctx.event('preempt', generated=len(seq.generated))
        self._publish()

    def _release(self, seq):
        for pool, table in zip(self.pools, seq.tables):
            if table.block_ids:
                pool.release(table)

    # ----------------------------------------------------------- finish
    def finish(self, seq, reason):
        with self._mu:
            self.running.remove(seq)
        self._release(seq)
        seq.state = FINISHED
        _obs.inc('decode.finished_total', reason=reason)
        seq.stream._finish(reason, seq.generated)
        self._publish()

    def fail_all(self, exc):
        """Worker-death path: every in-flight and queued request gets
        the exception instead of hanging its client forever. Returns
        the number of requests failed."""
        with self._mu:
            seqs = list(self.running) + list(self.waiting)
            self.running = []
            self.waiting.clear()
        for seq in seqs:
            self._release(seq)
            seq.state = FINISHED
            seq.stream._fail(exc)
        self._publish()
        return len(seqs)
