"""DecodeEngine: continuous-batching autoregressive decode serving.

The bucket `ServingEngine` serves single-shot forward passes; this
engine serves token-by-token generation — the dominant TPU serving
workload — over a decoder-only LM with a paged KV cache:

- **submit()** (any thread) validates a prompt against the page budget
  and returns a `GenerationStream` immediately: iterate it for tokens
  as they are generated, or `.result()` for the full sequence.
- **one worker thread** runs the prefill/decode loop: admit waiting
  requests into free batch slots (one `paged_prefill` dispatch per
  admission, bucketed prompt lengths), then one `paged_decode_step`
  for the whole running batch. Sequences enter and leave the running
  batch continuously; the batch never waits for its slowest member.
- **fixed decode signature**: the decode step always runs at
  [max_batch] with per-slot block tables — scheduling churn never
  creates a new XLA signature, so after `warmup()` (prefill buckets +
  the one decode key) live traffic is 100% executor cache hits: the
  contract tests/test_decode_serving.py asserts, same as the bucket
  engine's.
- **pool exhaustion** preempts the youngest running sequence
  (recompute-style requeue, scheduler.py) rather than failing it;
  flight events + counters make the resulting latency spikes
  explainable post-hoc (tools/flight_report.py). The prefix cache's
  LRU evictor runs first — reclaimable cached pages feed the free
  list before any victim is chosen.
- **global prefix cache** (`prefix_cache=True` or
  PADDLE_TPU_PREFIX_CACHE=1): frozen full pages are published to a
  radix trie keyed by token chains; a new request whose prompt hits a
  cached chain maps the shared pages and prefills only the uncached
  suffix — time-to-first-token drops by the shared span's cost.
- **speculative decoding** (`spec_k=K` or PADDLE_TPU_SPEC_K=K): a
  host-side draft (prompt-lookup n-gram by default, pluggable via
  ``draft=``) proposes k tokens per running sequence and ONE
  `paged_spec_verify` dispatch — a fixed [max_batch, k+1] signature —
  scores every proposal; longest-accepted-prefix acceptance emits
  up to k+1 tokens per step, bit-identical to plain decode.

- **block families** (model.LMSpec ``block``): the 2017 post-LN block,
  the parallel routed-expert block (grouped KV heads, sliding and
  full layers in one cache, the experts held here of those the router
  scores) and the latent block (latent attention over every position,
  under a learned sparse selection or under a window by layer kind, up
  to three kinds of cache under the one block table:
  ``LMSpec.cache_kinds``) and the grouped block with a page pool a
  layer kind (sliding layers whose pool keeps a window's pages of a
  sequence beside full layers whose pool keeps every page:
  ``LMSpec.page_pools``; a ``KVPool``, a page count and a block table a
  pool, ``self.pools``) and the state-space hybrid (Mamba-2 layers whose
  state is one slot a sequence, a cache kind with a size a sequence in
  a pool of slots, beside attention layers in pages: the programs are
  fed a slot a row, ``_table_widths``) and the shortcut block (two
  latent attentions a layer, so a token keeps two cache layers a layer
  of the one kind, and a router a third of whose outputs are identity
  experts) and the delta hybrid (linear-attention layers under the gated
  delta rule, whose state is a matrix a head in the same pool of slots,
  beside gated attention in pages) run through this same engine. For
  the latter six speculation and quantized arenas raise rather than run
  untested,
  and for the latent, the shortcut and the grouped block, and any that
  keeps a state, the page handoff too. The prefix cache runs
  for a spec whose frozen pages another sequence may map
  (``LMSpec.shares_frozen_pages``: 'post_ln', and a latent block all
  of whose layers read every cached position); the others raise: their
  logits with shared pages are held to no reference yet. A prefix
  longer than the top prompt bucket is prefilled in chunks of it
  (``prefill_chunk``), from wherever its cached span ends.

Per-row device math is batch-composition-independent, so each
request's token stream is bit-identical to running it alone —
continuous batching, prefix caching, and speculation are pure
throughput wins, never a correctness trade.

**The order of a decode step: one step ahead.** The worker enqueues
step n+1 before it fetches step n's tokens: build n+1 → enqueue n+1
(the device→host copy of its tokens started with it) → fetch n → emit
n, all under one ``decode.step`` span a program, so the device goes
from one decode program to the next while the host builds, dispatches
and wakes up. Everything step n+1 is fed but its tokens is known
without step n's result (``Sequence.position()``: ``cache_len`` plus
the steps ``ahead``; a finish by ``max_new_tokens`` is known by
count and leaves the row out); the tokens are step n's fetch, still on
the device, its rows moved to their places in the new batch and a row
that joined from a prefill given its first token from the host by one
tiny jitted program (``_merge_tokens``; none at all when the batch did
not change). The device runs programs in the order they were enqueued
and the arenas chain through the donated scope, so the order of the
writes is the synchronous one.

- *A finish only the token shows (EOS)* is seen when step n is emitted,
  with step n+1 already holding the row: that row's token of step n+1
  is dropped, never emitted or counted. Its write landed in a page the
  row still owned when the step was enqueued; the pages are released
  at the finish, after that enqueue, so their next owner's programs
  come later in the device's order.
- *The pipeline is empty (depth 0: enqueue, fetch, emit, as before)
  before anything but the next decode step is enqueued*, decided from
  what the engine observes where a step ends (``_stays_in_flight``), no
  flag: (1) a waiting request the scheduler would admit (a prefill);
  (2) a row whose next page the pool cannot give without a victim (a
  preemption: the step in flight is fetched before ``ensure_growth``
  picks one); (3) ``spec_k > 0`` (acceptance decides the next feeds);
  (4) ``read_pages`` / ``write_pages`` (under the arena lock they wait
  for the step in flight to end on the device before they enqueue
  theirs); (5) the end of the work: no row goes on, ``drain()``,
  ``shutdown()``, a worker error.
- ``decode.step_seconds`` is recorded once a decode program, where its
  tokens arrive: arrival(n) − max(arrival(n−1), the instant step n's
  dispatch began). Consecutive records tile the time the device spends
  on decode steps and never overlap. ``decode.steps_ahead_total``
  counts the steps enqueued while the one before was unfetched, beside
  ``decode.steps_total``.

**The worker's clock** (with observe on; one boolean read a call site
with it off). The four spans that partition the worker thread's wall
time (``decode.idle``, ``.admit``, ``.prefill``, ``.step``: the states
of ``decode.worker_seconds``) also move a ``StateClock``: the seconds
spent in each state so far, readable at any instant from any thread.
Three intervals that begin in one span or on one thread and end in
another are split by it, each as the difference of two readings, so
that the parts of each sum to what the older histogram records:

- ``decode.queue_wait_seconds{state}``: a request's wait from
  ``t_submit`` to ``t_admit`` (``decode.queue_seconds``): behind other
  requests' prefills, behind the step in flight, in the worker's
  wake-up from idle;
- ``decode.token_gap_seconds{state}`` over ``decode.token_gaps_total``:
  the gap between two tokens of one sequence
  (``decode.inter_token_seconds``): ``prefill`` is what other requests'
  prompts put between them;
- ``decode.device_empty_seconds{state}`` and the ring's span
  ``decode.device_empty``: the stretches in which the engine has **no
  program outstanding on the device**. A program is outstanding from
  the return of its enqueue (``Executor.run(return_numpy=False)``) to
  the arrival of its tokens on the host; a prefill's chunks are
  enqueued back to back and only the last is fetched, so a chunked
  prefill is outstanding from its first chunk's enqueue to its last
  chunk's fetch. A stretch begins where an arrival leaves nothing
  behind it (``_fetch`` of a step with no newer one in flight, the
  fetch of a prefill's last chunk, the worker's start) and ends where
  the next enqueue returns (a prefill's first chunk, a decode or
  verify step) or with the worker. ``idle`` in it is an engine with
  nothing to run; the other states are the host standing between two
  programs though work exists. What is idle on the device *while* a
  program is outstanding (launch latency, gaps inside a program) is in
  no such stretch. The page handoff's gathers and scatters and the
  token merge are not the worker's programs and are not counted.
"""

import collections
import contextlib
import itertools
import threading
import time

import numpy as np

from ... import observe as _obs
from ...observe import reqtrace as _reqtrace
from ...core.executor import Executor
from ...core.place import TPUPlace
from ...core.scope import Scope, scope_guard
from ..buckets import pow2_ladder
from ..engine import EngineClosedError, QueueFullError
from .kv_pool import KVPool
from .model import FULL, MOE, LMSpec, build_lm_programs, held_transposed
from .prefix_cache import PrefixCache, prefix_cache_enabled
from .scheduler import RUNNING, Scheduler, Sequence
from .spec import NgramDraft, accept_drafts, spec_k_from_env

__all__ = ['DecodeEngine', 'LMSpec']

_ENGINE_IDS = itertools.count(1)

# a step enqueued and not yet fetched: its rows in order, its fetches
# where the device leaves them, the instant its dispatch began, the
# ``decode.step`` span it was enqueued under
_Step = collections.namedtuple('_Step', 'batch tokens stats t0 no')


def _merge_tokens(prev, src, host):
    """The next step's tokens from the last step's, on the device: row i
    is ``prev[src[i]]``, or ``host[i]`` where ``src[i]`` < 0 (a row that
    joined from a prefill; a slot past the batch)."""
    import jax.numpy as jnp
    return jnp.where(src >= 0, prev[jnp.maximum(src, 0)],
                     host.astype(prev.dtype))


def _swapped(arr):
    """``arr`` with its last two axes swapped: a parameter's held layout
    from its declared one and back (``model.HeldTransposed``)."""
    import jax.numpy as jnp
    return jnp.swapaxes(arr, -1, -2)


# the worker thread's four states: their spans feed one histogram whose
# label sums partition the thread's wall time between start() and
# shutdown(), and the engine's ``StateClock``, by which an interval
# that crosses spans or threads is split
_WORKER_SECONDS = 'decode.worker_seconds'
_STATES = ('idle', 'admit', 'prefill', 'step')
_NO_TIME = (0.0,) * len(_STATES)
_IDLE = {'state': 'idle'}
_ADMIT = {'state': 'admit'}
_PREFILL = {'state': 'prefill'}
_STEP = {'state': 'step'}


class DecodeEngine(object):
    """Continuous-batching decode server over a paged KV cache.

    ::

        spec = LMSpec(vocab_size=1000, n_layer=2, ...)
        eng = DecodeEngine(spec, max_batch=8, block_size=16,
                           num_blocks=128, pages_per_seq=8)
        eng.warmup()                    # AOT: prefill buckets + decode
        eng.start()
        stream = eng.submit([1, 5, 7], max_new_tokens=32)
        for tok in stream: ...          # tokens as they generate
        eng.shutdown()

    ``pages_per_seq * block_size`` caps prompt_len + max_new_tokens of
    a single request; ``num_blocks`` is the shared HBM page budget that
    continuous batching packs.
    """

    def __init__(self, spec, max_batch=8, block_size=16, num_blocks=64,
                 pages_per_seq=8, max_queue_depth=64, max_prompt_len=None,
                 place=None, weights=None, prefix_cache=None, spec_k=None,
                 draft=None, kv_dtype=None, name=None, prefill_chunk=None,
                 min_prompt_bucket=1, pool_blocks=None):
        import jax
        from ...quant.core import resolve_kv_dtype
        from ...quant.core import kv_itemsize
        from .model import kv_bytes_per_kind, kv_bytes_per_token
        self.spec = spec
        # fleet identity: the routers key membership, placement, and
        # per-replica metrics on it (same contract as ServingEngine)
        self.name = str(name) if name else None
        self.max_batch = int(max_batch)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        # pages by page pool of the spec (LMSpec.page_pools): every pool
        # has ``num_blocks`` but those ``pool_blocks`` names
        page_pools = spec.page_pools()
        # the feeds of the pools past the first: ``pf_table<suffix>``
        self._more_tables = [pool.feed for pool in page_pools[1:]]
        # a pool of whole states has a slot a batch row: a sequence
        # holds one from admission to release, and none while it waits
        pages = {pool.name: self.max_batch if pool.per_sequence
                 else self.num_blocks for pool in page_pools}
        unknown = sorted(set(pool_blocks or ()) - set(pages) - {''})
        if unknown:
            raise ValueError('pool_blocks for %s: the spec\'s pools are %s'
                             % (unknown, sorted(pages)))
        pages.update({name: int(n) for name, n in (pool_blocks or {}).items()})
        self.pages_per_seq = int(pages_per_seq)
        # entries of a sequence's table, by pool
        self._table_widths = [pool.table_width(self.pages_per_seq)
                              for pool in page_pools]
        self.max_queue_depth = int(max_queue_depth)
        # feature knobs: explicit constructor args win, else the env
        # (PADDLE_TPU_PREFIX_CACHE / PADDLE_TPU_SPEC_K /
        # PADDLE_TPU_KV_DTYPE, read here — at call time — never at
        # import). spec_k is folded into the verify Program as a
        # static attr: one extra fixed signature, zero recompiles
        # however the scheduler batches. kv_dtype sets the arena
        # storage dtype (fp32 default = bit-identical to the
        # unquantized engine; int8/fp8 halve-to-quarter bytes/token,
        # which is more resident sequences per chip at equal HBM).
        self.prefix_cache_on = prefix_cache_enabled(prefix_cache)
        if self.prefix_cache_on and not spec.shares_frozen_pages():
            # a spec with a windowed or a selected cache kind, and the
            # parallel block: their logits with shared pages are held
            # to no reference yet; one that keeps a state has no page of
            # it to share (LMSpec.shares_frozen_pages, .refusal)
            raise NotImplementedError(
                "block=%r runs without the prefix cache: %s"
                % (spec.block, spec.refusal('prefix_cache')))
        self.spec_k = spec_k_from_env(spec_k)
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        self.kv_bytes_per_token = kv_bytes_per_token(spec, self.kv_dtype)
        self._kind_bytes = kv_bytes_per_kind(spec, self.kv_dtype)
        # by kind: a row's own bytes in one layer (what must be read)
        # and, for each cap on the positions a step reads of a sequence
        # (0: all), the layers that have it
        self._kind_reads = [
            (k.name, k.width * kv_itemsize(self.kv_dtype),
             sorted(collections.Counter(k.reads).items()))
            for k in spec.cache_kinds()]
        # per cap on the positions a layer's attention weighs for one
        # query (0: all), the layers that have it: a layer's is that of
        # the kind it attends over, the first it is in
        caps = {}
        for k in spec.cache_kinds():
            for layer, cap in zip(k.layers, k.reads):
                caps.setdefault(layer, cap)
        self._attn_caps = sorted(collections.Counter(caps.values()).items())
        self.draft = draft if draft is not None else \
            (NgramDraft() if self.spec_k > 0 else None)
        self._progs = build_lm_programs(spec, self.max_batch,
                                        self.block_size, pages,
                                        self.pages_per_seq,
                                        spec_k=self.spec_k,
                                        kv_dtype=self.kv_dtype)
        # static IR verification of all three programs before anything
        # compiles (default warn; PADDLE_TPU_VERIFY=strict refuses a
        # broken graph at construction, not mid-traffic)
        from ... import analysis as _analysis
        _analysis.startup_verify(self._progs.startup,
                                 label='decode_startup')
        _analysis.startup_verify(
            self._progs.prefill,
            fetch_names=[self._progs.prefill_fetch],
            label='decode_prefill')
        _analysis.startup_verify(
            self._progs.decode,
            fetch_names=[self._progs.decode_fetch],
            label='decode_step')
        if self._progs.verify is not None:
            _analysis.startup_verify(
                self._progs.verify,
                fetch_names=[self._progs.verify_fetch],
                label='decode_spec_verify')
        # the XLA modules' names: jit_decode_step, jit_spec_verify and
        # (set per dispatch, _run_prefill) jit_prefill_<bucket>
        self._progs.decode.name = 'decode_step'
        if self._progs.verify is not None:
            self._progs.verify.name = 'spec_verify'
        self.capacity = self._progs.capacity
        self.max_prompt_len = int(max_prompt_len) if max_prompt_len \
            else self.capacity - 1
        # a prefix longer than the top bucket is prefilled in chunks of
        # it; ``min_prompt_bucket`` drops the rungs below it (fewer
        # programs to compile, a short tail padded further)
        self.prefill_chunk = min(int(prefill_chunk or self.max_prompt_len),
                                 self.max_prompt_len)
        self.prompt_buckets = pow2_ladder(
            self.prefill_chunk,
            min(int(min_prompt_bucket), self.prefill_chunk))

        self._scope = Scope()
        self._exe = Executor(place if place is not None else TPUPlace(0))
        # the parameters held with their last two axes swapped, and the
        # swap on the way in (load_weights), which donates what it is given
        self._transposed = held_transposed(spec)
        self._swap_in = jax.jit(_swapped, donate_argnums=0)
        # the form a step's attention pairs run in, for the counters
        from ...ops.pallas.paged_attention import pairs_form
        from ...quant.core import kv_quantized
        platform = self._exe.place.jax_device().platform
        self._attn_form = pairs_form(platform, bool(spec.latent),
                                     kv_quantized(self.kv_dtype))
        # whether a selection's counting runs in the kernel, over the
        # live columns (ops/latent_moe_ops.py::select_topk), likewise
        self._selects_by_kernel = platform == 'tpu'
        with scope_guard(self._scope):
            self._exe.run(program=self._progs.startup)
        if weights:
            self.load_weights(weights)

        # a pool a page space; the first is ``self.pool``. A pool with a
        # lifetime keeps a window behind a program's first row, and a
        # program writes a prefill chunk at most
        self.pools = [
            KVPool(pages[pool.name], self.block_size,
                   kind=(pool.name or 'full') if len(page_pools) > 1
                   else None, keep=pool.keeps, ahead=self.prefill_chunk,
                   whole=pool.per_sequence)
            for pool in page_pools]
        self.pool = self.pools[0]
        self._trims = any(pool.keep for pool in self.pools)
        # the layers that keep a state a sequence (0: none): what a
        # step's state update and a chunk's scan are counted by
        self._state_layers = max(
            [len(k.layers) for k in spec.cache_kinds() if k.per_seq] or [0])
        # the expert layers whose experts sit inside a latent: every row
        # of a program takes their projection in and out (0: none)
        self._latent_layers = len(spec.layers_of(MOE)) \
            if spec.moe_latent else 0
        if _obs.enabled():
            _obs.set_gauge('decode.kv_bytes_per_token',
                           self.kv_bytes_per_token,
                           kv_dtype=self.kv_dtype)
            for kind, n in self._kind_bytes.items():
                _obs.set_gauge('decode.kv_bytes_per_token', n,
                               kv_dtype=self.kv_dtype, kind=kind)
        self.prefix_cache = PrefixCache(self.pool) \
            if self.prefix_cache_on else None
        self._sched = Scheduler(self.pools, self.max_batch,
                                cache=self.prefix_cache)
        # serializes arena access between the worker's executor
        # dispatches (which donate the arena buffers) and out-of-band
        # page readers/writers (KV handoff export/install) — a page
        # read racing a donating dispatch would observe invalidated
        # buffers, a page write racing the scope writeback would be
        # silently clobbered
        self._arena_mu = threading.Lock()
        # host-staging buffers for page export: one reusable buffer per
        # arena name (covers every layer at that name's dtype), so a
        # handoff serializes through ONE device transfer per arena and
        # zero fresh host allocations after the first export at a given
        # page count (serving/handoff.py's fast-path contract)
        self._staging = {}
        self._staging_allocs = 0
        self._mu = threading.Condition(threading.Lock())
        self._done_cv = threading.Condition(threading.Lock())
        self._unfinished = 0
        self._ids = itertools.count(1)
        self._closed = False
        self._draining = False
        self._started = False
        self._warmed = False
        self._broken = None
        self._thread = None
        self._health_name = None
        self._step_no = 0
        self._step_stats = None
        # the newest decode step enqueued whose tokens are unfetched
        # (None at depth 0) and the instant the last step's arrived
        self._ahead = None
        self._t_arrival = 0.0
        # with observe on: the worker's seconds by state; (instant, the
        # clock then) from which no program has been outstanding on the
        # device, None while one is; the token gaps of one emit pass,
        # [count, seconds by state]
        self._clock = _obs.StateClock(_STATES)
        self._empty_since = None
        self._gaps = [0, *_NO_TIME]
        self._merge = jax.jit(_merge_tokens)
        self._prefill_stats = []    # (device MoeStats, program rows) a chunk
        self.warmup_signatures = 0
        self.warmup_total_seconds = 0.0

    # ----------------------------------------------------------- weights
    def load_weights(self, weights):
        """Install a {param name: array} dict (names per
        model.DecodePrograms.param_names), each array in its parameter's
        declared layout (``model.block_param_shapes``). Each parameter
        keeps the dtype it was declared with; a jax array is cast on its
        device and never copied through the host, and is the engine's
        from here on (the programs donate what the scope holds). A
        parameter held transposed (``model.held_transposed``) has its
        last two axes swapped on the device, once, in a call that donates
        the array it was given: no second copy of it stays behind."""
        import jax
        import jax.numpy as jnp
        unknown = sorted(set(weights) - set(self._progs.param_names))
        if unknown:
            raise ValueError('unknown param names %s (expected a subset '
                             'of %s)' % (unknown, self._progs.param_names))
        for name, arr in weights.items():
            dtype = self._scope.get(name).dtype
            if isinstance(arr, jax.Array):
                arr = arr.astype(dtype)
            else:
                arr = jnp.asarray(np.asarray(arr), dtype)
            if name in self._transposed:
                arr = self._swap_in(arr)
            self._scope.set(name, arr)

    def export_weights(self):
        """{param name: numpy array} in the declared layout (a parameter
        held transposed as a view with its axes swapped back)."""
        return {n: np.swapaxes(self._scope.numpy(n), -1, -2)
                if n in self._transposed else self._scope.numpy(n)
                for n in self._progs.param_names}

    def device_weights(self):
        """{param name: the parameter on its device, in the declared
        layout}. A parameter held as declared is the array the programs
        read, where it lives, and nothing is copied; one held transposed
        (``model.held_transposed``: the latent blocks' ``q_b`` and
        ``idx_q``) is swapped back into a new array at each call, the
        only arrays this copies, so keep the dict no longer than it is
        read. The arrays are replaced, not written, by ``load_weights``."""
        return {n: _swapped(self._scope.get(n))
                if n in self._transposed else self._scope.get(n)
                for n in self._progs.param_names}

    # ------------------------------------------------------------ intake
    def submit(self, prompt_ids, max_new_tokens=16, temperature=0.0,
               seed=0, eos_id=None, ctx=None, deadline_s=None,
               tenant=None, priority=None):
        """Enqueue one generation request; returns a GenerationStream.
        Raises QueueFullError past max_queue_depth, EngineClosedError
        after shutdown, ValueError for prompts the page budget can
        never hold. ``ctx`` carries an upstream trace context; when
        absent one is created here (route 'decode', sampling per
        PADDLE_TPU_TRACE_SAMPLE) — sampled requests record queue-wait/
        prefill spans plus a per-token event timeline. ``tenant`` /
        ``priority`` (serving.tenancy) make the request a scheduling
        citizen of its class: admission order, preemption victim
        choice, and prefix-cache eviction all key off it; None means
        'standard' (today's behavior exactly)."""
        t_sub0 = time.perf_counter()
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        max_new = int(max_new_tokens)
        if not prompt:
            raise ValueError('submit: empty prompt')
        if max_new < 1:
            raise ValueError('submit: max_new_tokens must be >= 1')
        if len(prompt) > self.max_prompt_len:
            raise ValueError('prompt of %d tokens exceeds max_prompt_len'
                             '=%d' % (len(prompt), self.max_prompt_len))
        total = len(prompt) + max_new
        if total > self.capacity:
            raise ValueError(
                'prompt+max_new_tokens=%d exceeds per-sequence capacity '
                '%d (pages_per_seq=%d x block_size=%d)'
                % (total, self.capacity, self.pages_per_seq,
                   self.block_size))
        for pool in self.pools:
            if pool.span_pages(total) > pool.num_blocks:
                raise ValueError(
                    'request needs %d KV pages but the pool only has %d'
                    % (pool.span_pages(total), pool.num_blocks))
        with self._mu:
            if self._closed:
                raise EngineClosedError('DecodeEngine is shut down')
            if self._broken is not None:
                raise EngineClosedError(
                    'DecodeEngine worker died: %r' % self._broken)
            waiting, _ = self._sched.counts()
            if waiting >= self.max_queue_depth:
                _obs.inc('decode.rejected_total', reason='queue_full')
                _obs.flight_event('decode_rejected', reason='queue_full',
                                  queue_depth=waiting)
                raise QueueFullError(
                    'decode queue full (%d waiting >= max_queue_depth='
                    '%d)' % (waiting, self.max_queue_depth))
            if ctx is None:
                ctx = _reqtrace.new_context('decode',
                                            deadline_s=deadline_s)
            seq = Sequence(next(self._ids), prompt, max_new, temperature,
                           seed, eos_id, ctx=ctx, tenant=tenant,
                           priority=priority)
            if _obs.enabled():
                # before the worker can see the request
                seq.clk_submit = self._clock.at(seq.t_submit)
            with self._done_cv:
                self._unfinished += 1
            self._sched.add(seq)
            self._mu.notify_all()
        if ctx.sampled:
            ctx.stage('submit', t_sub0, time.perf_counter(),
                      prompt_tokens=len(prompt))
            ctx.flow_begin('decode_request')
        _obs.inc('decode.requests_total')
        return seq.stream

    def generate(self, prompt_ids, **kwargs):
        """submit() + wait: returns the generated token list."""
        timeout = kwargs.pop('timeout', None)
        return self.submit(prompt_ids, **kwargs).result(timeout)

    @property
    def resident_seqs_peak(self):
        """Most sequences ever concurrently RUNNING (page-resident) —
        the capacity number the quantized-KV ablation measures."""
        return self._sched.peak_running

    # ------------------------------------------------------- phase load
    def queue_depth(self):
        """Waiting requests — the router's least-loaded signal (same
        shape as ServingEngine.queue_depth())."""
        waiting, _ = self._sched.counts()
        return waiting

    def free_pages(self):
        """Free KV pages right now — the decode-phase admission signal
        (a decode replica is HBM-bound: pages, not FLOPs, are what it
        runs out of)."""
        return self.pool.free_blocks()

    def free_slots(self):
        """Open decode-batch slots (max_batch - running)."""
        return self._sched.free_slots()

    def decode_load(self):
        """(free_pages, free_slots, waiting) — the tuple the phase
        router ranks decode replicas by."""
        waiting, _ = self._sched.counts()
        return self.pool.free_blocks(), self._sched.free_slots(), waiting

    # -------------------------------------------------- KV page handoff
    def kv_geometry(self):
        """The arena contract a KV handoff packet must match exactly:
        geometry (layers/heads/head dims/block size) and storage dtype.
        serving/handoff.py refuses to install a packet whose geometry
        or dtype differs — a cross-dtype mismatch raises instead of
        silently dequantizing."""
        s = self.spec
        self._per_head_cache('kv_geometry')
        return {
            'n_layer': s.n_layer, 'n_head': s.n_head,
            'n_kv_head': s.n_kv_head,
            'd_key': s.d_key, 'd_value': s.d_value,
            'block_size': self.block_size, 'kv_dtype': self.kv_dtype,
            'arena_names': tuple(self._progs.arena_names),
        }

    def _per_head_cache(self, what):
        """The page handoff ships K and V rows of ``n_kv_head`` heads;
        a block that caches anything else has no packet format yet."""
        if not self.spec.per_head_cache() or len(self.pools) > 1:
            from ..handoff import CacheKindError
            raise CacheKindError(
                '%s: block=%r caches %s, not per-head K/V rows under one '
                'block table: the page handoff has no format for it'
                % (what, self.spec.block,
                   ', '.join(k.name for k in self.spec.cache_kinds())))

    def arena_specs(self):
        """{arena name: logical PartitionSpec or None} of the live
        arena arrays — what export stamps into the packet header.
        None (single-device sharding) serializes as replicated; a
        NamedSharding records its logical axis names only, never
        device positions."""
        with self._arena_mu:
            out = {}
            for name in self._progs.arena_names:
                sharding = getattr(self._scope.get(name),
                                   'sharding', None)
                out[name] = getattr(sharding, 'spec', None)
            return out

    def _page_rung(self, n):
        """Pad a page-group size up to its pow2 rung, capped at
        pages_per_seq — the largest shape warmup() pre-traces — so
        page reads/writes cycle through a SMALL fixed set of jax
        shapes instead of compiling one gather/scatter per distinct
        handoff size (which would stall decode steps behind the arena
        lock). Groups larger than pages_per_seq are chunked by
        read_pages/write_pages, never padded to an unwarmed shape."""
        r = 1
        while r < n:
            r *= 2
        return max(1, min(r, self.pages_per_seq))

    def read_pages(self, page_ids):
        """Read the frozen pages ``page_ids`` out of every arena:
        {arena name: host array [L, n_pages, ...]}. Each gather lands
        in the reused per-arena staging buffer (ONE device gather +
        transfer per arena per pages_per_seq chunk, never a per-page
        round trip) and is copied out under the arena lock, so the
        returned arrays are caller-owned — concurrent read_pages
        calls (thread-pooled handoff exports) cannot overwrite each
        other. Caller must hold references (pool refcounts) on the
        pages so they cannot be reallocated mid-read."""
        import jax
        import jax.numpy as jnp
        self._per_head_cache('read_pages')
        n = len(page_ids)
        pps = self.pages_per_seq
        # oversized groups walk warmed rungs chunk by chunk instead of
        # padding the gather to an untraced (compile-stalling) shape
        chunks = [list(page_ids[i:i + pps])
                  for i in range(0, n, pps)] or [[]]
        out = {}
        with self._arena_mu:
            self._settle()
            for name in self._progs.arena_names:
                arr = self._scope.get(name)
                dest = None
                done = 0
                for chunk in chunks:
                    c = len(chunk)
                    rung = self._page_rung(c)
                    # pad the gather to the rung with page 0
                    # (mode='clip' keeps it in bounds either way);
                    # pad rows are sliced off on the host
                    ids = np.zeros((rung,), dtype='int32')
                    ids[:c] = chunk
                    # one gather on device, one transfer to host
                    host = np.asarray(jax.device_get(
                        jnp.take(arr, ids, axis=1, mode='clip')))
                    buf = self._staging.get(name)
                    if buf is None or buf.shape[1] < rung or \
                            buf.dtype != host.dtype or \
                            buf.shape[2:] != host.shape[2:]:
                        shape = (host.shape[0], pps) + host.shape[2:]
                        buf = np.empty(shape, dtype=host.dtype)
                        self._staging[name] = buf
                        self._staging_allocs += 1
                    np.copyto(buf[:, :c], host[:, :c])
                    if dest is None:
                        dest = np.empty(
                            (host.shape[0], n) + host.shape[2:],
                            dtype=host.dtype)
                    dest[:, done:done + c] = buf[:, :c]
                    done += c
                out[name] = dest
        return out

    def write_pages(self, page_ids, arrays):
        """Install page payloads into the arenas at ``page_ids``:
        ``arrays`` maps arena name -> [L, n_pages, ...] host data (the
        other half of read_pages). One device-side scatter per arena
        per pages_per_seq chunk, under the arena lock — the write
        happens between executor dispatches, so no new XLA *executor*
        signature is ever created (the zero-recompile invariant holds
        on a replica receiving handoffs); the pow2 rung padding (pad
        indexes scatter with mode='drop') keeps the jax-level shape
        set small, warmable, and never larger than warmup traced.
        Pages must be caller-owned (freshly alloc'd)."""
        import jax.numpy as jnp
        self._per_head_cache('write_pages')
        n = len(page_ids)
        if not n:
            return
        pps = self.pages_per_seq
        with self._arena_mu:
            self._settle()
            for name in self._progs.arena_names:
                if name not in arrays:
                    raise KeyError('write_pages: missing arena %r'
                                   % name)
                arr = self._scope.get(name)
                src = np.asarray(arrays[name], dtype='float32')
                for start in range(0, n, pps):
                    c = min(pps, n - start)
                    rung = self._page_rung(c)
                    ids_np = np.full((rung,), self.num_blocks,
                                     dtype='int32')
                    ids_np[:c] = list(page_ids[start:start + c])
                    data = np.zeros(
                        (arr.shape[0], rung) + arr.shape[2:],
                        dtype='float32')
                    data[:, :c] = src[:, start:start + c]
                    payload = jnp.asarray(data).astype(arr.dtype)
                    arr = arr.at[:, jnp.asarray(ids_np)].set(
                        payload, mode='drop')
                    self._scope.set(name, arr)

    def _settle(self):
        """Wait until the decode step in flight, if any, has ended on
        the device. The caller holds the arena lock, so the worker
        enqueues nothing behind it meanwhile."""
        step = self._ahead
        if step is not None:
            step.tokens.block_until_ready()

    # ---------------------------------------------------------- lifecycle
    def ready(self):
        return bool(self._started and self._warmed and not self._closed
                    and self._broken is None)

    def start(self):
        with self._mu:
            if self._closed:
                raise EngineClosedError('DecodeEngine is shut down')
            if self._started:
                return self
            self._started = True
        self._thread = threading.Thread(
            target=self._worker, name='paddle_tpu_decode_worker',
            daemon=True)
        self._thread.start()
        self._health_name = 'decode.engine%d' % next(_ENGINE_IDS)
        _obs.register_health_check(self._health_name, self._ready_check,
                                   readiness_only=True)
        return self

    def _ready_check(self):
        if self.ready():
            return True, None
        if self._broken is not None:
            return False, 'worker died: %r' % self._broken
        if not self._warmed:
            return False, 'not warmed up'
        return False, 'shutting down' if self._closed else 'not started'

    def warmup(self):
        """AOT-compile every signature live traffic can produce: one
        prefill per prompt bucket, the single decode-step key with the
        token merge of a step run ahead, and — with speculation on —
        the single spec-verify key. Warmup feeds
        point every block-table entry past the pool (all writes drop),
        so device state is untouched. Returns the signature count."""
        t_all = time.perf_counter()
        for b in self.prompt_buckets:
            t0 = time.perf_counter()
            self._run_prefill(*self._warm_args(b))
            _obs.record('decode.warmup_seconds',
                        time.perf_counter() - t0, kind='prefill', bucket=b)
        t0 = time.perf_counter()
        args = self._warm_args('decode')
        out = self._dispatch_decode(*args)
        # what a step run ahead of a changed batch takes its tokens by
        np.asarray(self._merge(out, np.zeros((self.max_batch,), 'int32'),
                               args[0]))
        _obs.record('decode.warmup_seconds', time.perf_counter() - t0,
                    kind='decode', bucket='')
        self.warmup_signatures = len(self.prompt_buckets) + 1
        if self.spec_k > 0:
            t0 = time.perf_counter()
            np.asarray(self._dispatch_verify(*self._warm_args('verify')))
            _obs.record('decode.warmup_seconds',
                        time.perf_counter() - t0, kind='spec_verify',
                        bucket='')
            self.warmup_signatures += 1
        if self.prefix_cache is not None and self.spec.per_head_cache():
            # pre-trace the KV-handoff page gather/scatter rungs so a
            # live handoff never compiles behind the arena lock (the
            # jax-level twin of the executor-signature warmup above);
            # writes use all-dropped indexes, reads page 0 — device
            # state untouched
            t0 = time.perf_counter()
            rung = 1
            while rung <= self.pages_per_seq:
                self.read_pages([0] * rung)
                self.write_pages(
                    [self.num_blocks] * rung,
                    {name: np.zeros(
                        (self._scope.get(name).shape[0], rung)
                        + tuple(self._scope.get(name).shape[2:]),
                        'float32')
                     for name in self._progs.arena_names})
                rung *= 2
            _obs.record('decode.warmup_seconds',
                        time.perf_counter() - t0, kind='handoff',
                        bucket='')
        self._warmed = True
        self.warmup_total_seconds = time.perf_counter() - t_all
        return self.warmup_signatures

    def drain(self, timeout=None):
        deadline = None if timeout is None else \
            time.perf_counter() + timeout
        with self._done_cv:
            # a row that ended on EOS leaves its last step in flight
            while self._unfinished > 0 or self._ahead is not None:
                wait = None if deadline is None else \
                    deadline - time.perf_counter()
                if wait is not None and wait <= 0:
                    return False
                self._done_cv.wait(wait)
        return True

    def shutdown(self, drain=True, timeout=None):
        """Stop accepting requests; drain=True finishes everything
        already accepted, drain=False fails queued-and-running requests
        with EngineClosedError."""
        with self._mu:
            if self._closed and self._thread is None:
                return
            self._closed = True
            self._draining = bool(drain)
            self._mu.notify_all()
        if self._health_name is not None:
            _obs.unregister_health_check(self._health_name)
            self._health_name = None
        if drain and self._started and self._broken is None:
            self.drain(timeout)
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        if not drain or not self._started:
            self._fail_remaining(EngineClosedError(
                'DecodeEngine shut down without draining'))
        if self.prefix_cache is not None:
            # drop the cache's page references so the pool drains to
            # its initial free count (the cache dies with the engine)
            self.prefix_cache.clear()

    def close(self):
        self.shutdown(drain=True)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)
        return False

    def _request_done(self, n=1):
        with self._done_cv:
            self._unfinished -= n
            if self._unfinished <= 0:
                self._done_cv.notify_all()

    def _fail_remaining(self, exc):
        n = self._sched.fail_all(exc)
        if n:
            self._request_done(n)

    # ------------------------------------------------------------ worker
    def _idle_wait(self, timeout=None):
        """One wait on the engine's condition (held by the caller):
        nothing to run, or head-of-line blocked on pages."""
        with _obs.span('decode.idle', record=_WORKER_SECONDS,
                       labels=_IDLE, clock=self._clock):
            self._mu.wait(timeout)

    def _worker(self):
        """Admit and prefill what fits, then one decode program a
        ``decode.step`` span. With a step in flight (the module
        docstring has the order and the cases that empty the pipeline)
        nothing is admitted: where that step was left in flight no
        request was admittable, and the next step's end looks again."""
        self._empty_since = None
        if _obs.enabled():
            self._device_emptied(time.perf_counter())
        try:
            while True:
                with self._mu:
                    while not self._closed and \
                            self._sched.counts() == (0, 0):
                        self._idle_wait()
                    waiting, running = self._sched.counts()
                    if self._closed and (
                            not self._draining or
                            (waiting == 0 and running == 0)):
                        # shutdown(drain=False): its rows are failed, the
                        # device is let finish what it was given
                        self._settle()
                        self._ahead = None
                        return
                if self._ahead is None:
                    self._admit()
                if self._sched.running:
                    self._step_no += 1
                    with _obs.span('decode.step', record=_WORKER_SECONDS,
                                   labels=_STEP, clock=self._clock,
                                   step=self._step_no):
                        self._decode_step()
                elif self._sched.waiting:
                    # head-of-line blocked on pages with nothing running
                    # to free them — only another submit/shutdown can
                    # change that; avoid a hot spin
                    with self._mu:
                        if not self._closed:
                            self._idle_wait(0.05)
        except BaseException as e:  # fail fast, loudly, and visibly
            self._ahead = None
            self._broken = e
            _obs.inc('decode.worker_errors_total')
            _obs.flight_event('decode_worker_died', error=repr(e))
            self._fail_remaining(e)
        finally:
            # the worker's last stretch with nothing on the device ends
            # with the worker
            self._device_taken()

    def _admit(self):
        while True:
            with _obs.span('decode.admit', record=_WORKER_SECONDS,
                           labels=_ADMIT, clock=self._clock):
                seq = self._sched.pop_admittable()
                if seq is None:
                    return
                _obs.record('decode.queue_seconds',
                            seq.t_admit - seq.t_submit,
                            exemplar=seq.ctx.exemplar() if seq.ctx
                            else None)
                if _obs.enabled() and seq.clk_submit is not None:
                    # what the worker did while the request waited
                    self._by_state(
                        _obs.inc, 'decode.queue_wait_seconds',
                        self._clock.at(seq.t_admit), seq.clk_submit)
                if seq.ctx is not None and seq.ctx.sampled:
                    # began on the submit thread, ends here on the worker
                    seq.ctx.stage('queue_wait', seq.t_submit, seq.t_admit)
                    seq.ctx.flow_step()
            with _obs.span('decode.prefill', record=_WORKER_SECONDS,
                           labels=_PREFILL, clock=self._clock,
                           request_id=seq.request_id):
                self._prefill(seq)

    # ------------------------------------------------- the worker's clock
    @staticmethod
    def _by_state(feed, name, clk, clk0):
        """An interval between two readings of the worker's clock into
        the series ``name{state}`` (``feed``: ``observe.inc`` or
        ``observe.record``), the seconds of each state in it."""
        for state, t, t0 in zip(_STATES, clk, clk0):
            if t != t0:
                feed(name, t - t0, state=state)

    def _clock_at(self, now):
        """The worker's clock at ``now`` for an emit pass, None with
        observe off."""
        return self._clock.at(now) if _obs.enabled() else None

    def _count_token_gaps(self):
        """The token gaps ``_emit`` summed over one emit pass, into
        their counters."""
        gaps = self._gaps
        if gaps[0]:
            _obs.inc('decode.token_gaps_total', gaps[0])
            self._by_state(_obs.inc, 'decode.token_gap_seconds', gaps[1:],
                           _NO_TIME)
            gaps[:] = (0,) + _NO_TIME

    def _device_emptied(self, now):
        """``now`` is the arrival that left no program outstanding on
        the device (observe is on)."""
        self._empty_since = (now, self._clock.at(now))

    def _device_taken(self):
        """A program's enqueue has just returned: the stretch with
        none outstanding, if one was open, ends here, split by what the
        worker did in it and kept as a span of the ring."""
        since = self._empty_since
        if since is None:
            return
        self._empty_since = None
        if not _obs.enabled():
            return
        now = time.perf_counter()
        self._by_state(_obs.record, 'decode.device_empty_seconds',
                       self._clock.at(now), since[1])
        _obs.spans().add_span('decode.device_empty', since[0], now)

    # ----------------------------------------------------------- dispatch
    def _prefill_feed(self, ids, length, cached, table, temp, seed, *more):
        """A prefill's feeds; ``more`` the tables of the page pools past
        the first, in order."""
        feed = {'pf_ids': ids,
                'pf_len': np.asarray([length], 'int32'),
                'pf_cached': np.asarray([cached], 'int32'),
                'pf_table': table,
                'pf_temp': np.asarray([temp], 'float32'),
                'pf_seed': np.asarray([seed], 'int32')}
        for suffix, its in zip(self._more_tables, more):
            feed['pf_table' + suffix] = its
        return feed

    def _step_feed(self, prefix, tokens, lens, tables, temps, seeds, *more):
        """The decode step's ('dec') or the spec-verify step's ('sv')
        five feeds, and ``more``: the tables of the page pools past the
        first, in order."""
        feed = {prefix + '_tokens': tokens, prefix + '_lens': lens,
                prefix + '_tables': tables, prefix + '_temps': temps,
                prefix + '_seeds': seeds}
        for suffix, its in zip(self._more_tables, more):
            feed[prefix + '_tables' + suffix] = its
        return feed

    def _warm_args(self, which):
        """What ``warmup()`` dispatches ``which`` ('decode', 'verify' or
        a prefill bucket's size) with: the shapes live traffic uses,
        every block-table entry past the pool so nothing is written."""
        mb = self.max_batch
        rows = 1 if which not in ('decode', 'verify') else mb
        first, *more = [np.full((rows, width), pool.num_blocks, 'int32')
                        for pool, width in zip(self.pools,
                                               self._table_widths)]
        if which in ('decode', 'verify'):
            toks = (mb,) if which == 'decode' else (mb, self.spec_k + 1)
            return (np.zeros(toks, 'int64'), np.zeros((mb,), 'int32'),
                    first, np.zeros((mb,), 'float32'),
                    np.zeros((mb,), 'int32'), *more)
        return (np.zeros((1, int(which)), 'int64'), 1, 0, first, 0.0, 0,
                *more)

    def _run_prefill(self, ids, length, cached, table, temp, seed, *more,
                     wait=True):
        """One prefill dispatch. ``wait=False`` (a chunk that is not the
        prefix's last) leaves the sampled token on the device unread, so
        the next chunk is enqueued behind it without a round trip; the
        last one's is read after the enqueue has returned
        (``Executor.fetch``), as a step's is. A
        block that keeps router statistics hands them back beside the
        token; they are left in ``_prefill_stats`` (on the device, for a
        chunk that is not waited for) for the prefill's emit."""
        # one Program, one XLA module per bucket: the name is read when a
        # bucket's signature compiles
        self._progs.prefill.name = 'prefill_%d' % ids.shape[1]
        fetch = [self._progs.prefill_fetch]
        if self._progs.prefill_stats_fetch is not None:
            fetch.append(self._progs.prefill_stats_fetch)
        with self._arena_mu, scope_guard(self._scope):
            out = self._exe.run(
                program=self._progs.prefill,
                feed=self._prefill_feed(ids, length, cached, table, temp,
                                        seed, *more),
                fetch_list=fetch, return_numpy=False)
        if self._empty_since is not None:
            self._device_taken()
        if len(out) > 1:
            self._prefill_stats.append((out[1], ids.shape[1]))
        if not wait:
            return None
        token = int(self._exe.fetch(out[:1])[0].reshape(-1)[0])
        if _obs.enabled():
            self._device_emptied(time.perf_counter())
        return token

    def _dispatch_verify(self, *feeds):
        """Enqueue one spec-verify step; its fetch stays on the device."""
        with self._arena_mu, scope_guard(self._scope):
            return self._exe.run(
                program=self._progs.verify,
                feed=self._step_feed('sv', *feeds),
                fetch_list=[self._progs.verify_fetch],
                return_numpy=False)[0]

    def _dispatch_decode(self, *feeds):
        """Enqueue one decode step (``_step_feed``'s arguments); its
        fetch stays on the device. A
        block that keeps router statistics hands them back beside the
        tokens; they are left in ``_step_stats`` for the step's emit."""
        fetch = [self._progs.decode_fetch]
        if self._progs.stats_fetch is not None:
            fetch.append(self._progs.stats_fetch)
        with self._arena_mu, scope_guard(self._scope):
            out = self._exe.run(
                program=self._progs.decode,
                feed=self._step_feed('dec', *feeds),
                fetch_list=fetch, return_numpy=False)
        self._step_stats = out[1] if len(out) > 1 else None
        return out[0]

    def trace_program(self, which):
        """jax's ``Traced`` (``.jaxpr``, ``.lower().compile()``) for one
        of this engine's programs as the executor jits it — same step
        function, same module name, the scope donated — at the feeds
        ``warmup()`` compiles: ``which`` is ``'decode'``, ``'verify'``
        or a prefill bucket's size. For reading what the compiler made
        of a program (tests, chip_smoke.py); nothing is dispatched.
        Where a compile cache is armed, compiling it after ``warmup()``
        is a cache hit."""
        import jax
        args = self._warm_args(which)
        if which == 'decode':
            prog, fetch = self._progs.decode, self._progs.decode_fetch
            feed = self._step_feed('dec', *args)
        elif which == 'verify':
            prog, fetch = self._progs.verify, self._progs.verify_fetch
            feed = self._step_feed('sv', *args)
        else:
            prog, fetch = self._progs.prefill, self._progs.prefill_fetch
            prog.name = 'prefill_%d' % which
            feed = self._prefill_feed(*args)
        with self._arena_mu:
            step_fn, scope_vals, feed_vals = self._exe.compile_step(
                program=prog, feed=feed, fetch_list=[fetch],
                scope=self._scope)
            return jax.jit(step_fn, donate_argnums=(0,)).trace(
                scope_vals, feed_vals, np.int32(0))

    def _bucket(self, n):
        for b in self.prompt_buckets:
            if n <= b:
                return b
        raise ValueError('prefix of %d tokens exceeds the top prompt '
                         'bucket %d' % (n, self.prompt_buckets[-1]))

    def _table_rows(self, seq):
        """``seq``'s block tables as the programs take them, one a page
        pool: an entry it does not own (past its pages, or given back
        behind a window) points past the pool; of a pool of whole states
        the one entry, its slot."""
        rows = []
        for pool, table, width in zip(self.pools, seq.tables,
                                      self._table_widths):
            row = np.full((width,), pool.num_blocks, 'int32')
            ids = table.block_ids[table.freed:]
            row[table.freed:table.freed + len(ids)] = ids
            rows.append(row)
        return rows

    def _prefill(self, seq):
        """Prefill the uncached suffix of ``seq.prefix()`` — the whole
        prefix on a cache miss, only the tokens past the matched span
        on a hit (the hit's pages are already mapped in the block
        table; the suffix bucket, not the prompt bucket, sets the
        dispatch cost — that is the TTFT win). A suffix longer than the
        top bucket goes in chunks of it, back to back, each one
        dispatch of the bucket's program with ``pf_cached`` at the
        chunk's start; only the last chunk's token is read."""
        with _obs.span('decode.prefill.build'):
            prefix = seq.prefix()
            s = len(prefix)
            cached = seq.cached_len
            top = self.prefill_chunk
            starts = list(range(cached, s, top))
            table, *more = [row[None, :] for row in self._table_rows(seq)]
            # the largest program this prefill runs (its first chunk's):
            # the label of its span, its time and its trace stage
            bucket = self._bucket(min(top, s - cached))
            # each chunk's own count: a trace sets a chunk's attention
            # against the program run that is that chunk
            chunk_pairs = [self._attn_pairs(a, min(a + top, s))
                           if _obs.enabled() else 0 for a in starts]
            pairs = sum(chunk_pairs)
            if _obs.enabled() and self.spec.index_topk:
                for a in starts:
                    # a chunk's rows as its program holds them: one
                    # position more each, none past the piece
                    rows = min(top, s - a)
                    held = np.zeros(self._bucket(rows), 'int64')
                    held[:rows] = np.arange(a + 1, a + rows + 1)
                    self._count_selection_reach(held, 'prefill')
            seq.stream.cached_tokens = cached
            # with layers that keep a state: the (row, layer) steps of
            # the recurrence a span's programs take (what a FLOP count
            # of the chunked scan starts from)
            layers = self._state_layers

            def scanned(rows):
                return {'scan_rows': rows * layers} if layers else {}
        expanded = scan_chunks = 0
        del self._prefill_stats[:]
        with _obs.span('decode.prefill.run', bucket=bucket,
                       chunks=len(starts), cached_tokens=cached,
                       attn_pairs=pairs, request_id=seq.request_id,
                       **scanned(s - cached)):
            t0 = time.perf_counter()
            for start, its_pairs in zip(starts, chunk_pairs):
                piece = prefix[start:start + top]
                rung = self._bucket(len(piece))
                # the chunks whose latent attention runs expanded: the
                # rule the lowering takes its form by
                expanded += any(shape.expands(rung)
                                for shape in self.spec.latent.values())
                ids = np.zeros((1, rung), 'int64')
                ids[0, :len(piece)] = piece
                # a prefix of one chunk is the span above and no more
                chunk_span = _obs.span(
                    'decode.prefill.chunk', bucket=rung, start=start,
                    attn_pairs=its_pairs, request_id=seq.request_id,
                    **scanned(len(piece))) \
                    if len(starts) > 1 else contextlib.nullcontext()
                scan_chunks += layers * -(-rung // self.spec.ssm_chunk)
                if self._trims:
                    # a pool with a lifetime gives back what lies behind
                    # the chunk's first row and takes the chunk's pages
                    # (the last chunk's with the first decode write's);
                    # the others have theirs since admission
                    if not self._sched.grow(seq, min(start + top, s) + 1,
                                            start):
                        # admission took the most a sequence ever holds
                        raise RuntimeError(
                            'prefill of request %r: a page pool ran out '
                            'between chunks' % (seq.request_id,))
                    table, *more = [row[None, :]
                                    for row in self._table_rows(seq)]
                with chunk_span:
                    tok = self._run_prefill(
                        ids, len(piece), start, table, seq.temperature,
                        seq.seed, *more, wait=start == starts[-1])
            t1 = time.perf_counter()
        _obs.record('decode.prefill_seconds', t1 - t0, bucket=bucket)
        # the chunks run back to back and only the last is waited for
        _obs.record('decode.prefill_chunk_seconds', (t1 - t0) / len(starts))
        _obs.inc('decode.prefills_total')
        _obs.inc('decode.prefill_chunks', len(starts))
        _obs.inc('decode.prefill_chunks_expanded', expanded)
        _obs.inc('decode.prompt_tokens_total', s)
        _obs.inc('decode.prefill_attn_pairs', pairs)
        if self._latent_layers:
            _obs.inc('decode.moe_latent_rows_total',
                     (s - cached) * self._latent_layers)
        if self._state_layers:
            # a first chunk starts its slot from zeros; the scan runs
            # in chunks of ``ssm_chunk`` rows of each program's bucket
            _obs.inc('decode.state_resets_total')
            _obs.inc('decode.prefill_scan_chunks_total', scan_chunks)
            _obs.inc('decode.prefill_scan_rows_total',
                     (s - cached) * self._state_layers)
            if seq.preemptions:
                _obs.inc('decode.state_recomputed_tokens_total', s)
        with _obs.span('decode.prefill.emit'):
            if _obs.enabled():
                # every chunk's program ended before the token was read
                for stats, rows in self._prefill_stats:
                    self._record_moe_tiles(np.asarray(stats), rows)
            if cached:
                _obs.flight_event('decode_prefix_hit',
                                  request_id=seq.request_id,
                                  cached_tokens=cached, prefix_tokens=s)
            if seq.ctx is not None and seq.ctx.sampled:
                seq.ctx.stage('prefill', t0, t1, bucket=bucket,
                              prefix_tokens=s, cached_tokens=cached,
                              chunks=len(starts))
            seq.cache_len = s
            self._maybe_publish(seq)
            now = time.perf_counter()
            clk = self._clock_at(now)
            self._emit(seq, tok, now, clk)
            if clk is not None:
                self._count_token_gaps()
            reason = seq.finished()
            if reason:
                self._finish(seq, reason)

    def _attn_pairs(self, start, end):
        """The (query, key at or below it) pairs the attention of a
        prefill of positions ``start .. end - 1`` has to weigh, summed
        over the layers: every key up to its own for a layer that
        reads all, the last ``cap`` for one under a window or a
        selection (``CacheKind.reads``). What a FLOP count of the
        chunks' attention starts from."""
        seen = np.arange(start + 1, end + 1, dtype=np.int64)
        return int(sum(
            n * int((np.minimum(seen, cap) if cap else seen).sum())
            for cap, n in self._attn_caps))

    def _maybe_publish(self, seq):
        """Offer every newly frozen (full) page to the prefix cache.
        Called whenever cache_len may have crossed a page boundary;
        cheap no-op otherwise."""
        if self.prefix_cache is None:
            return
        full = seq.cache_len // self.block_size
        if full > seq.published_pages:
            self.prefix_cache.publish(seq.prefix(), seq.table,
                                      seq.cache_len,
                                      tenant=seq.tenant,
                                      priority=seq.priority)
            seq.published_pages = full

    def _step_feeds(self, batch, tokens_per_row=1):
        """``(lens, tables, temps, seeds)`` of one step over ``batch`` at
        the fixed [max_batch] signature, and after them the tables of
        the page pools past the first; the rows past the batch point
        every page beyond the pool, so their writes drop.
        ``tokens_per_row`` is what the step scores per row (k + 1 under
        speculation), for the counters only."""
        mb = self.max_batch
        lens = np.zeros((mb,), 'int32')
        tables, *more = [np.full((mb, width), pool.num_blocks, 'int32')
                         for pool, width in zip(self.pools,
                                                self._table_widths)]
        temps = np.zeros((mb,), 'float32')
        seeds = np.zeros((mb,), 'int32')
        for i, seq in enumerate(batch):
            lens[i] = seq.position()
            for mine, row in zip([tables] + more, self._table_rows(seq)):
                mine[i] = row
            temps[i] = seq.temperature
            seeds[i] = seq.seed
        if _obs.enabled():
            # the KV positions this step attends over
            _obs.record('decode.step_live_tokens', int(lens.sum()))
            _obs.inc('decode.step_rows', len(batch))
            if self._state_layers:
                # the states this step reads, advances and writes back
                _obs.inc('decode.step_state_rows_total',
                         len(batch) * self._state_layers)
            if self._latent_layers:
                _obs.inc('decode.moe_latent_rows_total',
                         len(batch) * self._latent_layers)
            self._count_attn_pages(lens, len(batch), tokens_per_row)
            window = self.spec.sliding_window
            if window:
                # rows whose sliding layers no longer see their first
                # keys, and the positions such a layer attends over
                _obs.inc('decode.step_window_rows',
                         int((lens[:len(batch)] >= window).sum()))
                _obs.record('decode.step_window_tokens',
                            int(np.minimum(lens, window).sum()))
            self._count_cache_reads(lens[:len(batch)] + 1)
            if self.spec.index_topk:
                self._count_selection_reach(
                    np.where(np.arange(mb) < len(batch), lens + 1, 0),
                    'decode')
        return (lens, tables, temps, seeds, *more)

    def _count_selection_reach(self, held, kind):
        """How far the learned selection's bounds engage in one program
        (``kind``: a decode step, a prefill chunk) whose rows hold
        ``held`` positions, over the layers that score: the positions
        whose index keys are gathered, and the columns a counting pass
        of the top-k runs over beside those the rows' tables address
        (``ops/latent_moe_ops.py::selection_reach``: the functions of
        the lengths that bound the program's loops)."""
        from ...ops.latent_moe_ops import selection_reach
        n = len(self.spec.scoring_layers())
        gathered, counted, addressed = selection_reach(
            held, kind == 'prefill', self.pages_per_seq, self.block_size,
            self.spec.index_topk, self._selects_by_kernel, np)
        _obs.inc('decode.index_positions_gathered', n * gathered, kind=kind)
        _obs.inc('decode.selection_columns_counted', n * counted, kind=kind)
        _obs.inc('decode.selection_columns_addressed', n * addressed,
                 kind=kind)

    def _count_cache_reads(self, seen):
        """What one decode step's attention has to read of the paged
        cache, by kind, from the rows' own lengths (``seen`` [rows]: the
        positions each live row holds, its new token's included) and
        what each kind says a layer reads of them (``CacheKind.reads``:
        the positions a selection keeps, a window, or all), at the row's
        own width; and, where full layers select, how far the selection
        is live. The layers that attend under a selection (every
        full_attention layer) and those that score one
        (``LMSpec.scoring_layers``) are counted apart: under a carried
        selection they are two counts."""
        total = int(seen.sum())
        for kind, row_bytes, caps in self._kind_reads:
            positions = sum(
                n * (int(np.minimum(seen, cap).sum()) if cap else total)
                for cap, n in caps)
            _obs.inc('decode.cache_bytes_read', positions * row_bytes,
                     kind=kind)
        topk = self.spec.index_topk
        if topk:
            n_full = len(self.spec.layers_of(FULL))
            n_scoring = len(self.spec.scoring_layers())
            _obs.inc('decode.sparse_positions_seen', n_full * total)
            _obs.inc('decode.sparse_positions_selected',
                     n_full * int(np.minimum(seen, topk).sum()))
            _obs.inc('decode.index_positions_scored', n_scoring * total)
            _obs.inc('decode.selection_layer_calls', n_scoring,
                     how='scored')
            _obs.inc('decode.selection_layer_calls', n_full - n_scoring,
                     how='carried')
            _obs.inc('decode.sparse_rows', len(seen))
            _obs.inc('decode.sparse_rows_live', int((seen > topk).sum()))

    def _count_attn_pages(self, lens, rows, k1):
        """How far the attention's bounds engage, summed over the
        layers: the step's (row, column block) pairs by the form that
        runs them (``pairs_form``: the kernel on a TPU for a latent
        kind, else the loop), the pages they hold (``held``: the live
        rows' own column blocks, the least a blocked form gathers) and
        the pages the forms gather (``read``: the kernel's pairs as
        they are; the loop's eight an iteration, the fill of a last
        iteration included), all from the functions of the lengths
        that give the program its bounds (ops/pallas/paged_attention.py),
        beside the pages its tables can address."""
        from ...ops.pallas.paged_attention import (
            pages_covered, pages_held, pages_per_block)
        pos = (lens[:, None] + np.arange(k1, dtype='int32')).reshape(-1)
        live = (np.arange(len(pos)) < rows * k1) & (pos < self.capacity)
        hi = np.where(live, pos + 1, 0)
        read = held = 0
        form = self._attn_form
        per = pages_per_block(self.pages_per_seq, self.block_size)
        windows = self.spec.attn_windows()
        for window, layers in collections.Counter(windows).items():
            lo = np.maximum(hi - window, 0) if window else np.zeros_like(hi)
            bounds = (lo, hi, self.pages_per_seq, self.block_size, np)
            mine = layers * int(pages_held(*bounds))
            held += mine
            read += mine if form == 'kernel' \
                else layers * int(pages_covered(*bounds))
        for which in ('kernel', 'loop'):
            _obs.inc('decode.attn_pairs',
                     held // per if which == form else 0, form=which)
        _obs.inc('decode.attn_pages_read', read)
        _obs.inc('decode.attn_pages_held', held)
        _obs.inc('decode.attn_pages_reachable',
                 len(windows) * len(pos) * self.pages_per_seq)

    def _enqueue(self, dispatch, batch, tokens, feeds):
        """Enqueue one step over ``batch`` and start the copy of its
        fetches to the host, so a fetch of a step that has ended does
        not sleep. Its rows are one step further ahead."""
        t0 = time.perf_counter()
        self._step_stats = None
        with _obs.span('decode.step.dispatch',
                       record='decode.step_dispatch_seconds',
                       batch=len(batch), step=self._step_no):
            out = dispatch(tokens, *feeds)
            if self._empty_since is not None:
                self._device_taken()
            step = _Step(batch, out, self._step_stats, t0, self._step_no)
            out.copy_to_host_async()
            if step.stats is not None and _obs.enabled():
                step.stats.copy_to_host_async()   # read where observe is on
        for seq in batch:
            seq.ahead += 1
        return step

    def _fetch(self, step):
        """Block on ``step``'s tokens: ``(tokens on the host, the
        instant they arrived)``. ``decode.step_seconds`` runs from the
        later of the last step's arrival and this step's dispatch to this
        arrival, so the records of consecutive steps tile the worker's
        time in steps and none spans two; the fetch span ends on the
        profiler's clock at the worker's wake-up."""
        with _obs.span('decode.step.fetch',
                       record='decode.step_fetch_seconds', step=step.no):
            out = np.asarray(step.tokens)
        now = time.perf_counter()
        if _obs.enabled() and (self._ahead is step or self._ahead is None):
            self._device_emptied(now)     # no step behind this one
        _obs.record('decode.step_seconds',
                    now - max(self._t_arrival, step.t0))
        self._t_arrival = now
        _obs.record('decode.batch_occupancy',
                    len(step.batch) / float(self.max_batch))
        _obs.inc('decode.steps_total')
        for seq in step.batch:
            seq.ahead -= 1
        if self._ahead is step:
            self._ahead = None
            if self._unfinished <= 0:
                with self._done_cv:
                    self._done_cv.notify_all()
        return out, now

    def _next_rows(self):
        """The running sequences the next decode step holds: those that
        need a token beyond the steps already enqueued for them."""
        return [seq for seq in self._sched.running
                if seq.state is RUNNING and seq.continues()]

    def _next_tokens(self, batch, prev):
        """The next step's ``dec_tokens``: from the host where no row of
        ``batch`` is in flight; else ``prev``'s fetch where it lies on
        the device, as it is if the batch is ``prev``'s, its rows moved
        and the joined rows' tokens merged in if not."""
        host = np.zeros((self.max_batch,), 'int64')
        src = np.full((self.max_batch,), -1, 'int32')
        row = {seq: i for i, seq in enumerate(prev.batch)} if prev else {}
        for i, seq in enumerate(batch):
            if seq.ahead:
                src[i] = row[seq]
            else:
                host[i] = seq.pending_token
        if not (src >= 0).any():
            return host
        if len(batch) == len(prev.batch) and \
                (src[:len(batch)] == np.arange(len(batch))).all():
            return prev.tokens
        return self._merge(prev.tokens, src, host)

    def _stays_in_flight(self):
        """Whether the step just enqueued may stay unfetched while the
        worker builds the next one: some row goes on, nothing else
        wants the device (a request the scheduler would admit), the
        next feeds do not hang on this step's result (speculation) and
        the engine is not being torn down."""
        if self.spec_k > 0 or (self._closed and not self._draining):
            return False
        return bool(self._next_rows()) and not self._sched.admittable()

    def _decode_step(self):
        """One decode program: build and enqueue step n+1, fetch and
        emit step n if it is in flight, then fetch and emit n+1 too
        unless it may stay in flight."""
        if self.spec_k > 0 and self._spec_step():
            return
        prev = self._ahead
        if prev is not None and not all(
                self._sched.grow(seq, seq.position() + 1)
                for seq in self._next_rows()):
            # a preemption comes: the pipeline empties first
            self._emit_step(prev)
            prev = None
        with _obs.span('decode.step.build',
                       record='decode.step_build_seconds'):
            for seq in self._next_rows():
                if seq.state is not RUNNING:
                    continue   # preempted as a victim earlier in this pass
                self._sched.ensure_growth(seq)
            batch = self._next_rows()
            if not batch:
                return
            tokens = self._next_tokens(batch, prev)
            feeds = self._step_feeds(batch)
        step = self._ahead = self._enqueue(self._dispatch_decode, batch,
                                           tokens, feeds)
        if prev is not None:
            _obs.inc('decode.steps_ahead_total')
            self._emit_step(prev)
        if not self._stays_in_flight():
            self._emit_step(step)

    def _emit_step(self, step):
        """Fetch one decode step and hand its tokens out. A row that
        finished on the token of the step before (EOS) is held by this
        step too: its token is dropped."""
        nxt, now = self._fetch(step)
        nxt = nxt.reshape(-1)
        with _obs.span('decode.step.emit',
                       record='decode.step_emit_seconds'):
            if step.stats is not None and _obs.enabled():
                self._record_moe(np.asarray(step.stats), len(step.batch))
            clk = self._clock_at(now)
            for i, seq in enumerate(step.batch):
                if seq.state is not RUNNING:
                    continue
                seq.cache_len += 1
                self._maybe_publish(seq)
                self._emit(seq, int(nxt[i]), now, clk)
                reason = seq.finished()
                if reason:
                    self._finish(seq, reason)
            if clk is not None:
                self._count_token_gaps()

    def _record_moe(self, stats, rows):
        """One decode step's router statistics ([routed layers, 4]: choices
        that landed on an expert held here, rows on the busiest of
        them, experts any row chose, row tiles the routed product ran)
        into the counters the benchmark reads: of rows x
        experts_per_token choices a layer, the local ones; the busiest
        expert's load against the mean, per layer-step; and, where the
        router has identity experts (``moe_held_ops.load_stats``' wider
        form), the choices that were real experts and those that were
        identities, and a (row, layer)'s real experts."""
        held = self.spec.experts_held
        self._record_moe_tiles(stats, self.max_batch)
        _obs.inc('decode.moe_assignments',
                 rows * self.spec.experts_per_token * len(stats))
        _obs.inc('decode.moe_local_assignments', int(stats[:, 0].sum()))
        _obs.inc('decode.moe_experts_touched', int(stats[:, 2].sum()))
        _obs.inc('decode.moe_layer_steps', len(stats))
        for local, busiest in stats[:, :2]:
            if local:
                _obs.record('decode.moe_load_max_over_mean',
                            busiest * held / float(local))
        if stats.shape[1] > 4:
            # a router with identity experts: per layer, the live rows
            # by the real experts each chose (0 .. experts_per_token);
            # the rest of a row's choices computed nothing
            by_real = stats[:, 4:].sum(axis=0)
            real = np.arange(len(by_real))
            _obs.inc('decode.moe_real_assignments',
                     int((by_real * real).sum()))
            _obs.inc('decode.moe_zero_assignments',
                     int((by_real * (len(by_real) - 1 - real)).sum()))
            for n_real, n_rows in zip(real, by_real):
                for _ in range(int(n_rows)):
                    _obs.record('decode.moe_real_experts_per_token',
                                int(n_real))

    def _record_moe_tiles(self, stats, program_rows):
        """The row tiles the routed experts' product ran in one program
        (a decode step or a prefill chunk of ``program_rows`` rows),
        beside what a product over every expert held costs at the same
        tile."""
        from ...ops.moe_held_ops import row_tiles
        _obs.inc('decode.moe_row_tiles_run', int(stats[:, 3].sum()))
        _obs.inc('decode.moe_row_tiles_dense',
                 len(stats) * self.spec.experts_held
                 * row_tiles(program_rows))

    def _spec_step(self):
        """Draft-and-verify decode: the draft proposes up to k tokens
        per running sequence, one fixed-signature ``paged_spec_verify``
        dispatch scores all k+1 positions per row, and the longest
        accepted prefix (plus the target's own bonus token) is emitted
        — up to k+1 tokens per sequence per step, bit-identical to
        one-at-a-time decode because sampling is (seed, position)-
        keyed. Returns False (step not taken) when no sequence has a
        live proposal — the plain decode step is the cheaper warmed
        signature for that case."""
        k = self.spec_k
        with _obs.span('decode.step.build',
                       record='decode.step_build_seconds'):
            pairs = [(s, list(self.draft.propose(s.prefix(), k))[:k])
                     for s in self._sched.running if s.state is RUNNING]
            if not any(d for _, d in pairs):
                return False
            for seq, _ in pairs:
                if seq.state is not RUNNING:
                    continue   # preempted as a victim earlier in this pass
                self._sched.ensure_growth(
                    seq, min(seq.cache_len + k + 1, self.capacity))
            # ensure_growth may have preempted members of this very batch
            pairs = [(s, d) for s, d in pairs if s.state is RUNNING]
            if not pairs:
                return True
            tokens = np.zeros((self.max_batch, k + 1), 'int64')
            drafts = []
            for i, (seq, d) in enumerate(pairs):
                _obs.inc('decode.spec_draft_tokens_total', len(d))
                d = d + [0] * (k - len(d))  # padded rows verify for free
                drafts.append(d)
                tokens[i, 0] = seq.pending_token
                tokens[i, 1:] = d
            batch = [seq for seq, _ in pairs]
            feeds = self._step_feeds(batch, k + 1)
        nxt, now = self._fetch(
            self._enqueue(self._dispatch_verify, batch, tokens, feeds))
        nxt = nxt.reshape(tokens.shape)
        _obs.inc('decode.spec_steps_total')
        with _obs.span('decode.step.emit',
                       record='decode.step_emit_seconds'):
            clk = self._clock_at(now)
            for i, (seq, _) in enumerate(pairs):
                emit = accept_drafts(drafts[i], nxt[i])
                _obs.record('decode.spec_accepted_len', len(emit) - 1)
                _obs.inc('decode.spec_accepted_tokens_total',
                         len(emit) - 1)
                for tok in emit:
                    seq.cache_len += 1
                    self._maybe_publish(seq)
                    self._emit(seq, int(tok), now, clk)
                    reason = seq.finished()
                    if reason:
                        self._finish(seq, reason)
                        break
            if clk is not None:
                self._count_token_gaps()
        return True

    def _emit(self, seq, token, now, clk):
        """Hand one token of ``seq`` out; it arrived at ``now``, where
        the worker's clock read ``clk`` (None with observe off)."""
        seq.generated.append(token)
        seq.pending_token = token
        if self.draft is not None and hasattr(self.draft, 'observe'):
            # online draft training: every target emission teaches the
            # draft what follows this context window
            g = seq.generated
            tail = g[-4:] if len(g) >= 4 else (seq.prompt + g)[-4:]
            self.draft.observe(tail)
        if seq.t_first_token is None:
            seq.t_first_token = now
            _obs.record('decode.ttft_seconds', now - seq.t_submit,
                        cached='1' if seq.cached_len else '0')
        if seq.t_last_token is not None:
            _obs.record('decode.inter_token_seconds',
                        now - seq.t_last_token)
            if clk is not None and seq.clk_last_token is not None:
                # what the worker did between the two tokens
                gaps = self._gaps
                gaps[0] += 1
                for i, t0 in enumerate(seq.clk_last_token):
                    gaps[i + 1] += clk[i] - t0
        seq.t_last_token = now
        if clk is not None:
            seq.clk_last_token = clk
        seq.stream._put(token)
        seq.streamed += 1
        if seq.ctx is not None and seq.ctx.sampled:
            # the per-token timeline: one instant mark per generated
            # token, so a sampled trace shows decode cadence directly
            seq.ctx.event('token', pos=len(seq.generated))
        _obs.inc('decode.tokens_total')

    def _finish(self, seq, reason):
        self._sched.finish(seq, reason)
        _obs.record('decode.request_seconds',
                    time.perf_counter() - seq.t_submit,
                    exemplar=seq.ctx.exemplar() if seq.ctx else None)
        if seq.ctx is not None and seq.ctx.sampled:
            seq.ctx.event('finish', reason=reason,
                          tokens=len(seq.generated))
            seq.ctx.flow_end()
        self._request_done()
