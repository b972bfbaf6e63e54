"""The one general traffic generator for serving cells.

A traffic file gives the parameters (rate, length ranges, tail shape,
pre-roll); ``schedule`` turns them and a seed into a list of requests
that is a pure function of its arguments; ``drive`` paces them in an
open loop and reads every answer, all from one thread. Latency is
clocked from the instant a request was *due*, not from when it was
sent, so a stall is charged to the requests behind it, and how late the
pacer itself ran is reported.

Copied and corrected from ``paddle_tpu/serving/loadgen.py``
(``heavy_tailed_rows`` as it is; ``open_loop`` clocked from the actual
send and reported no lateness). Every seed sends the same requests at
the same instants: arrival times, prompt lengths and answer lengths are
drawn once from the traffic file's ``pool_seed``, and the seed only
fills the prompts with other tokens (and, in the runner, draws other
weights). Dealing the same lengths in another order for each seed was
tried first (PERF.md, PR 24): with some hundred requests in a window and
answers that take up to half a minute, the order alone moved the tokens
delivered inside the window by 8% and the tails by more.
"""

import collections
import time

import numpy as np

Request = collections.namedtuple(
    'Request', 'index due prompt_len answer_len token_seed')


def heavy_tailed(rng, lo, hi, alpha):
    """Pareto-ish size in [lo, hi]: most draws small, a heavy tail
    large, a few percent at the top."""
    draw = float(rng.pareto(alpha))
    frac = min(1.0, draw / 10.0)
    return int(lo + round((hi - lo) * frac))


def _segment(traffic, pool, tokens, start_s, length_s, first_index):
    """One stretch of the schedule: round(rate x length) requests whose
    sizes and gaps come from ``pool`` and whose prompts from ``tokens``."""
    n = int(round(traffic['rate_rps'] * length_s))
    alpha = traffic['alpha']
    prompts = [heavy_tailed(pool, *traffic['prompt_len'], alpha=alpha)
               for _ in range(n)]
    answers = [heavy_tailed(pool, *traffic['answer_len'], alpha=alpha)
               for _ in range(n)]
    gaps = pool.exponential(1.0, n)
    gaps *= length_s / gaps.sum()       # the offered rate, exactly
    due = start_s + (np.cumsum(gaps) - gaps[0])
    token_seeds = tokens.randint(0, 1 << 31, n)
    return [Request(first_index + i, float(due[i]), prompts[i], answers[i],
                    int(token_seeds[i])) for i in range(n)]


def schedule(traffic, seed, window_s):
    """Requests sorted by due time: a pre-roll stretch over
    [0, preroll_s) and the window's stretch over [preroll_s, preroll_s +
    window_s). Arrival times and lengths are drawn from the traffic
    file's ``pool_seed`` alone; the seed fills the prompts."""
    pool = np.random.RandomState(traffic['pool_seed'])
    tokens = np.random.RandomState(seed % (1 << 32))
    preroll = traffic['preroll_s']
    head = _segment(traffic, pool, tokens, 0.0, preroll, 0) \
        if preroll else []
    return head + _segment(traffic, pool, tokens, preroll, window_s,
                           len(head))


def prompt_tokens(request, vocab):
    return np.random.RandomState(request.token_seed).randint(
        0, vocab, request.prompt_len).tolist()


class Record(object):
    """One request as its client saw it; times are perf_counter."""

    __slots__ = ('request', 'due_at', 'sent_at', 'token_at', 'tokens',
                 'refused', 'error', 'done')

    def __init__(self, request, due_at):
        self.request = request
        self.due_at = due_at
        self.sent_at = None
        self.token_at = []
        self.tokens = []
        self.refused = None
        self.error = None
        self.done = False

    @property
    def ttft(self):
        return self.token_at[0] - self.due_at if self.token_at else None

    @property
    def gaps(self):
        return [b - a for a, b in zip(self.token_at, self.token_at[1:])]

    @property
    def complete(self):
        return (self.done and not self.error and not self.refused
                and len(self.tokens) == self.request.answer_len)


def wait_until(t, idle=None):
    """Sleep to perf_counter ``t`` in steps of at most 2 ms, calling
    ``idle(now)`` between them."""
    while True:
        now = time.perf_counter()
        if idle is not None:
            idle(now)
        if now >= t:
            return
        time.sleep(min(t - now, 0.002))


class Client(object):
    """The one client of every request in flight, on the pacer's own
    thread. ``poll(stream)`` returns, without blocking, ``(tokens, done,
    error)``: the tokens that arrived since the last call, whether the
    stream has ended, and what failed it. ``step`` is called every 2 ms
    at most (``wait_until``), so a token is stamped up to 2 ms after it
    arrived.

    A thread per request blocked on its stream was tried first and put
    the host's scheduler into the system's own step: the engine emits a
    step's tokens to every running request in a loop, each put woke a
    thread that then wanted the interpreter lock, and the time between
    two decode steps was 2 ms in one run and 5-9 ms in the next
    (PERF.md, PR 24). With no thread waiting, a put wakes nobody."""

    def __init__(self, poll, idle=None):
        self.poll = poll
        self.idle = idle
        self.records = []
        self.live = []                  # (record, stream) still open

    def step(self, now):
        if self.idle is not None:
            self.idle(now)
        for pair in list(self.live):
            record, stream = pair
            tokens, done, error = self.poll(stream)
            if tokens:
                at = time.perf_counter()
                record.token_at.extend([at] * len(tokens))
                record.tokens.extend(tokens)
            if done:
                record.error = error
                record.done = True
                self.live.remove(pair)

    def finish(self, deadline):
        """Poll until every stream has ended or ``deadline`` passes;
        returns how many are still open."""
        while self.live and time.perf_counter() < deadline:
            time.sleep(0.002)
            self.step(time.perf_counter())
        return len(self.live)


def drive(submit, poll, requests, t0, idle=None):
    """Send each request at ``t0 + due`` whatever the system does (open
    loop). ``submit(request)`` returns a stream that ``poll`` can read,
    or raises: a refusal. Returns the Client, whose ``step`` the caller
    keeps calling (``wait_until(t, client.step)``, then ``finish``)."""
    client = Client(poll, idle)
    for request in requests:
        record = Record(request, t0 + request.due)
        wait_until(record.due_at, client.step)
        record.sent_at = time.perf_counter()
        try:
            stream = submit(request)
        except Exception as e:          # QueueFullError and the like
            record.refused = repr(e)
            record.done = True
        else:
            client.live.append((record, stream))
        client.records.append(record)
    return client
