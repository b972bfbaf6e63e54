"""Document sessions for serving cells: several short questions about
one long document, each ask a request of its own that repeats the
document (``traffic/doc_qa_sessions.json``).

``loadgen.schedule`` draws independent requests; it cannot make two
requests share a head. ``schedule`` here is the same kind of function (a
pure function of the traffic file, the seed and the window) and returns
the same ``loadgen.Request`` records, so ``loadgen.drive`` paces them
unchanged, plus for each request the ``Ask`` that says which document
it belongs to and how its prompt is made:

- documents open as a Poisson process at ``docs_per_request`` of the
  request rate, from the start of the pre-roll to the end of the window;
  a document's length is ``loadgen.heavy_tailed(*doc_len, alpha)``;
- a document is asked ``asks[0]..asks[1]`` times (uniform): the first
  ask at its opening, each later one ``ask_gap_floor_s`` + Exp(mean
  ``ask_gap_mean_s``) after the one before was *due* (not after it
  finished: the loop stays open); asks due after the window are not
  sent, documents opened in the pre-roll go on into the window;
- a request's prompt is the document's tokens followed by a question of
  its own, ``heavy_tailed(*question_len, alpha)`` fresh tokens; nothing
  of an earlier answer is carried; the answer is
  ``heavy_tailed(*answer_len, alpha)`` tokens.

Arrival times and every length come from the traffic file's
``pool_seed`` alone, so every run and every ``--seed`` sends the same
requests at the same instants; the seed fills documents and questions
with other tokens. The gaps between openings are scaled by one factor,
the one nearest 1 (between a quarter and four) at which the requests
due inside the window number exactly ``round(rate_rps x window_s)``: the
offered rate, exactly, as ``loadgen._segment`` scales its gaps.
"""

import collections

import numpy as np

from benchmark import loadgen

Ask = collections.namedtuple(
    'Ask', 'document ask doc_len doc_seed question_len')

# documents drawn from the pool: more than any rate of a sweep opens
POOL_DOCS = 512


def _drawn(traffic):
    """Everything the pool seed fixes, in a fixed order of draws."""
    pool = np.random.RandomState(traffic['pool_seed'])
    alpha, most = traffic['alpha'], traffic['asks'][1]

    def sizes(bounds, n):
        return [loadgen.heavy_tailed(pool, bounds[0], bounds[1], alpha)
                for _ in range(n)]
    return {
        'gaps': pool.exponential(1.0, POOL_DOCS),
        'doc_len': sizes(traffic['doc_len'], POOL_DOCS),
        'asks': pool.randint(traffic['asks'][0], most + 1, POOL_DOCS),
        'ask_gaps': traffic['ask_gap_floor_s'] + pool.exponential(
            traffic['ask_gap_mean_s'], (POOL_DOCS, most)),
        'question_len': np.reshape(
            sizes(traffic['question_len'], POOL_DOCS * most),
            (POOL_DOCS, most)),
        'answer_len': np.reshape(
            sizes(traffic['answer_len'], POOL_DOCS * most),
            (POOL_DOCS, most))}


def _openings(drawn, traffic, window_s):
    """The openings' gaps in seconds: the rate's own mean gap times the
    scale nearest 1 at which the asks due inside the window number
    exactly ``round(rate_rps x window_s)``. An ask of document d is due
    at ``scale x opening_d + its own gaps``, so it is inside the window
    over one interval of scales; the count changes only where an
    interval begins or ends, and every stretch between two such scales
    is tried, the nearest to 1 first, at the point of it nearest 1."""
    preroll = traffic['preroll_s']
    end = preroll + window_s
    target = int(round(traffic['rate_rps'] * window_s))
    mean_gap = 1.0 / (traffic['rate_rps'] * traffic['docs_per_request'])
    opened = mean_gap * (np.cumsum(drawn['gaps']) - drawn['gaps'][0])
    later = np.cumsum(np.concatenate(
        [np.zeros((POOL_DOCS, 1)), drawn['ask_gaps'][:, 1:]], axis=1), axis=1)
    sent = np.arange(later.shape[1])[None, :] < drawn['asks'][:, None]
    # document 0 opens at 0 whatever the scale
    always = int(np.sum(sent[0] & (later[0] >= preroll) & (later[0] < end)))
    first = ((preroll - later[1:]) / opened[1:, None])[sent[1:]]
    last = ((end - later[1:]) / opened[1:, None])[sent[1:]]
    edges = np.unique(np.concatenate([first, last, [0.25, 4.0]]))
    edges = edges[(edges >= 0.25) & (edges <= 4.0)]
    lo, hi = edges[:-1], edges[1:]
    middle = (lo + hi) / 2
    # the intervals that have begun at a scale less those that have ended
    count = always + np.searchsorted(np.sort(first), middle, 'right') \
        - np.searchsorted(np.sort(last), middle, 'right')
    at = np.clip(1.0, lo + (hi - lo) / 4, hi - (hi - lo) / 4)
    fits = np.flatnonzero(count == target)
    if not len(fits):
        raise ValueError('sessions: no scale of the openings gives %d '
                         'requests in the window' % target)
    return opened * at[fits[np.argmin(np.abs(np.log(at[fits])))]]


def schedule(traffic, seed, window_s):
    """(requests sorted by due time, {request index: Ask})."""
    drawn = _drawn(traffic)
    end = traffic['preroll_s'] + window_s
    opened = _openings(drawn, traffic, window_s)
    chosen = []
    for d in range(int(np.searchsorted(opened, end))):
        due = opened[d]
        for a in range(int(drawn['asks'][d])):
            if a:
                due = due + drawn['ask_gaps'][d, a]
            if due < end:
                chosen.append((float(due), d, a))
    chosen.sort()
    tokens = np.random.RandomState(seed % (1 << 32))
    doc_seeds = tokens.randint(0, 1 << 31, POOL_DOCS)
    ask_seeds = tokens.randint(0, 1 << 31, drawn['asks'].shape[0] *
                               traffic['asks'][1]).reshape(POOL_DOCS, -1)
    requests, asks = [], {}
    for index, (due, d, a) in enumerate(chosen):
        question = int(drawn['question_len'][d, a])
        requests.append(loadgen.Request(
            index, due, drawn['doc_len'][d] + question,
            int(drawn['answer_len'][d, a]), int(ask_seeds[d, a])))
        asks[index] = Ask(d, a, drawn['doc_len'][d], int(doc_seeds[d]),
                          question)
    return requests, asks


def prompt_tokens(request, ask, vocab):
    """The document's tokens (the same for every ask of it) followed by
    the question's own."""
    document = np.random.RandomState(ask.doc_seed).randint(
        0, vocab, ask.doc_len)
    question = np.random.RandomState(request.token_seed).randint(
        0, vocab, ask.question_len)
    return np.concatenate([document, question]).tolist()


def shared_share(requests, asks, block_size, since_s=0.0):
    """Of the prompt tokens of the requests due from ``since_s`` on, the
    share a prefix cache that never evicts serves from shared pages: for
    every ask of a document but the first one sent, the document's whole
    pages (a page is shared only when full, and at least one token of a
    prompt is always prefilled)."""
    seen, shared, total = set(), 0, 0
    for r in requests:
        ask = asks[r.index]
        if r.due >= since_s:
            total += r.prompt_len
            if ask.document in seen:
                shared += min(ask.doc_len, r.prompt_len - 1) \
                    // block_size * block_size
        seen.add(ask.document)
    return shared / float(total) if total else 0.0
