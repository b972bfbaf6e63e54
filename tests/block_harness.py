"""One driver for a block's row function, shared by the
``tests/test_*_block.py`` files: what a paged op's lowering reads of its
context (``_Ctx``), the block built through the chain the programs
themselves go through (``paged_decode_ops._block_of``), a prefill chunk
and a decode step as ``paged_prefill`` and ``paged_decode_step`` run
them, each under one ``jax.jit`` made once a ``Driver``, and the proofs
every block's file holds its block to. A file keeps its spec, its
reference, its tolerance and the numbers of each proof; a case that
needs another spec, or a program traced again in another form, builds a
``Driver`` of its own."""

import copy

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.ops import paged_decode_ops as pdo
from paddle_tpu.serving.decode import model as lm
from util import as_held

# the sizes the files of the kinds with a size a sequence share: 96
# positions a sequence, four slots and the spare
BS, PAGES, NB, SLOTS = 4, 24, 64, 4


class _Op(object):
    def __init__(self, slots):
        self._slots = slots

    def input(self, slot):
        return self._slots[slot]


class _Ctx(object):
    """What a paged op's lowering reads of its context, for driving the
    block's row function without a Program: the spec's attributes, the
    weights as the programs hold them (``util.as_held``) under the slots
    of ``model.block_param_shapes``, and, once ``fed``, the op inputs a
    block reads itself (the slot a row; a prefill's cached span)."""

    def __init__(self, spec, weights, bs):
        self._attrs = lm._block_attrs(spec, bs)
        self._feeds = {}
        held = as_held(spec, weights)
        self.env, slots = {}, {}
        for name, (_, _, slot) in lm.block_param_shapes(spec).items():
            self.env[name] = held[name]
            slots[slot] = name
        self.op = _Op(slots)

    def fed(self, **feeds):
        """This context with other feeds (the weights are shared)."""
        other = copy.copy(self)
        other._feeds = feeds
        return other

    def attr(self, name, default=None):
        return self._attrs.get(name, default)

    def has_input(self, slot):
        return slot in self._feeds or slot in self.op._slots

    def input(self, slot):
        if slot in self._feeds:
            return self._feeds[slot]
        return self.env[self.op.input(slot)]


def tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, 64, n).astype(np.int32)


class Driver(object):
    """One block of ``spec`` over ``weights`` ({name: array}, declared
    layout) and its two row functions, each under one ``jax.jit`` whose
    own cache keys the chunk lengths and batch sizes. ``nb``: the pages
    of the pool, or {pool name: pages} where the spec has several;
    ``slots``: the slots of a pool of kinds with a size a sequence, whose
    arenas have the spare beside them; ``pages``: the width of a table.
    Arenas are a caller's own: they are data, not programs."""

    def __init__(self, spec, weights, bs, nb, slots=None, pages=None):
        self.spec, self.weights, self.bs = spec, weights, bs
        self.nb, self.slots, self.pages = nb, slots, pages
        self._ctx = _Ctx(spec, weights, bs)
        self._chunk = jax.jit(self._chunk_rows)
        self._step = jax.jit(self._step_rows)

    def block(self, **feeds):
        """The block as an op with these ``feeds`` builds it: a block
        that keeps a state reads its rows' slots as it is built, any
        other reads none of them."""
        return pdo._block_of(self._ctx.fed(**feeds))

    def _pages_of(self, kind):
        if kind.per_seq:
            return self.slots + 1
        return self.nb[kind.pool] if isinstance(self.nb, dict) else self.nb

    def arenas(self):
        """Zeros, as wide as the engine makes them (model._arenas)."""
        return tuple(
            jnp.zeros((len(k.layers), self._pages_of(k))
                      + tuple(k.unit_shape(self.bs)), jnp.float32)
            for k in self.spec.cache_kinds())

    def table(self, first, n_tokens):
        """A block table whose pages start at page ``first``."""
        row = np.full((self.pages,), self.nb, np.int32)
        n = -(-n_tokens // self.bs)
        row[:n] = first + np.arange(n)
        return jnp.asarray(row)

    def packed_tables(self, order, seqs):
        """A table a row of ``seqs`` (None: an empty slot, which names no
        page), the pages of ``order`` handed out one sequence after
        another."""
        tables = np.full((len(seqs), self.pages), self.nb, np.int32)
        used = 0
        for i, seq in enumerate(seqs):
            if seq is not None:
                need = -(-len(seq) // self.bs)
                tables[i, :need] = order[used:used + need]
                used += need
        return tables

    # ------------------------------------------------- the two programs
    def _chunk_rows(self, arenas, tables, slot, tokens, start, length):
        block = self.block(BlockTableState=slot, Cached=start)
        rows = tokens.shape[0]
        pos = start + jnp.arange(rows, dtype=jnp.int32)
        place = [pdo._page_runs(t, start, length, rows, *shape) for t, shape
                 in zip(tables, pdo._pool_shapes(block, arenas))]
        h, arenas, stats = pdo._extend_rows(
            block, arenas, tokens, pos, tables[0], place[0],
            valid=jnp.arange(rows) < length, more=zip(tables[1:], place[1:]))
        return block.logits(h), arenas, stats

    def _step_rows(self, arenas, tables, slots, tokens, lens):
        block = self.block(BlockTablesState=slots)
        place = [pdo._single_rows(t, lens, *shape) for t, shape
                 in zip(tables, pdo._pool_shapes(block, arenas))]
        h, arenas, stats = pdo._extend_rows(
            block, arenas, tokens, lens, tables[0], place[0],
            valid=place[0].ok[:, 0], more=zip(tables[1:], place[1:]))
        return block.logits(h), arenas, stats

    @staticmethod
    def _tables(tables):
        """A table a page pool of the block: the one, or a list."""
        if not isinstance(tables, (list, tuple)):
            tables = [tables]
        return [jnp.asarray(t, jnp.int32) for t in tables]

    def prefill_chunk(self, arenas, table, tokens, start, length=None,
                      slot=None):
        """One chunk of one sequence through the one-table path, as the
        ``paged_prefill`` op runs it, from any offset; the rows from
        ``length`` on (none by default) are padding. The logits of every
        row, the arenas it leaves, the router statistics."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if length is None:
            length = tokens.shape[0]
        if slot is not None:
            slot = jnp.asarray([slot], jnp.int32)
        return self._chunk(arenas, self._tables(table), slot, tokens,
                           jnp.int32(start), jnp.int32(length))

    def decode(self, arenas, tables, tokens, lens, slots=None):
        """A decode step as ``paged_decode_step`` runs it: row i is one
        token at position ``lens[i]`` of ``tables[i]`` (and of slot
        ``slots[i]``). The logits, the arenas, the router statistics."""
        if slots is not None:
            slots = jnp.asarray(slots, jnp.int32)
        return self._step(arenas, self._tables(tables), slots,
                          jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(lens, jnp.int32))

    def prefill(self, arenas, table, tokens, pieces, slot=None):
        """``tokens`` prefilled in chunks of the given lengths, each
        padded to the next power of two of at least 4: (logits of the
        valid rows, arenas, the last chunk's router statistics)."""
        out, start, stats = [], 0, None
        for n in pieces:
            ids = np.zeros((max(4, 1 << (n - 1).bit_length()),), np.int32)
            ids[:n] = tokens[start:start + n]
            lg, arenas, stats = self.prefill_chunk(arenas, table, ids, start,
                                                   length=n, slot=slot)
            out.append(np.asarray(lg)[:n])
            start += n
        return np.concatenate(out), arenas, stats

    def reference_logits(self, ref, tokens, **lowered):
        """The plain reference's one full forward over ``tokens``, with
        ``lowered`` laid over its architecture."""
        return np.asarray(ref.logits(
            self.weights, np.asarray(tokens, np.int32),
            dict(ref.arch_of(self.spec), **lowered), ref.held_of(self.spec)))


# -------------------------------------------- the proofs the files share
def chunked_prefill_then_decode(driver, ref, prompt_len, chunk, answer, tol):
    """A prompt prefilled in chunks of ``chunk`` through the arenas, then
    ``answer`` tokens decoded one at a time, every chunk's and every
    step's rows against the reference's one full forward. [(rows, router
    statistics)] of the chunks."""
    rng = np.random.RandomState(prompt_len)
    total = prompt_len + answer
    tokens = rng.randint(0, driver.spec.vocab_size, total)
    want = driver.reference_logits(ref, tokens)
    arenas = driver.arenas()
    table = jnp.asarray(rng.permutation(driver.nb)[:driver.pages], jnp.int32)
    chunks = []
    for start in range(0, prompt_len, chunk):
        piece = tokens[start:min(start + chunk, prompt_len)]
        got, arenas, stats = driver.prefill_chunk(arenas, table, piece, start)
        np.testing.assert_allclose(
            np.asarray(got), want[start:start + len(piece)], atol=tol)
        chunks.append((len(piece), stats))
    for t in range(prompt_len, total):
        got, arenas, _ = driver.decode(arenas, table[None, :],
                                       tokens[t:t + 1], [t])
        np.testing.assert_allclose(np.asarray(got)[0], want[t], atol=tol)
    return chunks


def decode_batch_of_mixed_lengths(driver, ref, seqs, tables, tol):
    """Each of ``seqs`` (None: an empty slot) but its last token
    prefilled whole through its row of ``tables``, then one decode step
    of them all: every live row's logits are the reference's for that
    sequence. (logits, router statistics) of the step."""
    arenas = driver.arenas()
    for seq, table in zip(seqs, tables):
        if seq is not None:
            _, arenas, _ = driver.prefill_chunk(arenas, table, seq[:-1], 0)
    got, _, stats = driver.decode(
        arenas, tables, [0 if s is None else s[-1] for s in seqs],
        [0 if s is None else len(s) - 1 for s in seqs])
    for i, seq in enumerate(seqs):
        if seq is not None:
            np.testing.assert_allclose(
                np.asarray(got)[i], driver.reference_logits(ref, seq)[-1],
                atol=tol)
    return got, stats


def padded_chunk_rows_write_nothing(driver, tol):
    """A chunk of 5 tokens padded to a bucket of 8: the real rows' logits
    do not move, the real rows are written bit for bit as the unpadded
    chunk writes them and the rows past ``length`` leave every arena as
    it was."""
    rng = np.random.RandomState(3)
    tokens = rng.randint(0, driver.spec.vocab_size, 5)
    table = jnp.arange(driver.pages, dtype=jnp.int32)
    exact, want, _ = driver.prefill_chunk(driver.arenas(), table, tokens, 0)
    padded = np.concatenate([tokens, np.zeros(3, tokens.dtype)])
    logits, got, _ = driver.prefill_chunk(driver.arenas(), table, padded, 0,
                                          length=5)
    np.testing.assert_allclose(np.asarray(logits)[:5], np.asarray(exact),
                               atol=tol)
    for a, b in zip(want, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        flat = np.asarray(b).reshape(b.shape[0], -1, b.shape[-1])
        assert flat[:, :5].any() and not flat[:, 5:].any()


def shares_of_one_expert_add_up(ref, spec_of, layer, tol, **route):
    """The eight shares of a routed layer (8 experts, one a share; the
    spec is ``spec_of(experts_held=..., first_expert=...)``), the shared
    expert counted once, are the uncut layer's FFN: in the reference,
    and between the block's product and the reference, the router (a
    sigmoid's top k under its bias; ``route``: its scale) replicated.
    Everything else of a share is the uncut model's own array. For what
    a file proves on top: (the rows, the uncut weights, the reference's
    architecture, the uncut sum, the shared expert's, ``cut(first)``)."""
    from paddle_tpu.ops import moe_held_ops as moe
    from paddle_tpu.serving.decode import random_weights
    whole = spec_of(experts_held=8, first_expert=0)
    w = random_weights(whole, seed=11)
    n = jnp.asarray(np.random.RandomState(1).randn(7, whole.d_model),
                    jnp.float32)
    arch = ref.arch_of(whole)
    uncut = np.asarray(ref.experts(n, w, layer, arch, (0, 8)))

    def cut(first):
        out = dict(w)
        for part in ('gate', 'up', 'down'):
            name = 'lm_moe_exp_%s.w' % part
            out[name] = w[name][:, first:first + 1]
        return out

    shared = np.asarray(ref.expert(
        n, w['lm_moe_shr_gate.w'][layer, 0], w['lm_moe_shr_up.w'][layer, 0],
        w['lm_moe_shr_down.w'][layer, 0]))
    from_reference, from_block = shared.copy(), shared.copy()
    for first in range(8):
        share = cut(first)
        from_reference += np.asarray(
            ref.experts(n, share, layer, arch, (first, 1))) - shared
        chosen, weight = moe.route_sigmoid_topk(
            n, share['lm_moe_router.w'][layer], whole.experts_per_token,
            bias=share['lm_moe_router.b'][layer], **route)
        gate, _ = moe.held_gates(chosen, weight, first, 1)
        from_block += np.asarray(moe.gated_experts(
            n, gate, *(jnp.asarray(share['lm_moe_exp_%s.w' % p][layer])
                       for p in ('gate', 'up', 'down'))))
    np.testing.assert_allclose(from_reference, uncut, atol=tol)
    np.testing.assert_allclose(from_block, uncut, atol=tol)
    held = lm.block_param_shapes(spec_of(experts_held=1, first_expert=3))
    full = lm.block_param_shapes(whole)
    assert {k for k in full if full[k][0] != held[k][0]} == {
        'lm_moe_exp_gate.w', 'lm_moe_exp_up.w', 'lm_moe_exp_down.w'}
    return n, w, arch, uncut, shared, cut


def programs_write_arenas_in_place(eng):
    """The decode step and a prefill chunk as the executor jits them,
    over ``eng``'s pool, far larger than a block of the attention's
    gathers: no instruction of the compiled program materialises a layer
    of any arena (serving/decode/hlo_check.py). The two traced
    programs."""
    from paddle_tpu.serving.decode.hlo_check import arena_sized_instructions
    smallest = min(eng.pools[0].num_blocks * eng.block_size * k.width
                   for k in eng.spec.cache_kinds())
    traced = [eng.trace_program(which) for which in ('decode', 8)]
    for program in traced:
        hlo = program.lower().compile().as_text()
        assert arena_sized_instructions(hlo, smallest) == []
    return traced


def prefill_then_decode_through_the_cache(driver, ref, steps, chunk, tol,
                                          stepper=None):
    """Three sequences of unlike depth, each prefilled in chunks into
    its own slot and pages, then decoded together for ``steps`` steps
    (by ``stepper``, where the step is traced in another form than
    ``driver`` holds); between the steps the rows change places (the
    engine compacts its batch every step: a row index is no home for
    state, the slot is)."""
    seqs = [tokens(n, 10 + n) for n in (29, 42, 22)]
    prompts = (17, 30, 9)
    slots, firsts = (2, 0, 3), (0, 12, 30)
    arenas = driver.arenas()
    tables = [driver.table(f, len(s)) for f, s in zip(firsts, seqs)]
    for seq, p, slot, table in zip(seqs, prompts, slots, tables):
        _, arenas, _ = driver.prefill(
            arenas, table, seq[:p], [chunk] * (p // chunk) + [p % chunk],
            slot=slot)
    want = [driver.reference_logits(ref, s) for s in seqs]
    no_pages = jnp.full((driver.pages,), driver.nb, jnp.int32)
    order = [0, 1, 2]
    for step in range(steps):
        if step % 3 == 2:
            order = order[1:] + order[:1]       # rows move, slots stay
        rows = [i for i in order if prompts[i] + step < len(seqs[i])]
        pad = 4 - len(rows)
        lens = [prompts[i] + step for i in rows]
        lg, arenas, _ = (stepper or driver).decode(
            arenas, jnp.stack([tables[i] for i in rows] + [no_pages] * pad),
            [seqs[i][n] for i, n in zip(rows, lens)] + [0] * pad,
            lens + [0] * pad,
            slots=[slots[i] for i in rows] + [driver.slots] * pad)
        for r, (i, n) in enumerate(zip(rows, lens)):
            np.testing.assert_allclose(np.asarray(lg)[r], want[i][n],
                                       atol=tol)
