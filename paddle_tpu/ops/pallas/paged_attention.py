"""Ragged paged attention for autoregressive decode serving.

One decode step attends one query token per sequence against that
sequence's KV cache, which lives in a pool of fixed-size blocks
("pages") in HBM — the paged-KV design from PAPERS "Ragged Paged
Attention". Each sequence owns a *block table* (logical page i ->
physical page id) and a true length; batches are ragged (every row has
a different live length) so a dense [B, Tmax] cache would pay padding
FLOPs and, worse, padding HBM. Pages decouple cache capacity from
per-sequence reservation: a 17-token sequence holds ceil(17/bs) pages,
not Tmax slots.

Two backends, selected like ops/pallas/flash_attention.py:

- **XLA gather path** (default, and the CPU/tier-1 path): gather the
  per-sequence pages through the block table at (layer, table) into
  [B, P, bs, H*D], read it as [B, P*bs, H, D] (no transpose), mask
  columns >= seq_len, fp32 softmax. The gather is the only
  instruction that reads the arena. What follows it is sized by the
  batch's tables, not by the pool — and on the TPU the split of H*D
  into [H, D] is still a re-tiling of the gathered pages when D is
  under a lane tile (PERF.md section 5; ROADMAP S3b/S3c).
- **Pallas kernel** (PADDLE_TPU_USE_PALLAS=1): the block table rides
  scalar prefetch (pltpu.PrefetchScalarGridSpec) so each grid step's
  page index map reads table[b, page] — the kernel DMAs exactly the
  pages a sequence owns, pages past seq_len are skipped entirely
  (ragged: short sequences cost proportionally less), and the online-
  softmax recurrence matches the flash kernel's.

Parity across mixed sequence lengths vs a dense masked reference is
asserted in tests/test_decode_serving.py (XLA path) and
tests/test_pallas_kernels.py (kernel, interpret mode).

Layouts:
    q            [B, H, D]      one query token per sequence
    k/v_pages    [L, NB, bs, H*D]  the pooled page arena, all layers:
                 token-major inside a page, heads and head width merged
                 into one lane-dense minor axis (a token's K row is
                 H*D contiguous elements), so the TPU keeps the arena
                 row-major and a row can be written in place
                 (ops/paged_decode_ops.py). ``layer`` picks the layer.
    k/v_scales   [L, NB, bs, H] per-row fp32 scales (quantized arenas)
    block_tables [B, P] int32   physical page ids; >= NB means "no page"
    seq_lens     [B]  int32     live tokens (this token included)

The Pallas kernel wants one layer's pages head-major, [NB, H, bs, D]:
``_paged_pallas`` cuts its layer out of the arena and re-lays it on its
own path (it is gated off by default; the kernel written for the
token-major arena is ROADMAP S3c).
"""

import functools
import os

import jax
import jax.numpy as jnp

from . import interpret_mode
from . import pallas_enabled

_NEG_INF = -1e9


def _gather_pages(arena, layer, tables, n_head):
    """arena [L, NB, bs, H*W] read at (layer, table) -> [B, P*bs, H, W]
    (W = 1 for the scale arenas' [L, NB, bs, H]). One gather whose
    slices are whole pages; the reshape splits and merges adjacent
    axes only, so nothing is transposed."""
    b, p = tables.shape
    bs = arena.shape[2]
    pages = arena[layer, tables]                   # [B, P, bs, H*W]
    return pages.reshape(b, p * bs, n_head, -1)


def paged_attention_reference(q, k_pages, v_pages, block_tables, seq_lens,
                              sm_scale=None, k_scales=None,
                              v_scales=None, layer=0, lo=None):
    """XLA gather path. Bit-stable contract with the Pallas kernel's
    masking: columns >= seq_lens[b] contribute exactly 0 (exp of a
    large-negative underflows), so the result is independent of the
    garbage content of unowned/partial pages.

    Quantized arenas: ``k_scales``/``v_scales`` [L, NB, bs, H] carry
    one fp32 scale per stored (page, slot, head) K/V row; the gather
    dequantizes to fp32 through the same table indices before the
    attention math (fp32 accumulation — int8/fp8 only ever live in
    HBM).

    Grouped heads: where the arena's row holds fewer heads than ``q``
    has (row width = Hkv * D), query head h reads KV head
    h // (H / Hkv). ``lo`` [B] int32 is a lower bound on the columns a
    row sees (a sliding window: columns < lo[b] contribute exactly 0);
    None sees every column below ``seq_lens``."""
    if k_pages.shape[-1] != q.shape[1] * q.shape[2]:
        return _grouped_reference(q, k_pages, v_pages, block_tables,
                                  seq_lens, sm_scale, layer, lo)
    nb, bs = k_pages.shape[1], k_pages.shape[2]
    b, p = block_tables.shape
    h, d = q.shape[1], q.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, nb - 1)
    k = _gather_pages(k_pages, layer, tables, h)   # [B, P*bs, H, D]
    v = _gather_pages(v_pages, layer, tables, h)
    if k_scales is not None:
        k = k.astype(jnp.float32) * _gather_pages(k_scales, layer,
                                                  tables, h)
        v = v.astype(jnp.float32) * _gather_pages(v_scales, layer,
                                                  tables, h)
    logits = jnp.einsum('bhd,bkhd->bhk', (q * scale), k)
    mask = _seen(p * bs, lo, seq_lens)
    logits = jnp.where(mask[:, None, :], logits, _NEG_INF)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum('bhk,bkhd->bhd', w.astype(v.dtype), v)


def _seen(n_cols, lo, hi):
    """[B, n_cols] bool: columns lo[b] <= j < hi[b] (lo None: 0)."""
    cols = jnp.arange(n_cols)[None, :]
    mask = cols < hi.reshape(-1, 1)
    if lo is not None:
        mask &= cols >= lo.reshape(-1, 1)
    return mask


def _grouped_reference(q, k_pages, v_pages, block_tables, seq_lens,
                       sm_scale, layer, lo):
    """The gather path for grouped KV heads (unquantized arenas): q
    [B, H, D] against rows of Hkv * D, scores and softmax in float32."""
    nb, bs = k_pages.shape[1], k_pages.shape[2]
    b, p = block_tables.shape
    h, d = q.shape[1], q.shape[2]
    n_kv = k_pages.shape[-1] // d
    scale = sm_scale if sm_scale is not None else d ** -0.5
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, nb - 1)
    k = k_pages[layer, tables].reshape(b, p * bs, n_kv * d)
    v = v_pages[layer, tables].reshape(b, p * bs, n_kv * d)
    qg = (q * scale).astype(k.dtype).reshape(b, n_kv, h // n_kv, d)
    mask = _seen(p * bs, lo, seq_lens)[:, None, :]
    out = []
    # KV head by KV head on slices of the gathered rows' minor axis: a
    # head is a whole number of lane tiles there (D = 128), so a slice
    # feeds the product as it lies; splitting the axis into [Hkv, D]
    # and batching over Hkv had the compiler re-lay the gathered pages
    # head-major first (0.4 GB each for K and V a layer at 32 x 6,656
    # tokens; v5e compile, PR 28)
    for n in range(n_kv):
        kn = k[:, :, n * d:(n + 1) * d]
        vn = v[:, :, n * d:(n + 1) * d]
        logits = jnp.einsum('bgd,bkd->bgk', qg[:, n], kn,
                            preferred_element_type=jnp.float32)
        w = jax.nn.softmax(jnp.where(mask, logits, _NEG_INF), axis=-1)
        out.append(jnp.einsum('bgk,bkd->bgd', w.astype(vn.dtype), vn,
                              preferred_element_type=jnp.float32))
    return jnp.stack(out, axis=1).reshape(b, h, d)


def paged_attention_one_table(q, k_pages, v_pages, table, lo, hi,
                              sm_scale=None, layer=0, block_cols=512):
    """Consecutive rows of ONE sequence (a prefill chunk) against that
    sequence's pages, gathered once: q [S, H, D], ``table`` [P], row s
    sees columns lo[s] <= j < hi[s]. The per-row form above gathers
    the table once per row ([S, P, bs, W]), which is what kept prompts
    at 512 (benchmark/configs/tbig_lm.json); here the gather is [P, bs,
    W] whatever S is.

    The columns go in blocks of whole pages (about ``block_cols``), from
    the block that holds the smallest ``lo`` to the one that holds the
    largest ``hi`` and no further, under a running softmax (maximum,
    normaliser and weighted sum carried from block to block, float32):
    a chunk at the start of a prompt multiplies one block and not the
    table's whole extent, a chunk deep in a sliding layer its window's
    blocks, and the scores alive at a time are [H, S, block], not
    [H, S, P * bs]. Which blocks run depends on the chunk's place in
    its own sequence only, so a row's result does not depend on what
    else the engine holds. Grouped heads as in
    ``paged_attention_reference``; unquantized arenas only."""
    nb, bs = k_pages.shape[1], k_pages.shape[2]
    s, h, d = q.shape
    n_kv = k_pages.shape[-1] // d
    group = h // n_kv
    scale = sm_scale if sm_scale is not None else d ** -0.5
    table = jnp.clip(table.astype(jnp.int32), 0, nb - 1)
    n_pages = table.shape[0]
    cols = n_pages * bs
    # whole pages a block, a divisor of the table so every block is full
    per_block = max(p for p in range(1, n_pages + 1)
                    if n_pages % p == 0 and p * bs <= max(block_cols, bs))
    bk = per_block * bs
    k = k_pages[layer, table].reshape(cols, n_kv, d)
    v = v_pages[layer, table].reshape(cols, n_kv, d)
    qg = jnp.transpose((q * scale).astype(k.dtype).reshape(
        s, n_kv, group, d), (1, 2, 0, 3))                  # [Hkv, G, S, D]
    first = jnp.clip(jnp.min(lo) // bk, 0, cols // bk - 1)
    last = jnp.clip((jnp.max(hi) - 1) // bk, 0, cols // bk - 1)

    def block(j, state):
        top, norm, acc = state
        kb = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, 0)  # [bk, Hkv, D]
        vb = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, 0)
        at = j * bk + jnp.arange(bk)
        seen = (at[None, :] >= lo[:, None]) & (at[None, :] < hi[:, None])
        scores = jnp.einsum('ngsd,knd->ngsk', qg, kb,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(seen[None, None], scores, _NEG_INF)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        w = jnp.where(seen[None, None],
                      jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        norm = keep * norm + jnp.sum(w, axis=-1)
        acc = keep[..., None] * acc + jnp.einsum(
            'ngsk,knd->ngsd', w.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return new_top, norm, acc

    init = (jnp.full((n_kv, group, s), _NEG_INF, jnp.float32),
            jnp.zeros((n_kv, group, s), jnp.float32),
            jnp.zeros((n_kv, group, s, d), jnp.float32))
    _, norm, acc = jax.lax.fori_loop(first, last + 1, block, init)
    out = acc / jnp.where(norm == 0.0, 1.0, norm)[..., None]
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(s, h, d)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, bs, num_pages, sm_scale):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    pi = pl.program_id(2)
    seq_len = len_ref[b]

    @pl.when(pi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(pi * bs < seq_len)
    def _body():
        q = q_ref[0, 0]                                # [1, d]
        k = k_ref[0, 0]                                # [bs, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [1, bs]
        cols = pi * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(cols < seq_len, s, _NEG_INF)

        m_prev = m_scr[:]                              # [1, 128]
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)      # [1, 1]
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])                 # [1, bs] f32
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_next
        l_scr[:] = alpha * l_prev + l_cur
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [1, d]
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv

    @pl.when(pi == num_pages - 1)
    def _finish():
        denom = l_scr[:][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = (acc_scr[:] / denom).astype(o_ref.dtype)


def _layer_pages_head_major(arena, layer, n_head):
    """[L, NB, bs, H*D] at ``layer`` -> [NB, H, bs, D]: the kernel's
    page tile is (bs, D), which the token-major arena cannot hand out
    as a block (D alone is under a lane tile). A copy of one layer's
    pages, private to the Pallas path."""
    nb, bs = arena.shape[1], arena.shape[2]
    pages = jax.lax.dynamic_index_in_dim(arena, layer, keepdims=False)
    return jnp.transpose(pages.reshape(nb, bs, n_head, -1), (0, 2, 1, 3))


def _paged_pallas(q, k_pages, v_pages, block_tables, seq_lens, sm_scale,
                  layer=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    k_pages = _layer_pages_head_major(k_pages, layer, h)
    v_pages = _layer_pages_head_major(v_pages, layer, h)
    nb, bs = k_pages.shape[0], k_pages.shape[2]
    p = block_tables.shape[1]
    dv = v_pages.shape[-1]
    tables = jnp.clip(block_tables.astype(jnp.int32), 0, nb - 1)
    lens = seq_lens.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # block tables, lengths
        grid=(b, h, p),
        in_specs=[
            # q rides as [B, H, 1, D] so a (b, h) row is a (1, d) block
            # equal to the array's last two dims — a (1, 1, d) block
            # over [B, H, D] has a second-minor of 1 that is neither H
            # nor a multiple of 8, which the TPU lowering refuses
            pl.BlockSpec((1, 1, 1, d),
                         lambda bi, hi, pi, bt, ln: (bi, hi, 0, 0)),
            # pages: the physical page id comes from the prefetched
            # block table — the ragged gather IS the index map
            pl.BlockSpec((1, 1, bs, d),
                         lambda bi, hi, pi, bt, ln: (bt[bi, pi], hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, dv),
                         lambda bi, hi, pi, bt, ln: (bt[bi, pi], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dv),
                               lambda bi, hi, pi, bt, ln: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, bs=bs, num_pages=p,
                               sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret_mode(),
    )(tables, lens, q.reshape(b, h, 1, d), k_pages, v_pages)
    return out.reshape(b, h, dv)


def _use_pallas(q, k_pages, v_pages, block_tables):
    """The kernel wants lane-aligned page tiles; anything else takes the
    gather path (which handles every shape). Precedence: an EXPLICIT
    PADDLE_TPU_PAGED_PALLAS overrides everything (in either direction),
    then an explicit PADDLE_TPU_USE_PALLAS, then — with
    PADDLE_TPU_AUTOTUNE=on — the per-shape tuning table (this is the
    dispatch the decode engine's ops/paged_decode_ops.py hot loop rides
    through), then the pallas_enabled() default (off)."""
    bs = k_pages.shape[2]
    h, d = q.shape[1], q.shape[2]
    aligned = bs % 8 == 0 and d % 8 == 0
    env = os.environ.get('PADDLE_TPU_PAGED_PALLAS')
    if env is not None:
        return env not in ('0', 'false', 'False') and aligned
    from ... import tuning
    if tuning.autotune_mode() != 'off' and \
            not tuning.env_gate_set('PADDLE_TPU_USE_PALLAS'):
        b, p = block_tables.shape
        picked = tuning.decide_paged_attention(
            b, p, h, bs, d, v_pages.shape[-1] // h, str(q.dtype))
        if picked is not None:
            return picked.get('impl') == 'pallas' and aligned
    return pallas_enabled() and aligned


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    sm_scale=None, k_scales=None, v_scales=None, layer=0,
                    lo=None):
    """Ragged paged attention: one query per sequence against its paged
    KV cache. q [B, H, D]; pages [L, NB, bs, H*D*] read at ``layer``
    (a traced scalar inside the decode ops' layer loop); block_tables
    [B, P] int32 (entries >= NB mean "no page" and are never read);
    seq_lens [B] int32. Grouped KV heads (rows of Hkv * D) and a lower
    column bound ``lo`` [B] (a sliding window) take the gather path.
    Quantized arenas pass their per-row fp32 scale
    arenas as ``k_scales``/``v_scales`` [L, NB, bs, H] and take the
    gather path (which dequantizes inline; the Pallas kernel stays
    fp32/bf16). Returns [B, H, Dv]."""
    d = q.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    plain = lo is None and k_pages.shape[-1] == q.shape[1] * d
    if plain and k_scales is None \
            and str(k_pages.dtype) in ('float32', 'bfloat16') \
            and _use_pallas(q, k_pages, v_pages, block_tables):
        return _paged_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                             scale, layer=layer)
    return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     seq_lens, sm_scale=scale,
                                     k_scales=k_scales,
                                     v_scales=v_scales, layer=layer, lo=lo)
