"""Program builder for the decode engine's decoder-only LM.

Builds the three Programs the engine drives through one Executor +
Scope, all sharing one parameter namespace (prefix ``lm_``):

- **startup** — initializes the block's weights (``LMSpec.block``:
  'post_ln' — models.transformer._stacked_layer_params layout,
  ENC_SLOTS, causal self-attention + FFN + 2 LNs per layer, token
  embedding, sinusoid position table, output projection; every other
  block — ``block_param_shapes``, at ``LMSpec.dtype``) and the zeroed
  arenas of the block's cache
  kinds (``LMSpec.cache_kinds``: K and V ``[L, NB, bs, Hkv*d]``, a
  latent row and an index key per kind of layer, or K and V per kind of
  layer, each kind in a page pool of its own: ``LMSpec.page_pools``;
  and, for a kind whose size is a sequence's and not a token's, the
  recurrent state and the convolution's last inputs of the layers that
  keep a state (Mamba-2, the gated delta rule), ``[L, slots + 1,
  ...]``: one slot a sequence, the last one a
  spare that rows past the batch read and write). Arenas are persistable scope state: every
  prefill/decode run reads them from scope and writes them back
  through executor donation — in-place HBM updates, the same
  whole-program-state contract the trainer uses for params.
- **prefill** — the ``paged_prefill`` op over feeds
  (ids [1, S], len, cached-prefix length, block table row, temp,
  seed). S varies by prompt bucket; each bucket is one compile-cache
  key, enumerated by ``DecodeEngine.warmup()``. ``pf_cached`` carries
  the prefix-cache hit length (0 on a miss) — a traced feed, so cache
  hits of any depth share the bucket's one signature.
- **decode** — the ``paged_decode_step`` op over fixed [max_batch]
  feeds: ONE signature for the engine's whole lifetime.
- **verify** (only when ``spec_k > 0``) — the ``paged_spec_verify``
  op over fixed [max_batch, spec_k+1] feeds: speculative-decoding
  verification as one more lifetime-fixed signature (k is a static
  attr, never a shape the scheduler can vary).

A scope trained elsewhere can be served by passing its weights to
``DecodeEngine(weights=...)`` — names here are stable and listed in
``DecodePrograms.param_names``, shapes are the declared ones of
``block_param_shapes``. A few parameters live in the scope with their
last two axes swapped (``HeldTransposed``); the engine's
``load_weights`` / ``export_weights`` / ``device_weights`` swap them on
the way in and out, so only code that reads the scope itself sees the
held layout.
"""

import collections

import numpy as np

from ... import layers
from ...core.program import Program, program_guard
from ...initializer import Constant, Normal, NumpyArrayInitializer
from ...layers.helper import LayerHelper
from ...models.transformer import (_stacked_layer_params,
                                   position_encoding_table)
from ...ops.transformer_ops import _slot_to_input
from ...param_attr import ParamAttr

__all__ = ['LMSpec', 'DecodePrograms', 'build_lm_programs']


SLIDING, FULL = 'sliding_attention', 'full_attention'
# a full_attention layer of a latent block that scores no positions of
# its own: it attends over the selection of the nearest scoring layer
# below it (``LMSpec.indexer_types`` 'shared'); a kind of ``layer_plan``
# only, never of ``layer_types``
CARRIED = 'carried_selection'
# the layer kinds of block='ssm_hybrid': a Mamba-2 mixer, attention that
# sees every position and carries none, or (where a layer is one
# sublayer, ``LMSpec.mixer_only``) an expert layer, which caches nothing
MAMBA, ATTENTION, MOE = 'mamba', 'attention', 'moe'
# the other layer kind of block='delta_hybrid' beside full_attention: a
# linear-attention layer under the gated delta rule, whose cache is a
# matrix a head in one slot a sequence
LINEAR = 'linear_attention'


class CacheKind(collections.namedtuple(
        'CacheKind', ['name', 'slot', 'layers', 'width', 'reads',
                      'shared', 'pool', 'keeps', 'per_seq', 'dtype'])):
    """One arena of the cache: its name, the op's input slot, the
    cache layers that keep it (in order; a layer that attends
    ``LMSpec.sublayers`` times keeps as many, each with rows of its
    own), the elements its unit holds in one
    layer (``width``: a token's row; or, for a kind whose size is a
    sequence's whatever its length, ``per_seq``: the shape of the one
    slot a sequence holds, and ``width`` its elements),
    per layer of ``layers`` the most positions of a sequence one decode
    step's attention reads there (``reads``; 0: every position held),
    whether the one row serves every head (``shared``: a latent
    row, an index key) and not ``n_kv_head`` heads' rows side by side,
    the page pool whose ids and block table index it (``pool``:
    ``LMSpec.page_pools``), and the kind's lifetime (``keeps``): ``w``
    where every layer of it is under a window and reads at most the
    last ``w`` positions, its query's own included; 0 where some layer
    reads every position or chooses among them all (a selection's
    ``reads`` is no lifetime), and so every position is kept. A kind
    with ``per_seq`` reads no position (``reads`` empty) and has no
    lifetime in positions; ``dtype`` is what its arena is kept at where
    that is not the engine's ``kv_dtype`` (a recurrent state: float32,
    whatever K and V are stored at)."""

    LANES = 128

    def __new__(cls, name, slot, layers, width, reads, shared, pool='',
                keeps=0, per_seq=(), dtype=None):
        return super(CacheKind, cls).__new__(
            cls, name, slot, layers, width, reads, shared, pool, keeps,
            tuple(per_seq), dtype)

    def unit_shape(self, block_size):
        """The shape of one unit of the kind's pool in one layer: a
        page of ``block_size`` rows, or a sequence's slot."""
        return self.per_seq or (int(block_size), self.stored)

    @property
    def stored(self):
        """The elements a row takes in the arena: ``width``, or where a
        row all heads share is wider than a lane tile and not whole
        tiles (a latent row: 576, 1,088), the next whole number of
        them. The TPU's row-major tiling pads such a row to that anyway;
        left to itself the compiler instead lays the *page* axis minor
        and re-lays the whole arena at every program's entry and exit
        (v5e compile at the published widths, PR 34), so the padding is
        made explicit and written as zeros. Per-head rows are never
        padded: the attention reads their head count off the arena's
        width (ops/pallas/paged_attention.py)."""
        if not self.shared or self.width <= self.LANES:
            return self.width
        return -(-self.width // self.LANES) * self.LANES


class PagePool(collections.namedtuple('PagePool',
                                        ['name', 'kinds', 'keeps'])):
    """One space of page ids: the cache kinds (arenas) that one block
    table a sequence indexes, and the pool's lifetime, ``keeps``: 0
    where some kind of it keeps every position, else the most
    positions behind a query that any of its kinds reads, so that a
    page all of whose positions lie further back than that from every
    query still to come can go back to the pool (``KVPool.trim``). The
    first pool of a spec has no name and feeds the programs under the
    names one table always had; another is named after its layers' kind
    and has feeds of its own (``pf_table_<name>``). A pool of kinds with
    a size a sequence (``per_sequence``) has slots for pages: a sequence
    holds exactly one whatever its length, its table is that one entry,
    and the arenas have one slot more than the pool, a spare for the
    rows that hold none."""

    @property
    def per_sequence(self):
        return all(kind.per_seq for kind in self.kinds)

    def table_width(self, pages_per_seq):
        """Entries of a sequence's table in this pool."""
        return 1 if self.per_sequence else int(pages_per_seq)

    @property
    def feed(self):
        return '_' + self.name if self.name else ''

    @property
    def slot(self):
        return self.name.capitalize()


def yarn_frequencies(dim, theta, scaling=None):
    """Per rotated pair ``i`` of a head (or a part of one) of ``dim``
    the angle a position advances it by: ``theta^(-2i/dim)``, and under
    YaRN (``scaling``: ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``; as DeepSeek-V3's reference code and
    the transformers library compute it, ``truncate`` on) that
    frequency kept for the fast pairs, divided by ``factor`` for the
    slow ones and blended linearly between pair ``low`` and pair
    ``high``, the pairs that turn ``beta_fast`` and ``beta_slow`` times
    over the original positions. float64 [dim / 2]. The one place the
    table is computed: ``LatentShape.rope_frequencies`` (latent_moe)
    and ``LMSpec.rope_tables`` (gqa_moe) both call it."""
    half = dim // 2
    plain = float(theta) ** (-np.arange(half, dtype=np.float64) * 2 / dim)
    if not scaling:
        return plain
    low, high = yarn_range(dim, theta, scaling)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / ((high - low) or 1e-3), 0.0, 1.0)
    return plain * (1 - ramp) + plain / float(scaling['factor']) * ramp


def yarn_range(dim, theta, scaling):
    """(low, high): the first pair that is stretched at all and the
    first that is stretched by the whole ``factor``."""
    original = float(scaling['original_max_position_embeddings'])

    def pair_of(turns):
        return dim * np.log(original / (2 * np.pi * turns)) \
            / (2 * np.log(float(theta)))
    return (max(int(np.floor(pair_of(float(scaling.get('beta_fast', 32))))),
                0),
            min(int(np.ceil(pair_of(float(scaling.get('beta_slow', 1))))),
                dim - 1))


def latent_expands(kv_rank, d_nope, d_v, rows):
    """The form of a latent attention with ``rows`` queries a table, as
    a function of its shapes alone. Absorbed (the key up-projection
    folded into the query, the value up-projection applied to the
    weighted sum) a (query, key, head) costs ``2 (2 kv_rank + d_rope)``
    operations; expanded (a key and a value a head made from each
    latent row read) it costs ``2 (d_nope + d_rope + d_v)``, after
    ``2 kv_rank (d_nope + d_v)`` a (key, head) for the expansion
    whatever ``rows`` is. So expanding pays where
    ``rows > kv_rank (d_nope + d_v) / (2 kv_rank - d_nope - d_v)``: 171
    rows at rank 512 over 128 + 128, 190 at rank 1,024 over 192 + 128;
    never where the latent is no wider than what it expands to. A
    decode step and a short chunk stay absorbed."""
    return rows * (2 * kv_rank - d_nope - d_v) > kv_rank * (d_nope + d_v)


class LatentShape(object):
    """One layer kind's latent attention: ``n_head`` heads over a cached
    row ``[c_kv ; k_rope]`` of ``kv_rank + d_rope`` that all of them
    share; queries through a rank-``q_rank`` bottleneck, ``d_nope +
    d_rope`` a head; values ``d_v`` a head."""

    rope_scaling = None

    def __init__(self, n_head, q_rank, kv_rank, d_nope, d_rope, d_v,
                 rope_theta, rope_scaling=None):
        self.n_head, self.q_rank, self.kv_rank = \
            int(n_head), int(q_rank), int(kv_rank)
        self.d_nope, self.d_rope, self.d_v = \
            int(d_nope), int(d_rope), int(d_v)
        self.rope_theta = float(rope_theta)
        if rope_scaling:
            # a published ``rope_scaling`` group (type 'yarn'); a shape
            # without one keeps the attributes it always had (dots3_note's
            # benchmark test holds ``vars()`` of its shapes letter for
            # letter)
            self.rope_scaling = dict(rope_scaling)
        if self.d_rope % 2 or min(self.n_head, self.q_rank, self.kv_rank,
                                  self.d_nope, self.d_rope, self.d_v) < 1:
            raise ValueError('LatentShape: %r' % (vars(self),))
        if self.rope_scaling and self.rope_scaling.get('type') != 'yarn':
            raise ValueError('LatentShape: rope_scaling %r (yarn)'
                             % (self.rope_scaling,))

    @property
    def row_width(self):
        return self.kv_rank + self.d_rope

    def expands(self, rows):
        """Whether the attention of ``rows`` queries that share one
        block table (a prefill chunk's bucket) expands the latent rows
        it reads to per-head keys and values, or runs absorbed
        (``latent_expands``). Static: the lowering takes the form by it
        and the engine counts ``decode.prefill_chunks_expanded`` by
        it."""
        return latent_expands(self.kv_rank, self.d_nope, self.d_v, rows)

    def rope_frequencies(self):
        """Per rotated pair the angle a position advances it by
        (``yarn_frequencies`` over the ``d_rope`` rotated columns: the
        plain powers of theta, or YaRN's table under ``rope_scaling``).
        The programs take the table either way."""
        return yarn_frequencies(self.d_rope, self.rope_theta,
                                self.rope_scaling)

    def yarn_range(self):
        return yarn_range(self.d_rope, self.rope_theta, self.rope_scaling)

    def softmax_multiplier(self):
        """What the softmax scale ``(d_nope + d_rope)^-1/2`` is
        multiplied by: ``m^2`` with ``m = 0.1 mscale_all_dim ln(factor)
        + 1`` under YaRN with ``mscale_all_dim`` (the scores of
        stretched positions are sharpened), else 1. The cos and sin
        themselves carry ``mscale(factor, mscale) / mscale(factor,
        mscale_all_dim)``, which this form requires to be 1."""
        rs = self.rope_scaling
        if not rs or not rs.get('mscale_all_dim') or \
                float(rs['factor']) <= 1:
            return 1.0
        if float(rs.get('mscale', 1)) != float(rs['mscale_all_dim']):
            raise ValueError('LatentShape: mscale %r and mscale_all_dim '
                             '%r differ: cos and sin would be scaled'
                             % (rs.get('mscale'), rs['mscale_all_dim']))
        m = 0.1 * float(rs['mscale_all_dim']) * \
            np.log(float(rs['factor'])) + 1.0
        return float(m * m)


class LMSpec(object):
    """Decoder-only LM hyperparameters: a family of seven blocks.

    ``block='post_ln'`` (the default; every argument after ``d_inner``
    unused): the 2017 decoder block — embedding scaled by sqrt(d_model)
    plus a sinusoid position table, causal self-attention and a ReLU FFN
    each followed by residual add and LayerNorm, an output table of its
    own, float32.

    ``block='parallel_moe'`` (cohere2_moe): one bias-free LayerNorm
    (``norm_eps``) feeding attention and the expert FFN side by side,
    ``y = x + attn + experts``; ``n_head`` query heads over
    ``n_kv_head`` KV heads of ``d_key`` (= ``d_value``); per layer a
    kind from ``layer_types`` — ``sliding_attention`` rotates q and k
    (interleaved pairs, ``rope_theta``) and sees the last
    ``sliding_window`` keys, its own included, ``full_attention``
    carries no position and sees all; a gated SiLU FFN of width
    ``d_inner`` in every expert; a sigmoid router over ``n_experts``
    that keeps ``experts_per_token`` and normalises over them, of which
    this engine holds ``experts_held`` starting at ``first_expert`` (one
    chip's share of an expert-parallel deployment: the rest of the sum
    is left out) plus the mean of ``n_shared_experts`` shared ones; a
    tied, unscaled embedding (``vocab_size`` rows: a slice is a smaller
    vocabulary) behind a final LayerNorm, logits times ``logit_scale``.
    ``dtype`` is what the matrices are kept and multiplied at
    (float32 / bfloat16); the residual stream, the norms' statistics,
    the router, the softmax and the logits are float32.

    ``block='latent_moe'`` (dots3_note, kimi_k2_6): a serial pre-norm
    block, ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.
    Attention is latent (``latent``: {layer kind: LatentShape
    arguments}, with the kind's rope scaling if it has one): a token
    caches one row ``[c_kv ; k_rope]`` a layer that every head reads.
    ``full_attention`` layers see every position at or below their own
    where ``index_topk`` is 0 (dense latent attention: no indexer, no
    index cache); with ``index_topk`` > 0 they see the ``index_topk``
    positions that a learned indexer (``index_n_heads`` heads of
    ``index_head_dim``, whose key a token also caches) scores highest,
    all of them below ``index_topk``. ``sliding_attention`` layers see
    the last ``sliding_window``. The first ``dense_layers`` have a
    gated SiLU FFN of ``d_inner_dense``, the others the routed experts
    of ``parallel_moe`` with a selection-only router bias, their sum
    times ``routed_scale``, and the shared experts at weight 1; an
    output head of its own. Two options a configuration has on or off:
    ``attn_gate`` (a sigmoid gate a head on the attention's output) and
    ``lora_rescale`` (the normed latents times sqrt(d_model / rank)).
    ``n_head``, ``n_kv_head``, ``d_key``, ``d_value`` and ``rope_theta``
    are unused: the shapes are per kind. **A carried selection**
    (glm_5_2): ``indexer_types`` gives every layer as 'full' (it has an
    indexer, caches an index key and makes the selection) or 'shared'
    (it has neither and attends over the positions that the nearest
    'full' layer below it chose); without it every full_attention layer
    under ``index_topk`` scores for itself. ``index_rope_interleave``:
    the indexer rotates its queries and keys in interleaved pairs as the
    attention does, not in half-split pairs.

    ``block='gqa_moe'`` (mellum): the serial pre-norm block of
    ``latent_moe`` over per-head keys and values: ``n_head`` query heads
    over ``n_kv_head`` KV heads of ``d_key`` (= ``d_value``), q and k
    rotated over the whole head in half-split pairs (i, i + d/2) by the
    table of the layer's kind (``rope_parameters``: {layer kind: the
    published section: ``rope_theta``, and for ``rope_type`` 'yarn' its
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow`` and the ``attention_factor`` that scales cos and
    sin}); ``sliding_attention`` layers see the last ``sliding_window``
    keys, ``full_attention`` layers all; routed experts in every layer
    under a softmax router (the ``experts_per_token`` largest of the
    softmax over ``n_experts``, normalised over those), of which
    ``experts_held`` from ``first_expert`` are computed here; no shared
    expert; an output head of its own behind a final RMSNorm. Each
    layer kind's K and V are arenas of their own under a page pool of
    their own (``page_pools``): the sliding layers' pool keeps a
    window's pages a sequence, the full layers' every page.

    ``block='ssm_hybrid'`` (granitemoehybrid without experts): a serial
    pre-norm block with a mixer by layer kind (``layer_types``: 'mamba'
    / 'attention') and a dense gated SiLU MLP of ``d_inner`` in every
    layer: ``h = x + r Mixer(RMSNorm(x))``, ``y = h + r MLP(RMSNorm(h))``
    with ``r`` = ``residual_scale``; the embedding times
    ``embed_scale``, tied, logits times ``logit_scale``. An attention
    layer is ``n_head`` query heads over ``n_kv_head`` KV heads of
    ``d_key`` with **no position** and the softmax scale ``attn_scale``;
    its K and V are paged like every other block's. A Mamba-2 layer
    (``ssm_heads`` heads of ``ssm_head_dim``, state ``ssm_state``, one
    group, a causal depthwise convolution of ``ssm_conv`` taps, chunks
    of ``ssm_chunk`` in prefill) keeps per sequence, whatever its
    length, a state ``[ssm_state, ssm_heads x ssm_head_dim]`` in float32
    and the last ``ssm_conv - 1`` inputs of its convolution end to end in
    one row: cache kinds
    with a size a sequence (``CacheKind.per_seq``) in a pool of their
    own whose unit is one sequence's slot. Such a state cannot be
    mapped from a page boundary nor rewound past a rejected draft: the
    prefix cache and speculation are refused. Three more forms, each
    off by default (nemotron_h): ``ssm_groups`` > 1 gives ``B`` and
    ``C`` a group of ``ssm_heads / ssm_groups`` heads each (head ``h``
    reads group ``h // (heads / groups)``) and the gated norm its
    statistics a group; ``tie_embeddings`` False an output head of its
    own; and ``mixer_only``: **a layer is one sublayer**, ``h = x + r
    Mixer(RMSNorm(x))`` with no MLP behind it, and ``layer_types`` has a
    third kind, 'moe', an expert layer that owns no cache: a sigmoid
    router over ``n_experts`` on the hidden width with a selection-only
    bias, the ``experts_per_token`` chosen scores normalised and times
    ``routed_scale``; the experts inside a latent of ``moe_latent``
    (``u = n W_in``, ``r = sum_e w_e relu(u W1_e)^2 W2_e`` over the
    ``experts_held`` from ``first_expert``, width ``d_inner``, two
    matrices an expert and no gate matrix, ``r W_out``), and one shared
    expert ``relu(n V1)^2 V2`` of ``d_inner_shared`` on the hidden width
    at weight 1.

    ``block='shortcut_moe'`` (longcat_flash): a layer of two sublayers
    ``j``, each with its own latent attention (the one ``full_attention``
    shape of ``latent``, dense: no indexer, no window), two RMSNorms and
    a dense gated SiLU FFN of ``d_inner_dense``, and one expert branch
    beside them that leaves from the first sublayer and rejoins at the
    layer's end: ``a0 = x + Attn0(RMSNorm(x))``, ``n0 = RMSNorm(a0)``,
    ``s = MoE(n0)``, ``b0 = a0 + FFN0(n0)``, ``a1 = b0 +
    Attn1(RMSNorm(b0))``, ``y = a1 + FFN1(RMSNorm(a1)) + s``. A token
    therefore caches ``sublayers`` = 2 latent rows a layer: the one
    kind's arena has ``2 n_layer`` cache layers, sublayer ``j`` of layer
    ``l`` at ``2 l + j``, as have the attention, norm and dense FFN
    stacks. The router is a softmax over ``n_experts + zero_experts``
    outputs; the ``experts_per_token`` largest of score + a
    selection-only bias are chosen and weigh their own scores times
    ``routed_scale``, not normalised over the chosen. An index below
    ``n_experts`` is a gated SiLU FFN of ``d_inner`` (``experts_held``
    from ``first_expert`` are computed here); one at or above it is an
    identity expert, which returns its input: a row's identity choices
    cost one multiply by the sum of their weights and never enter the
    routed product. No shared expert; an untied head behind a final
    RMSNorm; ``lora_rescale`` as ``latent_moe``. The prefix cache,
    speculation, quantized arenas and the page handoff are refused.

    ``block='delta_hybrid'`` (qwen3_next): a serial pre-norm block under
    the zero-centred norm ``RMSNorm0(x) = x / rms(x) (1 + w)``, ``h = x
    + Mixer(RMSNorm0(x))``, ``y = h + MoE(RMSNorm0(h))``, the mixer by
    layer kind (``layer_types``: 'linear_attention' / 'full_attention').
    A **linear-attention layer** runs the gated delta rule
    (``ops/gated_delta_ops.py``): ``ssm_groups`` key heads of
    ``ssm_state`` for q and k and ``ssm_heads`` value heads of
    ``ssm_head_dim`` for v and the gate z (value head ``h`` reads key
    head ``h // (heads / groups)``), a causal depthwise convolution of
    ``ssm_conv`` taps without a bias over ``[q; k; v]``, l2-normalised q
    and k, a write strength ``sigmoid(b)`` and a decay ``exp(-exp(A_log)
    softplus(a + dt_bias))`` a head, scan chunks of ``ssm_chunk`` rows
    in prefill, a plain-gain RMSNorm over each head and then the gate
    ``silu(z)``. It keeps per sequence a state ``[ssm_heads, ssm_state,
    ssm_head_dim]`` in float32 and the convolution's last ``ssm_conv -
    1`` inputs: cache kinds with a size a sequence, as ``ssm_hybrid``'s,
    in the same pool of slots. A **full-attention layer** is ``n_head``
    query heads over ``n_kv_head`` KV heads of ``d_key``, paged like
    every other block's: a query and an output gate a head
    (``sigmoid(gate)`` on the attention's result), q and k through
    ``RMSNorm0`` over each head with gains of their own, the first
    ``rotary_dim`` columns of a head rotated in half-split pairs by the
    plain powers of ``rope_theta`` and the others not. Every layer has
    the routed experts of ``gqa_moe`` (softmax router over ``n_experts``,
    the ``experts_per_token`` largest normalised over those,
    ``experts_held`` from ``first_expert`` computed here, width
    ``d_inner``) plus one shared gated SiLU expert of ``d_inner_shared``
    times ``sigmoid(n w_sg)``, a gate of its own; an untied head behind
    a final ``RMSNorm0``. The prefix cache, speculation, quantized arenas
    and the page handoff are refused (``refusal``)."""

    def __init__(self, vocab_size, n_layer=2, n_head=2, d_key=16,
                 d_value=16, d_model=32, d_inner=64, block='post_ln',
                 n_kv_head=None, layer_types=None, sliding_window=0,
                 rope_theta=10000.0, n_experts=0, experts_held=None,
                 first_expert=0, experts_per_token=0, n_shared_experts=0,
                 norm_eps=1e-5, logit_scale=1.0, dtype='float32',
                 latent=None, dense_layers=0, d_inner_dense=0,
                 index_n_heads=0, index_head_dim=0, index_topk=0,
                 lora_rescale=True, attn_gate=True, routed_scale=1.0,
                 rope_parameters=None, ssm_heads=0, ssm_head_dim=0,
                 ssm_state=0, ssm_conv=4, ssm_chunk=256, embed_scale=1.0,
                 residual_scale=1.0, attn_scale=None, zero_experts=0,
                 indexer_types=None, index_rope_interleave=False,
                 ssm_groups=1, mixer_only=False, tie_embeddings=True,
                 moe_latent=0, d_inner_shared=0, rotary_dim=0):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.d_key = int(d_key)
        self.d_value = int(d_value)
        self.d_model = int(d_model)
        self.d_inner = int(d_inner)
        self.block = str(block)
        self.n_kv_head = int(n_kv_head) if n_kv_head else self.n_head
        self.layer_types = tuple(layer_types) if layer_types else \
            (FULL,) * self.n_layer
        self.sliding_window = int(sliding_window)
        self.rope_theta = float(rope_theta)
        self.n_experts = int(n_experts)
        self.zero_experts = int(zero_experts)
        self.experts_held = self.n_experts if experts_held is None \
            else int(experts_held)
        self.first_expert = int(first_expert)
        self.experts_per_token = int(experts_per_token)
        self.n_shared_experts = int(n_shared_experts)
        self.norm_eps = float(norm_eps)
        self.logit_scale = float(logit_scale)
        self.dtype = str(dtype)
        self.latent = {kind: LatentShape(**dict(shape))
                       for kind, shape in (latent or {}).items()}
        self.dense_layers = int(dense_layers)
        self.d_inner_dense = int(d_inner_dense)
        self.index_n_heads = int(index_n_heads)
        self.index_head_dim = int(index_head_dim)
        self.index_topk = int(index_topk)
        self.indexer_types = tuple(indexer_types or ())
        self.index_rope_interleave = bool(index_rope_interleave)
        self.lora_rescale = bool(lora_rescale)
        self.attn_gate = bool(attn_gate)
        self.routed_scale = float(routed_scale)
        self.rope_parameters = {kind: dict(section) for kind, section
                                in (rope_parameters or {}).items()}
        self.ssm_heads = int(ssm_heads)
        self.ssm_head_dim = int(ssm_head_dim)
        self.ssm_state = int(ssm_state)
        self.ssm_conv = int(ssm_conv)
        self.ssm_chunk = int(ssm_chunk)
        self.ssm_groups = int(ssm_groups)
        self.mixer_only = bool(mixer_only)
        self.tie_embeddings = bool(tie_embeddings)
        self.moe_latent = int(moe_latent)
        self.d_inner_shared = int(d_inner_shared)
        self.rotary_dim = int(rotary_dim) or self.d_key
        self.embed_scale = float(embed_scale)
        self.residual_scale = float(residual_scale)
        self.attn_scale = float(attn_scale) if attn_scale \
            else self.d_key ** -0.5
        if self.block == 'ssm_hybrid':
            self._check_ssm()
            return
        if self.block == 'delta_hybrid':
            self._check_delta()
            return
        if self.block == 'post_ln':
            if self.n_kv_head != self.n_head:
                raise ValueError("LMSpec: block='post_ln' has one KV head "
                                 "per query head")
            return
        if self.block not in ('parallel_moe', 'latent_moe', 'gqa_moe',
                              'shortcut_moe'):
            raise ValueError('LMSpec: unknown block %r (post_ln, '
                             'parallel_moe, latent_moe, gqa_moe, '
                             'ssm_hybrid, shortcut_moe, delta_hybrid)'
                             % self.block)
        if self.block in ('parallel_moe', 'gqa_moe') and (
                self.n_head % self.n_kv_head or self.d_key != self.d_value):
            raise ValueError('LMSpec: %d query heads over %d KV heads of '
                             '%d/%d' % (self.n_head, self.n_kv_head,
                                        self.d_key, self.d_value))
        if len(self.layer_types) != self.n_layer or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError('LMSpec: layer_types %r for %d layers'
                             % (self.layer_types, self.n_layer))
        if SLIDING in self.layer_types and self.sliding_window < 1:
            raise ValueError('LMSpec: sliding layers need a window')
        # a block has shared experts or has none: the other is refused
        shared_ok = self.n_shared_experts == 0 \
            if self.block in ('gqa_moe', 'shortcut_moe') \
            else self.n_shared_experts > 0
        # identity experts are the router's outputs past the real ones:
        # only the block that adds their term has them
        zero_ok = self.zero_experts > 0 if self.block == 'shortcut_moe' \
            else self.zero_experts == 0
        if not (0 < self.experts_per_token
                <= self.n_experts + self.zero_experts and
                0 < self.experts_held and shared_ok and zero_ok and
                self.first_expert + self.experts_held <= self.n_experts):
            raise ValueError(
                'LMSpec: experts %d..%d of %d (+ %d identity), %d per '
                'token, %d shared'
                % (self.first_expert,
                   self.first_expert + self.experts_held - 1,
                   self.n_experts, self.zero_experts,
                   self.experts_per_token, self.n_shared_experts))
        if self.block in ('latent_moe', 'shortcut_moe'):
            self._check_latent()
        if self.block == 'gqa_moe':
            self._check_rope()

    def _check_ssm(self):
        kinds = {MAMBA, ATTENTION} | ({MOE} if self.mixer_only else set())
        if len(self.layer_types) != self.n_layer or \
                set(self.layer_types) - kinds:
            raise ValueError('LMSpec: layer_types %r for %d layers (%s)'
                             % (self.layer_types, self.n_layer,
                                ', '.join(sorted(kinds))))
        if self.n_head % self.n_kv_head or self.d_key != self.d_value:
            raise ValueError('LMSpec: %d query heads over %d KV heads of '
                             '%d/%d' % (self.n_head, self.n_kv_head,
                                        self.d_key, self.d_value))
        if MAMBA in self.layer_types and (min(
                self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                self.ssm_conv - 1, self.ssm_chunk, self.ssm_groups) < 1
                or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                'LMSpec: mamba layers of %d heads of %d in %d groups, state '
                '%d, %d taps, chunks of %d'
                % (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                   self.ssm_state, self.ssm_conv, self.ssm_chunk))
        if MOE not in self.layer_types:
            if self.n_experts or self.dense_layers:
                raise ValueError(
                    "LMSpec: block='ssm_hybrid' has a dense MLP in every "
                    "layer and no experts, or (mixer_only) layers of one "
                    "sublayer of which the 'moe' ones hold the experts")
            return
        if not (0 < self.experts_per_token <= self.n_experts and
                0 < self.experts_held and
                self.first_expert + self.experts_held <= self.n_experts
                and self.n_shared_experts == 1 and not self.zero_experts
                and min(self.moe_latent, self.d_inner,
                        self.d_inner_shared) > 0):
            raise ValueError(
                'LMSpec: moe layers of experts %d..%d of %d, %d per token, '
                'width %d inside a latent of %d, %d shared of %d'
                % (self.first_expert,
                   self.first_expert + self.experts_held - 1,
                   self.n_experts, self.experts_per_token, self.d_inner,
                   self.moe_latent, self.n_shared_experts,
                   self.d_inner_shared))

    def _check_delta(self):
        if len(self.layer_types) != self.n_layer or \
                set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError('LMSpec: layer_types %r for %d layers (%s, %s)'
                             % (self.layer_types, self.n_layer, LINEAR,
                                FULL))
        if self.n_head % self.n_kv_head or self.d_key != self.d_value or \
                self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.d_key:
            raise ValueError(
                'LMSpec: %d query heads over %d KV heads of %d/%d, %d '
                'columns rotated' % (self.n_head, self.n_kv_head,
                                     self.d_key, self.d_value,
                                     self.rotary_dim))
        if LINEAR in self.layer_types and (min(
                self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                self.ssm_conv - 1, self.ssm_chunk, self.ssm_groups) < 1
                or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                'LMSpec: linear-attention layers of %d value heads of %d '
                'over %d key heads of %d, %d taps, chunks of %d'
                % (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                   self.ssm_state, self.ssm_conv, self.ssm_chunk))
        if not (0 < self.experts_per_token <= self.n_experts and
                0 < self.experts_held and
                self.first_expert + self.experts_held <= self.n_experts
                and self.n_shared_experts == 1 and not self.zero_experts
                and not self.dense_layers
                and min(self.d_inner, self.d_inner_shared) > 0):
            raise ValueError(
                'LMSpec: experts %d..%d of %d in every layer, %d per '
                'token, width %d, %d shared of %d behind a gate'
                % (self.first_expert,
                   self.first_expert + self.experts_held - 1,
                   self.n_experts, self.experts_per_token, self.d_inner,
                   self.n_shared_experts, self.d_inner_shared))

    @property
    def ssm_inner(self):
        """The width a layer that keeps a state works at: (value) heads
        x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self):
        """What the convolution runs over: x, and B and C of every
        group (Mamba-2); v, and q and k of every key head (the delta
        rule)."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def _check_rope(self):
        kinds = set(self.layer_types)
        if set(self.rope_parameters) != kinds or self.d_key % 2:
            raise ValueError('LMSpec: rope_parameters for %s, layers of %s,'
                             ' heads of %d' % (sorted(self.rope_parameters),
                                               sorted(kinds), self.d_key))
        for kind, section in self.rope_parameters.items():
            if section.get('rope_type', 'default') not in ('default',
                                                           'yarn'):
                raise ValueError('LMSpec: rope_type %r of %s (default, '
                                 'yarn)' % (section['rope_type'], kind))

    def _check_latent(self):
        kinds = set(self.layer_types)
        if set(self.latent) != kinds:
            raise ValueError('LMSpec: latent shapes for %s, layers of %s'
                             % (sorted(self.latent), sorted(kinds)))
        if not 0 <= self.dense_layers <= self.n_layer or (
                self.dense_layers and self.d_inner_dense < 1):
            raise ValueError('LMSpec: %d leading dense layers of width %d '
                             'in %d' % (self.dense_layers,
                                        self.d_inner_dense, self.n_layer))
        if FULL in kinds and self.index_topk and not (
                0 < self.index_topk and 0 < self.index_n_heads and
                self.latent[FULL].d_rope <= self.index_head_dim):
            raise ValueError(
                'LMSpec: full layers select by an indexer: index_topk %d, '
                '%d heads of %d' % (self.index_topk, self.index_n_heads,
                                    self.index_head_dim))
        if self.indexer_types or self.index_rope_interleave:
            # a layer that shares needs a selection made below it
            kept = [t for t, kind in zip(self.indexer_types,
                                         self.layer_types) if kind == FULL]
            if not self.index_topk or (self.indexer_types and (
                    len(self.indexer_types) != self.n_layer or
                    set(self.indexer_types) - {'full', 'shared'} or
                    kept[:1] != ['full'])):
                raise ValueError(
                    'LMSpec: indexer_types %r (full, shared; one a '
                    'layer, the first full_attention layer full) under '
                    'index_topk %d' % (self.indexer_types,
                                       self.index_topk))
        if self.block == 'shortcut_moe' and (
                kinds != {FULL} or self.index_topk or self.dense_layers
                or self.attn_gate or self.d_inner_dense < 1):
            raise ValueError(
                "LMSpec: block='shortcut_moe' has dense latent attention "
                "without a gate and a dense FFN of d_inner_dense in both "
                "sublayers of every layer (kinds %s, index_topk %d, "
                "dense_layers %d, attn_gate %s, d_inner_dense %d)"
                % (sorted(kinds), self.index_topk, self.dense_layers,
                   self.attn_gate, self.d_inner_dense))

    @property
    def sublayers(self):
        """Attention sublayers a layer, each with cache rows of its own:
        the cache layers of a kind are ``sublayers`` times its layers."""
        return 2 if self.block == 'shortcut_moe' else 1

    def cache_layers_of(self, kind):
        """The cache layers of one kind of layer, in the order its arena
        stacks them: sublayer ``j`` of layer ``l`` is ``l x sublayers +
        j`` (``layers_of(kind)`` where a layer attends once)."""
        return tuple(l * self.sublayers + j for l in self.layers_of(kind)
                     for j in range(self.sublayers))

    def layers_of(self, kind):
        """The layers of one kind, in order."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def scoring_layers(self):
        """The layers whose attention makes a selection of its own: the
        full_attention layers under ``index_topk``, less those that
        ``indexer_types`` gives as 'shared'. They alone have an
        indexer's weights and cache an index key."""
        if not self.index_topk:
            return ()
        return tuple(i for i in self.layers_of(FULL)
                     if not self.indexer_types
                     or self.indexer_types[i] == 'full')

    def plan_kinds(self):
        """Per layer the kind the layer loop runs it as:
        ``layer_types``, with ``CARRIED`` for a full_attention layer that
        attends over a selection made below it (``indexer_types``
        'shared'). Without ``indexer_types`` it is ``layer_types``."""
        if not self.indexer_types:
            return self.layer_types
        scoring = set(self.scoring_layers())
        return tuple(CARRIED if kind == FULL and i not in scoring else kind
                     for i, kind in enumerate(self.layer_types))

    def layer_plan(self):
        """(lead, period, n_periods, tail) of the latent_moe layer loop:
        ``lead`` the leading dense layers' kinds, ``period`` the
        shortest run of kinds that the routed layers repeat, how many
        whole periods there are, and ``tail`` the kinds of the routed
        layers left over (a prefix of a period). The published 46
        layers are 1 + 11 x (full, sliding, sliding, sliding) + 1. Under
        ``indexer_types`` the kinds are ``plan_kinds()``: glm_5_2's 78
        are 3 + 18 x (carried, carried, carried, full) + 3 carried."""
        kinds = self.plan_kinds()
        lead = kinds[:self.dense_layers]
        rest = kinds[self.dense_layers:]
        size = next(p for p in range(1, len(rest) + 2)
                    if all(rest[i] == rest[i % p]
                           for i in range(len(rest)))) if rest else 1
        whole = len(rest) // size
        return lead, rest[:size] if whole else (), whole, \
            rest[whole * size:]

    def cache_kinds(self):
        """What a token keeps in the paged cache: one ``CacheKind``
        (arena name, the op's input slot, the layers it holds in order,
        the row's width in elements; ``stored`` is what the row takes
        in the arena) per arena. The one place where a
        token's cache is written down: the arenas' shapes, the bytes a
        token costs and the pages a budget buys are all read from it.
        Every kind of a pool is indexed by the pool's one block table: a
        page id is a page of every arena of it. A full
        layer of the latent block keeps its latent rows, of which a
        step reads ``index_topk`` (0, no selection: all of them), and
        with a selection the indexer's keys beside them, in the layers
        that score (``scoring_layers``): a layer that attends over a
        carried selection keeps no index key, so the index arena may
        hold fewer layers than the latent arena it selects from."""
        every = tuple(range(self.n_layer))
        if self.block in ('ssm_hybrid', 'delta_hybrid'):
            # K and V of the attention layers in the pool that keeps
            # every page; the state and convolution rows of the layers
            # that keep a state in a pool whose unit is a sequence's slot
            out = []
            delta = self.block == 'delta_hybrid'
            held = self.layers_of(FULL if delta else ATTENTION)
            if held:
                reads = (0,) * len(held)
                out += [CacheKind('lm_kcache', 'KCache', held,
                                  self.n_kv_head * self.d_key, reads, False),
                        CacheKind('lm_vcache', 'VCache', held,
                                  self.n_kv_head * self.d_value, reads,
                                  False)]
            held = self.layers_of(LINEAR if delta else MAMBA)
            if held:
                # the convolution's K - 1 rows lie end to end in one
                # lane-dense row a slot: kept [K - 1, C] the v5e's
                # compiler laid the slot axis inside the three rows and
                # re-laid the arena around every program (compiled here
                # for a described chip, PR 45). Mamba-2's state is
                # state-major over every head's lanes; the delta rule's
                # a [keys, values] matrix a value head, so that a tile
                # of it is whole heads (ops/pallas/ssm_state_update.py)
                state = (self.ssm_heads, self.ssm_state,
                         self.ssm_head_dim) if delta \
                    else (self.ssm_state, self.ssm_inner)
                conv = ((self.ssm_conv - 1) * self.ssm_conv_width,)
                out += [CacheKind('lm_ssm_state', 'SsmState', held,
                                  int(np.prod(state)), (), True, 'state',
                                  per_seq=state, dtype='float32'),
                        CacheKind('lm_ssm_conv', 'SsmConv', held, conv[0],
                                  (), True, 'state', per_seq=conv,
                                  dtype=self.dtype)]
            return tuple(out)
        if self.block == 'gqa_moe':
            # K and V by layer kind: the full layers' pool has no name,
            # the sliding layers' is named and keeps a window's pages
            out = []
            for kind, tag in ((FULL, 'full'), (SLIDING, 'sliding')):
                held = self.layers_of(kind)
                reads = (self.sliding_window if kind == SLIDING else 0,
                         ) * len(held)
                pool = tag if kind == SLIDING and FULL in self.layer_types \
                    else ''
                if held:
                    out += [CacheKind('lm_kcache_' + tag,
                                      'KCache' + tag.capitalize(), held,
                                      self.n_kv_head * self.d_key, reads,
                                      False, pool, reads[0]),
                            CacheKind('lm_vcache_' + tag,
                                      'VCache' + tag.capitalize(), held,
                                      self.n_kv_head * self.d_value, reads,
                                      False, pool, reads[0])]
            return tuple(out)
        if self.block not in ('latent_moe', 'shortcut_moe'):
            reads = tuple(self.windows())
            return (CacheKind('lm_kcache', 'KCache', every,
                              self.n_kv_head * self.d_key, reads, False),
                    CacheKind('lm_vcache', 'VCache', every,
                              self.n_kv_head * self.d_value, reads, False))
        out = []
        if FULL in self.latent:
            full = self.cache_layers_of(FULL)
            out.append(CacheKind('lm_latent_full', 'LatentFull', full,
                                 self.latent[FULL].row_width,
                                 (self.index_topk,) * len(full), True))
            if self.index_topk:
                # the indexer scores every position to choose, in the
                # layers that have one (``scoring_layers``: all of them
                # but under ``indexer_types``)
                scoring = self.scoring_layers()
                out.append(CacheKind('lm_index_full', 'IndexFull', scoring,
                                     self.index_head_dim,
                                     (0,) * len(scoring), True))
        if SLIDING in self.latent:
            sliding = self.layers_of(SLIDING)
            out.append(CacheKind('lm_latent_sliding', 'LatentSliding',
                                 sliding, self.latent[SLIDING].row_width,
                                 (self.sliding_window,) * len(sliding),
                                 True))
        return tuple(out)

    def page_pools(self):
        """The spaces of page ids a token's cache lies in, one block
        table a sequence each (``PagePool``): the cache kinds grouped
        by ``CacheKind.pool`` in order of first appearance, each with
        its lifetime: the largest of its kinds' where each has one. A
        property of the spec, like ``shares_frozen_pages``: nothing
        turns it on. Every block but 'gqa_moe' has the one pool that
        keeps every page: there a windowed kind (dots3_note's
        ``lm_latent_sliding``) shares its table with kinds that keep
        all, or holds layers of both sorts (command_a_plus' K and V),
        and a table of its own would be a new feed of its programs."""
        names = []
        for kind in self.cache_kinds():
            if kind.pool not in names:
                names.append(kind.pool)
        out = []
        for name in names:
            kinds = tuple(k for k in self.cache_kinds() if k.pool == name)
            keeps = [k.keeps for k in kinds]
            out.append(PagePool(name, kinds,
                                max(keeps) if all(keeps) else 0))
        return tuple(out)

    def shares_frozen_pages(self):
        """Whether the prefix cache may map one sequence's frozen pages
        into another's table. The property tested: in every cache kind
        every layer's attention reads every position a row holds
        (``CacheKind.reads`` all 0: no window, no selection), so a
        page's rows are a pure function of the token chain from the
        root and are read the same way by whoever maps them; and the
        block's logits with shared pages are held to its reference
        (tests/test_decode_serving.py for 'post_ln',
        tests/test_kimi_k2_6_block.py for the dense 'latent_moe'). The
        windowed and the selected kinds, and 'parallel_moe', have no
        such test and stay refused (ROADMAP A8). A kind with a size a
        sequence has no pages at all: a recurrent state is a function
        of the whole chain and exists only at the position its owner
        has reached, so nothing of it can be mapped from a page
        boundary (``refusal``)."""
        if self.block == 'post_ln':
            return True
        return self.block == 'latent_moe' and not any(
            cap for kind in self.cache_kinds() for cap in kind.reads)

    def keeps_state(self):
        """Whether some cache kind is a sequence's state."""
        return any(kind.per_seq for kind in self.cache_kinds())

    def refusal(self, what):
        """Why this spec runs without ``what`` ('prefix_cache' or
        'speculation'), for the raise that refuses it."""
        if self.keeps_state():
            return {
                'prefix_cache': (
                    'a layer with a recurrent state keeps one state a '
                    'sequence, not rows a token: there is no page of it '
                    'to map from a page boundary, and no checkpoint of '
                    'it is kept there'),
                'speculation': (
                    'a recurrent layer\'s state is updated in place and '
                    'cannot be rewound past a rejected draft'),
            }[what]
        return {'prefix_cache': 'its logits with shared pages are held to '
                                'no reference yet',
                'speculation': 'it has no test against the block\'s '
                               'reference yet'}[what]

    def attn_windows(self):
        """``windows()`` of the layers that attend at all, once for
        each time a layer attends (``sublayers``)."""
        return [w for w, t in zip(self.windows(), self.layer_types)
                if t not in (MAMBA, LINEAR)
                for _ in range(self.sublayers)]

    def per_head_cache(self):
        """Whether a cached row is ``n_kv_head`` heads of K (or V)."""
        return not any(kind.shared for kind in self.cache_kinds())

    def windows(self):
        """Per layer, the keys a query sees (0: all of them)."""
        return [self.sliding_window if t == SLIDING else 0
                for t in self.layer_types]

    def rope_tables(self):
        """{layer kind: (frequencies float64 [d_key / 2], what cos and
        sin are multiplied by) or None}: the position table of each
        kind of layer of a per-head block, None for a kind that carries
        no position. 'parallel_moe' turns its sliding layers by the
        plain powers of ``rope_theta`` and leaves its full layers
        unturned; 'gqa_moe' turns every layer by its kind's section of
        ``rope_parameters`` (``yarn_frequencies``)."""
        if self.block == 'gqa_moe':
            return {kind: (yarn_frequencies(
                self.d_key, section['rope_theta'],
                section if section.get('rope_type') == 'yarn' else None),
                float(section.get('attention_factor', 1.0)))
                for kind, section in self.rope_parameters.items()}
        return {SLIDING: (yarn_frequencies(self.d_key, self.rope_theta),
                          1.0), FULL: None}

    def rotary(self):
        """Per layer, whether q and k are rotated."""
        tables = self.rope_tables()
        return [tables[t] is not None for t in self.layer_types]


DecodePrograms = collections.namedtuple(
    'DecodePrograms',
    ['startup', 'prefill', 'decode', 'verify', 'prefill_fetch',
     'decode_fetch', 'verify_fetch', 'param_names', 'arena_names',
     'capacity', 'kv_dtype', 'stats_fetch', 'prefill_stats_fetch'])


def kv_bytes_per_kind(spec, kv_dtype='float32'):
    """{arena name: HBM bytes one cached token costs in it}, over the
    layers that keep it (``LMSpec.cache_kinds``), at the arena dtype: 0
    for a kind whose size is a sequence's (``unit_bytes_per_kind`` has
    what a slot of it costs)."""
    from ...quant.core import kv_itemsize
    item = kv_itemsize(kv_dtype)
    return collections.OrderedDict(
        (kind.name, 0 if kind.per_seq
         else len(kind.layers) * kind.stored * item)
        for kind in spec.cache_kinds())


def unit_bytes_per_kind(spec, block_size, kv_dtype='float32'):
    """{arena name: HBM bytes one unit of its pool costs in it}, over
    the layers that keep it: a page of ``block_size`` tokens' rows at
    the arena dtype, or one sequence's slot at the kind's own."""
    from ...quant.core import kv_itemsize
    out = collections.OrderedDict()
    for kind in spec.cache_kinds():
        elements = 1
        for n in kind.unit_shape(block_size):
            elements *= n
        out[kind.name] = len(kind.layers) * elements * kv_itemsize(
            kind.dtype or kv_dtype)
    return out


def kv_bytes_per_token(spec, kv_dtype='float32'):
    """HBM bytes one cached token costs across all layers and cache
    kinds: the rows at the arena dtype plus (for quantized arenas) the
    per-token per-head fp32 scale pair. This is the number the ISSUE's
    capacity claim rides on: int8 at d_head=128 is ~3.9x less than
    fp32."""
    from ...quant.core import kv_quantized
    b = sum(kv_bytes_per_kind(spec, kv_dtype).values())
    if kv_quantized(kv_dtype):
        b += spec.n_layer * spec.n_kv_head * 2 * 4   # k + v scale rows
    return b


def pages_by_pool(spec, num_blocks):
    """{pool name: pages} from ``num_blocks``: one count, which every
    pool of the spec then has, or {pool name: pages} with the first
    pool's under ``''``."""
    pools = [pool.name for pool in spec.page_pools()]
    if not isinstance(num_blocks, dict):
        return {name: int(num_blocks) for name in pools}
    if sorted(num_blocks) != sorted(pools):
        raise ValueError('page counts for pools %s, the spec has %s'
                         % (sorted(num_blocks), sorted(pools)))
    return {name: int(num_blocks[name]) for name in pools}


def arena_bytes(spec, num_blocks, block_size, kv_dtype='float32'):
    """Total bytes of the cache (+ scale) arenas; ``num_blocks`` as
    ``pages_by_pool`` takes it."""
    pages = pages_by_pool(spec, num_blocks)
    if len(pages) == 1:
        return kv_page_bytes(spec, block_size, kv_dtype) * pages['']
    # a kind with a size a sequence has its spare slot beside the pool's
    return sum(n * (pages[kind.pool] + bool(kind.per_seq))
               for kind, n in zip(spec.cache_kinds(), unit_bytes_per_kind(
                   spec, block_size, kv_dtype).values()))


def kv_page_bytes(spec, block_size, kv_dtype='float32'):
    """Bytes one FULL page costs, in the arenas and on the wire of a KV
    handoff packet (serving/handoff.py): the page's rows at the arena
    dtype plus, for quantized arenas, its per-row fp32 scales. The 3-4x
    shrink the disaggregated fleet claims at ``kv_dtype='int8'`` is
    exactly this number's ratio to the fp32 one — quantized pages ship
    their scale sideband, never a dequantized copy."""
    return kv_bytes_per_token(spec, kv_dtype) * int(block_size)


def num_blocks_for_budget(budget_bytes, spec, block_size,
                          kv_dtype='float32'):
    """Pages an arena byte budget buys at ``kv_dtype``: the
    equal-bytes capacity count (tests/test_quant.py)."""
    return max(1, int(budget_bytes)
               // kv_page_bytes(spec, block_size, kv_dtype))


def _lm_params(spec, capacity):
    """Declare the shared parameter set in the CURRENT program (and its
    init ops in the current startup, first declaration wins): the op's
    weight inputs by slot, stacked ones under their slot names."""
    if spec.block != 'post_ln':
        return _moe_params(spec)
    stacked = _stacked_layer_params(
        'lm_stack', spec.n_layer, spec.n_head, spec.d_key, spec.d_value,
        spec.d_model, spec.d_inner, decoder=False)
    emb = layers.create_parameter(
        shape=[spec.vocab_size, spec.d_model], dtype='float32',
        name='lm_emb',
        attr=ParamAttr(name='lm_emb',
                       initializer=Normal(0., spec.d_model ** -0.5)))
    pos = layers.create_parameter(
        shape=[capacity, spec.d_model], dtype='float32',
        name='lm_pos_enc',
        attr=ParamAttr(name='lm_pos_enc',
                       initializer=NumpyArrayInitializer(
                           position_encoding_table(capacity,
                                                   spec.d_model)),
                       trainable=False))
    wout = layers.create_parameter(
        shape=[spec.d_model, spec.vocab_size], dtype='float32',
        name='lm_out_proj.w', attr=ParamAttr(name='lm_out_proj.w'))
    inputs = {'Emb': [emb], 'PosEnc': [pos], 'OutProj': [wout]}
    for slot, param in stacked.items():
        inputs[_slot_to_input(slot)] = [param]
    return inputs


def moe_param_shapes(spec):
    """{name: (shape, fan-in or None for a norm's gain, op input slot)}
    of the parallel_moe block's weights: matrices are kept at
    ``spec.dtype`` and drawn N(0, 1/fan-in); gains are float32 ones."""
    L, d, f = spec.n_layer, spec.d_model, spec.d_inner
    q, kv = spec.n_head * spec.d_key, spec.n_kv_head * spec.d_key
    e, sh = spec.experts_held, spec.n_shared_experts
    return collections.OrderedDict([
        ('lm_emb', ([spec.vocab_size, d], d, 'Emb')),
        ('lm_final_ln.w', ([d], None, 'FinalLN')),
        ('lm_stack_ln.w', ([L, d], None, 'LnW')),
        ('lm_stack_slf_q.w', ([L, d, q], d, 'SlfQ')),
        ('lm_stack_slf_k.w', ([L, d, kv], d, 'SlfK')),
        ('lm_stack_slf_v.w', ([L, d, kv], d, 'SlfV')),
        ('lm_stack_slf_o.w', ([L, q, d], q, 'SlfO')),
        ('lm_stack_router.w', ([L, d, spec.n_experts], d, 'Router')),
        ('lm_stack_exp_gate.w', ([L, e, d, f], d, 'ExpGate')),
        ('lm_stack_exp_up.w', ([L, e, d, f], d, 'ExpUp')),
        ('lm_stack_exp_down.w', ([L, e, f, d], f, 'ExpDown')),
        ('lm_stack_shr_gate.w', ([L, sh, d, f], d, 'ShrGate')),
        ('lm_stack_shr_up.w', ([L, sh, d, f], d, 'ShrUp')),
        ('lm_stack_shr_down.w', ([L, sh, f, d], f, 'ShrDown')),
    ])


class HeldTransposed(list):
    """The declared shape ``[..., a, b]`` of a parameter that the
    programs hold as ``[..., b, a]``. The declared shape is what
    ``load_weights``, ``export_weights``, ``device_weights``,
    ``random_weights`` and every draw by the table see; ``held`` is the
    shape the parameter is created with and the ops multiply
    (``_moe_params``, ``ops/paged_decode_ops.py::_mm_t``), and the
    engine swaps the two axes once, on the device, on the way in
    (``DecodeEngine.load_weights``)."""

    @property
    def held(self):
        return list(self[:-2]) + [self[-1], self[-2]]


def held_transposed(spec):
    """The names of a block's weights that the programs hold with their
    last two axes swapped (``HeldTransposed`` entries of its table)."""
    if spec.block == 'post_ln':
        return frozenset()
    return frozenset(
        name for name, (shape, _, _) in block_param_shapes(spec).items()
        if isinstance(shape, HeldTransposed))


def _latent_attention_shapes(spec, kind, tag, slot, n):
    """The eight attention entries of ``n`` stacked latent attentions of
    ``kind`` (``latent_param_shapes`` has the layouts and the fan-ins).
    ``q_b`` is held transposed, ``[n, heads x (nope + rope), q_rank]``:
    ``latent_param_shapes`` says why."""
    a, d = spec.latent[kind], spec.d_model
    qk = a.d_nope + a.d_rope
    from_q = d if spec.lora_rescale else a.q_rank
    from_kv = d if spec.lora_rescale else a.kv_rank
    return [
        ('lm_%s_q_a.w' % tag, ([n, d, a.q_rank], d, slot + 'QA')),
        ('lm_%s_q_ln.w' % tag, ([n, a.q_rank], None, slot + 'QLn')),
        ('lm_%s_q_b.w' % tag, (HeldTransposed([n, a.q_rank, a.n_head * qk]),
                               from_q, slot + 'QB')),
        ('lm_%s_kv_a.w' % tag, ([n, d, a.row_width], d, slot + 'KvA')),
        ('lm_%s_kv_ln.w' % tag, ([n, a.kv_rank], None, slot + 'KvLn')),
        ('lm_%s_kv_bk.w' % tag, ([n, a.n_head, a.d_nope, a.kv_rank],
                                 from_kv, slot + 'KvBK')),
        ('lm_%s_kv_bv.w' % tag, ([n, a.n_head, a.kv_rank, a.d_v],
                                 from_kv, slot + 'KvBV')),
        ('lm_%s_o.w' % tag, ([n, a.n_head * a.d_v, d],
                             a.n_head * a.d_v, slot + 'O')),
    ]


def shortcut_param_shapes(spec):
    """``moe_param_shapes`` of the shortcut_moe block: the two norms,
    the latent attention (``latent_param_shapes``' entries and layouts)
    and the dense gated FFN are stacks over the ``2 n_layer`` sublayers,
    sublayer ``j`` of layer ``l`` at ``2 l + j``; the router (as wide as
    the real and the identity experts together), its selection-only bias
    and the real experts held here are stacks over the layers. An
    identity expert has no weights and there is no shared expert."""
    L, d, f = spec.n_layer, spec.d_model, spec.d_inner
    e, fd, sub = spec.experts_held, spec.d_inner_dense, \
        spec.n_layer * spec.sublayers
    wide = spec.n_experts + spec.zero_experts
    out = collections.OrderedDict([
        ('lm_emb', ([spec.vocab_size, d], d, 'Emb')),
        ('lm_head.w', ([spec.vocab_size, d], d, 'Head')),
        ('lm_final_ln.w', ([d], None, 'FinalLN')),
        ('lm_stack_ln1.w', ([sub, d], None, 'Ln1W')),
        ('lm_stack_ln2.w', ([sub, d], None, 'Ln2W')),
    ])
    out.update(_latent_attention_shapes(spec, FULL, 'full', 'Full', sub))
    out.update([
        ('lm_dense_gate.w', ([sub, d, fd], d, 'DenseGate')),
        ('lm_dense_up.w', ([sub, d, fd], d, 'DenseUp')),
        ('lm_dense_down.w', ([sub, fd, d], fd, 'DenseDown')),
        ('lm_moe_router.w', ([L, d, wide], d, 'Router')),
        ('lm_moe_router.b', ([L, wide], 0, 'RouterBias')),
        ('lm_moe_exp_gate.w', ([L, e, d, f], d, 'ExpGate')),
        ('lm_moe_exp_up.w', ([L, e, d, f], d, 'ExpUp')),
        ('lm_moe_exp_down.w', ([L, e, f, d], f, 'ExpDown')),
    ])
    return out


def latent_param_shapes(spec):
    """``moe_param_shapes`` of the latent_moe block. Stacks are per
    kind, over the layers of that kind in order: ``lm_full_*`` the full
    layers' attention and indexer, ``lm_swa_*`` the sliding layers'
    attention, ``lm_dense_*`` the leading dense FFNs, ``lm_moe_*`` the
    routed layers' router and experts; the two norms' gains over all
    layers. The kv up-projection is kept as its two halves, head-major:
    ``kv_bk`` [H, d_nope, r] (keys) and ``kv_bv`` [H, r, d_v] (values),
    which is how both forms of the attention multiply them (absorbed:
    into the query and onto the sum; expanded: onto each block of
    latent rows read). A fan-in of 0 marks
    a bias: a float32 vector that starts at zero. The matrices that
    read a rescaled latent (``lora_rescale``: its RMS is sqrt(d_model /
    rank), not 1) count ``d_model`` as their fan-in, which is what the
    rescale is for: every matrix is drawn as if it read the hidden
    width, and queries, keys and scores come out at unit variance
    (drawn by the rank, scores have a deviation of 6 at the published
    widths, every head attends to one key, and the 0.5% of the selected
    set that bfloat16 flips at the selection's boundary moves the
    logits by 0.4 rms: PERF.md section 6, PR 34).

    **Declared layout, held layout.** The two matrices that project out
    of the query's rank, ``q_b`` of each kind and the indexer's
    ``idx_q``, are declared ``[n, q_rank, out]`` (what weights are loaded
    and handed back as) and held ``[n, out, q_rank]``
    (``HeldTransposed``), contracted over their last axis. Held as
    declared, the v5e's compiler wanted the rank minor and re-laid the
    matrix before it multiplied: longcat_flash_chat's whole stack at
    the entry of every program (``copy bf16[8,1536,12288]{1,2,0}``, 302
    MB read and written: 0.173 s of a 3 s traced tail, the cell's
    second-longest device op; ledger, PR 51), kimi_k2_6's 37.7 MB a
    layer and 235 MB of dots3_note's step (compiled for the v5e at the
    published geometry, PERF.md section 6, PR 52). gqa_moe's query
    projection is kept transposed for the same reason
    (``gqa_param_shapes``); these keep their declared shape because the
    references read it."""
    L, d, f = spec.n_layer, spec.d_model, spec.d_inner
    e, sh = spec.experts_held, spec.n_shared_experts
    n_dense, n_moe = spec.dense_layers, spec.n_layer - spec.dense_layers
    out = collections.OrderedDict([
        ('lm_emb', ([spec.vocab_size, d], d, 'Emb')),
        ('lm_head.w', ([spec.vocab_size, d], d, 'Head')),
        ('lm_final_ln.w', ([d], None, 'FinalLN')),
        ('lm_stack_ln1.w', ([L, d], None, 'Ln1W')),
        ('lm_stack_ln2.w', ([L, d], None, 'Ln2W')),
    ])
    for kind, tag, slot in ((FULL, 'full', 'Full'), (SLIDING, 'swa', 'Swa')):
        if kind not in spec.latent:
            continue
        a, n = spec.latent[kind], len(spec.layers_of(kind))
        from_q = d if spec.lora_rescale else a.q_rank
        out.update(_latent_attention_shapes(spec, kind, tag, slot, n))
        if spec.attn_gate:
            out['lm_%s_gate.w' % tag] = ([n, d, a.n_head], d, slot + 'Gate')
        if kind == FULL and spec.index_topk:
            hi, di = spec.index_n_heads, spec.index_head_dim
            # the indexer's stacks hold the layers that score
            n = len(spec.scoring_layers())
            out.update([
                ('lm_full_idx_q.w', (HeldTransposed([n, a.q_rank, hi * di]),
                                     from_q, 'IdxQ')),
                ('lm_full_idx_k.w', ([n, d, di], d, 'IdxK')),
                ('lm_full_idx_k_ln.w', ([n, di], None, 'IdxKLnW')),
                ('lm_full_idx_k_ln.b', ([n, di], 0, 'IdxKLnB')),
                ('lm_full_idx_w.w', ([n, d, hi], d, 'IdxW')),
            ])
    if n_dense:
        fd = spec.d_inner_dense
        out.update([
            ('lm_dense_gate.w', ([n_dense, d, fd], d, 'DenseGate')),
            ('lm_dense_up.w', ([n_dense, d, fd], d, 'DenseUp')),
            ('lm_dense_down.w', ([n_dense, fd, d], fd, 'DenseDown')),
        ])
    if n_moe:
        out.update([
            ('lm_moe_router.w', ([n_moe, d, spec.n_experts], d, 'Router')),
            ('lm_moe_router.b', ([n_moe, spec.n_experts], 0, 'RouterBias')),
            ('lm_moe_exp_gate.w', ([n_moe, e, d, f], d, 'ExpGate')),
            ('lm_moe_exp_up.w', ([n_moe, e, d, f], d, 'ExpUp')),
            ('lm_moe_exp_down.w', ([n_moe, e, f, d], f, 'ExpDown')),
            ('lm_moe_shr_gate.w', ([n_moe, sh, d, f], d, 'ShrGate')),
            ('lm_moe_shr_up.w', ([n_moe, sh, d, f], d, 'ShrUp')),
            ('lm_moe_shr_down.w', ([n_moe, sh, f, d], f, 'ShrDown')),
        ])
    return out


def gqa_param_shapes(spec):
    """``moe_param_shapes`` of the gqa_moe block: every layer has the
    one shape whatever its kind, so each matrix is one stack over all
    layers; two norms a layer, no shared expert, a head of its own. The
    query projection is kept as its transpose, ``[L, heads x d,
    d_model]`` (as the head is ``[V, d_model]``): kept ``[L, d_model,
    heads x d]`` the v5e's compiler re-laid the whole stack, 151 MB at
    the published widths, at the entry of every program (``copy
    bf16[8,2304,4096]{1,2,0}``: 0.46 ms of a 5.2 ms decode step; my chip
    run, PR 43)."""
    L, d, f = spec.n_layer, spec.d_model, spec.d_inner
    q, kv = spec.n_head * spec.d_key, spec.n_kv_head * spec.d_key
    e = spec.experts_held
    return collections.OrderedDict([
        ('lm_emb', ([spec.vocab_size, d], d, 'Emb')),
        ('lm_head.w', ([spec.vocab_size, d], d, 'Head')),
        ('lm_final_ln.w', ([d], None, 'FinalLN')),
        ('lm_stack_ln1.w', ([L, d], None, 'Ln1W')),
        ('lm_stack_ln2.w', ([L, d], None, 'Ln2W')),
        ('lm_stack_slf_q.w', ([L, q, d], d, 'SlfQ')),
        ('lm_stack_slf_k.w', ([L, d, kv], d, 'SlfK')),
        ('lm_stack_slf_v.w', ([L, d, kv], d, 'SlfV')),
        ('lm_stack_slf_o.w', ([L, q, d], q, 'SlfO')),
        ('lm_stack_router.w', ([L, d, spec.n_experts], d, 'Router')),
        ('lm_stack_exp_gate.w', ([L, e, d, f], d, 'ExpGate')),
        ('lm_stack_exp_up.w', ([L, e, d, f], d, 'ExpUp')),
        ('lm_stack_exp_down.w', ([L, e, f, d], f, 'ExpDown')),
    ])


def ssm_param_shapes(spec):
    """``moe_param_shapes`` of the ssm_hybrid block. The two norms and
    the gated MLP are stacks over all layers (the published input matrix
    ``[d, 2 f]`` as its two halves, ``mlp_gate`` and ``mlp_up``: as one
    stack of 40 layers it is 1.34 G elements, and the start-up program's
    draw of it in float32 asked the chip for 5.0 GB of scratch beside
    12.4 GB of arrays and did not load; my chip run, PR 45); ``lm_attn_*`` over the attention layers
    in order, ``lm_mamba_*`` over the Mamba-2 layers: ``in`` projects to
    ``[z (H P); x (H P); B (N); C (N); dt (H)]``, ``conv`` the depthwise
    taps ``[K, H P + 2 N]`` (tap K - 1 reads the row's own input) and
    their bias, ``dt.b`` and ``a_log`` a head each (biases: float32,
    zero at start, so A = -1 until weights are loaded), ``d`` the skip
    gain a head and ``norm`` the gated norm's (ones). With
    ``ssm_groups`` G the projection and the convolution carry ``B`` and
    ``C`` of every group, ``[... B (G N); C (G N) ...]``. Where a layer
    is one sublayer (``mixer_only``) there is one norm a layer and no
    MLP, and ``lm_moe_*`` are stacks over the expert layers: the router
    on the hidden width and its selection-only bias, the projection into
    the latent and out of it, an expert's two matrices inside the latent
    (``exp_up`` ``[e, latent, f]``, ``exp_down`` ``[e, f, latent]``) and
    the one shared expert's two on the hidden width."""
    L, d, f = spec.n_layer, spec.d_model, spec.d_inner
    q, kv = spec.n_head * spec.d_key, spec.n_kv_head * spec.d_key
    out = collections.OrderedDict([
        ('lm_emb', ([spec.vocab_size, d], d, 'Emb')),
        ('lm_final_ln.w', ([d], None, 'FinalLN')),
        ('lm_stack_ln1.w', ([L, d], None, 'Ln1W')),
    ])
    if not spec.tie_embeddings:
        # held [V, d] like the embedding (gqa_param_shapes)
        out['lm_head.w'] = ([spec.vocab_size, d], d, 'Head')
    if not spec.mixer_only:
        out.update([
            ('lm_stack_ln2.w', ([L, d], None, 'Ln2W')),
            ('lm_stack_mlp_gate.w', ([L, d, f], d, 'MlpGate')),
            ('lm_stack_mlp_up.w', ([L, d, f], d, 'MlpUp')),
            ('lm_stack_mlp_down.w', ([L, f, d], f, 'MlpDown')),
        ])
    n = len(spec.layers_of(ATTENTION))
    if n:
        out.update([
            ('lm_attn_q.w', ([n, d, q], d, 'SlfQ')),
            ('lm_attn_k.w', ([n, d, kv], d, 'SlfK')),
            ('lm_attn_v.w', ([n, d, kv], d, 'SlfV')),
            ('lm_attn_o.w', ([n, q, d], q, 'SlfO')),
        ])
    n = len(spec.layers_of(MAMBA))
    if n:
        heads, inner = spec.ssm_heads, spec.ssm_inner
        conv = spec.ssm_conv_width
        out.update([
            ('lm_mamba_in.w', ([n, d, inner + conv + heads], d, 'SsmIn')),
            ('lm_mamba_conv.w', ([n, spec.ssm_conv, conv], spec.ssm_conv,
                                 'SsmConvW')),
            ('lm_mamba_conv.b', ([n, conv], 0, 'SsmConvB')),
            ('lm_mamba_dt.b', ([n, heads], 0, 'SsmDtB')),
            ('lm_mamba_a_log', ([n, heads], 0, 'SsmALog')),
            ('lm_mamba_d', ([n, heads], None, 'SsmD')),
            ('lm_mamba_norm.w', ([n, inner], None, 'SsmNorm')),
            ('lm_mamba_out.w', ([n, inner, d], inner, 'SsmOut')),
        ])
    n = len(spec.layers_of(MOE))
    if n:
        e, lat, sh = spec.experts_held, spec.moe_latent, spec.d_inner_shared
        out.update([
            ('lm_moe_router.w', ([n, d, spec.n_experts], d, 'Router')),
            ('lm_moe_router.b', ([n, spec.n_experts], 0, 'RouterBias')),
            ('lm_moe_lat_in.w', ([n, d, lat], d, 'LatIn')),
            ('lm_moe_lat_out.w', ([n, lat, d], lat, 'LatOut')),
            ('lm_moe_exp_up.w', ([n, e, lat, f], lat, 'ExpUp')),
            ('lm_moe_exp_down.w', ([n, e, f, lat], f, 'ExpDown')),
            ('lm_moe_shr_up.w', ([n, d, sh], d, 'ShrUp')),
            ('lm_moe_shr_down.w', ([n, sh, d], sh, 'ShrDown')),
        ])
    return out


def delta_param_shapes(spec):
    """``moe_param_shapes`` of the delta_hybrid block. The two norms,
    the router, the routed experts and the shared expert with its gate
    are stacks over all layers; ``lm_attn_*`` over the full-attention
    layers in order (``q`` and ``gate``, a query and an output gate a
    head, kept as their transposes ``[n, heads x d, d_model]`` as
    ``gqa_param_shapes`` keeps its query projection; ``q_ln`` and
    ``k_ln`` the per-head norms' gains), ``lm_gdn_*`` over the
    linear-attention layers: ``in`` projects to ``[q (G K); k (G K); v
    (H V); z (H V)]`` (the published ``in_proj_qkvz`` interleaves the
    four by key head: a layout of trained weights, the same matrix under
    a permutation of its columns), ``ba`` to ``[b (H); a (H)]``,
    ``conv`` the depthwise taps ``[taps, 2 G K + H V]`` (no bias),
    ``dt.b`` and ``a_log`` a head each, ``norm`` the gated norm's plain
    gain over a head's V and ``out`` the output projection. A
    zero-centred gain is a fan-in of 0: a float32 vector that starts at
    zero (the norm multiplies by 1 + it); the gated norm's plain gain a
    fan-in of None (ones)."""
    L, d, f = spec.n_layer, spec.d_model, spec.d_inner
    q, kv = spec.n_head * spec.d_key, spec.n_kv_head * spec.d_key
    e, sh = spec.experts_held, spec.d_inner_shared
    out = collections.OrderedDict([
        ('lm_emb', ([spec.vocab_size, d], d, 'Emb')),
        ('lm_head.w', ([spec.vocab_size, d], d, 'Head')),
        ('lm_final_ln.w', ([d], 0, 'FinalLN')),
        ('lm_stack_ln1.w', ([L, d], 0, 'Ln1W')),
        ('lm_stack_ln2.w', ([L, d], 0, 'Ln2W')),
    ])
    n = len(spec.layers_of(FULL))
    if n:
        out.update([
            ('lm_attn_q.w', ([n, q, d], d, 'SlfQ')),
            ('lm_attn_gate.w', ([n, q, d], d, 'SlfGate')),
            ('lm_attn_k.w', ([n, d, kv], d, 'SlfK')),
            ('lm_attn_v.w', ([n, d, kv], d, 'SlfV')),
            ('lm_attn_o.w', ([n, q, d], q, 'SlfO')),
            ('lm_attn_q_ln.w', ([n, spec.d_key], 0, 'SlfQLn')),
            ('lm_attn_k_ln.w', ([n, spec.d_key], 0, 'SlfKLn')),
        ])
    n = len(spec.layers_of(LINEAR))
    if n:
        heads, inner, conv = spec.ssm_heads, spec.ssm_inner, \
            spec.ssm_conv_width
        out.update([
            ('lm_gdn_in.w', ([n, d, conv + inner], d, 'GdnIn')),
            ('lm_gdn_ba.w', ([n, d, 2 * heads], d, 'GdnBA')),
            ('lm_gdn_conv.w', ([n, spec.ssm_conv, conv], spec.ssm_conv,
                               'GdnConvW')),
            ('lm_gdn_dt.b', ([n, heads], 0, 'GdnDtB')),
            ('lm_gdn_a_log', ([n, heads], 0, 'GdnALog')),
            ('lm_gdn_norm.w', ([n, spec.ssm_head_dim], None, 'GdnNorm')),
            ('lm_gdn_out.w', ([n, inner, d], inner, 'GdnOut')),
        ])
    out.update([
        ('lm_moe_router.w', ([L, d, spec.n_experts], d, 'Router')),
        ('lm_moe_exp_gate.w', ([L, e, d, f], d, 'ExpGate')),
        ('lm_moe_exp_up.w', ([L, e, d, f], d, 'ExpUp')),
        ('lm_moe_exp_down.w', ([L, e, f, d], f, 'ExpDown')),
        ('lm_moe_shr_gate.w', ([L, d, sh], d, 'ShrGate')),
        ('lm_moe_shr_up.w', ([L, d, sh], d, 'ShrUp')),
        ('lm_moe_shr_down.w', ([L, sh, d], sh, 'ShrDown')),
        ('lm_moe_shr_sg.w', ([L, d], d, 'ShrSg')),
    ])
    return out


def block_param_shapes(spec):
    """{name: (shape, fan-in, op input slot)} of a block's weights
    (every block but 'post_ln'): a fan-in of None is a norm's gain
    (float32 ones), of 0 a bias (float32 zeros), anything else a matrix
    kept at ``spec.dtype`` and drawn N(0, 1 / fan-in)."""
    return {'latent_moe': latent_param_shapes,
            'shortcut_moe': shortcut_param_shapes,
            'gqa_moe': gqa_param_shapes,
            'ssm_hybrid': ssm_param_shapes,
            'delta_hybrid': delta_param_shapes}.get(spec.block,
                                                moe_param_shapes)(spec)


def _moe_params(spec):
    inputs = {}
    for name, (shape, fan_in, slot) in block_param_shapes(spec).items():
        init = Constant(1.0) if fan_in is None else \
            Constant(0.0) if fan_in == 0 else Normal(0., fan_in ** -0.5)
        inputs[slot] = [layers.create_parameter(
            shape=shape.held if isinstance(shape, HeldTransposed) else shape,
            dtype=spec.dtype if fan_in else 'float32',
            name=name, attr=ParamAttr(name=name, initializer=init))]
    return inputs


def _block_attrs(spec, block_size):
    attrs = {'n_head': spec.n_head, 'block_size': int(block_size)}
    if spec.block == 'parallel_moe':
        attrs.update({
            'block': spec.block, 'windows': spec.windows(),
            'rotary': [int(r) for r in spec.rotary()],
            'rope_theta': spec.rope_theta, 'norm_eps': spec.norm_eps,
            'top_k': spec.experts_per_token,
            'first_expert': spec.first_expert,
            'logit_scale': spec.logit_scale})
    if spec.block in ('latent_moe', 'shortcut_moe'):
        lead, period, n_periods, tail = spec.layer_plan()
        if spec.zero_experts:
            attrs['zero_experts'] = spec.zero_experts
        attrs.update({
            'block': spec.block, 'norm_eps': spec.norm_eps,
            'top_k': spec.experts_per_token,
            'first_expert': spec.first_expert,
            'lead': list(lead), 'period': list(period),
            'n_periods': n_periods, 'tail': list(tail),
            'window': spec.sliding_window,
            'index_n_heads': spec.index_n_heads,
            'index_topk': spec.index_topk,
            'lora_rescale': int(spec.lora_rescale),
            'attn_gate': int(spec.attn_gate),
            'routed_scale': spec.routed_scale})
        if spec.index_rope_interleave:
            # the other form is the default and has no attr
            attrs['index_rope_interleave'] = 1
        for kind, tag in ((FULL, 'full'), (SLIDING, 'swa')):
            if kind in spec.latent:
                a = spec.latent[kind]
                attrs[tag + '_shape'] = [a.n_head, a.d_nope, a.d_rope]
                attrs[tag + '_theta'] = a.rope_theta
                attrs[tag + '_rope_freq'] = [
                    float(f) for f in a.rope_frequencies()]
                attrs[tag + '_softmax_mult'] = a.softmax_multiplier()
    if spec.block == 'gqa_moe':
        lead, period, n_periods, tail = spec.layer_plan()
        attrs.update({
            'block': spec.block, 'norm_eps': spec.norm_eps,
            'top_k': spec.experts_per_token,
            'first_expert': spec.first_expert,
            'lead': list(lead), 'period': list(period),
            'n_periods': n_periods, 'tail': list(tail),
            'window': spec.sliding_window,
            'pools': [pool.slot for pool in spec.page_pools()]})
        for kind, (freq, factor) in spec.rope_tables().items():
            tag = 'full' if kind == FULL else 'sliding'
            attrs[tag + '_rope_freq'] = [float(f) for f in freq]
            # cos and sin times the factor, on q and on k: the scores
            # times its square
            attrs[tag + '_softmax_mult'] = factor * factor
    if spec.block == 'ssm_hybrid':
        lead, period, n_periods, tail = spec.layer_plan()
        attrs.update({
            'block': spec.block, 'norm_eps': spec.norm_eps,
            'lead': list(lead), 'period': list(period),
            'n_periods': n_periods, 'tail': list(tail),
            'ssm_heads': spec.ssm_heads, 'ssm_state': spec.ssm_state,
            'ssm_chunk': spec.ssm_chunk, 'embed_scale': spec.embed_scale,
            'residual_scale': spec.residual_scale,
            'attn_scale': spec.attn_scale,
            'logit_scale': spec.logit_scale,
            'ssm_groups': spec.ssm_groups,
            'mixer_only': int(spec.mixer_only),
            'top_k': spec.experts_per_token,
            'first_expert': spec.first_expert,
            'routed_scale': spec.routed_scale})
    if spec.block == 'delta_hybrid':
        lead, period, n_periods, tail = spec.layer_plan()
        attrs.update({
            'block': spec.block, 'norm_eps': spec.norm_eps,
            'lead': list(lead), 'period': list(period),
            'n_periods': n_periods, 'tail': list(tail),
            'ssm_heads': spec.ssm_heads, 'ssm_state': spec.ssm_state,
            'ssm_chunk': spec.ssm_chunk, 'ssm_groups': spec.ssm_groups,
            'top_k': spec.experts_per_token,
            'first_expert': spec.first_expert,
            'rope_freq': [float(f) for f in yarn_frequencies(
                spec.rotary_dim, spec.rope_theta)]})
    return attrs


def _arenas(spec, num_blocks, block_size, kv_dtype='float32'):
    """{op input slot: page arena} at ``kv_dtype``, one per cache kind
    of the block (``LMSpec.cache_kinds``): ``[layers of the kind, NB,
    bs, row width]`` (a kind with a size a sequence: ``[layers of the
    kind, slots + 1, slot shape]``), token-major inside a page and the row one
    lane-dense minor axis (K or V of all KV heads merged; a latent row;
    an index key), which is what lets the paged ops write a row in
    place (ops/paged_decode_ops.py). Axes 0 and 1 are layer and page for
    every arena — all that read_pages/write_pages and the handoff
    index by — and a page id is the same page of every arena of its
    pool (``num_blocks``: {pool name: pages}). Quantized
    dtypes (int8 / fp8) additionally get
    per-(page, slot, head) fp32 scale arenas ``[L, NB, bs, H]`` — one
    scale per written K/V row, so a page's stored bits are a pure
    function of the tokens written into it (the bit-consistency
    invariant) and prefix-cache sharing carries the scales for free
    (same physical page index)."""
    from ...quant.core import kv_quantized

    def arena(name, shape, dtype, fill):
        return layers.create_parameter(
            shape=shape, dtype=dtype, name=name,
            attr=ParamAttr(name=name, initializer=Constant(fill),
                           trainable=False))
    # a kind with a size a sequence: ``[layers, slots + 1, ...]``, the
    # last slot a spare for the rows that hold none, at its own dtype
    out = collections.OrderedDict(
        (kind.slot, arena(
            kind.name, [len(kind.layers),
                        num_blocks[kind.pool] + bool(kind.per_seq)]
            + list(kind.unit_shape(block_size)),
            kind.dtype or kv_dtype, 0.0))
        for kind in spec.cache_kinds())
    if kv_quantized(kv_dtype):
        sshape = [spec.n_layer, num_blocks[''], block_size, spec.n_head]
        for name, slot in (('lm_kscale', 'KScale'), ('lm_vscale', 'VScale')):
            out[slot] = arena(name, sshape, 'float32', 1.0)
    return out


def _common_inputs(params, arenas):
    return dict(params, **{slot: [a] for slot, a in arenas.items()})


def _arena_outputs(arenas):
    return {slot + 'Out': [a] for slot, a in arenas.items()}


def _moe_stats_output(helper, spec, outputs):
    """Give a routed block's program its MoeStats output (per routed layer:
    choices that landed on an expert held here, rows on the busiest of
    them, experts any row chose, row tiles the routed product ran; and,
    where the router has identity experts, the live rows by how many
    real experts each chose, 0 .. ``experts_per_token``) and
    return its name; None for a block that routes nothing."""
    if not spec.n_experts:
        return None
    stats = helper.create_variable_for_type_inference('int32')
    stats.shape = (len(spec.layers_of(MOE)) if spec.block == 'ssm_hybrid'
                   else spec.n_layer - spec.dense_layers,
                   4 + (spec.experts_per_token + 1
                        if spec.zero_experts else 0))
    outputs['MoeStats'] = [stats]
    return stats.name


def build_lm_programs(spec, max_batch, block_size, num_blocks,
                      pages_per_seq, spec_k=0, kv_dtype='float32'):
    """Returns DecodePrograms. ``capacity`` (= pages_per_seq *
    block_size) bounds prompt_len + max_new_tokens per sequence.
    ``spec_k > 0`` additionally builds the speculative-decoding
    verify Program ([max_batch, spec_k+1], one fixed signature).
    ``kv_dtype`` (fp32 default / bf16 / int8 / fp8) sets the arena
    storage dtype; quantized arenas carry fp32 scale arenas alongside
    and dequantize inside the shared paged-attention path, so every
    feed signature is unchanged — the zero-recompile contract holds at
    any dtype."""
    from ...quant.core import kv_quantized, resolve_kv_dtype
    kv_dtype = resolve_kv_dtype(kv_dtype)
    capacity = int(pages_per_seq) * int(block_size)
    spec_k = int(spec_k)
    if spec.block != 'post_ln' and (spec_k > 0 or kv_quantized(kv_dtype)):
        # neither has a test against these blocks' references yet; a
        # block that keeps a state has a reason of its own
        raise NotImplementedError(
            "block=%r runs without speculation (%s) and with an "
            "unquantized KV arena (got spec_k=%d, kv_dtype=%s)"
            % (spec.block, spec.refusal('speculation'), spec_k, kv_dtype))
    attrs = _block_attrs(spec, block_size)
    num_blocks = pages_by_pool(spec, num_blocks)
    pools = spec.page_pools()
    if len(pools) > 1 and spec_k > 0:
        raise NotImplementedError('speculation under one block table')
    startup = Program()
    prefill_prog = Program()
    decode_prog = Program()

    def tables_of(prefix, slot):
        """{op input slot: [feed]} of the block tables, one a pool: the
        first under the names one table always had; a pool of slots a
        sequence feeds the one slot index a row."""
        return {slot + pool.slot: [layers.data(
            name=prefix + pool.feed,
            shape=[pool.table_width(pages_per_seq)], dtype='int32')]
            for pool in pools}

    with program_guard(prefill_prog, startup):
        params = _lm_params(spec, capacity)
        arenas = _arenas(spec, num_blocks, block_size, kv_dtype)
        ids = layers.data(name='pf_ids', shape=[-1], dtype='int64')
        length = layers.data(name='pf_len', shape=[], dtype='int32')
        cached = layers.data(name='pf_cached', shape=[], dtype='int32')
        tables = tables_of('pf_table', 'BlockTable')
        temp = layers.data(name='pf_temp', shape=[], dtype='float32')
        seed = layers.data(name='pf_seed', shape=[], dtype='int32')
        helper = LayerHelper('paged_prefill', name='paged_prefill')
        nxt = helper.create_variable_for_type_inference('int64')
        nxt.shape = (1,)
        inputs = _common_inputs(params, arenas)
        inputs.update({'Ids': [ids], 'Len': [length], 'Cached': [cached],
                       'Temp': [temp], 'Seed': [seed]})
        inputs.update(tables)
        outputs = dict(_arena_outputs(arenas), NextToken=[nxt])
        prefill_stats_fetch = _moe_stats_output(helper, spec, outputs)
        helper.append_op(type='paged_prefill', inputs=inputs,
                         outputs=outputs, attrs=attrs)
        prefill_fetch = nxt.name

    with program_guard(decode_prog, startup):
        params = _lm_params(spec, capacity)
        arenas = _arenas(spec, num_blocks, block_size, kv_dtype)
        tokens = layers.data(name='dec_tokens', shape=[], dtype='int64')
        lens = layers.data(name='dec_lens', shape=[], dtype='int32')
        tables = tables_of('dec_tables', 'BlockTables')
        temps = layers.data(name='dec_temps', shape=[], dtype='float32')
        seeds = layers.data(name='dec_seeds', shape=[], dtype='int32')
        helper = LayerHelper('paged_decode_step', name='paged_decode_step')
        nxt = helper.create_variable_for_type_inference('int64')
        nxt.shape = (max_batch,)
        inputs = _common_inputs(params, arenas)
        inputs.update({'Tokens': [tokens], 'SeqLens': [lens],
                       'Temps': [temps], 'Seeds': [seeds]})
        inputs.update(tables)
        outputs = dict(_arena_outputs(arenas), NextTokens=[nxt])
        stats_fetch = _moe_stats_output(helper, spec, outputs)
        helper.append_op(type='paged_decode_step', inputs=inputs,
                         outputs=outputs, attrs=attrs)
        decode_fetch = nxt.name

    verify_prog, verify_fetch = None, None
    if spec_k > 0:
        verify_prog = Program()
        with program_guard(verify_prog, startup):
            params = _lm_params(spec, capacity)
            arenas = _arenas(spec, num_blocks, block_size, kv_dtype)
            tokens = layers.data(name='sv_tokens', shape=[spec_k + 1],
                                 dtype='int64')
            lens = layers.data(name='sv_lens', shape=[], dtype='int32')
            tables = layers.data(name='sv_tables', shape=[pages_per_seq],
                                 dtype='int32')
            temps = layers.data(name='sv_temps', shape=[],
                                dtype='float32')
            seeds = layers.data(name='sv_seeds', shape=[], dtype='int32')
            helper = LayerHelper('paged_spec_verify',
                                 name='paged_spec_verify')
            nxt = helper.create_variable_for_type_inference('int64')
            nxt.shape = (max_batch, spec_k + 1)
            inputs = _common_inputs(params, arenas)
            inputs.update({'Tokens': [tokens], 'SeqLens': [lens],
                           'BlockTables': [tables], 'Temps': [temps],
                           'Seeds': [seeds]})
            outputs = dict(_arena_outputs(arenas), NextTokens=[nxt])
            helper.append_op(type='paged_spec_verify', inputs=inputs,
                             outputs=outputs,
                             attrs=dict(attrs, k=spec_k))
            verify_fetch = nxt.name

    param_names = sorted(v[0].name for v in params.values())
    arena_names = tuple(a.name for a in arenas.values())
    return DecodePrograms(
        startup=startup, prefill=prefill_prog, decode=decode_prog,
        verify=verify_prog,
        prefill_fetch=prefill_fetch, decode_fetch=decode_fetch,
        verify_fetch=verify_fetch,
        param_names=param_names,
        arena_names=arena_names,
        capacity=capacity, kv_dtype=kv_dtype, stats_fetch=stats_fetch,
        prefill_stats_fetch=prefill_stats_fetch)


def random_weights(spec, seed=0):
    """Deterministic numpy weight set matching build_lm_programs'
    parameter names — handy for tests that need two engines to share
    identical weights (float32; an engine keeps each at its declared
    dtype)."""
    rng = np.random.RandomState(seed)
    if spec.block != 'post_ln':
        # a bias is drawn small, not zero, so that it changes choices
        return {name: np.ones(shape, 'float32') if fan_in is None else
                (rng.randn(*shape) * (fan_in ** -0.5 if fan_in else 0.05)
                 ).astype('float32')
                for name, (shape, fan_in, _) in
                block_param_shapes(spec).items()}
    d, dk, dv = spec.d_model, spec.d_key, spec.d_value
    h, L = spec.n_head, spec.n_layer

    def mat(*shape):
        fan = shape[-2] if len(shape) >= 2 else shape[-1]
        return (rng.randn(*shape) * (1.0 / np.sqrt(fan))) \
            .astype('float32')

    w = {
        'lm_emb': (rng.randn(spec.vocab_size, d) * d ** -0.5)
        .astype('float32'),
        'lm_out_proj.w': mat(d, spec.vocab_size),
        'lm_stack_slf_q.w': mat(L, d, dk * h),
        'lm_stack_slf_k.w': mat(L, d, dk * h),
        'lm_stack_slf_v.w': mat(L, d, dv * h),
        'lm_stack_slf_o.w': mat(L, dv * h, d),
        'lm_stack_ffn_1.w': mat(L, d, spec.d_inner),
        'lm_stack_ffn_1.b': np.zeros((L, spec.d_inner), 'float32'),
        'lm_stack_ffn_2.w': mat(L, spec.d_inner, d),
        'lm_stack_ffn_2.b': np.zeros((L, d), 'float32'),
        'lm_stack_ln1.w': np.ones((L, d), 'float32'),
        'lm_stack_ln1.b': np.zeros((L, d), 'float32'),
        'lm_stack_ln2.w': np.ones((L, d), 'float32'),
        'lm_stack_ln2.b': np.zeros((L, d), 'float32'),
    }
    return w
