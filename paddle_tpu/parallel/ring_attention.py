"""Ring attention: exact attention over sequence-sharded inputs.

Long-context design (SURVEY.md §2.4): Q/K/V are sharded over the 'sp' mesh
axis on the time dimension. Each step computes a local block of scores
while K/V blocks rotate around the ring via ppermute, overlapping compute
with ICI transfers; running max/denominator accumulators keep the softmax
exact (the flash-attention recurrence, distributed).
"""

import jax
import jax.numpy as jnp


def _block_attn(q, k, v, bias=None):
    """One block of scores -> (unnormalized out, running max, denom)."""
    s = jnp.einsum('...qd,...kd->...qk', q, k)
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum('...qk,...kd->...qd', p, v)
    return o, m, l


def ring_attention(q, k, v, axis_name='sp', causal=False, scale=None,
                   kv_len=None):
    """Exact attention with K/V rotating over `axis_name`.

    q, k, v: [batch, heads, t_local, d] — the per-shard slices.
    kv_len: optional [batch] int — GLOBAL valid key count per example
    (padding masks, r5): key positions ≥ kv_len[b] contribute -1e30
    bias, so variable-length batches stay exact under sequence
    parallelism too. Returns [batch, heads, t_local, d].
    """
    n = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q = q * scale
    t_local = q.shape[-2]

    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_bias(kv_idx):
        # global positions: q_pos = my_idx*t + i ; k_pos = kv_idx*t + j
        qi = my_idx * t_local + jnp.arange(t_local)[:, None]
        kj = kv_idx * t_local + jnp.arange(t_local)[None, :]
        bias = None
        if causal:
            bias = jnp.where(qi >= kj, 0.0, -1e30)        # [tq, tk]
        if kv_len is not None:
            # [B, 1, tq, tk] — broadcasts over heads; finite -1e30
            # keeps the m/l recurrence NaN-free on fully-masked blocks
            key_ok = kj[None, :, :] < kv_len.reshape(-1, 1, 1)
            kbias = jnp.where(key_ok, 0.0, -1e30)[:, None, :, :]
            bias = kbias if bias is None else bias[None, None] + kbias
        return bias

    def step(carry, _):
        o_acc, m_acc, l_acc, kv_k, kv_v, kv_idx = carry
        bias = block_bias(kv_idx)
        o_b, m_b, l_b = _block_attn(q, kv_k, kv_v, bias)
        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_b - m_new)
        o_acc = o_acc * alpha + o_b * beta
        l_acc = l_acc * alpha + l_b * beta
        kv_k = jax.lax.ppermute(kv_k, axis_name, perm)
        kv_v = jax.lax.ppermute(kv_v, axis_name, perm)
        kv_idx = jax.lax.ppermute(kv_idx, axis_name, perm)
        return (o_acc, m_new, l_acc, kv_k, kv_v, kv_idx), None

    # Derive accumulators from q so they carry q's varying ('sp') manual
    # axis — fresh constants would be unvarying and break the scan carry.
    o0 = jnp.zeros_like(q)
    m0 = jnp.full_like(q[..., :1], -1e30)
    l0 = jnp.zeros_like(q[..., :1])
    carry = (o0, m0, l0, k, v, my_idx)
    (o, m, l, _, _, _), _ = jax.lax.scan(step, carry, None, length=n)
    return o / jnp.maximum(l, 1e-20)
