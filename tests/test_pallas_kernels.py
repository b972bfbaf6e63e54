"""Pallas kernel parity in interpret mode (CPU): flash attention
forward AND the new FA2 backward kernels vs the XLA reference VJP, and
the fused layer_norm kernel. On-chip parity of the compiled kernels is
chip_smoke.py's kernels leg."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PALLAS_INTERPRET', '1')


def _qkv(b=1, h=2, t=256, d=128, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize('d', [64, 128])
@pytest.mark.parametrize('causal', [False, True])
def test_flash_forward_parity(causal, d):
    # d=64 is the base bench model's head dim — the shape class the
    # dispatch gate admits since it widened from %128 to %64
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       _reference)
    q, k, v = _qkv(d=d)
    scale = q.shape[-1] ** -0.5
    got = flash_attention(q, k, v, causal=causal, block_q=128)
    want = _reference(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize('d', [64, 128])
@pytest.mark.parametrize('causal', [False, True])
def test_flash_backward_parity(causal, d):
    """The FA2 two-kernel backward (dq / dk+dv, driven by the forward's
    saved logsumexp) must match the XLA reference VJP."""
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       _reference)
    q, k, v = _qkv(seed=1, d=d)
    scale = q.shape[-1] ** -0.5

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=128)
        return jnp.sum(out * jnp.cos(out))   # non-trivial cotangent

    def loss_ref(q, k, v):
        out = _reference(q, k, v, causal, scale)
        return jnp.sum(out * jnp.cos(out))

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, 'qkv'):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg='d%s mismatch' % name)


def test_flash_backward_xla_fallback_matches(monkeypatch):
    from paddle_tpu.ops.pallas import flash_attention as fa
    q, k, v = _qkv(seed=2)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          block_q=128) ** 2)

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv('PADDLE_TPU_PALLAS_BWD', '0')
    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-4)


def test_fused_layer_norm_kernel_parity(monkeypatch):
    from paddle_tpu.ops.pallas.layer_norm import _ln_pallas, _ln_reference
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 1024), jnp.float32)
    g = jnp.asarray(rng.rand(1024) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(1024), jnp.float32)
    got = _ln_pallas(x, g, b, 1e-5)
    want = _ln_reference(x, g, b, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_masked_parity(causal):
    """r5: per-example kv_len padding masks (VERDICT r4 next-#3/#4) —
    forward AND backward must match the masked XLA reference, including
    rows whose length is far below the padded T (whole key blocks
    skipped by the run predicate)."""
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       _reference)
    q, k, v = _qkv(b=3, h=2, t=256, d=64, seed=3)
    lens = jnp.asarray([256, 130, 7], jnp.int32)
    scale = q.shape[-1] ** -0.5

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              kv_len=lens)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = _reference(q, k, v, causal, scale, kv_len=lens)
        return jnp.sum(out * jnp.cos(out))

    got_o = flash_attention(q, k, v, causal=causal, block_q=128,
                            kv_len=lens)
    want_o = _reference(q, k, v, causal, scale, kv_len=lens)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, 'qkv'):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg='d%s mismatch' % name)


def test_flash_bf16_dots_stay_close():
    """r5: the kernels no longer upcast tiles to fp32 — bf16 inputs run
    bf16×bf16→fp32 MXU dots. Parity tolerance is bf16-level but the
    softmax recurrence stays fp32, so results track the fp32 reference
    to ~1e-2."""
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       _reference)
    q, k, v = _qkv(t=256, d=64, seed=4)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = flash_attention(qb, kb, vb, causal=True,
                          block_q=128).astype(jnp.float32)
    want = _reference(q, k, v, True, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_fused_attention_masked_dispatches_pallas(monkeypatch):
    """The dispatch gate admits key_length now: a variable-length batch
    at seq>=512 must take the Pallas path (not silently fall back) and
    match the unfused reference."""
    monkeypatch.setenv('PADDLE_TPU_USE_PALLAS', '1')
    import paddle_tpu.ops.attention_ops as ao
    from paddle_tpu.ops.pallas import flash_attention as fa
    calls = []
    orig = fa.flash_attention

    def spy(*a, **kw):
        calls.append(kw.get('kv_len') is not None)
        return orig(*a, **kw)

    monkeypatch.setattr(
        'paddle_tpu.ops.pallas.flash_attention.flash_attention', spy)
    rng = np.random.RandomState(5)
    b, t, hd, nh = 2, 512, 128, 2
    q3, k3, v3 = (jnp.asarray(rng.randn(b, t, hd), jnp.float32)
                  for _ in range(3))
    lens = jnp.asarray([512, 300], jnp.int32)
    got = ao.fused_attention(q3, k3, v3, nh, causal=True, key_length=lens)
    assert calls == [True], 'Pallas path not taken for masked batch'
    monkeypatch.setenv('PADDLE_TPU_USE_PALLAS', '0')
    want = ao.fused_attention(q3, k3, v3, nh, causal=True, key_length=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_fused_batch_norm_forward_parity():
    """r5 one-pass BN kernel (VERDICT r4 next-#2): y/mean/var must match
    the two-pass jnp form, fp32 stats, for NHWC 4-D and [N,C] inputs."""
    from paddle_tpu.ops.pallas.batch_norm import (fused_batch_norm_train,
                                                  _bn_reference)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 8, 8, 64) * 2 + 1, jnp.float32)
    scale = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(64), jnp.float32)
    y, m, v = fused_batch_norm_train(x, scale, bias, 1e-5, block_r=64)
    wy, wm, wv = _bn_reference(x.reshape(-1, 64), scale, bias, 1e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(wm), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(wv), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64),
                               np.asarray(wy), rtol=1e-4, atol=1e-4)


def test_fused_batch_norm_backward_parity():
    """custom_vjp BN gradient vs jax.grad through the reference form."""
    from paddle_tpu.ops.pallas.batch_norm import (fused_batch_norm_train,
                                                  _bn_reference)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(256, 64), jnp.float32)
    scale = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(64), jnp.float32)

    def loss_pallas(x, s, b):
        y, _, _ = fused_batch_norm_train(x, s, b, 1e-5, block_r=64)
        return jnp.sum(y * jnp.cos(y))

    def loss_ref(x, s, b):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=0)
        var = jnp.var(xf, axis=0)
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-5) * s + b
        return jnp.sum(y * jnp.cos(y))

    got = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, scale, bias)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    for g, w, name in zip(got, want, ['x', 'scale', 'bias']):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg='d%s mismatch' % name)


def test_batch_norm_ir_pallas_matches_default(monkeypatch):
    """The batch_norm lowering under PADDLE_TPU_BN_PALLAS=1 must train
    identically (same loss trajectory) to the default jnp path."""
    import paddle_tpu as fluid

    def train(env_on):
        if env_on:
            monkeypatch.setenv('PADDLE_TPU_BN_PALLAS', '1')
        else:
            monkeypatch.delenv('PADDLE_TPU_BN_PALLAS', raising=False)
        fluid.reset_default_programs()
        fluid.global_scope().clear()
        x = fluid.layers.data(name='x', shape=[8, 8, 8], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.batch_norm(input=x, data_layout='NCHW')
        h = fluid.layers.pool2d(h, pool_size=8, pool_type='avg')
        pred = fluid.layers.fc(input=h, size=1)
        cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(5):
            xs = rng.randn(16, 8, 8, 8).astype('f')
            ys = rng.randn(16, 1).astype('f')
            loss, = exe.run(feed={'x': xs, 'y': ys}, fetch_list=[cost])
            losses.append(float(np.asarray(loss).reshape(())))
        return losses

    base = train(False)
    pallas = train(True)
    np.testing.assert_allclose(pallas, base, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- ragged paged attention
def _paged_case(b=3, h=2, nb=16, bs=8, p=4, d=16, seed=5):
    rng = np.random.RandomState(seed)
    # the engine's arena shape [L, NB, bs, H*D], one layer
    k_pages = jnp.asarray(rng.randn(1, nb, bs, h * d), jnp.float32)
    v_pages = jnp.asarray(rng.randn(1, nb, bs, h * d), jnp.float32)
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    # distinct physical pages per sequence, deliberately out of order
    perm = rng.permutation(nb)[:b * p].reshape(b, p)
    tables = jnp.asarray(perm, jnp.int32)
    lens = jnp.asarray([1, 9, 25], jnp.int32)[:b]   # ragged, page-crossing
    return q, k_pages, v_pages, tables, lens


def test_paged_attention_parity():
    """The blocked form must match the dense reference across mixed
    lengths."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_blocked, paged_attention_reference)
    q, kp, vp, tables, lens = _paged_case()
    got = paged_attention_blocked(q, kp, vp, tables, lens)
    want = paged_attention_reference(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_paged_attention_ignores_unowned_pages():
    """Entries past a sequence's length (including the >= NB 'no page'
    sentinel) must not leak into the output."""
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_attention_blocked
    q, kp, vp, tables, lens = _paged_case()
    base = np.asarray(paged_attention_blocked(q, kp, vp, tables, lens))
    # scribble over every table entry beyond the owned pages
    t2 = np.asarray(tables).copy()
    nb, bs = kp.shape[1], kp.shape[2]
    for i, n in enumerate(np.asarray(lens)):
        owned = (int(n) + bs - 1) // bs
        t2[i, owned:] = nb + 7
    got = np.asarray(paged_attention_blocked(
        q, kp, vp, jnp.asarray(t2, jnp.int32), lens))
    np.testing.assert_array_equal(base, got)
