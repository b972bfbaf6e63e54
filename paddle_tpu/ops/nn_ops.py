"""NN ops: embedding, losses, dropout, normalization helpers.

Reference: paddle/fluid/operators/{lookup_table_op,cross_entropy_op,
softmax_with_cross_entropy_op,dropout_op,accuracy_op,...}.cc
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register
from .random_ops import keep_mask


def _fused_ce_enabled():
    # Read at TRACE time: the leg is frozen into the compiled graph, so
    # flipping it needs a fresh process (an A/B runs one a leg) or a
    # program-version bump — same contract as the other
    # env knobs (PADDLE_TPU_BN_COMPUTE, PADDLE_TPU_CONV_LAYOUT).
    return os.environ.get('PADDLE_TPU_FUSED_CE', '1') != '0'


@register('lookup_table')
def _lookup_table(ctx):
    """Embedding lookup (lookup_table_op.cc). On TPU a dense gather —
    XLA lowers to an efficient dynamic-gather on HBM.

    Sparse gradients (the reference's SelectedRows path,
    lookup_table_op.cc:119-127): when the executor planted a zero "row
    seed" for this lookup's output (is_sparse tables under an
    SGD/Adagrad minimize), the table itself is detached and the seed —
    shaped like the OUTPUT, O(batch x dim) — carries the gradient; the
    optimizer op scatters those rows into the table in place. A
    1e8-row CTR table then never materializes a 1e8-row grad."""
    from ..core.backward import SPARSE_SEED_PREFIX
    w = ctx.input('W')
    ids = ctx.input('Ids')
    squeeze_last = ids.ndim >= 2 and ids.shape[-1] == 1
    if squeeze_last:
        ids = ids.squeeze(-1)
    padding_idx = ctx.attr('padding_idx', -1)
    seed = ctx.env.get(SPARSE_SEED_PREFIX + ctx.op.output('Out'))
    if seed is not None:
        w = jax.lax.stop_gradient(w)
    out = jnp.take(w, ids, axis=0)
    if seed is not None:
        out = out + seed.reshape(out.shape)
    if padding_idx is not None and padding_idx >= 0:
        # mask AFTER the seed add so padding rows' seed grads zero out
        # exactly like the dense grad's masked rows
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    ctx.set_output('Out', out)


@register('cross_entropy')
def _cross_entropy(ctx):
    """-log(p[label]); soft_label supported (cross_entropy_op.cc)."""
    x = ctx.input('X')
    label = ctx.input('Label')
    eps = 1e-8
    if ctx.attr('soft_label', False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        if label.ndim == x.ndim and label.shape[-1] == 1:
            label = label.squeeze(-1)
        p = jnp.take_along_axis(x, label[..., None].astype(jnp.int32),
                                axis=-1)
        loss = -jnp.log(p + eps)
    ctx.set_output('Y', loss)


@register('softmax_with_cross_entropy')
def _softmax_xent(ctx):
    logits = ctx.input('Logits')
    label = ctx.input('Label')
    if ctx.attr('soft_label', False):
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.sum(label * log_probs, axis=-1, keepdims=True)
        ctx.set_output('Softmax', jnp.exp(log_probs))
        ctx.set_output('Loss', loss)
        return
    if label.ndim == logits.ndim and label.shape[-1] == 1:
        label = label.squeeze(-1)
    if _fused_ce_enabled():
        # hard labels: NLL == the eps=0 point of the fused label-
        # smoothed CE — same custom_vjp, so no fp32 [.., V] log-prob
        # tensor is materialized or saved (see _ls_ce_fused). The
        # Softmax output is computed independently and DCE'd by XLA
        # whenever unfetched; both outputs keep the logits dtype, as
        # the materializing form did.
        loss = _ls_ce_fused(logits, label, 0.0)[..., None] \
            .astype(logits.dtype)
        softmax = jax.nn.softmax(logits.astype(jnp.float32),
                                 axis=-1).astype(logits.dtype)
    else:
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.take_along_axis(log_probs,
                                    label[..., None].astype(jnp.int32),
                                    axis=-1)
        softmax = jnp.exp(log_probs)
    ignore_index = ctx.attr('ignore_index', -100)
    if ignore_index is not None and ignore_index >= 0:
        mask = (label[..., None] != ignore_index)
        loss = loss * mask.astype(loss.dtype)
    ctx.set_output('Softmax', softmax)
    ctx.set_output('Loss', loss)


@register('sigmoid_cross_entropy_with_logits')
def _sigmoid_xent(ctx):
    x = ctx.input('X')
    label = ctx.input('Label')
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ctx.set_output('Out', loss)


@register('square_error_cost')
def _square_error_cost(ctx):
    x = ctx.input('X')
    y = ctx.input('Y')
    ctx.set_output('Out', jnp.square(x - y))


@register('smooth_l1_loss')
def _smooth_l1(ctx):
    x = ctx.input('X')
    y = ctx.input('Y')
    sigma = ctx.attr('sigma', 1.0)
    sigma2 = sigma * sigma
    diff = x - y
    if ctx.has_input('InsideWeight'):
        diff = diff * ctx.input('InsideWeight')
    absd = jnp.abs(diff)
    loss = jnp.where(absd < 1.0 / sigma2, 0.5 * sigma2 * jnp.square(diff),
                     absd - 0.5 / sigma2)
    if ctx.has_input('OutsideWeight'):
        loss = loss * ctx.input('OutsideWeight')
    ctx.set_output('Diff', diff)
    if ctx.attr('last_dim_only', False):
        ctx.set_output('Out', jnp.sum(loss, axis=-1))
    else:
        ctx.set_output('Out', jnp.sum(loss,
                                      axis=tuple(range(1, loss.ndim)),
                                      keepdims=False)[..., None]
                       if loss.ndim > 1 else loss)


@register('dropout')
def _dropout(ctx):
    """dropout_op.cc semantics: train: out = x*mask (downgrade_in_infer)
    or x*mask/(1-p) (upscale_in_train); test: x*(1-p) or x."""
    x = ctx.input('X')
    p = ctx.attr('dropout_prob', 0.5)
    impl = ctx.attr('dropout_implementation', 'downgrade_in_infer')
    is_test = ctx.attr('is_test', False) or ctx.is_test
    if is_test:
        out = x * (1.0 - p) if impl == 'downgrade_in_infer' else x
        mask = jnp.ones_like(x)
    else:
        mask, kept = keep_mask(ctx.rng_key(), 1.0 - p, x.shape)
        mask = mask.astype(x.dtype)
        out = x * mask
        if impl == 'upscale_in_train' and p < 1.0:
            out = out / kept
    ctx.set_output('Mask', mask)
    ctx.set_output('Out', out)


@register('accuracy')
def _accuracy(ctx):
    """accuracy_op.cc: fraction of rows where any of top-k indices == label."""
    indices = ctx.input('Indices')
    label = ctx.input('Label')
    if label.ndim == 2 and label.shape[-1] == 1:
        label_cmp = label
    else:
        label_cmp = label[..., None]
    correct = jnp.any(indices == label_cmp, axis=-1)
    acc = jnp.mean(correct.astype(jnp.float32)).reshape(1)
    ctx.set_output('Accuracy', acc)
    ctx.set_output('Correct', jnp.sum(correct.astype(jnp.int32)).reshape(1))
    ctx.set_output('Total', jnp.asarray([indices.shape[0]], dtype=jnp.int32))


@register('auc')
def _auc(ctx):
    """Streaming-free AUC approximation over the batch (auc_op.cc)."""
    probs = ctx.input('Predict')
    label = ctx.input('Label').reshape(-1)
    pos_score = probs[:, 1] if probs.ndim == 2 and probs.shape[1] == 2 \
        else probs.reshape(-1)
    label_f = label.astype(jnp.float32)
    pos = label_f
    neg = 1.0 - label_f
    # rank-based AUC: P(score_pos > score_neg)
    diff = pos_score[:, None] - pos_score[None, :]
    wins = (diff > 0).astype(jnp.float32) + 0.5 * (diff == 0)
    num = jnp.sum(wins * pos[:, None] * neg[None, :])
    den = jnp.sum(pos) * jnp.sum(neg)
    ctx.set_output('AUC', (num / jnp.maximum(den, 1.0)).reshape(1))


@register('nce')
def _nce(ctx):
    """NCE via uniform negative sampling (nce_op.cc), fused sampled-softmax
    form: loss = -log σ(s_pos) - Σ log σ(-s_neg)."""
    x = ctx.input('Input')          # [b, d]
    label = ctx.input('Label')      # [b, 1]
    w = ctx.input('Weight')         # [V, d]
    b = ctx.input('Bias')           # [V, 1]
    num_neg = ctx.attr('num_neg_samples', 10)
    num_classes = ctx.attr('num_total_classes')
    ids = label.reshape(-1).astype(jnp.int32)
    pos_w = jnp.take(w, ids, axis=0)                    # [b, d]
    pos_b = jnp.take(b.reshape(-1), ids)                # [b]
    s_pos = jnp.sum(x * pos_w, axis=-1) + pos_b
    neg_ids = jax.random.randint(ctx.rng_key(), (num_neg,), 0, num_classes)
    neg_w = jnp.take(w, neg_ids, axis=0)                # [k, d]
    neg_b = jnp.take(b.reshape(-1), neg_ids)            # [k]
    s_neg = x @ neg_w.T + neg_b                         # [b, k]
    loss = -jax.nn.log_sigmoid(s_pos) - \
        jnp.sum(jax.nn.log_sigmoid(-s_neg), axis=-1)
    ctx.set_output('Cost', loss[:, None])


@register('l2_normalize')
def _l2_normalize(ctx):
    x = ctx.input('X')
    axis = ctx.attr('axis', -1)
    eps = ctx.attr('epsilon', 1e-12)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    ctx.set_output('Out', x / jnp.maximum(norm, eps))
    ctx.set_output('Norm', norm)


@register('maxout')
def _maxout(ctx):
    x = ctx.input('X')  # NCHW
    groups = ctx.attr('groups')
    n, c, h, w = x.shape
    out = x.reshape(n, c // groups, groups, h, w).max(axis=2)
    ctx.set_output('Out', out)


@register('im2sequence')
def _im2sequence(ctx):
    """im2sequence_op.cc: extract patches as a sequence (OCR models)."""
    x = ctx.input('X')  # NCHW
    kh, kw = ctx.attr('kernels')
    sh, sw = ctx.attr('strides', [1, 1])
    ph0, pw0, ph1, pw1 = ctx.attr('paddings', [0, 0, 0, 0])
    x = jnp.pad(x, ((0, 0), (0, 0), (ph0, ph1), (pw0, pw1)))
    n, c, h, w = x.shape
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), 'VALID',
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    # patches: [n, c*kh*kw, oh, ow] -> [n*oh*ow, c*kh*kw]
    out = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, c * kh * kw)
    ctx.set_output('Out', out)


@register('label_smooth')
def _label_smooth(ctx):
    x = ctx.input('X')
    eps = ctx.attr('epsilon', 0.1)
    k = x.shape[-1]
    if ctx.has_input('PriorDist'):
        prior = ctx.input('PriorDist')
        out = (1.0 - eps) * x + eps * prior
    else:
        out = (1.0 - eps) * x + eps / k
    ctx.set_output('Out', out)


@register('huber_loss')
def _huber_loss(ctx):
    x = ctx.input('X')
    y = ctx.input('Y')
    delta = ctx.attr('delta', 1.0)
    r = y - x
    absr = jnp.abs(r)
    loss = jnp.where(absr <= delta, 0.5 * jnp.square(r),
                     delta * (absr - 0.5 * delta))
    ctx.set_output('Residual', r)
    ctx.set_output('Out', loss)


@register('rank_loss')
def _rank_loss(ctx):
    label = ctx.input('Label')
    left = ctx.input('Left')
    right = ctx.input('Right')
    out = jnp.log1p(jnp.exp(left - right)) - label * (left - right)
    ctx.set_output('Out', out)


@register('margin_rank_loss')
def _margin_rank_loss(ctx):
    label = ctx.input('Label')
    x1 = ctx.input('X1')
    x2 = ctx.input('X2')
    margin = ctx.attr('margin', 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    ctx.set_output('Out', out)
    ctx.set_output('Activated', (out > 0).astype(x1.dtype))


@register('hinge_loss')
def _hinge_loss(ctx):
    logits = ctx.input('Logits')
    labels = ctx.input('Labels')
    ctx.set_output('Loss', jnp.maximum(
        0.0, 1.0 - (2.0 * labels - 1.0) * logits))


@register('log_loss')
def _log_loss(ctx):
    pred = ctx.input('Predicted')
    label = ctx.input('Labels')
    eps = ctx.attr('epsilon', 1e-7)
    ctx.set_output('Loss', -label * jnp.log(pred + eps) -
                   (1.0 - label) * jnp.log(1.0 - pred + eps))


@register('bilinear_tensor_product')
def _bilinear_tensor_product(ctx):
    x = ctx.input('X')  # [b, m]
    y = ctx.input('Y')  # [b, n]
    w = ctx.input('Weight')  # [k, m, n]
    out = jnp.einsum('bm,kmn,bn->bk', x, w, y)
    if ctx.has_input('Bias'):
        out = out + ctx.input('Bias')
    ctx.set_output('Out', out)


@register('pixel_shuffle')
def _pixel_shuffle(ctx):
    x = ctx.input('X')  # NCHW
    r = ctx.attr('upscale_factor')
    n, c, h, w = x.shape
    out = x.reshape(n, c // (r * r), r, r, h, w)
    out = out.transpose(0, 1, 4, 2, 5, 3).reshape(n, c // (r * r), h * r, w * r)
    ctx.set_output('Out', out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ls_ce_fused(logits, label, eps):
    """loss = -( (1-eps)·logp[y] + (eps/V)·Σ_j logp[j] ) with NO
    [.., V]-sized intermediate ever CREATED beyond the input logits:
    the residuals are (logits, label, lse) — logits are the op's input
    (alive regardless), lse is [.., 1]-sized — and the backward
    recomputes softmax from them in-register. jax.nn.log_softmax by
    contrast materializes (and autodiff saves) an ADDITIONAL fp32
    [.., V] log-prob tensor — at the Transformer's 32k vocab ~0.5 GB of
    HBM write+read traffic plus the same again held across the step as
    a second residual. Reductions accumulate fp32 (dtype=); elementwise
    fp32 stays in-register under XLA fusion."""
    loss, _ = _ls_ce_fwd(logits, label, eps)
    return loss


def _ls_ce_rows(logits, label):
    x = logits
    m = jnp.max(x, axis=-1).astype(jnp.float32)
    se = jnp.sum(jnp.exp(x.astype(jnp.float32) - m[..., None]), axis=-1,
                 dtype=jnp.float32)
    lse = m + jnp.log(se)
    x_y = jnp.take_along_axis(x, label[..., None].astype(jnp.int32),
                              axis=-1)[..., 0].astype(jnp.float32)
    x_mean = jnp.mean(x, axis=-1, dtype=jnp.float32)
    return lse, x_y, x_mean


def _ls_ce_fwd(logits, label, eps):
    lse, x_y, x_mean = _ls_ce_rows(logits, label)
    # logp[j] = x[j] - lse; nll = lse - x_y; uniform = lse - mean(x)
    loss = (1.0 - eps) * (lse - x_y) + eps * (lse - x_mean)
    return loss, (logits, label, lse)


def _ls_ce_bwd(eps, res, g):
    logits, label, lse = res
    v = logits.shape[-1]
    # d loss / d x_j = p_j - (1-eps)·1[j=y] - eps/V,  p = exp(x - lse)
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = (jnp.arange(v, dtype=jnp.int32) ==
              label[..., None].astype(jnp.int32))
    dx = p - (1.0 - eps) * onehot.astype(jnp.float32) - eps / v
    dx = (g[..., None].astype(jnp.float32) * dx).astype(logits.dtype)
    return dx, np.zeros(label.shape, dtype=jax.dtypes.float0)


_ls_ce_fused.defvjp(_ls_ce_fwd, _ls_ce_bwd)


@register('label_smoothed_cross_entropy')
def _label_smoothed_xent(ctx):
    """Fused label-smoothed softmax CE over hard int labels.

    Equals one_hot -> label_smooth -> softmax_with_cross_entropy(soft)
    but via _ls_ce_fused: no [.., V] smoothed target, no materialized
    log-prob tensor, no V-sized autodiff residual (the backward
    recomputes softmax in-register from the logits). For the
    Transformer's 32k vocab this removes multiple full-logit-sized HBM
    round-trips from the loss — the dominant non-matmul cost."""
    logits = ctx.input('Logits')
    label = ctx.input('Label')
    eps = ctx.attr('epsilon', 0.1)
    if label.ndim == logits.ndim:
        label = label.squeeze(-1)
    if not _fused_ce_enabled():
        # ablation leg: the naive materializing form, benchable A/B
        lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lsm, label[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        loss = (1.0 - eps) * nll + eps * -jnp.mean(lsm, axis=-1)
    else:
        loss = _ls_ce_fused(logits, label, float(eps))
    ctx.set_output('Loss', loss[..., None])


@register('modified_huber_loss')
def _modified_huber_loss(ctx):
    """Binary classification loss (modified_huber_loss_op.h:37-72):
    z = x * (2y - 1); loss = -4z for z < -1, (1-z)^2 for z < 1, else 0."""
    x = ctx.input('X')
    y = ctx.input('Y').astype(x.dtype)
    z = x * (2.0 * y - 1.0)
    loss = jnp.where(z < -1.0, -4.0 * z,
                     jnp.where(z < 1.0, jnp.square(1.0 - z), 0.0))
    ctx.set_output('IntermediateVal', z)
    ctx.set_output('Out', loss)
