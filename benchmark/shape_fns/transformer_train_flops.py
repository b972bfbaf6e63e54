"""Model FLOPs per second per chip of the encoder-decoder Transformer's
train step: the matrix multiplications and attention products the
forward and backward passes require, at the padded shapes, times the
measured tokens per second, over the chips (the arithmetic of the
repository's first bench.py, which went at PR 48; this file is the one
copy). 1 forward + 2 backward; recomputation, the optimizer, dropout,
softmax and layer norm are not counted."""


def step_flops(batch, src_len, trg_len, vocab, n_layer, n_head, d_key,
               d_model, d_inner):
    B, S, T = float(batch), float(src_len), float(trg_len)

    def proj(tokens, din, dout):
        return 2.0 * tokens * din * dout

    enc = n_layer * (
        4 * proj(B * S, d_model, d_model)             # q, k, v, o
        + 2 * 2.0 * B * n_head * S * S * d_key        # q.k^T and p.v
        + 2 * proj(B * S, d_model, d_inner))          # both FFN matrices
    dec = n_layer * (
        4 * proj(B * T, d_model, d_model)             # self q, k, v, o
        + 2 * 2.0 * B * n_head * T * T * d_key
        + 2 * proj(B * T, d_model, d_model)           # cross q, o
        + 2 * proj(B * S, d_model, d_model)           # cross k, v
        + 2 * 2.0 * B * n_head * T * S * d_key
        + 2 * proj(B * T, d_model, d_inner))
    logits = proj(B * T, d_model, vocab)
    return 3.0 * (enc + dec + logits)


def compute(sources):
    rate = sources['measured'].get('train_tokens_per_s')
    if rate is None:
        return None
    model = sources['config']['model']
    seq = sources['traffic']['seq_len']
    per_token = step_flops(1, seq, seq, model['vocab_size'],
                           model['n_layer'], model['n_head'],
                           model['d_key'], model['d_model'],
                           model['d_inner']) / seq
    return rate * per_token / sources['cell']['chips']
