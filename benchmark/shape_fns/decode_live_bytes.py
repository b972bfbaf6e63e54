"""Bytes per second that the decode step has to move for the tokens that
are alive: every weight once a step, plus the KV of the pages in use,
over the mean time of a step (which ends in a fetch, so it is the
synchronous step, host code between steps left out). It is not the
kernel's roofline share: the step may move far more than this."""

from benchmark import stats


def weight_bytes(model, bytes_per_weight=4):
    d, L = model['d_model'], model['n_layer']
    heads = model['n_head'] * (2 * model['d_key'] + 2 * model['d_value'])
    per_layer = d * heads + 2 * d * model['d_inner']
    return bytes_per_weight * (L * per_layer
                               + 2 * model['vocab_size'] * d)


def kv_bytes_per_token(model, bytes_per_value=4):
    return bytes_per_value * model['n_layer'] * model['n_head'] * (
        model['d_key'] + model['d_value'])


def compute(sources):
    step_s = stats.registry_mean(sources['registry_before'],
                                 sources['registry_after'],
                                 'decode.step_seconds')
    used = sources['samples'].get('kv_pages_used')
    if not step_s or not used:
        return None
    model = sources['config']['model']
    live = (sum(used) / len(used)) * sources['config']['engine'][
        'block_size'] * kv_bytes_per_token(model)
    return (weight_bytes(model) + live) / step_s
