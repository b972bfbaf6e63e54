"""Shared helpers for the test suite."""

import numpy as np

import paddle_tpu as fluid


def run_startup_and(feed, fetch_list, place=None):
    exe = fluid.Executor(place or fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe.run(feed=feed, fetch_list=fetch_list)


def rand(*shape, dtype='float32', seed=None, low=None, high=None):
    rng = np.random.RandomState(seed if seed is not None else 0)
    if dtype.startswith('int'):
        return rng.randint(low or 0, high or 10, shape).astype(dtype)
    return rng.uniform(low if low is not None else -1.0,
                       high if high is not None else 1.0,
                       shape).astype(dtype)


def as_held(spec, weights):
    """``weights`` ({name: array}, declared layout) as jax arrays in the
    layout the programs hold them in (model.HeldTransposed): what an op
    driven without an engine is fed."""
    import jax.numpy as jnp
    from paddle_tpu.serving.decode import model as lm
    swapped = lm.held_transposed(spec)
    return {name: jnp.swapaxes(jnp.asarray(w), -1, -2) if name in swapped
            else jnp.asarray(w) for name, w in weights.items()}


def cell_spec(cell, rehearsal=False, **over):
    """(spec, engine arguments) of a benchmark cell's configuration as
    the benchmark runs it, or with its ``rehearsal`` sizes laid over
    (what ``--rehearsal`` runs); ``over`` replaces keys of the config."""
    import os
    from benchmark import manifest
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    resolved = manifest.resolve(manifest.load(root), cell)
    config = dict(resolved['config'])
    small = config.pop('rehearsal')
    if rehearsal:
        config.update(small)
    config.update(over)
    return (manifest.load_module(resolved['runner']).spec_of(config),
            config['engine'])


def heads_of_held(spec, name):
    """The heads a latent block splits the product with the parameter
    ``name`` into, for a name of ``model.held_transposed(spec)``: a
    kind's ``q_b`` or the indexer's ``idx_q``."""
    from paddle_tpu.serving.decode import model as lm
    if name.endswith('_idx_q.w'):
        return spec.index_n_heads
    assert name.endswith('_q_b.w'), name
    return spec.latent[lm.FULL if '_full_' in name else lm.SLIDING].n_head


def weights_round_trip(spec, weights, marked, **engine_kw):
    """``weights`` ({name: float32 array}, declared layout) loaded into
    a DecodeEngine of ``spec``: ``export_weights`` and ``device_weights``
    hand every parameter back in its declared shape, bit for bit what
    was loaded at the declared dtype; the parameters named ``marked``
    are held with their last two axes swapped (model.HeldTransposed)
    and every other is handed out as the array the programs read; a jax
    array given for a marked parameter is donated to the swap; what was
    exported loads again to the same."""
    import jax.numpy as jnp
    from paddle_tpu.serving.decode import DecodeEngine
    from paddle_tpu.serving.decode import model as lm
    assert lm.held_transposed(spec) == set(marked)
    eng = DecodeEngine(spec, weights=weights, place=fluid.CPUPlace(),
                       **engine_kw)
    try:
        table = lm.block_param_shapes(spec)
        assert sorted(table) == sorted(eng.device_weights())

        def same_as_loaded(out):
            for name, (shape, fan_in, _) in table.items():
                want = jnp.asarray(weights[name]).astype(
                    spec.dtype if fan_in else 'float32')
                assert out[name].shape == tuple(shape), name
                assert str(out[name].dtype) == str(want.dtype), name
                np.testing.assert_array_equal(
                    np.asarray(out[name]), np.asarray(want), err_msg=name)
        exported, on_device = eng.export_weights(), eng.device_weights()
        same_as_loaded(exported)
        same_as_loaded(on_device)
        for name, (shape, _, _) in table.items():
            held = eng._scope.get(name)
            if name in marked:
                assert held.shape == tuple(shape[:-2]) + (
                    shape[-1], shape[-2]) == tuple(shape.held), name
            else:
                assert on_device[name] is held, name
        name = sorted(marked)[0]
        mine = jnp.asarray(weights[name]).astype(spec.dtype) * 2
        eng.load_weights({name: mine})
        assert mine.is_deleted()
        np.testing.assert_array_equal(
            np.asarray(eng.export_weights()[name]),
            np.asarray(on_device[name] * 2))
        eng.load_weights(exported)
        same_as_loaded(eng.export_weights())
    finally:
        eng.shutdown(drain=False)


def platform_forms(monkeypatch, which):
    """Make ``jax.lax.platform_dependent``'s choice here: the forms a
    TPU's programs take (``which`` 'tpu': the Pallas kernels,
    interpreted on the CPU) or every other platform's ('default')."""
    import jax
    monkeypatch.setenv('PADDLE_TPU_PALLAS_INTERPRET', '1')
    monkeypatch.setattr(
        jax.lax, 'platform_dependent',
        lambda *args, tpu, default: (
            tpu if which == 'tpu' else default)(*args))
