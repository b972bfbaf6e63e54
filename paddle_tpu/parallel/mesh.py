"""Device mesh construction.

Axes convention (scaling-book style):
  dp — data parallel (batch)          — outermost, DCN-friendly
  pp — pipeline stages
  tp — tensor parallel (hidden dims)  — innermost, ICI-bandwidth-hungry
  sp — sequence/context parallel (ring attention)
  ep — expert parallel (MoE)
"""

import numpy as np

AXES = ('dp', 'pp', 'sp', 'tp', 'ep')


class MeshConfig(object):
    def __init__(self, dp=1, pp=1, sp=1, tp=1, ep=1):
        self.sizes = {'dp': dp, 'pp': pp, 'sp': sp, 'tp': tp, 'ep': ep}

    @property
    def total(self):
        n = 1
        for v in self.sizes.values():
            n *= v
        return n

    def active_axes(self):
        return [a for a in AXES if self.sizes[a] > 1]

    def to_dict(self):
        """JSON-able {axis: size} — the form checkpoints record."""
        return {a: int(self.sizes[a]) for a in AXES}

    @classmethod
    def from_mesh(cls, mesh):
        """MeshConfig describing a jax Mesh's canonical axes (a mesh of
        None or without an axis means size 1 there)."""
        sizes = axis_sizes(mesh)
        return cls(**{a: sizes[a] for a in AXES})


def axis_sizes(mesh):
    """Canonical {axis: size} of a jax Mesh: every AXES entry present
    (missing -> 1), extra axis names preserved. None -> the unsharded
    all-ones topology. This is the topology signature checkpoints
    record and elastic restore compares."""
    sizes = {a: 1 for a in AXES}
    if mesh is not None:
        for a, s in dict(mesh.shape).items():
            sizes[str(a)] = int(s)
    return sizes


def make_mesh(dp=None, pp=1, sp=1, tp=1, ep=1, devices=None):
    """Build a jax Mesh. dp=None means 'use all remaining devices'."""
    import jax
    from jax.sharding import Mesh

    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    rest = pp * sp * tp * ep
    if dp is None:
        if n % rest:
            raise ValueError('device count %d not divisible by pp*sp*tp*ep'
                             ' = %d' % (n, rest))
        dp = n // rest
    total = dp * rest
    if total > n:
        raise ValueError('mesh needs %d devices, have %d' % (total, n))
    dev_array = np.asarray(devices[:total]).reshape(dp, pp, sp, tp, ep)
    return Mesh(dev_array, AXES)


def single_axis_mesh(axis='dp', devices=None):
    kwargs = {a: 1 for a in AXES if a != axis}
    return make_mesh(**{axis: None if axis == 'dp' else None}, **kwargs) \
        if axis == 'dp' else make_mesh(dp=1, **{axis: _all(devices)})


def _all(devices):
    import jax
    return len(devices if devices is not None else jax.devices())
