"""Device places (reference: paddle/fluid/platform/place.h CPUPlace/CUDAPlace).

TPU-native: TPUPlace maps onto a jax TPU device; CPUPlace onto the host
platform. A place resolves lazily so that importing paddle_tpu never forces
jax backend initialization.
"""


class Place(object):
    device_kind = None

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return '%s(%d)' % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def jax_device(self):
        """Resolve to a concrete jax device of this place's platform."""
        import jax
        return jax.devices(self.device_kind)[self.device_id]


class CPUPlace(Place):
    device_kind = 'cpu'


class TPUPlace(Place):
    """The TPU analog of the reference's CUDAPlace (platform/place.h:60).

    Resolves to a TPU device, or raises. The one exception is a process
    that asked for the host CPU by name (``JAX_PLATFORMS=cpu``, as the
    test suite does): there TPUPlace(i) is CPU device i, so programs
    written for the chip run unchanged on the test meshes. A machine
    that merely has no TPU is never silently used instead."""
    device_kind = 'tpu'

    def jax_device(self):
        import jax
        devs = jax.devices()
        platform = devs[0].platform
        if platform != 'tpu':
            # the first platform listed is the one jax makes the default
            asked = (jax.config.jax_platforms or '').split(',')[0]
            if asked != 'cpu':
                raise RuntimeError(
                    '%r: jax found no TPU (default platform is %r). To '
                    'run on the host CPU ask for it by name: '
                    'JAX_PLATFORMS=cpu' % (self, platform))
        return devs[self.device_id]


# Alias kept for scripts written against the reference's naming.
CUDAPlace = TPUPlace
