"""Hand-written Pallas TPU kernels for the hot ops (flash attention,
fused normalization). Everything here has a jnp fallback so the same IR
runs on CPU test meshes."""

import os


def interpret_mode():
    """PADDLE_TPU_PALLAS_INTERPRET=1 runs kernels in interpret mode
    (CPU parity tests, tests/test_pallas_kernels.py)."""
    return os.environ.get('PADDLE_TPU_PALLAS_INTERPRET') == '1'


def pallas_enabled():
    """Whether to dispatch hot ops to Pallas kernels.

    Default: OFF — opt in with PADDLE_TPU_USE_PALLAS=1. Every kernel
    here — flash forward and both FA2 backward kernels, layer norm,
    batch norm — compiles for the v5e and agrees with
    its jnp reference (chip_smoke.py's kernels leg, PR 21). None has a
    timing against XLA's own fusion in the driver's records, so a hand
    kernel has yet to earn its dispatch at any shape (ROADMAP S6).
    """
    env = os.environ.get('PADDLE_TPU_USE_PALLAS')
    if env is not None:
        return env not in ('0', 'false', 'False')
    return False
